"""What the serving kinds share: the engine behind ``fed.serve`` on the
chip party, the benchmark's own token sink, the warm-up wave, and the
comparison with the plain reference.

The generator is a task pinned to the serving party, in its process; it
submits through ``InferenceServer.submit(..., stream=sink)`` (the entry
``handle.submit`` lands on) and the sink stamps every push with the host
clock. A kind (``kinds/open_loop.py``, ``kinds/closed_loop.py``) supplies
only ``plan(ctx, vocab)`` (the requests, from ``traffic.py``) and
``drive(...)`` (when each is submitted).

``correct`` (contract: a model that is served): once the window has
closed and the engine's state is freed, a sample drawn from the seed of
the greedy requests the window finished, the longest among them, is
followed by the plain reference, one full forward pass over each prompt
with its served tokens; the number compared is the widest gap by which a
served token's logit lies below the reference's best at its position.
"""

from __future__ import annotations

import gc
import importlib
import threading
import time

from chipbench import common, seeded

# Widest gap of a served (greedy) token's reference logit below the
# reference's best, over the sample. On the chip (PR 23) 25 sound seeds
# read 0.03..0.091; the fp8 control's widest reads 0.36..0.58 over 6 seeds
# (its smallest single request 0.14). PERF.md section 2.
LIMITS = {"served_logit_gap": 0.18}
SAMPLE_REQUESTS = 6
DRAIN_BOUND_S = 40.0
REF_PAD = 256


class Sink:
    """The benchmark's token sink (``push``/``reset``/``fail``, the
    contract of ``rayfed_tpu.serving.stream``): O(1), never blocks."""

    __slots__ = ("t", "failed", "t_done")

    def __init__(self):
        self.t, self.failed, self.t_done = [], None, None

    def push(self, offset, toks, final):
        now = time.perf_counter()
        del self.t[offset:]          # a restart after preemption
        self.t.extend([now] * len(toks))
        if final:
            self.t_done = now

    def reset(self):
        del self.t[:]

    def fail(self, exc):
        self.failed = repr(exc)


def program_cfg(model):
    from rayfed_tpu.models import transformer as tfm

    v, d, h, _, f, n = seeded.dims_of(model)
    return tfm.TransformerConfig(
        vocab=v, d_model=d, n_heads=h, n_layers=n, d_ff=f,
        rope_theta=float(model["rope_theta"]))


def start_engine(ctx):
    """Weights on the device from the seed in one jitted call, published
    as version 1 of a party-hosted engine. Returns (handle, server)."""
    import jax

    from rayfed_tpu.serving.server import get_server

    dims = seeded.dims_of(ctx.model)
    params = seeded.make_program_tree(seeded.key_of(ctx.seed), dims)
    if ctx.mix.get("publish_from") == "host":
        # ModelBank.publish device-copies a jax.Array tree while the
        # caller still holds it: twice the weights at the peak, which this
        # layout cannot hold. A host tree is uploaded once.
        params = jax.device_get(params)
    handle = ctx.fed.serve(ctx.lead, program_cfg(ctx.model),
                           config=dict(ctx.mix["serving"]), params=params)
    del params
    gc.collect()
    return handle, get_server(handle.name)


def warm_up(ctx, srv, vocab, lengths):
    """One wave that hits exactly the prompt buckets and chunk shapes of
    the mix's own lengths, and the decode step."""
    import numpy as np

    chunk = srv.scfg.prefill_chunk
    classes = {}
    for n in lengths:
        if n <= chunk:
            key = ("prefill", next(b for b in srv._buckets if b >= n))
        else:
            first = n % chunk or chunk
            key = ("chunk", next(b for b in srv._chunk_buckets if b >= first))
        classes.setdefault(key, n)
    rng = np.random.default_rng(12345)
    futs = [srv.submit(rng.integers(1, vocab, size=n).tolist(),
                       max_new_tokens=3, temperature=0.0, stream=Sink())
            for n in classes.values()]
    # A sampled request too: the host-side sampler's first call.
    futs.append(srv.submit(rng.integers(1, vocab, size=min(lengths)).tolist(),
                           max_new_tokens=3, temperature=0.8, seed=1,
                           stream=Sink()))
    for f in futs:
        f.result(timeout=1000)
    return sorted(classes)


def _reference_gaps(ctx, sample, quant=None):
    """Widest gap per sampled request under the plain reference; with
    ``quant`` also the gap of the token the lower precision puts first."""
    import jax.numpy as jnp
    import numpy as np

    ref = importlib.import_module("chipbench.references."
                                  + ctx.spec["reference"])
    model, dims = ctx.model, seeded.dims_of(ctx.model)
    w = seeded.make_canonical(seeded.key_of(ctx.seed), dims)
    theta, eps = float(model["rope_theta"]), float(model["rms_norm_eps"])
    # One program per padded prompt length, whatever the sample: the
    # positions read are padded to the mix's longest output.
    n_pad = -(-int(ctx.mix["output_len"]["hi"]) // 64) * 64
    out = []
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        plen, n = len(r["prompt"]), len(r["tokens"])
        s_pad = -(-len(seq) // REF_PAD) * REF_PAD
        tokens = np.zeros(s_pad, np.int32)
        tokens[:len(seq)] = seq
        idx = np.full(n_pad, plen - 1, np.int32)
        idx[:n] = np.arange(plen - 1, plen - 1 + n)
        args = (jnp.asarray(tokens), jnp.asarray(idx), dims[2], theta, eps)
        logits = np.asarray(ref.logits_at(w, *args))[:n]
        best = logits.max(-1)
        served = logits[np.arange(n), np.asarray(r["tokens"])]
        row = {"prompt_len": plen, "n": n,
               "gap": float((best - served).max())}
        if quant:
            low = np.asarray(ref.logits_at(w, *args, quant))[:n]
            row["control_gap"] = float(
                (best - logits[np.arange(n), low.argmax(-1)]).max())
        out.append(row)
    return out


def run(ctx, kind):
    """The whole of a serving run on the chip party (the only party)."""
    import numpy as np

    fed = ctx.fed
    vocab = seeded.dims_of(ctx.model)[0]
    handle, srv = start_engine(ctx)
    ctx.part("weights_publish_engine")
    plan = kind.plan(ctx, vocab)
    if ctx.inject("broken-token"):
        # A token altered where it is produced: `correct` must be false.
        sample_fn = srv._sample
        srv._sample = lambda logits, req: (sample_fn(logits, req) + 1) % vocab
    warmed = warm_up(ctx, srv, vocab,
                     sorted({len(r["prompt"]) for r in plan["requests"]}))
    ctx.part("compile_warmup_wave")
    before = srv.stats()
    ctx.say("warm", classes=warmed, compiled_programs=before[
        "compiled_programs"], requests=len(plan["requests"]))
    record = common.ProgramRecord(ctx.trace)
    record.open()
    compiles_before = ctx.compiles
    setup_s = time.time() - ctx.spec["t0"]
    trace = common.DeviceTrace(ctx) if ctx.trace else None
    tracer = None
    if trace:
        def traced():
            time.sleep(ctx.seconds * 0.4)
            trace.start()
            time.sleep(min(4.0, ctx.seconds * 0.3))
            trace.stop()

        tracer = threading.Thread(target=traced, daemon=True)

    @fed.remote
    def generator():
        if tracer:
            tracer.start()
        return kind.drive(ctx, srv, plan, Sink)

    # ---- the window (inside the task, on the serving party) ------------
    win = fed.get(generator.party(ctx.lead).remote())
    if tracer:
        tracer.join()
    after = srv.stats()
    record.close()
    compiles_in_window = ctx.compiles - compiles_before
    peak = common.memory_peak_bytes()
    queue_wait = []
    if ctx.trace:
        from rayfed_tpu import tracing

        for events in tracing.request_timelines().values():
            at = {e.event: e.t_s for e in events}
            if "enqueue" in at and "admit" in at:
                queue_wait.append(at["admit"] - at["enqueue"])

    # ---- after the window ------------------------------------------------
    done = [r for r in win["records"] if r.get("tokens") is not None]
    greedy = [r for r in done if r["temperature"] <= 0 and r["in_window"]]
    rng = np.random.default_rng([ctx.seed, 7])
    sample = []
    if greedy:
        longest = max(greedy, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        rest = [r for r in greedy if r is not longest]
        picks = rng.permutation(len(rest))[:SAMPLE_REQUESTS - 1]
        sample = [longest] + [rest[i] for i in picks]
    # Same (version, prompt, seed) -> same tokens, alone in the batch.
    replay = None
    if sample:
        r = sample[-1]
        again = srv.submit(r["prompt"], max_new_tokens=r["max_new"],
                           temperature=0.0, seed=r["seed"]).result(300)
        replay = again["tokens"] == r["tokens"]
    fed.get(handle.shutdown())
    del srv
    gc.collect()
    checks, notes = [], []
    t0 = time.perf_counter()
    rows = _reference_gaps(ctx, sample, ctx.spec.get("control"))
    ref_s = time.perf_counter() - t0
    widest = max((r["gap"] for r in rows), default=None)
    checks.append(common.check(
        "served_logit_gap.widest", widest, LIMITS["served_logit_gap"],
        f"{len(rows)} greedy requests, {sum(r['n'] for r in rows)} served "
        f"tokens, prompts {[r['prompt_len'] for r in rows]}"))
    checks.append(common.check(
        "compiles_in_window", compiles_in_window
        + after["compiled_programs"] - before["compiled_programs"], 0,
        "backend compilations and new engine programs inside the window",
        exact=True))
    checks.append(common.check(
        "requests_failed", win["failed"], 0,
        "requests refused or failed in the window", exact=True))
    notes.append(f"reference followed {len(rows)} requests in {ref_s:.1f}s "
                 f"(outside setup_s and the window); replay of one request "
                 f"alone gave the same tokens: {replay}")
    if ctx.spec.get("control"):
        notes.append(f"control[{ctx.spec['control']}] gaps: " + repr(
            [round(r["control_gap"], 4) for r in rows])
            + " program gaps: " + repr([round(r["gap"], 4) for r in rows]))
    reduced = trace.reduce() if trace else None
    device = {"memory_peak_bytes": peak}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    steps = after["steps"] - before["steps"]
    facts = dict(
        win["facts"], kind=ctx.mix["kind"], window_s=win["window_s"],
        steps=steps, slots=ctx.mix["serving"]["max_slots"],
        prefill_chunks=after["prefill_chunks"] - before["prefill_chunks"],
        preempted=after["preempted"] - before["preempted"],
        queue_wait_s=queue_wait, trace=reduced,
        device_kind=ctx.device["kind"],
        # What the program measured of itself, from the warm-up's end to
        # the drain's (where ``steps`` is read too); None untraced.
        program=record.facts(before, after),
    )
    ctx.say("window", attempted=win["attempted"], failed=win["failed"],
            steps=steps, compiles_in_window=compiles_in_window,
            **{k: round(v, 3) for k, v in win["end_to_end"].items()})
    end_to_end = dict(win["end_to_end"], setup_s=setup_s)
    return {
        "correct": all(c["ok"] for c in checks) and bool(rows),
        "attempted": win["attempted"], "failed": win["failed"],
        "end_to_end": end_to_end, "facts": facts, "checks": checks,
        "notes": notes, "setup_parts": ctx.setup_parts, "device": device,
        "breakdown": common.breakdown_of(reduced),
    }


def collect(records, sinks, futures, t_start, deadline):
    """The window has closed on the clock. What is in flight may finish,
    within a bound; then sinks and responses are folded into the records.
    What finished after the close is in no rate and no gap."""
    bound = time.perf_counter() + DRAIN_BOUND_S
    for fut in futures:
        if fut is not None:
            try:
                fut.result(timeout=max(0.0, bound - time.perf_counter()))
            except Exception:  # noqa: BLE001 - counted by the caller
                pass
    for rec, sink, fut in zip(records, sinks, futures):
        rec["first_s"] = sink.t[0] - t_start if sink.t else None
        rec["push_t"] = [t - t_start for t in sink.t]
        rec["done_s"] = (sink.t_done - t_start
                         if sink.t_done is not None else None)
        rec["failed"] = sink.failed
        rec["tokens"] = None
        if fut is not None and fut.done() and fut.exception() is None:
            rec["tokens"] = fut.result()["tokens"]
        rec["in_window"] = (rec["done_s"] is not None
                            and rec["done_s"] <= deadline - t_start)
    return records


def served_in_window(records, seconds):
    """(tokens pushed inside the window, requests whose first token came
    inside it)."""
    pushed = sum(sum(1 for x in r["push_t"] if x <= seconds)
                 for r in records)
    started = [r for r in records
               if r["first_s"] is not None and r["first_s"] <= seconds]
    return pushed, started
