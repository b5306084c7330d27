"""kind ``closed_loop_arch``: the closed loop of ``kinds/closed_loop.py``
(its ``plan`` and ``drive``, unchanged) for an architecture that
``chipbench/serving.py`` cannot run: that file's ``run`` is bound to the
dense tree (``seeded.dims_of``, ``seeded.make_program_tree``, the dense
reference's argument list). This ``run`` is its counterpart with the
model-specific parts behind an adapter found by the configuration's
``reference``, ``chipbench/seeded_<reference>.py``, which gives

    vocab_of(model)                              the ids the traffic draws
    program_cfg(model, precision)                the config ``fed.serve`` takes
    program_params_host(seed, model, precision)  the program's tree, host arrays
    reference_logits_fn(seed, model, precision)  f(tokens, idx, quant) -> logits

and ``chipbench/flops_<reference>.py`` gives ``window_least_bytes(facts)``
(the bytes a decode step must move, by part: a note of every run, and the
roofline reader's numerator), so the next architecture adds an adapter, a
reference and its byte functions, and no kind.
The sink, the warm-up wave and the folding of the window's records are
``serving.py``'s own.

``correct``: as in ``serving.py``: a sample of the greedy requests the
window finished, the longest among them, is followed by the plain
reference, one full forward pass over each prompt with its served tokens;
the number compared is the widest gap by which a served token's logit
lies below the reference's best at its position. The limit is data, as
the reference's name is: the configuration's file states it
(``"limits": {"served_logit_gap": ...}``, calibrated on the chip for that
configuration; it reaches here in ``ctx.model``), and a configuration
without one is refused before any weight is drawn. Under ``--control``
the same number is also read for the tokens the reference puts first in
the lower precision and held to the same limit, as a check of its own: a
control that the limit catches reads ``correct`` false.

What the engine counted over the window goes into ``facts`` as deltas of
``stats()`` (``stats``: read as the window closes, not after the drain
that follows it), beside the model as run and its precision, for the
readers under ``layers/``; from a traced run also the device's own time
in each of the engine's programs (``programs``) and what the program
recorded of itself over the window (``program``, by
``common.ProgramRecord``: the phases' durations, the wire's spans, and
the growth of EVERY integer counter of ``stats()``, read as the window
closes like ``stats``, which stays the closed tuple its readers know).
"""

from __future__ import annotations

import gc
import importlib
import sys
import threading
import time

from chipbench import common, serving, trace_reduce
from chipbench.kinds import closed_loop

SAMPLE_REQUESTS = 6
REF_PAD = 256
MODULES_LINE = "XLA Modules"
# Engine counters whose growth over the window the readers use.
STATS_DELTAS = ("steps", "prefill_chunks", "preempted", "kv_blocks_attended",
                "kv_blocks_slab", "ssm_state_bytes", "state_resets",
                "state_rows_held")

plan = closed_loop.plan
drive = closed_loop.drive


def adapter_of(ctx):
    return importlib.import_module("chipbench.seeded_" + ctx.spec["reference"])


def start_engine(ctx, adapter):
    """The seeded tree, made leaf by leaf and published from the host
    (``ModelBank.publish`` device-copies a device tree while its caller
    still holds it: twice the weights), as version 1 of a party-hosted
    engine. Returns (handle, server)."""
    from rayfed_tpu.serving.server import get_server

    assert ctx.mix.get("publish_from") == "host", \
        "closed_loop_arch publishes from the host"
    precision = ctx.spec["precision"]
    # The program's config first: a program without this architecture
    # fails here, at once, before any weight is drawn.
    cfg = adapter.program_cfg(ctx.model, precision)
    params = adapter.program_params_host(ctx.seed, ctx.model, precision)
    handle = ctx.fed.serve(ctx.lead, cfg, config=dict(ctx.mix["serving"]),
                           params=params)
    del params
    gc.collect()
    return handle, get_server(handle.name)


def reference_gaps(ctx, adapter, sample, quant=None):
    """Widest gap per sampled request under the plain reference; with
    ``quant`` also the gap of the token the lower precision puts first."""
    import jax.numpy as jnp
    import numpy as np

    logits_at = adapter.reference_logits_fn(
        ctx.seed, ctx.model, ctx.spec["precision"])
    # One program per padded prompt length, whatever the sample: the
    # positions read are padded to the mix's longest output.
    n_pad = -(-int(ctx.mix["output_len"]["hi"]) // 64) * 64
    out = []
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        plen, n = len(r["prompt"]), len(r["tokens"])
        tokens = np.zeros(-(-len(seq) // REF_PAD) * REF_PAD, np.int32)
        tokens[:len(seq)] = seq
        idx = np.full(n_pad, plen - 1, np.int32)
        idx[:n] = np.arange(plen - 1, plen - 1 + n)
        args = (jnp.asarray(tokens), jnp.asarray(idx))
        logits = np.asarray(logits_at(*args))[:n]
        best = logits.max(-1)
        served = logits[np.arange(n), np.asarray(r["tokens"])]
        row = {"prompt_len": plen, "n": n,
               "gap": float((best - served).max())}
        if quant:
            low = np.asarray(logits_at(*args, quant))[:n]
            row["control_gap"] = float(
                (best - logits[np.arange(n), low.argmax(-1)]).max())
        out.append(row)
    return out


def limits_of(ctx):
    """The configuration's own limit for the comparison that decides
    ``correct``."""
    limits = ctx.model.get("limits") or {}
    if "served_logit_gap" not in limits:
        raise SystemExit(
            "closed_loop_arch: the configuration states no "
            "limits.served_logit_gap; calibrate one on the chip (sound "
            "runs against the --control runs) and write it into its file")
    return limits


def traced_programs(trace_dir):
    """``{program: {"seconds", "calls"}}`` of the first device, from the
    profile's line of whole programs (one event an execution, named
    ``jit_<function>(<id>)``); ``{}`` where the profile has no device
    plane or no such line. Read before ``DeviceTrace.reduce`` removes the
    profile."""
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return {}
    from jax.profiler import ProfileData

    planes = sorted(
        (p for p in ProfileData.from_file(path).planes
         if trace_reduce.DEVICE_PLANE.match(p.name)), key=lambda p: p.name)
    for line in (planes[0].lines if planes else ()):
        if line.name != MODULES_LINE:
            continue
        out = {}
        for e in line.events:
            slot = out.setdefault(e.name.split("(", 1)[0],
                                  {"seconds": 0.0, "calls": 0})
            slot["seconds"] += e.duration_ns / 1e9
            slot["calls"] += 1
        return out
    return {}


def break_state(srv):
    """The control of the mechanism itself (``--inject broken-state``): a
    round of prefilled rows lands its K/V but not its fresh recurrent
    state, so a request decodes on from whatever the slot's last occupant
    left. ``correct`` must come out false."""
    import numpy as np

    land = srv.pool.scatter_rows
    srv.pool.scatter_rows = (
        lambda k, v, tables, state_rows=None, landed=None: land(
            k, v, tables, state_rows,
            None if landed is None else np.zeros_like(landed)))


def run(ctx):
    """The whole of a serving run on the chip party (the only party)."""
    import numpy as np

    fed = ctx.fed
    kind = sys.modules[__name__]
    adapter = adapter_of(ctx)
    limits = limits_of(ctx)
    vocab = adapter.vocab_of(ctx.model)
    handle, srv = start_engine(ctx, adapter)
    ctx.part("weights_publish_engine")
    requests = kind.plan(ctx, vocab)
    if ctx.inject("broken-token"):
        sample_fn = srv._sample
        srv._sample = lambda logits, req: (sample_fn(logits, req) + 1) % vocab
    if ctx.inject("broken-state"):
        break_state(srv)
    warmed = serving.warm_up(
        ctx, srv, vocab,
        sorted({len(r["prompt"]) for r in requests["requests"]}))
    ctx.part("compile_warmup_wave")
    before = srv.stats()
    ctx.say("warm", classes=warmed,
            compiled_programs=before["compiled_programs"],
            requests=len(requests["requests"]))
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

    # The engine's counters as the window closes: ``drive`` returns only
    # when what was in flight at the close has drained, and those steps
    # (fewer and fewer rows live) are in no rate.
    at_close = {}
    closer = threading.Timer(
        ctx.seconds, lambda: (at_close.update(srv.stats()), record.close()))
    closer.daemon = True

    @fed.remote
    def generator():
        if tracer:
            tracer.start()
        closer.start()
        return kind.drive(ctx, srv, requests, serving.Sink)

    # ---- the window (inside the task, on the serving party) ------------
    win = fed.get(generator.party(ctx.lead).remote())
    if tracer:
        tracer.join()
    closer.join()
    after = srv.stats()
    compiles_in_window = ctx.compiles - compiles_before
    peak = common.memory_peak_bytes()

    # ---- after the window ------------------------------------------------
    done = [r for r in win["records"] if r.get("tokens") is not None]
    greedy = [r for r in done if r["temperature"] <= 0 and r["in_window"]]
    rng = np.random.default_rng([ctx.seed, 7])
    sample = []
    if greedy:
        longest = max(greedy,
                      key=lambda r: len(r["prompt"]) + len(r["tokens"]))
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
    rows = reference_gaps(ctx, adapter, sample, ctx.spec.get("control"))
    ref_s = time.perf_counter() - t0
    widest = max((r["gap"] for r in rows), default=None)
    checks.append(common.check(
        "served_logit_gap.widest", widest, limits["served_logit_gap"],
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
    control = ctx.spec.get("control")
    if control:
        # The limit's own control, through the same comparison: the
        # reference's tokens in the precision below must read not correct.
        checks.append(common.check(
            f"control[{control}].served_logit_gap.widest",
            max((r["control_gap"] for r in rows), default=None),
            limits["served_logit_gap"],
            f"the tokens the reference puts first in {control}, held to the "
            f"limit of the served ones: a control the limit catches fails"))
        notes.append(f"control[{control}] gaps: " + repr(
            [round(r["control_gap"], 4) for r in rows])
            + " program gaps: " + repr([round(r["gap"], 4) for r in rows]))
    programs = traced_programs(trace.dir) if trace else {}
    reduced = trace.reduce() if trace else None
    device = {"memory_peak_bytes": peak}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    stats = {k: at_close.get(k, 0) - before.get(k, 0) for k in STATS_DELTAS}
    facts = dict(
        win["facts"], kind=ctx.mix["kind"], window_s=win["window_s"],
        steps=stats["steps"], slots=ctx.mix["serving"]["max_slots"],
        prefill_chunks=stats["prefill_chunks"], preempted=stats["preempted"],
        stats=stats, model=ctx.model, precision=ctx.spec["precision"],
        reference=ctx.spec["reference"],
        kv_block_size=ctx.mix["serving"]["kv_block_size"],
        trace=reduced, programs=programs, device_kind=ctx.device["kind"],
        program=record.facts(before, at_close),
    )
    if stats["steps"]:
        # A fact of the configuration and the window's occupancy, not a
        # metric: what part of a step's least bytes each kind of state is.
        flops = importlib.import_module(
            "chipbench.flops_" + ctx.spec["reference"])
        parts = flops.window_least_bytes(facts)
        notes.append("least bytes of a decode step, MB: " + ", ".join(
            f"{k} {v / stats['steps'] / 1e6:.1f} "
            f"({100 * v / parts['total']:.1f} %)"
            for k, v in parts.items() if k != "total"))
    ctx.say("window", attempted=win["attempted"], failed=win["failed"],
            compiles_in_window=compiles_in_window, **stats,
            **{k: round(v, 3) for k, v in win["end_to_end"].items()})
    end_to_end = dict(win["end_to_end"], setup_s=setup_s)
    return {
        "correct": all(c["ok"] for c in checks) and bool(rows),
        "attempted": win["attempted"], "failed": win["failed"],
        "end_to_end": end_to_end, "facts": facts, "checks": checks,
        "notes": notes, "setup_parts": ctx.setup_parts, "device": device,
        "breakdown": common.breakdown_of(reduced),
    }
