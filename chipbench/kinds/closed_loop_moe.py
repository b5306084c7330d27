"""kind ``closed_loop_moe``: ``closed_loop_arch``'s run for an architecture
with routed experts and windowed layers. The closed loop itself
(``closed_loop``'s ``plan`` and ``drive``), the adapter by the
configuration's ``reference``, the engine's start, the comparison with the
plain reference, the configuration's own limit and the profile's whole
programs are ``closed_loop_arch``'s, by import. What that kind cannot
carry, and why this file exists: its ``STATS_DELTAS`` is a closed tuple
and its one control (``break_state``) is Falcon-H1's, and no file the
benchmark has may be edited by the PR that adds a cell. A ``benchmark``
issue should move the counter list and the controls behind the adapter
and fold the two kinds into one (ROADMAP Queue B).

Its own: the engine's counters for experts, windows and prefill
(``STATS_DELTAS``), read as the window closes (``facts["stats"]``) and
around the traced part of the window (``facts["traced_stats"]``: what
the engine counted between the profile's start and its stop, so that a
roofline divides what was needed by the time of the same executions;
``facts["program"]``, of a traced run, holds the growth of EVERY integer
counter beside the phases' durations, as in ``closed_loop_arch``),
and two controls of the mechanisms themselves, each of which must read
``correct`` false:

* ``--inject broken-route``: the layer takes its ``k`` among the held
  experts only (the classic wrong share: a chip that normalises over what
  it holds, not over what the router chose);
* ``--inject broken-window``: sliding layers read every key. It can only
  show on a request longer than the window; the sample always holds the
  window's longest.

``--control fp8`` and ``--inject broken-token`` are ``closed_loop_arch``'s.

``correct`` holds two numbers to two limits of the configuration's file
(``reference_gaps``, ``gap_checks``): ``served_logit_gap.widest`` as in
the other serving kinds, and ``served_logit_gap.mean``, the mean over
every served token of the sample; ``.widest_less_worst`` and
``.mean_less_worst`` where the configuration states
``limits.worst_requests``: both over all the sample's requests but that
many whose gaps sum highest. Routed experts make the first a coarse net
(a token in a hundred takes another k-th expert in bfloat16 than in
float32, and the widest gap is that token's); the second is the fine
one, and the one the ``fp8`` control fails. A request's tokens are not
independent draws (``gap_checks``), so the configuration also states how
many requests the reference follows (``limits.sample_requests``;
``SAMPLE_REQUESTS`` where it states none).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import sys
import threading
import time
import types

from chipbench import common, serving
from chipbench.kinds import closed_loop, closed_loop_arch as arch

SAMPLE_REQUESTS = arch.SAMPLE_REQUESTS
# Engine counters whose growth the readers use.
STATS_DELTAS = ("steps", "prefill_chunks", "preempted", "kv_blocks_attended",
                "kv_blocks_slab", "kv_layer_blocks_attended",
                "moe_experts_hit", "moe_assignments_local", "prefill_tokens",
                "prefill_keys_attended", "fetch_bytes")

PROGRESS_EVERY_S = 5.0

plan = closed_loop.plan
drive = closed_loop.drive


def break_route():
    """``--inject broken-route``: before any program is traced, the
    routed layer is handed a router that scores the held experts alone."""
    from rayfed_tpu.models import moe

    routed = moe.routed_experts

    def among_held(h, layer, held, k, live=None):
        import numpy as np

        held_only = dict(layer, router=layer["router"][:, np.asarray(held)])
        return routed(h, held_only, tuple(range(len(held))),
                      min(k, len(held)), live)

    moe.routed_experts = among_held


def with_broken_window(adapter):
    """``--inject broken-window``: the adapter, with a program
    configuration whose window no context reaches."""
    broken = types.SimpleNamespace(**vars(adapter))
    broken.program_cfg = lambda model, precision: dataclasses.replace(
        adapter.program_cfg(model, precision), window=1 << 30)
    return broken


def reference_gaps(ctx, adapter, sample, quant=None):
    """``closed_loop_arch.reference_gaps`` with one more number a request:
    beside the widest gap of a served token's reference logit below the
    reference's best, the MEAN of those gaps over the request's served
    tokens (and of both for the tokens the reference puts first under
    ``quant``). A router near a tie picks another k-th expert in bfloat16
    than in float32 at a token in a hundred, and such a token's logits
    move by an expert's worth: the widest gap of a sound run is that of
    its unluckiest token, while the mean moves only when most tokens
    do, which is what a lower precision or a wrong share does."""
    import jax.numpy as jnp
    import numpy as np

    logits_at = adapter.reference_logits_fn(
        ctx.seed, ctx.model, ctx.spec["precision"])
    n_pad = -(-int(ctx.mix["output_len"]["hi"]) // 64) * 64
    out = []
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        plen, n = len(r["prompt"]), len(r["tokens"])
        tokens = np.zeros(-(-len(seq) // arch.REF_PAD) * arch.REF_PAD,
                          np.int32)
        tokens[:len(seq)] = seq
        idx = np.full(n_pad, plen - 1, np.int32)
        idx[:n] = np.arange(plen - 1, plen - 1 + n)
        args = (jnp.asarray(tokens), jnp.asarray(idx))
        logits = np.asarray(logits_at(*args))[:n]
        best = logits.max(-1)
        gaps = best - logits[np.arange(n), np.asarray(r["tokens"])]
        row = {"prompt_len": plen, "n": n, "gap": float(gaps.max()),
               "mean": float(gaps.mean()),
               "agree": float((gaps == 0).mean()),
               # The reference's own lead of its best over its second: a
               # request whose tokens are near-tied reads off in runs.
               "lead": float(np.median(
                   best - np.partition(logits, -2, axis=-1)[:, -2])),
               "off": [(int(i), round(float(gaps[i]), 4))
                       for i in np.flatnonzero(gaps > 0)]}
        if quant:
            low = np.asarray(logits_at(*args, quant))[:n]
            gaps = best - logits[np.arange(n), low.argmax(-1)]
            row.update(control_gap=float(gaps.max()),
                       control_mean=float(gaps.mean()),
                       control_off=[(int(i), round(float(gaps[i]), 4))
                                    for i in np.flatnonzero(gaps > 0)])
        out.append(row)
    return out


def gap_checks(rows, limits, prefix="", key="gap", mean="mean", why=""):
    """The two numbers held to the configuration's two limits: the widest
    gap over the sample, and the mean gap over its served tokens. Where
    the configuration states ``limits.worst_requests``, both are taken
    over all the sample's requests but that many whose gaps sum highest
    (``.widest_less_worst``, ``.mean_less_worst``): greedy text cycles,
    and a rounding that flips one near-tied token flips it at every turn
    of the cycle, so a request in twenty-five reads off in runs and is
    most of a sample's sum, and a token in a hundred thousand sits on a
    router's tie and moves by an expert's worth. The request, not the
    token, is what is drawn; a lower precision or a broken mechanism
    moves most requests."""
    worst = int(limits.get("worst_requests", 0))
    kept = sorted(rows, key=lambda r: r[mean] * r["n"],
                  reverse=True)[worst:]
    less = "_less_worst" if worst else ""
    tokens = sum(r["n"] for r in kept)
    return [
        common.check(
            f"{prefix}served_logit_gap.widest{less}",
            max((r[key] for r in kept), default=None),
            limits["served_logit_gap"], why),
        common.check(
            f"{prefix}served_logit_gap.mean{less}",
            sum(r[mean] * r["n"] for r in kept) / tokens if tokens else None,
            limits["served_logit_gap_mean"], why),
    ]


def deltas(after, before):
    return {k: after.get(k, 0) - before.get(k, 0) for k in STATS_DELTAS}


def run(ctx):
    """The whole of a serving run on the chip party (the only party)."""
    import numpy as np

    fed = ctx.fed
    kind = sys.modules[__name__]
    adapter = arch.adapter_of(ctx)
    limits = arch.limits_of(ctx)
    if "served_logit_gap_mean" not in limits:
        raise SystemExit(
            "closed_loop_moe: the configuration states no "
            "limits.served_logit_gap_mean; calibrate it beside "
            "limits.served_logit_gap")
    vocab = adapter.vocab_of(ctx.model)
    if ctx.inject("broken-route"):
        break_route()
    handle, srv = arch.start_engine(
        ctx, with_broken_window(adapter) if ctx.inject("broken-window")
        else adapter)
    ctx.part("weights_publish_engine")
    requests = kind.plan(ctx, vocab)
    if ctx.inject("broken-token"):
        sample_fn = srv._sample
        srv._sample = lambda logits, req: (sample_fn(logits, req) + 1) % vocab
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
    traced_stats = {}
    if trace:
        def traced():
            time.sleep(ctx.seconds * 0.4)
            trace.start()
            at_start = srv.stats()
            time.sleep(min(4.0, ctx.seconds * 0.3))
            traced_stats.update(deltas(srv.stats(), at_start))
            trace.stop()

        tracer = threading.Thread(target=traced, daemon=True)

    # The engine's counters as the window closes: ``drive`` returns only
    # when what was in flight at the close has drained, and those steps
    # (fewer and fewer rows live) are in no rate.
    at_close = {}
    closer = threading.Timer(
        ctx.seconds, lambda: (at_close.update(srv.stats()), record.close()))
    closer.daemon = True
    # ... and every PROGRESS_EVERY_S seconds of it, so that a run that
    # reads low says whether it was slow throughout or stood still.
    progress = []

    def watch():
        t_end = time.perf_counter() + ctx.seconds
        while time.perf_counter() < t_end:
            time.sleep(PROGRESS_EVERY_S)
            st = srv.stats()
            progress.append((st["steps"], st["prefill_chunks"]))

    watcher = threading.Thread(target=watch, daemon=True)

    @fed.remote
    def generator():
        if tracer:
            tracer.start()
        closer.start()
        watcher.start()
        return kind.drive(ctx, srv, requests, serving.Sink)

    # ---- the window (inside the task, on the serving party) ------------
    win = fed.get(generator.party(ctx.lead).remote())
    if tracer:
        tracer.join()
    closer.join()
    watcher.join()
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
        # How many requests the reference follows is the configuration's,
        # beside the limits calibrated at that many (``--set
        # sample_requests=N`` overrides it, for a calibration run).
        n_sample = int(ctx.mix.get(
            "sample_requests",
            limits.get("sample_requests", SAMPLE_REQUESTS)))
        picks = rng.permutation(len(rest))[:n_sample - 1]
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
    control = ctx.spec.get("control")
    t0 = time.perf_counter()
    rows = reference_gaps(ctx, adapter, sample, control)
    ref_s = time.perf_counter() - t0
    checks += gap_checks(
        rows, limits,
        why=f"{len(rows)} greedy requests, {sum(r['n'] for r in rows)} "
        f"served tokens, prompts {[r['prompt_len'] for r in rows]}")
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
    for name in ("gap", "mean", "agree", "lead"):
        notes.append(f"program {name}: "
                     + repr([round(r[name], 4) for r in rows]))
    notes.append("served tokens off the reference's best, (index, gap) a "
                 "request: " + repr([r["off"] for r in rows]))
    notes.append("served tokens a request: " + repr([r["n"] for r in rows]))
    if control:
        # The limits' own control, through the same comparison: the
        # reference's tokens in the precision below must read not correct,
        # by one of the limits at least.
        checks += gap_checks(
            rows, limits, f"control[{control}].", "control_gap",
            "control_mean",
            f"the tokens the reference puts first in {control}, held to the "
            f"limits of the served ones: a control a limit catches fails")
        for name in ("control_gap", "control_mean"):
            notes.append(f"control[{control}] {name}: "
                         + repr([round(r[name], 4) for r in rows]))
        notes.append(f"control[{control}] tokens off the reference's best, "
                     "(index, gap) a request: "
                     + repr([r["control_off"] for r in rows]))
    programs = arch.traced_programs(trace.dir) if trace else {}
    reduced = trace.reduce() if trace else None
    device = {"memory_peak_bytes": peak}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    stats = deltas(at_close, before)
    facts = dict(
        win["facts"], kind=ctx.mix["kind"], window_s=win["window_s"],
        steps=stats["steps"], slots=ctx.mix["serving"]["max_slots"],
        prefill_chunks=stats["prefill_chunks"], preempted=stats["preempted"],
        stats=stats, traced_stats=traced_stats, model=ctx.model,
        precision=ctx.spec["precision"], reference=ctx.spec["reference"],
        kv_block_size=ctx.mix["serving"]["kv_block_size"],
        trace=reduced, programs=programs, device_kind=ctx.device["kind"],
        program=record.facts(before, at_close),
    )
    if stats["steps"]:
        # Facts of the configuration and the window's traffic, not
        # metrics: what part of a step's least bytes each kind of read is.
        flops = importlib.import_module(
            "chipbench.flops_" + ctx.spec["reference"])
        parts = flops.window_least_bytes(facts)
        notes.append("least bytes of a decode step, MB: " + ", ".join(
            f"{k} {v / stats['steps'] / 1e6:.1f} "
            f"({100 * v / parts['total']:.1f} %)"
            for k, v in parts.items() if k != "total"))
    for i, name in enumerate(("steps", "prefill_chunks")):
        counts = [before[name]] + [p[i] for p in progress]
        notes.append(f"{name} every {PROGRESS_EVERY_S:.0f} s of the window: "
                     + repr([b - a for a, b in zip(counts, counts[1:])]))
    ctx.say("window", attempted=win["attempted"], failed=win["failed"],
            compiles_in_window=compiles_in_window, **stats,
            **{k: round(v, 3) for k, v in win["end_to_end"].items()})
    if programs:
        ctx.say("programs", **{
            name: f"{p['calls']}x{1e3 * p['seconds'] / p['calls']:.2f}ms"
            for name, p in sorted(programs.items())})
        ctx.say("traced", **traced_stats)
    end_to_end = dict(win["end_to_end"], setup_s=setup_s)
    return {
        "correct": all(c["ok"] for c in checks) and bool(rows),
        "attempted": win["attempted"], "failed": win["failed"],
        "end_to_end": end_to_end, "facts": facts, "checks": checks,
        "notes": notes, "setup_parts": ctx.setup_parts, "device": device,
        "breakdown": common.breakdown_of(reduced),
    }
