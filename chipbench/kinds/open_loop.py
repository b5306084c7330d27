"""kind ``open_loop``: independent users. Requests are sent on a schedule
fixed by the mix's rate (Poisson arrivals), whether or not earlier ones
have finished, and each is timed from when it was DUE, so a stall is
charged to every request it delays. Generator lateness is reported.

Judged on the median gap between consecutive token pushes of a request,
pooled over all requests (thousands of samples a window). The tails
(``ttft_ms.p95``, ``gap_ms.p95``) are computed over all requests and
recorded as per-layer metrics: where a window holds some fifty requests
a 95th percentile is its third-largest sample (PERF.md section 2).
"""

from __future__ import annotations

import math
import os
import time

from chipbench import common, serving, traffic


def plan(ctx, vocab):
    n = max(8, math.ceil(ctx.mix["rate_per_s"] * ctx.seconds))
    return {"requests": traffic.requests(ctx.mix, ctx.seed, vocab, n),
            "due": traffic.arrivals(ctx.mix, ctx.seed, n).tolist()}


def drive(ctx, srv, plan, Sink):
    reqs = [r for r, d in zip(plan["requests"], plan["due"])
            if d < ctx.seconds]
    due = [d for d in plan["due"] if d < ctx.seconds]
    sinks, futures, late = [], [], []
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    for i, (r, d) in enumerate(zip(reqs, due)):
        wait = t_start + d - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if ctx.inject(f"exit:{ctx.party}") and d > ctx.seconds / 2:
            ctx.say("injected exit", code=3)
            os._exit(3)
        sink = Sink()
        late.append(time.perf_counter() - (t_start + d))
        try:
            fut = srv.submit(r["prompt"], max_new_tokens=r["max_new"],
                             temperature=r["temperature"], seed=r["seed"],
                             stream=sink)
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            sink.failed, fut = repr(e), None
        sinks.append(sink)
        futures.append(fut)
    rest = deadline - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    window_s = time.perf_counter() - t_start
    records = serving.collect([dict(r, due=d) for r, d in zip(reqs, due)],
                              sinks, futures, t_start, deadline)
    failed = sum(1 for r in records
                 if r["failed"] or r["first_s"] is None)
    ttft = [(r["first_s"] - r["due"]) * 1e3
            if r["first_s"] is not None and not r["failed"]
            else float("inf") for r in records]
    gaps = []
    for r in records:
        t = [x for x in r["push_t"] if x <= ctx.seconds]
        gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    pushed, started = serving.served_in_window(records, ctx.seconds)
    # Whether the rate is sustained: the backlog at the close, and the
    # median TTFT of the window's second half against its first.
    open_at_close = sum(1 for r in records if not r["in_window"])
    half = [[t for t, r in zip(ttft, records)
             if (r["due"] >= ctx.seconds / 2) == late_half]
            for late_half in (False, True)]
    return {
        "records": records, "attempted": len(records), "failed": failed,
        "window_s": window_s,
        "end_to_end": {"gap_ms.p50": common.percentile(gaps, 50)},
        "facts": {"late_s": late, "pushed_tokens": pushed,
                  "first_tokens": len(started), "requests": len(records),
                  "ttft_ms.p50": common.percentile(ttft, 50),
                  "ttft_ms.p95": common.percentile(ttft, 95),
                  "gap_ms.p95": common.percentile(gaps, 95),
                  "gap_samples": len(gaps), "ttft_ms": ttft,
                  "open_at_close": open_at_close,
                  "ttft_ms.p50.first_half": common.percentile(half[0], 50),
                  "ttft_ms.p50.second_half": common.percentile(half[1], 50)},
    }


def run(ctx):
    import sys

    return serving.run(ctx, sys.modules[__name__])
