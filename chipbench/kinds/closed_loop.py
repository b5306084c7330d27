"""kind ``closed_loop``: callers that each wait for a reply. ``clients``
clients each send their next request when the last one completes (an
editor waits for its completion), so a slow system receives less load.

Judged on the tokens served inside the window, per second of window: the
prompt of every request whose first token came inside it (its prefill was
done there) and every generated token pushed inside it. Counting only the
requests COMPLETED inside the window leaves out whatever is in flight at
the close, a fifth of the work when a request takes 11 s of a 45 s window,
and which fifth depends on the order: the same code then reads 6 % apart
from seed to seed (PR 23, PERF.md section 6).

Every seed gets the same work: the ``pool`` sizes of the mix, each once
per cycle, in the seed's order, with fresh token ids in every cycle (so a
second cycle never hits the first one's prefixes).
"""

from __future__ import annotations

import os
import queue
import time

from chipbench import common, serving, traffic


CYCLES = 8


def plan(ctx, vocab):
    reqs = []
    for cycle in range(CYCLES):
        reqs += traffic.requests(ctx.mix, [ctx.seed, cycle], vocab,
                                 int(ctx.mix["pool"]))
    return {"requests": reqs}


def drive(ctx, srv, plan, Sink):
    reqs = plan["requests"]
    finished = queue.Queue()
    records, sinks, futures = [], [], []
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds

    def send(client):
        r = reqs[len(records) % len(reqs)]
        sink = Sink()
        records.append(dict(r, client=client,
                            sent=time.perf_counter() - t_start))
        try:
            fut = srv.submit(r["prompt"], max_new_tokens=r["max_new"],
                             temperature=r["temperature"], seed=r["seed"],
                             stream=sink)
            fut.add_done_callback(lambda _f, c=client: finished.put(c))
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            sink.failed, fut = repr(e), None
        sinks.append(sink)
        futures.append(fut)

    for client in range(int(ctx.mix["clients"])):
        send(client)
    while True:
        rest = deadline - time.perf_counter()
        if rest <= 0:
            break
        try:
            client = finished.get(timeout=rest)
        except queue.Empty:
            break
        if time.perf_counter() < deadline:
            send(client)
        if ctx.inject(f"exit:{ctx.party}") and rest < ctx.seconds / 2:
            ctx.say("injected exit", code=3)
            os._exit(3)
    window_s = time.perf_counter() - t_start
    records = serving.collect(records, sinks, futures, t_start, deadline)
    failed = sum(1 for r in records if r["failed"])
    done = [r for r in records if r["in_window"] and r["tokens"] is not None]
    pushed, started = serving.served_in_window(records, ctx.seconds)
    tokens = sum(len(r["prompt"]) for r in started) + pushed
    latency = [(r["done_s"] - r["sent"]) * 1e3 for r in done]
    return {
        "records": records, "attempted": len(records), "failed": failed,
        "window_s": window_s,
        "end_to_end": {"serve_tokens_per_s": tokens / window_s},
        "facts": {"late_s": [], "pushed_tokens": pushed,
                  "first_tokens": len(started), "requests": len(done),
                  "prompt_tokens": sum(len(r["prompt"]) for r in started),
                  "completed_tokens": sum(len(r["prompt"]) + len(r["tokens"])
                                          for r in done),
                  "latency_ms.p50": common.percentile(latency, 50)},
    }


def run(ctx):
    import sys

    return serving.run(ctx, sys.modules[__name__])
