"""Serving engine, open-loop cells: gaps between consecutive token pushes of a request, pooled, 95th
percentile. Recorded, not judged: it sits on the edge between iterations with
and without a prefill chunk riding along and flips between the two (70 and
90 ms)."""


def read(facts):
    if facts.get("kind") != "open_loop":
        return None
    value = facts.get("gap_ms.p95")
    return None if value in (None, float("inf")) else value
