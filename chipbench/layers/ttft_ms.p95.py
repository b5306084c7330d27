"""Serving engine, open-loop cells: due time -> first token at the benchmark's sink, 95th percentile over
the window's requests; a refused or failed request counts as missing.
Recorded, not judged: some fifty requests a window make it the third-largest
sample."""


def read(facts):
    if facts.get("kind") != "open_loop":
        return None
    value = facts.get("ttft_ms.p95")
    return None if value in (None, float("inf")) else value
