"""Serving engine, open-loop cells: due time -> first token at the benchmark's sink, median over the
window's requests."""


def read(facts):
    if facts.get("kind") != "open_loop":
        return None
    value = facts.get("ttft_ms.p50")
    return None if value in (None, float("inf")) else value
