"""The benchmark's generator: sent - due, 95th percentile. A guard: a
starved generator is not a fast server."""

from chipbench.common import percentile


def read(facts):
    xs = facts.get("late_s") or []
    if facts.get("kind") != "open_loop" or not xs:
        return None
    return 1e3 * percentile(xs, 95)
