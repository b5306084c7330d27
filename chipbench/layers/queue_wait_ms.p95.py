"""Serving engine (admission): admit - enqueue from the program's request
timeline (``tracing.record_request``), 95th percentile over the window's
requests."""

from chipbench.common import percentile


def read(facts):
    xs = facts.get("queue_wait_s") or []
    if facts.get("kind") != "open_loop" or not xs:
        return None
    return 1e3 * percentile(xs, 95)
