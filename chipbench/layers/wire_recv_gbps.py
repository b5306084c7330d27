"""Wire + placement on arrival: how fast a large frame comes off the
socket at the lead, by the program's own ``recv`` spans (``tracing``'s span
ring; a frame of 1 MiB and more is timed from its header parsed to its last
byte: ``fed:wire:recv``): the median over the window's timed ``recv`` spans
of ``nbytes / duration_s``, in GB/s (1e9 bytes). The peer's tree is one
such frame a round. What a faster receive path (ROADMAP S6) should raise.

From ``facts["program"]["spans"]`` (``common.ProgramRecord``): None
without it (an untraced run); 0.0 where the window held no such frame."""

from chipbench.common import percentile


def read(facts):
    program = facts.get("program")
    if not program:
        return None
    rates = [s["nbytes"] / s["duration_s"] / 1e9 for s in program["spans"]
             if s["kind"] == "recv" and s["timed"] and s["duration_s"] > 0]
    return percentile(rates, 50) if rates else 0.0
