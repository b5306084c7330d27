"""Wire + placement on arrival: how fast a large frame leaves the lead, by
the program's own ``write`` spans (``tracing``'s span ring; the sender's lane
times a frame of 1 MiB and more from its first byte handed to the socket to
its last, in the ring only, the mirror of the receiver's timed ``recv``):
the median over the window's ``write`` spans of ``nbytes / duration_s``, in
GB/s (1e9 bytes). The aggregate for the peer is one such frame a round. Beside
``wire_recv_gbps`` it says which side of a connection sets its rate
(ROADMAP S6).

From ``facts["program"]["spans"]`` (``common.ProgramRecord``): None
without it (an untraced run); 0.0 where the window held no such frame."""

from chipbench.common import percentile


def read(facts):
    program = facts.get("program")
    if not program:
        return None
    rates = [s["nbytes"] / s["duration_s"] / 1e9 for s in program["spans"]
             if s["kind"] == "write" and s["duration_s"] > 0]
    return percentile(rates, 50) if rates else 0.0
