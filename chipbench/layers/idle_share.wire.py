"""Wire + placement on arrival: the share of the traced round in which the
device sat idle in pieces of gaps booked to a span of the wire:
``fed:wire:recv`` (a frame coming off the socket), ``:encode`` (the lead's
own tree staged on the host), ``:decode`` > ``:deserialize``, ``:place``.
With ``idle_share.agg`` and ``idle_share.wait`` it is the device's whole
idle share: ``.wire + .agg + .wait + 100 * idle_small_s / window_s ==
100 * (1 - busy_s / window_s)``.

Since PR 38 a gap is cut at the host events that overlap it and each piece
goes to the innermost ``fed:`` span (``chipbench/trace_reduce.py``), so the
benchmark's own ``chipbench:wait_aggregate`` no longer takes the round's
gaps whole. The wire's spans run on threads of their own: a piece of idle
time between two train steps that lies inside a ``fed:wire:recv`` is
counted here, whichever thread the device waited for (the run's notes give
those pieces apart). 0.0 where nothing is the wire's; None only without a
trace or for a program without spans."""

from chipbench.trace_reduce import idle_share


def counted(name):
    return name.startswith("fed:wire:")


def read(facts):
    return idle_share(facts.get("trace"), counted)
