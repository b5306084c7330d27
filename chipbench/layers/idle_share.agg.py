"""Aggregation: the share of the traced round in which the device sat idle
in pieces of gaps booked to ``fed:agg:*`` (``fed:agg:reduce``: the reducer
tasks' dispatch of the sum and the mean, ``federated.py``). The partition
and the booking rule: ``idle_share.wire``'s docstring. 0.0 where nothing is
the aggregation's; None only without a trace or for a program without
spans."""

from chipbench.trace_reduce import idle_share


def counted(name):
    return name.startswith("fed:agg:")


def read(facts):
    return idle_share(facts.get("trace"), counted)
