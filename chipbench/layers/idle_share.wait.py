"""Wire + placement on arrival: the share of the traced round in which the
device sat idle and NO span of the wire or of the aggregation was open at
the lead: every name that is neither ``fed:wire:*`` nor ``fed:agg:*``
(``chipbench:wait_aggregate``, ``:local_steps``, ``:wait_push``, the
runtime's TraceMes, ``no host span``, ``window edge``). The lead has no span
of its own there: it waits for the peer, or for its own train loop's host.
The rest of the partition: ``idle_share.wire``'s docstring. 0.0 where
nothing is left; None only without a trace or for a program without
spans."""

from chipbench.trace_reduce import idle_share


def counted(name):
    return not name.startswith(("fed:wire:", "fed:agg:"))


def read(facts):
    return idle_share(facts.get("trace"), counted)
