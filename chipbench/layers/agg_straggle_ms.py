"""Aggregation: the straggler's lag at the party that reduces, by the
program's own accumulator ``fed:agg:straggle`` (``federated.py``: each
reducer task of ``fed_aggregate`` observes the done-stamp of the last of its
contributions to arrive off the wire less that of the last its own party
made, 0 where the arrivals were there first): its seconds over the window's
rounds, a round. What a faster wire has to bring to 0 for the round to be
bound by its steps (ROADMAP S6): a peer's tree that lands earlier can only
lower it.

From ``facts["program"]`` (``common.ProgramRecord``: ``phases`` is
``tracing.phase_summary()`` over the recorded rounds, ``rounds`` their
number): None without it; 0.0 where the name was never recorded."""


def read(facts):
    program = facts.get("program")
    if not program or not program.get("rounds"):
        return None
    straggle = program["phases"].get("fed:agg:straggle", {})
    return 1e3 * straggle.get("seconds", 0.0) / program["rounds"]
