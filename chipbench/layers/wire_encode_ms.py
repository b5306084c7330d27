"""Wire + placement on arrival: the sender's host staging of a device tree
before it goes on the wire, by the program's own phase ``fed:wire:encode``
(``proxy/barriers.py``: the snapshot of the lead's aggregate for the peer):
its seconds over the window's rounds, a round. ROADMAP S6.

From ``facts["program"]`` (``common.ProgramRecord``: ``phases`` is
``tracing.phase_summary()`` over the recorded rounds, ``rounds`` their
number): None without it; 0.0 where no tree was staged."""


def read(facts):
    program = facts.get("program")
    if not program or not program.get("rounds"):
        return None
    encode = program["phases"].get("fed:wire:encode", {})
    return 1e3 * encode.get("seconds", 0.0) / program["rounds"]
