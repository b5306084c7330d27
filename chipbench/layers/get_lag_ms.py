"""Driver / task engine: how late the lead's driver saw a finished value, by
the program's own accumulator ``fed:get:lag`` (``api.py get``: for each value
that was not ready when ``fed.get`` was called, ``get``'s return less the
stamp of whoever resolved the value): its seconds over the window's rounds, a
round. In a fedround round it is the aggregate's: what the lead does between
the mean's result and its own ``fed.get`` returning (ROADMAP S6). The lag has
two humps (a few ms where a pool worker made the mean; the staging of the
aggregate for the peer, half a second, where the driver stole the task and
ran the result's callbacks itself), so this is what a round lost to it on
average and moves with how many of the window's rounds paid; the upper hump
is ``max_s`` beside it in ``facts["program"]["phases"]``.

From ``facts["program"]`` (``common.ProgramRecord``): None without it; 0.0
where the name was never recorded."""


def read(facts):
    program = facts.get("program")
    if not program or not program.get("rounds"):
        return None
    lag = program["phases"].get("fed:get:lag", {})
    return 1e3 * lag.get("seconds", 0.0) / program["rounds"]
