"""Driver / task engine: how long a task of the window sat between ``submit``
and the moment a pool worker, an actor's lane or a thief began it, by the
program's own accumulator ``fed:task:queued`` (``_private/executor.py``; 0
for a task run inline by its submitter): its seconds over its count, the
mean of the window's tasks, in ms. A guard: with a handful of tasks a round
over 32 workers no task waits for a worker; a lane that queues (a train call
behind the one before it) or a pool that runs dry shows here first. Not the
window's one maximum: that is the longest single hold of the interpreter's
lock, it wanders by a factor of four from run to run, and it is in
``facts["program"]["phases"]`` as ``max_s`` beside this.

From ``facts["program"]`` (``common.ProgramRecord``): None without it; 0.0
where the name was never recorded."""


def read(facts):
    program = facts.get("program")
    if not program:
        return None
    queued = program["phases"].get("fed:task:queued", {})
    return 1e3 * queued.get("seconds", 0.0) / max(queued.get("count", 0), 1)
