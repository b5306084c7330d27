"""Serving engine: the share of the window's decode steps that were
dispatched while the step before them had not been fetched
(``stats()["steps_ahead"] / ["steps"]``, PR 36: decode runs one step
ahead), so that the device had the next step queued behind the one it ran.
The rest found nothing in flight: a version group's first step, the step
after a lull or after every row sat one out. A guard: it falls when a
change makes the engine wait for a step before it builds the next.

From ``facts["program"]["stats"]`` (``common.ProgramRecord``: the growth of
every integer counter of ``stats()`` over the window): None without it (an
untraced run) or where the window ran no step."""


def read(facts):
    stats = (facts.get("program") or {}).get("stats")
    if not stats or not stats.get("steps"):
        return None
    return 100.0 * stats.get("steps_ahead", 0) / stats["steps"]
