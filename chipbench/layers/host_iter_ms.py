"""Serving engine: the host's own work of an iteration, by the program's
phases: the seconds of ``fed:serve:admit``, ``:build``, ``:dispatch``,
``:emit`` and ``:prefill_chunk`` over the window (``tracing.phase_summary()``)
over its decode steps. ``fed:serve:fetch`` (the wait for the device) and
``:idle`` (the wait for a request) are left out: they are waiting, not work.
Since decode runs one step ahead (PR 36) this runs under the step before
and costs the device nothing while it stays under ``decode_step_ms.*``;
what it rises to is what the device would wait for without run-ahead.
``:admit`` and ``:prefill_chunk`` hold the waits for a prefill's ids, so in
a cell with many admissions it is more than bookkeeping.

From ``facts["program"]`` (``common.ProgramRecord``: ``phases`` and the
counters' growth, both read where the kind reads its counters): None
without it (an untraced run) or where the window ran no step."""

PHASES = tuple("fed:serve:" + p for p in (
    "admit", "build", "dispatch", "emit", "prefill_chunk"))


def read(facts):
    program = facts.get("program") or {}
    steps = (program.get("stats") or {}).get("steps")
    if not steps:
        return None
    return 1e3 * sum(program["phases"].get(p, {}).get("seconds", 0.0)
                     for p in PHASES) / steps
