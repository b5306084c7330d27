"""Serving engine, the open-loop chat cell: ``steps_ahead_share``'s
arithmetic, by that reader itself. A reader of its own because the cell is
judged on another metric (``gap_ms.p50``) than the closed-loop cells, and
``moves`` is one name."""

from chipbench.run import load_reader


def read(facts):
    return load_reader("steps_ahead_share")(facts)
