"""Serving engine, the open-loop chat cell: ``host_iter_ms``'s arithmetic,
by that reader itself (``steps_ahead_share.chat`` says why it has a file of
its own)."""

from chipbench.run import load_reader


def read(facts):
    return load_reader("host_iter_ms")(facts)
