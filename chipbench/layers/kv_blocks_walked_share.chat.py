"""Serving engine, the open-loop chat cell: ``kv_blocks_walked_share``'s
arithmetic, by that reader itself (``steps_ahead_share.chat`` says why it
has a file of its own)."""

from chipbench.run import load_reader


def read(facts):
    return load_reader("kv_blocks_walked_share")(facts)
