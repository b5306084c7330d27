"""Serving engine, the learned-sparse-attention closed-loop cell:
``decode_step_ms.complete``'s arithmetic, by that reader itself, on the
facts of the kind that runs this cell (which counts ``steps`` as the
window closes, without the drain that follows it). Everything that rides
between two decode iterations is in it: admission, prefill chunks (nine
tenths of this cell's device time), the host."""

from chipbench.run import load_reader


def read(facts):
    if facts.get("kind") != "closed_loop_dsa":
        return None
    return load_reader("decode_step_ms.complete")(
        dict(facts, kind="closed_loop"))
