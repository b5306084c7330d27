"""Serving engine, the latent-attention closed-loop cell:
``decode_step_ms.complete``'s arithmetic, by that reader itself, on the
facts of the kind that runs this cell (which counts ``steps`` as the
window closes, without the drain that follows it). Everything that rides
between two decode iterations is in it: admission, prefill chunks, the
host."""

from chipbench.run import load_reader


def read(facts):
    if facts.get("kind") != "closed_loop_mla":
        return None
    return load_reader("decode_step_ms.complete")(
        dict(facts, kind="closed_loop"))
