"""Serving engine, the block-diffusion closed-loop cell:
``decode_step_ms.complete``'s arithmetic, by that reader itself, on the
facts of the kind that runs this cell (which counts ``steps`` as the
window closes, without the drain that follows it). A step here forwards a
block of every live row (denoising it or committing it), and everything
that rides between two steps is in it: admission, prefill, the host."""

from chipbench.run import load_reader


def read(facts):
    if facts.get("kind") != "closed_loop_diffusion":
        return None
    return load_reader("decode_step_ms.complete")(
        dict(facts, kind="closed_loop"))
