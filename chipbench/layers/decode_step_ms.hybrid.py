"""Serving engine, the hybrid (attention + state-space) closed-loop cell:
``decode_step_ms.complete``'s arithmetic, by that reader itself, on the
facts of the kind that runs this cell. One difference lies in the facts:
this kind counts ``steps`` as the window closes, without the drain that
follows it."""

from chipbench.run import load_reader


def read(facts):
    if facts.get("kind") != "closed_loop_arch":
        return None
    return load_reader("decode_step_ms.complete")(
        dict(facts, kind="closed_loop"))
