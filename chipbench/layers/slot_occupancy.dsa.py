"""Serving engine, the learned-sparse-attention closed-loop cell:
``slot_occupancy.complete``'s arithmetic, by that reader itself, on the
facts of the kind that runs this cell (``steps`` as the window closes,
without the drain)."""

from chipbench.run import load_reader


def read(facts):
    if facts.get("kind") != "closed_loop_dsa":
        return None
    return load_reader("slot_occupancy.complete")(
        dict(facts, kind="closed_loop"))
