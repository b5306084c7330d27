"""Serving engine, the block-diffusion closed-loop cell:
``slot_occupancy.complete``'s arithmetic, by that reader itself, on the
facts of the kind that runs this cell: the share of decode-row slots that
forwarded a block, over the window's decode steps. What a row forwards
here is a block, and how many tokens come of it is another metric's
(``tokens_per_forward.diffusion``), so the reader is handed the rows the
device counted as live (``diffusion_row_forwards``) where it looks for
decoded tokens, and no first tokens (none comes from a prefill here)."""

from chipbench.run import load_reader


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_diffusion"
            or "diffusion_row_forwards" not in stats):
        return None
    return load_reader("slot_occupancy.complete")(dict(
        facts, kind="closed_loop", first_tokens=0,
        pushed_tokens=stats["diffusion_row_forwards"]))
