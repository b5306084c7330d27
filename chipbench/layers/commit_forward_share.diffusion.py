"""Serving engine, the block-diffusion closed-loop cell: of the rows a
decode step forwarded (``diffusion_row_forwards``), the share whose
forward committed a clean block (``diffusion_commit_forwards``: its K/V
kept, no token of it). 1 / (T + 1) = 20 % at the schedule's floor, 50 %
where every block ends in one denoising step: what fusing the commit into
the next block's first step would save."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_diffusion"
            or not stats.get("diffusion_row_forwards")):
        return None
    return (100.0 * stats["diffusion_commit_forwards"]
            / stats["diffusion_row_forwards"])
