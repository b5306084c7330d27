"""Serving engine, the block-diffusion closed-loop cell: tokens a row's
forward yields. Positions unmasked (``diffusion_tokens_unmasked``) over
rows forwarded (``diffusion_row_forwards``), both counted on the device
and fetched behind the blocks. A block of ``B`` costs 1 to ``T`` denoising
forwards and one that commits it, so this lies between ``B / (T + 1)``
and ``B / 2``: 0.8 to 2 at ``B`` = ``T`` = 4 (a request's first block, led
by the prompt's left-over tokens, and its last, never committed, move it a
little)."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_diffusion"
            or not stats.get("diffusion_row_forwards")):
        return None
    return stats["diffusion_tokens_unmasked"] / stats["diffusion_row_forwards"]
