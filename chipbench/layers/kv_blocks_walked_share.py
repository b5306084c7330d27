"""Serving engine: the blocks the decode read copied for the live rows
over the blocks those rows' lengths cover:
``100 x stats()["kv_blocks_walked"] / ["kv_blocks_attended"]`` (per paged
step; the walked count is the mean over the layers, a windowed layer's
from its window on). About 100 where each row reads its own blocks once
(the Pallas kernel of ``rayfed_tpu/ops/paged_attention.py``, PR 43: it
copies the blocks that hold cached keys, one fewer than ``attended``
counts for a row that stands at a block's first key); ``100 /
kv_blocks_share`` and more where every row of the program is walked as
far as the longest (the gather loop: every backend but a TPU).

From ``facts["program"]["stats"]`` (``common.ProgramRecord``): None
without it (an untraced run), where the window ran no paged step, or
where the program has no such counter (before PR 43)."""


def read(facts):
    stats = (facts.get("program") or {}).get("stats")
    if (not stats or not stats.get("kv_blocks_attended")
            or "kv_blocks_walked" not in stats):
        return None
    return 100.0 * stats["kv_blocks_walked"] / stats["kv_blocks_attended"]
