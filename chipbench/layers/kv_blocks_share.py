"""Serving engine: what part of every row at full length the window's
decode steps needed: ``stats()["kv_blocks_attended"]`` (per paged step, the
blocks the live rows' lengths cover) over ``["kv_blocks_slab"]``
(``max_slots x blocks_per_row``), PR 25. A fact of the traffic and the
engine's settings until the paged read skips what it does not need per
row (ROADMAP S10): then a step's time should follow it.

From ``facts["program"]["stats"]`` (``common.ProgramRecord``): None without
it (an untraced run) or where the window ran no paged step."""


def read(facts):
    stats = (facts.get("program") or {}).get("stats")
    if not stats or not stats.get("kv_blocks_slab"):
        return None
    return 100.0 * stats.get("kv_blocks_attended", 0) / stats["kv_blocks_slab"]
