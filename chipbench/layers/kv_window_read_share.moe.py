"""Serving engine, the routed-experts closed-loop cell: the K/V blocks the
layers of the live rows must read in the window's decode steps
(``kv_layer_blocks_attended``: a sliding layer only its window's) over
what they would read if every layer read every key (layers x
``kv_blocks_attended``). Under 100 % where the window bites."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_moe"
            or not stats.get("kv_blocks_attended")
            or "kv_layer_blocks_attended" not in stats):
        return None
    return 100.0 * stats["kv_layer_blocks_attended"] / (
        facts["model"]["num_hidden_layers"] * stats["kv_blocks_attended"])
