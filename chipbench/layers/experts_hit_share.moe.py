"""Serving engine, the routed-experts closed-loop cell: of the held
experts a decode step could read (held x layers x steps), the share that
at least one live row chose (``moe_experts_hit``, counted on the device
and fetched behind the ids). What a step that streams every held expert
reads beyond what it must is the rest."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_moe" or not facts.get("steps")
            or "moe_experts_hit" not in stats):
        return None
    model = facts["model"]
    return 100.0 * stats["moe_experts_hit"] / (
        model["num_experts"] * model["num_hidden_layers"] * facts["steps"])
