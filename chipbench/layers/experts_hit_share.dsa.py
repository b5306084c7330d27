"""Serving engine, the learned-sparse-attention closed-loop cell: of the
held experts a decode step could read (held x expert layers x steps: the
leading dense layer holds none), the share that at least one live row
chose (``moe_experts_hit``, counted on the device under the selection bias
and fetched behind the ids). What a step that streams every held expert
reads beyond what it must is the rest."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_dsa" or not facts.get("steps")
            or "moe_experts_hit" not in stats):
        return None
    model = facts["model"]
    layers = model["num_hidden_layers"] - min(
        model["first_k_dense_replace"], model["num_hidden_layers"])
    if not layers:
        return None
    return 100.0 * stats["moe_experts_hit"] / (
        model["n_routed_experts"] * layers * facts["steps"])
