"""Serving engine, the block-diffusion closed-loop cell: of the experts a
decode step could read (experts x layers x steps; every expert is held),
the share that at least one live position chose (``moe_experts_hit``,
counted on the device and fetched behind the blocks). 96 rows x 4
positions x 8 experts over 128: near all of them, every step."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_diffusion"
            or not facts.get("steps") or "moe_experts_hit" not in stats):
        return None
    model = facts["model"]
    return 100.0 * stats["moe_experts_hit"] / (
        model["num_experts"] * model["num_hidden_layers"] * facts["steps"])
