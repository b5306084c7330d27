"""Serving engine, the latent-attention closed-loop cell: of the least
bytes of the window's decode steps (``chipbench/flops_<reference>.py:
window_least_bytes``: weights held whole, hit experts, latent rows), the
share that is the latent cache: the keys the live rows' steps scored
(``decode_keys_attended``, summed over layers) at the bytes a token keeps
in one layer (``kv_lora_rank + qk_rope_head_dim`` values, one row read
once as key and value). The cache's weight in a step: it grows with the
contexts the rows hold and with the rows a step carries."""

import importlib


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_mla" or not stats.get("steps")
            or "decode_keys_attended" not in stats
            or "moe_experts_hit" not in stats):
        return None
    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    parts = arch.window_least_bytes(facts)
    return 100.0 * parts["latent"] / parts["total"]
