"""Parameters, operations and bytes of Pangu-Ultra-MoE (``model_type:
pangu_ultra_moe``), computed from the published keys and the chip's share
(``chipbench/seeded_pangu_ultra_moe.py``: ``n_routed_experts`` held of
``router_experts`` scored, ``first_k_dense_replace`` leading dense layers,
a slice of the vocabulary). The yardstick's arithmetic for the ``*.mla``
readers: no PR that claims a gain may change it.

**A decode step's least bytes** are what must cross HBM whatever the
program does: every weight that is held whole once (latent attention's
five matrices, the dense SwiGLU, the shared expert, the router and the
norms of every layer; the final norm and the head), the three matrices of
each held expert that at least one live row chose (``moe_experts_hit``,
counted on the device), and the latent rows the live rows' steps scored
(``decode_keys_attended``, summed over layers, at ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer: ONE row, read once as key and
value). Activations, ids, the embedding's rows and the new token's row
are left out: small beside these, and leaving them out can only lower
the share.

**A decode step's operations**, in the form the program runs (absorbed):
two per parameter of what every row passes (``wk_b`` and ``wv_b`` are
multiplied into each row's query and output once, like any matrix), two
per parameter of an expert for each (row, expert) pair on a held expert
(``moe_assignments_local``, counted on the device), and per key a row
scores ``2 H (2 rkv + dr)``: the scores over the whole 576-wide row and
the values over its first 512 columns. The engine does not count live
rows a step: they are taken at the window's own mean (tokens decoded /
steps). At 48 rows a step is memory-bound by a wide margin.

**The prefill programs' least seconds** (``chunk_step`` and
``prefill_rows`` together) are the larger of their operations at the
MXU's peak and their bytes at HBM's. Operations, in the form the program
runs (expanded): two per parameter of what every token passes, the head
left out (one position a prompt); two per parameter of an expert for
each (token, expert) pair on a held expert, taken at the share the decode
steps of the same window measured; ``2 H (dn + dr + dv)`` for each
(query, key) pair attended (``prefill_keys_attended``); and ``2 rkv H
(dn + dv)`` for each cached row a chunk gathers and expands again
(``chunk_blocks_read`` blocks of the pool, whole: a chunk's own rows are
in the first term). Bytes: the weights held whole once a call and the
experts a call touches, each once, at their expectation under even
routing (512 tokens touch all 16). The latent rows read are left out.
"""

from __future__ import annotations

from chipbench import flops

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
STEP_PROGRAM = "jit_decode_step"
PREFILL_PROGRAMS = ("jit_chunk_step", "jit_prefill_rows")


def param_counts(model: dict) -> dict:
    """Parameters of one layer's parts and of the model as held here."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    attention = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
                 + rkv * h * (dn + dv) + h * dv * d)
    norms = 4 * d + rq + rkv
    dense = 3 * d * model["intermediate_size"]
    expert = 3 * d * model["moe_intermediate_size"]
    shared = model["n_shared_experts"] * expert
    router = d * model.get("router_experts", model["n_routed_experts"])
    n_layers = model["num_hidden_layers"]
    n_dense = min(model["first_k_dense_replace"], n_layers)
    n_expert = n_layers - n_dense
    head = model["vocab_size"] * d
    # What every token passes, all layers: matrices only, then with norms.
    whole_matmul = (n_layers * attention + n_dense * dense
                    + n_expert * (shared + router))
    whole = whole_matmul + n_layers * norms
    return {
        "attention": attention, "dense": dense, "expert": expert,
        "shared": shared, "router": router, "head": head, "embed": head,
        "expert_layers": n_expert, "whole_matmul": whole_matmul,
        "whole": whole,
        "total": whole + n_expert * model["n_routed_experts"] * expert
        + 2 * head + d,
    }


def _param_bytes(precision: dict) -> int:
    return DTYPE_BYTES[precision.get("parameters", "bfloat16")]


def whole_bytes_per_call(model: dict, precision: dict) -> int:
    """What every execution of a serving program reads whatever it
    routes: the layers' whole parts, the final norm, the head."""
    c = param_counts(model)
    return (c["whole"] + c["head"] + model["hidden_size"]) \
        * _param_bytes(precision)


def expert_bytes(model: dict, precision: dict) -> int:
    return param_counts(model)["expert"] * _param_bytes(precision)


def latent_bytes_per_key(model: dict, precision: dict) -> int:
    """What a token keeps in ONE layer: the latent and the rotated key."""
    return ((model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])


def window_least_bytes(facts: dict) -> dict:
    """Least bytes of the decode steps that ``facts["stats"]`` counts
    (deltas of the engine's ``stats()``), by part."""
    model, precision, stats = (facts["model"], facts["precision"],
                               facts["stats"])
    weights = stats["steps"] * whole_bytes_per_call(model, precision)
    experts = stats["moe_experts_hit"] * expert_bytes(model, precision)
    latent = stats["decode_keys_attended"] * latent_bytes_per_key(
        model, precision)
    return {"weights": weights, "experts": experts, "latent": latent,
            "total": weights + experts + latent}


def rows_per_step(facts: dict) -> float:
    """Live rows a decode step, the window's mean."""
    decoded = facts.get("pushed_tokens", 0) - facts.get("first_tokens", 0)
    if decoded > 0 and facts.get("steps"):
        return min(decoded / facts["steps"], facts["slots"])
    return float(facts["slots"])


def decode_least_seconds(facts: dict) -> dict:
    """Least seconds of the decode steps in the traced part of the
    window (``facts["traced_stats"]``): operations and bytes by part, and
    the larger of the two times."""
    counted = facts["traced_stats"]
    model = facts["model"]
    c = param_counts(model)
    h, rkv = model["num_attention_heads"], model["kv_lora_rank"]
    rows = counted["steps"] * rows_per_step(facts)
    ops = {
        "whole": 2.0 * rows * (c["whole_matmul"] + c["head"]),
        "experts": 2.0 * counted["moe_assignments_local"] * c["expert"],
        "attention": 2.0 * h * (2 * rkv + model["qk_rope_head_dim"])
        * counted["decode_keys_attended"],
    }
    nbytes = window_least_bytes(dict(facts, stats=counted))
    seconds, bound = flops.least_time(
        sum(ops.values()), nbytes["total"], flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}


def local_share(facts: dict) -> float:
    """(token, expert) pairs on held experts per token and expert layer,
    as a share of the ``k`` pairs a token has: what the window's decode
    steps measured, or the share of the experts held."""
    model, stats = facts["model"], facts["stats"]
    decoded = facts.get("pushed_tokens", 0) - facts.get("first_tokens", 0)
    layers = param_counts(model)["expert_layers"]
    if decoded > 0 and layers and stats.get("moe_assignments_local"):
        return stats["moe_assignments_local"] / (
            decoded * model["num_experts_per_tok"] * layers)
    return model["n_routed_experts"] / model.get(
        "router_experts", model["n_routed_experts"])


def chunk_least_seconds(facts: dict) -> dict:
    """Least seconds of the prefill programs' executions in the traced
    part of the window: ``facts["programs"]`` has how often each ran
    there, ``facts["traced_stats"]`` what the engine counted between the
    profile's start and its stop. Operations and bytes by part, and the
    larger of the two times."""
    counted = facts["traced_stats"]
    calls = sum(facts["programs"].get(name, {}).get("calls", 0)
                for name in PREFILL_PROGRAMS)
    model, precision = facts["model"], facts["precision"]
    c = param_counts(model)
    h, k = model["num_attention_heads"], model["num_experts_per_tok"]
    dn, dv = model["qk_nope_head_dim"], model["v_head_dim"]
    held = model["n_routed_experts"]
    scored = model.get("router_experts", held)
    tokens = counted["prefill_tokens"]
    ops = {
        "whole": 2.0 * tokens * c["whole_matmul"],
        "experts": 2.0 * tokens * k * local_share(facts)
        * c["expert_layers"] * c["expert"],
        "attention": 2.0 * h * (dn + model["qk_rope_head_dim"] + dv)
        * counted["prefill_keys_attended"],
        "expand": 2.0 * model["kv_lora_rank"] * h * (dn + dv)
        * counted.get("chunk_blocks_read", 0) * facts["kv_block_size"],
    }
    touched = held * (1.0 - (1.0 - k / scored) ** (tokens / max(calls, 1)))
    nbytes = {
        "whole": calls * whole_bytes_per_call(model, precision),
        "experts": calls * c["expert_layers"] * touched
        * expert_bytes(model, precision),
    }
    seconds, bound = flops.least_time(
        sum(ops.values()), sum(nbytes.values()),
        flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}
