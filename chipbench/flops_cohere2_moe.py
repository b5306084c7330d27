"""Parameters, operations and bytes of Cohere2-MoE (``model_type:
cohere2_moe``), computed from the published keys and the chip's share
(``chipbench/seeded_cohere2_moe.py``: ``num_experts`` held of
``router_experts`` scored, a slice of the tied vocabulary). The
yardstick's arithmetic for the ``*.moe`` readers: no PR that claims a gain
may change it.

**A decode step's least bytes** are what must cross HBM whatever the
program does: every weight that is held whole once (attention, the shared
experts, the router and the norms of every layer; the final norm and the
tied embedding as the head), the three matrices of each held expert that
at least one live row chose (``moe_experts_hit``, counted on the device),
and per layer the K/V blocks that layer must read (all of a row's on a
full layer, the window's on a sliding one: ``kv_layer_blocks_attended``).
Activations, ids and the new token's K/V are left out: small beside
these, and leaving them out can only lower the share.

**The prefill programs' least seconds** (``chunk_step`` and
``prefill_rows`` together: the engine's counters do not tell them apart)
are the larger of their operations at the MXU's peak and their bytes at
HBM's peak. Operations: two per parameter of what every token passes
(attention's projections, the shared experts, the router), two per
parameter of an expert for each (token, expert) pair that falls on a held
expert, and four per head dimension for each (query, key) pair attended
(``prefill_keys_attended``, which knows the windows). The pairs on held
experts are not counted in prefill (it would cost a fetch per chunk);
they are taken at the share the decode steps of the same window measured
(``moe_assignments_local`` per decoded row), or the share held where no
step ran. Bytes: the weights held whole once a call and the experts a
call touches, each once: ``held * (1 - (1 - k / E) ** tokens)`` a layer,
the expected count under the seeded weights' even routing at the calls'
mean size (512 tokens touch all 16; only a ragged first chunk under 40
tokens misses some). The head's matmul (one position a prompt) and the
K/V read are left out.
"""

from __future__ import annotations

from chipbench import flops

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
PREFILL_PROGRAMS = ("jit_chunk_step", "jit_prefill_rows")


def param_counts(model: dict) -> dict:
    """Parameters of one layer's parts, of a layer as held here, and of
    the model as held here."""
    d, f = model["hidden_size"], model["intermediate_size"]
    dh = model["head_dim"]
    q, kv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    attention = d * q + 2 * d * kv + q * d
    shared = model["num_shared_experts"] * 3 * d * f
    router = d * model.get("router_experts", model["num_experts"])
    expert = 3 * d * f
    whole = attention + shared + router + d           # + the one norm
    layer = whole + model["num_experts"] * expert
    embed = model["vocab_size"] * d
    return {
        "attention": attention, "shared": shared, "router": router,
        "expert": expert, "layer_whole": whole, "layer": layer,
        "embed": embed,
        "total": model["num_hidden_layers"] * layer + embed + d,
    }


def _param_bytes(precision: dict) -> int:
    return DTYPE_BYTES[precision.get("parameters", "bfloat16")]


def whole_bytes_per_call(model: dict, precision: dict) -> int:
    """What every execution of a serving program reads whatever it
    routes: the layers' whole parts, the final norm, the head."""
    c = param_counts(model)
    return (model["num_hidden_layers"] * c["layer_whole"] + c["embed"]
            + model["hidden_size"]) * _param_bytes(precision)


def expert_bytes(model: dict, precision: dict) -> int:
    return param_counts(model)["expert"] * _param_bytes(precision)


def kv_bytes_per_layer_block(model: dict, precision: dict,
                             block_size: int) -> int:
    """K and V of one block of one row in ONE layer."""
    return (2 * block_size * model["num_key_value_heads"] * model["head_dim"]
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])


def window_least_bytes(facts: dict) -> dict:
    """Least bytes of the decode steps that ``facts["stats"]`` counts
    (deltas of the engine's ``stats()``), by part."""
    model, precision, stats = (facts["model"], facts["precision"],
                               facts["stats"])
    weights = stats["steps"] * whole_bytes_per_call(model, precision)
    experts = stats["moe_experts_hit"] * expert_bytes(model, precision)
    kv = stats["kv_layer_blocks_attended"] * kv_bytes_per_layer_block(
        model, precision, facts["kv_block_size"])
    return {"weights": weights, "experts": experts, "kv": kv,
            "total": weights + experts + kv}


def local_share(facts: dict) -> float:
    """(token, expert) pairs on held experts per token and layer, as a
    share of the ``k`` pairs a token has: what the window's decode steps
    measured, or the share of the experts held."""
    model, stats = facts["model"], facts["stats"]
    decoded = facts.get("pushed_tokens", 0) - facts.get("first_tokens", 0)
    if decoded > 0 and stats.get("moe_assignments_local"):
        return stats["moe_assignments_local"] / (
            decoded * model["num_experts_per_tok"]
            * model["num_hidden_layers"])
    return model["num_experts"] / model.get(
        "router_experts", model["num_experts"])


def chunk_least_seconds(facts: dict) -> dict:
    """Least seconds of the prefill programs' executions in the traced
    part of the window: ``facts["programs"]`` has how often each ran
    there, ``facts["traced_stats"]`` what the engine counted between the
    profile's start and its stop (``prefill_tokens`` real tokens put
    through, ``prefill_keys_attended`` (query, key) pairs). Operations
    and bytes by part, and the larger of the two times."""
    counted = facts["traced_stats"]
    calls = sum(facts["programs"].get(name, {}).get("calls", 0)
                for name in PREFILL_PROGRAMS)
    model, precision = facts["model"], facts["precision"]
    peak = flops.peaks(facts["device_kind"])
    c = param_counts(model)
    n_layers, k = model["num_hidden_layers"], model["num_experts_per_tok"]
    held = model["num_experts"]
    scored = model.get("router_experts", held)
    tokens = counted["prefill_tokens"]
    ops = {
        "whole": 2.0 * tokens * n_layers * (c["layer_whole"]
                                            - model["hidden_size"]),
        "experts": 2.0 * tokens * k * local_share(facts) * n_layers
        * c["expert"],
        "attention": 4.0 * model["num_attention_heads"] * model["head_dim"]
        * counted["prefill_keys_attended"],
    }
    touched = held * (1.0 - (1.0 - k / scored) ** (tokens / max(calls, 1)))
    nbytes = {
        "whole": calls * whole_bytes_per_call(model, precision),
        "experts": calls * n_layers * touched
        * expert_bytes(model, precision),
    }
    seconds, bound = flops.least_time(
        sum(ops.values()), sum(nbytes.values()), peak)
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}
