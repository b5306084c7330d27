"""Parameters, operations and bytes of SDAR-MoE (``model_type: sdar_moe``),
computed from the published keys (``chipbench/seeded_sdar_moe.py``: every
expert held, the whole vocabulary, the head untied). The yardstick's
arithmetic for the ``*.diffusion`` readers: no PR that claims a gain may
change it. The same work whatever implements it.

**A decode step** forwards a block of ``B`` positions for every live row
(``diffusion_row_forwards`` rows, counted on the device). Its least bytes
are what must cross HBM: attention's weights, the router and the norms of
every layer, the final norm and the head, once a step; the three matrices
of each expert that at least one live position chose
(``moe_experts_hit``, counted on the device); and per layer the K/V blocks
a row's context and block lie in (``kv_layer_blocks_attended``: read once
for the row's ``B`` queries). The embedding's rows, activations, ids and
the committed K/V are left out: small beside these. Its operations: two
per parameter of attention's projections and the router for every
forwarded position, two per parameter of an expert for each (position,
expert) pair (``moe_assignments_local``), four per head dimension for
each (query, key) pair scored (``decode_keys_attended``: ``B`` queries a
row over the context and the block), and two per parameter of the head
for every forwarded position. The least time of a stretch of steps is the
larger of the operations at the MXU's peak and the bytes at HBM's.

**The prefill programs** (``chunk_step`` and ``prefill_rows`` together)
compute no logits (a prompt's position predicts itself), so a prompt
needs the K/V of every layer and nothing behind the last layer's
attention: its router and experts feed no one (the compiler drops them:
``tests/test_tpu_compile.py``). Operations: attention's projections of
every layer and the router and experts of all layers but the last, over
the real prompt tokens put through (``prefill_tokens``) with eight (token,
expert) pairs a token a layer (every expert is held), and the (query,
key) pairs the block-causal mask admits (``prefill_keys_attended``).
Bytes: the same parts once a call and the experts a call touches, each
once: ``E * (1 - (1 - k / E) ** tokens)`` a layer, the expected count
under the seeded weights' even routing at the calls' mean size. The K/V
read and written are left out.
"""

from __future__ import annotations

from chipbench import flops

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
PREFILL_PROGRAMS = ("jit_chunk_step", "jit_prefill_rows")
STEP_PROGRAM = "jit_decode_step"


def param_counts(model: dict) -> dict:
    """Parameters of one layer's parts, of a layer and of the model."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    dh = model["head_dim"]
    q, kv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    attention = d * q + 2 * d * kv + q * d
    router = d * model["num_experts"]
    expert = 3 * d * f
    norms = 2 * d + 2 * dh
    whole = attention + router + norms
    layer = whole + model["num_experts"] * expert
    embed = model["vocab_size"] * d
    return {
        "attention": attention, "router": router, "expert": expert,
        "norms": norms, "layer_whole": whole, "layer": layer,
        "embed": embed, "head": embed,
        "total": model["num_hidden_layers"] * layer + 2 * embed + d,
    }


def _param_bytes(precision: dict) -> int:
    return DTYPE_BYTES[precision.get("parameters", "bfloat16")]


def whole_bytes_per_step(model: dict, precision: dict) -> int:
    """What every decode step reads whatever it routes: the layers' whole
    parts, the final norm, the head."""
    c = param_counts(model)
    return (model["num_hidden_layers"] * c["layer_whole"] + c["head"]
            + model["hidden_size"]) * _param_bytes(precision)


def expert_bytes(model: dict, precision: dict) -> int:
    return param_counts(model)["expert"] * _param_bytes(precision)


def kv_bytes_per_layer_block(model: dict, precision: dict,
                             block_size: int) -> int:
    """K and V of one block of the pool of one row in ONE layer."""
    return (2 * block_size * model["num_key_value_heads"] * model["head_dim"]
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])


def window_least_bytes(facts: dict) -> dict:
    """Least bytes of the decode steps that ``facts["stats"]`` counts
    (deltas of the engine's ``stats()``), by part."""
    model, precision, stats = (facts["model"], facts["precision"],
                               facts["stats"])
    weights = stats["steps"] * whole_bytes_per_step(model, precision)
    experts = stats["moe_experts_hit"] * expert_bytes(model, precision)
    kv = stats["kv_layer_blocks_attended"] * kv_bytes_per_layer_block(
        model, precision, facts["kv_block_size"])
    return {"weights": weights, "experts": experts, "kv": kv,
            "total": weights + experts + kv}


def block_length(model: dict) -> int:
    return int(model.get("block_length", 4))


def window_ops(facts: dict) -> dict:
    """Operations of the decode steps that ``facts["stats"]`` counts, by
    part."""
    model, stats = facts["model"], facts["stats"]
    c = param_counts(model)
    positions = stats["diffusion_row_forwards"] * block_length(model)
    return {
        "whole": 2.0 * positions * model["num_hidden_layers"]
        * (c["attention"] + c["router"]),
        "experts": 2.0 * stats["moe_assignments_local"] * c["expert"],
        "attention": 4.0 * model["num_attention_heads"] * model["head_dim"]
        * stats["decode_keys_attended"],
        "head": 2.0 * positions * c["head"],
    }


def decode_least_seconds(facts: dict) -> dict:
    """Least seconds of the decode steps in the traced part of the window
    (``facts["traced_stats"]``): operations and bytes by part, and the
    larger of the two times."""
    counted = dict(facts, stats=facts["traced_stats"])
    ops, nbytes = window_ops(counted), window_least_bytes(counted)
    seconds, bound = flops.least_time(
        sum(ops.values()), nbytes["total"], flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}


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
    n_layers, k = model["num_hidden_layers"], model["num_experts_per_tok"]
    n_experts = model["num_experts"]
    routed_layers = n_layers - 1      # nothing reads the last layer's
    tokens = counted["prefill_tokens"]
    ops = {
        "whole": 2.0 * tokens * (n_layers * c["attention"]
                                 + routed_layers * c["router"]),
        "experts": 2.0 * tokens * k * routed_layers * c["expert"],
        "attention": 4.0 * model["num_attention_heads"] * model["head_dim"]
        * counted["prefill_keys_attended"],
    }
    touched = n_experts * (
        1.0 - (1.0 - k / n_experts) ** (tokens / max(calls, 1)))
    nbytes = {
        "whole": calls * (n_layers * (c["attention"] + c["norms"])
                          + routed_layers * c["router"])
        * _param_bytes(precision),
        "experts": calls * routed_layers * touched
        * expert_bytes(model, precision),
    }
    seconds, bound = flops.least_time(
        sum(ops.values()), sum(nbytes.values()),
        flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}
