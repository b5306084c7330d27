"""Parameters, operations and bytes of Olmo-Hybrid (``model_type:
olmo_hybrid``), computed from the published keys. The yardstick's
arithmetic for the ``*.linear`` readers: no PR that claims a gain may
change it.

A decode step's least bytes are what must cross HBM whatever the program
does: every weight once (the layers, the final norm and the head; of the
embedding only one row a token, not counted), the recurrent state of the
rows that advance read and written once each (the LINEAR layers: ``S``
and the convolution's tail), and the K/V of the blocks the live rows'
lengths cover, read once (the FULL layers alone keep K/V). Activations,
logits and the new token's K/V are left out: they are small beside these,
and leaving them out can only make the least time smaller and the share
of it lower.

A prompt chunk's least work: its operations (two a matmul parameter a
real token, the head once a chunk, and the recurrence in the chunked
form at sub-chunks of ``DELTA_CHUNK``) at the MXU's peak, or its bytes
(every weight once a chunk, the slot's state read and written once a
chunk a linear layer, the cached K/V blocks the full layers gather) at
HBM's, whichever is the larger. The full layers' attention operations
(under half a percent of a chunk's at these contexts) are left out: the
same direction.
"""

from __future__ import annotations

from chipbench import flops

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
LINEAR, FULL = "linear_attention", "full_attention"
# Positions the chunked form of the delta rule solves at once (the
# program's ``olmo_hybrid.DELTA_CHUNK``; ``tests/test_olmo_hybrid.py``
# holds the two equal).
DELTA_CHUNK = 64
CHUNK_PROGRAM = "jit_chunk_step"


def layer_kinds(model: dict) -> list:
    return list(model["layer_types"][:model["num_hidden_layers"]])


def _dims(model: dict):
    h = model["linear_num_key_heads"]
    return h, model["linear_key_head_dim"], model["linear_value_head_dim"]


def param_counts(model: dict) -> dict:
    """Parameters of a layer of each kind and of the whole model, every
    leaf counted."""
    d, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    dh = model.get("head_dim") or d // model["num_attention_heads"]
    q = model["num_attention_heads"] * dh
    kv = model["num_key_value_heads"] * dh
    h, dk, dv = _dims(model)
    conv_dim = h * (2 * dk + dv)
    kinds = layer_kinds(model)
    linear_matmul = d * conv_dim + d * 2 * h + d * h * dv + h * dv * d
    linear_mixer = (linear_matmul
                    + model["linear_conv_kernel_dim"] * conv_dim   # conv_w
                    + 2 * h                                  # A_log, dt_bias
                    + dv)                                    # the gated norm
    full_matmul = d * q + 2 * d * kv + q * d
    full_mixer = full_matmul + q + kv                        # q_norm, k_norm
    mlp = 3 * d * f
    n_lin, n_full = kinds.count(LINEAR), kinds.count(FULL)
    linear, full = linear_mixer + mlp + 2 * d, full_mixer + mlp + 2 * d
    return {
        "linear_mixer": linear_mixer, "full_mixer": full_mixer, "mlp": mlp,
        "linear_layer": linear, "full_layer": full,
        "n_linear": n_lin, "n_full": n_full,
        "embed": v * d, "head": d * v,
        "layers": n_lin * linear + n_full * full,
        # What a token multiplies by in the layers (all but the
        # convolution, the decay's constants and the norms).
        "layers_matmul": n_lin * (linear_matmul + mlp)
        + n_full * (full_matmul + mlp),
        "total": n_lin * linear + n_full * full + 2 * v * d + d,
    }


def state_bytes_per_row(model: dict, precision: dict) -> dict:
    """Bytes of recurrent state one slot holds over the LINEAR layers:
    ``S`` (heads x dv x dk) in the state's type and the convolution's tail
    (kernel - 1 inputs of the q | k | v channels) in the cache's."""
    h, dk, dv = _dims(model)
    n = layer_kinds(model).count(LINEAR)
    delta = n * h * dv * dk * DTYPE_BYTES[
        precision.get("delta_state", "float32")]
    conv = (n * (model["linear_conv_kernel_dim"] - 1) * h * (2 * dk + dv)
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])
    return {"delta": delta, "conv": conv, "total": delta + conv}


def kv_bytes_per_block(model: dict, precision: dict, block_size: int) -> int:
    """K and V of one block of one row, over the FULL layers only."""
    d = model["hidden_size"]
    dh = model.get("head_dim") or d // model["num_attention_heads"]
    return (2 * layer_kinds(model).count(FULL) * block_size
            * model["num_key_value_heads"] * dh
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])


def weight_bytes_per_step(model: dict, precision: dict) -> int:
    c = param_counts(model)
    streamed = c["layers"] + c["head"] + model["hidden_size"]
    return streamed * DTYPE_BYTES[precision.get("parameters", "bfloat16")]


def window_least_bytes(facts: dict) -> dict:
    """Least bytes of all the window's decode steps, by part, from the
    engine's counters (``facts["stats"]``: deltas of ``stats()``)."""
    model, precision, stats = (facts["model"], facts["precision"],
                               facts["stats"])
    weights = stats["steps"] * weight_bytes_per_step(model, precision)
    kv = stats["kv_blocks_attended"] * kv_bytes_per_block(
        model, precision, facts["kv_block_size"])
    # Counted by the engine: live rows x state bytes x 2 (read, write).
    state = stats["ssm_state_bytes"]
    return {"weights": weights, "kv": kv, "state": state,
            "total": weights + kv + state}


def delta_rule_ops_per_token(model: dict, chunk: int = DELTA_CHUNK) -> float:
    """Operations of the recurrence a token, all linear layers and heads,
    in the chunked form at sub-chunks of ``chunk``: a head's three ``dk x
    dv`` products (``W S^T``, ``Q S^T``, the state's update), the two
    ``chunk``-wide score products (``K K^T``, ``Q K^T``), their product
    with ``U'``, and the triangular solve at half a dense one."""
    h, dk, dv = _dims(model)
    per_head = 2.0 * (3 * dk * dv + chunk * (2.5 * dk + 1.5 * dv))
    return layer_kinds(model).count(LINEAR) * h * per_head


def chunk_least_seconds(facts: dict) -> dict:
    """Least seconds of ONE prompt chunk, the window's mean: operations
    and bytes of all the window's chunks by part (from the growth of the
    engine's counters over the window, ``facts["program"]["stats"]``), the
    larger of their two times, over the chunks."""
    counted = facts["program"]["stats"]
    model, precision = facts["model"], facts["precision"]
    c = param_counts(model)
    chunks, tokens = counted["prefill_chunks"], counted["chunk_tokens"]
    ops = {
        "layers": 2.0 * tokens * c["layers_matmul"],
        "head": 2.0 * chunks * c["head"],
        "delta_rule": tokens * delta_rule_ops_per_token(model),
    }
    nbytes = {
        "weights": chunks * weight_bytes_per_step(model, precision),
        "state": counted["chunk_state_bytes"],
        "kv": counted["chunk_blocks_read"] * kv_bytes_per_block(
            model, precision, facts["kv_block_size"])
        / max(layer_kinds(model).count(FULL), 1),
    }
    seconds, bound = flops.least_time(
        sum(ops.values()), sum(nbytes.values()),
        flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds / chunks,
            "bound": bound}
