"""Parameters and bytes of Falcon-H1 (``model_type: falcon_h1``), computed
from the published keys. The yardstick's arithmetic for the ``*.hybrid``
readers: no PR that claims a gain may change it.

A decode step's least bytes are what must cross HBM whatever the program
does: every weight once (the layers, the final norm and the head; of the
embedding only one row a token, not counted), the recurrent state of the
rows that advance read and written once each, and the K/V of the blocks
the live rows' lengths cover, read once. Activations, logits and the new
token's K/V are left out: they are small beside these, and leaving them
out can only make the least time smaller and the share of it lower.
"""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def param_counts(model: dict) -> dict:
    """Parameters of one Falcon-H1 block and of the whole model."""
    d, f = model["hidden_size"], model["intermediate_size"]
    v, n = model["vocab_size"], model["num_hidden_layers"]
    dh = model["head_dim"]
    q, kv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    d_ssm, hs = model["mamba_d_ssm"], model["mamba_n_heads"]
    conv_dim = d_ssm + 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    attention = d * q + 2 * d * kv + q * d
    mixer = (d * (d_ssm + conv_dim + hs)            # in_proj: z | xBC | dt
             + conv_dim * model["mamba_d_conv"] + conv_dim   # conv + bias
             + 3 * hs                               # dt_bias, A_log, D
             + d_ssm                                # the gated norm
             + d_ssm * d)                           # out_proj
    mlp = 3 * d * f
    layer = attention + mixer + mlp + 2 * d
    return {
        "attention": attention, "mixer": mixer, "mlp": mlp, "layer": layer,
        "embed": v * d, "head": d * v,
        "total": n * layer + 2 * v * d + d,
    }


def state_bytes_per_row(model: dict, precision: dict) -> dict:
    """Bytes of recurrent state one slot holds over all layers: the SSM
    state (heads x head size x state size) in ``ssm_state``'s type and the
    convolution tail (kernel - 1 inputs) in the cache's."""
    n = model["num_hidden_layers"]
    conv_dim = (model["mamba_d_ssm"]
                + 2 * model["mamba_n_groups"] * model["mamba_d_state"])
    ssm = (n * model["mamba_n_heads"] * model["mamba_d_head"]
           * model["mamba_d_state"]
           * DTYPE_BYTES[precision.get("ssm_state", "float32")])
    conv = (n * (model["mamba_d_conv"] - 1) * conv_dim
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])
    return {"ssm": ssm, "conv": conv, "total": ssm + conv}


def kv_bytes_per_block(model: dict, precision: dict, block_size: int) -> int:
    """K and V of one block of one row, over all layers."""
    return (2 * model["num_hidden_layers"] * block_size
            * model["num_key_value_heads"] * model["head_dim"]
            * DTYPE_BYTES[precision.get("kv_cache", "bfloat16")])


def weight_bytes_per_step(model: dict, precision: dict) -> int:
    c = param_counts(model)
    streamed = (model["num_hidden_layers"] * c["layer"] + c["head"]
                + model["hidden_size"])
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
