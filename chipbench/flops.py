"""Operations and bytes, computed from shapes. The yardstick's arithmetic:
no PR that claims a gain may change it.

Copied in substance from ``benchmarks/transformer_train_benchmark.py``
(operations per token ``6*N_matmul + 12*L*d*S*0.5``; peak keyed by
``device_kind``; an unknown device is an error). Recomputed operations
(remat) are never counted.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peak for device kind {device_kind!r} in chipbench/peaks.json "
            f"(known: {sorted(table)}); add it with its source, do not guess")
    return table[device_kind]


def param_counts(model: dict) -> dict:
    """Parameters of a dense MHA + SwiGLU decoder with an untied head."""
    d, f = model["hidden_size"], model["intermediate_size"]
    v, n = model["vocab_size"], model["num_hidden_layers"]
    layer_matmul = 4 * d * d + 3 * d * f
    return {
        "layer": layer_matmul + 2 * d,
        "layer_matmul": layer_matmul,
        "embed": v * d,
        "head": d * v,
        "total": n * (layer_matmul + 2 * d) + 2 * v * d + d,
        "matmul": n * layer_matmul + d * v,  # all but the embedding lookup
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward operations one trained token needs: 6 per matmul
    parameter, plus causal attention (QK^T and PV, forward 4*S*d per layer
    halved by the mask, backward twice that)."""
    c = param_counts(model)
    attn = 12 * model["num_hidden_layers"] * model["hidden_size"] * seq * 0.5
    return 6.0 * c["matmul"] + attn


# Matmuls of (S x Dh) by (Dh x S) size each flash kernel performs per
# (batch row, head): forward QK^T, PV; dq kernel QK^T, dO V^T, dS K; dkv
# kernel QK^T, P^T dO, dO V^T, dS^T Q.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# Arrays of (S x Dh) each kernel reads or writes per (row, head), in the
# storage type: fwd q k v -> o; dq q k v do -> dq; dkv q k v do -> dk dv.
FLASH_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}


def flash_call(kernel: str, rows_heads: int, seq: int, head_dim: int,
               itemsize: int = 2) -> tuple:
    """(operations, bytes) of ONE call of a causal flash kernel on
    (rows*heads, seq, head_dim)."""
    ops = FLASH_MATMULS[kernel] * 2.0 * seq * seq * head_dim * 0.5
    nbytes = FLASH_ARRAYS[kernel] * seq * head_dim * itemsize
    return ops * rows_heads, float(nbytes * rows_heads)


def least_time(ops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, which bound) of the roofline for ops and bytes."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
