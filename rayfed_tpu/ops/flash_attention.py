# Copyright 2026 The rayfed-tpu Authors.
#
# Licensed under the Apache License, Version 2.0 (the "License");
# you may not use this file except in compliance with the License.
# You may obtain a copy of the License at
#
#     http://www.apache.org/licenses/LICENSE-2.0
#
# Unless required by applicable law or agreed to in writing, software
# distributed under the License is distributed on an "AS IS" BASIS,
# WITHOUT WARRANTIES OR CONDITIONS OF ANY KIND, either express or implied.
# See the License for the specific language governing permissions and
# limitations under the License.

"""Pallas TPU flash attention (causal) with a blockwise backward pass.

Forward: blockwise attention with an online softmax — each q-block streams
through the k/v blocks at or below its diagonal, keeping the running max
and normalizer in registers, so the S x S score matrix never materializes
in HBM: O(S) memory instead of O(S^2), with the block matmuls sized for
the MXU (128-lane tiles, f32 accumulation via ``preferred_element_type``).
The kernel also emits the per-row logsumexp, which makes the attention
differentiable without rerunning the online softmax.

Backward: the standard FlashAttention recurrences (dP = dO V^T,
dS = P (dP - D), dQ = dS K, dK = dS^T Q, dV = P^T dO) as two Pallas
kernels — a dq pass (one q-block per program streaming its causal k/v
blocks) and a dk/dv pass (one k-block per program streaming its q blocks).
The p/dS tiles live only in VMEM, so the backward, like the forward, never
puts S^2 score traffic through HBM.

On non-TPU backends the kernel runs in interpret mode (tests), and
:func:`make_flash_attn_fn` plugs it into the transformer's ``attn_fn`` seam
(``models/transformer.layer_fn``), composing with the ring-attention lane:
ring handles the cross-device sequence axis, this kernel the on-device
blocks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_BIG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, block_q: int,
                  block_k: int, scale: float, seq_len: int,
                  q_offset_base: int):
    qi = pl.program_id(1)
    # Keep q/k/v in their storage dtype (bf16) for the MXU — f32 matmul
    # inputs run at a fraction of the bf16 rate; accumulation is f32 via
    # preferred_element_type. Scaling happens on the f32 scores.
    q = q_ref[0]  # (block_q, D)
    d = q.shape[-1]

    q_pos = (
        q_offset_base + qi * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    )

    # Causal: only k/v blocks at or below this q block's last row.
    last_q_pos = q_offset_base + qi * block_q + block_q - 1
    n_kb = jax.lax.min(
        (last_q_pos // block_k) + 1,
        jnp.int32(seq_len // block_k),
    )

    def body(kb, carry):
        m, l, acc = carry
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, kblk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k) f32
        k_pos = (
            kb * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        )
        s = jnp.where(q_pos >= k_pos, s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # Per-row logsumexp of the (scaled, masked) scores — the backward's
    # softmax replay key. Trailing singleton keeps the block TPU-tileable.
    l_ref[0] = (m + jnp.log(l_safe))[:, None]


def _flash_fwd_raw(q, k, v, block_q, block_k, interpret, q_offset):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d**-0.5

    # Fold batch and heads into one leading grid axis: (B*H, S, D).
    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        scale=scale,
        seq_len=sk,
        q_offset_base=q_offset,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    out = jnp.transpose(out.reshape(b, h, sq, d), (0, 2, 1, 3))
    lse = jnp.transpose(lse.reshape(b, h, sq), (0, 2, 1))  # (B, S, H)
    return out, lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q: int, block_k: int, scale: float,
                         seq_len: int, q_offset_base: int):
    """dQ pass: one q-block per program, streaming its causal k/v blocks.
    The p/dS tiles live only in VMEM — no S^2 HBM traffic."""
    qi = pl.program_id(1)
    q = q_ref[0]              # (block_q, D) storage dtype
    do = do_ref[0]            # (block_q, D)
    lse = lse_ref[0]          # (block_q, 1) f32
    delta = delta_ref[0]      # (block_q, 1) f32
    d = q.shape[-1]

    q_pos = (
        q_offset_base + qi * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    )
    last_q_pos = q_offset_base + qi * block_q + block_q - 1
    n_kb = jax.lax.min(
        (last_q_pos // block_k) + 1, jnp.int32(seq_len // block_k)
    )

    def body(kb, dq):
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, kblk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        k_pos = (
            kb * block_k
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        )
        p = jnp.where(q_pos >= k_pos, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, vblk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        return dq + jax.lax.dot_general(
            ds, kblk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(
        0, n_kb, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, block_k: int,
                          scale: float, seq_len_q: int, q_offset_base: int):
    """dK/dV pass: one k-block per program, streaming the q blocks at or
    above its diagonal."""
    ki = pl.program_id(1)
    kblk = k_ref[0]           # (block_k, D)
    vblk = v_ref[0]           # (block_k, D)
    d = kblk.shape[-1]

    k_pos = (
        ki * block_k
        + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    )
    # First q block whose last global position reaches this k block.
    first_q_pos = ki * block_k - q_offset_base
    qb_start = jax.lax.max(
        jnp.int32(0), (first_q_pos - (block_q - 1)) // block_q
    )
    n_qb = jnp.int32(seq_len_q // block_q)

    def body(qb, carry):
        dk, dv = carry
        qblk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        doblk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(
            qblk, kblk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = (
            q_offset_base + qb * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        )
        p = jnp.where(q_pos >= k_pos, jnp.exp(s - lse), 0.0)
        p_lo = p.astype(qblk.dtype)
        dv = dv + jax.lax.dot_general(
            p_lo, doblk, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            doblk, vblk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * scale).astype(qblk.dtype)
        dk = dk + jax.lax.dot_general(
            ds, qblk, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb_start, n_qb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, block_q, block_k, interpret,
                      q_offset):
    """Backward via the two Pallas passes; inputs (B, S, H, D)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d**-0.5

    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)

    qf, kf, vf, dof = fold(q), fold(k), fold(v), fold(do)
    # delta_i = rowsum(dO * O) — cheap elementwise, stays in XLA.
    delta = jnp.einsum(
        "bqhd,bqhd->bqh", do.astype(jnp.float32), o.astype(jnp.float32)
    )
    deltaf = jnp.transpose(delta, (0, 2, 1)).reshape(b * h, sq, 1)
    lsef = jnp.transpose(lse, (0, 2, 1)).reshape(b * h, sq, 1)

    row_spec = pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0))
    stat_spec = pl.BlockSpec((1, block_q, 1), lambda bh, i: (bh, i, 0))
    full_q = pl.BlockSpec((1, sq, d), lambda bh, i: (bh, 0, 0))
    full_k = pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0))
    full_stat = pl.BlockSpec((1, sq, 1), lambda bh, i: (bh, 0, 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
            scale=scale, seq_len=sk, q_offset_base=q_offset,
        ),
        grid=(b * h, sq // block_q),
        in_specs=[row_spec, full_k, full_k, row_spec, stat_spec, stat_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    kcol_spec = pl.BlockSpec((1, block_k, d), lambda bh, i: (bh, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            scale=scale, seq_len_q=sq, q_offset_base=q_offset,
        ),
        grid=(b * h, sk // block_k),
        in_specs=[full_q, kcol_spec, kcol_spec, full_q, full_stat, full_stat],
        out_specs=[kcol_spec, kcol_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lsef, deltaf)

    def unfold(x, s):
        return jnp.transpose(x.reshape(b, h, s, d), (0, 2, 1, 3))

    return unfold(dq, sq), unfold(dk, sk), unfold(dv, sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_diff(q, k, v, block_q, block_k, interpret, q_offset):
    out, _ = _flash_fwd_raw(q, k, v, block_q, block_k, interpret, q_offset)
    return out


def _flash_diff_fwd(q, k, v, block_q, block_k, interpret, q_offset):
    out, lse = _flash_fwd_raw(q, k, v, block_q, block_k, interpret, q_offset)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(block_q, block_k, interpret, q_offset, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_pallas(
        q, k, v, out, lse, do, block_q, block_k, interpret, q_offset
    )


_flash_attention_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret", "q_offset"),
)
def flash_attention(
    q, k, v,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    q_offset: int = 0,
):
    """Causal flash attention on (B, S, H, D) tensors; differentiable.

    ``q_offset`` shifts query positions (sequence-parallel callers pass the
    shard's global offset). Sequence length must be divisible by the block
    sizes (pad upstream); block sizes auto-shrink for short sequences.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # Shrink to the largest power-of-two divisor so any 8-divisible S works
    # with the default block sizes.
    block_q = min(block_q, _pow2_block(sq, cap=block_q))
    block_k = min(block_k, _pow2_block(sk, cap=block_k))
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    if interpret is None:
        from rayfed_tpu.utils import is_tpu_backend

        interpret = not is_tpu_backend()
    return _flash_attention_diff(q, k, v, block_q, block_k, interpret, q_offset)


def _pow2_block(s: int, cap: int = 128) -> int:
    """Largest power-of-two divisor of ``s``, capped."""
    blk = 1
    while blk < cap and s % (blk * 2) == 0:
        blk *= 2
    return blk


def make_flash_attn_fn(block_q: int = 512, block_k: int = 512,
                       min_block: int = 16):
    """An ``attn_fn`` for ``models.transformer.forward``: (B, S, H, D)
    q/k/v -> (B, S, H, D), causal. Falls back to the XLA attention at
    trace time when the sequence doesn't tile into at least ``min_block``
    blocks (flash pays off only at block scale)."""
    from rayfed_tpu.models.transformer import causal_attention

    def attn(q, k, v):
        bq = min(block_q, _pow2_block(q.shape[1], cap=block_q))
        bk = min(block_k, _pow2_block(k.shape[1], cap=block_k))
        if bq < min_block or bk < min_block:
            return causal_attention(q, k, v)
        return flash_attention(q, k, v, block_q=bq, block_k=bk)

    return attn
