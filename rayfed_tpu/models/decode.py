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

"""KV-cache autoregressive decoding for the flagship transformer.

The reference engine never runs models, so inference is pure new surface
for this framework: party-local generation on whatever checkpoint a
federated job just trained (e.g. sample from the aggregated model after a
FedAvg round, or serve the label party's head in split learning).

TPU-first design:
 - the K/V cache is **stacked over layers** — (n_layers, B, T, H, Dh) —
   mirroring the stacked layer parameters, so one ``lax.scan`` over layers
   threads (x, cache) through a single compiled block body;
 - the decode loop is a ``lax.scan`` over steps with static lengths: one
   compile for the whole generation, no per-token retrace, cache updates
   via ``lax.dynamic_update_slice_in_dim`` (in-place on TPU thanks to
   donation inside the scan carry);
 - prefill and decode share one cached-block implementation (prefill is
   just the S>1 case at offset 0), and the projections/FFN come from
   :mod:`rayfed_tpu.models.transformer` so the numerics match training
   bit-for-bit at equal dtypes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rayfed_tpu import utils
from rayfed_tpu.models import transformer as tfm

Cache = dict


def cache_spec(
    mesh: Mesh,
    party_axis: Optional[str] = "party",
    data_axis: Optional[str] = "data",
    model_axis: Optional[str] = "model",
    n_heads: Optional[int] = None,
) -> P:
    """PartitionSpec for the stacked (L, B, T, H, Dh) K/V cache: batch over
    party x data, heads over the tensor-parallel axis — the same layout the
    Megatron rules give the attention activations, so cached decode runs
    with zero resharding against tp-sharded parameters. Pass ``n_heads``
    to replicate the head dim when it does not divide the model axis
    (e.g. a tiny draft model on a wide tp mesh)."""
    from rayfed_tpu.parallel import sharding as shd

    batch = shd.batch_spec(mesh, party_axis, data_axis)[0]
    heads = model_axis if model_axis in mesh.axis_names else None
    if (
        heads is not None
        and n_heads is not None
        and n_heads % mesh.shape[model_axis] != 0
    ):
        heads = None
    return P(None, batch, None, heads, None)


def init_cache(
    cfg: tfm.TransformerConfig, batch: int, max_len: int, dtype=None
) -> Cache:
    """Zero-filled K/V cache covering ``max_len`` total positions."""
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _block_tail(x, o, layer, cfg: tfm.TransformerConfig):
    """The rest of a block after attention: output projection of ``o``
    (B, S, H, Dh) into the residual, then the FFN half."""
    x = x + jnp.einsum(
        "bshk,hkd->bsd", o, layer["wo"].astype(cfg.compute_dtype)
    )
    return x + tfm.ffn_apply(tfm.rms_norm(x, layer["ln2"]), layer, cfg)


def _head(x, params, cfg: tfm.TransformerConfig):
    """Logits (.., vocab) float32 of hidden states ``x`` (.., d): the
    final norm, then the head."""
    x = tfm.rms_norm(x, params["ln_f"])
    return (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(
        jnp.float32
    )


def forward_with_cache(
    params, tokens, cache: Cache, offset, cfg: tfm.TransformerConfig
):
    """Run ``tokens`` (B, S) int32 starting at global position ``offset``
    (S=1 while decoding, S=prompt length during prefill), reading and
    updating ``cache``. Returns (logits (B, S, vocab) f32, new_cache).

    The stacked (L, B, T, H, Dh) cache rides the **carry** of the layer
    scan: each layer writes only its (B, S, H, Dh) slice via
    ``dynamic_update_slice``, so XLA updates the donated carry buffer in
    place — per-step cache traffic is one slab read (the attention) plus
    one slice write, not a rewrite of the whole stack. Cache slots past
    ``offset + S`` hold zeros; the causal mask in
    :func:`transformer.causal_attention` (q_pos >= k_pos) never attends
    to them.
    """
    b, s = tokens.shape
    max_len = cache["k"].shape[2]
    # dynamic_update_slice would silently CLAMP an out-of-range start index
    # (misplacing K/V and corrupting logits); fail loudly where the bound
    # is checkable — s is always static, offset whenever passed concrete.
    if s > max_len:
        raise ValueError(f"token block ({s}) longer than cache ({max_len})")
    if not isinstance(offset, jax.core.Tracer) and int(offset) + s > max_len:
        raise ValueError(
            f"cache overflow: offset {int(offset)} + block {s} > {max_len}"
        )
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    positions = jnp.broadcast_to(offset + jnp.arange(s)[None, :], (b, s))

    def body(carry, layer):
        x, ck, cv, i = carry
        q, k, v = tfm.qkv_proj(x, layer, positions, cfg)
        at = (i, 0, offset, 0, 0)
        ck = jax.lax.dynamic_update_slice(ck, k[None].astype(ck.dtype), at)
        cv = jax.lax.dynamic_update_slice(cv, v[None].astype(cv.dtype), at)
        o = tfm.causal_attention(
            q,
            jax.lax.dynamic_index_in_dim(ck, i, axis=0, keepdims=False),
            jax.lax.dynamic_index_in_dim(cv, i, axis=0, keepdims=False),
            q_offset=offset,
        )
        return (_block_tail(x, o, layer, cfg), ck, cv, i + 1), None

    init = (x, cache["k"], cache["v"], jnp.asarray(0, jnp.int32))
    (x, ck, cv, _), _ = jax.lax.scan(body, init, params["layers"])
    return _head(x, params, cfg), {"k": ck, "v": cv}


# Keys gathered per trip of the paged attention loop. A trip reads whole
# blocks for every row, so a row pays for the longest live row rounded up
# to this many keys; the loop's own cost per trip is a few microseconds.
# On a v5e (PR 25, PERF.md) 128, 256 and 512 read within 0.7 ms of one
# another at 6 rows x 16 heads and 8 rows x 32 heads, 256 least or next
# to least in every pattern of lengths; 1024 cost 4 to 8 ms more at
# 8 x 32 heads.
PAGED_CHUNK_KEYS = 256


def to_width(x, width: int):
    """``x`` with its last dimension zero-padded to ``width`` (a pool of
    single vectors keeps them padded to whole tiles:
    ``kv_pool.LANES``); as it is where it is that wide already."""
    short = width - x.shape[-1]
    if not short:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def _pool_dims(pk):
    """(L, P, block, heads, width) of a pool array: (L, P, block, Hkv,
    Dh), or (L, P, block, width) where a token keeps one vector (a
    latent row: one head, in effect)."""
    if pk.ndim == 4:
        return (*pk.shape[:3], 1, pk.shape[3])
    return pk.shape


def paged_attention(pk, pv, positions, tables, window=None, *,
                    scale=None, v_width=None):
    """The read of a block-paged K/V pool through the block tables, for
    one decode token per row. Returns ``attend(q, k1, v1, base)``: ``q``
    (R, H, Dh) a layer's queries, ``k1``/``v1`` (R, Hkv, Dh) its new key
    and value, ``base`` the layer's first row of the flattened pool
    (layer * P); the result is the attention output (R, H, Dh). K/V head
    i serves query heads i*G..(i+1)*G-1 (G = H / Hkv; 1 for plain MHA).

    A pool that keeps ONE array a token (``pv`` None: a latent cache,
    ``pk`` (L, P, bs, width), :mod:`rayfed_tpu.models.pangu_ultra_moe`)
    is read once: every query head reads the same row, and a key's value
    is its own first ``v_width`` columns, taken from the keys a trip
    gathered (``k1`` (R, 1, width), ``v1`` None too, and the output (R,
    H, v_width)). The pool may keep its rows zero-padded beyond what
    ``q`` and ``k1`` carry (to whole tiles): they are padded to match.
    ``scale`` multiplies the scores (None: ``Dh ** -0.5`` of the keys as
    cached; a query that was multiplied into the latent space keeps the
    scale of the head it came from).

    Chunks of ``PAGED_CHUNK_KEYS`` keys are gathered through the table
    under an online softmax (float32 max, sum and accumulator), and the
    trip count is a runtime value (the longest row's keys), so one
    program serves every length. The current token is the softmax's first
    key: the running max starts finite, and a chunk wholly past a row's
    length is an exact no-op for it (max unchanged, sum + 0, accumulator
    * 1 + 0): a row's output depends on nothing another row holds. The
    mathematics is :func:`transformer.causal_attention`'s (operands in
    the compute dtype, float32 scores, probabilities cast to the value
    dtype before the PV product), re-associated, nothing rounded lower.

    ``window`` (a static int, or None: every key) makes this the read of
    a sliding-window layer: a row at position ``p`` attends the keys
    ``p - window + 1 .. p``, its own among them. Each row's loop then
    starts at ITS window's first chunk: in trip ``c`` row ``r`` gathers
    chunk ``first[r] + c`` of its own table, the trip count is the most
    chunks any row's window spans (at most ``window / chunk + 1``,
    however long the rows are and however unequal), and keys before a
    row's window are masked. A step reads the window's blocks, not the
    row's history. A model whose layers differ builds one ``attend`` per
    kind from the same pool (:mod:`rayfed_tpu.models.cohere2_moe`); with
    ``window=None`` the loop and the mask are exactly the lines below,
    nothing added.

    On a TPU backend the same ``attend`` is one Pallas kernel
    (:mod:`rayfed_tpu.ops.paged_attention`, ``paged_read`` in a device
    trace): each row copies its own blocks through its table, once, up to
    its own length, and the loop below is the definition it is tested
    against. What the two walk is :func:`paged_blocks_walked`.
    """
    n_layers, n_phys, bs, n_kv, dh = _pool_dims(pk)
    if scale is None:
        scale = dh**-0.5
    if paged_read_is_kernel(pk, pv, tables.size):
        # The import is the engine's, begun on a thread of its own when
        # the server was made: by now it is done, or this waits for it.
        from rayfed_tpu.ops import paged_attention as kernel

        pk_flat, pv_flat = (
            None if a is None else a.reshape(-1, *a.shape[2:])
            for a in (pk, pv))

        def attend(q, k1, v1, base):
            return kernel.paged_read(
                q, k1, v1, pk_flat, pv_flat, positions, tables, base,
                window=window, scale=scale, v_width=v_width)

        return attend
    n_rows, blocks_per_row = tables.shape
    chunk_blocks = max(1, min(blocks_per_row, PAGED_CHUNK_KEYS // bs))
    chunk_keys = chunk_blocks * bs
    tables_p = jnp.pad(
        tables, ((0, 0), (0, -blocks_per_row % chunk_blocks))
    )
    trips = (jnp.max(positions) + chunk_keys - 1) // chunk_keys
    if window is not None:
        # The first key each row still sees and the chunk that holds it;
        # a row without cached keys (junk: position 0) asks for no trip.
        lo = jnp.maximum(positions - window + 1, 0)
        first = lo // chunk_keys
        spans = (jnp.maximum(positions, 1) - 1) // chunk_keys - first + 1
        trips = jnp.max(jnp.where(positions > 0, spans, 0))
        last_block = tables_p.shape[1] - 1
    # (L * P, bs, Hkv, Dh): a layer's block b is row layer * P + b, so one
    # gather reads a chunk's blocks and never the layer's whole pool.
    pk_flat = pk.reshape(n_layers * n_phys, *pk.shape[2:])
    if pv is not None:
        pv_flat = pv.reshape(n_layers * n_phys, bs, n_kv, dh)

    def attend(q, k1, v1, base):
        n_heads = q.shape[1]
        if pv is None:
            # The pool's rows may be padded to whole tiles (zeros): the
            # query and the new key are padded to match, and score the
            # padding as nothing.
            v1 = k1[..., :v_width]
            q, k1 = to_width(q, dh), to_width(k1, dh)
        q = q.reshape(n_rows, n_kv, n_heads // n_kv, dh)
        s1 = jnp.einsum(
            "rhgd,rhd->rhg", q, k1, preferred_element_type=jnp.float32
        ) * scale

        def chunk(c, carry):
            m, den, acc = carry
            if window is None:
                blocks = base + jax.lax.dynamic_slice_in_dim(
                    tables_p, c * chunk_blocks, chunk_blocks, axis=1
                )
            else:
                # Row r's chunk first[r] + c; past a row's last block the
                # index is held in range and every key masked.
                at = ((first + c) * chunk_blocks)[:, None] + jnp.arange(
                    chunk_blocks)
                blocks = base + jnp.take_along_axis(
                    tables_p, jnp.minimum(at, last_block), axis=1
                )
            kc = pk_flat[blocks].reshape(n_rows, chunk_keys, n_kv, dh)
            if pv is None:
                vc = kc[..., :v_width]
            else:
                vc = pv_flat[blocks].reshape(n_rows, chunk_keys, n_kv, dh)
            if window is None:
                k_pos = c * chunk_keys + jnp.arange(chunk_keys)
                cached = k_pos[None, :] < positions[:, None]
            else:
                k_pos = ((first + c) * chunk_keys)[:, None] + jnp.arange(
                    chunk_keys)
                cached = (k_pos < positions[:, None]) & (k_pos >= lo[:, None])
            s = jnp.einsum(
                "rhgd,rkhd->rhgk", q, kc, preferred_element_type=jnp.float32
            ) * scale
            s = jnp.where(cached[:, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            den = den * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "rhgk,rkhd->rhgd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return m_new, den, acc

        v_first = jnp.broadcast_to(
            v1.astype(jnp.float32)[:, :, None, :], s1.shape + v1.shape[-1:]
        )
        init = (s1, jnp.ones_like(s1), v_first)
        _, den, acc = jax.lax.fori_loop(0, trips, chunk, init)
        out = (acc / den[..., None]).astype(v1.dtype)
        return out.reshape(n_rows, n_heads, v1.shape[-1])

    return attend


# Entries of the block tables the kernel takes: they are a scalar-prefetch
# operand, whole in a core's 1 MiB of scalar memory beside the positions
# (a v5e compiles 131,072 and refuses 262,144: ``tests/test_tpu_compile``).
PAGED_KERNEL_TABLE_ENTRIES = 1 << 17


def paged_read_operands(kv_spec, kv):
    """``(pk, pv)`` of a pool's arrays ``kv`` as :func:`paged_attention`
    takes them, by what the model's ``kv_spec()`` declares a token keeps:
    a key and a value row of (heads, head size) are read together; an
    array of single vectors (a latent row) is read alone, ``pv`` None,
    and whatever else the pool holds is another read's."""
    per_head = len(kv_spec[0][1]) == 2
    return kv[0], (kv[1] if per_head else None)


def paged_read_is_kernel(pk, pv, table_entries: int) -> bool:
    """Whether :func:`paged_attention` reads this pool through the Pallas
    kernel: on a TPU backend, a pool the kernel can read under tables it
    can hold. It takes K/V heads out of a block two at a time, from
    32-bit words: an odd number of heads, or a dtype of another size than
    these, keeps the loop, and so do tables of more than
    ``PAGED_KERNEL_TABLE_ENTRIES`` (rows x blocks a row)."""
    return (utils.is_tpu_backend()
            and (pv is None or _pool_dims(pk)[3] % 2 == 0)
            and pk.dtype in (jnp.bfloat16, jnp.float32)
            and table_entries <= PAGED_KERNEL_TABLE_ENTRIES)


def paged_blocks_walked(positions, block_size: int, n_rows: int,
                        blocks_per_row: int, window=None, *,
                        kernel: bool) -> int:
    """Blocks of one array the read of one layer copies in a decode step
    whose live rows are at ``positions`` (a host count, of Python ints).
    The kernel copies what each live row's length covers, from its
    window's first block where the layer has one. The loop gathers for
    every one of the program's ``n_rows`` rows, live or junk, a chunk of
    blocks a trip, as many trips as the longest row (or the widest
    window's span) needs."""
    bs = block_size
    if kernel:
        lo = [0 if window is None else max(p - window + 1, 0)
              for p in positions]
        return sum(-(-p // bs) - first // bs
                   for p, first in zip(positions, lo))
    chunk_blocks = max(1, min(blocks_per_row, PAGED_CHUNK_KEYS // bs))
    chunk_keys = chunk_blocks * bs
    if window is None:
        trips = -(-max(positions, default=0) // chunk_keys)
    else:
        trips = max((
            (p - 1) // chunk_keys - max(p - window + 1, 0) // chunk_keys + 1
            for p in positions if p > 0), default=0)
    return n_rows * trips * chunk_blocks


def _write_rows(pool, rows, w_block, w_off):
    """``rows`` (L, N, width) into ``(w_block[n], w_off[n])`` of every
    layer of a pool (L, P, bs, width) that keeps one vector a token: as
    L * N single rows of the pool with its layers flattened into its
    blocks. (One scatter whose window spans the layers, as the K/V pair
    is written, makes the layers a tiled dimension of this pool on a TPU:
    (5, 576) pads to (8, 640), and the program first copies the whole
    pool into that layout.)"""
    n_layers, n_phys = pool.shape[:2]
    flat = pool.reshape(n_layers * n_phys, *pool.shape[2:])
    blocks = (jnp.arange(n_layers) * n_phys)[:, None] + w_block[None, :]
    rows = to_width(rows, pool.shape[-1])
    return flat.at[blocks, w_off[None, :]].set(rows).reshape(pool.shape)


def paged_write(pk, pv, k_new, v_new, positions, tables):
    """The one write of a paged decode step: each row's new K/V (L, R,
    Hkv, Dh) into its (block, offset) in every layer. A pool of one
    array (``pv`` None) gets its one new row a token."""
    bs = pk.shape[2]
    w_block = jnp.take_along_axis(
        tables, (positions // bs)[:, None], axis=1
    )[:, 0]
    w_off = positions % bs
    if pv is None:
        return _write_rows(pk, k_new, w_block, w_off), None
    return (pk.at[:, w_block, w_off].set(k_new),
            pv.at[:, w_block, w_off].set(v_new))


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What a model that generates by blocks declares to the serving
    engine (``block_spec()`` of its serving protocol, optional): a decode
    step then carries ``length`` ids a row, ``mask_id`` where a position
    is still masked, and a block is denoised in at most ``steps``
    forwards under ``rule`` (:func:`rayfed_tpu.serving.sampling.unmask`).
    The forward that finds the carried block clean commits it AND is the
    first denoising forward of the block behind it: the model's
    ``decode_step`` forwards both and hands back the logits of the one
    that denoises."""

    length: int
    mask_id: int
    steps: int
    rule: str = "low_confidence_dynamic"
    threshold: float = 0.9

    def quota(self, step: int) -> int:
        """Positions a denoising step must unmask at least: ``length //
        steps``, the remainder given to the first steps."""
        return self.length // self.steps + (
            1 if step < self.length % self.steps else 0)


def paged_block_attention(pk, pv, positions, tables):
    """:func:`paged_attention` for a decode step whose rows forward a
    pair of blocks under the block-causal mask: the read of a block-paged
    K/V pool through the block tables. ``positions`` (R, 2): each row's
    first position, which is also the number of keys it has cached, and
    the position at which its SECOND block starts. Returns ``attend(q,
    kb, vb, base)``: ``q`` (R, n, H, Dh) a layer's queries, ``kb``/``vb``
    (R, c, Hkv, Dh) the row's own keys and values at positions
    ``positions[r, 0] .. + c - 1`` (in hand, beside the pool: they are
    written there only when a block commits, :func:`paged_block_write`),
    the queries at the LAST ``n`` of those places; ``base`` as in
    :func:`paged_attention`; the result is the attention output (R, n,
    H, Dh).

    The row's own keys are the online softmax's first block: a key of the
    second block is visible to the queries of the second block only, a
    key before it to every query, so the first block's queries see what
    they would see alone and the second's see both blocks. The cached
    keys, all of which every query of the row sees, follow
    ``PAGED_CHUNK_KEYS`` at a time as in :func:`paged_attention`, ONE
    walk for all of the row's queries, the trip count a runtime value. A
    junk row (position 0 under an all-zero table) reads block 0 masked."""
    n_layers, n_phys, bs, n_kv, dh = pk.shape
    n_rows, blocks_per_row = tables.shape
    # Own place at which each row's second block starts.
    second = (positions[:, 1] - positions[:, 0])[:, None, None]
    positions = positions[:, 0]
    chunk_blocks = max(1, min(blocks_per_row, PAGED_CHUNK_KEYS // bs))
    chunk_keys = chunk_blocks * bs
    tables_p = jnp.pad(
        tables, ((0, 0), (0, -blocks_per_row % chunk_blocks))
    )
    trips = (jnp.max(positions) + chunk_keys - 1) // chunk_keys
    pk_flat = pk.reshape(n_layers * n_phys, bs, n_kv, dh)
    pv_flat = pv.reshape(n_layers * n_phys, bs, n_kv, dh)
    scale = dh**-0.5

    def attend(q, kb, vb, base):
        n_q, n_heads, n_own = q.shape[1], q.shape[2], kb.shape[1]
        q = q.reshape(n_rows, n_q, n_kv, n_heads // n_kv, dh)
        s = jnp.einsum(
            "rbhgd,rchd->rhgbc", q, kb, preferred_element_type=jnp.float32
        ) * scale
        at = (n_own - n_q + jnp.arange(n_q))[None, :, None]
        own = (jnp.arange(n_own)[None, None, :] < second) | (at >= second)
        s = jnp.where(own[:, None, None], s, -jnp.inf)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        init = (m, p.sum(-1), jnp.einsum(
            "rhgbc,rchd->rhgbd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        ))

        def chunk(c, carry):
            m, den, acc = carry
            blocks = base + jax.lax.dynamic_slice_in_dim(
                tables_p, c * chunk_blocks, chunk_blocks, axis=1
            )
            kc = pk_flat[blocks].reshape(n_rows, chunk_keys, n_kv, dh)
            vc = pv_flat[blocks].reshape(n_rows, chunk_keys, n_kv, dh)
            k_pos = c * chunk_keys + jnp.arange(chunk_keys)
            cached = k_pos[None, :] < positions[:, None]
            s = jnp.einsum(
                "rbhgd,rkhd->rhgbk", q, kc,
                preferred_element_type=jnp.float32
            ) * scale
            s = jnp.where(cached[:, None, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            den = den * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "rhgbk,rkhd->rhgbd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return m_new, den, acc

        _, den, acc = jax.lax.fori_loop(0, trips, chunk, init)
        out = (acc / den[..., None]).astype(vb.dtype)
        return jnp.moveaxis(out, 3, 1).reshape(n_rows, n_q, n_heads, dh)

    return attend


def paged_block_write(pk, pv, k_new, v_new, positions, tables, commit):
    """The one write of a decode step that carries a block a row: the
    block's K/V (L, R, B, Hkv, Dh) into positions ``positions[r] .. + B -
    1`` of every layer, for the rows whose block commits (``commit`` (R,)
    bool); every other row's go to the sacrificial block 0. ``B`` divides
    the pool's block size and a block starts at a multiple of ``B``, so a
    row's ``B`` positions lie in one block of the pool."""
    bs = pk.shape[2]
    w_block = jnp.take_along_axis(
        tables, (positions // bs)[:, None], axis=1
    )
    w_block = jnp.where(commit[:, None], w_block, 0)
    w_off = (positions % bs)[:, None] + jnp.arange(k_new.shape[2])
    return (pk.at[:, w_block, w_off].set(k_new),
            pv.at[:, w_block, w_off].set(v_new))


# Keys gathered per trip of a prompt chunk's loop over its cached context
# (:func:`paged_chunk_attention`). A trip scores all of the chunk's
# queries against this many keys, so its float32 scores are C x H x this
# wide, and the context is read rounded up to it. On a v5e (PR 32,
# PERF.md; host clock around one call) the dense chunk program (256
# queries x 16 heads, 24 layers) read 8.4 / 9.1 / 10.0 ms at contexts of
# 188 / 700 / 1,468 keys with 256, 8.6 / 9.0 / 9.6 with 512 and 8.9 /
# 9.0 / 9.8 with 1,024: within 0.4 ms of one another. At 512 queries x
# 128 heads over 8 K/V heads (four layers' attention alone, three of
# them under a 4,096 window) contexts of 2,048 / 6,144 / 11,776 keys
# read 7.0 / 12.4 / 15.9 ms with 256, 12.5 / 24.6 / 31.2 with 512 and
# 13.2 / 26.1 / 37.5 with 1,024: there a trip's scores are 67 MB at 256
# and 134 MB at 512, and a key costs twice as much in the wider trip.
# Since PR 45 the pools whose slots reach ``CHUNK_KERNEL_REACH`` keys and
# more read a trip as a kernel on a TPU (below); this loop is still the
# read of the pools that reach less (at most eight trips of 16-32 heads:
# 4-8 MB of scores a trip), of a chunk under a ``block`` mask, and of
# every pool on every other backend.
CHUNK_TRIP_KEYS = 256
# The same trip where it is one Pallas kernel
# (:mod:`rayfed_tpu.ops.paged_chunk_attention`): scores, mask and softmax
# stay in fast memory, the gathered (a latent pool's EXPANDED) keys and
# values cross memory once each way and the softmax's state (2 x 33 MB at
# 128 heads) once a trip. On a v5e (PR 45, PERF.md section 6: one layer's
# read of 512 queries x 128 heads over a latent pool with a selection, a
# context of 2k / 8k / 32k) trips of 1,024 keys read 2.6 / 6.4 / 20.7 ms
# where the loop above reads 2.7 / 7.4 / 26.1; an earlier form of the
# kernel read a key slower in trips of 2,048 and 4,096, and slower still
# where a trip was walked in turns of 256 or 512 keys. And the reach from
# which a pool's chunks read that way. No context was found at which the
# kernel reads slower than the loop (2k to 32k, every form); what the
# constant weighs is a start: a Pallas call costs every chunk program
# about 0.05 s of trace and lowering a shape of trip, the short pools
# (at most 2,048 keys a slot, eight trips of the loop) start in 17-21 s
# under a bound of a tenth, and with the loop they compile the programs
# they compiled before. The three pools past it reach 11,264 keys and
# more.
CHUNK_KERNEL_TRIP_KEYS = 1024
CHUNK_KERNEL_REACH = 8192


def paged_chunk_attention(pk, pv, table, offset, n_real, window=None,
                          block=None):
    """The read of a block-paged K/V pool through one slot's block table,
    for one chunk of its prompt. Returns ``attend(q, k, v, base)``: ``q``
    (C, H, Dh) a layer's queries at positions ``offset .. offset + C - 1``,
    ``k``/``v`` (C, Hkv, Dh) the chunk's own keys and values (in hand:
    they are not read back from the pool), ``base`` the layer's first row
    of the flattened pool (layer * P), as in :func:`paged_attention`; the
    result is the attention output (C, H, Dh). K/V head i serves query
    heads i*G..(i+1)*G-1.

    The chunk's own C keys are the online softmax's first block, under
    the causal mask inside the chunk. The first ``n_real`` positions are
    the prompt's, the rest padding: a padded query attends the real keys
    and itself (so no softmax is ever empty and no NaN can reach the
    pool); nobody reads it. The cached keys ``[lo, offset)`` are then
    gathered through ``table`` ``CHUNK_TRIP_KEYS`` at a time, and the
    trip count is a runtime value: a chunk costs what its context costs,
    not what the row's length would. The mathematics is
    :func:`transformer.causal_attention`'s (operands in the compute
    dtype, float32 scores, max, sum and accumulator, probabilities cast
    to the value dtype before the PV product), re-associated, nothing
    rounded lower.

    ``window`` (a static int, or None: every key) makes this the read of
    a sliding-window layer: the query at ``p`` attends ``p - window + 1
    .. p``, ``lo`` is the first key the chunk's first query sees, rounded
    down to a trip, and keys before a query's window are masked.

    ``attend``'s ``seen`` (C, W) bool, where given, is a SELECTION: query
    ``i`` attends key position ``s`` only where ``seen[i, s]`` (and the
    masks above allow it): the keys a learned indexer kept
    (:func:`select_mask` of :func:`paged_chunk_index_scores`), each query
    its own set. The trips are those that exist; a trip's part of the
    selection masks its scores. A query's own key need not be among its
    keys, so the softmax is then kept finite where a block holds none of
    them (the maximum starts at minus infinity and the sum at 0), and
    every query must be given at least one key.

    A pool that keeps one latent array a token (``pv`` None, ``pk`` (L,
    P, bs, width)) is read in the expanded form: ``attend`` takes a fifth
    argument, ``expand``,
    the layer's map from a trip's cached rows (trip keys, 1, width as
    allocated: the model's own width and zero padding) to
    the keys and values the queries attend ((trip keys, Hk, Dh), (trip
    keys, Hk, Dv)); heads, head sizes and the scores' scale are then
    those of ``q``, ``k`` and ``v`` as handed over, not the pool's.

    On a TPU backend, for a pool whose slots reach far
    (:func:`paged_chunk_is_kernel`), the same ``attend`` runs each trip's
    scores, mask, softmax and PV product as one Pallas kernel
    (:func:`_paged_chunk_kernel_attention`, ``paged_chunk_read`` in a
    device trace), and the loop below is the definition it is tested
    against.
    """
    (blocks_per_row,) = table.shape
    if paged_chunk_is_kernel(pk, pv, blocks_per_row, block=block):
        return _paged_chunk_kernel_attention(
            pk, pv, table, offset, n_real, window)
    n_layers, n_phys, bs, n_kv, dh = _pool_dims(pk)
    trip_blocks = max(1, min(blocks_per_row, CHUNK_TRIP_KEYS // bs))
    trip_keys = trip_blocks * bs
    table_p = jnp.pad(table, (0, -blocks_per_row % trip_blocks))
    first = 0
    if window is not None:
        first = jnp.maximum(offset - window + 1, 0) // trip_keys
    last = (offset + trip_keys - 1) // trip_keys
    pk_flat = pk.reshape(n_layers * n_phys, *pk.shape[2:])
    if pv is not None:
        pv_flat = pv.reshape(n_layers * n_phys, bs, n_kv, dh)

    def attend(q, k, v, base, expand=None, seen=None):
        c, n_heads, d_qk = q.shape
        n_k = k.shape[1]
        scale = d_qk**-0.5
        q = q.reshape(c, n_k, n_heads // n_k, d_qk)
        idx = jnp.arange(c)
        q_pos = offset + idx
        if seen is not None:
            # As wide as the trips reach and the chunk's own keys lie.
            reach = max(table_p.shape[0] * bs, blocks_per_row * bs + c)
            seen = jnp.pad(
                seen, ((0, 0), (0, max(0, reach - seen.shape[1]))))

        def finite(m):
            # The maximum a softmax is shifted by: 0 where no key was seen
            # yet (a selection only; else it is always finite).
            return m if seen is None else jnp.where(jnp.isfinite(m), m, 0.0)

        def scores(keys, seen):
            s = jnp.einsum(
                "qhgd,khd->hgqk", q, keys, preferred_element_type=jnp.float32
            ) * scale
            return jnp.where(seen[None, None], s, -jnp.inf)

        s = scores(k, _own_keys_seen(c, offset, n_real, window, block, seen))
        m = s.max(-1)
        p = jnp.exp(s - finite(m)[..., None])
        init = (m, p.sum(-1), jnp.einsum(
            "hgqk,khd->hgqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ))

        def trip(t, carry):
            m, den, acc = carry
            blocks = base + jax.lax.dynamic_slice_in_dim(
                table_p, t * trip_blocks, trip_blocks)
            kc = pk_flat[blocks].reshape(trip_keys, n_kv, dh)
            if pv is None:
                kc, vc = expand(kc)
            else:
                vc = pv_flat[blocks].reshape(trip_keys, n_kv, dh)
            k_pos = t * trip_keys + jnp.arange(trip_keys)
            cached = (k_pos < offset)[None, :]
            if window is not None:
                cached &= k_pos[None, :] > q_pos[:, None] - window
            if seen is not None:
                cached &= jax.lax.dynamic_slice(
                    seen, (0, t * trip_keys), (c, trip_keys))
            s = scores(kc, cached)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - finite(m_new))
            p = jnp.exp(s - finite(m_new)[..., None])
            den = den * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "hgqk,khd->hgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return m_new, den, acc

        _, den, acc = jax.lax.fori_loop(first, last, trip, init)
        out = (acc / den[..., None]).astype(v.dtype)
        return jnp.moveaxis(out, 2, 0).reshape(c, n_heads, v.shape[-1])

    return attend


def _own_keys_seen(c: int, offset, n_real, window, block, seen):
    """(C, C) bool: which of a chunk's own keys each of its queries
    attends. Causal inside the chunk (up to its block's end under
    ``block``); a padded query attends the real keys and itself; a window
    and a selection ``seen`` (C, W) cut further, but a padded query keeps
    itself whatever its junk scores chose: no softmax is empty."""
    idx = jnp.arange(c)
    itself = idx[None, :] == idx[:, None]
    upto = idx if block is None else idx | (block - 1)
    own = (idx[None, :] <= upto[:, None]) & ((idx < n_real)[None, :] | itself)
    if window is not None:
        own &= idx[None, :] > idx[:, None] - window
    if seen is not None:
        own &= jax.lax.dynamic_slice(seen, (0, offset), (c, c)) | (
            (idx >= n_real)[:, None] & itself)
    return own


def paged_chunk_is_kernel(pk, pv, blocks_per_row: int, block=None) -> bool:
    """Whether :func:`paged_chunk_attention` reads this pool's trips
    through the Pallas kernel: on a TPU backend, a pool of a dtype the
    kernel takes whose slots reach ``CHUNK_KERNEL_REACH`` keys and more
    (``blocks_per_row`` blocks of the pool's size: static in every chunk
    program), and no ``block`` mask (the one model that asks for it
    reaches 1,024 keys). Nothing else chooses: no serving key, no
    environment variable, no model's name."""
    del pv
    return (block is None and utils.is_tpu_backend()
            and pk.dtype in (jnp.bfloat16, jnp.float32)
            and blocks_per_row * _pool_dims(pk)[2] >= CHUNK_KERNEL_REACH)


def _paged_chunk_kernel_attention(pk, pv, table, offset, n_real, window):
    """:func:`paged_chunk_attention`'s ``attend`` with each trip as one
    call of :func:`rayfed_tpu.ops.paged_chunk_attention.chunk_trip`. What
    stays here: the runtime trip count, the gather of a trip's blocks
    through the table, ``expand``, and a trip's mask, every form's in one
    int8 tile (queries, keys). The chunk's own keys are the first trip.
    The cached keys follow from the first block any query sees (block 0,
    or the block that holds the first query's window's first key: a
    window's trips start there, not at a multiple of a trip), in trips
    sized from what a chunk may have to visit
    (:func:`~rayfed_tpu.ops.paged_chunk_attention.trip_keys`)."""
    # The import is the engine's, begun on a thread of its own when the
    # server was made: by now it is done, or this waits for it.
    from rayfed_tpu.ops import paged_chunk_attention as kernel

    n_layers, n_phys, bs, n_kv, dh = _pool_dims(pk)
    (blocks_per_row,) = table.shape
    reach = blocks_per_row * bs
    span = reach if window is None else min(reach, window + bs - 2)
    trip_keys = kernel.trip_keys(bs, span, CHUNK_KERNEL_TRIP_KEYS)
    trip_blocks = trip_keys // bs
    # (A slice of a trip's blocks never runs off the table's end.)
    table_p = jnp.pad(table, (0, trip_blocks))
    lo = 0
    if window is not None:
        lo = jnp.maximum(offset - window + 1, 0) // bs * bs
    trips = (offset - lo + trip_keys - 1) // trip_keys
    pk_flat = pk.reshape(n_layers * n_phys, *pk.shape[2:])
    if pv is not None:
        pv_flat = pv.reshape(n_layers * n_phys, bs, n_kv, dh)

    def attend(q, k, v, base, expand=None, seen=None):
        c, n_heads, d_qk = q.shape
        n_k = k.shape[1]
        q = jnp.moveaxis(q.reshape(c, n_k, n_heads // n_k, d_qk), 0, 2)
        q_pos = offset + jnp.arange(c)
        if seen is not None:
            # As wide as the chunk's own keys lie and a trip may reach.
            wide = reach + max(c, trip_keys)
            seen = jnp.pad(
                seen, ((0, 0), (0, max(0, wide - seen.shape[1]))))

        def read(keys, values, mask, state):
            # (Operands of one dtype, as the loop's product promotes.)
            op = jnp.promote_types(q.dtype, keys.dtype)
            return kernel.chunk_trip(
                q.astype(op), jnp.swapaxes(keys, 0, 1).astype(op),
                jnp.swapaxes(values, 0, 1), mask.astype(jnp.int8), state,
                scale=d_qk**-0.5)

        def trip(t, state):
            start = lo + t * trip_keys
            blocks = base + jax.lax.dynamic_slice_in_dim(
                table_p, start // bs, trip_blocks)
            kc = pk_flat[blocks].reshape(trip_keys, n_kv, dh)
            if pv is None:
                kc, vc = expand(kc)
            else:
                vc = pv_flat[blocks].reshape(trip_keys, n_kv, dh)
            k_pos = start + jnp.arange(trip_keys)
            cached = jnp.broadcast_to(
                (k_pos < offset)[None, :], (c, trip_keys))
            if window is not None:
                cached &= k_pos[None, :] > q_pos[:, None] - window
            if seen is not None:
                cached &= jax.lax.dynamic_slice(
                    seen, (0, start), (c, trip_keys))
            return read(kc, vc, cached, state)

        own = _own_keys_seen(c, offset, n_real, window, None, seen)
        out = kernel.chunk_output(jax.lax.fori_loop(
            0, trips, trip, read(k, v, own, None)), v.dtype)
        out = out.reshape(n_k, n_heads // n_k, c, v.shape[-1])
        return jnp.moveaxis(out, 2, 0).reshape(c, n_heads, v.shape[-1])

    return attend


# ---------------------------------------------------------------------------
# A learned selection of keys (an indexer's cache beside the rows it picks)
# ---------------------------------------------------------------------------


def index_scores(qi, w, keys):
    """An indexer's scores ``I[q, s] = sum_j w[q, j] relu(qi[q, j] .
    keys[s])``, float32: ``qi`` (Q, J, D) the queries' index heads, ``w``
    (Q, J) float32 their weights, ``keys`` (K, D) one index key a token,
    or (Q, K, D) where each query has keys of its own (a decode step's
    rows). Products take the operands as they come and accumulate in
    float32."""
    eq = "qjd,qkd->qjk" if keys.ndim == 3 else "qjd,kd->qjk"
    s = jnp.einsum(eq, qi, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("qjk,qj->qk", jax.nn.relu(s), w)


def select_mask(scores, valid, k: int):
    """The selection itself: per row of ``scores`` (Q, W) float32 the
    ``min(k, valid keys)`` positions of largest score among those
    ``valid`` (Q, W) bool names, the lower position at a tie, as a (Q, W)
    bool. EXACT: the set is the definition of the model's attention, not
    an approximation of it.

    No sort: the ``k``-th largest score of a row is found bit by bit (a
    float's bits, the sign flipped, order as the float does; 32 counts of
    ``scores >= candidate`` over the row), then every score above it is
    kept and of those equal to it the lowest positions that fill the
    ``k``. A sort of (512, 33k) scores costs a chunk more than the rest
    of its layer (``PERF.md`` section 6, PR 44)."""
    u = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
    u = jnp.where(valid, u, jnp.uint32(0))
    need = jnp.minimum(k, jnp.sum(valid, -1, dtype=jnp.int32))

    def bit(i, th):
        cand = th | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        count = jnp.sum(u >= cand[:, None], -1, dtype=jnp.int32)
        return jnp.where(count >= need, cand, th)

    # The largest threshold that at least ``need`` keys reach: the
    # need-th largest score (all ones where a row needs none).
    th = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))
    above = u > th[:, None]
    equal = (u == th[:, None]) & valid
    room = need - jnp.sum(above, -1, dtype=jnp.int32)
    n_equal = jnp.sum(equal, -1, dtype=jnp.int32)
    # Nearly always the threshold is one key's score: only a tie that
    # does not fit is ranked by position.
    equal = jax.lax.cond(
        jnp.all(n_equal == room), lambda: equal,
        lambda: equal & (jnp.cumsum(equal, -1, dtype=jnp.int32)
                         <= room[:, None]))
    return above | equal


def _index_pool(pi, blocks_per_row: int, trip_keys: int):
    """(flat pool, block size, key width, blocks a trip, keys a trip) of
    an array of index keys (L_a, P, bs, D) read ``trip_keys`` at a time."""
    n_layers, n_phys, bs, _, dh = _pool_dims(pi)
    trip_blocks = max(1, min(blocks_per_row, trip_keys // bs))
    return (pi.reshape(n_layers * n_phys, bs, dh), bs, dh, trip_blocks,
            trip_blocks * bs)


def paged_index_scores(pi, positions, tables):
    """An indexer's read of ITS array of the pool (``pi`` (L_a, P, bs,
    D): one index key a token on each indexed layer) through the block
    tables, for one decode token a row. Returns ``score(qi, w, k1,
    base)``: ``qi`` (R, J, D) a layer's index queries, ``w`` (R, J) their
    weights, ``k1`` (R, D) the new token's own index key (in hand, not
    yet in the pool), ``base`` the layer's first row of the flattened
    array (its ordinal among the indexed layers times P); the result is
    ``(scores (R, W) float32, valid (R, W) bool)``, column ``s`` the key
    at position ``s``, valid up to the row's own position.
    ``PAGED_CHUNK_KEYS`` keys are gathered a trip, and the trips are a
    runtime count (the longest row's): a junk row (position 0) scores
    its own key and nothing else."""
    n_rows, blocks_per_row = tables.shape
    pi_flat, bs, dh, chunk_blocks, chunk_keys = _index_pool(
        pi, blocks_per_row, PAGED_CHUNK_KEYS)
    tables_p = jnp.pad(
        tables, ((0, 0), (0, -blocks_per_row % chunk_blocks)))
    width = tables_p.shape[1] * bs
    trips = (jnp.max(positions) + chunk_keys - 1) // chunk_keys
    col = jnp.arange(width)

    def score(qi, w, k1, base):
        # (The pool's rows may be padded to whole tiles, with zeros.)
        qi, k1 = to_width(qi, dh), to_width(k1, dh)

        def chunk(c, buf):
            blocks = base + jax.lax.dynamic_slice_in_dim(
                tables_p, c * chunk_blocks, chunk_blocks, axis=1)
            kc = pi_flat[blocks].reshape(n_rows, chunk_keys, dh)
            return jax.lax.dynamic_update_slice(
                buf, index_scores(qi, w, kc), (0, c * chunk_keys))

        buf = jax.lax.fori_loop(
            0, trips, chunk, jnp.zeros((n_rows, width), jnp.float32))
        own = index_scores(qi, w, k1[:, None].astype(pi.dtype))
        buf = jnp.where(col[None] == positions[:, None], own, buf)
        return buf, col[None] <= positions[:, None]

    return score


def paged_selected_attention(pc, positions, tables, k: int, *, scale,
                             v_width):
    """The read of a pool of latent rows (``pc`` (L_a, P, bs, width))
    over a SELECTION, for one decode token a row: of each row's keys the
    ``min(k, position + 1)`` of largest index score. Returns ``attend(q,
    c1, scores, valid, base)``: ``q`` (R, H, <= width) the queries in the
    space of the cached row, ``c1`` (R, 1, <= width) the new token's own
    row (in hand), ``scores``/``valid`` (R, W) of
    :func:`paged_index_scores`, ``base`` the layer's first row of the
    flattened array; the result is (R, H, v_width): the softmax over the
    selected rows only, a key's value its own first ``v_width`` columns.

    The top-``k`` is ``jax.lax.top_k`` (exact; equal scores in order of
    position) over each row's valid scores; the chosen rows are read one
    gather through the tables ((R, k) single rows: what a step reads of
    this array does not grow with the context past ``k``), the row's own
    among them taken from ``c1``. A junk row (position 0) attends its
    own key."""
    n_layers, n_phys, bs, _, width = _pool_dims(pc)
    pc_flat = pc.reshape(n_layers * n_phys, bs, width)
    n_sel = min(k, tables.shape[1] * bs)

    def attend(q, c1, scores, valid, base):
        top, sel = jax.lax.top_k(
            jnp.where(valid, scores, -jnp.inf)[:, :tables.shape[1] * bs],
            n_sel)
        kept = top > -jnp.inf
        blocks = base + jnp.take_along_axis(tables, sel // bs, axis=1)
        rows = pc_flat[blocks, sel % bs]                 # (R, n_sel, width)
        own = (sel == positions[:, None])[..., None]
        rows = jnp.where(own, to_width(c1, width).astype(pc.dtype), rows)
        s = jnp.einsum("rhd,rkd->rhk", to_width(q, width), rows,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("rhk,rkd->rhd", p.astype(rows.dtype),
                         rows[..., :v_width],
                         preferred_element_type=jnp.float32)
        return out.astype(c1.dtype)

    return attend


# Index keys scored a trip of a prompt chunk's indexer: a trip's float32
# products are C x J x this wide (512 x 64 x 1,024: 134 MB) before the
# heads are summed.
INDEX_TRIP_KEYS = 1024


def paged_chunk_index_scores(pi, table, offset):
    """:func:`paged_index_scores` for one chunk of one slot's prompt:
    ``score(qi, w, k_own, base)`` with ``qi`` (C, J, D), ``w`` (C, J) the
    chunk's index queries at positions ``offset .. offset + C - 1`` and
    ``k_own`` (C, D) its own index keys (in hand); the cached keys ``[0,
    offset)`` are gathered through ``table`` ``INDEX_TRIP_KEYS`` at a
    time, a runtime count of trips. Returns ``(scores (C, W), valid (C,
    W))``: column ``s`` the key at position ``s``, valid up to each
    query's own position: :func:`select_mask`'s arguments."""
    (blocks_per_row,) = table.shape
    pi_flat, bs, dh, trip_blocks, trip_keys = _index_pool(
        pi, blocks_per_row, INDEX_TRIP_KEYS)
    table_p = jnp.pad(table, (0, -blocks_per_row % trip_blocks))

    def score(qi, w, k_own, base):
        c = qi.shape[0]
        width = table_p.shape[0] * bs + c
        qi, k_own = to_width(qi, dh), to_width(k_own, dh)

        def trip(t, buf):
            blocks = base + jax.lax.dynamic_slice_in_dim(
                table_p, t * trip_blocks, trip_blocks)
            kc = pi_flat[blocks].reshape(trip_keys, dh)
            return jax.lax.dynamic_update_slice(
                buf, index_scores(qi, w, kc), (0, t * trip_keys))

        buf = jax.lax.fori_loop(
            0, (offset + trip_keys - 1) // trip_keys, trip,
            jnp.zeros((c, width), jnp.float32))
        # The chunk's own keys last: a trip's tail past ``offset`` held
        # whatever the blocks did.
        buf = jax.lax.dynamic_update_slice(
            buf, index_scores(qi, w, k_own.astype(pi.dtype)), (0, offset))
        valid = jnp.arange(width)[None] <= (offset + jnp.arange(c))[:, None]
        return buf, valid

    return score


def paged_chunk_write(pk, pv, k_new, v_new, table, offset):
    """The one write of a prompt chunk: the stack's new K/V (L, C, Hkv,
    Dh) into ``(table[p // bs], p % bs)`` for ``p = offset .. offset + C -
    1`` in every layer. A position whose block is not granted (padding
    past the prompt's granted blocks: the table holds 0 there) falls into
    the sacrificial block."""
    bs = pk.shape[2]
    pos = offset + jnp.arange(k_new.shape[1])
    w_block = table[pos // bs]
    w_off = pos % bs
    if pv is None:
        return _write_rows(pk, k_new, w_block, w_off), None
    return (pk.at[:, w_block, w_off].set(k_new),
            pv.at[:, w_block, w_off].set(v_new))


def paged_decode_step(
    params, pk, pv, tokens, positions, tables, cfg: tfm.TransformerConfig
):
    """One decode token for every row of a block-paged K/V pool, reading
    K/V through the block tables and writing the new token's K/V in place.

    ``pk``/``pv`` (L, P, bs, H, Dh) are the pool's physical blocks (donate
    them: they come back updated); ``tokens``/``positions`` (R,) int32 are
    each row's input token and its position (= the number of keys it has
    cached); ``tables`` (R, NB) int32 maps a row's logical block to a
    physical one. Returns (logits (R, vocab) f32, pk, pv).

    No contiguous per-row cache exists at any point: the layer scan reads
    the pool as a loop invariant and each layer attends through the table
    (:func:`paged_attention`).

    A junk row (position 0 under an all-zero table) visits no block and
    writes into block 0, which no real query attends.
    """
    n_phys = pk.shape[1]
    attend = paged_attention(pk, pv, positions, tables)
    x = params["embed"][tokens][:, None].astype(cfg.compute_dtype)

    def body(carry, layer):
        x, i = carry
        q, k, v = tfm.qkv_proj(x, layer, positions[:, None], cfg)
        k1 = k[:, 0].astype(pk.dtype)
        v1 = v[:, 0].astype(pv.dtype)
        o = attend(q[:, 0], k1, v1, i * n_phys)
        return (_block_tail(x, o[:, None], layer, cfg), i + 1), (k1, v1)

    (x, _), (k_new, v_new) = jax.lax.scan(
        body, (x, jnp.asarray(0, jnp.int32)), params["layers"]
    )
    logits = _head(x[:, 0], params, cfg)
    pk, pv = paged_write(pk, pv, k_new, v_new, positions, tables)
    return logits, pk, pv


def landed_rows(one_row, landed, zeros):
    """The loop of a ``prefill_rows`` that computes only the rows that
    are requests. ``one_row(i)`` is row ``i`` of the round: its logits
    and its row of every other output; ``landed`` (R,) bool names the
    rows to compute (None: all); ``zeros`` are the outputs at zero, the
    logits ``(R, ...)`` and every other ``(layers, R, ...)``.

    The landed rows run first, in slot order, one at a time under a trip
    count that is a runtime value: an admission round costs what its
    requests cost, not what ``R`` rows of the bucket would (at 32 slots a
    round is mostly one request), and a row is the same one-row program
    whoever its neighbours are. A row's logits land at ``[i]``, its other
    outputs at ``[:, i]`` (from the start of the later axes where it is
    shorter than they: a row as long as its bucket in an output as long
    as the cache's rows). The other rows stay zero: the pool scatters
    them into the sacrificial block and a state of theirs lands
    nowhere."""
    zeros = tuple(zeros)
    if landed is None:
        landed = jnp.ones((zeros[0].shape[0],), bool)
    order = jnp.argsort(jnp.logical_not(landed), stable=True)

    def step(j, out):
        i = order[j]
        logits, *rows = one_row(i)
        return (jax.lax.dynamic_update_index_in_dim(out[0], logits, i, 0),
                *(jax.lax.dynamic_update_index_in_dim(o, new, i, 1)
                  for o, new in zip(out[1:], rows)))

    return jax.lax.fori_loop(
        0, jnp.sum(landed, dtype=jnp.int32), step, zeros)


class TransformerServing:
    """What the serving engine asks of a model: the protocol, with the
    dense transformer as its first implementer.

    The engine (:mod:`rayfed_tpu.serving.server`) and its pool resolve a
    model's config to such an object through ``serving_model(cfg)`` of
    the module that defines the config's class. It tells the pool what
    to hold and gives the engine its programs.

    ``kv_spec()`` declares what a token keeps in the paged pool: one
    ``(layers, shape)`` pair per array, ``shape`` a token's row in that
    array and ``layers`` how many of the model's layers keep such a row
    (the layers OF THAT ARRAY, not the model's depth: a program indexes
    an array by a layer's ordinal among the layers that keep it). This
    model (and the hybrid and the expert models) declares a key and a
    value array over all its layers, ``((L, (H, Dh)), (L, (H, Dh)))``; a
    model with latent attention declares one vector, ``((L, (width,)),)``
    (:mod:`rayfed_tpu.models.pangu_ultra_moe`); one whose layer kinds
    keep different things declares an array per thing, each as deep as
    the layers of its kind (:mod:`rayfed_tpu.models.dots3_note`: a
    latent row and an index key on its indexed layers, a wider latent
    row on its windowed ones). The pool allocates one
    ``(layers, 1 + blocks, block, *shape)`` array per entry (a lone
    vector padded to whole tiles: ``kv_pool.LANES``) and hands the
    programs the tuple of them, ``kv``, donated; they hand it back in the
    same order. Nothing else is assumed of it: how a token's row is read
    is the model's.

    ``prefill_rows`` (a round of right-padded short prompts, one row
    each) returns the logits at each row's last position, the rows of
    the cache ``(layers of the array, R, S, *shape)`` per declared
    array, and the rows' state; ``chunk`` (one chunk of a long prompt: its context read from
    the pool through the slot's block table, its own rows written there
    in place) and ``decode_step`` (one token for every row, the cache
    read through the block tables) take and return ``kv``.
    ``state_spec`` is what a slot holds beside its paged rows, ``name ->
    (layers that keep it, shape, dtype)``: the pool allocates one
    ``(layers, slots, *shape)`` array per entry, each as deep as the
    layers that keep it, as the arrays of ``kv_spec`` are (every layer in
    :mod:`rayfed_tpu.models.falcon_h1`; the linear-attention layers alone
    in :mod:`rayfed_tpu.models.olmo_hybrid`, whose full layers alone keep
    K/V), and a program indexes it by a layer's ordinal among those; a
    model that has none (this one) returns ``{}``, takes and
    returns ``{}`` wherever a program hands state on, and ignores
    ``live``, ``n_real`` and what else exists for the sake of a state.
    ``serving_dtype`` is the dtype in which the programs read the
    floating leaves of a published tree, or None for "as published": the
    engine's bank casts a version once, when it is installed, and the
    programs are handed that tree (``InferenceServer._make_snapshot_fn``).

    Four further members are optional, declared by the model that needs
    them and absent here: ``layer_windows()`` (per layer the keys a token
    attends, None for every key, 0 for a layer that attends none, as a
    linear-attention layer: the engine counts the blocks each layer must
    read, over the layers that attend) and ``step_counters`` (names of int32 counts only
    the device knows: ``decode_step`` then returns them as a fourth value
    and they ride home behind the ids), both first declared by
    :mod:`rayfed_tpu.models.cohere2_moe`; ``layer_index_topk()`` (per
    indexed layer the keys its learned indexer keeps: the engine counts
    the pairs scored and kept, :mod:`rayfed_tpu.models.dots3_note`); and
    ``block_spec()`` (a :class:`BlockSpec`: the model generates by
    blocks, a row of a decode step carries a block of ids and
    ``decode_step`` is also told which rows commit,
    :mod:`rayfed_tpu.models.sdar_moe`). ``prefill_rows`` may hand back
    rows shorter than ``row_len`` (as long as its bucket): the pool lands
    rows of the length they come in.
    """

    def __init__(self, cfg: tfm.TransformerConfig):
        self.cfg = cfg

    def kv_spec(self):
        """A (layers of the array, per-token shape) pair per array the
        pool holds: a key and a value row of (heads, head size), both
        over every layer."""
        head = (self.cfg.n_heads, self.cfg.head_dim)
        return (self.cfg.n_layers, head), (self.cfg.n_layers, head)

    def state_spec(self, cache_dtype=None):
        return {}

    def serving_dtype(self):
        """Every leaf is used only through ``.astype(compute_dtype)``
        (the embedding after its gather, the norms' scales, the ``moe``
        subtree), so the cast made once gives every program the bits it
        computed from the wider tree, and those casts become no-ops."""
        return self.cfg.compute_dtype

    def prefill_rows(self, params, prompts, last_idx, row_len, cache_dtype,
                     landed):
        """Prompts (R, S) from fresh zero rows: bit-identical logits to
        recycled ones (masked positions cannot contribute). Returns the
        logits at ``last_idx``, the K and V rows (L, R, row_len, H, Dh)
        and the rows' state. ``landed`` (R,) bool names the rows that are
        requests: only those are read or land anywhere, so what comes
        back for the others is unspecified. Here every lane is computed
        (ROADMAP S2b replaces this with the landed-rows loop)."""
        cfg = self.cfg

        def one_row(prompt_row, last_i, params):
            cache = init_cache(cfg, 1, row_len, cache_dtype)
            logits, cache = forward_with_cache(
                params, prompt_row[None], cache, 0, cfg
            )
            last = jax.lax.dynamic_index_in_dim(
                logits[0], last_i, axis=0, keepdims=False
            )
            return last, cache["k"][:, 0], cache["v"][:, 0]

        rows = jax.vmap(one_row, in_axes=(0, 0, None), out_axes=(0, 1, 1))
        last, k, v = rows(prompts, last_idx, params)
        return last, (k, v), {}

    def chunk(self, params, kv, state, table, slot, toks, offset, n_real):
        """One chunk ``toks`` (C,) of a long prompt, at positions ``offset
        .. offset + C - 1`` of the slot whose block table is ``table``:
        the first ``n_real`` are the prompt's, the rest padding. The
        chunk's context is read from the donated pool through the table
        (:func:`paged_chunk_attention`) and its own K/V written there in
        place, once, after the last layer (:func:`paged_chunk_write`).
        Returns the logits of the last real position (vocab,), the pool's
        arrays and the state (``slot`` names its row of a state; none
        here)."""
        cfg = self.cfg
        pk, pv = kv
        n_phys = pk.shape[1]
        attend = paged_chunk_attention(pk, pv, table, offset, n_real)
        positions = (offset + jnp.arange(toks.shape[0]))[None]
        x = params["embed"][toks][None].astype(cfg.compute_dtype)

        def body(carry, layer):
            x, i = carry
            q, k, v = tfm.qkv_proj(x, layer, positions, cfg)
            k, v = k[0].astype(pk.dtype), v[0].astype(pv.dtype)
            o = attend(q[0], k, v, i * n_phys)
            return (_block_tail(x, o[None], layer, cfg), i + 1), (k, v)

        (x, _), (k_new, v_new) = jax.lax.scan(
            body, (x, jnp.asarray(0, jnp.int32)), params["layers"]
        )
        # The head on the one position read, not on all C.
        last = jax.lax.dynamic_index_in_dim(
            x[0], n_real - 1, axis=0, keepdims=False
        )
        kv = paged_chunk_write(pk, pv, k_new, v_new, table, offset)
        return _head(last, params, cfg), kv, state

    def decode_step(self, params, kv, state, tokens, positions, tables,
                    live):
        logits, *kv = paged_decode_step(
            params, *kv, tokens, positions, tables, self.cfg
        )
        return logits, tuple(kv), state


def serving_model(cfg):
    """The serving engine's view of ``cfg``'s model: ``serving_model`` of
    the module that defines the config's class."""
    import sys

    return sys.modules[type(cfg).__module__].serving_model(cfg)


def prefill(params, prompt, cache: Cache, cfg: tfm.TransformerConfig):
    """Fill the cache from a (B, S) prompt; returns (last-position logits
    (B, vocab), cache)."""
    logits, cache = forward_with_cache(params, prompt, cache, 0, cfg)
    return logits[:, -1], cache


def _sharded_jit(fn, mesh: Mesh, party_axis, data_axis, n_extra_args: int,
                 n_param_trees: int = 1):
    """jit ``fn(*param_trees, prompt, *extras)`` with Megatron param
    shardings for each leading param tree and a party x data prompt
    sharding, keyed per tree structure/shapes/dtypes — a later call with
    a different tree (e.g. LoRA-merged vs base) gets its own
    in_shardings instead of reusing stale ones. Shared by the sharded
    generate / beam-search / speculative dispatchers so the keying
    scheme cannot drift between them."""
    from rayfed_tpu.parallel import sharding as shd

    prompt_sharding = NamedSharding(
        mesh, shd.batch_spec(mesh, party_axis, data_axis)
    )
    jitted_by_tree = {}

    def tree_key(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return (treedef, tuple((x.shape, x.dtype) for x in leaves))

    def dispatch(*args):
        trees, rest = args[:n_param_trees], args[n_param_trees:]
        key = tuple(tree_key(t) for t in trees)
        jitted = jitted_by_tree.get(key)
        if jitted is None:
            shardings = tuple(
                shd.make_param_shardings(mesh, t) for t in trees
            )
            jitted = jitted_by_tree[key] = jax.jit(
                fn,
                in_shardings=shardings + (prompt_sharding,)
                + (None,) * n_extra_args,
            )
        return jitted(*args)

    return dispatch


def make_generate_fn(
    cfg: tfm.TransformerConfig,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    jit: bool = True,
    mesh: Optional[Mesh] = None,
    party_axis: Optional[str] = "party",
    data_axis: Optional[str] = "data",
):
    """Build ``generate(params, prompt, rng=None) -> (B, S+max_new)``.

    Greedy when ``temperature == 0`` (rng unused), otherwise softmax
    sampling at the given temperature, optionally truncated to the
    ``top_k`` highest-probability tokens and/or the ``top_p`` nucleus
    (smallest set of tokens whose probability mass reaches ``top_p``).
    Lengths are static: the returned function compiles once per prompt
    shape. With ``eos_id``, a row that emits it keeps emitting EOS for
    the rest of the (static-length) generation — the output still has
    shape (B, S+max_new), terminated rows are EOS-padded.

    With ``mesh``, decoding runs sharded: params follow the Megatron tp
    rules (:mod:`rayfed_tpu.parallel.sharding`), the prompt/batch shards
    over party x data, and the K/V cache pins heads to the ``model`` axis
    via :func:`cache_spec` — per-step collectives are the same one
    all-reduce per block as the training forward.
    """
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if top_k is not None and not 1 <= top_k <= cfg.vocab:
        raise ValueError(f"top_k must be in [1, {cfg.vocab}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab:
        raise ValueError(f"eos_id must be in [0, {cfg.vocab}), got {eos_id}")
    if temperature <= 0.0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p truncate the sampling distribution; with "
            "temperature<=0 decoding is greedy and they would be silently "
            "ignored — set temperature > 0"
        )

    cache_sharding = None
    if mesh is not None:
        cache_sharding = NamedSharding(
            mesh, cache_spec(mesh, party_axis, data_axis, n_heads=cfg.n_heads)
        )

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_k is not None and top_k < cfg.vocab:
            kth = jnp.sort(logits, axis=-1)[..., -top_k, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p is not None and top_p < 1.0:
            desc = jnp.sort(logits, axis=-1)[..., ::-1]
            cum_excl = jnp.cumsum(
                jax.nn.softmax(desc, axis=-1), axis=-1
            ) - jax.nn.softmax(desc, axis=-1)
            # Nucleus = tokens whose exclusive cumulative mass is still
            # under top_p (always contains the argmax); mask the rest.
            thresh = jnp.min(
                jnp.where(cum_excl < top_p, desc, jnp.inf),
                axis=-1, keepdims=True,
            )
            logits = jnp.where(logits < thresh, -jnp.inf, logits)
        return jax.random.categorical(key, logits, axis=-1)

    def generate(params, prompt, rng: Optional[jax.Array] = None):
        b, s = prompt.shape
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # The cache only ever holds tokens that later tokens attend to, so
        # the final sampled token needs no slot (and no forward pass).
        cache = init_cache(cfg, b, s + max_new_tokens - 1)
        if cache_sharding is not None:
            cache = jax.tree_util.tree_map(
                lambda c: jax.lax.with_sharding_constraint(c, cache_sharding),
                cache,
            )
        last_logits, cache = prefill(params, prompt, cache, cfg)
        rng, sub = jax.random.split(rng)
        first = sample(last_logits, sub).astype(prompt.dtype)
        done0 = (
            first == eos_id if eos_id is not None
            else jnp.zeros(first.shape, bool)
        )

        def step(carry, _):
            tok, cache, pos, key, done = carry
            logits, cache = forward_with_cache(
                params, tok[:, None], cache, pos, cfg
            )
            key, sub = jax.random.split(key)
            nxt = sample(logits[:, -1], sub).astype(prompt.dtype)
            if eos_id is not None:
                nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt)
                done = done | (nxt == eos_id)
            return (nxt, cache, pos + 1, key, done), nxt

        _, toks = jax.lax.scan(
            step,
            (first, cache, jnp.asarray(s, jnp.int32), rng, done0),
            None,
            length=max_new_tokens - 1,
        )
        new = jnp.concatenate([first[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)
        return jnp.concatenate([prompt, new], axis=1)

    if not jit:
        return generate
    if mesh is None:
        return jax.jit(generate)

    dispatch = _sharded_jit(generate, mesh, party_axis, data_axis, 1)

    def sharded_generate(params, prompt, rng: Optional[jax.Array] = None):
        return dispatch(
            params, prompt, rng if rng is not None else jax.random.PRNGKey(0)
        )

    return sharded_generate


def make_beam_search_fn(
    cfg: tfm.TransformerConfig,
    *,
    max_new_tokens: int,
    n_beams: int,
    eos_id: Optional[int] = None,
    jit: bool = True,
    mesh: Optional[Mesh] = None,
    party_axis: Optional[str] = "party",
    data_axis: Optional[str] = "data",
):
    """Build ``beam_search(params, prompt) -> (seqs, scores)``.

    Beam search over ``max_new_tokens`` steps, returning ``seqs``
    (B, n_beams, S+max_new) and their total log-probabilities ``scores``
    (B, n_beams), best first. With ``eos_id`` set, a beam that emits it
    is FINISHED: its score freezes and its remaining slots pad with
    ``eos_id`` (the scored sequence is everything up to and including
    the first EOS) — the result is the exact top-K over the space of
    EOS-terminated-or-length-capped continuations when the beam is wide
    enough (pinned against enumeration in tests). Without ``eos_id``
    every beam decodes the full length.

    With ``mesh``, the search runs sharded exactly like
    :func:`make_generate_fn`: Megatron-tp params, the prompt batch over
    party x data, and the K/V cache pinned by :func:`cache_spec` (its
    batch dim is B*n_beams rows; beam reordering is a batched gather
    XLA turns into on-device collectives where rows cross shards).

    TPU-first shape: ONE compile for the whole search — the step body is
    a ``lax.scan`` whose carry holds the flattened (B*n_beams) decode
    rows; beam reordering is a batched gather over the K/V cache's batch
    dim (``jnp.take``), which XLA lowers to an on-device dynamic-gather
    with no host trips; all candidate expansion is a single
    (B, n_beams*vocab) ``top_k``. Prefill runs once at batch B and the
    cache is tiled to B*n_beams afterwards, so prompt compute is not
    duplicated per beam.
    """
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if n_beams < 1:
        raise ValueError("n_beams must be >= 1")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab:
        raise ValueError(f"eos_id must be in [0, {cfg.vocab}), got {eos_id}")
    k_beams = n_beams
    vocab = cfg.vocab
    cache_sharding = None
    if mesh is not None:
        cache_sharding = NamedSharding(
            mesh, cache_spec(mesh, party_axis, data_axis, n_heads=cfg.n_heads)
        )

    def beam_search(params, prompt):
        b, s = prompt.shape
        cache = init_cache(cfg, b, s + max_new_tokens - 1)
        if cache_sharding is not None:
            # Pin the layout BEFORE prefill (like make_generate_fn) so
            # GSPMD cannot pick a different prefill-time layout and
            # reshard the whole stack at the tile below.
            cache = jax.tree_util.tree_map(
                lambda c: jax.lax.with_sharding_constraint(
                    c, cache_sharding
                ),
                cache,
            )
        last_logits, cache = prefill(params, prompt, cache, cfg)
        logp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)

        # First expansion: top-K tokens of the prompt's next-token
        # distribution seed the K beams (B, K). With K > vocab only
        # vocab distinct depth-1 prefixes exist — the surplus beams are
        # seeded dead (-inf) and repopulated by later expansions.
        k0 = min(k_beams, vocab)
        scores0, first0 = jax.lax.top_k(logp0, k0)
        pad = k_beams - k0
        scores = jnp.pad(scores0, ((0, 0), (0, pad)),
                         constant_values=-jnp.inf)
        first = jnp.pad(first0, ((0, 0), (0, pad))).astype(prompt.dtype)
        finished = (
            first == eos_id if eos_id is not None
            else jnp.zeros(first.shape, bool)
        )

        # Tile the cache to B*K rows: row b*K + j = beam j of batch b.
        cache = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, k_beams, axis=1), cache
        )
        if cache_sharding is not None:
            cache = jax.tree_util.tree_map(
                lambda c: jax.lax.with_sharding_constraint(
                    c, cache_sharding
                ),
                cache,
            )
        seqs = jnp.zeros((b, k_beams, max_new_tokens), prompt.dtype)
        seqs = seqs.at[:, :, 0].set(first)

        def step(carry, t):
            tok, cache, seqs, scores, finished = carry
            logits, cache = forward_with_cache(
                params, tok.reshape(b * k_beams, 1), cache, s + t, cfg
            )
            logp = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1
            ).reshape(b, k_beams, vocab)
            if eos_id is not None:
                # A finished beam survives UNCHANGED: its only candidate
                # is "emit EOS again at zero cost", so its frozen score
                # competes in the top-K and its trailing slots pad with
                # EOS.
                freeze = jnp.full((vocab,), -jnp.inf).at[eos_id].set(0.0)
                logp = jnp.where(finished[:, :, None], freeze, logp)
            cand = scores[:, :, None] + logp           # (B, K, V)
            scores, flat = jax.lax.top_k(
                cand.reshape(b, k_beams * vocab), k_beams
            )
            parent = flat // vocab                     # (B, K) beam index
            nxt = (flat % vocab).astype(tok.dtype)     # (B, K) token
            # Reorder histories and cache rows under the surviving beams.
            seqs = jnp.take_along_axis(seqs, parent[:, :, None], axis=1)
            seqs = seqs.at[:, :, t + 1].set(nxt)
            if eos_id is not None:
                finished = jnp.take_along_axis(finished, parent, axis=1)
                finished = finished | (nxt == eos_id)
            rows = (
                jnp.arange(b)[:, None] * k_beams + parent
            ).reshape(b * k_beams)
            cache = jax.tree_util.tree_map(
                lambda c: jnp.take(c, rows, axis=1), cache
            )
            return (nxt, cache, seqs, scores, finished), None

        if max_new_tokens > 1:
            (_, _, seqs, scores, _), _ = jax.lax.scan(
                step,
                (first, cache, seqs, scores, finished),
                jnp.arange(max_new_tokens - 1),
            )
        prompts = jnp.broadcast_to(
            prompt[:, None, :], (b, k_beams, s)
        ).astype(prompt.dtype)
        return jnp.concatenate([prompts, seqs], axis=2), scores

    if not jit:
        return beam_search
    if mesh is None:
        return jax.jit(beam_search)

    return _sharded_jit(beam_search, mesh, party_axis, data_axis, 0)
