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

"""Pallas TPU kernel for a prompt chunk's read of the paged pool: one trip
of :func:`rayfed_tpu.models.decode.paged_chunk_attention`'s online softmax
(a chunk's queries against a trip's keys) with its scores, mask, softmax
and PV product in fast memory.

The loop in ``decode.paged_chunk_attention`` is the definition, every
other backend's read and the tests' reference; on a TPU backend, for a
pool whose slots reach far (``decode.paged_chunk_is_kernel``), a trip is
one call of :func:`chunk_trip`. XLA keeps what it does well: the runtime
trip count, the gather of a trip's whole blocks through the slot's table,
a latent pool's ``expand`` (a plain matmul) and the mask of a trip, one
int8 tile (queries, keys) that carries every form there is (causal
inside the chunk, a window, a selection each query its own, a padded
query that keeps itself). The kernel never sees which form it serves.

The grid is (K/V head, block of queries); a step holds its head's keys
and values of the whole trip and scores them in one go (a trip is at most
1,024 keys: on a v5e a wider trip, or one walked in turns of 256 or 512
keys, read slower a key: ``PERF.md`` section 6, PR 45). The running
maximum and sum (one array: half a tile's lanes each) and the accumulator
(float32) come in and go out through the same buffers
(``input_output_aliases``), so a chunk's trips carry them in place; the
first trip (the chunk's own keys) starts them itself. The
mathematics is the loop's: operands in the compute dtype on the MXU,
float32 scores scaled after the product, probabilities cast to the value
dtype before the PV product, and a softmax that stays finite where a
query has seen no key yet (the shift is 0 while the maximum is minus
infinity): nothing is rounded lower.

Pallas comes from :mod:`rayfed_tpu.ops.paged_attention`, which holds the
Mosaic GPU interpreter out of its import; like that module this one is
imported by nothing under ``import rayfed_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from rayfed_tpu.ops.paged_attention import pl, pltpu

# Queries a grid step scores: a chunk of 512 is one block, so the mask's
# tile and a head's keys are copied once a head (256 read a tenth slower).
BLOCK_Q = 512
# What a trip's keys are a multiple of: the lanes of the mask's tile.
KEY_TILE = 128
# Lanes of the array the maximum and the sum cross memory in between two
# trips: a tile's, half for each (in the kernel each has every lane).
STAT_LANES = 128

_NT = (((1,), (1,)), ((), ()))   # (q, d) x (k, d) -> (q, k)
_NN = (((1,), (0,)), ((), ()))   # (q, k) x (k, d) -> (q, d)


def trip_keys(block_size: int, span: int, most: int) -> int:
    """Keys a trip gathers where the keys a chunk may have to visit are
    at most ``span`` and a trip holds at most about ``most``: the span cut
    into as few equal trips as ``most`` allows, each whole blocks and
    whole tiles of the mask."""
    per = -(-span // -(-span // most))
    tile = math.lcm(block_size, KEY_TILE)
    return -(-per // tile) * tile


def _kernel(q_ref, k_ref, v_ref, mask_ref, *state, scale):
    """One (head, block of queries): ``q_ref`` (bq, Dqk), ``k_ref`` (T,
    Dqk), ``v_ref`` (T, Dv), ``mask_ref`` (bq, T) int8, then the state
    coming in (absent in a chunk's first trip) and going out, float32:
    maximum and sum as ONE array (bq, STAT_LANES), the maximum on its
    lower lanes and the sum on its upper ones, and the accumulator (bq,
    Dv)."""
    stat_ref, acc_ref = state[-2:]
    lower = jax.lax.broadcasted_iota(
        jnp.int32, stat_ref.shape, 1) < STAT_LANES // 2
    if len(state) == 2:
        m = jnp.full(stat_ref.shape, -jnp.inf, jnp.float32)
        den = acc = 0.0
    else:
        # Each on every lane: the other half turned over it.
        stat = state[0][...]
        turned = pltpu.roll(stat, STAT_LANES // 2, 1)
        m = jnp.where(lower, stat, turned)
        den = jnp.where(lower, turned, stat)
        acc = state[1][...]
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...], _NT,
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_ref[...].astype(jnp.int32) != 0, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # The shift of a softmax that has seen no key yet is 0: a query's own
    # key need not be among a selection's.
    shift = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    alpha = jnp.exp(m - shift)
    p = jnp.exp(s - shift[:, :1])
    den = den * alpha + p.sum(axis=-1, keepdims=True)
    v = v_ref[...]
    acc_ref[...] = acc * alpha[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
    stat_ref[...] = jnp.where(lower, m_new, den)


def chunk_output(state, dtype):
    """A chunk's attention output (Hk, G * C, Dv) from the state its last
    trip left: the accumulator over the sum."""
    stat, acc = state
    return (acc / stat[..., STAT_LANES // 2:STAT_LANES // 2 + 1]).astype(
        dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def chunk_trip(q, k, v, mask, state=None, *, scale: float,
               interpret: bool = False):
    """One trip of a chunk's online softmax. ``q`` (Hk, G, C, Dqk) the
    chunk's queries by K/V head and the head's place in its group, ``k``
    (Hk, T, Dqk) and ``v`` (Hk, T, Dv) a trip's keys and values; ``mask``
    (C, T) int8: query ``i`` attends key ``j`` where it is not 0,
    whatever its head. ``state`` is what the trip before left, ``(maximum
    | sum (Hk, G * C, STAT_LANES), accumulator (Hk, G * C, Dv))``,
    float32, or None for a chunk's first trip; returns the state after
    this trip, written where the old one was (:func:`chunk_output` of the
    last is the chunk's result). Jitted here, so that a program lowers a
    form once and calls it from every layer and every trip."""
    n_kv, group, c, d_qk = q.shape
    t, d_v = k.shape[1], v.shape[-1]
    rows = group * c
    bq = BLOCK_Q if c % BLOCK_Q == 0 else c
    q_blocks = c // bq
    first = state is None

    def per_head(shape, index):
        return pl.BlockSpec((None, *shape), index)

    def at_queries(h, j):
        return h, j, 0

    def at_head(h, j):
        return h, 0, 0

    stat = per_head((bq, STAT_LANES), at_queries)
    acc = per_head((bq, d_v), at_queries)
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(n_kv, rows // bq),
        in_specs=[
            per_head((bq, d_qk), at_queries),
            per_head((t, d_qk), at_head),
            per_head((t, d_v), at_head),
            pl.BlockSpec((bq, t), lambda h, j: (j % q_blocks, 0)),
            *(() if first else (stat, acc)),
        ],
        out_specs=[stat, acc],
        out_shape=[jax.ShapeDtypeStruct((n_kv, rows, STAT_LANES), f32),
                   jax.ShapeDtypeStruct((n_kv, rows, d_v), f32)],
        input_output_aliases={} if first else {4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="paged_chunk_read",
    )(q.reshape(n_kv, rows, d_qk), k, v, mask, *(() if first else state))
    return tuple(out)
