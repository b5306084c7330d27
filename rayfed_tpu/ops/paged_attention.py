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

"""Pallas TPU kernel for the paged decode read: one decode token per row
attends its own blocks of the K/V pool through the block table.

:func:`rayfed_tpu.models.decode.paged_attention` is the definition (a
gather loop that walks every row to the longest row's length); this is
what it returns on a TPU backend. The pool stays where it is in HBM,
flattened over its layers; the rows' positions, the block tables and the
layer's first block are scalar-prefetch operands, and the grid is the
rows. A grid step copies the blocks ``base + tables[r, j]`` of its row
only, for ``j`` up to the blocks ``positions[r]`` covers (from the block
that holds ``positions[r] - window + 1`` on a windowed layer), several
blocks a trip into one of two buffers: while a trip is scored the next is
in flight, and a row's last trip starts the first trip of the next row
that has any. A junk row (position 0) copies nothing.

One kernel serves every form by what its operands show: ``pv is None``
(a pool of one latent array a token: every query head reads the same row,
a key's value is its first ``v_width`` columns), ``G = H / Hkv`` query
heads a K/V head, ``window``, ``scale``. The mathematics is the loop's:
operands in the cache's dtype, float32 scores, an online softmax with
float32 maximum, sum and accumulator whose first key is the current
token's own, probabilities cast to the value dtype before the PV
product. A row's result depends on nothing another row holds.

Importing this module imports Pallas (half a second on a serving host,
as it is imported below): nothing under ``import rayfed_tpu`` does, and
the serving engine starts the import on a thread of its own
(``InferenceServer.__init__``).
"""

from __future__ import annotations

import functools
import sys
from typing import Optional

import jax
import jax.numpy as jnp

# Two thirds of Pallas's import (0.9 s of 1.4 on a serving host) is the
# Mosaic GPU interpreter, which ``pallas_call`` loads beside the TPU's
# and, by its own ``except ImportError``, does without where it cannot:
# no process of this package runs it, so it is held absent (``None`` in
# ``sys.modules`` is Python's way to say so) while Pallas loads here
# first. A process that had Pallas already keeps what it had.
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
_held = _GPU_INTERPRETER not in sys.modules
if _held:
    sys.modules[_GPU_INTERPRETER] = None
try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
finally:
    if _held:
        del sys.modules[_GPU_INTERPRETER]

# Keys a trip copies and scores at most, and the bytes one of the two
# buffers of one array may take: a trip is whole blocks, as many as fit
# both. On a v5e (PR 43, PERF.md section 6; a layer's read at the cells'
# shapes) 256 / 512 / 1,024 keys read 1,423 / 1,191 / 1,087 us over 48
# latent rows of 500-9,000 keys, 80 / 76 / 73 over 32 rows of 4 K/V heads
# and 40-760 keys, 315 / 304 / 310 at 8 K/V heads x 16 query heads; at
# 16 heads x 128 (4 KB a key) 2 MB hold 512 keys, as fast as 256.
TRIP_KEYS = 1024
TRIP_BYTES = 2 << 20
# Pairs of K/V heads one turn of a pass's loop holds: their products do
# not depend on one another, and the scheduler interleaves only what one
# turn holds. 4 / 2 / 1 read 68 / 71 / 77 us at 16 heads and 78 / 84 /
# 105 at 32; each pair more is traced and lowered at every start.
PAIRS_A_TURN = 4


def trip_blocks(block_size: int, token_bytes: int, blocks_per_row: int) -> int:
    """Blocks a trip of the kernel copies: static, from the shapes. A
    block of ``block_size`` keys, ``token_bytes`` a key in one array of
    one layer."""
    keys = min(TRIP_KEYS, max(block_size, TRIP_BYTES // token_bytes))
    return max(1, min(blocks_per_row, keys // block_size))


def _head_pairs(ref2d, pair, n_kv: int, keys: int, op_dtype):
    """The (keys, Dh) rows of K/V heads ``2 * pair`` and ``2 * pair + 1``
    out of a trip's buffer seen as (keys * n_kv, Dh): head ``h`` of key
    ``k`` is row ``k * n_kv + h``. A 16-bit dtype keeps two rows a
    32-bit word (row 2i low, row 2i + 1 high): the pair is one strided
    load of words, split by a shift and a mask."""
    if ref2d.dtype == jnp.bfloat16:
        words = ref2d.bitcast(jnp.uint32)[
            pl.ds(pair, keys, stride=n_kv // 2), :]
        halves = (jax.lax.shift_left(words, jnp.uint32(16)),
                  jax.lax.bitwise_and(words, jnp.uint32(0xFFFF0000)))
        return tuple(
            jax.lax.convert_element_type(
                pltpu.bitcast(half, jnp.float32), op_dtype)
            for half in halves)
    return tuple(
        jax.lax.convert_element_type(
            ref2d[pl.ds(2 * pair + i, keys, stride=n_kv), :], op_dtype)
        for i in range(2))


def _kernel(pos_ref, tab_ref, base_ref, q_ref, k1_ref, v1_ref, k_hbm, v_hbm,
            o_ref, kbuf, vbuf, sems, m_ref, l_ref, s_ref, p_ref, acc_ref,
            flow,
            *, window, scale, v_width, n_blocks_row, op_dtype):
    """Row ``r`` of the grid: ``q_ref`` (Hkv, G, width), ``k1_ref`` /
    ``v1_ref`` (Hkv, 1, width) float32, the pool in HBM, ``o_ref`` (Hkv,
    G, Dv). Scratch: two buffers of a trip's blocks for each array and
    their semaphores, the softmax's maximum and sum (Hkv, G, 1), a trip's
    scores and probabilities (Hkv, G, keys), the accumulator, and two
    words that outlive a grid step (``flow``)."""
    r = pl.program_id(0)
    n_rows = pl.num_programs(0)
    latent = v_hbm is None
    _, cb, bs = kbuf.shape[:3]
    n_kv = 1 if latent else kbuf.shape[3]
    keys = cb * bs
    base = base_ref[0]

    def span(row):
        """(first block, blocks) of ``row``'s read."""
        pos = pos_ref[row]
        first = 0
        if window is not None:
            first = jnp.maximum(pos - window + 1, 0) // bs
        return first, (pos + bs - 1) // bs - first

    def each_copy(row, trip, slot, act):
        """``act`` on the copies of ``row``'s trip ``trip`` into buffer
        ``slot``: the blocks the row has of it, no other."""
        first, n = span(row)

        def block(i, _):
            at = base + tab_ref[row * n_blocks_row + first + trip * cb + i]
            for a, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                if hbm is not None:
                    act(pltpu.make_async_copy(
                        hbm.at[at], buf.at[slot, i], sems.at[a, slot]))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(cb, n - trip * cb), block, 0)

    def start(row, trip, slot):
        each_copy(row, trip, slot, lambda copy: copy.start())

    def wait(row, trip, slot):
        """For ``row``'s trip ``trip`` to have landed in ``slot``: block
        by block, or at once for a whole trip's bytes where the trip is
        whole (a wait is for a count of bytes on the buffer's semaphore,
        whatever copies brought them)."""
        _, n = span(row)

        def whole():
            for a, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                if hbm is not None:
                    pltpu.make_async_copy(
                        hbm.at[pl.ds(0, cb)], buf.at[slot], sems.at[a, slot]
                    ).wait()

        jax.lax.cond(
            n - trip * cb >= cb, whole,
            lambda: each_copy(row, trip, slot, lambda copy: copy.wait()))

    @pl.when(r == 0)
    def _():
        # flow[0]: the buffer the next trip to be scored lands in;
        # flow[1]: whether that trip is already in flight. What a trip
        # does not copy keeps what the buffer held, scored as nothing:
        # finite times zero. Memory never written may hold anything.
        flow[0] = 0
        flow[1] = 0
        values = kbuf if latent else vbuf
        values[...] = jnp.zeros_like(values)

    pos = pos_ref[r]
    first, n = span(r)
    trips = (n + cb - 1) // cb
    slot0 = flow[0]

    @pl.when((trips > 0) & (flow[1] == 0))
    def _():
        start(r, 0, slot0)

    # The current token is the softmax's first key.
    k1 = k1_ref[...]
    m_ref[...] = jnp.sum(
        q_ref[...].astype(jnp.float32) * k1, axis=-1, keepdims=True) * scale
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.broadcast_to(
        k1[..., :v_width] if latent else v1_ref[...], acc_ref.shape)

    # A trip in three passes, each over all the heads, so that no head's
    # softmax waits between its two products: the scores of every head
    # (float32, into ``s_ref``), one online-softmax update of them all,
    # every head's probabilities times its values.
    def soften(seen):
        s = jnp.where(seen, s_ref[...] * scale, -jnp.inf)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha
        p_ref[...] = p.astype(p_ref.dtype)

    # (Per head, so as bare lax calls: a line here is traced
    # ``2 * PAIRS_A_TURN`` times, at the start of every process.)
    def scores(h, k):
        s_ref[h] = jax.lax.dot_general(
            q_ref[h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def values(h, v):
        acc_ref[h] = jax.lax.add(acc_ref[h], jax.lax.dot_general(
            p_ref[h], v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))

    def each_pair(buf, slot, dtype, act):
        """``act(h, rows)`` on every head's (keys, Dh) rows of a trip."""
        rows2d = buf.at[slot].reshape(keys * n_kv, buf.shape[-1])

        def pair(i):
            for j, rows in enumerate(
                    _head_pairs(rows2d, i, n_kv, keys, dtype)):
                act(2 * i + j, rows)

        pairs = n_kv // 2
        turn = next(u for u in (PAIRS_A_TURN, 2, 1) if pairs % u == 0)

        def some(g, _):
            for j in range(turn):
                pair(g * turn + j)
            return 0

        jax.lax.fori_loop(0, pairs // turn, some, 0)

    def trip(c, _):
        slot = (slot0 + c) % 2

        # What is scored next flies meanwhile: this row's next trip, or
        # after its last the first trip of the next row that reads any.
        nxt = jax.lax.cond(
            c + 1 < trips, lambda: r,
            lambda: jax.lax.while_loop(
                lambda i: (i < n_rows)
                & (pos_ref[jnp.minimum(i, n_rows - 1)] == 0),
                lambda i: i + 1, r + 1))
        flow[1] = ((nxt > r) & (nxt < n_rows)).astype(jnp.int32)

        @pl.when(nxt < n_rows)
        def _():
            start(nxt, jnp.where(nxt == r, c + 1, 0), 1 - slot)

        wait(r, c, slot)
        k_pos = (first + c * cb) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1)
        seen = k_pos < pos
        if window is not None:
            seen &= k_pos > pos - window
        if latent:
            k = kbuf.at[slot].reshape(keys, kbuf.shape[-1])[...]
            scores(0, k.astype(op_dtype))
            soften(seen)
            values(0, k[:, :v_width])
            return 0
        each_pair(kbuf, slot, op_dtype, scores)
        soften(seen)
        each_pair(vbuf, slot, vbuf.dtype, values)
        return 0

    jax.lax.fori_loop(0, trips, trip, 0)
    flow[0] = (slot0 + trips) % 2

    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "v_width", "interpret"))
def paged_read(q, k1, v1, pk_flat, pv_flat, positions, tables, base, *,
               window: Optional[int], scale: float,
               v_width: Optional[int] = None, interpret: bool = False):
    """``attend(q, k1, v1, base)`` of :func:`decode.paged_attention` as
    one kernel call: ``q`` (R, H, Dh), ``k1``/``v1`` (R, Hkv, Dh) in the
    pool's dtype (``v1`` None and ``k1`` (R, 1, width) for a pool of one
    array), ``pk_flat``/``pv_flat`` the pool with its layers flattened
    into its blocks ((L * P, bs, Hkv, Dh), or (L * P, bs, width) and
    None), ``positions`` (R,), ``tables`` (R, NB), ``base`` the layer's
    first block. Returns (R, H, Dh) (or (R, H, v_width)) in the value
    dtype. Jitted here, so that a program whose layers are a list lowers
    the kernel once and calls it from every layer."""
    n_rows, n_heads = q.shape[:2]
    latent = pv_flat is None
    bs = pk_flat.shape[1]
    n_kv = 1 if latent else pk_flat.shape[2]
    width = pk_flat.shape[-1]
    d_out = v_width if latent else width
    group = n_heads // n_kv
    if not latent and n_kv % 2:
        raise NotImplementedError(f"an odd number of K/V heads ({n_kv})")
    op_dtype = jnp.promote_types(q.dtype, pk_flat.dtype)
    n_blocks_row = tables.shape[1]
    token_bytes = n_kv * width * pk_flat.dtype.itemsize
    cb = trip_blocks(bs, token_bytes, n_blocks_row)

    def per_row(*shape):
        return pl.BlockSpec(
            (None, *shape), lambda r, *_: (r,) + (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    operands = [
        q.reshape(n_rows, n_kv, group, -1).astype(op_dtype),
        k1.reshape(n_rows, n_kv, 1, -1).astype(f32),
        None if latent else v1.reshape(n_rows, n_kv, 1, width).astype(f32),
        pk_flat, pv_flat,
    ]
    if latent:
        # The pool's rows may be padded to whole tiles (zeros): the query
        # and the new key are padded to match, and score the padding as
        # nothing.
        operands[:2] = [
            jnp.pad(x, [(0, 0)] * 3 + [(0, width - x.shape[-1])])
            for x in operands[:2]]
    in_specs = [
        per_row(n_kv, group, width), per_row(n_kv, 1, width),
        None if latent else per_row(n_kv, 1, width), hbm,
        None if latent else hbm,
    ]
    buf = pltpu.VMEM((2, cb, *pk_flat.shape[1:]), pk_flat.dtype)
    out = pl.pallas_call(
        functools.partial(
            _kernel, window=window, scale=scale, v_width=v_width,
            n_blocks_row=n_blocks_row, op_dtype=op_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_rows,),
            in_specs=in_specs,
            out_specs=per_row(n_kv, group, d_out),
            scratch_shapes=[
                buf, None if latent else buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_kv, group, 1), f32),
                pltpu.VMEM((n_kv, group, 1), f32),
                pltpu.VMEM((n_kv, group, cb * bs), f32),
                pltpu.VMEM((n_kv, group, cb * bs), pk_flat.dtype),
                pltpu.VMEM((n_kv, group, d_out), f32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_rows, n_kv, group, d_out), pk_flat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="paged_read",
    )(positions.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      jnp.reshape(base, (1,)).astype(jnp.int32), *operands)
    return out.reshape(n_rows, n_heads, d_out)
