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

"""Pallas TPU kernel for one decode step of the gated delta rule: a
linear-attention layer's rows of the state ``S`` are read once, advanced
in fast memory and written back to the same place.

:func:`rayfed_tpu.models.olmo_hybrid.delta_step` is the definition, every
other backend's step and the tests' reference. Written in plain ``jnp``
around a slice of the stacked state, a TPU's compiler makes four passes
over a layer's ``S`` of it, three reads and a write (the two sums over
``dk`` are a fusion each, the update with the ``live`` rows' select and
the write-back into the stack a third: PERF.md section 6, PR 49). Here
the WHOLE stacked state (layers, rows, heads, dv, dk) float32 is the
kernel's input and its output (``input_output_aliases``); the layer's
``ordinal`` and the rows that are ``live`` are scalar-prefetch operands,
and the grid is (rows, groups of heads) of that one layer. A grid step
copies its heads' tiles of one row into fast memory once, forms there,
a head at a time and in float32 throughout, the definition's own lines::

    decayed = exp(g) S          u = v - decayed k^
    S' = decayed + (beta u) k^T     o = S' q^

and the tiles go back where they came from. Another layer's tiles are
never visited; a row that is not ``live`` gets its tiles back bit for bit
(and a zero ``o``, which its caller discards). A kernel that took one
layer's slice and returned a new one would have the compiler copy the
slice in and the result out: the four passes again.

A head's ``S`` is (dv, dk): ``dk`` along the lanes, so ``k^`` and ``q^``
are rows that broadcast down the sublanes and the two sums are lane
reductions that leave a column (dv, 1); ``v`` comes in and ``o`` goes
out as such columns, a head a lane ((rows, groups, dv, heads a group):
the transposes are XLA's, of arrays a thousandth of the state). ``exp(g)``
and ``beta`` are scalars of the step: ``exp`` is XLA's, as in the
definition, and both are scalar-prefetch operands.

Pallas comes from :mod:`rayfed_tpu.ops.paged_attention`, which holds the
Mosaic GPU interpreter out of its import; like that module this one is
imported by nothing under ``import rayfed_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from rayfed_tpu.ops.paged_attention import pl, pltpu

# Bytes of ``S`` a grid step copies in at most (and out again, each
# double-buffered): whole heads of one row, as many as fit. On a v5e a
# grid step costs about a third of a microsecond whatever it moves, and a
# (192, 96) float32 head is 98 KB as the device tiles it (96 columns in
# 128 lanes), a microsecond's worth of the chip's 819 GB/s: a row's 30
# heads (2.9 MB) in one step. Measured (PERF.md section 6, PR 49; twelve
# layers of 32 rows x 30 heads): 30 / 16 / 10 / 5 heads a step read 300 /
# 325 / 321 / 370 us a layer, where a kernel that only copies the tiles
# through reads 304 and the plain step 553. In and out, double-buffered,
# a step of 30 heads holds 12 MB of fast memory (``vmem_limit_bytes``
# below leaves the compiler room for a head's intermediate values).
STEP_BYTES = 4 << 20
LANES = 128


def heads_a_step(n_heads: int, dv: int, dk: int) -> int:
    """Heads of one row a grid step holds: static, from the shapes."""
    tile = dv * -(-dk // LANES) * LANES * 4
    return max(1, min(n_heads, STEP_BYTES // tile))


def _kernel(ord_ref, live_ref, decay_ref, beta_ref, q_ref, k_ref, v_ref,
            s_ref, o_ref, s_out, *, group: int):
    """Row ``r``, head group ``c`` of the grid: ``q_ref`` / ``k_ref``
    (group, dk), ``v_ref`` / ``o_ref`` (dv, group) a head a lane,
    ``s_ref`` / ``s_out`` (group, dv, dk) the same tiles of the stacked
    state; ``decay_ref`` / ``beta_ref`` (rows * groups * group,) in
    scalar memory."""
    del ord_ref
    r, c = pl.program_id(0), pl.program_id(1)
    first = (r * pl.num_programs(1) + c) * group

    @pl.when(live_ref[r] != 0)
    def _():
        v = v_ref[...]
        for j in range(group):
            k, q = k_ref[pl.ds(j, 1), :], q_ref[pl.ds(j, 1), :]
            decayed = decay_ref[first + j] * s_ref[j]
            u = v[:, j:j + 1] - jnp.sum(decayed * k, axis=-1, keepdims=True)
            state = decayed + (beta_ref[first + j] * u) * k
            s_out[j] = state
            o_ref[:, j:j + 1] = jnp.sum(state * q, axis=-1, keepdims=True)

    @pl.when(live_ref[r] == 0)
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_state_step(delta, ordinal, live, q, k, v, g, beta, *,
                     interpret: bool = False):
    """:func:`olmo_hybrid.delta_step` on layer ``ordinal`` of the stacked
    state ``delta`` (layers, R, H, dv, dk) float32, for the rows ``live``
    (R,) bool names: ``q`` / ``k`` (R, H, dk), ``v`` (R, H, dv), ``g`` /
    ``beta`` (R, H), all float32. Returns ``o`` (R, H, dv) and the
    stacked state, which is the argument's own buffer wherever the caller
    lets it go. Jitted here, so that a program whose layers are unrolled
    lowers the kernel once and calls it from every one."""
    _, n_rows, n_heads, dv, dk = delta.shape
    group = heads_a_step(n_heads, dv, dk)
    n_groups = -(-n_heads // group)
    short = n_groups * group - n_heads

    def grouped(x):
        # (R, H, ..) -> (R, groups, heads a group, ..), zero heads behind.
        x = jnp.pad(x, [(0, 0), (0, short)] + [(0, 0)] * (x.ndim - 2))
        return x.reshape(n_rows, n_groups, group, *x.shape[2:])

    def block(*shape):
        return pl.BlockSpec(
            (None, None, *shape), lambda r, c, *_: (r, c, 0, 0))

    tiles = pl.BlockSpec(
        (None, None, group, dv, dk),
        lambda r, c, ord_ref, *_: (ord_ref[0], r, c, 0, 0))
    o, delta = pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_rows, n_groups),
            in_specs=[block(group, dk), block(group, dk), block(dv, group),
                      tiles],
            out_specs=[block(dv, group), tiles],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, n_groups, dv, group), delta.dtype),
            jax.ShapeDtypeStruct(delta.shape, delta.dtype)],
        # (Operands count the scalar-prefetch ones.)
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="delta_state_step",
    )(jnp.reshape(ordinal, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      grouped(jnp.exp(g)).reshape(-1), grouped(beta).reshape(-1),
      grouped(q), grouped(k), jnp.swapaxes(grouped(v), 2, 3), delta)
    o = jnp.swapaxes(o, 2, 3).reshape(n_rows, n_groups * group, dv)
    return o[:, :n_heads], delta
