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

"""The gated delta rule's decode step as a Pallas kernel over the stacked
state (``rayfed_tpu/ops/delta_rule.py``: what
``olmo_hybrid.paged_decode_step`` runs on a TPU backend), in interpret
mode against its definition, ``olmo_hybrid.delta_step`` between a slice
of the stack and its write-back, which every other backend runs: at the
published head shape, every pattern of decay, ``beta``, idle rows and
layer a decode step meets, and the whole decode step with the kernel in
it. ``tests/test_tpu_compile.py`` compiles the same kernel for a
described v5e inside the cell's decode step.
"""

from __future__ import annotations

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rayfed_tpu.models import olmo_hybrid as oh
from rayfed_tpu.ops import delta_rule as kernel

# The published head shape, the 30 heads cut to a few; a stack of three
# layers of four rows.
DV, DK, HEADS, ROWS, LAYERS = 192, 96, 4, 4, 3
TOL = 2e-5

# g (the log of the decay) and beta of the step, by case.
STEPS = {
    "seeded": lambda rng, s: (-rng.uniform(1e-3, 0.5, s),
                              rng.uniform(0.05, 1.95, s)),
    "beta-under-1": lambda rng, s: (-rng.uniform(1e-3, 0.5, s),
                                    rng.uniform(0.05, 0.95, s)),
    # The transition's eigenvalue along k^ is 1 - beta: negative here.
    "beta-over-1": lambda rng, s: (-rng.uniform(1e-3, 0.5, s),
                                   rng.uniform(1.05, 1.95, s)),
    "g-near-0": lambda rng, s: (-rng.uniform(0, 1e-6, s),
                                rng.uniform(0.05, 1.95, s)),
    "g-strongly-negative": lambda rng, s: (-rng.uniform(20, 60, s),
                                           rng.uniform(0.05, 1.95, s)),
}
LIVE = {
    "every-row-live": [True] * ROWS,
    "a-row-idle": [True, False, True, True],
    "one-row-live": [False, False, True, False],
    "every-row-idle": [False] * ROWS,
}


def _case(step="seeded", heads=HEADS, seed=0):
    rng = np.random.default_rng(seed)

    def f(a):
        return jnp.asarray(a, jnp.float32)

    lead = (ROWS, heads)
    g, beta = STEPS[step](rng, lead)
    q = oh._l2(f(rng.standard_normal(lead + (DK,)))) * DK ** -0.5
    k = oh._l2(f(rng.standard_normal(lead + (DK,))))
    return dict(
        delta=f(rng.standard_normal((LAYERS,) + lead + (DV, DK))),
        step=(q, k, f(rng.standard_normal(lead + (DV,))), f(g), f(beta)))


def _definition(c, ordinal, live):
    """What ``paged_decode_step`` does around ``delta_step`` on every
    other backend."""
    st = c["delta"][ordinal]
    o, new = oh.delta_step(*c["step"], st)
    keep = jnp.asarray(live)[:, None, None, None]
    return o, c["delta"].at[ordinal].set(jnp.where(keep, new, st))


def _kernel(c, ordinal, live):
    return kernel.delta_state_step(
        c["delta"], jnp.int32(ordinal), jnp.asarray(live), *c["step"],
        interpret=True)


def _assert_is_the_definition(c, ordinal, live):
    want_o, want = _definition(c, ordinal, live)
    got_o, got = _kernel(c, ordinal, live)
    start = np.asarray(c["delta"])
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    assert got_o.shape == want_o.shape and got_o.dtype == jnp.float32
    rows = np.asarray(live)
    want_o, want, got_o, got = (
        np.asarray(a) for a in (want_o, want, got_o, got))
    # (An idle row's ``o`` is whatever: the step's caller discards it.)
    np.testing.assert_allclose(got_o[rows], want_o[rows], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(
        got[ordinal][rows], want[ordinal][rows], atol=TOL, rtol=TOL)
    # Bit for bit: an idle row's tiles, and every other layer's.
    np.testing.assert_array_equal(
        got[ordinal][~rows], start[ordinal][~rows])
    others = [i for i in range(LAYERS) if i != ordinal]
    np.testing.assert_array_equal(got[others], start[others])
    assert np.isfinite(got_o).all()


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("step", list(STEPS))
def test_the_kernel_is_the_definition_to_float32_rounding(step, live):
    _assert_is_the_definition(_case(step), 1, LIVE[live])


@pytest.mark.parametrize("ordinal", [0, 1, LAYERS - 1],
                         ids=["first", "middle", "last"])
def test_the_kernel_visits_its_layers_tiles_alone(ordinal):
    _assert_is_the_definition(_case(seed=ordinal), ordinal, LIVE["a-row-idle"])


@pytest.mark.parametrize("heads, group", [(5, 2), (5, 3), (4, 1), (30, 30)])
def test_a_grid_step_takes_whole_heads_as_many_as_fit(
        heads, group, monkeypatch):
    """The head group is what ``STEP_BYTES`` holds of a row's heads as the
    device tiles them (96 columns in 128 lanes); where it does not divide
    the heads the last group's tiles end with the row's."""
    tile = DV * 128 * 4
    assert kernel.heads_a_step(30, DV, DK) == 30        # the cell's: a row
    monkeypatch.setattr(kernel, "STEP_BYTES", group * tile + tile // 2)
    assert kernel.heads_a_step(heads, DV, DK) == group
    # (Traced anew: the group is read when the kernel is traced.)
    monkeypatch.setattr(
        kernel, "delta_state_step", kernel.delta_state_step.__wrapped__)
    _assert_is_the_definition(
        _case(heads=heads, seed=heads), 2, LIVE["a-row-idle"])
    monkeypatch.setattr(kernel, "STEP_BYTES", 1)
    assert kernel.heads_a_step(heads, DV, DK) == 1


def test_steps_carry_the_state_as_the_definition_does():
    """A dozen steps, each from the last one's state, a row going idle and
    coming back: the kernel's stack stays with the definition's."""
    c = _case("seeded", seed=9)
    want, got = c["delta"], c["delta"]
    for t in range(12):
        live = [True, t % 3 != 1, True, t < 6]
        step = _case("seeded", seed=100 + t)["step"]
        ordinal = t % LAYERS
        _, want = _definition(dict(delta=want, step=step), ordinal, live)
        _, got = _kernel(dict(delta=got, step=step), ordinal, live)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_the_choice_is_the_backends_and_the_states(monkeypatch):
    f32 = jax.ShapeDtypeStruct((LAYERS, ROWS, HEADS, DV, DK), jnp.float32)
    assert not oh.delta_step_is_kernel(f32)             # a CPU backend
    monkeypatch.setattr(
        oh, "utils", types.SimpleNamespace(is_tpu_backend=lambda: True))
    assert oh.delta_step_is_kernel(f32)
    assert not oh.delta_step_is_kernel(
        jax.ShapeDtypeStruct(f32.shape, jnp.bfloat16))
    assert not oh.delta_step_is_kernel(
        jax.ShapeDtypeStruct((LAYERS, ROWS, HEADS, 12, DK), jnp.float32))


def test_the_decode_step_is_the_same_through_the_kernel(monkeypatch):
    """``paged_decode_step`` whole (two periods of three linear layers to
    one full, rows live and idle, two steps one after the other), once as
    every CPU party runs it and once with the kernel in it (interpret
    mode; only ``olmo_hybrid`` is told it is on a TPU): the same logits
    and the same pool and state to float32 rounding, an idle row's state
    bit for bit, and the kernel lowered once for the unrolled layers."""
    from tests import test_olmo_hybrid as t

    cfg, params = t.CFG, t.PARAMS
    model = oh.serving_model(cfg)
    rows, n_blocks, bs = 3, 4, 8
    rng = np.random.default_rng(7)

    def arr(shape, dtype):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    kv = tuple(arr((layers, 1 + rows * n_blocks, bs) + row, jnp.float32)
               for layers, row in model.kv_spec())
    state = {name: arr((layers, rows) + shape, dtype) for name, (
        layers, shape, dtype) in model.state_spec(jnp.float32).items()}
    granted = 1 + rng.permutation(rows * n_blocks).reshape(rows, n_blocks)
    # (tokens, positions, live): an idle row as the engine sends it, at
    # position 0 under an all-zero table (its write lands in block 0).
    steps = [([5, 9, 200], [11, 3, 0], [True, True, False]),
             ([17, 1, 2], [12, 0, 0], [True, False, False])]

    def run():
        program = jax.jit(model.decode_step)
        out, pool, st = [], kv, state
        for tokens, positions, live in steps:
            call = (jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(positions, jnp.int32),
                    jnp.asarray(granted * np.asarray(live)[:, None],
                                jnp.int32), jnp.asarray(live))
            logits, pool, st = program(params, pool, st, *call)
            out.append(logits[np.asarray(live)])
        return program.lower(params, kv, state, *call), out, pool, st

    _, want, want_kv, want_state = run()
    monkeypatch.setattr(
        oh, "utils", types.SimpleNamespace(is_tpu_backend=lambda: True))
    compiled = kernel.delta_state_step.__wrapped__

    def delta_state_step(*a):
        return compiled(*a, interpret=True)

    monkeypatch.setattr(kernel, "delta_state_step", jax.jit(delta_state_step))
    lowered, got, got_kv, got_state = run()
    assert lowered.as_text().count(
        "func.func private @delta_state_step") == 1
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=t.TOL32)
    for a, b in zip(got_kv, want_kv):
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], atol=TOL)
    np.testing.assert_allclose(
        got_state["delta"], want_state["delta"], atol=TOL, rtol=TOL)
    # (A later layer's tail is its input's: the layers before moved it.)
    np.testing.assert_allclose(
        got_state["conv"], want_state["conv"], atol=TOL, rtol=TOL)
    # Row 2 sat both steps out.
    np.testing.assert_array_equal(
        got_state["delta"][:, 2], state["delta"][:, 2])
