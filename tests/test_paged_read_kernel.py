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

"""The paged decode read as a Pallas kernel
(``rayfed_tpu/ops/paged_attention.py``: what ``decode.paged_attention``
returns on a TPU backend), in interpret mode against its definition, the
gather loop every other backend runs: every form the serving models ask
for, every pattern of rows an engine hands a decode step, the engine end
to end with the kernel in its decode program, and what the engine counts
of the two reads. ``tests/test_tpu_compile.py`` compiles the same kernel
for a described v5e inside the four models' decode steps.
"""

from __future__ import annotations

import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rayfed_tpu.models import decode
from rayfed_tpu.ops import paged_attention as kernel

BS, NB, LAYERS = 8, 10, 2   # a block, blocks a row, layers of the pool

# (query heads, K/V heads, head size, width as allocated, v_width)
FORMS = {
    "mha": (4, 4, 32, 32, None),
    "grouped": (8, 2, 32, 32, None),
    # One array a token: the value is a key's first 512 columns, the rows
    # are padded from 576 to whole tiles.
    "latent": (4, 1, 576, 640, 512),
}
# Lengths a decode step meets, one batch: ragged rows, one that ends on a
# block boundary, one of a single block, one of a single key, a junk row
# (position 0 under an all-zero table), the longest a row can be.
ROWS = {
    "ragged": [37, 24, 5, 1, 0, NB * BS - 1],
    "every-row-junk": [0, 0, 0, 0, 0, 0],
    "one-live-row-among-junk": [0, 0, 0, 53, 0, 0],
}
# No window; one smaller than the rows, one equal to a row's keys (its
# own among them: position 37 attends 0..37), one larger than any row.
WINDOWS = {"no-window": None, "smaller": 11, "a-rows-own": 38,
           "larger": 500}


def _case(form, lengths, dtype=jnp.float32, seed=0):
    n_heads, n_kv, dh, width, v_width = FORMS[form]
    rng = np.random.default_rng(seed)
    n_rows = len(lengths)
    n_phys = 1 + n_rows * NB

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(
            dtype)

    if v_width is None:
        pk, pv = arr(LAYERS, n_phys, BS, n_kv, dh), arr(
            LAYERS, n_phys, BS, n_kv, dh)
        v1 = arr(n_rows, n_kv, dh)
    else:
        pk, pv, v1 = arr(LAYERS, n_phys, BS, width).at[..., dh:].set(0), \
            None, None
    pos = np.asarray(lengths, np.int32)
    granted = rng.permutation(np.arange(1, n_phys)).reshape(n_rows, NB)
    tables = np.where(np.arange(NB)[None] * BS <= pos[:, None] - (pos == 0)[
        :, None], granted, 0).astype(np.int32)
    return dict(pk=pk, pv=pv, pos=jnp.asarray(pos), tables=jnp.asarray(tables),
                q=arr(n_rows, n_heads, dh), k1=arr(n_rows, n_kv, dh), v1=v1,
                v_width=v_width, n_phys=n_phys,
                scale=0.07 if v_width else dh ** -0.5)


def _loop(c, window, layer=1):
    attend = decode.paged_attention(
        c["pk"], c["pv"], c["pos"], c["tables"], window, scale=c["scale"],
        v_width=c["v_width"])
    return attend(c["q"], c["k1"], c["v1"], layer * c["n_phys"])


def _kernel(c, window, layer=1):
    flat = [None if a is None else a.reshape(-1, *a.shape[2:])
            for a in (c["pk"], c["pv"])]
    return kernel.paged_read(
        c["q"], c["k1"], c["v1"], *flat, c["pos"], c["tables"],
        layer * c["n_phys"], window=window, scale=c["scale"],
        v_width=c["v_width"], interpret=True)


@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("form", list(FORMS))
def test_the_kernel_is_the_loop_to_float32_rounding(form, window, rows):
    c = _case(form, ROWS[rows])
    want, got = _loop(c, WINDOWS[window]), _kernel(c, WINDOWS[window])
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    if rows == "every-row-junk":
        # A row without cached keys attends its own token alone.
        v1 = c["k1"][..., :c["v_width"]] if c["v1"] is None else c["v1"]
        group = c["q"].shape[1] // v1.shape[1]
        np.testing.assert_array_equal(got, jnp.repeat(v1, group, axis=1))


@pytest.mark.parametrize("form", list(FORMS))
def test_the_kernel_reads_a_bfloat16_pool_as_the_loop_does(form):
    """The cells' dtype: two K/V heads a 32-bit word of a block. The
    operands and the probabilities are bfloat16 in both, the scores and
    the softmax float32: what differs is the order of float32 sums, then
    one rounding of the output."""
    c = _case(form, ROWS["ragged"], jnp.bfloat16)
    for window in (None, 11):
        want = np.asarray(_loop(c, window), np.float32)
        got = np.asarray(_kernel(c, window), np.float32)
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2e-3)


@pytest.mark.parametrize("form", list(FORMS))
def test_a_rows_output_is_bitwise_its_own(form):
    """Row 0 of two steps that differ in every other row's length, table
    and operands: equal bit for bit (a trip's buffer keeps what another
    row left there, scored as nothing)."""
    a = _case(form, [37, 24, 5, 1, 0, NB * BS - 1], seed=1)
    b = _case(form, [37, 0, 71, 16, 9, 0], seed=2)
    for name in ("q", "k1", "v1"):
        if a[name] is not None:
            b[name] = b[name].at[0].set(a[name][0])
    # Row 0's blocks hold the same keys in both pools, wherever they lie.
    n = -(-37 // BS)
    for name in ("pk", "pv"):
        if a[name] is not None:
            b[name] = b[name].at[:, b["tables"][0, :n]].set(
                a[name][:, a["tables"][0, :n]])
    for window in (None, 11):
        np.testing.assert_array_equal(
            _kernel(a, window)[0], _kernel(b, window)[0])


def test_a_trip_is_whole_blocks_from_the_shapes():
    # closed16: 4 KB a key of 16 heads x 128 bfloat16: 512 keys in 2 MB.
    assert kernel.trip_blocks(16, 16 * 128 * 2, 129) == 32
    # The latent row of 640 bfloat16: the most keys a trip takes.
    assert kernel.trip_blocks(16, 640 * 2, 705) == 64
    # A row shorter than a trip; a block larger than the budget.
    assert kernel.trip_blocks(16, 2 * 128 * 2, 5) == 5
    assert kernel.trip_blocks(4096, 32 * 128 * 2, 8) == 1


# -- what the engine counts of the two reads ---------------------------------


@pytest.mark.parametrize("window", [None, 11, 38, 500])
def test_the_kernel_walks_the_blocks_the_lengths_cover(window):
    lengths = [p for p in ROWS["ragged"] if p]
    lo = [0 if window is None else max(p - window + 1, 0) for p in lengths]
    walked = decode.paged_blocks_walked(
        lengths, BS, 6, NB, window, kernel=True)
    assert walked == sum(
        len({k // BS for k in range(first, p)})
        for p, first in zip(lengths, lo))


@pytest.mark.parametrize("window", [None, 11, 38, 500])
def test_the_loop_walks_every_row_as_far_as_the_longest(window):
    """``rows x trips x chunk_blocks``, the trips as the loop itself
    computes them on the device."""
    lengths = [p for p in ROWS["ragged"] if p]
    chunk_blocks = min(NB, decode.PAGED_CHUNK_KEYS // BS)
    keys = chunk_blocks * BS
    pos = np.asarray(lengths)
    if window is None:
        trips = -(-pos.max() // keys)
    else:
        first = np.maximum(pos - window + 1, 0) // keys
        trips = ((pos - 1) // keys - first + 1).max()
    assert decode.paged_blocks_walked(
        lengths, BS, 6, NB, window, kernel=False) == 6 * trips * chunk_blocks
    assert decode.paged_blocks_walked(
        [], BS, 6, NB, window, kernel=False) == 0


# -- the engine with the kernel in its decode program ------------------------


def _tiny_dense():
    from rayfed_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64)
    return cfg, tfm.init_params(jax.random.PRNGKey(0), cfg), {}


def _tiny_of(module):
    mod = __import__("tests." + module, fromlist=["CFG"])
    return mod.CFG, mod.PARAMS, {"prefix_reuse": False}


MODELS = {
    "dense": _tiny_dense,
    "hybrid": lambda: _tiny_of("test_falcon_h1"),
    "windowed": lambda: _tiny_of("test_cohere2_moe"),
    "latent": lambda: _tiny_of("test_pangu_ultra_moe"),
    # (Its windowed layers' read alone is the kernel: a latent pool under
    # a window; the indexed layers read single rows by a gather.)
    "selected": lambda: _tiny_of("test_dots3_note"),
}


def _serve(cfg, params, extra):
    from rayfed_tpu.config import ServingConfig
    from rayfed_tpu.serving.server import InferenceServer

    srv = InferenceServer(cfg, ServingConfig(
        max_slots=3, max_len=64, kv_block_size=4, prefill_chunk=8,
        prefill_token_budget=16, max_new_tokens=6, **extra),
        params=params, cache_dtype=cfg.compute_dtype)
    try:
        rng = np.random.default_rng(5)
        futs = [srv.submit(rng.integers(1, cfg.vocab, size=n).tolist(),
                           max_new_tokens=6, temperature=0.0)
                for n in (3, 21, 9, 14)]
        tokens = [f.result(timeout=600)["tokens"] for f in futs]
        return tokens, srv.stats()
    finally:
        srv.stop()


@pytest.mark.parametrize("model", list(MODELS))
def test_the_engine_serves_the_same_tokens_through_the_kernel(
        model, monkeypatch):
    """The five serving models through ``InferenceServer``, once as every
    CPU party runs them (the loop) and once with the decode read as the
    kernel (interpret mode; only ``decode`` and the engine are told they
    are on a TPU): the same greedy tokens, the same number of compiled
    programs (the kernel's inner ``jit`` is none of the engine's), and the
    blocks walked fall from every row at the longest's length to what the
    live rows' lengths cover."""
    from rayfed_tpu.serving import server

    cfg, params, extra = MODELS[model]()
    want, loop_stats = _serve(cfg, params, extra)
    on_tpu = types.SimpleNamespace(is_tpu_backend=lambda: True)
    monkeypatch.setattr(decode, "utils", on_tpu)
    monkeypatch.setattr(server, "utils", on_tpu)
    compiled = kernel.paged_read.__wrapped__
    monkeypatch.setattr(kernel, "paged_read", jax.jit(
        lambda *a, **kw: compiled(*a, **kw, interpret=True),
        static_argnames=("window", "scale", "v_width")))
    got, stats = _serve(cfg, params, extra)
    assert got == want
    assert stats["compiled_programs"] == loop_stats["compiled_programs"]
    assert stats["kv_blocks_attended"] == loop_stats["kv_blocks_attended"]
    if model == "selected":
        # (An indexer walks its index keys by the loop on every backend.)
        assert 0 < stats["kv_blocks_walked"] < loop_stats["kv_blocks_walked"]
        return
    assert 0 < stats["kv_blocks_walked"] <= stats["kv_blocks_attended"]
    assert loop_stats["kv_blocks_walked"] >= loop_stats["kv_blocks_attended"]


def test_importing_the_engine_imports_no_pallas():
    """Pallas costs a serving process half a second and more to import:
    the engine starts that on a thread of its own, on a TPU alone, and
    nothing under ``import rayfed_tpu.serving.server`` pays it."""
    code = ("import sys, rayfed_tpu, rayfed_tpu.serving.server\n"
            "bad = [m for m in sys.modules if 'pallas' in m]\n"
            "assert not bad, bad\n")
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
