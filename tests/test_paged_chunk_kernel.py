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

"""A prompt chunk's read of the pool as a Pallas kernel
(``rayfed_tpu/ops/paged_chunk_attention.py``: what
``decode.paged_chunk_attention`` runs a trip as on a TPU backend, for a
pool whose slots reach far), in interpret mode against its definition,
the loop every other backend runs: every form the three long-context
models ask for, at the offsets an engine hands a chunk, the rule that
sends a pool to the kernel, the engine end to end with the kernel in its
chunk programs, and what the engine counts of it.
``tests/test_tpu_compile.py`` compiles the same kernel for a described
v5e inside the three models' chunk programs.
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
from rayfed_tpu.ops import paged_chunk_attention as kernel

BS, NB, LAYERS, C = 4, 24, 2, 8   # a block, blocks a slot, layers, a chunk
N_PHYS = 1 + NB

# (query heads, K/V heads or None: a latent pool read in the expanded
# form, width of the queries and keys, width of the values, window,
# whether each query attends a selection of its own)
FORMS = {
    # dots3's full layers: a latent row, each query its own set of keys.
    "latent-selected": (4, None, 24, 16, None, True),
    # dots3's sliding layers, and pangu without a window.
    "latent-window": (4, None, 24, 16, 13, False),
    "latent": (4, None, 24, 16, None, False),
    # commandaplus: G query heads a K/V head, with and without a window.
    "grouped": (8, 2, 16, 16, None, False),
    "grouped-window": (8, 2, 16, 16, 21, False),
}
# Where a chunk stands: the slot's first chunk (no trip), an offset that
# is no multiple of a trip (nor of a block), and a last chunk whose tail
# is padding; the last two make several trips.
CHUNKS = {
    "offset-0": (0, C), "offset-off-a-trip": (37, C),
    "padded-last-chunk": (66, 5),
}
LATENT = 20    # a latent row's width as the model defines it ...
PADDED = 32    # ... and as the pool keeps it (zeros beyond)


@pytest.fixture
def small_trips(monkeypatch):
    """Trips of 16 keys, so a slot of 96 makes several; the loop's trips
    at 8, so the two reads cut the context at different places."""
    monkeypatch.setattr(decode, "CHUNK_KERNEL_TRIP_KEYS", 16)
    monkeypatch.setattr(decode, "CHUNK_TRIP_KEYS", 8)
    monkeypatch.setattr(kernel, "KEY_TILE", 8)


def _as_kernel(monkeypatch, reach=0):
    """``decode`` told it is on a TPU and the kernel run in interpret
    mode: what a chunk program traces there."""
    monkeypatch.setattr(
        decode, "utils", types.SimpleNamespace(is_tpu_backend=lambda: True))
    monkeypatch.setattr(decode, "CHUNK_KERNEL_REACH", reach)
    compiled = kernel.chunk_trip.__wrapped__
    monkeypatch.setattr(kernel, "chunk_trip", jax.jit(
        lambda *a, **kw: compiled(*a, **kw, interpret=True),
        static_argnames=("scale",)))


def _case(form, dtype, seed=0):
    heads, kv_heads, d_qk, d_v, window, selected = FORMS[form]
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(
            dtype)

    case = dict(window=window, expand=None, seen=None)
    if kv_heads is None:
        pool = arr(LAYERS, N_PHYS, BS, PADDED).at[..., LATENT:].set(0)
        wk, wv = (w * LATENT ** -0.5 for w in (
            arr(LATENT, heads * d_qk), arr(LATENT, heads * d_v)))

        def expand(rows):
            rows = rows[:, 0, :LATENT]
            return ((rows @ wk).reshape(-1, heads, d_qk),
                    (rows @ wv).reshape(-1, heads, d_v))

        own = arr(C, 1, PADDED).at[..., LATENT:].set(0)
        case.update(pk=pool, pv=None, expand=expand, kv=expand(own))
    else:
        case.update(pk=arr(LAYERS, N_PHYS, BS, kv_heads, d_qk),
                    pv=arr(LAYERS, N_PHYS, BS, kv_heads, d_v),
                    kv=(arr(C, kv_heads, d_qk), arr(C, kv_heads, d_v)))
    case["q"] = arr(C, heads, d_qk)
    case["table"] = jnp.asarray(
        1 + rng.permutation(NB), jnp.int32)
    if selected:
        # Each query its own third of the positions; query 3's own key is
        # not among its keys, and no query has any of positions 16..31
        # (a whole trip of the kernel, two of the loop).
        seen = rng.random((C, NB * BS + C)) < 0.35
        seen[:, 16:32] = False
        case["selection"] = seen
    return case


def _attend(case, offset, n_real, layer=1):
    seen = None
    if "selection" in case:
        seen = case["selection"].copy()
        # Every query is given at least one key: position 0, and its own
        # but for query 3, whose own the selection leaves out.
        seen[:, 0] = True
        seen[np.arange(C), offset + np.arange(C)] = np.arange(C) != 3
        seen[4:, offset + 3] = True          # (the later queries keep it)
        seen = jnp.asarray(seen)
    attend = decode.paged_chunk_attention(
        case["pk"], case["pv"], case["table"], jnp.int32(offset),
        jnp.int32(n_real), window=case["window"])
    return attend(case["q"], *case["kv"], layer * N_PHYS, case["expand"],
                  seen)


@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("form", list(FORMS))
def test_the_kernel_is_the_loop_to_float32_rounding(
        form, chunk, small_trips, monkeypatch):
    case = _case(form, jnp.float32)
    offset, n_real = CHUNKS[chunk]
    want = _attend(case, offset, n_real)
    _as_kernel(monkeypatch)
    got = _attend(case, offset, n_real)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got)).all()      # the padded queries too
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("form", list(FORMS))
def test_the_kernel_reads_a_bfloat16_pool_as_the_loop_does(
        form, chunk, small_trips, monkeypatch):
    """The cells' dtype. Operands and probabilities are bfloat16 in both,
    scores and softmax float32: what differs is the order of float32
    sums, then one rounding of the output."""
    case = _case(form, jnp.bfloat16)
    offset, n_real = CHUNKS[chunk]
    want = np.asarray(_attend(case, offset, n_real), np.float32)
    _as_kernel(monkeypatch)
    got = np.asarray(_attend(case, offset, n_real), np.float32)
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -8)


def test_a_selection_may_leave_a_block_and_a_querys_own_key_out(
        small_trips, monkeypatch):
    """Under ``seen`` a whole trip holds none of any query's keys and
    query 3's own key is not selected: the softmax stays finite (no NaN
    from minus infinity less minus infinity) and query 3 attends exactly
    its selected keys: moving its own key or an unselected block changes
    nothing it returns."""
    _as_kernel(monkeypatch)
    case = _case("latent-selected", jnp.float32)
    offset = 37
    assert not case["selection"][:, 16:32].any()
    got = np.asarray(_attend(case, offset, C))
    assert np.isfinite(got).all()
    moved = dict(case)
    keys, values = case["kv"]
    moved["kv"] = (keys.at[3].add(5.0), values.at[3].add(5.0))
    # Positions 16..31 are blocks 4..7 of the slot.
    blocks = case["table"][4:8]
    moved["pk"] = case["pk"].at[:, blocks].add(3.0)
    again = np.asarray(_attend(moved, offset, C))
    np.testing.assert_array_equal(again[3], got[3])
    # (Query 4 sees key 3 where its selection kept it: the move is real.)
    assert not np.array_equal(again[4:], got[4:])


@pytest.mark.parametrize("form", ["latent-selected", "latent", "grouped"])
def test_a_padded_query_attends_itself(form, small_trips, monkeypatch):
    """Past ``n_real`` a query's scores are junk; it keeps the real keys
    and itself, so its softmax is never empty, and a real query's output
    does not depend on what the padding holds."""
    _as_kernel(monkeypatch)
    case = _case(form, jnp.float32)
    got = np.asarray(_attend(case, 66, 5))
    assert np.isfinite(got).all()
    junk = dict(case)
    junk["q"] = case["q"].at[5:].mul(-7.0)
    junk["kv"] = tuple(a.at[5:].add(11.0) for a in case["kv"])
    again = np.asarray(_attend(junk, 66, 5))
    np.testing.assert_array_equal(again[:5], got[:5])
    assert np.isfinite(again).all()


def test_a_trip_is_whole_blocks_and_whole_tiles_from_the_shapes():
    # dots3's full layers and pangu: the slot's reach in trips of 1,024.
    assert kernel.trip_keys(16, 33024, 1024) == 1024
    assert kernel.trip_keys(16, 11264, 1024) == 1024
    # commandaplus' sliding layers: 4,096 + 14 keys in five equal trips.
    assert kernel.trip_keys(16, 4110, 1024) == 896
    # dots3's sliding layers: 513 + 14 keys are one trip of five tiles.
    assert kernel.trip_keys(16, 527, 1024) == 640
    # A block that is no divisor of a tile.
    assert kernel.trip_keys(48, 527, 1024) == 768


# -- the rule ----------------------------------------------------------------


def _pool(reach, bs=16, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((2, 8, bs, 640), dtype), reach // bs


@pytest.mark.parametrize("why, on_tpu, reach, dtype, block, want", [
    ("dots3", True, 33024, jnp.bfloat16, None, True),
    ("commandaplus", True, 12800, jnp.bfloat16, None, True),
    ("pangu", True, 11264, jnp.bfloat16, None, True),
    ("float32-pool", True, 11264, jnp.float32, None, True),
    ("off-a-tpu", False, 33024, jnp.bfloat16, None, False),
    ("closed16", True, 2048, jnp.bfloat16, None, False),
    ("chat-steady", True, 1280, jnp.bfloat16, None, False),
    ("falconh1", True, 768, jnp.bfloat16, None, False),
    ("sdar30b", True, 1024, jnp.bfloat16, 4, False),
    ("a-block-mask-however-far", True, 33024, jnp.bfloat16, 4, False),
    ("a-dtype-it-does-not-take", True, 33024, jnp.float16, None, False),
])
def test_the_reach_of_a_slot_sends_a_pool_to_the_kernel(
        why, on_tpu, reach, dtype, block, want, monkeypatch):
    monkeypatch.setattr(decode, "utils", types.SimpleNamespace(
        is_tpu_backend=lambda: on_tpu))
    pk, blocks_per_row = _pool(reach, dtype=dtype)
    assert decode.paged_chunk_is_kernel(
        pk, None, blocks_per_row, block=block) is want
    assert 2048 < decode.CHUNK_KERNEL_REACH <= 11264


def test_off_a_tpu_a_chunk_is_the_loop():
    """What a CPU party runs: no Pallas call in the chunk's program."""
    pk, blocks_per_row = _pool(33024)
    assert not decode.paged_chunk_is_kernel(pk, None, blocks_per_row)
    case = _case("latent", jnp.float32)
    text = jax.jit(lambda q: decode.paged_chunk_attention(
        case["pk"], None, case["table"], jnp.int32(37), jnp.int32(C))(
            q, *case["kv"], 0, case["expand"])).lower(case["q"]).as_text()
    assert "while" in text and "custom_call" not in text


# -- the engine with the kernel in its chunk programs ------------------------


def _tiny_of(module):
    mod = __import__("tests." + module, fromlist=["CFG"])
    return mod.CFG, mod.PARAMS


MODELS = {
    "windowed": "test_cohere2_moe",     # K and V arrays, two kinds of layer
    "latent": "test_pangu_ultra_moe",   # one latent array, expanded
    "selected": "test_dots3_note",      # two latent arrays, a selection
}


def _serve(cfg, params):
    from rayfed_tpu.config import ServingConfig
    from rayfed_tpu.serving.server import InferenceServer

    srv = InferenceServer(cfg, ServingConfig(
        max_slots=3, max_len=64, kv_block_size=4, prefill_chunk=8,
        prefill_token_budget=16, max_new_tokens=6, prefix_reuse=False),
        params=params, cache_dtype=cfg.compute_dtype)
    try:
        rng = np.random.default_rng(5)
        futs = [srv.submit(rng.integers(1, cfg.vocab, size=n).tolist(),
                           max_new_tokens=6, temperature=0.0)
                for n in (3, 37, 9, 30)]
        tokens = [f.result(timeout=600)["tokens"] for f in futs]
        return tokens, srv.stats()
    finally:
        srv.stop()


@pytest.mark.parametrize("model", list(MODELS))
def test_the_engine_serves_the_same_tokens_through_the_kernel(
        model, small_trips, monkeypatch):
    """The three long-context models through ``InferenceServer``, once as
    every CPU party runs them (the loop) and once with a chunk's trips as
    the kernel (interpret mode; only ``decode`` is told it is on a TPU,
    so the decode step keeps its loop): the same greedy tokens, the same
    number of compiled programs (the kernel's inner ``jit`` is none of
    the engine's), and every chunk counted as the kernel's."""
    cfg, params = _tiny_of(MODELS[model])
    want, loop_stats = _serve(cfg, params)
    assert loop_stats["prefill_chunks"] > 0
    assert loop_stats["prefill_chunks_kernel"] == 0
    _as_kernel(monkeypatch)
    monkeypatch.setattr(decode, "paged_read_is_kernel", lambda *a: False)
    got, stats = _serve(cfg, params)
    assert got == want
    assert stats["compiled_programs"] == loop_stats["compiled_programs"]
    assert stats["prefill_chunks"] == loop_stats["prefill_chunks"]
    assert stats["prefill_chunks_kernel"] == stats["prefill_chunks"]


def test_importing_the_engine_still_imports_no_pallas():
    """Nor does ``decode``: the kernel's module is imported where a chunk
    program that calls it is traced, and on the engine's import thread."""
    code = ("import sys, rayfed_tpu, rayfed_tpu.serving.server\n"
            "import rayfed_tpu.models.decode\n"
            "bad = [m for m in sys.modules if 'pallas' in m\n"
            "       or m == 'rayfed_tpu.ops.paged_chunk_attention']\n"
            "assert not bad, bad\n")
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
