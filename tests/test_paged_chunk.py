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

"""A prompt chunk against the paged pool (``decode.paged_chunk_attention``
and ``decode.paged_chunk_write``): the chunk reads its context through
the slot's block table and writes its own K/V in place; no contiguous row
of the slot exists.

Held here: a prompt put through in chunks attends what
``transformer.causal_attention`` attends over a contiguous row (plain and
grouped heads, a window shorter than the context, a ragged first chunk,
offsets that are no multiple of the block, a table that runs through the
pool in no order and ends in ungranted zeros), and leaves every block
outside the slot's table bit for bit; through the engine, each of the
three models serves a chunked prompt the tokens it serves the same prompt
prefilled in one round; ``chunk_blocks_read`` / ``chunk_blocks_row``
count what they say.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rayfed_tpu.config import ServingConfig  # noqa: E402
from rayfed_tpu.models import decode  # noqa: E402
from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.serving.server import InferenceServer  # noqa: E402
from rayfed_tpu.telemetry import metrics as telemetry_metrics  # noqa: E402
from tests import test_cohere2_moe as t_moe  # noqa: E402
from tests import test_falcon_h1 as t_hybrid  # noqa: E402

BS, LAYERS, DH = 4, 2, 8
C = 8                           # the padded chunk length
BLOCKS_PER_ROW = 7              # rows of 25 positions
N_PHYS = 1 + 2 * BLOCKS_PER_ROW


def _reference(q, k, v, q_pos, window):
    """Softmax attention of queries at ``q_pos`` over keys at 0..K-1 in
    float64, K/V head i serving its group of query heads, each query
    seeing the keys up to its own position and, under a window, the last
    ``window`` of them."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    groups = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, groups, axis=1), np.repeat(v, groups, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    k_pos = np.arange(k.shape[0])
    seen = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("ragged", [5, 2], ids=["ragged5", "ragged2"])
@pytest.mark.parametrize(
    "heads, kv_heads, window, dtype, tol",
    [(4, 4, None, jnp.float32, 1e-5), (4, 2, None, jnp.float32, 1e-5),
     (4, 2, 6, jnp.float32, 1e-5), (4, 1, 11, jnp.float32, 1e-5),
     (4, 2, None, jnp.bfloat16, 3e-2)],
    ids=["mha", "grouped", "window6", "window11", "grouped-bfloat16"],
)
def test_chunks_through_the_table_attend_what_a_contiguous_row_attends(
        heads, kv_heads, window, dtype, tol, ragged, monkeypatch):
    """A prompt of ``ragged + 16`` tokens as three chunks: the ragged
    rest first, padded to C with junk, then two whole chunks at offsets
    that are no multiple of the block. A trip holds two blocks, so the
    last chunk makes several and, under a window, starts past the row's
    first."""
    monkeypatch.setattr(decode, "CHUNK_TRIP_KEYS", 2 * BS)
    rng = np.random.default_rng(heads * 10 + kv_heads + (window or 0))
    total = ragged + 2 * C
    q = rng.normal(size=(LAYERS, total, heads, DH)).astype(np.float32)
    k = rng.normal(size=(LAYERS, total, kv_heads, DH)).astype(np.float32)
    v = rng.normal(size=(LAYERS, total, kv_heads, DH)).astype(np.float32)
    q, k, v = (np.asarray(jnp.asarray(a, dtype), np.float32)
               for a in (q, k, v))
    shape = (LAYERS, N_PHYS, BS, kv_heads, DH)
    # Stale values everywhere: a recycled pool, a neighbour's rows.
    pk = jnp.asarray(rng.normal(size=shape), dtype)
    pv = jnp.asarray(rng.normal(size=shape), dtype)
    order = 1 + rng.permutation(N_PHYS - 1)[:BLOCKS_PER_ROW]
    table = np.zeros(BLOCKS_PER_ROW, np.int32)

    def step(pk, pv, table, offset, n_real, q, k, v):
        attend = decode.paged_chunk_attention(
            pk, pv, table, offset, n_real, window=window)
        out = jnp.stack([attend(q[i], k[i], v[i], i * N_PHYS)
                         for i in range(LAYERS)])
        return (out, *decode.paged_chunk_write(pk, pv, k, v, table, offset))

    step = jax.jit(step)
    offset = 0
    for n_real in (ragged, C, C):
        # Blocks are granted as far as the chunk's last REAL position:
        # the table's tail is zeros, the padding's blocks among them.
        granted = (offset + n_real - 1) // BS + 1
        table[:granted] = order[:granted]
        chunk = np.zeros((3, LAYERS, C, heads, DH), np.float32)
        for j, a in enumerate((q, k, v)):
            chunk[j, :, :n_real, :a.shape[2]] = a[:, offset:offset + n_real]
            chunk[j, :, n_real:] = rng.normal(size=chunk[j, :, n_real:].shape)
        cq = jnp.asarray(chunk[0], dtype)
        ck, cv = (jnp.asarray(chunk[j][:, :, :kv_heads], dtype)
                  for j in (1, 2))
        before = (np.asarray(pk, np.float32), np.asarray(pv, np.float32))
        out, pk, pv = step(pk, pv, table, np.int32(offset), np.int32(n_real),
                           cq, ck, cv)
        out = np.asarray(out, np.float32)
        assert np.isfinite(out).all()       # the padded queries too
        end = offset + n_real
        for i in range(LAYERS):
            want = _reference(q[i, offset:end], k[i, :end], v[i, :end],
                              np.arange(offset, end), window)
            assert np.abs(out[i, :n_real] - want).max() < tol, (offset, i)
            if window is None:
                groups = heads // kv_heads
                dense = tfm.causal_attention(
                    jnp.asarray(q[None, i, offset:end], dtype),
                    jnp.asarray(np.repeat(k[None, i, :end], groups, 2), dtype),
                    jnp.asarray(np.repeat(v[None, i, :end], groups, 2), dtype),
                    q_offset=offset)[0]
                assert np.abs(out[i, :n_real] - np.asarray(
                    dense, np.float32)).max() < tol, (offset, i)
        # Outside the slot's granted blocks (and the sacrificial block,
        # where padding past them falls) nothing moved; inside, exactly
        # the chunk's real positions hold its K/V.
        others = np.setdiff1d(np.arange(1, N_PHYS), table[:granted])
        for old, new, mine in zip(before, (pk, pv), (ck, cv)):
            new = np.asarray(new, np.float32)
            assert np.array_equal(new[:, others], old[:, others])
            for p in range(offset, end):
                assert np.array_equal(
                    new[:, table[p // BS], p % BS],
                    np.asarray(mine, np.float32)[:, p - offset])
        offset = end


def test_a_chunk_at_offset_zero_reads_no_block(monkeypatch):
    """No trip runs for a first chunk: NaNs in every block of the pool
    reach no output (a cached block is gathered only when a trip holds
    keys before ``offset``)."""
    shape = (1, N_PHYS, BS, 2, DH)
    pk = jnp.full(shape, jnp.nan, jnp.float32)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(C, 4, DH)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(C, 2, DH)), jnp.float32)
    table = jnp.arange(1, 1 + BLOCKS_PER_ROW, dtype=jnp.int32)
    for window in (None, 3):
        out = decode.paged_chunk_attention(
            pk, pk, table, jnp.int32(0), jnp.int32(5), window=window)(
                q, k, k, 0)
        assert np.isfinite(np.asarray(out)).all()


# -- through the engine -----------------------------------------------------


def _dense():
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    return cfg, tfm.init_params(jax.random.PRNGKey(0), cfg), cfg.vocab


def _hybrid():
    return t_hybrid.CFG, t_hybrid.PARAMS, t_hybrid.TINY["vocab_size"]


def _moe():
    return t_moe.CFG, t_moe.PARAMS, t_moe.TINY["vocab_size"]


def _serve(model, prompt, chunk, n_new=6):
    cfg, params, _ = model
    srv = InferenceServer(cfg, ServingConfig(
        max_slots=3, max_len=64, kv_block_size=4, prefill_chunk=chunk,
        prefill_token_budget=2 * chunk, max_new_tokens=8,
        prefix_reuse=False), params=params, cache_dtype=cfg.compute_dtype)
    try:
        # A neighbour decodes beside the prompt's chunks.
        other = srv.submit(prompt[:3], max_new_tokens=8)
        out = srv.submit(prompt, max_new_tokens=n_new).result(timeout=300)
        other.result(timeout=300)
        return out["tokens"], srv.stats()
    finally:
        srv.stop()


@pytest.mark.parametrize("model", [_dense, _hybrid, _moe],
                         ids=["dense", "falcon_h1", "cohere2_moe"])
def test_a_chunked_prompt_is_served_the_tokens_of_one_prefill_round(model):
    """21 tokens as chunks of 8 (the ragged 5 first, then offsets 5 and
    13 over blocks of 4) against the same prompt through
    ``prefill_rows``: float32 at test size, so the tokens are equal."""
    model = model()
    prompt = np.random.default_rng(21).integers(
        1, model[2], size=21).tolist()
    chunked, st = _serve(model, prompt, chunk=8)
    whole, st_whole = _serve(model, prompt, chunk=32)
    assert (st["prefill_chunks"], st_whole["prefill_chunks"]) == (3, 0)
    assert chunked == whole


@pytest.mark.parametrize(
    "model, read",
    # Offsets 0, 5, 13 over blocks of 4: a layer that attends every key
    # gathers 0 + 2 + 4 blocks; one with a window of 8 starts the last
    # chunk at key 6, in block 1: 0 + 2 + 3.
    [(_dense, 2 * 6), (_moe, 1 * 6 + 3 * 5)], ids=["dense", "cohere2_moe"])
def test_chunk_block_counters_by_hand(model, read):
    cfg, _, vocab = model = model()
    prompt = np.random.default_rng(5).integers(1, vocab, size=21).tolist()
    reg = telemetry_metrics.get_registry()
    mirrors = [reg.get(f"fed_serving_chunk_blocks_{kind}_total")
               for kind in ("read", "row")]
    was = [m.labels(server="default").value() if m else 0 for m in mirrors]
    _, st = _serve(model, prompt, chunk=8)
    n_layers = cfg.n_layers
    blocks_per_row = -(-(64 + 1) // 4)
    assert st["chunk_blocks_read"] == read
    assert st["chunk_blocks_row"] == 3 * n_layers * blocks_per_row
    now = [reg.get(f"fed_serving_chunk_blocks_{kind}_total").labels(
        server="default").value() for kind in ("read", "row")]
    assert [b - a for a, b in zip(was, now)] == [
        st["chunk_blocks_read"], st["chunk_blocks_row"]]
