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

"""The paged decode step (``decode.paged_decode_step``): one program
that reads K/V through the block tables and writes the new token's K/V
in place.

The pool's program ends in the choice of each row's token and returns
ids; where a test needs the logits it runs the model's own ``decode_step``
on the pool's arrays (``tests.utils.step_logits``).

Held here: its logits against ``decode.forward_with_cache`` on a
contiguous cache at mixed lengths; a row's logits bit for bit whatever
shares its batch; junk rows touch block 0 only; a step changes exactly
each live row's (block, offset); the engine's ``kv_blocks_attended`` /
``kv_blocks_slab`` count what they say; one compiled variant serves
every length.
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
from rayfed_tpu.serving.kv_pool import PagedKVPool  # noqa: E402
from rayfed_tpu.serving.server import InferenceServer  # noqa: E402
from tests.utils import land_row, step_logits  # noqa: E402

BS = 4
MAX_LEN = 24
# 1, a block boundary - 1, a boundary, boundary + 1, max_len - 1.
LENGTHS = (1, 2 * BS - 1, 2 * BS, 2 * BS + 1, MAX_LEN - 1)
# The sampler's scalars with every row greedy (``sampling.pack`` of zeros).
GREEDY = np.zeros((3, len(LENGTHS)), np.int32)


def _setup(dtype, lengths, seed=0):
    """A pool whose slot ``slots[r]`` holds ``lengths[r]`` cached
    positions of a seeded sequence (written by the contiguous-cache
    forward) and the same rows as a contiguous cache. ``tokens``,
    ``positions`` and ``tables`` are the step's inputs, indexed by slot."""
    cfg = tfm.tiny_config(compute_dtype=dtype)
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    rows = len(lengths)
    pool = PagedKVPool(cfg, max_slots=rows, max_len=MAX_LEN, block_size=BS)
    rng = np.random.default_rng(seed)
    seqs = rng.integers(1, cfg.vocab, size=(rows, MAX_LEN)).astype(np.int32)
    cache = decode.init_cache(cfg, rows, pool.row_len)
    slots = []
    tokens = np.zeros(rows, np.int32)
    positions = np.zeros(rows, np.int32)
    tables = np.zeros((rows, pool.blocks_per_row), np.int32)
    for r, n in enumerate(lengths):
        # Row r alone, positions [0, n): later positions stay zero.
        _, row = decode.forward_with_cache(
            params, jnp.asarray(seqs[r:r + 1, :n]),
            {"k": cache["k"][:, r:r + 1], "v": cache["v"][:, r:r + 1]},
            0, cfg,
        )
        cache = {
            "k": cache["k"].at[:, r:r + 1].set(row["k"]),
            "v": cache["v"].at[:, r:r + 1].set(row["v"]),
        }
        slot = pool.acquire()
        assert pool.ensure_blocks(slot, n) == "ok"
        land_row(pool, slot, cache["k"][:, r], cache["v"][:, r])
        slots.append(slot)
        tokens[slot], positions[slot] = seqs[r, n], n
        tables[slot] = pool.table(slot)
    return cfg, params, pool, cache, slots, tokens, positions, tables


def _reference_logits(cfg, params, cache, slots, tokens, positions):
    out = []
    for r, slot in enumerate(slots):
        logits, _ = decode.forward_with_cache(
            params, jnp.asarray(tokens[slot:slot + 1, None]),
            {"k": cache["k"][:, r:r + 1], "v": cache["v"][:, r:r + 1]},
            int(positions[slot]), cfg,
        )
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


@pytest.mark.parametrize(
    "dtype, tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
    ids=["float32", "bfloat16"],
)
def test_paged_step_matches_contiguous_cache_at_mixed_lengths(dtype, tol):
    cfg, params, pool, cache, slots, tokens, positions, tables = _setup(
        dtype, LENGTHS)
    logits = np.asarray(step_logits(pool, params, tokens, positions, tables))
    ref = _reference_logits(cfg, params, cache, slots, tokens, positions)
    # The tolerance is a share of the logits' scale: in bfloat16 the two
    # programs round activations at different places (the contiguous path
    # itself stands 0.046 from the float32 logits at a scale of 3.6).
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(logits[slots], ref, atol=tol * scale, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_row_alone_equals_row_among_neighbours_bitwise(dtype, monkeypatch):
    # Two blocks a chunk: the longest neighbour makes the loop run trips
    # that lie wholly past the shorter rows' lengths.
    monkeypatch.setattr(decode, "PAGED_CHUNK_KEYS", 2 * BS)
    _, params, pool, _, slots, tokens, positions, tables = _setup(
        dtype, LENGTHS)
    k0, v0 = (np.asarray(a) for a in pool.kv)
    together = np.asarray(step_logits(pool, params, tokens, positions, tables))
    for slot in slots:
        pool._kv = (jnp.asarray(k0), jnp.asarray(v0))
        tok, pos, tab = (np.zeros_like(a) for a in (tokens, positions, tables))
        tok[slot], pos[slot], tab[slot] = (
            tokens[slot], positions[slot], tables[slot])
        alone = np.asarray(step_logits(pool, params, tok, pos, tab))
        np.testing.assert_array_equal(alone[slot], together[slot])


def test_junk_rows_write_block_zero_only():
    _, params, pool, _, _, tokens, positions, tables = _setup(
        jnp.float32, LENGTHS)
    k0, v0 = (np.asarray(a) for a in pool.kv)
    pool.decode_step(
        params, tokens, np.zeros_like(positions), np.zeros_like(tables),
        GREEDY)
    k1, v1 = (np.asarray(a) for a in pool.kv)
    # Every granted block is bit-unchanged; block 0 took the junk write,
    # at offset 0.
    np.testing.assert_array_equal(k1[:, 1:], k0[:, 1:])
    np.testing.assert_array_equal(v1[:, 1:], v0[:, 1:])
    assert np.any(k1[:, 0, 0] != k0[:, 0, 0])
    np.testing.assert_array_equal(k1[:, 0, 1:], k0[:, 0, 1:])


def test_step_changes_only_each_live_rows_block_and_offset():
    _, params, pool, _, slots, tokens, positions, tables = _setup(
        jnp.float32, LENGTHS)
    k0, v0 = (np.asarray(a) for a in pool.kv)
    pool.decode_step(params, tokens, positions, tables, GREEDY)
    k1, v1 = (np.asarray(a) for a in pool.kv)
    written = np.zeros(k0.shape[1:3], bool)
    for slot in slots:
        n = int(positions[slot])
        block, off = tables[slot, n // BS], n % BS
        assert block > 0
        written[block, off] = True
        assert np.any(k1[:, block, off] != k0[:, block, off])
        assert np.any(v1[:, block, off] != v0[:, block, off])
    assert written.sum() == len(LENGTHS)
    np.testing.assert_array_equal(k1[:, ~written], k0[:, ~written])
    np.testing.assert_array_equal(v1[:, ~written], v0[:, ~written])


def test_engine_counts_blocks_attended_beside_a_slabs():
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServingConfig(max_slots=3, max_len=MAX_LEN, kv_block_size=BS,
                         max_new_tokens=8)
    srv = InferenceServer(cfg, scfg, params=params, name="paged-count")
    try:
        plen, n_new = 6, 5
        srv.submit_and_wait(list(range(1, plen + 1)), max_new_tokens=n_new)
        st = srv.stats()
    finally:
        srv.stop()
    # The prefill samples token 1; steps run at positions plen .. plen+n-2.
    steps = n_new - 1
    assert st["steps"] == steps
    assert st["kv_blocks_attended"] == sum(
        (plen + i) // BS + 1 for i in range(steps))
    assert st["kv_blocks_slab"] == steps * 3 * srv.pool.blocks_per_row
    from rayfed_tpu.telemetry import metrics as telemetry_metrics

    reg = telemetry_metrics.get_registry()
    for key in ("kv_blocks_attended", "kv_blocks_slab"):
        series = reg.get(f"fed_serving_{key}_total")
        assert series.labels(server="paged-count").value() == st[key]


def test_one_compiled_program_serves_every_length(monkeypatch):
    monkeypatch.setattr(decode, "PAGED_CHUNK_KEYS", 2 * BS)
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    pool = PagedKVPool(cfg, max_slots=2, max_len=MAX_LEN, block_size=BS)
    slot = pool.acquire()
    tokens = np.zeros(2, np.int32)
    positions = np.zeros(2, np.int32)
    tables = np.zeros((2, pool.blocks_per_row), np.int32)
    for pos in range(1, MAX_LEN):
        assert pool.ensure_blocks(slot, pos) == "ok"
        tables[slot] = pool.table(slot)
        tokens[slot], positions[slot] = 1 + pos, pos
        ids = np.asarray(pool.decode_step(
            params, tokens, positions, tables, np.zeros((3, 2), np.int32)))
        assert ids.shape == (2,) and 0 <= ids[slot] < cfg.vocab
    assert pool._decode_step_fn._cache_size() == 1
