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

"""Falcon-H1 (attention and a Mamba-2 mixer side by side) through the
serving engine, against the repo's one plain reference
(``chipbench/references/falcon_h1.py``: float32, the recurrence as a scan
over time, no cache, no chunks) on seeded weights at a tiny size.

What a recurrent state asks of a cache manager that K/V never did is
tested here where it can be tested bit for bit: padding never advances a
state, a row that sits a step out keeps it, a slot starts from zero, rows
do not mix.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import seeded_falcon_h1 as seeded
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import decode
from rayfed_tpu.models import falcon_h1 as fh
from rayfed_tpu.models import transformer as tfm
from rayfed_tpu.serving import sampling
from rayfed_tpu.serving.kv_pool import PagedKVPool
from rayfed_tpu.serving.server import InferenceServer
from tests.utils import step_logits

ref = importlib.import_module("chipbench.references.falcon_h1")

# Published keys at a tiny size; every multiplier off 1.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 176,
    "num_hidden_layers": 2, "mamba_d_ssm": 64, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_d_state": 8, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "rope_theta": 1e11,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1.25,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
}
HP = ref.hyper_of(TINY)
CHUNK = 16          # serving.prefill_chunk in the engine tests
MAX_LEN = 64
# float32 program against the float32 reference; and the share of the
# logits' scale (std 1, widest about 4) that bfloat16 rounding may move
# them: the bfloat16 program stands 0.04 from the reference at this size.
TOL32, TOL16 = 1e-4, 0.08


def _weights(dtype, seed=3):
    w = seeded.make_canonical(seeded.key_of(seed), TINY, dtype)
    cfg = fh.FalconH1Config.from_published(
        TINY, compute_dtype=dtype, param_dtype=dtype)
    return cfg, w, seeded.to_program_tree(w, TINY)


CFG, W, PARAMS = _weights(jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).astype(np.int32)


def _ref_logits(seq, w=W, hp=HP):
    return np.asarray(ref.forward(w, jnp.asarray(seq, jnp.int32), hp))


def _server(cfg=CFG, params=PARAMS, **kw):
    base = dict(max_slots=4, max_len=MAX_LEN, kv_block_size=8,
                prefill_chunk=CHUNK, prefill_token_budget=2 * CHUNK,
                max_new_tokens=8, prefix_reuse=False)
    base.update(kw)
    return InferenceServer(cfg, ServingConfig(**base), params=params,
                           cache_dtype=cfg.compute_dtype)


def _record_logits(monkeypatch, seed):
    """Every logits row the engine's programs choose a token from for the
    request submitted with ``seed`` (greedy: the seed only marks its row),
    by the token's position in the output. The sampler is looked up when
    a program is traced, so engines built after this call record; a
    chunk that is not the prompt's last also reaches the sampler at
    position 0, and the last one, which comes last, is the one kept."""
    seen = {}
    choose = sampling.choose_tokens

    def record(logits, seeds, index):
        for row in np.flatnonzero(seeds == seed):
            seen[int(index[row])] = np.array(logits[row])

    def spy(logits, temperature, seeds, index):
        jax.debug.callback(record, logits, seeds, index, ordered=True)
        return choose(logits, temperature, seeds, index)

    monkeypatch.setattr(sampling, "choose_tokens", spy)
    return seen


# -- the model against the reference ----------------------------------------


@pytest.mark.parametrize(
    "dtype, tol", [(jnp.float32, TOL32), (jnp.bfloat16, TOL16)],
    ids=["float32", "bfloat16"],
)
def test_forward_matches_the_plain_reference(dtype, tol):
    cfg, w, params = _weights(dtype)
    toks = _tokens(37)
    want = _ref_logits(toks, w)
    got = np.asarray(jax.jit(lambda p, t: fh.forward(p, t, cfg))(
        params, jnp.asarray(toks[None])))[0]
    assert 0.5 < want.std() < 2.0, "the logits' scale the tolerance assumes"
    assert np.abs(got - want).max() < tol


def test_chunked_form_is_the_one_step_form():
    """The SSD form over a sequence == the one-step form position by
    position, whatever the chunk size (both == the reference's scan by the
    test above)."""
    rng = np.random.default_rng(5)
    layer = jax.tree_util.tree_map(lambda t: t[0], PARAMS["layers"])
    s, hs, p, g, n = 21, CFG.ssm_heads, CFG.ssm_head_dim, 2, CFG.ssm_state
    x = jnp.asarray(rng.normal(size=(1, s, hs, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(1, s, hs)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1, s, g, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(1, s, g, n)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(1, hs, p, n)), jnp.float32)
    st, ys = s0, []
    for t in range(s):
        y, st = fh.ssm_step(x[:, t], dt[:, t], b[:, t], c[:, t], layer, st,
                            CFG)
        ys.append(y)
    want_y, want_s = np.stack([np.asarray(y) for y in ys], 1), np.asarray(st)
    for chunk in (4, 8, 64):
        cfg = fh.FalconH1Config.from_published(
            dict(TINY, mamba_chunk_size=chunk), compute_dtype=jnp.float32,
            param_dtype=jnp.float32)
        y, state = fh.ssd_scan(x, dt, b, c, layer, s0, cfg)
        np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-4)
        np.testing.assert_allclose(np.asarray(state), want_s, atol=2e-4)


MULTIPLIERS = [
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers.0", "ssm_multipliers.1",
    "ssm_multipliers.2", "ssm_multipliers.3", "ssm_multipliers.4",
    "mlp_multipliers.0", "mlp_multipliers.1",
]


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_every_multiplier_matters_and_matches_the_reference(name):
    """One multiplier moved (the weights held): the program's logits move,
    and by what the reference's move."""
    model = dict(TINY, ssm_multipliers=list(TINY["ssm_multipliers"]),
                 mlp_multipliers=list(TINY["mlp_multipliers"]))
    key, _, i = name.partition(".")
    if i:
        model[key][int(i)] *= 1.7
    else:
        model[key] *= 1.7
    cfg = fh.FalconH1Config.from_published(
        model, compute_dtype=jnp.float32, param_dtype=jnp.float32)
    toks = _tokens(19, seed=2)
    got = np.asarray(fh.forward(PARAMS, jnp.asarray(toks[None]), cfg))[0]
    base = np.asarray(fh.forward(PARAMS, jnp.asarray(toks[None]), CFG))[0]
    assert np.abs(got - base).max() > 1e-2, "the multiplier is not applied"
    want = _ref_logits(toks, hp=ref.hyper_of(model))
    assert np.abs(got - want).max() < TOL32


# -- prefill then decode through the engine ------------------------------------


@pytest.mark.parametrize(
    "plen", [1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 5],
    ids=["one", "chunk-1", "chunk", "chunk+1", "two-chunks-and-a-rest"],
)
def test_prefill_then_decode_matches_the_reference_forward(plen, monkeypatch):
    """Every logits row the engine chooses a token from (the prefill's
    last position, then each decode step through the cache and the
    carried state) == the reference's full forward over prompt + served
    tokens."""
    seen = _record_logits(monkeypatch, seed=4242)
    srv = _server()
    try:
        prompt = _tokens(plen, seed=plen).tolist()
        out = srv.submit(prompt, max_new_tokens=6, seed=4242).result(
            timeout=300)
        got = np.stack([seen[i] for i in range(6)])
        want = _ref_logits(prompt + out["tokens"][:-1])[plen - 1:]
        assert got.shape == want.shape
        assert np.abs(got - want).max() < TOL32
        st = srv.stats()
        assert st["state_resets"] == 1
        assert st["prefill_chunks"] == (0 if plen <= CHUNK
                                        else -(-plen // CHUNK))
    finally:
        srv.stop()


def _prefill(prompts, last_idx, landed=None, cfg=CFG, params=PARAMS):
    landed = None if landed is None else jnp.asarray(landed)
    return jax.jit(lambda p, t, i, w: fh.prefill_rows(
        p, t, i, MAX_LEN + 1, jnp.float32, cfg, w))(
            params, jnp.asarray(prompts), jnp.asarray(last_idx), landed)


def test_padding_never_advances_a_state():
    """A right-padded bucket row ends in the state of its last real token:
    bit for bit whatever the padding holds (a padded position is an exact
    no-op: ``exp(0) = 1``, ``0 * x B^T = 0``), and the state of the run
    that was never padded (another program shape, so to rounding)."""
    n, bucket = 16, 32
    toks = _tokens(n, seed=9)
    rows = np.zeros((3, bucket), np.int32)
    rows[0, :n] = rows[1, :n] = toks
    rows[1, n:] = _tokens(bucket - n, seed=10)      # junk in the padding
    last_idx = np.array([n - 1, n - 1, 0], np.int32)
    last, _, _, state = _prefill(rows, last_idx)
    for name in ("conv", "ssm"):
        a = np.asarray(state[name])
        assert np.array_equal(a[:, 0], a[:, 1]), name
    assert np.array_equal(np.asarray(last[0]), np.asarray(last[1]))
    last_u, _, _, state_u = _prefill(toks[None], np.array([n - 1], np.int32))
    for name in ("conv", "ssm"):
        np.testing.assert_allclose(
            np.asarray(state[name])[:, 0], np.asarray(state_u[name])[:, 0],
            atol=1e-5, err_msg=name)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(last_u[0]),
                               atol=1e-5)
    # The tail kept is that of the last three REAL inputs, not of padding.
    assert np.abs(np.asarray(state["conv"])[:, 0]).min() > 0


def test_an_admission_round_computes_its_landed_rows_and_no_other():
    """One program whatever the round holds: a landed row is what it is in
    a round where every row landed, bit for bit; a lane that is no request
    comes back zero (its K/V goes to the sacrificial block, its state
    lands nowhere)."""
    bucket, lengths = 32, [9, 20, 31, 4]
    rows = np.zeros((4, bucket), np.int32)
    for r, n in enumerate(lengths):
        rows[r, :n] = _tokens(n, seed=40 + r)
    last_idx = np.array(lengths, np.int32) - 1
    full = jax.tree_util.tree_leaves(_prefill(rows, last_idx))
    for landed in ([True, False, False, True], [False, False, True, False]):
        landed = np.array(landed)
        part = jax.tree_util.tree_leaves(_prefill(rows, last_idx, landed))
        for a, b in zip(full, part):
            a, b = np.asarray(a), np.asarray(b)
            axis = 0 if a.ndim == 2 else 1          # logits (R, V): rows first
            a, b = np.moveaxis(a, axis, 0), np.moveaxis(b, axis, 0)
            assert np.array_equal(a[landed], b[landed])
            assert not b[~landed].any()


def test_padded_ragged_chunk_ends_in_the_last_real_tokens_state():
    """The chunk program: a ragged first chunk padded to its bucket hands
    on the state of its last real token, whatever the padding holds, and
    starts from zero whatever its slot's rows held; the other slot's rows
    of the state come back bit for bit."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN,
                       dtype=jnp.float32, block_size=8)
    other, slot = pool.acquire(), pool.acquire()
    assert pool.ensure_blocks(slot, CHUNK) == "ok"
    rng = np.random.default_rng(3)
    held = {name: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for name, a in pool.state.items()}
    chunk = jax.jit(lambda *a: fh.chunk(*a, CFG))
    real = 5
    outs = []
    for junk_seed in (1, 2):
        toks = _tokens(8, seed=junk_seed)
        toks[:real] = _tokens(real, seed=7)
        outs.append(chunk(PARAMS, *pool.kv, held, pool.table(slot),
                          np.int32(slot), jnp.asarray(toks), np.int32(0),
                          np.int32(real)))
    for name in ("conv", "ssm"):
        new = [np.asarray(o[3][name]) for o in outs]
        assert np.array_equal(new[0][:, slot], new[1][:, slot]), name
        assert np.array_equal(new[0][:, other],
                              np.asarray(held[name])[:, other]), name
        assert not np.array_equal(new[0][:, slot],
                                  np.asarray(held[name])[:, slot]), name
    assert np.array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
    want = _ref_logits(_tokens(real, seed=7))[-1]
    assert np.abs(np.asarray(outs[0][0]) - want).max() < TOL32


# -- rows stay independent, held rows keep their state ----------------------------


def _pool_with_rows(lengths, seed=0):
    """A pool whose slot r holds ``lengths[r]`` prefilled positions of a
    seeded sequence and the state after them; returns the decode step's
    inputs (indexed by slot) and each row's slot."""
    rows = len(lengths)
    pool = PagedKVPool(CFG, max_slots=rows, max_len=MAX_LEN,
                       dtype=jnp.float32, block_size=8)
    bucket = 32
    seqs = [_tokens(n + 1, seed=seed + r) for r, n in enumerate(lengths)]
    slots = []
    prompts = np.zeros((rows, bucket), np.int32)
    last_idx = np.zeros(rows, np.int32)
    tables = np.zeros((rows, pool.blocks_per_row), np.int32)
    tokens = np.zeros(rows, np.int32)
    positions = np.zeros(rows, np.int32)
    for r, n in enumerate(lengths):
        slot = pool.acquire()
        slots.append(slot)
        assert pool.ensure_blocks(slot, n) == "ok"
        prompts[slot, :n] = seqs[r][:n]
        last_idx[slot] = n - 1
        tables[slot] = pool.table(slot)
        tokens[slot], positions[slot] = seqs[r][n], n
    _, k, v, state = _prefill(prompts, last_idx)
    pool.scatter_rows(k, v, tables, state, np.ones(rows, bool))
    return pool, slots, tokens, positions, tables


def _state_of(pool):
    return {k: np.array(v) for k, v in pool.state.items()}


def test_a_row_alone_is_the_row_among_neighbours_bitwise():
    lengths = [5, 17, 30]
    pool, slots, tokens, positions, tables = _pool_with_rows(lengths)
    before = _state_of(pool)
    together = np.asarray(step_logits(
        pool, PARAMS, tokens, positions, tables, np.ones(3, bool)))
    after = _state_of(pool)
    for r in range(3):
        solo, _, _, _, _ = _pool_with_rows(lengths)
        live = np.arange(3) == r
        alone = np.asarray(step_logits(
            solo, PARAMS, tokens * live, positions * live,
            tables * live[:, None], live))
        assert np.array_equal(alone[r], together[r]), r
        solo_state = _state_of(solo)
        for name in before:
            # The live row advanced exactly as among neighbours; the two
            # that sat the step out kept their state bit for bit.
            assert np.array_equal(solo_state[name][:, r],
                                  after[name][:, r]), (name, r)
            others = [i for i in range(3) if i != r]
            assert np.array_equal(solo_state[name][:, others],
                                  before[name][:, others]), (name, r)
    # And each row's logits are the reference's at its position.
    for r, n in enumerate(lengths):
        seq = _tokens(n + 1, seed=r)
        assert np.abs(together[slots[r]] - _ref_logits(seq)[n]).max() < TOL32


def test_a_held_rows_next_token_is_what_it_would_have_been():
    """Row 1 sits two steps out (as a stalled row does) while row 0
    decodes; when it steps again its logits are those of a row that never
    waited."""
    lengths = [9, 12]
    pool, _, tokens, positions, tables = _pool_with_rows(lengths)
    base, *_ = _pool_with_rows(lengths)
    want = np.asarray(step_logits(
        base, PARAMS, tokens, positions, tables, np.ones(2, bool)))[1]
    live0 = np.array([True, False])
    tok, pos = tokens.copy(), positions.copy()
    for _ in range(2):
        logits = np.asarray(step_logits(
            pool, PARAMS, tok * live0, pos * live0, tables * live0[:, None],
            live0))
        tok[0], pos[0] = int(logits[0].argmax()), pos[0] + 1
    got = np.asarray(step_logits(
        pool, PARAMS, tok, pos, tables, np.ones(2, bool)))[1]
    assert np.array_equal(got, want)


def test_zeroing_the_state_moves_the_logits_beyond_the_tolerance():
    """The comparison can see the state: the same step from a zeroed state
    lands far outside what rounding explains."""
    pool, _, tokens, positions, tables = _pool_with_rows([20])
    fresh, *_ = _pool_with_rows([20])
    fresh._state = jax.tree_util.tree_map(jnp.zeros_like, fresh._state)
    live = np.ones(1, bool)
    good = np.asarray(step_logits(pool, PARAMS, tokens, positions, tables,
                                  live))[0]
    bad = np.asarray(step_logits(fresh, PARAMS, tokens, positions, tables,
                                 live))[0]
    assert np.abs(good - bad).max() > 10 * TOL16


def test_a_recycled_slot_starts_from_zero():
    """One slot, a long request then a short one: the second gets the
    tokens it gets alone in a fresh engine (both prefill paths)."""
    long_prompt = _tokens(2 * CHUNK + 3, seed=21).tolist()
    for plen in (7, CHUNK + 4):
        prompt = _tokens(plen, seed=22).tolist()
        srv = _server(max_slots=1)
        try:
            srv.submit(long_prompt, max_new_tokens=8).result(timeout=300)
            after_long = srv.submit(prompt, max_new_tokens=8).result(300)
            assert srv.stats()["state_resets"] == 2
        finally:
            srv.stop()
        srv = _server(max_slots=1)
        try:
            alone = srv.submit(prompt, max_new_tokens=8).result(timeout=300)
        finally:
            srv.stop()
        assert after_long["tokens"] == alone["tokens"], plen
        want = _ref_logits(prompt + alone["tokens"][:-1])[plen - 1:]
        assert [int(t) for t in want.argmax(-1)] == alone["tokens"]


def test_concurrent_requests_and_preemption_replay_from_a_zero_state():
    """Six requests over four slots, then the same under block pressure
    that only preemption can break: a preempted request re-runs from an
    empty cache and a zero state, and nobody's tokens change."""
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(1, 255, size=8)]
               for _ in range(6)]

    def run(**kw):
        srv = _server(max_slots=4, kv_block_size=4, max_len=32, **kw)
        try:
            futs = [srv.submit(p, max_new_tokens=8, seed=i)
                    for i, p in enumerate(prompts)]
            return [f.result(timeout=300)["tokens"] for f in futs], srv.stats()
        finally:
            srv.stop()

    base, st = run()
    assert st["ssm_state_bytes"] > 0 and st["state_resets"] == 6
    tight, st = run(kv_blocks=12)
    assert tight == base
    assert st["preempted"] >= 1 and st["state_resets"] > 6
    for p, toks in zip(prompts, base):
        want = _ref_logits(p + toks[:-1])[len(p) - 1:]
        assert [int(t) for t in want.argmax(-1)] == toks


def test_a_row_between_two_chunks_of_its_prompt_is_held():
    """A short request decodes while a long prompt goes in a chunk an
    iteration: the long row's state waits in the pool between its chunks
    (counted), and both get the tokens they get alone."""
    short, long_ = _tokens(6, seed=31).tolist(), _tokens(
        3 * CHUNK + 2, seed=32).tolist()

    def run(prompts):
        srv = _server(max_slots=2, prefill_token_budget=CHUNK)
        try:
            futs = [srv.submit(p, max_new_tokens=8) for p in prompts]
            return [f.result(timeout=300)["tokens"] for f in futs], srv.stats()
        finally:
            srv.stop()

    both, st = run([short, long_])
    assert st["state_rows_held"] >= 1 and st["prefill_chunks"] == 4
    assert both == [run([short])[0][0], run([long_])[0][0]]


# -- grouped heads ---------------------------------------------------------------


def _attend_pr25(pk, pv, positions, tables, q, k1, v1, base):
    """``paged_decode_step``'s read through the table as PR 25 left it (one
    head count), kept here as the oracle for the grouped read."""
    n_layers, n_phys, bs, n_heads, dh = pk.shape
    n_rows, blocks_per_row = tables.shape
    chunk_blocks = max(1, min(blocks_per_row, decode.PAGED_CHUNK_KEYS // bs))
    chunk_keys = chunk_blocks * bs
    tables_p = jnp.pad(tables, ((0, 0), (0, -blocks_per_row % chunk_blocks)))
    trips = (jnp.max(positions) + chunk_keys - 1) // chunk_keys
    pk_flat = pk.reshape(n_layers * n_phys, bs, n_heads, dh)
    pv_flat = pv.reshape(n_layers * n_phys, bs, n_heads, dh)
    scale = dh**-0.5
    s1 = jnp.einsum("rhd,rhd->rh", q, k1,
                    preferred_element_type=jnp.float32) * scale

    def chunk(c, carry):
        m, den, acc = carry
        blocks = base + jax.lax.dynamic_slice_in_dim(
            tables_p, c * chunk_blocks, chunk_blocks, axis=1)
        kc = pk_flat[blocks].reshape(n_rows, chunk_keys, n_heads, dh)
        vc = pv_flat[blocks].reshape(n_rows, chunk_keys, n_heads, dh)
        k_pos = c * chunk_keys + jnp.arange(chunk_keys)
        cached = k_pos[None, :] < positions[:, None]
        s = jnp.einsum("rhd,rkhd->rhk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(cached[:, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        den = den * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "rhk,rkhd->rhd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, den, acc

    init = (s1, jnp.ones_like(s1), v1.astype(jnp.float32))
    _, den, acc = jax.lax.fori_loop(0, trips, chunk, init)
    return (acc / den[..., None]).astype(v1.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_grouped_read_with_one_query_head_per_kv_head_is_todays_bitwise(
        dtype):
    rng = np.random.default_rng(4)
    layers, rows, bs, heads, dh, nb = 2, 3, 4, 4, 8, 6
    n_phys = 1 + rows * nb
    pk = jnp.asarray(rng.normal(size=(layers, n_phys, bs, heads, dh)), dtype)
    pv = jnp.asarray(rng.normal(size=(layers, n_phys, bs, heads, dh)), dtype)
    tables = jnp.asarray(
        1 + rng.permutation(rows * nb).reshape(rows, nb), jnp.int32)
    positions = jnp.asarray([3, 22, 11], jnp.int32)
    q, k1, v1 = (jnp.asarray(rng.normal(size=(rows, heads, dh)), dtype)
                 for _ in range(3))
    for layer in range(layers):
        base = layer * n_phys
        want = jax.jit(_attend_pr25)(pk, pv, positions, tables, q, k1, v1,
                                     base)
        got = jax.jit(lambda *a: decode.paged_attention(*a[:4])(*a[4:]))(
            pk, pv, positions, tables, q, k1, v1, base)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))


def test_grouped_read_is_each_kv_head_serving_its_group():
    """G query heads a K/V head == the same read with K/V repeated G times
    (what the dense layout would hold), to float32 rounding."""
    rng = np.random.default_rng(6)
    rows, bs, kvh, g, dh, nb = 2, 4, 2, 3, 8, 5
    n_phys = 1 + rows * nb
    pk = jnp.asarray(rng.normal(size=(1, n_phys, bs, kvh, dh)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(1, n_phys, bs, kvh, dh)), jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(rows * nb).reshape(rows, nb), jnp.int32)
    positions = jnp.asarray([13, 7], jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, kvh * g, dh)), jnp.float32)
    k1, v1 = (jnp.asarray(rng.normal(size=(rows, kvh, dh)), jnp.float32)
              for _ in range(2))
    got = decode.paged_attention(pk, pv, positions, tables)(q, k1, v1, 0)
    rep = lambda t, axis: jnp.repeat(t, g, axis=axis)  # noqa: E731
    want = decode.paged_attention(
        rep(pk, 3), rep(pv, 3), positions, tables)(
            q, rep(k1, 1), rep(v1, 1), 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- what is refused, by name -------------------------------------------------------


@pytest.mark.parametrize("what, match", [
    ("prefix_reuse", "prefix_reuse"),
    ("slab", "kv_layout"),
    ("beam", "mode='beam'"),
    ("speculative", "mode='speculative'"),
])
def test_what_a_recurrent_state_cannot_do_is_refused_by_name(what, match):
    if what == "prefix_reuse":
        with pytest.raises(ValueError, match=match):
            _server(prefix_reuse=True)
        return
    if what == "slab":
        with pytest.raises(ValueError, match=match):
            _server(kv_layout="slab")
        return
    srv = _server()
    try:
        with pytest.raises(ValueError, match=match) as err:
            srv.submit([1, 2, 3], mode=what)
        assert "recurrent state" in str(err.value)
    finally:
        srv.stop()


def test_mixer_scopes_are_metadata_on_the_lowered_programs():
    pool = PagedKVPool(CFG, max_slots=2, max_len=16, dtype=jnp.float32,
                       block_size=8)
    rows = jnp.zeros((2,), jnp.int32)
    step = pool._decode_step_fn.lower(
        PARAMS, pool.kv, rows, rows,
        jnp.zeros((2, pool.blocks_per_row), jnp.int32),
        jnp.zeros((3, 2), jnp.int32), rows, jnp.ones((2,), bool),
        pool.state, jnp.ones((2,), bool)).as_text(debug_info=True)
    for scope in ("serve/decode_step", "serve/ssm_step", "serve/conv"):
        assert scope in step, scope
    prefill = jax.jit(lambda p, t, i: fh.prefill_rows(
        p, t, i, 17, jnp.float32, CFG)).lower(
            PARAMS, jnp.zeros((2, 8), jnp.int32), rows).as_text(
                debug_info=True)
    for scope in ("serve/ssd_scan", "serve/conv"):
        assert scope in prefill, scope


def test_the_dense_transformer_has_no_state_and_counts_none():
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    srv = InferenceServer(
        cfg, ServingConfig(max_slots=2, max_len=32, kv_block_size=8),
        params=tfm.init_params(jax.random.PRNGKey(0), cfg))
    try:
        srv.submit([1, 2, 3, 4], max_new_tokens=4).result(timeout=300)
        st = srv.stats()
        assert srv.pool.state == {} and srv.pool.state_row_bytes == 0
        assert (st["ssm_state_bytes"], st["state_resets"],
                st["state_rows_held"]) == (0, 0, 0)
    finally:
        srv.stop()


def test_both_implementers_chunk_returns_the_last_real_positions_logits():
    """One contract: ``chunk`` hands back the one row of logits that is
    read, whatever the padding after it holds. (Falcon-H1's is compared
    with the reference above; here the dense transformer's.)"""
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    model = decode.serving_model(cfg)
    real, clen = 5, 8
    pool = PagedKVPool(cfg, max_slots=1, max_len=16, dtype=jnp.float32,
                       block_size=8)
    slot = pool.acquire()
    assert pool.ensure_blocks(slot, real - 1) == "ok"
    chunk = jax.jit(model.chunk)
    outs = []
    for junk_seed in (1, 2):
        toks = _tokens(clen, seed=junk_seed) % cfg.vocab
        toks[:real] = _tokens(real, seed=7) % cfg.vocab
        outs.append(np.asarray(chunk(
            params, pool.kv, {}, pool.table(slot), np.int32(slot),
            jnp.asarray(toks), np.int32(0), np.int32(real))[0]))
    assert outs[0].shape == (cfg.vocab,)
    assert np.array_equal(outs[0], outs[1])
    want = tfm.forward(params, jnp.asarray(toks[None, :real]), cfg)[0, -1]
    assert np.abs(outs[0] - np.asarray(want)).max() < TOL32
    # The same shape from the second implementer.
    pool = PagedKVPool(CFG, max_slots=1, max_len=MAX_LEN,
                       dtype=jnp.float32, block_size=8)
    slot = pool.acquire()
    last = decode.serving_model(CFG).chunk(
        PARAMS, pool.kv, pool.state, pool.table(slot), np.int32(slot),
        jnp.asarray(_tokens(clen, seed=1)), np.int32(0), np.int32(real))[0]
    assert last.shape == (TINY["vocab_size"],)
