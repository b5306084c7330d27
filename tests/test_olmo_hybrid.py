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

"""Olmo-Hybrid (gated delta-rule linear-attention layers, a full-attention
layer among every few) through the serving engine, against the repo's one
plain reference (``chipbench/references/olmo_hybrid.py``: float32, the
recurrence as a scan over positions, no cache, no chunks) on seeded
weights at the rehearsal's ratios (two periods of three linear layers to
one full, ``dv = 2 dk``).

Two kinds of cache live in different layers of one stack: the pool holds
the recurrent state as deep as the linear layers and K/V as deep as the
full ones, and the engine's counts by layer are over the layers that
attend.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import seeded_olmo_hybrid as seeded
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import falcon_h1 as fh
from rayfed_tpu.models import olmo_hybrid as oh
from rayfed_tpu.serving.kv_pool import PagedKVPool
from rayfed_tpu.serving.server import InferenceServer
from tests import test_falcon_h1 as tf
from tests.utils import step_logits

ref = importlib.import_module("chipbench.references.olmo_hybrid")

PERIOD = [oh.LINEAR] * 3 + [oh.FULL]
# Published keys at a tiny size, every ratio kept.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 176,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "hidden_act": "silu",
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
CHUNK = 16          # serving.prefill_chunk in the engine tests
SUB = 8             # positions the chunked delta rule solves at once
PROGRAM_SUB = oh.DELTA_CHUNK    # as served, before this file shortens it
MAX_LEN = 64
# float32 program against the float32 reference: the chunked form
# re-associates the recurrence's sums and the paged read the softmax's,
# nothing is rounded lower; 8 layers of that stay under 2e-4 of logits of
# std 1 (read: 3e-5). A state kept in bfloat16 stands 1e-3 and more away
# (``test_a_bfloat16_state_is_outside_the_tolerance``). The bfloat16
# program: rounding moves single logits far at this size (dk = 8: a
# linear layer's output is a few terms under an RMS norm, and where they
# nearly cancel a rounding turns it; the reference's own bfloat16 control
# reads the same), so it is held by the error's root mean square, from
# position 16 on (where the benchmark's served tokens lie): read 0.08 of
# logits of std 1, the reference's control 0.07.
TOL32, TOL16_RMS = 2e-4, 0.15


def _weights(dtype, model=TINY, seed=3, **overrides):
    w = seeded.make_canonical(seeded.key_of(seed), model, dtype)
    cfg = oh.OlmoHybridConfig.from_published(
        model, compute_dtype=dtype, param_dtype=dtype, **overrides)
    return cfg, w, seeded.to_program_tree(w, model)


@pytest.fixture(autouse=True)
def _short_sub_chunks(monkeypatch):
    """Every program of this file solves ``SUB`` positions at once, so
    that a prompt chunk of ``CHUNK`` crosses a sub-chunk boundary."""
    monkeypatch.setattr(oh, "DELTA_CHUNK", SUB)


CFG, W, PARAMS = _weights(jnp.float32)
HP = ref.hyper_of(TINY)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).astype(np.int32)


def _ref_logits(seq, w=W, hp=HP, quant=None):
    return np.asarray(ref.forward(w, jnp.asarray(seq, jnp.int32), hp, quant))


def _server(cfg=CFG, params=PARAMS, **kw):
    base = dict(max_slots=4, max_len=MAX_LEN, kv_block_size=8,
                prefill_chunk=CHUNK, prefill_token_budget=2 * CHUNK,
                max_new_tokens=8, prefix_reuse=False)
    base.update(kw)
    return InferenceServer(cfg, ServingConfig(**base), params=params,
                           cache_dtype=cfg.compute_dtype)


def _served_logits(monkeypatch, prompt, n=6, **kw):
    # (``tests/test_falcon_h1.py``'s recorder of the logits a request's
    # tokens were chosen from.)
    seen = tf._record_logits(monkeypatch, seed=4242)
    srv = _server(**kw)
    try:
        out = srv.submit(prompt, max_new_tokens=n, seed=4242).result(
            timeout=300)
        return np.stack([seen[i] for i in range(n)]), out, srv.stats()
    finally:
        srv.stop()


# -- the model against the reference ----------------------------------------


def test_forward_matches_the_plain_reference():
    toks = np.stack([_tokens(40, seed=1), _tokens(40, seed=2)])
    got = np.asarray(jax.jit(lambda p, t: oh.forward(p, t, CFG))(
        PARAMS, jnp.asarray(toks)))
    want = np.stack([_ref_logits(t) for t in toks])
    assert got.shape == want.shape == (2, 40, TINY["vocab_size"])
    assert np.abs(got - want).max() < TOL32


def test_the_bfloat16_program_stands_where_the_references_control_does():
    cfg, w, params = _weights(jnp.bfloat16)
    toks = np.stack([_tokens(40, seed=1), _tokens(40, seed=2)])
    got = np.asarray(jax.jit(lambda p, t: oh.forward(p, t, cfg))(
        params, jnp.asarray(toks)))
    want = np.stack([_ref_logits(t, w) for t in toks])
    control = np.stack([_ref_logits(t, w, quant="bf16") for t in toks])
    rms = lambda e: float(np.sqrt(np.mean(e[:, 16:] ** 2)))  # noqa: E731
    assert rms(got - want) < TOL16_RMS
    assert rms(got - want) < 2 * rms(control - want)


def _delta_inputs(t, seed=0, rows=2, heads=3, dk=8, dv=16):
    rng = np.random.default_rng(seed)
    def norm(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    def f(a):
        return jnp.asarray(a, jnp.float32)

    q = norm(rng.standard_normal((rows, t, heads, dk))) * dk ** -0.5
    k = norm(rng.standard_normal((rows, t, heads, dk)))
    v = rng.standard_normal((rows, t, heads, dv))
    g = -rng.uniform(1e-3, 0.5, (rows, t, heads))
    beta = rng.uniform(0.05, 1.95, (rows, t, heads))
    state = rng.standard_normal((rows, heads, dv, dk))
    return tuple(f(a) for a in (q, k, v, g, beta, state))


def _sequential(q, k, v, g, beta, state):
    """The definition: :func:`olmo_hybrid.delta_step` a position at a
    time."""
    outs = []
    for t in range(q.shape[1]):
        o, state = oh.delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                 beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("t, sub", [(24, 8), (21, 8), (13, 64), (32, 4)],
                         ids=["whole", "ragged", "one-short", "many"])
def test_chunked_form_is_the_recurrence(t, sub):
    """Across sub-chunk boundaries, with a sequence that does not fill
    its last sub-chunk, from a state that is not zero, ``beta`` on both
    sides of 1."""
    q, k, v, g, beta, state = _delta_inputs(t, seed=t)
    o, s = jax.jit(oh.delta_chunked, static_argnums=6)(
        q, k, v, g, beta, state, sub)
    o_seq, s_seq = _sequential(q, k, v, g, beta, state)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_seq), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_seq), atol=2e-5)


def test_chunked_form_hands_the_state_across_prompt_chunks():
    """Two calls, the second from the state the first returned, are one
    call over both (what a prompt's chunks do through the pool's row), and
    a padded tail (``g = beta = 0``) leaves the state bit for bit."""
    q, k, v, g, beta, state = _delta_inputs(32, seed=5)
    run = jax.jit(oh.delta_chunked, static_argnums=6)
    o, s = run(q, k, v, g, beta, state, SUB)
    a = [x[:, :16] for x in (q, k, v, g, beta)]
    b = [x[:, 16:] for x in (q, k, v, g, beta)]
    o1, s1 = run(*a, state, SUB)
    o2, s2 = run(*b, s1, SUB)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([o1, o2], 1)), np.asarray(o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=2e-5)
    # A whole padded sub-chunk behind the real positions, junk in it.
    pad = lambda x, fill: jnp.concatenate(  # noqa: E731
        [x, jnp.full_like(x[:, :SUB], fill)], 1)
    _, s_pad = run(pad(q, 0.3), pad(k, 0.3), pad(v, 7.0), pad(g, 0.0),
                   pad(beta, 0.0), state, SUB)
    assert np.array_equal(np.asarray(s_pad), np.asarray(s))


@pytest.mark.parametrize(
    "plen", [1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 5],
    ids=["one", "chunk-1", "chunk", "chunk+1", "two-chunks-and-a-rest"],
)
def test_prefill_then_decode_matches_the_reference_forward(plen, monkeypatch):
    """Every logits row the engine chooses a token from (the prefill's
    last position, then each decode step through the paged K/V of the
    full layers and the carried state of the linear ones) == the
    reference's full forward over prompt + served tokens."""
    prompt = _tokens(plen, seed=plen).tolist()
    got, out, st = _served_logits(monkeypatch, prompt)
    want = _ref_logits(prompt + out["tokens"][:-1])[plen - 1:]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL32
    assert st["state_resets"] == 1
    assert st["prefill_chunks"] == (0 if plen <= CHUNK
                                    else -(-plen // CHUNK))


def test_bucketed_and_chunked_prefill_agree(monkeypatch):
    """The same prompt through the bucketed prefill (one program, from a
    zero state) and through chunks (the state handed on through the
    pool's row) ends in the same logits, to re-association."""
    prompt = _tokens(2 * CHUNK + 7, seed=77).tolist()
    chunked, out_c, st_c = _served_logits(monkeypatch, prompt)
    bucketed, out_b, st_b = _served_logits(
        monkeypatch, prompt, prefill_chunk=MAX_LEN,
        prefill_token_budget=MAX_LEN)
    assert st_c["prefill_chunks"] == 3 and st_b["prefill_chunks"] == 0
    assert out_c["tokens"] == out_b["tokens"]
    assert np.abs(chunked - bucketed).max() < TOL32


def test_a_bfloat16_state_is_outside_the_tolerance(monkeypatch):
    """The comparison sees the state's precision: the same engine with
    ``S`` rounded to bfloat16 after every decode step (the prefill left
    as it is) stands outside ``TOL32`` within six tokens."""
    step = oh.delta_step

    def rounded(*args):
        o, state = step(*args)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(oh, "delta_step", rounded)
    prompt = _tokens(CHUNK + 3, seed=8).tolist()
    got, out, _ = _served_logits(monkeypatch, prompt)
    want = _ref_logits(prompt + out["tokens"][:-1])[len(prompt) - 1:]
    assert np.abs(got[0] - want[0]).max() < TOL32       # the prefill's
    assert np.abs(got - want).max() > 5 * TOL32
    # And the reference's own control rounds it too.
    assert np.abs(_ref_logits(prompt, quant="bf16")
                  - _ref_logits(prompt)).max() > 5 * TOL32


def test_without_negative_eigenvalues_the_logits_are_others():
    model = dict(TINY, linear_allow_neg_eigval=False)
    cfg, w, params = _weights(jnp.float32, model)
    assert not cfg.allow_neg_eigval
    toks = _tokens(24, seed=4)
    got = np.asarray(oh.forward(params, jnp.asarray(toks)[None], cfg))[0]
    want = _ref_logits(toks, w, ref.hyper_of(model))
    assert np.abs(got - want).max() < TOL32
    assert np.abs(got - _ref_logits(toks)).max() > 100 * TOL32


def test_a_numeric_rope_theta_rotates():
    """The published ``rope_theta`` is null: no rotary positions. A
    number there rotates the full layers' queries and keys by halves, in
    the program and in the reference alike, through the cache too."""
    model = dict(TINY, rope_parameters={"rope_theta": 10000.0,
                                        "rope_type": "default"})
    cfg, w, params = _weights(jnp.float32, model)
    assert cfg.rope_theta == 10000.0 and CFG.rope_theta is None
    toks = _tokens(24, seed=6)
    want = _ref_logits(toks, w, ref.hyper_of(model))
    got = np.asarray(oh.forward(params, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(got - want).max() < TOL32
    assert np.abs(got - _ref_logits(toks)).max() > 100 * TOL32
    srv = _server(cfg, params)
    try:
        out = srv.submit(toks[:CHUNK + 2].tolist(), max_new_tokens=4).result(
            timeout=300)
    finally:
        srv.stop()
    seq = toks[:CHUNK + 2].tolist() + out["tokens"][:-1]
    follow = _ref_logits(seq, w, ref.hyper_of(model))[CHUNK + 1:]
    assert [int(t) for t in follow.argmax(-1)] == out["tokens"]


# -- what a carried state asks of the pool ----------------------------------


def _prefill(prompts, last_idx, landed=None, cfg=CFG, params=PARAMS):
    landed = None if landed is None else jnp.asarray(landed)
    return jax.jit(lambda p, t, i, w: oh.prefill_rows(
        p, t, i, MAX_LEN + 1, jnp.float32, cfg, w))(
            params, jnp.asarray(prompts), jnp.asarray(last_idx), landed)


def test_padding_never_advances_a_state():
    """A right-padded bucket row ends in the state of its last real token
    bit for bit whatever the padding holds (``g = 0``: ``alpha = 1``;
    ``beta = 0``: no update), the tail kept is that of the last real
    inputs, and a lane that is no request comes back zero."""
    n, bucket = 11, 32
    toks = _tokens(n, seed=9)
    rows = np.zeros((3, bucket), np.int32)
    rows[0, :n] = rows[1, :n] = toks
    rows[1, n:] = _tokens(bucket - n, seed=10)      # junk in the padding
    last_idx = np.array([n - 1, n - 1, 0], np.int32)
    last, k, _, state = _prefill(rows, last_idx, [True, True, False])
    assert k.shape[0] == CFG.n_full == 2
    for name, depth in (("conv", 6), ("delta", 6)):
        a = np.asarray(state[name])
        assert a.shape[0] == depth == CFG.n_linear
        assert np.array_equal(a[:, 0], a[:, 1]), name
        assert not a[:, 2].any()
    assert np.array_equal(np.asarray(last[0]), np.asarray(last[1]))
    last_u, _, _, state_u = _prefill(toks[None], np.array([n - 1], np.int32))
    for name in ("conv", "delta"):
        np.testing.assert_allclose(
            np.asarray(state[name])[:, 0], np.asarray(state_u[name])[:, 0],
            atol=5e-5, err_msg=name)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(last_u[0]),
                               atol=1e-5)
    assert np.abs(np.asarray(state["conv"])[:, 0]).min() > 0


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


def test_a_row_that_sits_a_step_out_and_a_junk_row_keep_their_state():
    """``live`` false: ``S`` and the tail come back bit for bit, for a
    held row (its position and table as they are) and for a junk row
    (position 0 under an all-zero table); the live row advances exactly
    as among neighbours, and its logits are the reference's."""
    lengths = [5, 17, 30]
    pool, slots, tokens, positions, tables = _pool_with_rows(lengths)
    before = _state_of(pool)
    together = np.asarray(step_logits(
        pool, PARAMS, tokens, positions, tables, np.ones(3, bool)))
    after = _state_of(pool)
    for r in range(3):
        solo, *_ = _pool_with_rows(lengths)
        live = np.arange(3) == r
        held = np.arange(3) == (r + 1) % 3   # kept as it is; the third: junk
        seen = live | held
        alone = np.asarray(step_logits(
            solo, PARAMS, tokens * seen, positions * seen,
            tables * seen[:, None], live))
        assert np.array_equal(alone[r], together[r]), r
        solo_state = _state_of(solo)
        for name in before:
            assert np.array_equal(solo_state[name][:, r],
                                  after[name][:, r]), (name, r)
            others = [i for i in range(3) if i != r]
            assert np.array_equal(solo_state[name][:, others],
                                  before[name][:, others]), (name, r)
    for r, n in enumerate(lengths):
        seq = _tokens(n + 1, seed=r)
        assert np.abs(together[slots[r]] - _ref_logits(seq)[n]).max() < TOL32


def test_a_chunk_starts_from_zero_and_leaves_other_slots_alone():
    """The chunk program: a ragged first chunk padded to its bucket hands
    on the state of its last real token whatever the padding holds and
    whatever its slot's rows held; the other slot's rows come back bit
    for bit."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN,
                       dtype=jnp.float32, block_size=8)
    other, slot = pool.acquire(), pool.acquire()
    assert pool.ensure_blocks(slot, CHUNK) == "ok"
    rng = np.random.default_rng(3)
    held = {name: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for name, a in pool.state.items()}
    chunk = jax.jit(lambda *a: oh.chunk(*a, CFG))
    real, outs = 5, []
    for junk_seed in (1, 2):
        toks = _tokens(CHUNK, seed=junk_seed)
        toks[:real] = _tokens(real, seed=7)
        logits, _, _, state = chunk(
            PARAMS, *pool.kv, dict(held), jnp.asarray(pool.table(slot)),
            jnp.int32(slot), jnp.asarray(toks), jnp.int32(0),
            jnp.int32(real))
        outs.append((np.asarray(logits),
                     {k: np.asarray(v) for k, v in state.items()}))
    assert np.array_equal(outs[0][0], outs[1][0])
    for name in held:
        assert np.array_equal(outs[0][1][name], outs[1][1][name]), name
        assert np.array_equal(outs[0][1][name][:, other],
                              np.asarray(held[name])[:, other]), name
    want = _ref_logits(_tokens(real, seed=7))[real - 1]
    assert np.abs(outs[0][0] - want).max() < TOL32


# -- the pool's two depths, and the engine's counts -------------------------

PUBLISHED = dict(
    TINY, vocab_size=100352, hidden_size=3840, intermediate_size=11008,
    num_hidden_layers=16, num_attention_heads=30, num_key_value_heads=30,
    linear_num_key_heads=30, linear_num_value_heads=30,
    linear_key_head_dim=96, linear_value_head_dim=192)


def test_the_pool_holds_state_and_kv_of_different_depths():
    """At depth 16 of the published widths: the state 12 layers deep
    (27.37 MB a slot), K/V 4 layers deep (61,440 B a token of the
    model's, 65,536 B as cached: the 30 heads of a row padded to 32, what
    a TPU's tiles hold for them anyway); under one depth for all they
    would be 36.5 MB and 245,760 B."""
    cfg = oh.OlmoHybridConfig.from_published(PUBLISHED)
    assert (cfg.n_layers, cfg.n_linear, cfg.n_full, cfg.period) == (
        16, 12, 4, 4)
    assert (cfg.n_kv_heads, cfg.cache_kv_heads) == (30, 32)
    pool = PagedKVPool(cfg, max_slots=1, max_len=31, block_size=16)
    assert [a.shape for a in pool.kv] == [(4, 3, 16, 32, 128)] * 2
    assert {k: v.shape[:2] for k, v in pool.state.items()} == {
        "conv": (12, 1), "delta": (12, 1)}
    assert 4 * 2 * 30 * 128 * 2 == 61440
    assert pool.token_bytes == 4 * 2 * 32 * 128 * 2 == 65536
    # ``S`` is float32 whatever the compute type (bfloat16 here): a state
    # that followed it would halve these bytes, and no chip check would
    # see it (the configuration's ``limits.calibrated``).
    assert cfg.compute_dtype == jnp.bfloat16
    assert pool.state["delta"].dtype == jnp.float32
    assert pool.state_row_bytes == 12 * (
        30 * 192 * 96 * 4 + 3 * 11520 * 2) == 27371520
    assert pool.nbytes == 27371520 + 65536 * 16 * (1 + pool.num_blocks)


def test_the_yardstick_counts_the_sub_chunk_the_program_solves():
    """``chunk_roofline.linear``'s operations are those of sub-chunks of
    ``flops_olmo_hybrid.DELTA_CHUNK``: the program's own."""
    from chipbench import flops_olmo_hybrid as fo

    assert fo.DELTA_CHUNK == PROGRAM_SUB == 64


def test_falcon_h1_declares_every_layer_and_its_pool_is_unchanged():
    pool = PagedKVPool(tf.CFG, max_slots=3, max_len=MAX_LEN,
                       dtype=jnp.float32, block_size=8)
    spec = fh.serving_model(tf.CFG).state_spec(jnp.float32)
    assert {k: v[0] for k, v in spec.items()} == {"conv": 2, "ssm": 2}
    assert {k: v.shape for k, v in pool.state.items()} == {
        "conv": (2, 3, 3, tf.CFG.conv_dim),
        "ssm": (2, 3, 4, 16, 8)}
    assert pool.state_row_bytes == 2 * 4 * (
        3 * tf.CFG.conv_dim + 4 * 16 * 8)


def test_the_engines_layer_counts_are_over_the_layers_that_attend():
    """A linear layer reads no key and walks no block: the counts by
    layer sum over the 2 full layers of this stack, not its 8."""
    srv = _server()
    try:
        assert srv._n_attending == CFG.n_full == 2 and srv._windows == ()
        plen = 2 * CHUNK + 5
        out = srv.submit(_tokens(plen, seed=12).tolist(),
                         max_new_tokens=6).result(timeout=300)
        st = srv.stats()
    finally:
        srv.stop()
    assert len(out["tokens"]) == 6
    full, bpr = CFG.n_full, srv.pool.blocks_per_row
    assert st["prefill_tokens"] == st["chunk_tokens"] == plen
    assert st["prefill_keys_attended"] == full * plen * (plen + 1) // 2
    assert st["prefill_chunks"] == 3
    assert st["chunk_blocks_row"] == 3 * full * bpr
    # (The ragged remainder goes first: chunks at 0, 5 and 21.)
    assert st["chunk_blocks_read"] == full * (0 + 1 + 3)
    assert st["chunk_state_bytes"] == 3 * 2 * srv.pool.state_row_bytes
    assert st["kv_layer_blocks_attended"] == full * st["kv_blocks_attended"]
    # Five steps (the first token is the prefill's), at plen .. plen + 4.
    assert st["decode_keys_attended"] == full * sum(
        plen + i + 1 for i in range(5))
    assert st["ssm_state_bytes"] == 5 * 2 * srv.pool.state_row_bytes
    assert srv.pool.state_row_bytes == 6 * (3 * CFG.conv_dim * 4
                                            + 4 * 16 * 8 * 4)


# -- refusals, by name ------------------------------------------------------


@pytest.mark.parametrize("change, match", [
    ({"layer_types": [oh.LINEAR, "sliding_attention"] * 4},
     "sliding_attention"),
    ({"linear_num_value_heads": 8}, "linear_num_value_heads"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_parameters": {"rope_theta": 1e4, "rope_type": "yarn",
                          "factor": 8.0}}, "rope_parameters"),
    ({"layer_types": [oh.LINEAR] * 8}, "both kinds"),
    ({"num_hidden_layers": 40}, "num_hidden_layers"),
])
def test_from_published_refuses_by_name_what_is_not_computed(change, match):
    with pytest.raises(ValueError, match=match):
        oh.OlmoHybridConfig.from_published(dict(TINY, **change))


@pytest.mark.parametrize("what, match", [
    ("prefix_reuse", "prefix_reuse"),
    ("beam", "mode='beam'"),
    ("speculative", "mode='speculative'"),
])
def test_what_a_recurrent_state_cannot_do_is_refused_by_name(what, match):
    if what == "prefix_reuse":
        with pytest.raises(ValueError, match=match):
            _server(prefix_reuse=True)
        return
    srv = _server()
    try:
        with pytest.raises(ValueError, match=match) as err:
            srv.submit([1, 2, 3], mode=what)
        assert "recurrent state" in str(err.value)
    finally:
        srv.stop()


def test_the_mixers_scopes_are_metadata_on_the_lowered_programs():
    pool = PagedKVPool(CFG, max_slots=2, max_len=16, dtype=jnp.float32,
                       block_size=8)
    rows = jnp.zeros((2,), jnp.int32)
    step = pool._decode_step_fn.lower(
        PARAMS, pool.kv, rows, rows,
        jnp.zeros((2, pool.blocks_per_row), jnp.int32),
        jnp.zeros((3, 2), jnp.int32), rows, jnp.ones((2,), bool),
        pool.state, jnp.ones((2,), bool)).as_text(debug_info=True)
    prefill = jax.jit(lambda p, t, i: oh.prefill_rows(
        p, t, i, 17, jnp.float32, CFG)).lower(
            PARAMS, jnp.zeros((2, 8), jnp.int32), rows).as_text(
                debug_info=True)
    for text in (step, prefill):
        for scope in ("serve/linear_attn", "serve/delta_rule", "serve/conv",
                      "serve/attn_full"):
            assert scope in text, scope
    assert "serve/decode_step" in step
