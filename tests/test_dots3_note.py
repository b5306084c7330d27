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

"""dots3-note (latent attention of two widths: full layers that attend
the keys a learned indexer picks, sliding layers over a wider latent row
and a window; headwise output gates; routed experts chosen under a
selection bias) through the serving engine, against the repo's plain
reference (``chipbench/references/dots3_note.py``: float32, expanded
attention, the selection computed densely, every held expert on every
token, no cache) on seeded weights at a tiny size: 5 layers (full dense,
full, three sliding), ``index_topk`` 6, window 9, 16 experts with 4 a
token, block 4, chunk 8.

Tolerances. The float32 program against the float32 reference differs by
the order of its sums only: 2e-4 on logits of unit scale.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import seeded_dots3_note as seeded
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import decode
from rayfed_tpu.models import dots3_note as dm
from rayfed_tpu.models import moe
from rayfed_tpu.serving.kv_pool import PagedKVPool, _allocated
from rayfed_tpu.serving.server import InferenceServer
from tests.utils import record_logits

ref = importlib.import_module("chipbench.references.dots3_note")

BLOCK, CHUNK, MAX_LEN = 4, 8, 64
FULL, SLIDING = dm.FULL, dm.SLIDING
# Published keys at a tiny size; every expert held.
TINY = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 5,
    "layer_types": [FULL, FULL, SLIDING, SLIDING, SLIDING],
    "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "rope_theta": 80000000,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_q_lora_rank": 16, "swa_kv_lora_rank": 24,
    "swa_qk_nope_head_dim": 12, "swa_qk_rope_head_dim": 4,
    "swa_v_head_dim": 8, "swa_rope_theta": 50000, "sliding_window_size": 9,
    "index_n_heads": 4, "index_head_dim": 8, "index_topk": 6,
    "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise", "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-5, "rope_scaling": None, "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False, "moe_layer_freq": 1,
    "model_type": "dots3_note",
}
TOL = 2e-4
WAIT_S = 300
F32 = {"compute": "float32", "parameters": "float32"}


def _weights(model=TINY, seed=3):
    w = seeded.make_canonical(seeded.key_of(seed), model, jnp.float32)
    cfg = seeded.program_cfg(model, F32)
    params = jax.tree_util.tree_map(
        jnp.asarray, seeded.to_program_tree(w, model))
    return cfg, w, params


CFG, W, PARAMS = _weights()
HP = ref.hyper_of(TINY, seeded.held_of(TINY))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).astype(np.int32)


def _ref_logits(seq, w=W, hp=HP):
    return np.asarray(ref.forward(w, jnp.asarray(seq, jnp.int32), hp))


def _forward(toks, cfg=CFG, params=PARAMS):
    return np.asarray(jax.jit(lambda p, t: dm.forward(p, t, cfg))(
        params, jnp.asarray(toks[None])))[0]


def _server(cfg=CFG, params=PARAMS, name="default", **kw):
    base = dict(max_slots=3, max_len=MAX_LEN, kv_block_size=BLOCK,
                prefill_chunk=CHUNK, prefill_token_budget=2 * CHUNK,
                max_new_tokens=8, prefix_reuse=False)
    base.update(kw)
    return InferenceServer(cfg, ServingConfig(**base), params=params,
                           cache_dtype=cfg.compute_dtype, name=name)


def _served_against_reference(seen, seed, prompt, out, n_new):
    got = np.stack([seen[seed][i] for i in range(n_new)])
    want = _ref_logits(list(prompt) + out["tokens"][:-1])[len(prompt) - 1:]
    assert got.shape == want.shape
    return np.abs(got - want).max()


def _without_run_ahead(srv):
    """Every step fetched before the next is built (as
    ``tests/test_sdar_moe.py`` does it)."""
    step = srv._step_groups

    def no_lag():
        progressed = step()
        active, srv._active = srv._active, {}
        try:
            step()
        finally:
            srv._active = {s: r for s, r in active.items() if r.slot == s}
        return progressed

    srv._step_groups = no_lag


# -- the configuration --------------------------------------------------------


def test_the_configuration_from_published_keys():
    assert (CFG.n_layers, CFG.n_dense, CFG.layer_types) == (
        5, 1, (FULL, FULL, SLIDING, SLIDING, SLIDING))
    full, sliding = CFG.dims(FULL), CFG.dims(SLIDING)
    assert (full.n_heads, full.kv_rank, full.cache_width) == (4, 16, 20)
    assert (sliding.n_heads, sliding.kv_rank, sliding.cache_width) == (
        2, 24, 28)
    # apply_mla_qkv_lora_rescale: sqrt(hidden / rank) on each latent.
    assert full.q_scale == full.kv_scale == 2 ** 0.5
    assert sliding.kv_scale == (32 / 24) ** 0.5
    assert [CFG.ordinal(i) for i in range(5)] == [0, 1, 0, 1, 2]
    assert (CFG.index_heads, CFG.index_dim, CFG.index_topk, CFG.window) == (
        4, 8, 6, 9)
    # The published pattern: layers 0 and 1 full, then three sliding and
    # a full one, eleven times.
    published = dm.Dots3NoteConfig()
    assert published.layer_types.count(FULL) == 13
    assert published.layer_types[:6] == (
        FULL, FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert published.layer_types[-1] == FULL
    model = decode.serving_model(published)
    assert model.kv_spec() == ((13, (576,)), (13, (128,)), (33, (1088,)))
    assert model.layer_windows().count(513) == 33
    assert model.layer_index_topk().count(2048) == 13


@pytest.mark.parametrize("key, value, match", [
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("attention_gate_type", "elementwise", "attention_gate_type"),
    ("swa_attention_gate_type", "none", "swa_attention_gate_type"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("layer_types", [FULL, "linear_attention", SLIDING, SLIDING, SLIDING],
     "linear_attention"),
], ids=lambda v: v if isinstance(v, str) and " " not in v else None)
def test_what_from_published_refuses_by_name(key, value, match):
    with pytest.raises(ValueError, match=match):
        dm.Dots3NoteConfig.from_published(dict(TINY, **{key: value}))


def test_what_the_engine_refuses_for_a_model_with_a_learned_selection():
    """Prefix reuse, beam and speculation: no test shows them sound under
    a selection, so they are refused by name."""
    with pytest.raises(ValueError, match="prefix_reuse"):
        _server(prefix_reuse=True)
    srv = _server()
    try:
        for mode in ("beam", "speculative"):
            with pytest.raises(ValueError, match="learned selection"):
                srv.submit(_tokens(5).tolist(), mode=mode)
    finally:
        srv.stop()


# -- the model against the reference -----------------------------------------


@pytest.mark.parametrize("n", [5, 8, 37], ids=[
    "under-topk-and-window", "over-topk-under-window", "over-both"])
def test_forward_matches_the_plain_reference(n):
    """Logits at every position, both layer kinds, contexts below and
    above ``index_topk`` (6) and the window (9)."""
    toks = _tokens(n)
    want = _ref_logits(toks)
    got = _forward(toks)
    assert np.abs(got - want).max() < TOL
    if n == 37:
        assert 0.5 < want.std() < 2.0, "the logits' scale TOL assumes"


@pytest.mark.parametrize("part", ["gate", "rescale", "index", "bias",
                                  "shared"])
def test_the_gate_the_rescale_the_selection_and_the_bias_matter(part):
    """The reference with one part left out (no output gate, the latents
    not rescaled, a full layer attending every causal key, the experts
    chosen without the bias, no shared expert) lies far from the program:
    the comparison above is blind to none of them."""
    toks = _tokens(23, seed=1)
    got = _forward(toks)
    assert np.abs(got - _ref_logits(toks)).max() < TOL
    without = ref.hyper_of(TINY, seeded.held_of(TINY), without=(part,))
    assert np.abs(got - _ref_logits(toks, W, without)).max() > 0.05, part


def test_a_tree_without_the_gate_or_the_rescale_fails_the_comparison():
    """The PROGRAM with the gate's weights zeroed (every gate one half)
    or told no rescale is far from the reference."""
    toks = _tokens(23, seed=1)
    want = _ref_logits(toks)
    flat = dict(PARAMS, layers=[
        dict(lay, w_og=jnp.zeros_like(lay["w_og"]))
        for lay in PARAMS["layers"]])
    assert np.abs(_forward(toks, CFG, flat) - want).max() > 0.05
    plain = seeded.program_cfg(
        dict(TINY, apply_mla_qkv_lora_rescale=False), F32)
    assert plain.dims(FULL).q_scale == 1.0
    assert np.abs(_forward(toks, plain) - want).max() > 0.05


# -- the selection -------------------------------------------------------------


def _program_sets(toks):
    """The (S, S) masks ``decode.select_mask`` gave the two full layers
    over one sequence, the stack run eagerly."""
    masks = []
    select = decode.select_mask

    def spy(scores, valid, k):
        masks.append(np.asarray(select(scores, valid, k)))
        return jnp.asarray(masks[-1])

    decode.select_mask = spy
    try:
        dm._seq_layers(dm._embed(PARAMS, jnp.asarray(toks), CFG), PARAMS,
                       jnp.arange(len(toks)), None, CFG)
    finally:
        decode.select_mask = select
    return masks


def test_the_selected_sets_are_the_references_position_for_position():
    """Each query's set on both full layers, against the reference's
    dense ``top_k``; a query before ``index_topk`` attends every causal
    key (the selection is the identity there), a later one exactly
    ``index_topk``."""
    toks = _tokens(29, seed=2)
    masks = _program_sets(toks)
    assert len(masks) == 2
    causal = np.tril(np.ones((29, 29), bool))
    for i, got in enumerate(masks):
        want = np.asarray(ref.selected_sets(W, jnp.asarray(toks), i, HP))
        assert np.array_equal(got, want), i
        assert np.array_equal(got[:6], causal[:6])
        assert (got[6:].sum(-1) == 6).all()
        assert not (got & ~causal).any()
        # ... and it is not the most recent six.
        recent = causal & ~np.tril(np.ones((29, 29), bool), -6)
        assert (got != recent).any()


@pytest.mark.parametrize("k", [1, 3, 7, 40])
def test_select_mask_is_an_exact_top_k_with_ties_to_the_lower_position(k):
    """Against ``jax.lax.top_k`` (equal scores in order of position) on
    scores full of ties, signs, zeros and infinities, under a causal
    ``valid``."""
    rng = np.random.default_rng(k)
    scores = rng.integers(-3, 4, size=(19, 33)).astype(np.float32) / 2
    scores[3, :5] = -np.inf
    scores[4, 2] = np.inf
    scores[5] = 0.0
    valid = np.arange(33)[None, :] <= (np.arange(19) * 2)[:, None]
    got = np.asarray(jax.jit(decode.select_mask, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(valid), k))
    assert np.array_equal(got.sum(-1), np.minimum(k, valid.sum(-1)))
    assert not (got & ~valid).any()
    for row in range(19):
        n = int(valid[row].sum())
        order = np.asarray(jax.lax.top_k(
            jnp.asarray(scores[row, :n]), min(k, n))[1])
        want = np.zeros(33, bool)
        want[order] = True
        assert np.array_equal(got[row], want), row


# -- prefill then decode through the engine -----------------------------------


@pytest.mark.parametrize(
    "plen", [3, CHUNK, CHUNK + 1, 21, 4 * CHUNK, 4 * CHUNK + 5],
    ids=["short", "chunk", "chunk+1", "three-chunks", "four-chunks",
         "four-chunks-and-a-rest"],
)
def test_prefill_then_decode_matches_the_reference_forward(plen, monkeypatch):
    """Every logits row the engine chooses a token from (the bucketed or
    the chunked prefill's last position, then each decode step through
    the block tables: the indexer's keys read from the pool, the top-k,
    the chosen rows in the absorbed form, the sliding layers' windows;
    contexts crossing block, chunk, ``index_topk`` and window boundaries)
    == the reference's full forward over prompt + served tokens."""
    seen = record_logits(monkeypatch)
    srv = _server()
    try:
        prompt = _tokens(plen, seed=plen).tolist()
        out = srv.submit(prompt, max_new_tokens=7, seed=4242).result(
            timeout=WAIT_S)
        assert _served_against_reference(seen, 4242, prompt, out, 7) < TOL
        st = srv.stats()
        assert st["prefill_tokens"] == plen
        assert st["prefill_chunks"] == (0 if plen <= CHUNK
                                        else -(-plen // CHUNK))
    finally:
        srv.stop()


def test_the_chunks_trips_and_the_steps_trips_cross_their_boundaries(
        monkeypatch):
    """Trips of two blocks (index keys, latent rows, the sliding
    layers' windows): a long prompt's later chunks and the decode steps
    run several trips a layer, and the last is partly past the context."""
    monkeypatch.setattr(decode, "PAGED_CHUNK_KEYS", 2 * BLOCK)
    monkeypatch.setattr(decode, "CHUNK_TRIP_KEYS", 2 * BLOCK)
    monkeypatch.setattr(decode, "INDEX_TRIP_KEYS", 2 * BLOCK)
    seen = record_logits(monkeypatch)
    srv = _server()
    try:
        prompt = _tokens(43, seed=7).tolist()
        out = srv.submit(prompt, max_new_tokens=9, seed=77).result(
            timeout=WAIT_S)
        assert _served_against_reference(seen, 77, prompt, out, 9) < TOL
    finally:
        srv.stop()


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "in-step"])
def test_rows_enter_and_leave_and_a_junk_row_rides_along(ahead, monkeypatch):
    """More requests than slots, one bucketed and the rest chunked, a
    slot free at the start and the end (a junk row: position 0 under an
    all-zero table), a shorter request into a slot that held a longer
    one: each request's logits are the reference's for it alone, with a
    step in flight or without."""
    seen = record_logits(monkeypatch)
    srv = _server(max_slots=3)
    if not ahead:
        _without_run_ahead(srv)
    try:
        sizes = {101: (5, 9), 102: (43, 4), 103: (8, 6), 104: (19, 8),
                 105: (4, 5)}
        prompts = {s: _tokens(n, seed=s).tolist()
                   for s, (n, _) in sizes.items()}
        futs = {s: srv.submit(prompts[s], max_new_tokens=new, seed=s)
                for s, (_, new) in sizes.items()}
        outs = {s: f.result(timeout=WAIT_S) for s, f in futs.items()}
        st = srv.stats()
    finally:
        srv.stop()
    for s, (_, new) in sizes.items():
        assert _served_against_reference(
            seen, s, prompts[s], outs[s], new) < TOL, s
    assert (st["steps_ahead"] > 0) == ahead
    assert st["kv_blocks_in_use"] == 0


def test_run_ahead_on_and_off_give_the_same_tokens():
    prompts = [_tokens(n, seed=40 + n).tolist() for n in (6, 27, 13)]
    tokens = []
    for ahead in (True, False):
        srv = _server()
        if not ahead:
            _without_run_ahead(srv)
        try:
            futs = [srv.submit(p, max_new_tokens=10) for p in prompts]
            tokens.append([f.result(timeout=WAIT_S)["tokens"] for f in futs])
            assert (srv.stats()["steps_ahead"] > 0) == ahead
        finally:
            srv.stop()
    assert tokens[0] == tokens[1]


def test_a_stalled_row_and_a_preempted_row_replay_to_the_same_tokens():
    """Fewer pool blocks than the rows need: a grant fails, the row sits
    a step out (position 0, nothing scored), the youngest is preempted
    and runs again from zero; the tokens are those of a request alone."""
    prompts = [_tokens(8, seed=80 + i).tolist() for i in range(4)]
    alone = _server(max_slots=1, max_new_tokens=16)
    try:
        want = [alone.submit(p, max_new_tokens=14).result(
            timeout=WAIT_S)["tokens"] for p in prompts]
    finally:
        alone.stop()
    srv = _server(max_slots=3, kv_blocks=9, max_new_tokens=16)
    try:
        futs = [srv.submit(p, max_new_tokens=14) for p in prompts]
        got = [f.result(timeout=WAIT_S)["tokens"] for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    assert got == want
    assert st["preempted"] >= 1 and st["kv_blocks_in_use"] == 0


def test_a_junk_row_scores_nothing_and_touches_no_expert():
    """One decode step over a pool with one live row and one junk row:
    the counters are the live row's alone, and the junk row's output
    does not move the live row's logits."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN, dtype=jnp.float32,
                       block_size=BLOCK)
    toks = _tokens(12, seed=9)
    _, rows = jax.jit(lambda p, t, i: dm.prefill_rows(
        p, t, i, jnp.float32, CFG))(
        PARAMS, jnp.asarray(toks[None, :11]), jnp.asarray([10]))
    slot = pool.acquire()
    assert pool.ensure_blocks(slot, 11) == "ok"
    tables = np.zeros((2, pool.blocks_per_row), np.int32)
    tables[slot] = pool.table(slot)
    both = [np.zeros((a.shape[0], 2, *a.shape[2:]), np.float32)
            for a in rows]
    for slab, a in zip(both, rows):
        slab[:, slot] = np.asarray(a)[:, 0]
    pool.scatter_rows(*map(jnp.asarray, both), tables)
    step = jax.jit(lambda p, kv, t, pos, tab, live: dm.paged_decode_step(
        p, kv, t, pos, tab, live, CFG))
    tokens = np.zeros(2, np.int32)
    tokens[slot] = toks[11]
    positions = np.zeros(2, np.int32)
    positions[slot] = 11
    live = np.arange(2) == slot
    logits, _, counters = step(PARAMS, pool.kv, tokens, positions, tables,
                               live)
    assert np.abs(np.asarray(logits[slot]) - _ref_logits(toks)[-1]).max() \
        < TOL
    assert np.isfinite(np.asarray(logits)).all()
    # Four expert layers x 4 choices of ONE row, all 16 experts held.
    assert int(counters[1]) == 4 * 4


# -- the expert layer, the bias and the share -----------------------------------


def _share(lay, held):
    """A canonical layer with only the experts ``held`` handed over."""
    held = np.asarray(held)
    return dict(lay, **{name: lay[name][held]
                        for name in ("we_gate", "we_up", "we_down")})


def _program_layer(lay, i=1):
    tree = seeded._program_layer(lay.items(), TINY, i)
    return jax.tree_util.tree_map(jnp.asarray, tree)


def test_the_selection_bias_changes_a_choice_and_not_a_weight():
    """With a bias that favours expert 0 enough, every token takes it;
    the weights are still the chosen experts' own sigmoid scores,
    normalised. Without the bias, or with a zero one, the routing is the
    unbiased one, bit for bit."""
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(17, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)) * 32 ** -0.5, jnp.float32)
    sigma = np.asarray(jax.nn.sigmoid(h @ router))
    idx0, w0 = moe.route_sigmoid_topk(h, router, 4)
    idxz, wz = moe.route_sigmoid_topk(h, router, 4, jnp.zeros(16))
    assert np.array_equal(idx0, idxz) and np.array_equal(w0, wz)
    bias = jnp.zeros(16).at[0].set(2.0)
    idx, w = moe.route_sigmoid_topk(h, router, 4, bias)
    assert (np.asarray(idx) == 0).any(-1).all()
    assert not (np.asarray(idx0) == 0).any(-1).all()
    chosen = np.take_along_axis(sigma, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # The seeded N(0, 0.02) changes some tokens' choices against the
    # reference without it, and no weight's formula.
    lay = W["layers"][2]
    with_b = ref.routing(h, lay, HP, None)
    without = ref.routing(h, lay, HP._replace(without=("bias",)), None)
    differ = (np.sort(np.asarray(with_b[0]), -1)
              != np.sort(np.asarray(without[0]), -1)).any(-1)
    assert 0 < differ.sum() < len(differ)


def _parent_route(h, router, k, scoring):
    """The router as it was before it took a bias."""
    scores = jnp.einsum("td,de->te", h, router.astype(h.dtype),
                        preferred_element_type=jnp.float32)
    scores = (jax.nn.sigmoid(scores) if scoring == "sigmoid"
              else jax.nn.softmax(scores, axis=-1))
    top, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), top / top.sum(-1, keepdims=True)


@pytest.mark.parametrize("scoring, scale", [
    ("sigmoid", 1.0), ("sigmoid", 2.5), ("softmax", 1.0)],
    ids=["cohere2", "pangu", "sdar"])
def test_routed_experts_without_a_bias_is_the_parents_for_the_three_models(
        scoring, scale, monkeypatch):
    """A layer without ``router_bias`` (the three expert models the
    benchmark had): the routing and the layer's result are bit for bit
    those of the router as it was."""
    lay = {k: v for k, v in _program_layer(
        _share(W["layers"][3], (2, 3, 9, 12)), 3).items()
        if k != "router_bias"}
    lay = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), lay)
    h = jax.random.normal(jax.random.PRNGKey(2), (23, 32), jnp.bfloat16)
    live = jnp.arange(23) % 5 != 0
    held = (2, 3, 9, 12)
    run = jax.jit(lambda h: moe.routed_experts(
        h, lay, held, 4, live, scale, scoring))
    new = run(h)
    monkeypatch.setitem(
        moe.ROUTERS, scoring,
        lambda h, router, k: _parent_route(h, router, k, scoring))
    old = jax.jit(lambda h: moe.routed_experts(
        h, lay, held, 4, live, scale, scoring))(h)
    for a, b in zip(new, old):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert np.abs(np.asarray(new[0], np.float32)).max() > 1e-3


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """For ``held`` = each eighth of the experts in turn: the routed
    parts the shares give (chosen under the bias), summed, with attention
    and the shared expert counted once, are the uncut reference layer;
    and each share's part is the reference's for that share."""
    x = jnp.asarray(np.random.default_rng(5).normal(size=(29, 32)),
                    jnp.float32)
    positions = jnp.arange(x.shape[0])
    for i, kind in ((1, FULL), (3, SLIDING)):
        lay = W["layers"][i]
        uncut = np.asarray(ref.layer(x, lay, positions, kind, HP))
        a = x + ref.attention(ref.rms_norm(x, lay["ln1"], HP.eps), lay,
                              positions, kind, HP, None)
        h = ref.rms_norm(a, lay["ln2"], HP.eps)
        total = np.asarray(ref.shared(h, lay, None))
        for e in range(8):
            held = (2 * e, 2 * e + 1)
            part, _, _ = moe.routed_experts(
                h, _program_layer(_share(lay, held), i), held, HP.top_k)
            want = ref.routed(h, _share(lay, held), HP._replace(held=held),
                              None)
            assert np.abs(np.asarray(part) - np.asarray(want)).max() < 1e-5
            total = total + np.asarray(part)
        assert np.abs(np.asarray(a) + total - uncut).max() < 1e-4


# -- the pool -------------------------------------------------------------------


def _published_cut():
    """The cell's five layers at the published widths (no weights)."""
    return dm.Dots3NoteConfig(
        n_layers=5, layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING))


def test_the_pool_holds_arrays_of_different_depth():
    """Three arrays, two layer counts: 9,344 B a token as declared and
    9,984 B as allocated (rows padded to 640 / 128 / 1,152 lanes); under
    ONE layer count of 5 for every array it would be 19,200."""
    pool = PagedKVPool(_published_cut(), max_slots=1, max_len=31,
                       block_size=16)
    full, index, sliding = pool.kv
    n = 1 + pool.num_blocks
    assert full.shape == (2, n, 16, 640)
    assert index.shape == (2, n, 16, 128)
    assert sliding.shape == (3, n, 16, 1152)
    assert pool.token_bytes == 2 * (576 + 128) * 2 + 3 * 1088 * 2 == 9344
    assert pool.nbytes == 9984 * n * 16
    assert 5 * (640 + 128 + 1152) * 2 == 19200
    tiny = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN, dtype=jnp.float32,
                       block_size=BLOCK)
    assert [a.shape[0] for a in tiny.kv] == [2, 2, 3]
    assert tiny.token_bytes == (2 * (20 + 8) + 3 * 28) * 4
    srv = _server()
    try:
        assert srv.stats()["kv_token_bytes"] == tiny.token_bytes
    finally:
        srv.stop()


def _dense_cfg():
    from rayfed_tpu.models import transformer as tfm

    return tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                 d_ff=64, compute_dtype=jnp.float32)


def _hybrid_cfg():
    from rayfed_tpu.models import falcon_h1

    return falcon_h1.FalconH1Config(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=64, d_ssm=32, ssm_heads=4, ssm_head_dim=8,
        ssm_state=8, ssm_groups=1, ssm_conv=4, compute_dtype=jnp.float32,
        param_dtype=jnp.float32)


def _window_cfg():
    from rayfed_tpu.models import cohere2_moe

    return cohere2_moe.Cohere2MoeConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_expert=16, n_experts=4, top_k=2, n_shared=1,
        layer_types=("sliding", "full"), window=8,
        compute_dtype=jnp.float32, param_dtype=jnp.float32)


def _latent_cfg():
    from rayfed_tpu.models import pangu_ultra_moe

    return pangu_ultra_moe.PanguUltraMoeConfig(
        vocab=64, d_model=32, n_layers=3, n_dense=1, n_heads=4, q_rank=24,
        kv_rank=16, d_nope=8, d_rope=4, d_v=8, d_dense=48, d_expert=16,
        n_experts=4, top_k=2, compute_dtype=jnp.float32,
        param_dtype=jnp.float32)


def _block_cfg():
    from rayfed_tpu.models import sdar_moe

    return sdar_moe.SdarMoeConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_expert=16, n_experts=4, top_k=2, block_length=4,
        denoising_steps=4, mask_id=63, compute_dtype=jnp.float32,
        param_dtype=jnp.float32)


@pytest.mark.parametrize("make, declared", [
    (_dense_cfg, ((2, (4, 8)), (2, (4, 8)))),
    (_hybrid_cfg, ((2, (2, 8)), (2, (2, 8)))),
    (_window_cfg, ((2, (2, 8)), (2, (2, 8)))),
    (_latent_cfg, ((3, (20,)),)),
    (_block_cfg, ((2, (2, 8)), (2, (2, 8)))),
    (lambda: CFG, ((2, (20,)), (2, (8,)), (3, (28,)))),
], ids=["dense", "hybrid", "window-experts", "latent", "blocks",
        "two-latents-and-an-index"])
def test_the_pool_of_each_model_allocates_what_its_kv_spec_declares(
        make, declared):
    """Each array as deep as ITS layers; the five models the engine had
    declare one count for all their arrays, and get the arrays they
    had."""
    cfg = make()
    assert decode.serving_model(cfg).kv_spec() == declared
    pool = PagedKVPool(cfg, max_slots=2, max_len=24, dtype=jnp.float32,
                       block_size=BLOCK)
    n = 1 + 2 * pool.blocks_per_row
    assert [a.shape for a in pool.kv] == [
        (layers, n, BLOCK, *_allocated(shape)) for layers, shape in declared]
    assert pool.token_bytes == 4 * sum(
        layers * int(np.prod(shape)) for layers, shape in declared)
    assert pool.nbytes == sum(a.nbytes for a in pool.kv) + sum(
        a.nbytes for a in pool.state.values())
    for a in pool.state.values():
        assert a.shape[:2] == (cfg.n_layers, 2)
    # What the decode step's read takes as keys and values is told by the
    # declared shapes: a K/V pair of per-head rows, or a latent array
    # alone (the index keys and the other width are other reads').
    pk, pv = decode.paged_read_operands(declared, pool.kv)
    assert pk is pool.kv[0]
    assert (pv is pool.kv[1]) if len(declared[0][1]) == 2 else pv is None


def test_what_is_written_leaves_the_padding_zero_and_the_arrays_apart():
    """After prefill (bucketed and chunked) and decode steps each array
    holds rows at its own width, and its columns past it are still
    zero."""
    srv = _server()
    try:
        for n in (5, 21):
            srv.submit(_tokens(n, seed=n).tolist(),
                       max_new_tokens=5).result(timeout=WAIT_S)
        full, index, sliding = map(np.asarray, srv.pool.kv)
    finally:
        srv.stop()
    for a, width in ((full, 20), (index, 8), (sliding, 28)):
        assert a.shape[-1] == 128
        assert np.abs(a[..., :width]).max() > 0
        assert not a[..., width:].any()


# -- counters and scopes ----------------------------------------------------------


def test_the_engines_counters_against_the_positions():
    """One request alone: ``index_keys_scored`` / ``_selected`` (the two
    full layers: every causal key scored, ``min(6, pos + 1)`` kept, in
    prefill and in decode), ``decode_keys_attended`` (an indexed layer's
    row attends what was kept, a windowed one at most 9),
    ``kv_dead_blocks`` (the three sliding layers' blocks wholly behind
    the window), and their telemetry mirrors."""
    from rayfed_tpu.telemetry import metrics as telemetry_metrics

    plen, n_new = 21, 9
    srv = _server(name="dots3-counters")
    try:
        srv.submit(_tokens(plen, seed=21).tolist(),
                   max_new_tokens=n_new).result(timeout=WAIT_S)
        st = srv.stats()
    finally:
        srv.stop()
    prompt_q = range(plen)
    decode_pos = range(plen, plen + n_new - 1)
    assert st["steps"] == n_new - 1
    assert st["index_keys_scored"] == 2 * (
        sum(q + 1 for q in prompt_q) + sum(p + 1 for p in decode_pos))
    assert st["index_keys_selected"] == 2 * (
        sum(min(q + 1, 6) for q in prompt_q)
        + sum(min(p + 1, 6) for p in decode_pos))
    assert st["prefill_keys_attended"] == sum(
        2 * min(q + 1, 6) + 3 * min(q + 1, 9) for q in prompt_q)
    assert st["decode_keys_attended"] == sum(
        2 * min(p + 1, 6) + 3 * min(p + 1, 9) for p in decode_pos)
    assert st["kv_dead_blocks"] == 3 * sum(
        (p - 9 + 1) // BLOCK for p in decode_pos)
    # An indexed layer reads every block of its index keys and the
    # fewest blocks that can hold the six rows kept (two of four).
    assert st["kv_layer_blocks_attended"] == sum(
        2 * (p // BLOCK + 1 + 2) + 3 * (p // BLOCK - (p - 8) // BLOCK + 1)
        for p in decode_pos)
    reg = telemetry_metrics.get_registry()
    for name in ("index_keys_scored", "index_keys_selected",
                 "kv_dead_blocks"):
        mirror = reg.get(f"fed_serving_{name}_total").labels(
            server="dots3-counters")
        assert mirror.value() == st[name]


def test_the_gated_rows_of_the_counter_table_in_stats_and_registry():
    """With windows, an index and ``step_counters``: the rows they gate
    are in ``stats()``, their series read the same, the ``*_decode`` keys
    have none, and no row of a model that generates by blocks is there
    (``tests/test_serving.py`` has the dense engine's half)."""
    from tests.utils import assert_counters_agree

    srv = _server(name="dots3-table")
    try:
        srv.submit(_tokens(5).tolist(), max_new_tokens=4).result(
            timeout=WAIT_S)
        srv.submit(_tokens(21, seed=1).tolist(), max_new_tokens=6,
                   temperature=0.7, seed=3).result(timeout=WAIT_S)
        st = srv.stats()
    finally:
        srv.stop()
    counters = assert_counters_agree(srv, st)
    assert {"index_keys_scored", "index_keys_selected", "kv_dead_blocks",
            "index_keys_scored_decode", "index_keys_selected_decode",
            *srv.pool.step_counters} <= counters
    assert srv.pool.step_counters and not any(
        key.startswith("diffusion_") for key in counters)
    assert 0 < st["index_keys_scored_decode"] < st["index_keys_scored"]
    assert st["kv_dead_blocks"] > 0 and st["moe_experts_hit"] > 0


def test_a_model_without_an_indexer_or_a_window_has_no_such_counters():
    srv = InferenceServer(
        _dense_cfg(), ServingConfig(max_slots=2, max_len=32,
                                    kv_block_size=BLOCK, prefill_chunk=CHUNK),
        cache_dtype=jnp.float32)
    try:
        st = srv.stats()
    finally:
        srv.stop()
    for name in ("index_keys_scored", "index_keys_selected",
                 "kv_dead_blocks"):
        assert name not in st


def test_the_scopes_are_metadata_on_the_lowered_programs():
    """``serve/attn_index``, ``serve/attn_sparse`` and
    ``serve/attn_window_latent`` in all three programs, beside
    ``serve/mla_project`` and the expert layer's three."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=16, dtype=jnp.float32,
                       block_size=BLOCK)
    rows = jnp.zeros((2,), jnp.int32)
    step = pool._decode_step_fn.lower(
        PARAMS, pool.kv, rows, rows,
        jnp.zeros((2, pool.blocks_per_row), jnp.int32),
        jnp.zeros((3, 2), jnp.int32), rows, jnp.ones((2,), bool), {},
        jnp.ones((2,), bool),
    ).as_text(debug_info=True)
    model = decode.serving_model(CFG)
    chunk = jax.jit(model.chunk).lower(
        PARAMS, pool.kv, {}, jnp.zeros((pool.blocks_per_row,), jnp.int32),
        jnp.int32(0), jnp.zeros((8,), jnp.int32), jnp.int32(0),
        jnp.int32(8)).as_text(debug_info=True)
    prefill = jax.jit(lambda p, t, i: dm.prefill_rows(
        p, t, i, jnp.float32, CFG)).lower(
        PARAMS, jnp.zeros((2, 8), jnp.int32),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    for text in (step, chunk, prefill):
        for scope in ("serve/attn_index", "serve/attn_sparse",
                      "serve/attn_window_latent", "serve/mla_project",
                      "serve/moe_route", "serve/moe_experts",
                      "serve/moe_shared", "serve/dense_ffn"):
            assert scope in text, scope
