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

"""Pangu-Ultra-MoE (latent attention over a paged latent cache, sandwich
norms, a leading dense layer, routed experts scaled by 2.5 beside a shared
one) through the serving engine, against the repo's plain reference
(``chipbench/references/pangu_ultra_moe.py``: float32, expanded attention,
every held expert on every token, no cache) on seeded weights at a tiny
size: 3 layers (one dense, two expert), 4 heads of 8 + 4 over a latent of
16 + 4, 16 experts with 4 a token, block 4, chunk 8.

Tolerances. The float32 program against the float32 reference differs by
the order of its sums only: 2e-4 on logits of unit scale. A router near a
tie may pick another k-th expert once activations are rounded; every
comparison here runs the program in float32, and the routing counters are
compared only after asserting the margin between the reference's k-th and
(k+1)-th scores.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import seeded_cohere2_moe
from chipbench import seeded_pangu_ultra_moe as seeded
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import decode
from rayfed_tpu.models import moe
from rayfed_tpu.models import pangu_ultra_moe as pm
from rayfed_tpu.models import transformer as tfm
from rayfed_tpu.serving.kv_pool import PagedKVPool
from rayfed_tpu.serving.server import InferenceServer
from tests.utils import record_logits, slot_rows

ref = importlib.import_module("chipbench.references.pangu_ultra_moe")

BLOCK, CHUNK, MAX_LEN = 4, 8, 64
# Published keys at a tiny size; every expert held.
TINY = {
    "vocab_size": 96, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "rope_theta": 25600000, "rms_norm_eps": 1e-5, "sandwich_norm": True,
    "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
    "model_type": "pangu_ultra_moe",
}
TOL = 2e-4
TIE = 1e-5
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


def _server(cfg=CFG, params=PARAMS, **kw):
    base = dict(max_slots=3, max_len=MAX_LEN, kv_block_size=BLOCK,
                prefill_chunk=CHUNK, prefill_token_budget=2 * CHUNK,
                max_new_tokens=8, prefix_reuse=False)
    base.update(kw)
    return InferenceServer(cfg, ServingConfig(**base), params=params,
                           cache_dtype=cfg.compute_dtype)


def _served_against_reference(seen, seed, prompt, out, n_new):
    got = np.stack([seen[seed][i] for i in range(n_new)])
    want = _ref_logits(list(prompt) + out["tokens"][:-1])[len(prompt) - 1:]
    assert got.shape == want.shape
    return np.abs(got - want).max()


# -- the configuration --------------------------------------------------------


def test_the_configuration_from_published_keys_and_its_refusals():
    assert (CFG.n_layers, CFG.n_dense, CFG.n_heads) == (3, 1, 4)
    assert (CFG.q_rank, CFG.kv_rank, CFG.d_nope, CFG.d_rope, CFG.d_v) == (
        24, 16, 8, 4, 8)
    assert CFG.held == tuple(range(16)) and CFG.routed_scale == 2.5
    assert CFG.cache_width == 20
    # The extra prediction layer of the published config is not served,
    # and cannot be asked for.
    assert CFG.nextn == 0
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        pm.PanguUltraMoeConfig.from_published(TINY, nextn=1)
    with pytest.raises(ValueError, match="rope_scaling"):
        pm.PanguUltraMoeConfig.from_published(
            dict(TINY, rope_scaling={"type": "yarn", "factor": 4}))
    with pytest.raises(ValueError, match="scoring_func"):
        pm.PanguUltraMoeConfig.from_published(
            dict(TINY, scoring_func="softmax"))
    with pytest.raises(ValueError, match="sandwich_norm"):
        pm.PanguUltraMoeConfig.from_published(dict(TINY, sandwich_norm=False))
    with pytest.raises(ValueError, match="held"):
        pm.PanguUltraMoeConfig.from_published(TINY, held=(3, 3))
    # At the published sizes a token keeps 1,152 B a layer in bfloat16.
    published = pm.PanguUltraMoeConfig()
    assert published.cache_width * 2 == 1152
    assert decode.serving_model(published).kv_spec() == ((61, (576,)),)


# -- the model against the reference -----------------------------------------


@pytest.mark.parametrize("dense", [0, 1, 3], ids=["no-dense", "one", "all"])
def test_forward_matches_the_plain_reference(dense):
    """Logits at every position, every expert held, with no, one and only
    leading dense layers."""
    model = dict(TINY, first_k_dense_replace=dense)
    cfg, w, params = _weights(model)
    toks = _tokens(37)
    want = _ref_logits(toks, w, ref.hyper_of(model, seeded.held_of(model)))
    got = np.asarray(jax.jit(lambda p, t: pm.forward(p, t, cfg))(
        params, jnp.asarray(toks[None])))[0]
    assert 0.5 < want.std() < 2.0, "the logits' scale the tolerance assumes"
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize(
    "part", ["ln1", "ln2", "ln3", "ln4", "scale", "shared"])
def test_each_norm_the_scale_and_the_shared_expert_matter(part):
    """The reference with one part left out (a norm as the identity, the
    routed weights unscaled, no shared expert) lies far from the program:
    the comparison above is blind to none of them."""
    toks = _tokens(23, seed=1)
    got = np.asarray(jax.jit(lambda p, t: pm.forward(p, t, CFG))(
        PARAMS, jnp.asarray(toks[None])))[0]
    assert np.abs(got - _ref_logits(toks)).max() < TOL
    without = ref.hyper_of(TINY, seeded.held_of(TINY), without=(part,))
    assert np.abs(got - _ref_logits(toks, W, without)).max() > 0.05, part


def test_rotation_is_by_halves_and_the_positional_key_is_one_for_all_heads():
    x = jnp.zeros((1, 1, 4)).at[0, 0, 0].set(1.0)
    got = np.asarray(pm.rope_halves(x, jnp.asarray([1]), 25600000.0))[0, 0]
    # Dimension 0 turns with dimension 2 (the second half's first).
    np.testing.assert_allclose(got, [np.cos(1.0), 0, np.sin(1.0), 0],
                               atol=1e-6)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(6, 32)),
                    jnp.float32)
    layer = PARAMS["layers"][1]
    qn, qr, c = pm.project(h, layer, jnp.arange(6), CFG)
    assert (qn.shape, qr.shape, c.shape) == ((6, 4, 8), (6, 4, 4),
                                             (6, 1, 20))
    k, v = pm.expand(c, layer, CFG)
    assert (k.shape, v.shape) == ((6, 4, 12), (6, 4, 8))
    for head in range(1, 4):
        assert np.array_equal(np.asarray(k[:, head, 8:]),
                              np.asarray(k[:, 0, 8:]))
    # What attention reads of positions is their difference.
    _, qr5, c5 = pm.project(h, layer, jnp.arange(6) + 5, CFG)
    s0 = np.einsum("qhd,kd->hqk", qr, c[:, 0, 16:])
    s5 = np.einsum("qhd,kd->hqk", qr5, c5[:, 0, 16:])
    np.testing.assert_allclose(s0, s5, atol=1e-4)
    assert np.array_equal(np.asarray(c[..., :16]), np.asarray(c5[..., :16]))


# -- the latent read ----------------------------------------------------------


def _filled_pool(lengths, seed=5):
    """A pool whose slots hold ``lengths`` cached rows each, as a prefill
    would have left them, with the inputs of the decode step that comes
    next."""
    pool = PagedKVPool(CFG, max_slots=len(lengths), max_len=MAX_LEN,
                       dtype=jnp.float32, block_size=BLOCK)
    rows = len(lengths)
    tables = np.zeros((rows, pool.blocks_per_row), np.int32)
    slab = np.zeros((CFG.n_layers, rows, max(lengths), CFG.cache_width),
                    np.float32)
    tokens = np.zeros(rows, np.int32)
    for slot, n in enumerate(lengths):
        assert pool.acquire() is not None
        toks = _tokens(n + 1, seed=seed + slot)
        _, c = jax.jit(lambda p, t, i: pm.prefill_rows(
            p, t, i, jnp.float32, CFG))(
            PARAMS, jnp.asarray(toks[None, :n]), jnp.asarray([n - 1]))
        slab[:, slot, :n] = np.asarray(c)[:, 0]
        tokens[slot] = toks[n]
        # One block more than the rows cached: the new row's.
        assert pool.ensure_blocks(slot, n) == "ok"
        tables[slot] = pool.table(slot)
    pool.scatter_rows(jnp.asarray(slab), tables)
    return pool, tokens, np.asarray(lengths, np.int32), tables


@pytest.mark.parametrize("lengths", [(9,), (3, 17, 8)],
                         ids=["one-row", "unequal-rows"])
def test_the_absorbed_decode_equals_the_expanded_form(lengths, monkeypatch):
    """One decode step over cached contexts. A layer's read in the form
    the step runs (``Wk`` multiplied into the query and ``Wv`` into the
    output, each cached row read once as key and value through the block
    tables) against every cached row expanded to per-head keys and
    values; and the whole step's logits against the reference's."""
    # Two blocks a trip: the longest row makes the loop run trips that
    # lie wholly past the shorter rows' lengths.
    monkeypatch.setattr(decode, "PAGED_CHUNK_KEYS", 2 * BLOCK)
    pool, tokens, positions, tables = _filled_pool(lengths)
    (pc,) = pool.kv
    layer_i = 1
    layer = PARAMS["layers"][layer_i]
    h = jnp.asarray(np.random.default_rng(4).normal(
        size=(len(lengths), 32)), jnp.float32)
    qn, qr, c = pm.project(h[:, None], layer, jnp.asarray(positions)[:, None],
                           CFG)
    attend = decode.paged_attention(
        pc, None, jnp.asarray(positions), jnp.asarray(tables),
        v_width=CFG.kv_rank, scale=(CFG.d_nope + CFG.d_rope) ** -0.5)
    absorbed = pm.absorb_output(attend(
        pm.absorb_query(qn[:, 0], qr[:, 0], layer, CFG), c[:, 0], None,
        layer_i * pc.shape[1]), layer, CFG)
    for row, n in enumerate(lengths):
        cached = np.asarray(pc)[layer_i, tables[row]].reshape(
            -1, 1, pc.shape[-1])[:n]
        own = decode.to_width(c[row], pc.shape[-1])
        k, v = pm.expand(jnp.concatenate([cached, own]), layer, CFG)
        q = jnp.concatenate([qn[row, 0], qr[row, 0]], -1)
        scores = jnp.einsum("hd,khd->hk", q, k) * q.shape[-1] ** -0.5
        want = jnp.einsum("hk,khd->hd", jax.nn.softmax(scores, -1), v)
        assert np.abs(np.asarray(absorbed[row] - want)).max() < 1e-5
    logits, _, _ = jax.jit(lambda p, c, t, pos, tab: pm.paged_decode_step(
        p, c, t, pos, tab, jnp.ones(len(lengths), bool), CFG))(
        PARAMS, pc, tokens, positions, tables)
    for slot, n in enumerate(lengths):
        seq = _tokens(n + 1, seed=5 + slot)
        assert np.abs(np.asarray(logits[slot])
                      - _ref_logits(seq)[-1]).max() < TOL


def test_the_latent_read_takes_its_values_from_the_keys_it_gathered():
    """``decode.paged_attention`` with no value array: one array of rows,
    128-style query heads over ONE cached head, key width 20, value width
    16, a scale of its own; against NumPy over each row's own keys."""
    rng = np.random.default_rng(7)
    lengths, heads, width, v_width = (5, 13), 4, 20, 16
    n_blocks = 8
    pool = jnp.asarray(rng.normal(size=(2, 1 + n_blocks, BLOCK, width)),
                       jnp.float32)
    tables = np.zeros((2, 4), np.int32)
    tables[0, :2], tables[1, :4] = (3, 1), (2, 7, 5, 4)
    q = jnp.asarray(rng.normal(size=(2, heads, width)), jnp.float32)
    k1 = jnp.asarray(rng.normal(size=(2, 1, width)), jnp.float32)
    layer = 1
    out = decode.paged_attention(
        pool, None, jnp.asarray(lengths), jnp.asarray(tables), scale=0.3,
        v_width=v_width)(q, k1, None, layer * (1 + n_blocks))
    assert out.shape == (2, heads, v_width)
    for row, n in enumerate(lengths):
        cached = np.asarray(pool)[layer, tables[row]].reshape(-1, width)[:n]
        keys = np.concatenate([cached, np.asarray(k1[row])])
        s = np.asarray(q[row]) @ keys.T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ keys[:, :v_width]
        np.testing.assert_allclose(np.asarray(out[row]), want, atol=1e-5)


# -- the expert layer and the share -------------------------------------------


def _share(lay, held):
    """A canonical layer with only the experts ``held`` handed over."""
    held = np.asarray(held)
    return dict(lay, **{name: lay[name][held]
                        for name in ("we_gate", "we_up", "we_down")})


def _program_layer(lay):
    tree = seeded.to_program_tree({"layers": [lay]}, TINY)["layers"][0]
    return jax.tree_util.tree_map(jnp.asarray, tree)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """For ``held`` = each sixteenth of the experts in turn: the routed
    parts the shares give (scaled by 2.5), summed, with attention, the
    four norms and the shared expert counted once, are the uncut
    reference layer; and each share's part is the reference's for that
    share. The dense layer holds no expert: every chip computes it
    alike, and it is the reference's."""
    x = jnp.asarray(np.random.default_rng(5).normal(size=(29, 32)),
                    jnp.float32)
    positions = jnp.arange(x.shape[0])
    lay = W["layers"][1]
    uncut = np.asarray(ref.layer(x, lay, positions, HP))
    a = x + ref.rms_norm(
        ref.attention(ref.rms_norm(x, lay["ln1"], HP.eps), lay, positions,
                      HP, None), lay["ln2"], HP.eps)
    h = ref.rms_norm(a, lay["ln3"], HP.eps)
    total = np.asarray(ref.shared(h, lay, None))
    for e in range(16):
        held = (e,)
        part, _, _ = moe.routed_experts(
            h, _program_layer(_share(lay, held)), held, HP.top_k,
            scale=HP.routed_scale)
        want = ref.routed(h, _share(lay, held), HP._replace(held=held), None)
        assert np.abs(np.asarray(part) - np.asarray(want)).max() < 1e-5
        total = total + np.asarray(part)
    whole = np.asarray(a + ref.rms_norm(jnp.asarray(total), lay["ln4"],
                                        HP.eps))
    assert np.abs(whole - uncut).max() < 1e-4
    # The leading dense layer, by the program's own pieces.
    dense = W["layers"][0]
    layer = _program_layer(dense)
    got, hit, local = pm.ffn(h, layer, CFG)
    want = ref.feed_forward(h, dense, HP, None)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert int(hit) == int(local) == 0


def test_the_routed_weights_are_scaled_after_the_normalisation():
    """``moe.routed_experts`` told a scale gives that multiple of what it
    gives told none (1: what the Cohere2 model passes), whatever is held."""
    h = jnp.asarray(np.random.default_rng(6).normal(size=(11, 32)),
                    jnp.float32)
    layer = _program_layer(_share(W["layers"][2], (2, 3, 9)))
    plain, hit, local = moe.routed_experts(h, layer, (2, 3, 9), 4)
    scaled, hit2, local2 = moe.routed_experts(h, layer, (2, 3, 9), 4,
                                              scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(plain),
                               rtol=1e-5, atol=1e-7)
    assert (int(hit), int(local)) == (int(hit2), int(local2))
    assert np.abs(np.asarray(plain)).max() > 1e-3


# -- prefill then decode through the engine -----------------------------------


@pytest.mark.parametrize(
    "plen", [3, CHUNK, CHUNK + 1, 21, 4 * CHUNK + 5],
    ids=["short", "chunk", "chunk+1", "three-chunks",
         "four-chunks-and-a-rest"],
)
def test_prefill_then_decode_matches_the_reference_forward(plen, monkeypatch):
    """Every logits row the engine chooses a token from (the bucketed or
    the chunked prefill's last position in the expanded form, then each
    decode step in the absorbed form through the block tables, contexts
    crossing block and chunk boundaries) == the reference's full forward
    over prompt + served tokens."""
    seen = record_logits(monkeypatch)
    srv = _server()
    try:
        prompt = _tokens(plen, seed=plen).tolist()
        out = srv.submit(prompt, max_new_tokens=7, seed=4242).result(
            timeout=300)
        assert _served_against_reference(seen, 4242, prompt, out, 7) < TOL
        st = srv.stats()
        assert st["prefill_tokens"] == plen
        assert st["prefill_chunks"] == (0 if plen <= CHUNK
                                        else -(-plen // CHUNK))
    finally:
        srv.stop()


def test_rows_of_unequal_length_share_a_batch_and_a_slot_is_reused(
        monkeypatch):
    """Three requests in one batch, one bucketed, one chunked, then a
    shorter request into a slot that held a longer one: each one's logits
    are the reference's for it alone."""
    seen = record_logits(monkeypatch)
    srv = _server(max_slots=3)
    try:
        first = {101: _tokens(5, seed=1).tolist(),
                 102: _tokens(43, seed=2).tolist(),
                 103: _tokens(8, seed=3).tolist()}
        futs = {s: srv.submit(p, max_new_tokens=9, seed=s)
                for s, p in first.items()}
        outs = {s: f.result(timeout=300) for s, f in futs.items()}
        for s, p in first.items():
            assert _served_against_reference(seen, s, p, outs[s], 9) < TOL, s
        again = _tokens(4, seed=4).tolist()
        out = srv.submit(again, max_new_tokens=9, seed=104).result(
            timeout=300)
        assert _served_against_reference(seen, 104, again, out, 9) < TOL
    finally:
        srv.stop()


def test_prefix_reuse_shares_latent_blocks_and_clones_the_boundary():
    """Two requests with the same prompt, the second while the first
    decodes: it adopts the first's full blocks and a copy of the boundary
    block (one array to copy, not two), and both serve the tokens a
    request alone is served."""
    prompt = _tokens(14, seed=8).tolist()        # 3 blocks and a half
    srv = _server(prefix_reuse=True, max_new_tokens=16)
    try:
        alone = srv.submit(prompt, max_new_tokens=6).result(timeout=300)
        a, stream = srv.submit_stream(prompt, max_new_tokens=16)
        next(iter(stream))          # a's prefill is done: it is a donor
        b = srv.submit(prompt, max_new_tokens=6)
        a, b = a.result(timeout=300), b.result(timeout=300)
        st = srv.stats()
    finally:
        srv.stop()
    assert b["tokens"] == alone["tokens"] == a["tokens"][:6]
    assert st["prefix_hits"] >= 1


# -- the pool -------------------------------------------------------------------


def test_the_pool_holds_one_latent_array_and_its_bytes_a_token():
    """One array of (L, 1 + blocks, block, width), no second copy and no
    per-head K/V: 1,152 B a token a layer at the published widths in
    bfloat16 (here 20 values x 4 B x 3 layers), in ``stats()`` too. Its
    rows are allocated padded to whole tiles of 128 values, and the
    padding stays zero whatever is written."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN, dtype=jnp.float32,
                       block_size=BLOCK)
    (latent,) = pool.kv
    assert latent.shape == (3, 1 + 2 * pool.blocks_per_row, BLOCK, 128)
    assert pool.token_bytes == 3 * 20 * 4
    assert pool.nbytes == latent.nbytes
    published = pm.PanguUltraMoeConfig.from_published(
        dict(TINY, hidden_size=64, kv_lora_rank=512, qk_rope_head_dim=64,
             num_hidden_layers=2))
    wide = PagedKVPool(published, max_slots=1, max_len=16, block_size=BLOCK)
    assert wide.kv[0].dtype == jnp.bfloat16
    assert wide.token_bytes == 2 * 1152
    assert wide.kv[0].shape[-1] == 640
    srv = _server()
    try:
        assert srv.stats()["kv_token_bytes"] == 3 * 20 * 4
    finally:
        srv.stop()


def test_the_pool_lands_and_reads_back_latent_rows():
    pool = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN, dtype=jnp.float32,
                       block_size=BLOCK)
    slot = pool.acquire()
    assert pool.ensure_blocks(slot, 9) == "ok"
    tables = np.zeros((2, pool.blocks_per_row), np.int32)
    tables[slot] = pool.table(slot)
    rows = np.random.default_rng(3).normal(size=(3, 2, 10, 20)).astype(
        np.float32)
    pool.scatter_rows(jnp.asarray(rows), tables)
    (got,) = slot_rows(pool, slot)
    assert np.array_equal(got[:, :10, :20], rows[:, slot])
    assert not got[..., 20:].any()


def _dense_cfg():
    return tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                 d_ff=64, compute_dtype=jnp.float32)


def _hybrid_cfg():
    from rayfed_tpu.models import falcon_h1

    return falcon_h1.FalconH1Config(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=64, d_ssm=32, ssm_heads=4, ssm_head_dim=8,
        ssm_state=8,
        ssm_groups=1, ssm_conv=4, compute_dtype=jnp.float32,
        param_dtype=jnp.float32)


def _expert_cfg():
    from rayfed_tpu.models import cohere2_moe

    return cohere2_moe.Cohere2MoeConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_expert=16, n_experts=4, top_k=2, n_shared=1,
        layer_types=("sliding", "full"), window=8,
        compute_dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.mark.parametrize(
    "make, kv_heads", [(_dense_cfg, 4), (_hybrid_cfg, 2), (_expert_cfg, 2)],
    ids=["dense", "hybrid", "expert"])
def test_the_other_models_pools_are_a_key_and_a_value_array_as_before(
        make, kv_heads):
    """The dense, hybrid and expert models declare a key and a value array
    of (K/V heads, head size): two arrays of the shape the pool gave them
    before it asked."""
    cfg = make()
    pool = PagedKVPool(cfg, max_slots=2, max_len=24, dtype=jnp.float32,
                       block_size=BLOCK)
    assert decode.serving_model(cfg).kv_spec() == (
        (2, (kv_heads, 8)), (2, (kv_heads, 8)))
    k, v = pool.kv
    assert k.shape == v.shape == (2, 1 + 2 * pool.blocks_per_row, BLOCK,
                                  kv_heads, 8)
    assert pool.token_bytes == 2 * 2 * kv_heads * 8 * 4
    assert pool.nbytes == k.nbytes + v.nbytes + sum(
        a.nbytes for a in pool.state.values())


def test_only_a_row_of_single_vectors_is_padded_to_whole_tiles():
    """A per-token shape of one dimension that is not a multiple of 128
    is allocated padded (the latent pool); a whole-tile width is not, nor
    is any (heads, head size) array, whatever its head size: the dense,
    hybrid and expert models' pools are allocated as they were."""
    wide = pm.PanguUltraMoeConfig.from_published(
        dict(TINY, kv_lora_rank=120, qk_rope_head_dim=8))
    assert PagedKVPool(wide, max_slots=1, max_len=16,
                       block_size=BLOCK).kv[0].shape[-1] == 128
    pool = PagedKVPool(_dense_cfg(), max_slots=1, max_len=16,
                       block_size=BLOCK)
    assert pool.kv[0].shape[-2:] == (4, 8)
    assert pool.nbytes == pool.token_bytes * (
        1 + pool.num_blocks) * BLOCK


def test_what_is_written_leaves_the_padding_zero():
    """After prefill (bucketed and chunked) and decode steps the pool's
    columns past the model's width are still zero."""
    srv = _server()
    try:
        for n in (5, 21):
            srv.submit(_tokens(n, seed=n).tolist(),
                       max_new_tokens=5).result(timeout=300)
        latent = np.asarray(srv.pool.kv[0])
    finally:
        srv.stop()
    assert np.abs(latent[..., :20]).max() > 0
    assert not latent[..., 20:].any()


# -- counters ---------------------------------------------------------------------


def _reference_routing(seq, w, hp):
    """Per expert layer the experts each position chose, (Le, S, k), and
    the smallest margin between a k-th and a (k+1)-th score."""
    positions = jnp.arange(len(seq))
    x = w["embed"][jnp.asarray(seq)].astype(jnp.float32)
    chosen, margin = [], np.inf
    for lay in w["layers"]:
        if "router" in lay:
            a = x + ref.rms_norm(ref.attention(
                ref.rms_norm(x, lay["ln1"], hp.eps), lay, positions, hp,
                None), lay["ln2"], hp.eps)
            h = ref.rms_norm(a, lay["ln3"], hp.eps)
            idx, _, sigma = ref.routing(h, lay["router"], hp, None)
            ranked = np.sort(np.asarray(sigma), -1)[:, ::-1]
            margin = min(margin, (ranked[:, hp.top_k - 1]
                                  - ranked[:, hp.top_k]).min())
            chosen.append(np.asarray(idx))
        x = ref.layer(x, lay, positions, hp)
    return np.stack(chosen), margin


def test_the_engines_counters_against_the_references_routing():
    """One request alone on a chip that holds experts 4..7 of 16:
    ``moe_experts_hit`` and ``moe_assignments_local`` against NumPy's
    count from the reference's routing at the decoded positions (the
    dense layer holds no expert), ``decode_keys_attended`` from the
    positions, and the ids' array two counters longer."""
    model = dict(TINY, n_routed_experts=4, router_experts=16,
                 held_experts_first=4)
    cfg, w, params = _weights(model, seed=5)
    held = seeded.held_of(model)
    assert cfg.held == held == (4, 5, 6, 7) and cfg.n_experts == 16
    plen, n_new = 13, 11
    srv = _server(cfg, params, max_slots=2)
    try:
        prompt = _tokens(plen, seed=21).tolist()
        out = srv.submit(prompt, max_new_tokens=n_new).result(timeout=300)
        st = srv.stats()
    finally:
        srv.stop()
    seq = prompt + out["tokens"][:-1]
    chosen, margin = _reference_routing(seq, w, ref.hyper_of(model, held))
    assert margin > TIE, "a tie the rounding could turn: draw other tokens"
    assert chosen.shape[0] == 2
    decoded = np.isin(chosen[:, plen:], held)
    assert st["steps"] == n_new - 1
    assert st["moe_assignments_local"] == decoded.sum()
    assert st["moe_experts_hit"] == decoded.sum()   # one row: distinct ids
    assert 0 < decoded.sum() < decoded.size
    # A row at position p scores its p cached keys and its own, on each
    # of the three layers.
    assert st["decode_keys_attended"] == 3 * sum(
        pos + 1 for pos in range(plen, plen + n_new - 1))
    assert st["kv_layer_blocks_attended"] == 3 * st["kv_blocks_attended"]
    assert st["prefill_tokens"] == plen
    assert st["prefill_keys_attended"] == 3 * sum(
        q + 1 for q in range(plen))
    assert st["kv_token_bytes"] == 3 * 20 * 4
    assert st["fetch_bytes"] == (n_new - 1) * 4 * (2 + 2) + 4


def test_a_windowed_model_counts_the_keys_its_windows_let_it_score():
    """``decode_keys_attended`` for the expert model with a window: a
    sliding layer's row scores at most ``window`` keys."""
    model = dict(
        seeded_cohere2_moe_tiny(), num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"])
    w = seeded_cohere2_moe.make_canonical(
        seeded_cohere2_moe.key_of(3), model, jnp.float32)
    cfg = seeded_cohere2_moe.program_cfg(model, F32)
    srv = InferenceServer(cfg, ServingConfig(
        max_slots=2, max_len=MAX_LEN, kv_block_size=BLOCK,
        prefill_chunk=CHUNK, prefix_reuse=False),
        params=seeded_cohere2_moe.to_program_tree(w),
        cache_dtype=jnp.float32)
    try:
        srv.submit(_tokens(13, seed=2).tolist(),
                   max_new_tokens=6).result(timeout=300)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["decode_keys_attended"] == sum(
        min(pos + 1, 8) + pos + 1 for pos in range(13, 18))
    assert st["kv_token_bytes"] == 2 * 2 * 2 * 8 * 4


def seeded_cohere2_moe_tiny():
    return {
        "vocab_size": 96, "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 16,
        "num_experts": 16, "num_experts_per_tok": 4,
        "num_shared_experts": 2, "sliding_window": 8, "rope_theta": 50000,
        "layer_norm_eps": 1e-5, "logit_scale": 1,
        "model_type": "cohere2_moe",
    }


def test_the_scopes_are_metadata_on_the_lowered_programs():
    """``serve/mla_project`` in all three programs, ``serve/attn_latent``
    in the decode step, ``serve/attn_expand`` in both prefill programs,
    and the expert layer's three."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=16, dtype=jnp.float32,
                       block_size=BLOCK)
    rows = jnp.zeros((2,), jnp.int32)
    step = pool._decode_step_fn.lower(
        PARAMS, pool.kv, rows, rows,
        jnp.zeros((2, pool.blocks_per_row), jnp.int32),
        jnp.zeros((3, 2), jnp.int32), rows, jnp.ones((2,), bool), {},
        jnp.ones((2,), bool),
    ).as_text(debug_info=True)
    for scope in ("serve/decode_step", "serve/mla_project",
                  "serve/attn_latent", "serve/moe_route",
                  "serve/moe_experts", "serve/moe_shared"):
        assert scope in step, scope
    assert "serve/attn_expand" not in step
    model = decode.serving_model(CFG)
    chunk = jax.jit(model.chunk).lower(
        PARAMS, pool.kv, {}, jnp.zeros((pool.blocks_per_row,), jnp.int32),
        jnp.int32(0), jnp.zeros((8,), jnp.int32), jnp.int32(0),
        jnp.int32(8)).as_text(debug_info=True)
    prefill = jax.jit(lambda p, t, i: pm.prefill_rows(
        p, t, i, jnp.float32, CFG)).lower(
        PARAMS, jnp.zeros((2, 8), jnp.int32),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    for text in (chunk, prefill):
        for scope in ("serve/mla_project", "serve/attn_expand",
                      "serve/moe_experts"):
            assert scope in text, scope
        assert "serve/attn_latent" not in text
