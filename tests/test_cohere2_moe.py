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

"""Cohere2-MoE (routed and shared experts in a parallel block over sliding
and full layers) through the serving engine, against the repo's plain
reference (``chipbench/references/cohere2_moe.py``: float32, every held
expert on every token, no cache, no grouping) on seeded weights at a tiny
size: 4 layers (three sliding, one full), window 8, block 4, 16 experts
with 4 a token, 2 shared, 4 query heads over 2 K/V heads.

Tolerances. The float32 program against the float32 reference differs by
the order of its sums only: 2e-4 on logits of unit scale. A router near a
tie may pick another k-th expert once activations are rounded; every
comparison here runs the program in float32, where the reference's k-th
and (k+1)-th scores (apart by 1e-3 and more in all but a handful of the
tokens drawn) never swap, and the routing counters are compared only
after asserting that margin.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import seeded_cohere2_moe as seeded
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import cohere2_moe as cm
from rayfed_tpu.models import decode
from rayfed_tpu.models import moe
from rayfed_tpu.models import transformer as tfm
from rayfed_tpu.serving.kv_pool import PagedKVPool
from rayfed_tpu.serving.server import InferenceServer
from tests.utils import record_logits, slot_rows

ref = importlib.import_module("chipbench.references.cohere2_moe")

WINDOW, BLOCK, CHUNK, MAX_LEN = 8, 4, 8, 64
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
# Published keys at a tiny size; every expert held.
TINY = {
    "vocab_size": 96, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 16,
    "num_hidden_layers": 4, "layer_types": KINDS, "num_experts": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 2,
    "sliding_window": WINDOW, "rope_theta": 50000, "layer_norm_eps": 1e-5,
    "logit_scale": 1, "model_type": "cohere2_moe",
}
TOL = 2e-4
# The routing margin under which a comparison of routing is not made.
TIE = 1e-5


def _weights(model=TINY, seed=3, dtype=jnp.float32):
    w = seeded.make_canonical(seeded.key_of(seed), model, dtype)
    cfg = seeded.program_cfg(model, {"compute": jnp.dtype(dtype).name,
                                     "parameters": jnp.dtype(dtype).name})
    return cfg, w, seeded.to_program_tree(w)


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


# -- the model against the reference ----------------------------------------


def test_the_configuration_from_published_keys():
    assert CFG.layer_types == ("sliding",) * 3 + ("full",)
    assert CFG.held == tuple(range(16))
    assert cm.serving_model(CFG).layer_windows() == (8, 8, 8, None)
    # The depth cuts the published list of layer types.
    six = cm.Cohere2MoeConfig.from_published(
        dict(TINY, num_hidden_layers=6, layer_types=KINDS * 2))
    assert six.layer_types[3:] == ("full", "sliding", "sliding")
    with pytest.raises(ValueError, match="expert_selection_fn"):
        cm.Cohere2MoeConfig.from_published(
            dict(TINY, expert_selection_fn="softmax"))
    with pytest.raises(ValueError, match="held"):
        cm.Cohere2MoeConfig.from_published(TINY, held=(3, 3))


@pytest.mark.parametrize("layers", [4, 6], ids=["one-period", "and-a-half"])
def test_forward_matches_the_plain_reference(layers):
    """Logits at every position of a context several windows long, every
    expert held."""
    model = dict(TINY, num_hidden_layers=layers, layer_types=KINDS * 2)
    cfg, w, params = _weights(model)
    toks = _tokens(37)
    want = _ref_logits(toks, w, ref.hyper_of(model, seeded.held_of(model)))
    got = np.asarray(jax.jit(lambda p, t: cm.forward(p, t, cfg))(
        params, jnp.asarray(toks[None])))[0]
    assert 0.5 < want.std() < 2.0, "the logits' scale the tolerance assumes"
    assert np.abs(got - want).max() < TOL


def test_every_branch_of_the_parallel_block_matters():
    """A branch whose weights are zeroed moves the logits by far more
    than the tolerance: the comparison above is blind to none."""
    toks = jnp.asarray(_tokens(23, seed=1)[None])
    forward = jax.jit(lambda p: cm.forward(p, toks, CFG))
    base = np.asarray(forward(PARAMS))
    for name in ("wo", "we_down", "ws_down"):
        layers = [dict(lay, **{name: np.zeros_like(lay[name])})
                  for lay in PARAMS["layers"]]
        got = np.asarray(forward(dict(PARAMS, layers=layers)))
        assert np.abs(got - base).max() > 0.05, name


# -- the expert layer ----------------------------------------------------------


def _layer_inputs(seed=5, s=29):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(s, 32)),
                    jnp.float32)
    return x, ref.layer_norm(x, W["layers"][0]["ln"], HP.eps)


def _share(lay, held):
    """A canonical layer with only the experts ``held`` handed over."""
    held = np.asarray(held)
    return dict(lay, **{name: lay[name][held]
                        for name in ("we_gate", "we_up", "we_down")})


def _program_layer(lay):
    tree = seeded.to_program_tree({"layers": [lay]})["layers"][0]
    return jax.tree_util.tree_map(jnp.asarray, tree)


def test_the_shares_add_up_to_the_uncut_layer():
    """For ``held`` = each eighth of the experts in turn: the routed parts
    the shares give, summed, plus attention and the shared experts counted
    once, are the uncut reference layer; and each share's part is the
    reference's for that share."""
    x, h = _layer_inputs()
    lay = W["layers"][0]
    positions = jnp.arange(x.shape[0])
    uncut = np.asarray(ref.layer(x, lay, positions, KINDS[0], HP))
    att = ref.attention(h, lay, positions, KINDS[0], HP, None)
    total = np.asarray(x + att + ref.shared(h, lay, None))
    for first in range(0, 16, 2):
        held = (first, first + 1)
        part, _, _ = moe.routed_experts(
            h, _program_layer(_share(lay, held)), held, HP.top_k)
        want = ref.routed(h, _share(lay, held), HP._replace(held=held), None)
        assert np.abs(np.asarray(part) - np.asarray(want)).max() < 1e-5
        assert np.abs(np.asarray(part)).max() > 1e-3 or first  # not empty
        total = total + np.asarray(part)
    assert np.abs(total - uncut).max() < 1e-4


def test_routing_is_sigmoid_top_k_normalised_over_the_k():
    _, h = _layer_inputs(seed=6)
    idx, w = moe.route_sigmoid_topk(h, jnp.asarray(W["layers"][1]["router"]),
                                    HP.top_k)
    scores = 1 / (1 + np.exp(-np.asarray(h) @ np.asarray(
        W["layers"][1]["router"])))
    want = np.argsort(-scores, -1)[:, :HP.top_k]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want))
    top = np.take_along_axis(scores, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("rows", [1, 5, 40], ids=["1", "5", "40-rows"])
def test_grouped_experts_count_what_the_routing_chose(rows):
    """``experts_hit`` and ``assignments`` against NumPy's count from the
    reference's routing, rows that are not live left out; and a row that
    is not live gets nothing from any expert."""
    _, h = _layer_inputs(seed=7, s=rows)
    lay, held = W["layers"][2], (2, 3, 9, 12)
    live = np.arange(rows) % 3 != 1
    idx, _, sigma = ref.routing(h, lay["router"], HP, None)
    ranked = np.sort(np.asarray(sigma), -1)[:, ::-1]
    assert (ranked[:, HP.top_k - 1] - ranked[:, HP.top_k]).min() > TIE
    chosen = np.isin(np.asarray(idx), held) & live[:, None]
    layer = _program_layer(_share(lay, held))
    y, hit, local = jax.jit(
        lambda h, live: moe.routed_experts(h, layer, held, HP.top_k, live)
    )(h, jnp.asarray(live))
    assert int(local) == chosen.sum()
    assert int(hit) == len(set(np.asarray(idx)[chosen].tolist()))
    assert not np.asarray(y)[~live].any()
    want = ref.routed(h, _share(lay, held), HP._replace(held=held), None)
    assert np.abs(np.asarray(y) - np.asarray(want))[live].max() < 1e-5


# -- the two kinds of layer ----------------------------------------------------------


def test_a_full_layer_ignores_positions_and_a_sliding_one_rotates():
    _, h = _layer_inputs(seed=9, s=12)
    layer = _program_layer(W["layers"][0])
    h = h[None]
    pos = jnp.arange(12)[None]
    q0, k0, _ = cm.qkv(h, layer, pos, "full", CFG)
    q1, k1, _ = cm.qkv(h, layer, pos + 5, "full", CFG)
    assert np.array_equal(np.asarray(q0), np.asarray(q1))
    assert np.array_equal(np.asarray(k0), np.asarray(k1))
    qs0, ks0, _ = cm.qkv(h, layer, pos, "sliding", CFG)
    qs1, ks1, _ = cm.qkv(h, layer, pos + 5, "sliding", CFG)
    assert np.abs(np.asarray(qs0) - np.asarray(qs1)).max() > 0.1
    # ... and what attention reads of positions is their difference.
    s0 = np.einsum("bqhd,bkhd->bhqk", qs0[:, :, :2], ks0)
    s1 = np.einsum("bqhd,bkhd->bhqk", qs1[:, :, :2], ks1)
    np.testing.assert_allclose(s0, s1, atol=1e-4)
    # Adjacent pairs (GPT-J's form): position 1 turns (x0, x1) by 1 rad.
    x = jnp.zeros((1, 1, 8)).at[0, 0, 0].set(1.0)
    got = np.asarray(cm.rope_pairs(x, jnp.asarray([1]), 50000.0))[0, 0]
    np.testing.assert_allclose(got[:2], [np.cos(1.0), np.sin(1.0)], atol=1e-6)


def _attention_paths():
    """Three ways a layer attends: over a whole sequence, a chunk and a
    decode token through the block tables; each as
    ``f(k_all, kind) -> the last position's output`` for keys (P + 1, Hkv,
    Dh) at positions 0..P."""
    rng = np.random.default_rng(11)
    p = 21                                     # the last query's position
    q = jnp.asarray(rng.normal(size=(p + 1, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(p + 1, 2, 8)), jnp.float32)

    def whole(k, kind):
        return cm.seq_attention(q, k, v, jnp.arange(p + 1), kind, CFG)[-1]

    def chunked(k, kind):
        # The last CHUNK positions as a chunk: its own keys in hand, the
        # ones before it in the pool, behind a table that runs backwards.
        off = p + 1 - CHUNK
        nb = -(-off // BLOCK)
        pad = nb * BLOCK - off
        blocks = lambda a: jnp.pad(  # noqa: E731
            a[:off], ((0, pad), (0, 0), (0, 0))).reshape(nb, BLOCK, 2, 8)
        order = np.arange(nb)[::-1]
        pk = jnp.zeros((2, 1 + nb, BLOCK, 2, 8)).at[1, 1 + order].set(
            blocks(k))
        pv = jnp.zeros((2, 1 + nb, BLOCK, 2, 8)).at[1, 1 + order].set(
            blocks(v))
        table = jnp.zeros((-(-(MAX_LEN + 1) // BLOCK),), jnp.int32).at[
            :nb].set(1 + order)
        attend = decode.paged_chunk_attention(
            pk, pv, table, jnp.asarray(off), jnp.asarray(CHUNK),
            window=WINDOW if kind == "sliding" else None)
        return attend(q[off:], k[off:], v[off:], 1 + nb)[-1]

    def paged(k, kind):
        nb = -(-(p + 1) // BLOCK)
        pad = nb * BLOCK - p - 1
        blocks = lambda a: jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(  # noqa: E731
            1, nb, BLOCK, 2, 8)
        # Physical block 0 is the sacrificial one; the row's follow it.
        pk = jnp.concatenate([jnp.zeros((1, 1, BLOCK, 2, 8)), blocks(k)], 1)
        pv = jnp.concatenate([jnp.zeros((1, 1, BLOCK, 2, 8)), blocks(v)], 1)
        tables = jnp.arange(1, nb + 1)[None]
        attend = decode.paged_attention(
            pk, pv, jnp.asarray([p]), tables,
            window=WINDOW if kind == "sliding" else None)
        return attend(q[p:], k[p:], v[p:], 0)[0]

    k = jnp.asarray(rng.normal(size=(p + 1, 2, 8)), jnp.float32)
    return p, k, {"sequence": whole, "chunk": chunked, "paged": paged}


@pytest.mark.parametrize("path", ["sequence", "chunk", "paged"])
def test_a_sliding_layer_sees_its_window_and_nothing_before_it(path):
    """Altering a key just outside the window leaves the output as it
    was, bit for bit; altering the oldest key inside it does not. A full
    layer sees both."""
    p, k, paths = _attention_paths()
    attend = paths[path]
    outside, oldest = p - WINDOW, p - WINDOW + 1
    for kind, blind in (("sliding", True), ("full", False)):
        base = np.asarray(attend(k, kind))
        moved = np.asarray(attend(k.at[outside].add(3.0), kind))
        assert np.array_equal(base, moved) == blind, kind
        moved = np.asarray(attend(k.at[oldest].add(3.0), kind))
        assert np.abs(base - moved).max() > 1e-3, kind
    # The three paths are one attention.
    want = np.asarray(paths["sequence"](k, "sliding"))
    assert np.abs(np.asarray(attend(k, "sliding")) - want).max() < 1e-5


def test_the_windowed_paged_read_starts_at_each_rows_window(
        monkeypatch):
    """Rows of unequal length, one shorter than the window, one several
    windows long, a junk row at position 0: each row's output is its own
    windowed attention, and the loop is as long as a window, not as the
    longest row."""
    monkeypatch.setattr(decode, "PAGED_CHUNK_KEYS", 2 * BLOCK)
    rng = np.random.default_rng(13)
    lengths = [5, 0, 43, 30]                   # cached keys per row
    nb = 12
    pk = jnp.asarray(rng.normal(size=(1, 1 + 4 * nb, BLOCK, 2, 8)),
                     jnp.float32)
    pv = jnp.asarray(rng.normal(size=pk.shape), jnp.float32)
    tables = np.zeros((4, nb), np.int32)
    for r, n in enumerate(lengths):
        if n:
            tables[r] = 1 + r * nb + np.arange(nb)
    q = jnp.asarray(rng.normal(size=(4, 4, 8)), jnp.float32)
    k1 = jnp.asarray(rng.normal(size=(4, 2, 8)), jnp.float32)
    v1 = jnp.asarray(rng.normal(size=(4, 2, 8)), jnp.float32)
    pos = jnp.asarray(lengths, jnp.int32)
    out = decode.paged_attention(pk, pv, pos, jnp.asarray(tables),
                                 window=WINDOW)(q, k1, v1, 0)
    for r, n in enumerate(lengths):
        keys = np.asarray(pk)[0, tables[r]].reshape(-1, 2, 8)[:n]
        vals = np.asarray(pv)[0, tables[r]].reshape(-1, 2, 8)[:n]
        keys = np.concatenate([keys, np.asarray(k1)[r:r + 1]])
        vals = np.concatenate([vals, np.asarray(v1)[r:r + 1]])
        want = cm.seq_attention(
            jnp.zeros((n + 1, 4, 8)).at[n].set(q[r]), jnp.asarray(keys),
            jnp.asarray(vals), jnp.arange(n + 1), "sliding", CFG)[-1]
        assert np.abs(np.asarray(out[r]) - np.asarray(want)).max() < 1e-5, r
    # Trips follow the widest window in chunks (8 keys here: a window of
    # 8 spans at most two chunks), not the longest row (6 chunks).
    trips = []
    fori = jax.lax.fori_loop
    monkeypatch.setattr(
        jax.lax, "fori_loop",
        lambda lo, hi, *a: trips.append((int(lo), int(hi))) or fori(
            lo, hi, *a))
    with jax.disable_jit():
        decode.paged_attention(pk, pv, pos, jnp.asarray(tables),
                               window=WINDOW)(q, k1, v1, 0)
        decode.paged_attention(pk, pv, pos, jnp.asarray(tables))(
            q, k1, v1, 0)
    assert trips == [(0, 2), (0, 6)]


def _parent_paged_attention(pk, pv, positions, tables):
    """``decode.paged_attention`` as the parent commit had it, verbatim
    (no window): what the dense and hybrid cells' programs were built
    from."""
    n_layers, n_phys, bs, n_kv, dh = pk.shape
    n_rows, blocks_per_row = tables.shape
    chunk_blocks = max(1, min(blocks_per_row, decode.PAGED_CHUNK_KEYS // bs))
    chunk_keys = chunk_blocks * bs
    tables_p = jnp.pad(
        tables, ((0, 0), (0, -blocks_per_row % chunk_blocks))
    )
    trips = (jnp.max(positions) + chunk_keys - 1) // chunk_keys
    pk_flat = pk.reshape(n_layers * n_phys, bs, n_kv, dh)
    pv_flat = pv.reshape(n_layers * n_phys, bs, n_kv, dh)
    scale = dh**-0.5

    def attend(q, k1, v1, base):
        n_heads = q.shape[1]
        q = q.reshape(n_rows, n_kv, n_heads // n_kv, dh)
        s1 = jnp.einsum(
            "rhgd,rhd->rhg", q, k1, preferred_element_type=jnp.float32
        ) * scale

        def chunk(c, carry):
            m, den, acc = carry
            blocks = base + jax.lax.dynamic_slice_in_dim(
                tables_p, c * chunk_blocks, chunk_blocks, axis=1
            )
            kc = pk_flat[blocks].reshape(n_rows, chunk_keys, n_kv, dh)
            vc = pv_flat[blocks].reshape(n_rows, chunk_keys, n_kv, dh)
            k_pos = c * chunk_keys + jnp.arange(chunk_keys)
            cached = k_pos[None, :] < positions[:, None]
            s = jnp.einsum(
                "rhgd,rkhd->rhgk", q, kc, preferred_element_type=jnp.float32
            ) * scale
            s = jnp.where(cached[:, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            den = den * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "rhgk,rkhd->rhgd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return m_new, den, acc

        v_first = jnp.broadcast_to(
            v1.astype(jnp.float32)[:, :, None, :], q.shape
        )
        init = (s1, jnp.ones_like(s1), v_first)
        _, den, acc = jax.lax.fori_loop(0, trips, chunk, init)
        out = (acc / den[..., None]).astype(v1.dtype)
        return out.reshape(n_rows, n_heads, dh)

    return attend


@pytest.mark.parametrize(
    "heads, kv_heads", [(4, 4), (4, 2)], ids=["dense", "grouped-heads"])
def test_the_paged_read_without_a_window_is_the_parents_program(
        heads, kv_heads):
    """With no window the loop and the mask are the parent's to the bit:
    the same traced program (its jaxpr, letter for letter) and the same
    numbers, on the dense and the grouped-head shapes."""
    rng = np.random.default_rng(17)
    nb, rows = 6, 3
    pk = jnp.asarray(rng.normal(size=(2, 1 + rows * nb, BLOCK, kv_heads, 8)),
                     jnp.bfloat16)
    pv = jnp.asarray(rng.normal(size=pk.shape), jnp.bfloat16)
    tables = jnp.asarray(
        1 + np.arange(rows * nb).reshape(rows, nb), jnp.int32)
    pos = jnp.asarray([3, 0, 22], jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, heads, 8)), jnp.bfloat16)
    k1 = jnp.asarray(rng.normal(size=(rows, kv_heads, 8)), jnp.bfloat16)
    v1 = jnp.asarray(rng.normal(size=(rows, kv_heads, 8)), jnp.bfloat16)

    def run(fn):
        return lambda *a: fn(*a[:4])(*a[4:], 1 + rows * nb)

    args = (pk, pv, pos, tables, q, k1, v1)
    new, old = run(decode.paged_attention), run(_parent_paged_attention)
    assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(old)(*args))
    assert np.array_equal(np.asarray(jax.jit(new)(*args), np.float32),
                          np.asarray(jax.jit(old)(*args), np.float32))


# -- prefill then decode through the engine ------------------------------------


@pytest.mark.parametrize(
    "plen", [3, CHUNK, CHUNK + 1, 21, 4 * CHUNK + 5],
    ids=["under-a-window", "chunk", "chunk+1", "two-windows",
         "four-chunks-and-a-rest"],
)
def test_prefill_then_decode_matches_the_reference_forward(plen, monkeypatch):
    """Every logits row the engine chooses a token from (the bucketed or
    the chunked prefill's last position, then each decode step through the
    block tables, contexts crossing window, block and chunk boundaries) ==
    the reference's full forward over prompt + served tokens."""
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
    """Three requests in one batch, one shorter than the window, one
    several windows long (chunked), then a shorter request into a slot
    that held a longer one: each one's logits are the reference's for it
    alone."""
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
        # Every slot has held a request; the next one reuses one, over
        # blocks and positions that a longer request wrote.
        again = _tokens(4, seed=4).tolist()
        out = srv.submit(again, max_new_tokens=9, seed=104).result(
            timeout=300)
        assert _served_against_reference(seen, 104, again, out, 9) < TOL
    finally:
        srv.stop()


# -- counters ------------------------------------------------------------------------


def _reference_routing(seq, w, hp):
    """Per layer the experts each position chose, (L, S, k), and the
    smallest margin between a k-th and a (k+1)-th score."""
    positions = jnp.arange(len(seq))
    x = w["embed"][jnp.asarray(seq)].astype(jnp.float32)
    chosen, margin = [], np.inf
    for lay, kind in zip(w["layers"], hp.layer_types):
        h = ref.layer_norm(x, lay["ln"], hp.eps)
        idx, _, sigma = ref.routing(h, lay["router"], hp, None)
        ranked = np.sort(np.asarray(sigma), -1)[:, ::-1]
        margin = min(margin,
                     (ranked[:, hp.top_k - 1] - ranked[:, hp.top_k]).min())
        chosen.append(np.asarray(idx))
        x = ref.layer(x, lay, positions, kind, hp)
    return np.stack(chosen), margin


def test_the_engines_counters_against_the_references_routing():
    """One request alone on a chip that holds experts 4..7 of 16:
    ``moe_experts_hit`` and ``moe_assignments_local`` against NumPy's
    count from the reference's routing at the decoded positions,
    ``kv_layer_blocks_attended`` and ``prefill_keys_attended`` from the
    positions and the window, and the ids' array two counters longer."""
    model = dict(TINY, num_experts=4, router_experts=16, held_experts_first=4)
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
    # Decode step t reads the token at position plen + t - 1 ... of n_new
    # tokens the first comes from prefill.
    decoded = np.isin(chosen[:, plen:], held)
    assert st["steps"] == n_new - 1
    assert st["moe_assignments_local"] == decoded.sum()
    assert st["moe_experts_hit"] == decoded.sum()   # one row: distinct ids
    assert 0 < decoded.sum() < decoded.size
    want_blocks = want_all = 0
    for pos in range(plen, plen + n_new - 1):
        full = pos // BLOCK + 1
        windowed = pos // BLOCK - max(pos - WINDOW + 1, 0) // BLOCK + 1
        want_blocks += 3 * windowed + full
        want_all += full
    assert st["kv_blocks_attended"] == want_all
    assert st["kv_layer_blocks_attended"] == want_blocks < 4 * want_all
    assert st["prefill_tokens"] == plen
    assert st["prefill_keys_attended"] == sum(
        3 * min(q + 1, WINDOW) + q + 1 for q in range(plen))
    # R ids and the two counters a step; R ids a prefill round or 4 B a
    # last chunk.
    assert st["fetch_bytes"] == (n_new - 1) * 4 * (2 + 2) + 4


def test_a_model_without_counters_fetches_the_ids_alone():
    """The dense model's ``fetch_bytes`` a step is 4 x ``max_slots`` as
    before, its decode program takes no ``live``, and its per-layer block
    count is the plain one times its layers."""
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    srv = InferenceServer(cfg, ServingConfig(
        max_slots=3, max_len=32, kv_block_size=4, prefill_chunk=8,
        prefix_reuse=False), params=params)
    try:
        assert srv.pool.step_counters == ()
        srv.submit(list(range(1, 6)), max_new_tokens=6).result(timeout=300)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["steps"] == 5
    assert st["fetch_bytes"] == 4 * 3 * (st["steps"] + 1)
    assert st["kv_layer_blocks_attended"] == 2 * st["kv_blocks_attended"]
    assert "moe_experts_hit" not in st


def test_the_pool_lands_rows_of_the_length_they_come_in():
    """A model's bucketed prefill hands back rows as long as its bucket:
    they land in the first blocks of each landed row's table, and what
    lies behind them stays."""
    pool = PagedKVPool(CFG, max_slots=2, max_len=MAX_LEN, dtype=jnp.float32,
                       block_size=BLOCK)
    slot = pool.acquire()
    assert pool.ensure_blocks(slot, 11) == "ok"
    tables = np.zeros((2, pool.blocks_per_row), np.int32)
    tables[slot] = pool.table(slot)
    rng = np.random.default_rng(3)
    long_rows = rng.normal(size=(4, 2, 12, 2, 8)).astype(np.float32)
    pool.scatter_rows(jnp.asarray(long_rows), jnp.asarray(long_rows), tables)
    short = rng.normal(size=(4, 2, 6, 2, 8)).astype(np.float32)
    pool.scatter_rows(jnp.asarray(short), jnp.asarray(-short), tables)
    k_row, v_row = slot_rows(pool, slot)
    assert np.array_equal(np.asarray(k_row)[:, :6], short[:, slot])
    assert np.array_equal(np.asarray(v_row)[:, :6], -short[:, slot])
    # Block 1 (positions 4..7) was rewritten whole: 6, 7 by the padding.
    assert np.array_equal(np.asarray(k_row)[:, 8:12], long_rows[:, slot, 8:])
