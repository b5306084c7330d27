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

"""SDAR-MoE (routed experts by softmax in every layer, per-head query/key
norms, generation by diffusion over blocks) through the serving engine,
against the repo's plain reference (``chipbench/references/sdar_moe.py``:
float32, every expert on every token, no cache, the routine written out)
on seeded weights at a tiny size: 3 layers, 8 experts with 2 a token, 4
query heads over 2 K/V heads, blocks of 4 denoised in 4 steps, the pool's
blocks 4 or 8 long.

Tolerances. The float32 program against the float32 reference differs by
the order of its sums only: 2e-4 on logits of unit scale. Tokens are
compared where the head is peaked (``PEAK``: the head's rows scaled so
that a position's best logit leads its second by far more than that).
"""

from __future__ import annotations

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import seeded_cohere2_moe as seeded_cohere
from chipbench import seeded_sdar_moe as seeded
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import decode
from rayfed_tpu.models import moe
from rayfed_tpu.models import sdar_moe as sm
from rayfed_tpu.serving import sampling
from rayfed_tpu.serving.server import InferenceServer

ref = importlib.import_module("chipbench.references.sdar_moe")

B, MASK, CHUNK, MAX_LEN = 4, 95, 8, 64
TINY = {
    "vocab_size": 96, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 16,
    "intermediate_size": 64, "num_hidden_layers": 3, "num_experts": 8,
    "num_experts_per_tok": 2, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rope_scaling": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "model_type": "sdar_moe",
    "block_length": B, "denoising_steps": 4, "mask_token_id": MASK,
}
TOL = 2e-4
# The head's scale under which confidences pass 0.9 at some positions and
# not at others, so that blocks end after one, two, three and four steps.
PEAK = 12.0
WAIT_S = 300


def _weights(model=TINY, seed=3, peak=1.0):
    w = seeded.make_canonical(seeded.key_of(seed), model, jnp.float32)
    w["lm_head"] = w["lm_head"] * peak
    cfg = seeded.program_cfg(model, {"compute": "float32",
                                     "parameters": "float32"})
    return cfg, w, seeded.to_program_tree(w), ref.hyper_of(model)


CFG, W, PARAMS, HP = _weights()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, MASK, size=n).tolist()


def _server(cfg=CFG, params=PARAMS, **kw):
    base = dict(max_slots=3, max_len=MAX_LEN, kv_block_size=4,
                prefill_chunk=CHUNK, prefill_token_budget=2 * CHUNK,
                max_new_tokens=8, prefix_reuse=False)
    base.update(kw)
    return InferenceServer(cfg, ServingConfig(**base), params=params,
                           cache_dtype=cfg.compute_dtype)


def _record_blocks(monkeypatch):
    """Every (block as it came in, its logits) the engine's decode step
    unmasks from, by the request's seed: ``{seed: [(first position less
    the prompt's length, step, block, logits (B, V))]}``. Engines built
    after this call record."""
    seen = {}
    unmask = sampling.unmask

    def record(logits, block, draw, active):
        for row in np.flatnonzero(active):
            seen.setdefault(int(np.uint32(draw[1][row])), []).append(
                (int(draw[2][row]), int(draw[3][row]),
                 [int(t) for t in block[row]], np.array(logits[row])))

    def spy(logits, block, draw, spec, active):
        jax.debug.callback(record, logits, block, draw, active, ordered=True)
        return unmask(logits, block, draw, spec, active)

    monkeypatch.setattr(sampling, "unmask", spy)
    return seen


# -- the model against the reference ------------------------------------------


def test_the_configuration_from_published_keys_and_what_it_refuses():
    assert CFG.held == tuple(range(8)) and CFG.d_expert == 16
    spec = decode.serving_model(CFG).block_spec()
    assert (spec.length, spec.mask_id, spec.steps) == (B, MASK, 4)
    assert [spec.quota(k) for k in range(4)] == [1, 1, 1, 1]
    assert [decode.BlockSpec(8, 0, 3).quota(k) for k in range(3)] == [3, 3, 2]
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            sm.SdarMoeConfig.from_published(dict(TINY, **{key: value}),
                                            mask_id=MASK)
    for field, value in (("block_length", 6), ("denoising_steps", 5),
                         ("remasking", "sequential"), ("mask_id", 96)):
        with pytest.raises(ValueError, match=field):
            sm.SdarMoeConfig.from_published(
                TINY, **{"mask_id": MASK, field: value})


@pytest.mark.parametrize("n", [8, 11], ids=["whole-blocks", "a-cut-block"])
def test_forward_matches_the_plain_reference_under_the_block_mask(n):
    seq = _tokens(n, seed=n)
    run = jax.jit(lambda params, tokens: sm.forward(params, tokens, CFG))
    forward = lambda tokens: run(PARAMS, tokens)  # noqa: E731
    got = np.asarray(forward(jnp.asarray([seq])))[0]
    want = np.asarray(ref.forward(W, jnp.asarray(seq, jnp.int32), HP))
    assert np.abs(got - want).max() < TOL
    # The mask is the block's: a later token of the same block moves a
    # position's logits, one of a later block does not.
    moved = list(seq)
    moved[3] = moved[3] % 90 + 1
    other = np.asarray(forward(jnp.asarray([moved])))[0]
    assert np.abs(other[0] - got[0]).max() > 1e-3
    moved = list(seq)
    moved[4] = moved[4] % 90 + 1
    other = np.asarray(forward(jnp.asarray([moved])))[0]
    assert np.abs(other[:4] - got[:4]).max() == 0


def test_routing_is_softmax_top_k_normalised_over_the_k():
    h = jax.random.normal(jax.random.PRNGKey(5), (29, 32), jnp.float32)
    router = W["layers"][0]["router"]
    idx, w = moe.route_softmax_topk(h, router, 2)
    want_idx, want_w, p = ref.routing(h, router, HP, None)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.abs(np.asarray(w) - np.asarray(want_w)).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    # The sigmoid's experts, other weights.
    s_idx, s_w = moe.route_sigmoid_topk(h, router, 2)
    assert np.array_equal(np.asarray(s_idx), np.asarray(idx))
    assert np.abs(np.asarray(s_w) - np.asarray(w)).max() > 1e-3
    y, hit, local = sm.ffn(h, PARAMS["layers"][0], CFG)
    want = ref.routed(h, W["layers"][0], HP, None)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL
    assert int(local) == 29 * 2 and 0 < int(hit) <= 8


def _parent_routed_experts(h, layer, held, k, live=None, scale=1.0):
    """``moe.routed_experts`` as the parent commit had it (PR 33)."""
    t, d = h.shape
    n_held = len(held)
    n_experts = layer["router"].shape[-1]
    idx, w = moe.route_sigmoid_topk(h, layer["router"], k)
    local_of = np.full(n_experts, n_held, np.int32)
    local_of[np.asarray(held, np.int64)] = np.arange(n_held)
    lid = jnp.asarray(local_of)[idx]
    if live is not None:
        lid = jnp.where(live[:, None], lid, n_held)
    m = t * k
    flat = lid.reshape(m)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.sum(
        flat[:, None] == jnp.arange(n_held), axis=0, dtype=jnp.int32)
    xs = h[order // k]

    def grouped(x, name):
        return moe.grouped_matmul(x, layer[name].astype(h.dtype), counts)

    act = (jax.nn.silu(grouped(xs, "we_gate"))
           * grouped(xs, "we_up")).astype(h.dtype)
    ys = grouped(act, "we_down")
    mine = (jnp.arange(m) < jnp.sum(counts))[:, None]
    if scale != 1.0:
        w = w * scale
    weighted = jnp.where(mine, ys * w.reshape(m)[order][:, None], 0.0)
    return weighted[jnp.argsort(order)].reshape(t, k, d).sum(1)


@pytest.mark.parametrize("scale", [1.0, 2.5], ids=["cohere2", "pangu"])
def test_the_sigmoid_models_get_the_parents_routed_experts(scale):
    """``routed_experts`` without a scoring is the parent's program for
    the two models that pass none: equal bits."""
    model = {"hidden_size": 32, "intermediate_size": 16, "head_dim": 8,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_experts": 4, "router_experts": 16, "num_shared_experts": 1}
    lay = dict(seeded_cohere._leaves(
        jax.random.PRNGKey(9), seeded_cohere.layer_specs(model),
        jnp.bfloat16))
    h = jax.random.normal(jax.random.PRNGKey(2), (23, 32), jnp.bfloat16)
    live = jnp.arange(23) % 5 != 0
    held = (4, 5, 6, 7)
    new = jax.jit(lambda h: moe.routed_experts(
        h, lay, held, 4, live, scale)[0])(h)
    old = jax.jit(lambda h: _parent_routed_experts(
        h, lay, held, 4, live, scale))(h)
    assert np.array_equal(np.asarray(new, np.float32),
                          np.asarray(old, np.float32))


# -- the forward of a pair of blocks ------------------------------------------


def _prefilled(seq, n_phys=12):
    """An empty pool of three slots (tables of 4 blocks of 4 each, slot
    ``r``'s blocks ``1 + 4 r ..``) with ``seq`` (whole blocks) prefilled
    into slot 0 and slot 1: (pk, pv, tables)."""
    head = (CFG.n_layers, 1 + n_phys, B, CFG.n_kv_heads, CFG.head_dim)
    pk = pv = jnp.zeros(head, jnp.float32)
    tables = np.arange(1, 1 + n_phys, dtype=np.int32).reshape(3, -1)
    toks = jnp.asarray(seq + [0] * (-len(seq) % CHUNK), jnp.int32)
    for slot in (0, 1):
        _, pk, pv = sm.chunk(PARAMS, pk, pv, tables[slot], toks, 0, len(seq),
                             CFG)
    return pk, pv, tables


def _block_of(pool, table, start):
    """The K or V rows (L, B, Hkv, Dh) of one slot at positions ``start
    .. start + B - 1``."""
    return np.asarray(pool)[:, table[start // B]]


@pytest.mark.parametrize("second", ["denoises", "junk"])
def test_the_pair_forward_is_the_commit_and_the_next_blocks_first_step(
        second):
    """ONE forward of a clean carried block and the block behind it ==
    the two it replaces: the K/V it keeps for the carried block are those
    a prefill of the same tokens keeps, and the logits it hands back are
    those of the next block's step 0 over that context (a forward of its
    own after the commit, and the reference's ``block_logits``). A row
    beside it whose carried block still holds a mask id denoises that
    block as it would alone and keeps nothing; a junk row touches no
    slot."""
    seq = _tokens(12, seed=21)
    ctx, clean = seq[:8], seq[8:]
    pk, pv, tables = _prefilled(ctx)
    noisy = [clean[0], MASK, clean[2], MASK]
    live = jnp.asarray([True, second == "denoises", False])
    blocks = jnp.asarray([clean, noisy, [0] * B], jnp.int32)
    run = jax.jit(functools.partial(sm.paged_decode_step, cfg=CFG))
    step = functools.partial(run, PARAMS)
    at = jnp.asarray([8, 8, 0], jnp.int32)
    commit = jnp.asarray([True, False, False])
    run_tables = np.array(tables)
    run_tables[2] = 0
    logits, pk1, pv1, counters = step(pk, pv, blocks, at, run_tables, live,
                                      commit)
    # The committed K/V: what a prefill of context + block keeps there.
    want_k, want_v, _ = _prefilled(seq)
    for got, want in ((pk1, want_k), (pv1, want_v)):
        assert np.abs(_block_of(got, tables[0], 8)
                      - _block_of(want, tables[0], 8)).max() < TOL
        # Nothing of the row that denoised, nothing of the second half.
        assert not _block_of(got, tables[1], 8).any()
        assert not _block_of(got, tables[0], 12).any()
        assert not np.asarray(got)[:, tables[2]].any()
    # The next block's step 0: the forward after the commit, alone.
    opened = jnp.asarray([[MASK] * B] * 3, jnp.int32)
    after, pk2, _, _ = step(
        pk1, pv1, opened, jnp.asarray([12, 0, 0], jnp.int32),
        run_tables * np.asarray([[1], [0], [0]], np.int32),
        jnp.asarray([True, False, False]), jnp.zeros(3, bool))
    assert np.array_equal(_block_of(pk2, tables[0], 8),
                          _block_of(pk1, tables[0], 8))
    assert np.abs(np.asarray(logits[0]) - np.asarray(after[0])).max() < TOL
    want = np.asarray(ref.block_logits(W, seq, [MASK] * B, HP))
    assert np.abs(np.asarray(logits[0]) - want).max() < TOL
    if second == "denoises":
        # It reads its carried block's logits.
        want = np.asarray(ref.block_logits(W, ctx, noisy, HP))
        assert np.abs(np.asarray(logits[1]) - want).max() < TOL
    # Routed: the live rows' carried blocks and the committing row's
    # second block, two experts a position, on every layer.
    n_live = B * (2 + (second == "denoises"))
    assert int(counters[1]) == CFG.n_layers * CFG.top_k * n_live


# -- prefill then block decoding through the engine ----------------------------


@pytest.mark.parametrize("rule", sm.RULES)
@pytest.mark.parametrize(
    "plen", [1, 4, 6, 7, CHUNK + 1, 21, 4 * CHUNK + 3],
    ids=["under-a-block", "a-block", "rest-2", "rest-3", "chunk+1",
         "two-chunks-and-a-rest", "four-chunks-rest-3"])
def test_prefill_then_block_decoding_matches_the_references_replay(
        plen, rule, monkeypatch):
    """Every logits row the engine unmasks from (the bucketed or the
    chunked prefill of the prompt's whole blocks, the left-over tokens
    leading the first block, then each denoising step through the block
    tables over the committed blocks) == the reference's one forward of
    the clean served context and the block as the engine had it; and the
    tokens and their steps are the routine's."""
    seen = _record_blocks(monkeypatch)
    cfg, w, params, hp = _weights(dict(TINY, remasking=rule))
    srv = _server(cfg, params)
    try:
        prompt = _tokens(plen, seed=plen)
        out = srv.submit(prompt, max_new_tokens=9, seed=777).result(
            timeout=WAIT_S)
        st = srv.stats()
    finally:
        srv.stop()
    seq = prompt + out["tokens"]
    steps = []
    for index, step, block, logits in seen[777]:
        start = plen + index
        assert start % B == 0 and start <= len(seq)
        want = np.asarray(ref.block_logits(w, seq[:start], block, hp))
        assert np.abs(logits - want).max() < TOL, (start, step)
        steps.append((start, step))
    # Every block of the request was denoised, from step 0 on, in order.
    assert steps == sorted(steps) and steps[0] == (plen - plen % B, 0)
    want_tokens, want_steps = ref.generate(w, prompt, 9, hp)
    assert out["tokens"] == want_tokens
    assert out["unmask_steps"] == want_steps
    whole = plen - plen % B
    assert st["prefill_tokens"] == whole
    assert st["prefill_chunks"] == (0 if whole <= CHUNK
                                    else -(-whole // CHUNK))
    assert st["diffusion_tokens_unmasked"] >= 9
    assert st["diffusion_positions_dropped"] == (-(plen + 9)) % B


def _peaked(steps=4, rule="low_confidence_dynamic"):
    return _weights(dict(TINY, denoising_steps=steps, remasking=rule),
                    peak=PEAK)


@pytest.mark.parametrize("steps", [4, 2], ids=["T=B", "T<B"])
def test_the_dynamic_rule_with_a_peaked_head_token_for_token(steps):
    """Confidences over the threshold end a block early, the others use
    every step: the engine's tokens and the step that unmasked each are
    the routine's, whatever mix of the two a request runs into."""
    cfg, w, params, hp = _peaked(steps)
    srv = _server(cfg, params, max_new_tokens=32)
    try:
        prompts = [_tokens(n, seed=40 + n) for n in (5, 8, 14, 3)]
        outs = [srv.submit(p, max_new_tokens=24).result(timeout=WAIT_S)
                for p in prompts]
        st = srv.stats()
    finally:
        srv.stop()
    last = []
    for prompt, out in zip(prompts, outs):
        tokens, want_steps = ref.generate(w, prompt, 24, hp)
        assert out["tokens"] == tokens
        assert out["unmask_steps"] == want_steps
        start = len(prompt) - len(prompt) % B
        seq_steps = [-1] * (len(prompt) - start) + want_steps
        last += [max(seq_steps[i:i + B]) for i in range(0, 24, B)]
    # Blocks that ended in fewer steps than the schedule, and blocks that
    # needed all of them.
    assert min(last) < steps - 1 and max(last) == steps - 1
    # Every forward of a live row unmasks its step's quota at least (no
    # forward is a commit's alone) and a block at most.
    forwards = st["diffusion_row_forwards"]
    assert B / steps <= st["diffusion_tokens_unmasked"] / forwards < B
    commits, fused = (st["diffusion_commit_forwards"],
                      st["diffusion_fused_forwards"])
    assert 0 < fused <= commits < forwards
    # Every commit the host fetched had opened the next block; the others
    # are those of rows that had ended under their step.
    assert commits - fused <= st["rows_wasted"]


def _without_run_ahead(srv):
    """Every step fetched before the next is built: after an iteration's
    steps, one more pass with no row live takes what is in flight."""
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


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "in-step"])
@pytest.mark.parametrize("steps", [4, 2, 1], ids=["T=4", "T=2", "T=1"])
def test_whole_blocks_at_the_schedules_floor_spend_no_forward_on_a_commit(
        steps, ahead):
    """``N`` blocks under the static rule take ``T`` forwards each and not
    one more: every commit rides in the forward that opens the next
    block, the last block is never committed, and the host never puts a
    row into a step that it knows is past its end."""
    n_blocks = 5
    cfg, w, params, hp = _weights(dict(
        TINY, denoising_steps=steps, remasking="low_confidence_static"))
    srv = _server(cfg, params, max_new_tokens=32)
    if not ahead:
        _without_run_ahead(srv)
    try:
        prompt = _tokens(8, seed=33)
        out = srv.submit(prompt, max_new_tokens=B * n_blocks).result(
            timeout=WAIT_S)
        st = srv.stats()
    finally:
        srv.stop()
    tokens, want_steps = ref.generate(w, prompt, B * n_blocks, hp)
    assert out["tokens"] == tokens and out["unmask_steps"] == want_steps
    assert st["diffusion_row_forwards"] == steps * n_blocks
    assert st["diffusion_tokens_unmasked"] == B * n_blocks
    assert st["diffusion_fused_forwards"] == n_blocks - 1
    assert st["diffusion_commit_forwards"] == n_blocks - 1
    assert st["rows_wasted"] == 0 and st["steps"] == steps * n_blocks
    assert (st["steps_ahead"] > 0) == ahead
    # A forward's B queries see their context and their block. Where a
    # block opens in the forward that commits the one before, those are
    # the committed block's queries, and the opened block's B see the
    # context and both blocks.
    assert st["decode_keys_attended"] == cfg.n_layers * B * sum(
        steps * (8 + B * i + B) + (8 + B * i if i else 0)
        for i in range(n_blocks))


@pytest.mark.parametrize("fault", ["break_commit", "break_blockmask",
                                   "break_unmask"])
def test_the_benchmarks_controls_still_engage_through_the_fused_step(
        fault, monkeypatch):
    """The benchmark's controls patch three functions by name and call
    them by position (``chipbench/kinds/closed_loop_diffusion.py``): the
    step that forwards a pair of blocks still goes through all three, so
    an engine built under one serves without raising, and serves other
    tokens, other steps or keeps other K/V than a sound one."""
    kind = importlib.import_module("chipbench.kinds.closed_loop_diffusion")

    plens = (6, 9)

    def served():
        srv = _server()
        try:
            outs = [srv.submit(_tokens(n, seed=70 + n),
                               max_new_tokens=13).result(timeout=WAIT_S)
                    for n in plens]
            kept = [np.asarray(a) for a in srv.pool.kv]
        finally:
            srv.stop()
        return [(o["tokens"], o["unmask_steps"]) for o in outs], kept

    sound, sound_kv = served()
    # (monkeypatch puts the sound functions back.)
    for module, name in ((decode, "paged_block_write"),
                         (decode, "paged_block_attention"),
                         (sampling, "choose_with_confidence")):
        monkeypatch.setattr(module, name, getattr(module, name))
    getattr(kind, fault)()
    broken, broken_kv = served()
    if fault == "break_unmask":
        # Left to right in every block, whatever the confidences.
        for n, (_, steps) in zip(plens, broken):
            steps = [-1] * (n % B) + steps
            assert all(steps[i:i + B] == sorted(steps[i:i + B])
                       for i in range(0, len(steps), B))
        assert broken != sound
    else:
        assert any(not np.array_equal(a, b)
                   for a, b in zip(sound_kv, broken_kv))


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "in-step"])
def test_rows_come_and_go_in_the_middle_of_others_blocks(ahead):
    """More requests than slots, lengths that are no multiple of the
    block, greedy and sampled rows, a slot left free (a junk row) at the
    start and the end: each request's tokens are the routine's for it
    alone, with a step in flight or without."""
    cfg, w, params, hp = _peaked()
    srv = _server(cfg, params, max_slots=3, max_new_tokens=32)
    if not ahead:
        _without_run_ahead(srv)
    try:
        sizes = [(5, 9), (8, 3), (3, 6), (12, 2), (7, 13), (4, 5), (10, 7)]
        prompts = [_tokens(n, seed=60 + n) for n, _ in sizes]
        futs = [srv.submit(p, max_new_tokens=n) for p, (_, n) in
                zip(prompts, sizes)]
        outs = [f.result(timeout=WAIT_S) for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    for prompt, (_, n), out in zip(prompts, sizes, outs):
        tokens, steps = ref.generate(w, prompt, n, hp)
        assert out["tokens"] == tokens and out["unmask_steps"] == steps
    assert (st["steps_ahead"] > 0) == ahead
    assert st["kv_blocks_in_use"] == 0
    assert st["diffusion_positions_dropped"] == sum(
        -(len(p) + n) % B for p, (_, n) in zip(prompts, sizes))
    # A block's B queries see the context and the block, on every layer.
    assert st["decode_keys_attended"] % (cfg.n_layers * B) == 0
    assert st["tokens_out"] == sum(n for _, n in sizes)


def test_run_ahead_on_and_off_serve_the_same_sampled_tokens():
    """A sampled row's tokens are a function of (prompt, seed): the same
    alone in a batch of one slot without run-ahead, and among other rows
    in another slot with a step in flight."""
    cfg, _, params, _ = _weights()
    prompt = _tokens(6, seed=5)
    alone = _server(cfg, params, max_slots=1)
    _without_run_ahead(alone)
    try:
        want = alone.submit(prompt, max_new_tokens=10, temperature=0.9,
                            seed=4242).result(timeout=WAIT_S)
        assert alone.stats()["steps_ahead"] == 0
    finally:
        alone.stop()
    srv = _server(cfg, params, max_slots=3)
    try:
        others = [srv.submit(_tokens(n, seed=n), max_new_tokens=12,
                             temperature=0.7, seed=n) for n in (9, 3)]
        got = srv.submit(prompt, max_new_tokens=10, temperature=0.9,
                         seed=4242).result(timeout=WAIT_S)
        greedy = srv.submit(prompt, max_new_tokens=10).result(timeout=WAIT_S)
        for f in others:
            f.result(timeout=WAIT_S)
        assert srv.stats()["draw_steps"] > 0
    finally:
        srv.stop()
    assert got["tokens"] == want["tokens"]
    assert got["unmask_steps"] == want["unmask_steps"]
    assert got["tokens"] != greedy["tokens"]


def test_a_stalled_row_and_a_preempted_row_replay_to_the_same_tokens():
    """Fewer pool blocks than the rows need: a grant fails, the row sits
    a step out, the youngest is preempted and runs again from zero; the
    tokens are the routine's and a stream skips the replay."""
    cfg, w, params, hp = _peaked()
    srv = _server(cfg, params, max_slots=3, kv_blocks=9, max_new_tokens=32)
    try:
        prompts = [_tokens(8, seed=80 + i) for i in range(4)]
        pairs = [srv.submit_stream(p, max_new_tokens=14) for p in prompts]
        outs = [f.result(timeout=WAIT_S) for f, _ in pairs]
        streamed = [s.tokens() for _, s in pairs]
        st = srv.stats()
    finally:
        srv.stop()
    for prompt, out, tokens in zip(prompts, outs, streamed):
        assert out["tokens"] == ref.generate(w, prompt, 14, hp)[0] == tokens
    assert st["preempted"] >= 1 and st["kv_blocks_in_use"] == 0


def test_the_block_rows_of_the_counter_table_in_stats_and_registry():
    """Generation by blocks: the host's ``diffusion_*`` rows and the
    device's (``kv_pool.BLOCK_STEP_COUNTERS``, behind the model's own)
    are in ``stats()`` and read the same in the registry; no row of an
    index or a window is there."""
    from tests.utils import assert_counters_agree

    srv = InferenceServer(
        CFG, ServingConfig(max_slots=3, max_len=MAX_LEN, kv_block_size=4,
                           prefill_chunk=CHUNK,
                           prefill_token_budget=2 * CHUNK,
                           prefix_reuse=False),
        params=PARAMS, cache_dtype=CFG.compute_dtype, name="sdar-table")
    try:
        srv.submit(_tokens(6), max_new_tokens=7).result(timeout=WAIT_S)
        srv.submit(_tokens(2 * CHUNK + 2, seed=1),
                   max_new_tokens=9).result(timeout=WAIT_S)
        st = srv.stats()
    finally:
        srv.stop()
    counters = assert_counters_agree(srv, st)
    assert {"diffusion_positions_dropped", "diffusion_fused_forwards",
            *srv.pool.step_counters} <= counters
    assert not counters & {"index_keys_scored", "kv_dead_blocks"}
    assert st["diffusion_fused_forwards"] > 0
    assert st["diffusion_positions_dropped"] > 0
    assert st["diffusion_row_forwards"] >= st["diffusion_commit_forwards"] > 0


def test_an_eos_ends_a_request_at_the_token_that_leaves():
    cfg, w, params, hp = _peaked()
    prompt = _tokens(6, seed=91)
    tokens, _ = ref.generate(w, prompt, 20, hp)
    eos = tokens[9]
    cut = tokens.index(eos) + 1
    srv = _server(cfg, params, eos_id=eos, max_new_tokens=32)
    try:
        fut, stream = srv.submit_stream(prompt, max_new_tokens=20)
        out = fut.result(timeout=WAIT_S)
        # The engine is still sound after a row left under its step.
        again = srv.submit(prompt, max_new_tokens=20).result(timeout=WAIT_S)
    finally:
        srv.stop()
    assert out["tokens"] == tokens[:cut] == stream.tokens()
    assert again["tokens"] == tokens[:cut]


def test_what_the_engine_refuses_for_a_model_that_generates_by_blocks():
    with pytest.raises(ValueError, match="prefix_reuse"):
        _server(prefix_reuse=True)
    with pytest.raises(ValueError, match="kv_block_size"):
        _server(kv_block_size=6)
    with pytest.raises(ValueError, match="prefill_chunk"):
        _server(prefill_chunk=6, prefill_token_budget=12)
    srv = _server()
    try:
        for mode in ("beam", "speculative"):
            with pytest.raises(ValueError, match="generation by blocks"):
                srv.submit([1, 2, 3], mode=mode)
        with pytest.raises(ValueError, match="mask id"):
            srv.submit([1, MASK, 3])
    finally:
        srv.stop()


def test_the_mask_id_is_never_a_candidate_and_the_two_rules():
    """A position whose best logit is the mask id's takes its second; the
    static rule unmasks its quota, the dynamic one every position over
    the threshold where the quota is met; an idle row is left alone."""
    logits = np.full((1, 4, 8), -5.0, np.float32)
    logits[0, :, 2] = 9.0            # the mask id leads everywhere
    logits[0, :, 5] = 1.0            # confidence 0.985 of token 5 ...
    logits[0, 2, 5], logits[0, 2, 6] = -5.0, 3.0    # ... 0.998 of token 6
    block = jnp.asarray([[7, 2, 2, 2]], jnp.int32)
    draw = sampling.pack([0.0], [0], [0], [0])
    live, idle = jnp.asarray([True]), jnp.asarray([False])
    static = decode.BlockSpec(4, 2, 4, rule="low_confidence_static")
    out, n = sampling.unmask(jnp.asarray(logits), block, draw, static, live)
    assert np.asarray(out).tolist() == [[7, 2, 6, 2]] and int(n) == 1
    dynamic = decode.BlockSpec(4, 2, 4)
    out, n = sampling.unmask(jnp.asarray(logits), block, draw, dynamic, live)
    assert np.asarray(out).tolist() == [[7, 5, 6, 5]] and int(n) == 3
    strict = decode.BlockSpec(4, 2, 4, threshold=0.999)
    out, n = sampling.unmask(jnp.asarray(logits), block, draw, strict, live)
    assert np.asarray(out).tolist() == [[7, 2, 6, 2]] and int(n) == 1
    out, n = sampling.unmask(jnp.asarray(logits), block, draw, dynamic, idle)
    assert np.asarray(out).tolist() == block.tolist() and int(n) == 0


def test_the_engine_without_a_block_spec_runs_the_programs_it_ran():
    """The four other models declare no ``block_spec``: their pool keeps
    one id a row and their decode step takes no ``commit``."""
    from rayfed_tpu.models import transformer as tfm
    from rayfed_tpu.serving.kv_pool import PagedKVPool

    dense = tfm.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                  n_layers=1, d_ff=32)
    pool = PagedKVPool(dense, 3, 16, block_size=4)
    assert pool.block is None and pool.ids_len == 3
    assert pool.step_counters == ()
    blocks = PagedKVPool(CFG, 3, 16, block_size=4)
    assert blocks.ids_len == 3 * B
    assert blocks.step_counters[-3:] == (
        "diffusion_row_forwards", "diffusion_commit_forwards",
        "diffusion_tokens_unmasked")
