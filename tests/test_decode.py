"""KV-cache decoding: cached forward must match the full forward, and
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

generation must match the naive recompute-everything loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu.models import decode, transformer as tfm


def _cfg(**kw):
    # f32 compute so cached-vs-full comparisons are tight.
    base = dict(compute_dtype=jnp.float32)
    base.update(kw)
    return tfm.tiny_config(**base)


def test_prefill_matches_full_forward():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)

    full = tfm.forward(params, tokens, cfg)
    cache = decode.init_cache(cfg, 2, 16)
    cached, _ = decode.forward_with_cache(params, tokens, cache, 0, cfg)
    np.testing.assert_allclose(
        np.asarray(cached), np.asarray(full), rtol=2e-5, atol=2e-5
    )


def test_incremental_decode_matches_full_forward():
    """Feeding tokens one at a time through the cache reproduces the
    last-position logits of the growing full forward at every step."""
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, cfg.vocab)

    cache = decode.init_cache(cfg, 1, tokens.shape[1])
    step = jax.jit(
        lambda p, t, c, o: decode.forward_with_cache(p, t, c, o, cfg)
    )
    for pos in range(tokens.shape[1]):
        logits, cache = step(
            params, tokens[:, pos : pos + 1], cache, jnp.int32(pos)
        )
        full = tfm.forward(params, tokens[:, : pos + 1], cfg)
        np.testing.assert_allclose(
            np.asarray(logits[:, -1]),
            np.asarray(full[:, -1]),
            rtol=2e-5,
            atol=2e-5,
        )


def test_greedy_generate_matches_naive_loop():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 5), 0, cfg.vocab)
    max_new = 6

    gen = decode.make_generate_fn(cfg, max_new_tokens=max_new)
    out = np.asarray(gen(params, prompt))
    assert out.shape == (2, 5 + max_new)
    np.testing.assert_array_equal(out[:, :5], np.asarray(prompt))

    # Naive reference: recompute the full forward for every new token.
    seq = np.asarray(prompt)
    for _ in range(max_new):
        logits = tfm.forward(params, jnp.asarray(seq), cfg)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, seq)


def test_sampled_generate_deterministic_per_key_and_in_vocab():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(6), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 4), 0, cfg.vocab)

    gen = decode.make_generate_fn(cfg, max_new_tokens=5, temperature=0.8)
    a = np.asarray(gen(params, prompt, jax.random.PRNGKey(8)))
    b = np.asarray(gen(params, prompt, jax.random.PRNGKey(8)))
    c = np.asarray(gen(params, prompt, jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 9)
    assert (a[:, 4:] >= 0).all() and (a[:, 4:] < cfg.vocab).all()
    # Different keys should (overwhelmingly) sample different continuations.
    assert not np.array_equal(a, c)


def test_moe_config_decodes():
    cfg = _cfg(n_experts=2)
    params = tfm.init_params(jax.random.PRNGKey(10), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(11), (1, 4), 0, cfg.vocab)
    gen = decode.make_generate_fn(cfg, max_new_tokens=3)
    out = np.asarray(gen(params, prompt))
    assert out.shape == (1, 7)

    full = tfm.forward(params, prompt, cfg)
    cache = decode.init_cache(cfg, 1, 8)
    cached, _ = decode.forward_with_cache(params, prompt, cache, 0, cfg)
    np.testing.assert_allclose(
        np.asarray(cached), np.asarray(full), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("bad", [0, -3])
def test_generate_rejects_bad_lengths(bad):
    with pytest.raises(ValueError):
        decode.make_generate_fn(_cfg(), max_new_tokens=bad)


def test_cache_overflow_raises():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(12), cfg)
    cache = decode.init_cache(cfg, 1, 4)
    with pytest.raises(ValueError, match="longer than cache"):
        decode.forward_with_cache(
            params, jnp.zeros((1, 6), jnp.int32), cache, 0, cfg
        )
    with pytest.raises(ValueError, match="cache overflow"):
        decode.forward_with_cache(
            params, jnp.zeros((1, 2), jnp.int32), cache, 3, cfg
        )


def test_sharded_generate_matches_single_device():
    """Generation over a data x model mesh (tp-sharded params, head-sharded
    cache) must reproduce the unsharded greedy tokens."""
    import numpy as np
    from jax.sharding import Mesh

    from rayfed_tpu.parallel import sharding as shd

    cfg = _cfg(n_heads=4)
    params = tfm.init_params(jax.random.PRNGKey(20), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(21), (4, 6), 0, cfg.vocab)

    ref = decode.make_generate_fn(cfg, max_new_tokens=5)(params, prompt)

    devices = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devices, ("data", "model"))
    sharded_params = shd.shard_params(mesh, params)
    gen = decode.make_generate_fn(cfg, max_new_tokens=5, mesh=mesh)
    out = gen(sharded_params, prompt)

    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_topk_one_equals_greedy():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(30), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(31), (2, 4), 0, cfg.vocab)
    greedy = decode.make_generate_fn(cfg, max_new_tokens=5)(params, prompt)
    topk1 = decode.make_generate_fn(
        cfg, max_new_tokens=5, temperature=0.7, top_k=1
    )(params, prompt, jax.random.PRNGKey(32))
    np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))


def test_topk_topp_sampling_stays_in_nucleus():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(33), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(34), (2, 4), 0, cfg.vocab)
    gen = decode.make_generate_fn(
        cfg, max_new_tokens=6, temperature=1.0, top_k=16, top_p=0.9
    )
    out = np.asarray(gen(params, prompt, jax.random.PRNGKey(35)))
    assert out.shape == (2, 10)
    # Every sampled token must be one of the top-16 next-token candidates
    # for its prefix (checked against the full forward).
    seq = np.asarray(prompt)
    for step in range(6):
        logits = np.asarray(tfm.forward(params, jnp.asarray(seq), cfg))
        top16 = np.argsort(logits[:, -1], axis=-1)[:, -16:]
        for b in range(2):
            assert out[b, 4 + step] in top16[b]
        seq = np.concatenate([seq, out[:, 4 + step][:, None]], axis=1)


def test_sampling_params_validated():
    cfg = _cfg()
    with pytest.raises(ValueError, match="top_k"):
        decode.make_generate_fn(cfg, max_new_tokens=2, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        decode.make_generate_fn(cfg, max_new_tokens=2, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        decode.make_generate_fn(cfg, max_new_tokens=2, top_p=1.5)


def _brute_force_best(params, prompt, cfg, t_new):
    """Exhaustive argmax over all vocab^t_new continuations (tiny shapes)."""
    import itertools

    best_score, best_seq = -np.inf, None
    for cont in itertools.product(range(cfg.vocab), repeat=t_new):
        toks = jnp.concatenate(
            [prompt, jnp.asarray([cont], prompt.dtype)], axis=1
        )
        logits = tfm.forward(params, toks, cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        score = sum(
            float(logp[0, prompt.shape[1] - 1 + i, cont[i]])
            for i in range(t_new)
        )
        if score > best_score:
            best_score, best_seq = score, cont
    return best_score, best_seq


def test_beam_search_finds_exhaustive_argmax():
    """With n_beams >= vocab^(t-1) the beam can never prune the optimum:
    the top beam must equal the brute-force best continuation, score and
    tokens both."""
    cfg = tfm.tiny_config(vocab=6, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0, cfg.vocab)

    t_new = 2
    bs = decode.make_beam_search_fn(cfg, max_new_tokens=t_new,
                                    n_beams=cfg.vocab ** (t_new - 1) * 2)
    seqs, scores = bs(params, prompt)
    ref_score, ref_seq = _brute_force_best(params, prompt, cfg, t_new)
    got = tuple(int(x) for x in np.asarray(seqs)[0, 0, -t_new:])
    assert got == ref_seq, (got, ref_seq)
    np.testing.assert_allclose(float(scores[0, 0]), ref_score, rtol=1e-4)
    # Scores are sorted best-first.
    s = np.asarray(scores)[0]
    assert np.all(s[:-1] >= s[1:] - 1e-6)


def test_beam_search_beam1_is_greedy():
    cfg = tfm.tiny_config(vocab=16, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0, cfg.vocab)

    bs = decode.make_beam_search_fn(cfg, max_new_tokens=4, n_beams=1)
    seqs, _ = bs(params, prompt)
    greedy = decode.make_generate_fn(cfg, max_new_tokens=4)(params, prompt)
    np.testing.assert_array_equal(np.asarray(seqs)[:, 0, :],
                                  np.asarray(greedy))


def test_beam_search_validates_args():
    cfg = tfm.tiny_config()
    with pytest.raises(ValueError, match="max_new_tokens"):
        decode.make_beam_search_fn(cfg, max_new_tokens=0, n_beams=2)
    with pytest.raises(ValueError, match="n_beams"):
        decode.make_beam_search_fn(cfg, max_new_tokens=2, n_beams=0)


def test_beam_search_batched_rows_do_not_cross_contaminate():
    """B>=2 with n_beams>=2: each batch element's top beam must equal
    ITS OWN brute-force best — any mismatch in the flattened
    (b * n_beams + parent) cache-gather arithmetic would leak K/V rows
    across batch elements."""
    cfg = tfm.tiny_config(vocab=5, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0, cfg.vocab)

    t_new = 2
    bs = decode.make_beam_search_fn(cfg, max_new_tokens=t_new,
                                    n_beams=cfg.vocab ** (t_new - 1))
    seqs, scores = bs(params, prompts)
    for row in range(2):
        ref_score, ref_seq = _brute_force_best(
            params, prompts[row:row + 1], cfg, t_new
        )
        got = tuple(int(x) for x in np.asarray(seqs)[row, 0, -t_new:])
        assert got == ref_seq, (row, got, ref_seq)
        np.testing.assert_allclose(
            float(scores[row, 0]), ref_score, rtol=1e-4
        )


def test_beam_search_eos_matches_exhaustive():
    """With eos_id set and a wide-enough beam, the top beam must equal
    the best sequence over the space of EOS-terminated-or-length-capped
    continuations (each scored up to and including its first EOS)."""
    import itertools

    cfg = tfm.tiny_config(vocab=5, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(8), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(9), (1, 5), 0, cfg.vocab)
    eos, t_new = 0, 3

    # Brute force: every full continuation, truncated at its first EOS
    # (inclusive); dedupe truncated forms; keep the best score.
    best = {}
    for cont in itertools.product(range(cfg.vocab), repeat=t_new):
        cut = t_new
        for i, c in enumerate(cont):
            if c == eos:
                cut = i + 1
                break
        trunc = cont[:cut]
        toks = jnp.concatenate(
            [prompt, jnp.asarray([cont], jnp.int32)], axis=1
        )
        logp = jax.nn.log_softmax(
            tfm.forward(params, toks, cfg).astype(jnp.float32), axis=-1
        )
        score = sum(
            float(logp[0, prompt.shape[1] - 1 + i, trunc[i]])
            for i in range(cut)
        )
        if trunc not in best or score > best[trunc]:
            best[trunc] = score
    ref_seq, ref_score = max(best.items(), key=lambda kv: kv[1])

    bs = decode.make_beam_search_fn(
        cfg, max_new_tokens=t_new, n_beams=cfg.vocab ** (t_new - 1),
        eos_id=eos,
    )
    seqs, scores = bs(params, prompt)
    got_full = [int(x) for x in np.asarray(seqs)[0, 0, prompt.shape[1]:]]
    cut = t_new
    for i, c in enumerate(got_full):
        if c == eos:
            cut = i + 1
            break
    assert tuple(got_full[:cut]) == ref_seq, (got_full, ref_seq)
    # Trailing slots of a finished beam pad with EOS.
    assert all(c == eos for c in got_full[cut:]), got_full
    np.testing.assert_allclose(float(scores[0, 0]), ref_score, rtol=1e-4)


def test_beam_search_eos_validates():
    cfg = tfm.tiny_config()
    with pytest.raises(ValueError, match="eos_id"):
        decode.make_beam_search_fn(
            cfg, max_new_tokens=2, n_beams=2, eos_id=cfg.vocab
        )


def test_generate_eos_pads_terminated_rows():
    """eos_id: tokens before the first EOS match the plain generation;
    everything after the first EOS is EOS."""
    cfg = tfm.tiny_config(vocab=5, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(10), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(11), (3, 4), 0, cfg.vocab)
    eos, t_new = 0, 8

    # Sampled at a fixed key so rows actually hit EOS within the
    # budget; both runs share the key, and the per-step key chain is
    # identical regardless of termination, so the trajectories must
    # agree up to each row's first EOS.
    key = jax.random.PRNGKey(12)
    plain = np.asarray(
        decode.make_generate_fn(cfg, max_new_tokens=t_new, temperature=1.0)(
            params, prompt, key
        )
    )
    with_eos = np.asarray(
        decode.make_generate_fn(
            cfg, max_new_tokens=t_new, temperature=1.0, eos_id=eos
        )(params, prompt, key)
    )
    s = prompt.shape[1]
    terminated = 0
    for row in range(prompt.shape[0]):
        gen_plain, gen_eos = plain[row, s:], with_eos[row, s:]
        cut = t_new
        for i, c in enumerate(gen_plain):
            if c == eos:
                cut = i + 1
                break
        # Up to and including the first EOS the trajectories agree...
        np.testing.assert_array_equal(gen_eos[:cut], gen_plain[:cut])
        # ...and afterwards the eos_id variant pads with EOS.
        assert all(c == eos for c in gen_eos[cut:]), gen_eos
        terminated += cut < t_new
    # vocab=5 over 8 steps: at least one row should actually terminate,
    # otherwise this test exercised nothing (deterministic, seed-fixed).
    assert terminated >= 1


def test_generate_eos_validates():
    cfg = tfm.tiny_config()
    with pytest.raises(ValueError, match="eos_id"):
        decode.make_generate_fn(cfg, max_new_tokens=2, eos_id=-1)


def test_sharded_beam_search_matches_single_device():
    """Beam search over a data x model mesh (tp params, head-sharded
    B*n_beams cache rows) must reproduce the unsharded beams exactly —
    sequences AND scores."""
    from jax.sharding import Mesh

    from rayfed_tpu.parallel import sharding as shd

    cfg = _cfg(n_heads=4)
    params = tfm.init_params(jax.random.PRNGKey(30), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(31), (4, 6), 0, cfg.vocab)

    ref_seqs, ref_scores = decode.make_beam_search_fn(
        cfg, max_new_tokens=4, n_beams=3, eos_id=0
    )(params, prompt)

    devices = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devices, ("data", "model"))
    sharded_params = shd.shard_params(mesh, params)
    bs = decode.make_beam_search_fn(
        cfg, max_new_tokens=4, n_beams=3, eos_id=0, mesh=mesh
    )
    seqs, scores = bs(sharded_params, prompt)

    np.testing.assert_array_equal(np.asarray(seqs), np.asarray(ref_seqs))
    np.testing.assert_allclose(
        np.asarray(scores), np.asarray(ref_scores), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("landed", [
    None, [True, False, True, False], [False, False, False, True],
    [False] * 4, [True] * 4,
])
def test_landed_rows_computes_and_lands_only_the_named_rows(landed):
    """The loop every landed-rows ``prefill_rows`` shares: ``one_row`` runs
    ``sum(landed)`` times, landed rows first in slot order; a row's logits
    land at ``[i]`` and its other outputs at ``[:, i]``, one shorter than
    its output from the start of the later axis; the rest stays zero;
    ``landed=None`` lands all."""
    R, V, L, S = 4, 5, 2, 3
    calls = []

    def one_row(i):
        jax.debug.callback(lambda i: calls.append(int(i)), i, ordered=True)
        f = i.astype(jnp.float32) + 1
        return (jnp.full((V,), f), jnp.full((L, S), 10 * f),
                jnp.full((L, S - 1, 2), 100 * f))

    zeros = (jnp.zeros((R, V)), jnp.zeros((L, R, S)),
             jnp.zeros((L, R, S + 2, 2)))
    mask = None if landed is None else jnp.asarray(landed)
    logits, rows, longer = jax.jit(
        lambda m: decode.landed_rows(one_row, m, zeros))(mask)
    jax.effects_barrier()
    want = [i for i in range(R) if landed is None or landed[i]]
    assert calls == want
    f = np.zeros(R, np.float32)
    f[want] = np.asarray(want, np.float32) + 1
    np.testing.assert_array_equal(logits, np.tile(f[:, None], (1, V)))
    np.testing.assert_array_equal(
        rows, np.tile(10 * f[None, :, None], (L, 1, S)))
    np.testing.assert_array_equal(
        longer[:, :, :S - 1], np.tile(100 * f[None, :, None, None],
                                      (L, 1, S - 1, 2)))
    assert not np.asarray(longer[:, :, S - 1:]).any()


def test_landed_rows_trip_count_is_a_runtime_value():
    """One program whatever ``landed`` holds: the loop's bound is
    ``sum(landed)`` computed in the program, not a constant of the trace."""
    zeros = (jnp.zeros((4, 2)), jnp.zeros((1, 4, 3)))
    fn = jax.jit(lambda m: decode.landed_rows(
        lambda i: (jnp.ones((2,)), jnp.ones((1, 3))), m, zeros))
    text = fn.lower(jnp.zeros((4,), bool)).as_text()
    assert "stablehlo.while" in text and text.count("stablehlo.sort") == 1
    for mask in ([True] * 4, [False, True, False, False]):
        logits, _ = fn(jnp.asarray(mask))
        np.testing.assert_array_equal(logits[:, 0], np.asarray(mask, float))
    assert fn._cache_size() == 1
