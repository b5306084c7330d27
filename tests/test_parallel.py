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

"""Parallel-layer tests on the 8-device CPU mesh: ring attention
equivalence, partition rules, and the federated dp/tp/sp train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.parallel import sharding as shd  # noqa: E402
from rayfed_tpu.parallel.ring import ring_attention  # noqa: E402
from rayfed_tpu.parallel.train import make_fed_train_step  # noqa: E402


def seq_mesh(n=8):
    import numpy as _np

    return Mesh(_np.array(jax.devices()[:n]).reshape(n), ("seq",))


def test_ring_attention_matches_reference():
    rng = jax.random.PRNGKey(0)
    b, s, h, dh = 2, 32, 4, 16
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, dh), jnp.float32)

    expect = tfm.causal_attention(q, k, v)

    mesh = seq_mesh(8)
    pspec = P(None, "seq", None, None)
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=(pspec, pspec, pspec),
        out_specs=pspec,
        check_vma=False,
    )
    got = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_bf16():
    rng = jax.random.PRNGKey(1)
    b, s, h, dh = 1, 16, 2, 8
    q, k, v = (
        jax.random.normal(key, (b, s, h, dh), jnp.float32).astype(jnp.bfloat16)
        for key in jax.random.split(rng, 3)
    )
    expect = tfm.causal_attention(q, k, v)
    mesh = seq_mesh(4 if jax.device_count() >= 4 else 1)
    pspec = P(None, "seq", None, None)
    got = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=(pspec, pspec, pspec),
        out_specs=pspec,
        check_vma=False,
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expect, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_partition_rules():
    cfg = tfm.tiny_config()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    specs = shd.make_param_specs(params)
    # Stacked layer leaves get a leading None for the n_layers dim.
    assert specs["layers"]["wq"] == P(None, None, "model", None)
    assert specs["layers"]["w_down"] == P(None, "model", None)
    assert specs["layers"]["ln1"] == P()
    assert specs["lm_head"] == P(None, "model")
    assert specs["embed"] == P(None, None)


def _mesh(shape_names):
    import numpy as _np

    names = tuple(n for n, _ in shape_names)
    shape = tuple(s for _, s in shape_names)
    return Mesh(_np.array(jax.devices()).reshape(shape), names)


def test_forward_runs():
    cfg = tfm.tiny_config()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    logits = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


def _token_pair(key, batch, seq, vocab, mesh, seq_axis=None):
    tokens = jax.random.randint(key, (batch, seq + 1), 0, vocab)
    sharding = NamedSharding(mesh, shd.batch_spec(mesh, seq_axis=seq_axis))
    inputs = jax.device_put(tokens[:, :-1], sharding)
    targets = jax.device_put(tokens[:, 1:], sharding)
    return inputs, targets


def test_fed_train_step_dp_tp():
    # party=2 x data=2 x model=2 (8 devices), no seq sharding.
    mesh = _mesh([("party", 2), ("data", 2), ("model", 2)])
    cfg = tfm.tiny_config()
    init_fn, step_fn = make_fed_train_step(cfg, mesh, lr=1e-2)
    inputs, targets = _token_pair(jax.random.PRNGKey(2), 8, 16, cfg.vocab, mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_fed_train_step_with_ring_seq_parallel():
    # party=2 x model=2 x seq=2 (8 devices via data=1).
    mesh = _mesh([("party", 2), ("data", 1), ("model", 2), ("seq", 2)])
    cfg = tfm.tiny_config()
    init_fn, step_fn = make_fed_train_step(cfg, mesh, seq_axis="seq", lr=1e-2)
    inputs, targets = _token_pair(
        jax.random.PRNGKey(3), 4, 16, cfg.vocab, mesh, seq_axis="seq"
    )
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
    l0 = None
    loss = None
    for i in range(3):
        params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
        if i == 0:
            l0 = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < l0


def test_remat_matches_non_remat():
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    base = tfm.lm_loss_pair(params, inputs, targets, cfg)
    remat = tfm.lm_loss_pair(params, inputs, targets, cfg, remat=True)
    np.testing.assert_allclose(float(remat), float(base), rtol=1e-6)
    g_base = jax.grad(
        lambda p: tfm.lm_loss_pair(p, inputs, targets, cfg)
    )(params)
    g_remat = jax.grad(
        lambda p: tfm.lm_loss_pair(p, inputs, targets, cfg, remat=True)
    )(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_base), jax.tree_util.tree_leaves(g_remat)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_ring_flash_attention_matches_reference():
    """Flash kernels inside the ring (VERDICT long-context lane): forward
    equals the dense reference across sequence shards."""
    from rayfed_tpu.parallel.ring import ring_flash_attention

    rng = jax.random.PRNGKey(5)
    b, s, h, dh = 2, 64, 2, 16
    q, k, v = (
        jax.random.normal(key, (b, s, h, dh), jnp.float32)
        for key in jax.random.split(rng, 3)
    )
    expect = tfm.causal_attention(q, k, v)
    mesh = seq_mesh(4)
    pspec = P(None, "seq", None, None)
    ringf = shard_map(
        lambda q, k, v: ring_flash_attention(
            q, k, v, axis_name="seq", block_q=8, block_k=8
        ),
        mesh=mesh,
        in_specs=(pspec, pspec, pspec),
        out_specs=pspec,
        check_vma=False,
    )
    got = jax.jit(ringf)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_attention_gradients_match_reference():
    """Backward: the rotating dk/dv accumulators deliver each block's
    gradients home; dq/dk/dv equal autodiff through dense attention."""
    from rayfed_tpu.parallel.ring import ring_flash_attention

    rng = jax.random.PRNGKey(6)
    b, s, h, dh = 1, 32, 2, 16
    q, k, v = (
        jax.random.normal(key, (b, s, h, dh), jnp.float32)
        for key in jax.random.split(rng, 3)
    )
    mesh = seq_mesh(4)
    pspec = P(None, "seq", None, None)
    ringf = shard_map(
        lambda q, k, v: ring_flash_attention(
            q, k, v, axis_name="seq", block_q=8, block_k=8
        ),
        mesh=mesh,
        in_specs=(pspec, pspec, pspec),
        out_specs=pspec,
        check_vma=False,
    )

    def loss_ring(q, k, v):
        return (ringf(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (tfm.causal_attention(q, k, v) ** 2).sum()

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    ge = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, ge):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-4
        )


def test_fed_train_step_ring_flash():
    """Full train step with sp=ring+flash: finite loss, params move."""
    from rayfed_tpu.parallel.train import make_fed_train_step

    cfg = tfm.tiny_config()
    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2), ("party", "data", "seq")
    )
    init_fn, step_fn = make_fed_train_step(
        cfg, mesh, seq_axis="seq", attn="flash", lr=1e-2
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
    params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
    assert np.isfinite(float(loss)), float(loss)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=2 must reproduce the full-batch step (equal-sized
    microbatches: mean of means == global mean; f32 accumulation)."""
    mesh = _mesh([("party", 2), ("data", 2), ("model", 2)])
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    init_full, step_full = make_fed_train_step(cfg, mesh, lr=1e-2)
    init_acc, step_acc = make_fed_train_step(
        cfg, mesh, lr=1e-2, accum_steps=2
    )
    inputs, targets = _token_pair(jax.random.PRNGKey(4), 8, 16, cfg.vocab, mesh)

    p_full, o_full = init_full(jax.random.PRNGKey(0), inputs)
    p_acc, o_acc = init_acc(jax.random.PRNGKey(0), inputs)
    for _ in range(2):
        p_full, o_full, l_full = step_full(p_full, o_full, inputs, targets)
        p_acc, o_acc, l_acc = step_acc(p_acc, o_acc, inputs, targets)
    np.testing.assert_allclose(float(l_acc), float(l_full), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_acc), jax.tree_util.tree_leaves(p_full)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


def test_accum_steps_validation():
    mesh = _mesh([("party", 2), ("data", 2), ("model", 2)])
    cfg = tfm.tiny_config()
    with pytest.raises(ValueError, match="accum_steps"):
        make_fed_train_step(cfg, mesh, accum_steps=0)
    init_fn, step_fn = make_fed_train_step(cfg, mesh, accum_steps=3)
    inputs, targets = _token_pair(jax.random.PRNGKey(5), 8, 16, cfg.vocab, mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
    with pytest.raises(ValueError, match="not divisible"):
        step_fn(params, opt_state, inputs, targets)


def test_zero1_sharded_opt_state_matches_replicated():
    """shard_opt_state=True: moments are dp-sharded (memory / dp world
    size) and training stays numerically identical."""
    mesh = _mesh([("party", 2), ("data", 2), ("model", 2)])
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    init_rep, step_rep = make_fed_train_step(cfg, mesh, lr=1e-2)
    init_z1, step_z1 = make_fed_train_step(
        cfg, mesh, lr=1e-2, shard_opt_state=True
    )
    inputs, targets = _token_pair(jax.random.PRNGKey(6), 8, 16, cfg.vocab, mesh)

    p_rep, o_rep = init_rep(jax.random.PRNGKey(0), inputs)
    p_z1, o_z1 = init_z1(jax.random.PRNGKey(0), inputs)

    # The moments actually shard over a dp axis (party/data), not just tp.
    dp_sharded = 0
    for leaf in jax.tree_util.tree_leaves(o_z1):
        spec = getattr(leaf.sharding, "spec", None)
        if spec is None:
            continue
        axes = set()
        for entry in spec:
            if entry is None:
                continue
            axes.update(entry if isinstance(entry, tuple) else (entry,))
        if axes & {"party", "data"}:
            dp_sharded += 1
    assert dp_sharded > 0, "no optimizer leaf is dp-sharded"

    for _ in range(3):
        p_rep, o_rep, l_rep = step_rep(p_rep, o_rep, inputs, targets)
        p_z1, o_z1, l_z1 = step_z1(p_z1, o_z1, inputs, targets)
        np.testing.assert_allclose(float(l_z1), float(l_rep), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_z1), jax.tree_util.tree_leaves(p_rep)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )
