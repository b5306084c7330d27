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

"""Pipeline (pp) and expert (ep) parallelism equivalence tests on the
8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh

from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.models.moe import (  # noqa: E402
    init_moe_ffn,
    make_ep_moe_apply,
    moe_ffn_apply,
)
from rayfed_tpu.parallel.pipeline import make_pp_loss_fn  # noqa: E402


def _stage_mesh(n):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("stage",))


def _cfg():
    return tfm.tiny_config(n_layers=4, compute_dtype=jnp.float32)


def test_pp_loss_matches_serial():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    serial = float(tfm.lm_loss_pair(params, inputs, targets, cfg))
    for n_stages, m in [(2, 4), (4, 2)]:
        mesh = _stage_mesh(n_stages)
        pp_loss = make_pp_loss_fn(cfg, mesh, n_microbatches=m)
        got = float(jax.jit(pp_loss)(params, inputs, targets))
        np.testing.assert_allclose(
            got, serial, rtol=1e-5, err_msg=f"stages={n_stages} micro={m}"
        )


def test_pp_grads_match_serial():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    serial_grads = jax.grad(
        lambda p: tfm.lm_loss_pair(p, inputs, targets, cfg)
    )(params)
    mesh = _stage_mesh(2)
    pp_loss = make_pp_loss_fn(cfg, mesh, n_microbatches=2)
    pp_grads = jax.jit(jax.grad(pp_loss))(params, inputs, targets)
    for path_serial, path_pp in zip(
        jax.tree_util.tree_leaves_with_path(serial_grads),
        jax.tree_util.tree_leaves_with_path(pp_grads),
    ):
        np.testing.assert_allclose(
            np.asarray(path_pp[1]), np.asarray(path_serial[1]),
            rtol=2e-4, atol=2e-5, err_msg=str(path_serial[0]),
        )


def test_pp_trains():
    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mesh = _stage_mesh(4)
    pp_loss = make_pp_loss_fn(cfg, mesh, n_microbatches=4)

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(pp_loss)(p, inputs, targets)
        return jax.tree_util.tree_map(lambda w, g: w - 1e-2 * g, p, grads), loss

    l0 = None
    for i in range(3):
        params, loss = step(params)
        if i == 0:
            l0 = float(loss)
    assert float(loss) < l0, (float(loss), l0)


def test_ep_moe_matches_dense():
    d, f, e = 16, 32, 4
    params = init_moe_ffn(jax.random.PRNGKey(0), d, f, e)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 10, d))
    dense = moe_ffn_apply(params, x, top1=True)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("expert",))
    ep = make_ep_moe_apply(mesh)
    got = jax.jit(ep)(params, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_ep_moe_grads_flow():
    d, f, e = 8, 16, 8
    params = init_moe_ffn(jax.random.PRNGKey(2), d, f, e)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 6, d))
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    ep = make_ep_moe_apply(mesh)

    def loss(p):
        return (ep(p, x) ** 2).mean()

    grads = jax.jit(jax.grad(loss))(params)
    norms = [float(jnp.abs(g).sum()) for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(norms)) and sum(norms) > 0

def test_moe_transformer_trains_with_ep_rules():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from rayfed_tpu.parallel import sharding as shd

    cfg = tfm.tiny_config(
        n_layers=2, n_experts=4, compute_dtype=jnp.float32
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    # Stacked MoE leaves pick up the expert axis (with leading n_layers dim).
    specs = shd.make_param_specs(params)
    assert specs["layers"]["moe"]["w_up"] == P(None, "expert", None, None)
    assert specs["layers"]["moe"]["router"] == P()

    # Train a couple of steps over a party x expert mesh via GSPMD.
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("party", "expert"))
    params = shd.shard_params(mesh, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab)
    inputs = jax.device_put(
        tokens[:, :-1], NamedSharding(mesh, shd.batch_spec(mesh, data_axis=None))
    )
    targets = jax.device_put(
        tokens[:, 1:], NamedSharding(mesh, shd.batch_spec(mesh, data_axis=None))
    )

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(
            lambda p: tfm.lm_loss_pair(p, inputs, targets, cfg)
        )(p)
        return jax.tree_util.tree_map(lambda w, g: w - 1e-2 * g, p, grads), loss

    l0 = None
    for i in range(3):
        params, loss = step(params)
        if i == 0:
            l0 = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < l0


def test_pp_composes_with_tp_and_dp_axes():
    # shard_map is manual over 'stage' only; GSPMD auto-handles the other
    # mesh axes inside the pipeline body, so pp composes with tp/dp.
    from rayfed_tpu.parallel import sharding as shd

    cfg = _cfg()  # n_layers=4, f32
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    serial = float(tfm.lm_loss_pair(params, inputs, targets, cfg))

    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2), ("stage", "model", "data")
    )
    # Model-axis-sharded params (the TP layout) must flow through unchanged.
    params = shd.shard_params(mesh, params)
    pp_loss = make_pp_loss_fn(cfg, mesh, n_microbatches=2)
    got = float(jax.jit(pp_loss)(params, inputs, targets))
    np.testing.assert_allclose(got, serial, rtol=1e-5)


def test_a2a_moe_matches_dense_with_ample_capacity():
    from rayfed_tpu.models.moe import make_a2a_moe_apply

    d, f, e = 16, 32, 8
    params = init_moe_ffn(jax.random.PRNGKey(0), d, f, e)
    n = 64  # tokens, sharded 8 ways over the expert axis
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    dense = moe_ffn_apply(params, x, top1=True)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    # capacity_factor large enough that no token is dropped.
    a2a = make_a2a_moe_apply(mesh, capacity_factor=8.0)
    got = jax.jit(a2a)(params, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_a2a_moe_drops_overflow_tokens():
    from rayfed_tpu.models.moe import make_a2a_moe_apply

    d, f, e = 8, 16, 8
    params = init_moe_ffn(jax.random.PRNGKey(2), d, f, e)
    n = 64
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    tight = jax.jit(make_a2a_moe_apply(mesh, capacity_factor=0.5))(params, x)
    ample = jax.jit(make_a2a_moe_apply(mesh, capacity_factor=8.0))(params, x)
    # Overflowed tokens produce exactly zero output; kept tokens match.
    tight_np, ample_np = np.asarray(tight), np.asarray(ample)
    dropped = np.all(tight_np == 0, axis=-1)
    assert dropped.any(), "expected some tokens to overflow capacity"
    np.testing.assert_allclose(
        tight_np[~dropped], ample_np[~dropped], rtol=2e-5, atol=2e-5
    )


def test_a2a_moe_bf16_tokens_route_consistently():
    # Rank accumulation must be integer: with bf16 tokens and >256 per
    # shard a float cumsum would collide slots silently (hundreds of
    # corrupted tokens). A handful of tokens may still legitimately flip
    # experts between lanes — borderline router logits whose argmax
    # differs between compiled paths at bf16 precision — so the assertion
    # is "almost all tokens identical", which a slot-collision bug fails
    # by an order of magnitude.
    from rayfed_tpu.models.moe import make_a2a_moe_apply

    d, f, e = 8, 16, 8
    params = init_moe_ffn(jax.random.PRNGKey(4), d, f, e)
    bf16 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda p: p.astype(jnp.bfloat16), t
    )
    n = 8 * 512  # 512 tokens per device shard
    x = jax.random.normal(jax.random.PRNGKey(5), (n, d)).astype(jnp.bfloat16)
    dense = np.asarray(moe_ffn_apply(bf16(params), x, top1=True), np.float32)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    got = np.asarray(
        jax.jit(make_a2a_moe_apply(mesh, capacity_factor=16.0))(
            bf16(params), x
        ),
        np.float32,
    )
    mismatched = (np.abs(got - dense).max(axis=-1) > 0.1).mean()
    assert mismatched < 0.01, f"{mismatched:.2%} tokens mismatched"


def test_topk_gates_and_loss():
    from rayfed_tpu.models.moe import (
        load_balance_loss,
        moe_ffn_apply_topk,
        topk_gates,
    )

    d, f, e = 8, 16, 4
    params = init_moe_ffn(jax.random.PRNGKey(6), d, f, e)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, d))
    g = np.asarray(topk_gates(params, x, k=2))
    # Exactly two experts per token, gates normalized.
    assert ((g > 0).sum(axis=-1) == 2).all()
    np.testing.assert_allclose(g.sum(axis=-1), 1.0, rtol=1e-5)
    # k = E degenerates to the full softmax (already normalized).
    g_all = np.asarray(topk_gates(params, x, k=e))
    assert ((g_all > 0).sum(axis=-1) == e).all()

    out = moe_ffn_apply_topk(params, x, k=2)
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())

    # Aux loss: >= 1 always; == 1 under a perfectly uniform router.
    lb = float(load_balance_loss(params, x))
    lb2 = float(load_balance_loss(params, x, k=2))
    assert lb2 >= 1.0 - 1e-6, lb2
    assert lb >= 1.0 - 1e-6, lb
    uniform = dict(params, router=jnp.zeros_like(params["router"]))
    # Zero logits -> uniform probs; f depends on argmax ties (all index 0),
    # so only P is uniform: E * sum(f * 1/E) == 1 regardless of f.
    np.testing.assert_allclose(
        float(load_balance_loss(uniform, x)), 1.0, rtol=1e-5
    )
    # Differentiable.
    grad = jax.grad(lambda p: load_balance_loss(p, x))(params)
    assert bool(jnp.isfinite(grad["router"]).all())


def test_a2a_moe_topk_matches_dense_topk():
    """k=2 all-to-all dispatch equals the dense top-k lane when capacity
    is ample (VERDICT r1 #7)."""
    from rayfed_tpu.models.moe import make_a2a_moe_apply, moe_ffn_apply_topk

    d, f, e = 16, 32, 8
    params = init_moe_ffn(jax.random.PRNGKey(6), d, f, e)
    n = 64
    x = jax.random.normal(jax.random.PRNGKey(7), (n, d))
    dense = moe_ffn_apply_topk(params, x, k=2)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    got = jax.jit(make_a2a_moe_apply(mesh, capacity_factor=8.0, k=2))(
        params, x
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


def test_a2a_moe_topk_drops_only_overflowed_choices():
    """Under tight capacity a token keeps the contribution of choices that
    fit — k=2 degrades gracefully instead of zeroing whole tokens."""
    from rayfed_tpu.models.moe import make_a2a_moe_apply

    d, f, e = 8, 16, 8
    params = init_moe_ffn(jax.random.PRNGKey(8), d, f, e)
    n = 64
    x = jax.random.normal(jax.random.PRNGKey(9), (n, d))
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    tight = np.asarray(
        jax.jit(make_a2a_moe_apply(mesh, capacity_factor=0.5, k=2))(params, x)
    )
    ample = np.asarray(
        jax.jit(make_a2a_moe_apply(mesh, capacity_factor=8.0, k=2))(params, x)
    )
    # Some choices overflowed (outputs differ), but full-token zeros should
    # be rarer than in top-1: a token is zero only if BOTH choices dropped.
    assert not np.allclose(tight, ample)
    changed = ~np.isclose(tight, ample, rtol=2e-5, atol=2e-5).all(axis=-1)
    assert changed.any()


def test_a2a_moe_topk_gradients_flow():
    from rayfed_tpu.models.moe import make_a2a_moe_apply

    d, f, e = 8, 16, 8
    params = init_moe_ffn(jax.random.PRNGKey(10), d, f, e)
    x = jax.random.normal(jax.random.PRNGKey(11), (32, d))
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("expert",))
    apply_fn = make_a2a_moe_apply(mesh, capacity_factor=4.0, k=2)

    def loss(p):
        return (apply_fn(p, x) ** 2).mean()

    val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    for g in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.isfinite(g).all())


def test_pp_train_step_composes_party_stage_model():
    """VERDICT r1 #6: one jit over a party x stage x model mesh — pipeline
    schedule, TP-sharded params, and the party grad all-reduce (the
    federated aggregate) in a single program."""
    from rayfed_tpu.parallel.pipeline import make_pp_train_step

    cfg = tfm.tiny_config(n_layers=4)
    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2),
        ("party", "stage", "model"),
    )
    init_fn, step_fn = make_pp_train_step(
        cfg, mesh, party_axis="party", n_microbatches=2, lr=1e-2
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
    p0 = np.asarray(jax.tree_util.tree_leaves(params)[0])  # pre-donation copy
    params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
    assert np.isfinite(float(loss)), float(loss)
    # Params actually moved.
    p1 = np.asarray(jax.tree_util.tree_leaves(params)[0])
    assert not np.allclose(p0, p1)
    # Second step reuses the compiled program.
    params, opt_state, loss2 = step_fn(params, opt_state, inputs, targets)
    assert np.isfinite(float(loss2))


def test_pp_microbatch_groups_match_full_schedule():
    """Grouped gradient accumulation (the 1F1B-style memory bound) computes
    the same loss as one full GPipe wave."""
    from rayfed_tpu.parallel.pipeline import make_pp_loss_fn, make_pp_train_step

    cfg = tfm.tiny_config(n_layers=4)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("stage",))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)

    full = make_pp_loss_fn(cfg, mesh, n_microbatches=4)
    loss_full = float(jax.jit(full)(params, inputs, targets))

    init_fn, step_fn = make_pp_train_step(
        cfg, mesh, n_microbatches=4, microbatch_group=2, lr=1e-2
    )
    p2, opt2 = init_fn(jax.random.PRNGKey(1), inputs)
    _, _, loss_grouped = step_fn(p2, opt2, inputs, targets)
    np.testing.assert_allclose(float(loss_grouped), loss_full, rtol=1e-5)


def test_1f1b_schedule_tables_well_formed():
    from rayfed_tpu.parallel.pipeline import schedule_1f1b

    for S, M in [(2, 2), (2, 4), (4, 4), (4, 8), (4, 2), (8, 8)]:
        F, B, R, ring = schedule_1f1b(S, M)  # internal asserts check slots
        # Every microbatch is forwarded and backed at every stage, and
        # every non-first stage sees each activation arrive exactly once.
        for s in range(S):
            assert sorted(F[:, s][F[:, s] >= 0].tolist()) == list(range(M))
            assert sorted(B[:, s][B[:, s] >= 0].tolist()) == list(range(M))
            if s > 0:
                assert sorted(R[:, s][R[:, s] >= 0].tolist()) == list(range(M))
        # Backward grads must arrive one hop per tick: stage s consumes
        # the dh stage s+1 produced the tick before.
        for s in range(S - 1):
            for m in range(M):
                tb_here = int(np.where(B[:, s] == m)[0][0])
                tb_next = int(np.where(B[:, s + 1] == m)[0][0])
                assert tb_here == tb_next + 1, (s, m)
        # The memory property: ring is bounded by stage depth, not M.
        assert ring <= (3 * (S - 1)) // 2 + 1, (S, M, ring)


def test_1f1b_loss_and_grads_match_gpipe():
    from rayfed_tpu.parallel.pipeline import (
        make_1f1b_loss_and_grad, make_pp_loss_fn,
    )

    cfg = _cfg()
    params = tfm.init_params(jax.random.PRNGKey(6), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    for n_stages, m in [(2, 4), (4, 4), (4, 2)]:
        mesh = _stage_mesh(n_stages)
        gpipe_loss = make_pp_loss_fn(cfg, mesh, n_microbatches=m)
        ref_loss, ref_grads = jax.jit(
            jax.value_and_grad(gpipe_loss)
        )(params, inputs, targets)
        fn = make_1f1b_loss_and_grad(cfg, mesh, n_microbatches=m)
        loss, grads = jax.jit(fn)(params, inputs, targets)
        np.testing.assert_allclose(
            float(loss), float(ref_loss), rtol=1e-5,
            err_msg=f"stages={n_stages} micro={m}",
        )
        for (kp, ref), (_, got) in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree_util.tree_leaves_with_path(grads),
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-5,
                err_msg=f"stages={n_stages} micro={m} {kp}",
            )


def test_1f1b_train_step_trains():
    from rayfed_tpu.parallel.pipeline import make_pp_train_step

    cfg = _cfg()
    mesh = _stage_mesh(4)
    init_fn, step_fn = make_pp_train_step(
        cfg, mesh, n_microbatches=4, schedule="1f1b", lr=1e-2
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (8, 17), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params, opt_state = init_fn(jax.random.PRNGKey(9), inputs)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(x) for x in losses)


def test_1f1b_composes_with_tp_and_party():
    from rayfed_tpu.parallel.pipeline import make_pp_train_step

    cfg = _cfg()  # n_layers=4
    tokens = jax.random.randint(
        jax.random.PRNGKey(10), (8, 17), 0, cfg.vocab
    )
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2), ("party", "stage", "model")
    )
    init_fn, step_fn = make_pp_train_step(
        cfg, mesh, party_axis="party", n_microbatches=4,
        schedule="1f1b", lr=1e-2,
    )
    params, opt_state = init_fn(jax.random.PRNGKey(11), inputs)
    losses = []
    for _ in range(2):
        params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses

    # The party-sharded batch 1F1B loss equals the GPipe loss on the
    # same program (both average microbatches then parties).
    gpipe_init, gpipe_step = make_pp_train_step(
        cfg, mesh, party_axis="party", n_microbatches=4, lr=1e-2,
    )
    g_params, g_opt = gpipe_init(jax.random.PRNGKey(11), inputs)
    _, _, g_loss = gpipe_step(g_params, g_opt, inputs, targets)
    f_params, f_opt = init_fn(jax.random.PRNGKey(11), inputs)
    _, _, f_loss = step_fn(f_params, f_opt, inputs, targets)
    np.testing.assert_allclose(float(f_loss), float(g_loss), rtol=1e-5)


def test_moe_composes_into_flagship_mesh_matches_single_device():
    """MoE (experts sharded over the ``model`` axis via the
    prune_spec_to_mesh fallback) inside the composed party x data x model
    x seq train step equals the same step on one device (VERDICT r2 #6)."""
    from jax.sharding import NamedSharding

    from rayfed_tpu.parallel import sharding as shd
    from rayfed_tpu.parallel.train import make_fed_train_step

    cfg = tfm.tiny_config(n_experts=4, compute_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab)

    def loss_and_grads(mesh, seq_axis):
        init_fn, step_fn = make_fed_train_step(
            cfg, mesh, seq_axis=seq_axis, lr=1e-2,
        )
        sharding = NamedSharding(mesh, shd.batch_spec(mesh, seq_axis=seq_axis))
        inputs = jax.device_put(tokens[:, :-1], sharding)
        targets = jax.device_put(tokens[:, 1:], sharding)
        params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
        # Equivalence is pinned on loss + raw grads: comparing post-Adam
        # params would amplify float-rounding grad noise to O(lr)
        # wherever a gradient is near zero (sign-like first step).
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: tfm.lm_loss_pair(p, inputs, targets, cfg)
        ))(params)
        spec = tuple(params["layers"]["moe"]["w_up"].sharding.spec)
        # One full step must also run and stay finite (exercises the
        # composed update path; donates params/opt_state, so last).
        _, _, step_loss = step_fn(params, opt_state, inputs, targets)
        assert np.isfinite(float(step_loss))
        return float(loss), grads, spec

    composed = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 1, 2, 2),
        ("party", "data", "model", "seq"),
    )
    single = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                  ("party", "data", "model", "seq"))
    loss_c, grads_c, spec = loss_and_grads(composed, "seq")
    loss_s, grads_s, _ = loss_and_grads(single, None)

    # Experts really shard over the model axis on the composed mesh.
    assert "model" in spec, spec
    np.testing.assert_allclose(loss_c, loss_s, rtol=2e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads_c),
        jax.tree_util.tree_leaves(grads_s),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5
        )


def test_pp_train_step_with_moe_layers():
    """pp x tp x ep: the 1F1B pipeline step trains a MoE transformer on a
    party x stage x model mesh (experts over the model axis)."""
    from rayfed_tpu.parallel.pipeline import make_pp_train_step

    cfg = tfm.tiny_config(
        n_layers=4, n_experts=4, compute_dtype=jnp.float32
    )
    mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2),
        ("party", "stage", "model"),
    )
    init_fn, step_fn = make_pp_train_step(
        cfg, mesh, party_axis="party", n_microbatches=4, schedule="1f1b",
        lr=1e-2,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab)
    params, opt_state = init_fn(jax.random.PRNGKey(0), tokens[:, :-1])
    spec = tuple(params["layers"]["moe"]["w_up"].sharding.spec)
    assert "model" in spec, spec
    l0 = None
    for i in range(3):
        params, opt_state, loss = step_fn(
            params, opt_state, tokens[:, :-1], tokens[:, 1:]
        )
        if i == 0:
            l0 = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < l0
