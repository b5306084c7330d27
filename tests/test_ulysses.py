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

"""All-to-all (Ulysses) sequence parallelism: exactness against the
unsharded attention, gradients, the train-step integration, and the
head-divisibility guard. SURVEY §5.7 names "ring attention or
all-to-all sequence/context parallelism" — this is the second strategy
(first: tests/test_ring_attention.py)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.parallel.ulysses import (  # noqa: E402
    reference_full_attention,
    ulysses_attention,
)

B, S, H, DH = 2, 32, 8, 16
N_SEQ = 4


def _mesh():
    devs = np.array(jax.devices()[:N_SEQ])
    return Mesh(devs.reshape(N_SEQ), ("seq",))


def _qkv(key):
    ks = jax.random.split(key, 3)
    shape = (B, S, H, DH)
    return tuple(
        jax.random.normal(k, shape, jnp.float32) for k in ks
    )


def _sharded_apply(mesh, fn, q, k, v):
    pspec = P(None, "seq", None, None)
    sharding = NamedSharding(mesh, pspec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(pspec, pspec, pspec), out_specs=pspec,
        check_vma=False, axis_names={"seq"},
    )
    return jax.jit(mapped)(q, k, v)


def test_matches_unsharded_attention():
    mesh = _mesh()
    q, k, v = _qkv(jax.random.PRNGKey(0))
    got = _sharded_apply(
        mesh, functools.partial(ulysses_attention, axis_name="seq"), q, k, v
    )
    want = reference_full_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_gradients_match_unsharded():
    mesh = _mesh()
    q, k, v = _qkv(jax.random.PRNGKey(1))
    pspec = P(None, "seq", None, None)
    mapped = shard_map(
        functools.partial(ulysses_attention, axis_name="seq"),
        mesh=mesh, in_specs=(pspec, pspec, pspec), out_specs=pspec,
        check_vma=False, axis_names={"seq"},
    )

    def loss_sharded(q, k, v):
        return (mapped(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_full_attention(q, k, v) ** 2).sum()

    gs = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


def test_heads_not_divisible_raises():
    mesh = _mesh()
    q, k, v = _qkv(jax.random.PRNGKey(2))
    q, k, v = (x[:, :, :6] for x in (q, k, v))  # 6 heads on a 4-axis
    with pytest.raises(ValueError, match="divisible"):
        _sharded_apply(
            mesh, functools.partial(ulysses_attention, axis_name="seq"),
            q, k, v,
        )


def test_fed_train_step_a2a_matches_unsharded_loss():
    from rayfed_tpu.parallel.train import make_fed_train_step

    devs = np.array(jax.devices()[:8]).reshape(2, 1, 1, 4)
    mesh = Mesh(devs, ("party", "data", "model", "seq"))
    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=176
    )
    init_fn, step_fn = make_fed_train_step(
        cfg, mesh, seq_axis="seq", seq_parallel="a2a", lr=1e-2, attn="xla",
    )
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 65), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params, opt_state = init_fn(jax.random.PRNGKey(3), inputs)
    params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
    assert np.isfinite(float(loss))

    # Same key + same data through the unsharded model = same first-step
    # loss (both paths compute EXACT attention; only the layout differs).
    init2, step2 = make_fed_train_step(
        cfg, Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                  ("party", "data", "model", "seq")),
        lr=1e-2, attn="xla",
    )
    p2, o2 = init2(jax.random.PRNGKey(3), inputs)
    _, _, loss_ref = step2(p2, o2, inputs, targets)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-4)


def test_train_step_rejects_a2a_on_too_wide_axis():
    from rayfed_tpu.parallel.train import make_fed_train_step

    devs = np.array(jax.devices()[:8]).reshape(1, 1, 1, 8)
    mesh = Mesh(devs, ("party", "data", "model", "seq"))
    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=176
    )
    with pytest.raises(ValueError, match="divisible"):
        make_fed_train_step(
            cfg, mesh, seq_axis="seq", seq_parallel="a2a", attn="xla"
        )
