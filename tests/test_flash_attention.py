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

"""Pallas flash attention equivalence tests (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu.models import transformer as tfm
from rayfed_tpu.ops.flash_attention import flash_attention, make_flash_attn_fn


def _qkv(key, b, s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, s, h, d), dtype),
        jax.random.normal(kk, (b, s, h, d), dtype),
        jax.random.normal(kv, (b, s, h, d), dtype),
    )


@pytest.mark.parametrize("s,block", [(64, 16), (128, 128), (96, 32)])
def test_matches_reference(s, block):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, s, 2, 32)
    expect = tfm.causal_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=block, block_k=block)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_q_offset_matches_suffix():
    # Second half of the queries with q_offset == full-attention suffix.
    s = 64
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, s, 2, 16)
    full = tfm.causal_attention(q, k, v)
    half = flash_attention(
        q[:, s // 2:], k, v, block_q=16, block_k=16, q_offset=s // 2
    )
    np.testing.assert_allclose(
        np.asarray(half), np.asarray(full[:, s // 2:]), rtol=2e-5, atol=2e-5
    )


def test_transformer_forward_with_flash_attn():
    # f32 compute: in bf16 the flash kernel is MORE accurate than the
    # reference path (full f32 accumulation vs bf16 prob-matmul), so
    # logits drift apart through layers for reasons that are not bugs.
    cfg = tfm.tiny_config(compute_dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab)
    ref_logits = tfm.forward(params, tokens, cfg)
    flash_logits = tfm.forward(
        params, tokens, cfg, attn_fn=make_flash_attn_fn(block_q=16, block_k=16)
    )
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(ref_logits), rtol=2e-2, atol=2e-2
    )


def test_bf16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 32, 2, 16, jnp.bfloat16)
    expect = tfm.causal_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expect, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_flash_backward_matches_xla_grads():
    """The Pallas backward (dq/dk/dv two-pass) must match autodiff through
    the dense reference attention."""
    b, s, h, d = 2, 64, 4, 32
    q, k, v = _qkv(jax.random.PRNGKey(7), b, s, h, d)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16) ** 2).sum()

    def loss_ref(q, k, v):
        return (tfm.causal_attention(q, k, v) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-4
        )


def test_flash_backward_q_offset():
    """Gradients with a query offset (ring-attention decomposition): the
    suffix-query grads must match the corresponding slice of full grads."""
    s = 64
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, s, 2, 16)

    def loss_suffix(qs, k, v):
        return (
            flash_attention(
                qs, k, v, block_q=16, block_k=16, q_offset=s // 2
            ) ** 2
        ).sum()

    def loss_full(q, k, v):
        out = tfm.causal_attention(q, k, v)
        return (out[:, s // 2:] ** 2).sum()

    dq_s = jax.grad(loss_suffix)(q[:, s // 2:], k, v)
    dq_f = jax.grad(loss_full)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(dq_s), np.asarray(dq_f[:, s // 2:]), rtol=3e-4, atol=3e-4
    )


def test_train_step_with_flash_attn_and_chunked_loss():
    """End-to-end: make_fed_train_step(attn='flash') takes a finite step
    and chunked CE equals the dense CE."""
    import numpy as onp
    from jax.sharding import Mesh

    from rayfed_tpu.parallel.train import make_fed_train_step

    cfg = tfm.tiny_config(d_model=64, n_heads=4, n_layers=2)
    mesh = Mesh(onp.array(jax.devices()[:1]), ("data",))
    init_fn, step_fn = make_fed_train_step(
        cfg, mesh, party_axis=None, data_axis="data", attn="flash", lr=1e-2
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, cfg.vocab)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)
    params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
    assert np.isfinite(float(loss))

    params2 = tfm.init_params(jax.random.PRNGKey(0), cfg)
    dense = tfm.lm_loss_pair(params2, inputs, targets, cfg)
    chunked = tfm.lm_loss_pair(params2, inputs, targets, cfg, loss_chunk=8)
    np.testing.assert_allclose(
        float(chunked), float(dense), rtol=1e-5, atol=1e-5
    )


def test_flash_train_step_is_mapped_over_batch_and_heads(monkeypatch):
    """On more than one real chip a Mosaic call cannot be partitioned by
    GSPMD: jax refuses to lower it ("Mosaic kernels cannot be
    automatically partitioned") — which interpret mode hides on the CPU.
    make_fed_train_step maps the kernel over the batch and head axes
    instead; lowering the step for TPU on a data x model mesh shows each
    device's kernel working on its (batch/2) x (heads/2) shard."""
    import re

    import numpy as onp
    from jax.sharding import Mesh, NamedSharding

    import rayfed_tpu.utils as utils
    from rayfed_tpu.parallel import sharding as shd
    from rayfed_tpu.parallel.train import make_fed_train_step

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    cfg = tfm.tiny_config(d_model=256, n_heads=4, n_layers=1, d_ff=256)
    mesh = Mesh(onp.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    _, step_fn = make_fed_train_step(
        cfg, mesh, party_axis=None, attn="auto", donate=False
    )
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)
    )
    from rayfed_tpu.parallel.train import make_optimizer

    opt_state = jax.eval_shape(make_optimizer().init, params)
    batch = jax.ShapeDtypeStruct(
        (4, 128), jnp.int32,
        sharding=NamedSharding(mesh, shd.batch_spec(mesh, party_axis=None)),
    )
    text = step_fn.trace(params, opt_state, batch, batch).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    kernels = re.findall(
        r'@tpu_custom_call\((.*?)\).*?kernel_name = "(\w+)".*?'
        r"-> \(?tensor<(\d+)x128x64x",
        text,
    )
    assert {name for _, name, _ in kernels} == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"
    }, kernels
    # 4 rows x 4 heads over a 2 x 2 mesh: each device folds 2 x 2 = 4.
    assert {lead for _, _, lead in kernels} == {"4"}, kernels
