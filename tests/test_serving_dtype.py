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

"""An engine's bank holds the tree its programs read (docs/serving.md,
"What an engine's bank holds"): a published version is cast once to the
model's ``serving_dtype`` when it is installed, by every way in, and the
programs give the bits they gave on the wider tree.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import seeded_falcon_h1  # noqa: E402
from rayfed_tpu import tracing  # noqa: E402
from rayfed_tpu.config import ServingConfig  # noqa: E402
from rayfed_tpu.models import decode  # noqa: E402
from rayfed_tpu.models import falcon_h1 as fh  # noqa: E402
from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.serving.kv_pool import PagedKVPool  # noqa: E402
from rayfed_tpu.serving.publish import (  # noqa: E402
    ModelBank,
    cast_nbytes,
    snapshot_tree,
)
from rayfed_tpu.serving.server import InferenceServer  # noqa: E402
from rayfed_tpu.telemetry import metrics as telemetry_metrics  # noqa: E402

# bfloat16 compute over float32 parameters: what the cells serve.
CFG = tfm.tiny_config()
CDT = jnp.dtype(CFG.compute_dtype)
PARAMS = tfm.init_params(jax.random.PRNGKey(28), CFG)
PARAMS_B = tfm.init_params(jax.random.PRNGKey(29), CFG)
MODEL = decode.serving_model(CFG)
SERVED = snapshot_tree(PARAMS, MODEL.serving_dtype())
F32_BYTES = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(PARAMS))

SHORT = [(7 * i + 3) % 256 for i in range(11)]
LONG = [(5 * i + 1) % 256 for i in range(45)]      # over prefill_chunk: chunked


def _server(params=PARAMS, **kw):
    base = dict(max_slots=4, max_len=96, max_new_tokens=12)
    base.update(kw)
    return InferenceServer(CFG, ServingConfig(**base), params=params)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _all_in_serving_dtype(tree):
    return all(
        x.dtype == CDT for x in _leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    )


def _same_bits(a, b):
    la, lb = _leaves(a), _leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# -- (a) the programs: the same bits from either tree -----------------------


def _run_prefill_rows(params):
    prompts = np.zeros((3, 16), np.int32)
    prompts[0, :11], prompts[1, :5], prompts[2, :16] = SHORT, SHORT[:5], LONG[:16]
    fn = jax.jit(lambda p, t, i, m: MODEL.prefill_rows(p, t, i, 24, CDT, m))
    return fn(params, jnp.asarray(prompts), jnp.asarray([10, 4, 15]),
              jnp.ones(3, bool))


def _run_chunk(params):
    pool = PagedKVPool(CFG, max_slots=1, max_len=40, dtype=CDT, block_size=8)
    toks = np.asarray(LONG[:16], np.int32)
    table = np.arange(1, 1 + pool.blocks_per_row, dtype=np.int32)
    out = None
    kv = pool.kv
    for offset, n_real in ((0, 16), (16, 9)):
        out = jax.jit(MODEL.chunk)(
            params, kv, {}, table, np.int32(0), jnp.asarray(toks),
            np.int32(offset), np.int32(n_real))
        kv = out[1]
    return out


def _run_decode_step(params):
    pool = PagedKVPool(CFG, max_slots=3, max_len=32, dtype=CDT, block_size=8)
    key = jax.random.PRNGKey(5)
    kv = tuple(jax.random.normal(k, pool.kv[0].shape, CDT)
               for k in jax.random.split(key))
    tables = np.zeros((3, pool.blocks_per_row), np.int32)
    tables[0, :2], tables[1, :1] = (1, 2), (3,)       # row 2 is a junk row
    return jax.jit(MODEL.decode_step)(
        params, kv, {}, jnp.asarray([17, 99, 0], jnp.int32),
        jnp.asarray([13, 4, 0], jnp.int32), jnp.asarray(tables), None)


def _run_forward_with_cache(params):
    cache = decode.init_cache(CFG, 2, 24, CDT)
    toks = jnp.asarray([SHORT, LONG[:11]], jnp.int32)
    return jax.jit(
        lambda p: decode.forward_with_cache(p, toks, cache, 0, CFG))(params)


@pytest.mark.parametrize(
    "run", [_run_prefill_rows, _run_chunk, _run_decode_step,
            _run_forward_with_cache],
    ids=["prefill_rows", "chunk", "decode_step", "forward_with_cache"],
)
def test_a_program_gives_the_same_bits_on_the_published_and_the_served_tree(
        run):
    """Logits, K/V and what else a program returns, bit for bit: the cast
    of a float32 value is the same bfloat16 value wherever it is made."""
    assert _all_in_serving_dtype(SERVED) and not _all_in_serving_dtype(PARAMS)
    wide, served = run(PARAMS), run(SERVED)
    assert _leaves(wide)[0].dtype == jnp.float32        # the logits stay wide
    assert _same_bits(wide, served)


# -- (b) every way into an engine's bank ------------------------------------


@pytest.mark.parametrize("source", ["device", "host"])
def test_the_engines_bank_holds_the_serving_dtype_and_counts_the_cast(source):
    """``InferenceServer(params=)`` and ``publish``, from a device tree and
    from a NumPy one (a tree that crossed the wire): every floating leaf
    of the bank's tree has the compute dtype and the value the cast gives,
    the published tree is untouched and its buffers may be donated
    afterwards, and the counter reads the float32 bytes published."""
    first, second = PARAMS, PARAMS_B
    if source == "host":
        first, second = jax.tree_util.tree_map(np.array, (first, second))
    else:
        second = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                        second)
    mirror = telemetry_metrics.get_registry().counter(
        "fed_serving_publish_cast_bytes_total", "", labels=("server",),
    ).labels(server=f"cast-{source}")
    mirror_before = mirror.value()
    srv = InferenceServer(
        CFG, ServingConfig(max_slots=2, max_len=32), params=first,
        name=f"cast-{source}")
    try:
        assert srv.stats()["publish_cast_bytes"] == F32_BYTES
        assert _same_bits(srv.bank.get(1), SERVED)
        assert _same_bits(first, PARAMS)                # untouched
        assert all(isinstance(x, jax.Array) for x in _leaves(srv.bank.get(1)))
        assert srv.publish(second) == 2
        want = snapshot_tree(PARAMS_B, CDT)
        if source == "device":
            # The trainer feeds the buffers it published to a donating step.
            jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 0, t),
                    donate_argnums=0)(second)
        else:
            for x in _leaves(second):
                x[...] = -1.0                           # a recycled buffer
        assert _same_bits(srv.bank.get(2), want)
        assert srv.stats()["publish_cast_bytes"] == 2 * F32_BYTES
        assert mirror.value() - mirror_before == 2 * F32_BYTES
        out = srv.submit(SHORT, max_new_tokens=4).result(timeout=300)
        assert out["version"] == 2 and len(out["tokens"]) == 4
    finally:
        srv.stop()


@pytest.mark.parametrize("exporter", ["standby", "engine"])
def test_a_promoted_standbys_bank_holds_the_serving_dtype(exporter):
    """Promotion (``_serve_promote``) restores a bank's exported state into
    a new engine's bank. A standby's plain ``ModelBank`` holds the tree as
    published and the promotion casts it; an engine's exported state is in
    the serving dtype already and its cast is the identity, counted 0."""
    if exporter == "standby":
        replica = ModelBank()
        replica.publish(PARAMS)
        replica.restore_state({"version": 5, "params": PARAMS_B})
        assert not _all_in_serving_dtype(replica.get(5))    # as it was given
        state, cast = replica.export_state(), F32_BYTES
    else:
        old = _server(params=PARAMS_B)
        old.stop()
        state, cast = dict(old.bank.export_state(), version=5), 0
    srv = InferenceServer(
        CFG, ServingConfig(max_slots=2, max_len=32), params=None)
    try:
        assert srv.bank.restore_state(state) == 5
        assert _same_bits(srv.bank.get(5), snapshot_tree(PARAMS_B, CDT))
        assert srv.stats()["publish_cast_bytes"] == cast
        out = srv.submit(SHORT, max_new_tokens=4).result(timeout=300)
        assert out["version"] == 5
        assert srv.publish(PARAMS) == 6                     # numbering goes on
    finally:
        srv.stop()


def test_a_plain_bank_keeps_what_it_was_given():
    bank = ModelBank()
    bank.publish(PARAMS, draft_params=jax.device_get(PARAMS_B))
    assert _same_bits(bank.get(1), PARAMS)
    assert _same_bits(bank.get_extra(1, "draft_params"), PARAMS_B)
    assert _same_bits(snapshot_tree(PARAMS), PARAMS)
    assert cast_nbytes(PARAMS, None) == cast_nbytes(SERVED, CDT) == 0
    assert cast_nbytes(PARAMS, CDT) == F32_BYTES


def test_a_falcon_h1_tree_comes_back_as_published_and_counts_no_cast():
    """The second implementer takes its tree as published (bfloat16, and a
    float32 one too: its mixer's small leaves must not be narrowed)."""
    tiny = {
        "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 176,
        "num_hidden_layers": 2, "mamba_d_ssm": 64, "mamba_n_heads": 4,
        "mamba_d_head": 16, "mamba_d_state": 8, "mamba_n_groups": 2,
        "mamba_d_conv": 4, "mamba_chunk_size": 8, "rope_theta": 1e11,
        "rms_norm_eps": 1e-5, "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
        "mlp_multipliers": [0.18, 0.011], "embedding_multiplier": 5.66,
        "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1.25,
        "attention_out_multiplier": 0.0375, "key_multiplier": 0.011,
        "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.088,
    }
    assert fh.serving_model(
        fh.FalconH1Config.from_published(tiny)).serving_dtype() is None
    for dtype in (jnp.bfloat16, jnp.float32):
        cfg = fh.FalconH1Config.from_published(
            tiny, compute_dtype=jnp.bfloat16, param_dtype=dtype)
        tree = seeded_falcon_h1.to_program_tree(
            seeded_falcon_h1.make_canonical(
                seeded_falcon_h1.key_of(3), tiny, dtype), tiny)
        srv = InferenceServer(
            cfg, ServingConfig(max_slots=2, max_len=32, kv_block_size=8,
                               prefix_reuse=False),
            params=jax.device_get(tree))
        try:
            assert _same_bits(srv.bank.get(1), tree)
            assert srv.stats()["publish_cast_bytes"] == 0
        finally:
            srv.stop()


def test_draft_params_go_through_the_draft_configs_model():
    """The draft of speculative serving computes in float32 here and the
    target in bfloat16: each tree is cast for the model that reads it."""
    draft_cfg = tfm.tiny_config(n_layers=1, compute_dtype=jnp.float32)
    draft = tfm.init_params(jax.random.PRNGKey(3), draft_cfg)
    srv = InferenceServer(
        CFG, ServingConfig(max_slots=2, max_len=48), draft_cfg=draft_cfg)
    try:
        v = srv.publish(PARAMS, draft_params=draft)
        assert _same_bits(srv.bank.get(v), SERVED)
        assert _same_bits(srv.bank.get_extra(v, "draft_params"), draft)
        assert srv.stats()["publish_cast_bytes"] == F32_BYTES
        spec = srv.submit(SHORT, max_new_tokens=6, mode="speculative",
                          temperature=0.0).result(timeout=300)
        plain = srv.submit(SHORT, max_new_tokens=6,
                           temperature=0.0).result(timeout=300)
        assert spec["tokens"] == plain["tokens"]
    finally:
        srv.stop()


def test_the_cast_is_a_span_when_tracing_is_on():
    tracing.clear()
    tracing.enable()
    try:
        srv = _server(max_slots=2, max_len=32)
        srv.stop()
        again = InferenceServer(
            CFG, ServingConfig(max_slots=2, max_len=32), params=SERVED)
        again.stop()
        spans = tracing.phase_summary()
    finally:
        tracing.disable()
        tracing.clear()
    # One cast; publishing a tree that is in the dtype already opens none.
    assert spans["fed:serve:publish_cast"]["count"] == 1
    assert again.stats()["publish_cast_bytes"] == 0


# -- (c) the tokens the parent served ---------------------------------------

# The greedy cases are pinned from commit 1fd2692 (PR 27, casts in every
# program) on this CPU: same prompts, paged layout, max_len 96; they have
# not moved since, and guard that PR 30 (the token chosen on the device)
# left greedy decoding bit for bit. The sampled cases are pinned from
# PR 30's own tree (parent a6fe4ab): that PR changed the generator once
# (Gumbel-max under the Threefry key (seed, position), serving/sampling.py),
# and with it every fixed-seed sampled sequence; from commit 1fd2692 they
# read [153, 223, 219, ...] and [24, 115, 150, ...].
PINNED = {
    "greedy_short": (SHORT, dict(temperature=0.0),
                     [58, 219, 46, 167, 58, 219, 83, 139, 36, 58, 179, 46]),
    "sampled_short": (SHORT, dict(temperature=0.8, seed=7),
                      [146, 209, 174, 177, 80, 148, 9, 107, 93, 160, 206,
                       184]),
    "greedy_chunked": (LONG, dict(temperature=0.0),
                       [225, 115, 199, 199, 199, 199, 13, 194, 53, 46, 71,
                        157]),
    "sampled_chunked": (LONG, dict(temperature=0.8, seed=11),
                        [223, 124, 216, 201, 202, 149, 30, 78, 170, 183, 139,
                         30]),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_served_tokens_are_the_parents(case):
    prompt, opts, want = PINNED[case]
    srv = _server()
    try:
        got = srv.submit(prompt, **opts).result(timeout=300)["tokens"]
    finally:
        srv.stop()
    assert got == want
    if opts["temperature"] == 0.0:
        # And what the float32 tree generates outside any engine.
        gen = decode.make_generate_fn(CFG, max_new_tokens=len(want))
        ref = np.asarray(gen(PARAMS, np.asarray(prompt, np.int32)[None]))
        assert [int(t) for t in ref[0, len(prompt):]] == got


# -- (d) two versions live during a swap ------------------------------------


def test_both_versions_of_a_swap_hold_served_trees_and_retire_as_before():
    srv = _server(max_slots=2)
    try:
        fut, stream = srv.submit_stream(SHORT, max_new_tokens=40,
                                        temperature=0.0)
        next(iter(stream))                      # admitted and decoding on v1
        assert srv.publish(PARAMS_B) == 2
        assert srv.bank.live_versions() == [1, 2]
        for v in (1, 2):
            assert _all_in_serving_dtype(srv.bank.get(v))
        new = srv.submit(SHORT, max_new_tokens=4, temperature=0.0)
        assert new.result(timeout=300)["version"] == 2
        old = fut.result(timeout=300)
        assert old["version"] == 1 and len(old["tokens"]) == 40
        assert srv.bank.live_versions() == [2]              # v1 retired
        gen = decode.make_generate_fn(CFG, max_new_tokens=40)
        ref = np.asarray(gen(PARAMS, np.asarray(SHORT, np.int32)[None]))
        assert [int(t) for t in ref[0, len(SHORT):]] == old["tokens"]
    finally:
        srv.stop()
