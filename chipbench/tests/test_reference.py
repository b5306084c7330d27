"""The plain reference against the program at the tiny size on the CPU,
and the lower-precision controls that must come out as not correct."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = {"hidden_size": 128, "intermediate_size": 352, "vocab_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "num_hidden_layers": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-6}
OPT = {"lr": 1e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.01}
B, S = 2, 64


def program_readings(seed, compute_dtype):
    """Three steps of the program's own train step from seeded weights:
    losses, first-gradient leaf norms (from AdamW's first moment), change."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from chipbench import compare, seeded
    from rayfed_tpu.models import transformer as tfm
    from rayfed_tpu.parallel.train import make_fed_train_step, make_optimizer

    dims = seeded.dims_of(MODEL)
    cfg = tfm.TransformerConfig(vocab=512, d_model=128, n_heads=4, n_layers=2,
                                d_ff=352, compute_dtype=compute_dtype)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    _, step = make_fed_train_step(cfg, mesh, party_axis=None, lr=OPT["lr"],
                                  remat=True, attn="auto", donate=False)
    key = seeded.key_of(seed)
    params = seeded.make_program_tree(key, dims)
    opt_state = jax.jit(make_optimizer(OPT["lr"]).init)(params)
    losses, grad = [], None
    for i in range(3):
        x, y = seeded.make_batch(seeded.batch_key(seed, 0, i), B, S, 512)
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
        if grad is None:
            mu = seeded.from_program_tree(opt_state[0].mu, dims)
            grad = {k: [v / (1 - OPT["b1"]) for v in vs] for k, vs in
                    compare.to_host(compare.leaf_norms(mu)).items()}
    change = compare.to_host(compare.change_norms_fn(
        lambda k: seeded.canonical_weights(k, dims))(
            seeded.from_program_tree(params, dims), key))
    del jnp
    return losses, grad, change


def reference_readings(seed, quant=None):
    from chipbench import compare, seeded
    from chipbench.references import dense_mha_swiglu as ref

    dims = seeded.dims_of(MODEL)
    key = seeded.key_of(seed)
    batches = [seeded.make_batch(seeded.batch_key(seed, 0, i), B, S, 512)
               for i in range(3)]
    change = compare.change_norms_fn(
        lambda k: seeded.canonical_weights(k, dims))
    return ref.train_readings(
        lambda: seeded.make_canonical(key, dims), lambda w: change(w, key),
        batches, 4, MODEL["rope_theta"], MODEL["rms_norm_eps"], OPT, quant)


def gaps(got, ref):
    from chipbench import compare

    return (max(abs(a - b) for a, b in zip(got[0], ref[0])),
            compare.worst_leaf_gap(got[1], ref[1])[0],
            compare.worst_leaf_gap(got[2], ref[2])[0])


def test_train_float32_program_matches_and_bf16_as_float32_fails():
    """A configuration that states float32 compute: the program in float32
    agrees with the reference to float32 rounding over three steps (loss
    1e-5, gradient norms 1e-4 by the worst leaf: sums of 1e4..1e5 float32
    products in another order). The same program computing in bfloat16 and
    passed off as float32 must fail those limits."""
    import jax.numpy as jnp

    ref = reference_readings(21)
    loss_gap, grad_gap, change_gap = gaps(
        program_readings(21, jnp.float32), ref)
    assert loss_gap < 1e-5 and grad_gap < 1e-4 and change_gap < 1e-3
    loss_gap, grad_gap, _ = gaps(program_readings(21, jnp.bfloat16), ref)
    assert loss_gap > 1e-5 and grad_gap > 1e-4


def test_train_fp8_control_is_told_from_sound_bf16_runs():
    """The control of the cells as configured (bfloat16 compute): the
    reference with every matmul operand rounded to float8 must read at
    least three times the sound program's largest gap in the first
    gradient's norm, over three seeds, at this size too."""
    import jax.numpy as jnp

    sound, control = [], []
    for seed in (31, 32, 33):
        ref = reference_readings(seed)
        sound.append(gaps(program_readings(seed, jnp.bfloat16), ref)[1])
        control.append(gaps(reference_readings(seed, "fp8"), ref)[1])
    assert min(control) > 3 * max(sound), (sound, control)


def test_a_step_that_returns_its_state_unchanged_fails_the_change_norm():
    from chipbench import compare
    from chipbench.kinds import fedround  # noqa: F401 - limits live there

    ref = reference_readings(41)
    zero = {k: [0.0] * len(v) for k, v in ref[2].items()}
    assert compare.worst_leaf_gap(zero, ref[2])[0] == pytest.approx(1.0)
    assert 1.0 > fedround.LIMITS["change_norm_gap"]


def test_served_logit_gap_tells_fp8_from_the_reference():
    """Serving's number: at every position of seeded sequences, the gap of
    the token a lower precision puts first, under the float32 reference.
    float8 must read at least three times bfloat16's widest."""
    import jax.numpy as jnp

    from chipbench import seeded
    from chipbench.references import dense_mha_swiglu as ref

    dims = seeded.dims_of(MODEL)
    widest = {"bf16": 0.0, "fp8": 0.0}
    for seed in (51, 52, 53):
        w = seeded.make_canonical(seeded.key_of(seed), dims)
        tokens = np.random.default_rng(seed).integers(1, 512, 128)
        idx = jnp.arange(32, 128)
        args = (jnp.asarray(tokens, jnp.int32), idx, 4, 1e4, 1e-6)
        exact = np.asarray(ref.logits_at(w, *args))
        for quant in widest:
            low = np.asarray(ref.logits_at(w, *args, quant))
            gap = exact.max(-1) - exact[np.arange(len(exact)),
                                        low.argmax(-1)]
            widest[quant] = max(widest[quant], float(gap.max()))
    assert widest["fp8"] > 3 * widest["bf16"], widest
