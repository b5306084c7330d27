"""The ``falcon_h1`` adapter: everything a run of that architecture draws
from ``--seed``, the program's configuration from the published keys, and
how its plain reference is called. ``kinds/closed_loop_arch.py`` finds it
by the configuration's ``reference`` (``chipbench/seeded_<reference>.py``);
the next architecture adds such a file and no kind.

The benchmark makes the weights; the program and the plain reference are
each handed them. They are made in the configuration's parameter type
(bfloat16: the type the model is published in), one leaf at a time and a
block of a leaf at a time, so that neither the float32 draw of a 1.3 G
element leaf nor a second copy of the tree is ever held: the program's
tree goes to the host leaf by leaf (``publish_from: host``), the
reference's stays on the device once the engine is gone.

Canonical layout (what the reference reads)::

    embed (V, d)   ln_f (d)   lm_head (d, V)
    layers: ln1 ln2 (L, d)
            wq (L, d, H*Dh)  wk wv (L, d, Hkv*Dh)  wo (L, H*Dh, d)
            in_proj (L, d, 2*d_ssm + 2*G*N + Hs)   zones z | x | B | C | dt
            conv_w (L, K, C)  conv_b (L, C)      C = d_ssm + 2*G*N
            dt_bias A_log D (L, Hs)   ssm_norm (L, d_ssm)
            out_proj (L, d_ssm, d)
            w_gate w_up (L, d, f)   w_down (L, f, d)

Scales (the configuration's ``assumed``): every matrix is normal with std
``fan_in**-0.5`` divided by the published multipliers that follow it on
its path, so that each path runs at its natural scale whatever muP
constant it carries (``key_multiplier`` 0.011 would otherwise flatten
every softmax, ``attention_out_multiplier`` 0.0375, ``ssm_out_multiplier``
0.088 and ``mlp_multipliers[1]`` 0.011 would leave a branch under the
comparison's tolerance and the comparison blind to it); ``wo`` a further
``WO_BOOST`` because a softmax's average shrinks what it averages. The
mixer's own parameters take Mamba-2's initial ranges: ``A`` uniform in
[1, 16], ``dt`` log-uniform in [1e-3, 0.1] stored as its inverse
softplus, ``D`` = 1, convolution weights std ``K**-0.5``, so that a state
neither dies in a step nor never decays. Norm scales are 1 + 0.1 N(0,1).
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.seeded import key_of  # noqa: F401 - the adapter's own name for it

WO_BOOST = 3.0
# A leaf with more elements than this is drawn a block of its first axis
# at a time (32 blocks, or one per layer).
BLOCKED_ABOVE = 1 << 24


class Dims(NamedTuple):
    vocab: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    f: int
    layers: int
    d_ssm: int
    ssm_heads: int
    d_state: int
    groups: int
    d_conv: int

    @property
    def conv_dim(self):
        return self.d_ssm + 2 * self.groups * self.d_state

    @property
    def in_proj_dim(self):
        return self.d_ssm + self.conv_dim + self.ssm_heads


def dims_of(model: dict) -> Dims:
    return Dims(
        vocab=int(model["vocab_size"]), d=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]), f=int(model["intermediate_size"]),
        layers=int(model["num_hidden_layers"]),
        d_ssm=int(model["mamba_d_ssm"]),
        ssm_heads=int(model["mamba_n_heads"]),
        d_state=int(model["mamba_d_state"]),
        groups=int(model["mamba_n_groups"]),
        d_conv=int(model["mamba_d_conv"]),
    )


def vocab_of(model: dict) -> int:
    return int(model["vocab_size"])


def param_dtype(precision: dict):
    return jnp.dtype(precision.get("parameters", "bfloat16"))


# ---------------------------------------------------------------------------
# Leaves: name -> (shape, how it is drawn)
# ---------------------------------------------------------------------------


def leaf_specs(model: dict) -> dict:
    """Every canonical leaf, in a fixed order (a leaf's index keys its
    draw): ``name -> (shape, kind, scale)`` with kind "normal" (std
    ``scale``, a number or a vector over the last axis), "norm" (1 + 0.1
    N), "ones", "zeros", "dt_bias" or "a_log"."""
    m, x = model, dims_of(model)
    n, d, f = x.layers, x.d, x.f
    qd, kvd = x.heads * x.head_dim, x.kv_heads * x.head_dim
    gn = x.groups * x.d_state
    zone = np.concatenate([
        np.full(w, mult, np.float32) for w, mult in zip(
            (x.d_ssm, x.d_ssm, gn, gn, x.ssm_heads), m["ssm_multipliers"])
    ])
    nat = d ** -0.5
    a_in = float(m["attention_in_multiplier"])
    return {
        "embed": ((x.vocab, d), "normal",
                  1.0 / float(m["embedding_multiplier"])),
        "ln_f": ((d,), "norm", None),
        "lm_head": ((d, x.vocab), "normal",
                    nat / float(m["lm_head_multiplier"])),
        "layers.ln1": ((n, d), "norm", None),
        "layers.wq": ((n, d, qd), "normal", nat / a_in),
        "layers.wk": ((n, d, kvd), "normal",
                      nat / (a_in * float(m["key_multiplier"]))),
        "layers.wv": ((n, d, kvd), "normal", nat / a_in),
        "layers.wo": ((n, qd, d), "normal", WO_BOOST * qd ** -0.5
                      / float(m["attention_out_multiplier"])),
        "layers.in_proj": ((n, d, x.in_proj_dim), "normal",
                           nat / (float(m["ssm_in_multiplier"]) * zone)),
        "layers.conv_w": ((n, x.d_conv, x.conv_dim), "normal",
                          x.d_conv ** -0.5),
        "layers.conv_b": ((n, x.conv_dim), "zeros", None),
        "layers.dt_bias": ((n, x.ssm_heads), "dt_bias", None),
        "layers.A_log": ((n, x.ssm_heads), "a_log", None),
        "layers.D": ((n, x.ssm_heads), "ones", None),
        "layers.ssm_norm": ((n, x.d_ssm), "norm", None),
        "layers.out_proj": ((n, x.d_ssm, d), "normal", x.d_ssm ** -0.5
                            / float(m["ssm_out_multiplier"])),
        "layers.ln2": ((n, d), "norm", None),
        "layers.w_gate": ((n, d, f), "normal",
                          nat / float(m["mlp_multipliers"][0])),
        "layers.w_up": ((n, d, f), "normal", nat),
        "layers.w_down": ((n, f, d), "normal",
                          f ** -0.5 / float(m["mlp_multipliers"][1])),
    }


def _draw(key, shape, kind, scale, dtype):
    f32 = jnp.float32
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "norm":
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(
            key, shape, f32, 1.0, 16.0)).astype(dtype)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, f32, math.log(1e-3), math.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    assert kind == "normal", kind
    scale = jnp.asarray(scale, f32)
    if math.prod(shape) <= BLOCKED_ABOVE:
        return (jax.random.normal(key, shape, f32) * scale).astype(dtype)
    n0 = shape[0]
    blocks = n0 if n0 <= 64 else math.gcd(n0, 32)
    rest = (n0 // blocks,) + tuple(shape[1:])
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, rest, f32) * scale).astype(dtype),
        jax.random.split(key, blocks))
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _draw_leaf(key, scale, shape, kind, dtype):
    return _draw(key, shape, kind, scale, dtype)


def make_leaf(key, model: dict, name: str, dtype):
    """One canonical leaf on the device, from its own key."""
    specs = leaf_specs(model)
    shape, kind, scale = specs[name]
    return _draw_leaf(
        jax.random.fold_in(key, list(specs).index(name)),
        np.float32(0.0) if scale is None else np.asarray(scale, np.float32),
        shape, kind, jnp.dtype(dtype))


def _nest(flat: dict) -> dict:
    out = {"layers": {}}
    for name, leaf in flat.items():
        if name.startswith("layers."):
            out["layers"][name[len("layers."):]] = leaf
        else:
            out[name] = leaf
    return out


def make_canonical(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The canonical tree on the device, leaf by leaf."""
    return _nest({name: make_leaf(key, model, name, dtype)
                  for name in leaf_specs(model)})


def _program_shape(name: str, x: Dims):
    """The program's shape of a canonical leaf (a free reshape), or None
    where the two agree."""
    n, d = x.layers, x.d
    return {
        "layers.wq": (n, d, x.heads, x.head_dim),
        "layers.wk": (n, d, x.kv_heads, x.head_dim),
        "layers.wv": (n, d, x.kv_heads, x.head_dim),
        "layers.wo": (n, x.heads, x.head_dim, d),
    }.get(name)


def to_program_tree(w: dict, model: dict) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.falcon_h1``: only
    reshapes."""
    x = dims_of(model)
    flat = {k: v for k, v in w.items() if k != "layers"}
    flat.update({"layers." + k: v for k, v in w["layers"].items()})
    return _nest({
        name: leaf.reshape(_program_shape(name, x) or leaf.shape)
        for name, leaf in flat.items()
    })


def make_program_tree_host(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The program's tree as host arrays: each leaf is drawn on the
    device, brought to the host and dropped before the next is drawn, so
    the device never holds more than the largest leaf."""
    x = dims_of(model)
    flat = {}
    for name in leaf_specs(model):
        leaf = jax.device_get(make_leaf(key, model, name, dtype))
        flat[name] = leaf.reshape(_program_shape(name, x) or leaf.shape)
    return _nest(flat)


# ---------------------------------------------------------------------------
# The program and the reference
# ---------------------------------------------------------------------------


def program_cfg(model: dict, precision: dict):
    from rayfed_tpu.models import falcon_h1

    return falcon_h1.FalconH1Config.from_published(
        model,
        compute_dtype=jnp.dtype(precision.get("compute", "bfloat16")),
        param_dtype=param_dtype(precision),
    )


def program_params_host(seed: int, model: dict, precision: dict) -> dict:
    return make_program_tree_host(key_of(seed), model, param_dtype(precision))


def reference_logits_fn(seed: int, model: dict, precision: dict,
                        name: str = "falcon_h1"):
    """``f(tokens, idx, quant=None) -> logits (len(idx), V)`` under the
    plain reference, holding the seeded canonical tree in the parameter
    type."""
    ref = importlib.import_module("chipbench.references." + name)
    w = make_canonical(key_of(seed), model, param_dtype(precision))
    hp = ref.hyper_of(model)
    return lambda tokens, idx, quant=None: ref.logits_at(
        w, tokens, idx, hp, quant)
