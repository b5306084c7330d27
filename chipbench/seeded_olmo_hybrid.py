"""The ``olmo_hybrid`` adapter: everything a run of that architecture
draws from ``--seed``, the program's configuration from the published
keys, and how its plain reference is called. ``kinds/closed_loop_arch.py``
finds it by the configuration's ``reference``.

The benchmark makes the weights; the program and the plain reference are
each handed them, in the configuration's parameter type (bfloat16), one
leaf at a time and a block of a leaf at a time (``seeded_falcon_h1``'s
draw: neither the float32 draw of a large leaf nor a second copy of the
tree is ever held). The program's tree goes to the host leaf by leaf
(``publish_from: host``), the reference's stays on the device once the
engine is gone.

Canonical layout (what the reference reads; the program reads the same:
``Ll`` linear layers, ``Lf`` full ones, each kind's leaves stacked over
the layers of the kind in the stack's order)::

    embed (V, d)   ln_f (d)   lm_head (d, V)
    linear: w_qkv (Ll, d, 2*H*dk + H*dv)       zones q | k | v
            conv_w (Ll, K, 2*H*dk + H*dv)
            w_ab (Ll, d, 2*H)                  zones a (decay) | b (beta)
            A_log dt_bias (Ll, H)
            w_g (Ll, d, H*dv)  o_norm (Ll, dv)  w_o (Ll, H*dv, d)
            norm_mixer norm_mlp (Ll, d)
            w_gate w_up (Ll, d, f)   w_down (Ll, f, d)
    full:   wq (Lf, d, Hq*Dh)  wk wv (Lf, d, Hkv*Dh)  wo (Lf, Hq*Dh, d)
            q_norm (Lf, Hq*Dh)  k_norm (Lf, Hkv*Dh)
            norm_mixer norm_mlp (Lf, d)
            w_gate w_up (Lf, d, f)   w_down (Lf, f, d)

Scales (the configuration's ``assumed``): every matrix is normal with std
``fan_in**-0.5``, the embedding std 1, norm scales 1 + 0.1 N(0,1),
convolution weights std ``K**-0.5``. The residual stream is not normed
before a mixer reads it (the family's norms sit on the parts' outputs),
so its scale grows with depth (rms about ``sqrt(1 + 2 layers)``); q^ and
k^ are L2-normed and ``o`` is RMS-normed, but the decay's and beta's
projections see that scale, so their zones of ``w_ab`` are drawn
narrower: the decay's at 0.1 and beta's at 0.5 of ``d**-0.5``. The
decay: ``A_log = log U(1, 4)`` and ``dt_bias`` the inverse softplus of
``dt`` log-uniform in [1e-3, 0.025], so that ``alpha = exp(-A dt)``
spans about 0.9-0.999 where ``x Wa`` is 0 (a state neither dies in a
step nor never decays) and a head's memory is 10 to 1,000 positions;
``beta = 2 sigmoid(x Wb)`` falls on both sides of 1, so the
negative-eigenvalue branch (``1 - beta < 0``) is exercised.
"""

from __future__ import annotations

import importlib
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded_falcon_h1 as draw
from chipbench.seeded import key_of

LINEAR, FULL = "linear_attention", "full_attention"
# Of ``d**-0.5``: the zones a (decay) and b (beta) of ``w_ab``.
DECAY_ZONE, BETA_ZONE = 0.1, 0.5
A_RANGE = (1.0, 4.0)
DT_RANGE = (1e-3, 0.025)


class Dims(NamedTuple):
    vocab: int
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    n_linear: int
    n_full: int
    lin_heads: int
    dk: int
    dv: int
    conv: int

    @property
    def conv_dim(self):
        return self.lin_heads * (2 * self.dk + self.dv)


def dims_of(model: dict) -> Dims:
    kinds = list(model["layer_types"][:int(model["num_hidden_layers"])])
    return Dims(
        vocab=int(model["vocab_size"]), d=int(model["hidden_size"]),
        f=int(model["intermediate_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model.get("head_dim") or model["hidden_size"]
                     // model["num_attention_heads"]),
        n_linear=kinds.count(LINEAR), n_full=kinds.count(FULL),
        lin_heads=int(model["linear_num_key_heads"]),
        dk=int(model["linear_key_head_dim"]),
        dv=int(model["linear_value_head_dim"]),
        conv=int(model["linear_conv_kernel_dim"]),
    )


def vocab_of(model: dict) -> int:
    return int(model["vocab_size"])


param_dtype = draw.param_dtype


def leaf_specs(model: dict) -> dict:
    """Every canonical leaf, in a fixed order (a leaf's index keys its
    draw): ``name -> (shape, kind, scale)``, the kinds of
    ``seeded_falcon_h1._draw`` and two of this file's."""
    x = dims_of(model)
    d, f, nl, nf = x.d, x.f, x.n_linear, x.n_full
    qd, kvd = x.heads * x.head_dim, x.kv_heads * x.head_dim
    hv = x.lin_heads * x.dv
    nat = d ** -0.5
    ab = np.concatenate([np.full(x.lin_heads, DECAY_ZONE * nat, np.float32),
                         np.full(x.lin_heads, BETA_ZONE * nat, np.float32)])
    specs = {
        "embed": ((x.vocab, d), "normal", 1.0),
        "ln_f": ((d,), "norm", None),
        "lm_head": ((d, x.vocab), "normal", nat),
        "linear.w_qkv": ((nl, d, x.conv_dim), "normal", nat),
        "linear.conv_w": ((nl, x.conv, x.conv_dim), "normal",
                          x.conv ** -0.5),
        "linear.w_ab": ((nl, d, 2 * x.lin_heads), "normal", ab),
        "linear.A_log": ((nl, x.lin_heads), "delta_a_log", None),
        "linear.dt_bias": ((nl, x.lin_heads), "delta_dt_bias", None),
        "linear.w_g": ((nl, d, hv), "normal", nat),
        "linear.o_norm": ((nl, x.dv), "norm", None),
        "linear.w_o": ((nl, hv, d), "normal", hv ** -0.5),
        "full.wq": ((nf, d, qd), "normal", nat),
        "full.wk": ((nf, d, kvd), "normal", nat),
        "full.wv": ((nf, d, kvd), "normal", nat),
        "full.wo": ((nf, qd, d), "normal", qd ** -0.5),
        "full.q_norm": ((nf, qd), "norm", None),
        "full.k_norm": ((nf, kvd), "norm", None),
    }
    for kind, n in (("linear", nl), ("full", nf)):
        specs.update({
            f"{kind}.norm_mixer": ((n, d), "norm", None),
            f"{kind}.norm_mlp": ((n, d), "norm", None),
            f"{kind}.w_gate": ((n, d, f), "normal", nat),
            f"{kind}.w_up": ((n, d, f), "normal", nat),
            f"{kind}.w_down": ((n, f, d), "normal", f ** -0.5),
        })
    return specs


def _draw_decay(key, shape, kind, dtype):
    f32 = jnp.float32
    if kind == "delta_a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, *A_RANGE)).astype(
            dtype)
    dt = jnp.exp(jax.random.uniform(
        key, shape, f32, math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def make_leaf(key, model: dict, name: str, dtype):
    """One canonical leaf on the device, from its own key."""
    specs = leaf_specs(model)
    shape, kind, scale = specs[name]
    key = jax.random.fold_in(key, list(specs).index(name))
    if kind.startswith("delta_"):
        return _draw_decay(key, shape, kind, jnp.dtype(dtype))
    return draw._draw_leaf(
        key, np.float32(0.0) if scale is None else np.asarray(
            scale, np.float32), shape, kind, jnp.dtype(dtype))


def _nest(flat: dict) -> dict:
    out = {"linear": {}, "full": {}}
    for name, leaf in flat.items():
        group, _, rest = name.partition(".")
        if rest:
            out[group][rest] = leaf
        else:
            out[name] = leaf
    return out


def make_canonical(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The canonical tree on the device, leaf by leaf."""
    return _nest({name: make_leaf(key, model, name, dtype)
                  for name in leaf_specs(model)})


def to_program_tree(w: dict, model: dict) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.olmo_hybrid``: the
    same tree."""
    del model
    return w


def make_program_tree_host(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The program's tree as host arrays: each leaf is drawn on the
    device, brought to the host and dropped before the next is drawn."""
    return _nest({name: jax.device_get(make_leaf(key, model, name, dtype))
                  for name in leaf_specs(model)})


# ---------------------------------------------------------------------------
# The program and the reference
# ---------------------------------------------------------------------------


def program_cfg(model: dict, precision: dict):
    from rayfed_tpu.models import olmo_hybrid

    cfg = olmo_hybrid.OlmoHybridConfig.from_published(
        model,
        compute_dtype=jnp.dtype(precision.get("compute", "bfloat16")),
        param_dtype=param_dtype(precision),
    )
    # The configuration states the type ``S`` is kept in, and the logit
    # comparison cannot see it (its ``limits.calibrated``): what a run
    # can hold is the type the model declares to the pool.
    held = jnp.dtype(olmo_hybrid.serving_model(cfg).state_spec()["delta"][2])
    if held != jnp.dtype(precision["delta_state"]):
        raise SystemExit(
            f"olmo_hybrid: the program keeps S in {held}, the configuration "
            f"states precision.delta_state {precision['delta_state']}")
    return cfg


def program_params_host(seed: int, model: dict, precision: dict) -> dict:
    return make_program_tree_host(key_of(seed), model, param_dtype(precision))


def reference_logits_fn(seed: int, model: dict, precision: dict,
                        name: str = "olmo_hybrid"):
    """``f(tokens, idx, quant=None) -> logits (len(idx), V)`` under the
    plain reference, holding the seeded canonical tree in the parameter
    type."""
    ref = importlib.import_module("chipbench.references." + name)
    w = make_canonical(key_of(seed), model, param_dtype(precision))
    hp = ref.hyper_of(model)
    return lambda tokens, idx, quant=None: ref.logits_at(
        w, tokens, idx, hp, quant)
