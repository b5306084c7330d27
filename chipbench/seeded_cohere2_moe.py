"""The ``cohere2_moe`` adapter: everything a run of that architecture draws
from ``--seed``, the program's configuration from the published keys and
the chip's share, and how its plain reference is called. The kind finds
it by the configuration's ``reference`` (``chipbench/seeded_<reference>.py``).

The benchmark makes the weights; the program and the plain reference are
each handed them. They are made in the configuration's parameter type
(bfloat16), one leaf of one layer at a time and an expert at a time, so
that no float32 draw of a 268 M element leaf is ever held: the program's
tree goes to the host leaf by leaf (``publish_from: host``), the
reference's stays on the device once the engine is gone.

**The share.** ``model`` is the configuration as run: ``num_experts`` is
the number of routed experts HELD (the chip's share), ``router_experts``
the router's width (all the experts it scores; absent: every expert is
held), ``held_experts_first`` the first held expert's global id (the
share is a run of consecutive ids), ``vocab_size`` the slice of the
vocabulary held. The reference is handed the same weights and the same
list of held experts.

Canonical layout (what the reference reads; ``layers`` is a list, one
dict a layer)::

    embed (V, d)   ln_f (d)                  the head is the embedding
    layers[i]: ln (d)
               wq (d, H*Dh)  wk wv (d, Hkv*Dh)  wo (H*Dh, d)
               router (d, E)
               we_gate we_up (Eh, d, f)   we_down (Eh, f, d)
               ws_gate ws_up (S, d, f)    ws_down (S, f, d)

The program's tree has the same leaves, ``layers`` a list too, in its own
shape in one place: the ``S`` shared experts are one gated MLP, ``ws_gate``/
``ws_up`` (d, S*f) and ``ws_down`` (S*f, d); the reference runs them one
by one and takes the mean.

Scales (the configuration's ``assumed``): every matrix is normal with std
``fan_in**-0.5``, so every pre-activation has unit scale: the router's
(sigmoid scores spread over 0.1..0.9), attention's scores, the experts'
gates. Three outputs are boosted so that no branch sinks under the
comparison's tolerance: ``wo`` by ``WO_BOOST`` (a softmax's average
shrinks what it averages), ``we_down`` by ``ROUTED_BOOST`` (a chip that
holds an eighth of the experts sees about one of a token's eight, at a
weight near 1/8; no more than 2: at 8 one expert's part was half a
layer's output, and the ties of the router, where bfloat16 picks another
8th expert than float32 at a token in a hundred, moved sound runs as far
from the reference as the fp8 control: ``PERF.md`` section 6, PR 31),
``ws_down`` by ``SHARED_BOOST`` (a mean of four
independent outputs halves their scale). The embedding has std
``d**-0.5``: tied, it gives logits of unit scale under the final norm.
Norm scales are 1 + 0.1 N(0,1).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.seeded import key_of

WO_BOOST = 3.0
ROUTED_BOOST = 2.0
SHARED_BOOST = 2.0
# The reference's programs are keyed by the padded length of a sequence:
# lengths go up to this grid, so that a cell whose prompts span 256 to
# 12,288 compiles at most seven of them, not one per sampled request.
REF_GRID = 2048


def vocab_of(model: dict) -> int:
    return int(model["vocab_size"])


def router_width(model: dict) -> int:
    return int(model.get("router_experts", model["num_experts"]))


def held_of(model: dict) -> tuple:
    first = int(model.get("held_experts_first", 0))
    return tuple(range(first, first + int(model["num_experts"])))


def param_dtype(precision: dict):
    return jnp.dtype(precision.get("parameters", "bfloat16"))


# ---------------------------------------------------------------------------
# Leaves: name -> (shape, std or None for a norm's scale)
# ---------------------------------------------------------------------------


def layer_specs(model: dict) -> dict:
    """The canonical leaves of ONE layer, in a fixed order (a leaf's
    index keys its draw)."""
    d, f = int(model["hidden_size"]), int(model["intermediate_size"])
    dh = int(model["head_dim"])
    qd = int(model["num_attention_heads"]) * dh
    kvd = int(model["num_key_value_heads"]) * dh
    eh, s = int(model["num_experts"]), int(model["num_shared_experts"])
    nat = d ** -0.5
    return {
        "ln": ((d,), None),
        "wq": ((d, qd), nat), "wk": ((d, kvd), nat), "wv": ((d, kvd), nat),
        "wo": ((qd, d), WO_BOOST * qd ** -0.5),
        "router": ((d, router_width(model)), nat),
        "we_gate": ((eh, d, f), nat), "we_up": ((eh, d, f), nat),
        "we_down": ((eh, f, d), ROUTED_BOOST * f ** -0.5),
        "ws_gate": ((s, d, f), nat), "ws_up": ((s, d, f), nat),
        "ws_down": ((s, f, d), SHARED_BOOST * f ** -0.5),
    }


def top_specs(model: dict) -> dict:
    d = int(model["hidden_size"])
    return {"embed": ((vocab_of(model), d), d ** -0.5), "ln_f": ((d,), None)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    f32 = jnp.float32
    if std is None:
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    if len(shape) < 3:
        return (jax.random.normal(key, shape, f32) * std).astype(dtype)
    # A stack of matrices (experts): one matrix's float32 draw at a time.
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], f32) * std).astype(dtype),
        jax.random.split(key, shape[0]))


def _leaves(key, specs: dict, dtype):
    """``(name, leaf on the device)`` one at a time, each from its own
    key."""
    for i, (name, (shape, std)) in enumerate(specs.items()):
        yield name, _draw(jax.random.fold_in(key, i), shape, std,
                          jnp.dtype(dtype))


def _layer_key(key, i: int):
    return jax.random.fold_in(key, 1000 + i)


def make_canonical(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The canonical tree on the device, leaf by leaf."""
    w = dict(_leaves(key, top_specs(model), dtype))
    w["layers"] = [
        dict(_leaves(_layer_key(key, i), layer_specs(model), dtype))
        for i in range(int(model["num_hidden_layers"]))
    ]
    return w


def _to_program(name: str, leaf: np.ndarray) -> np.ndarray:
    """One canonical leaf of one layer as the program holds it: the S
    shared experts' matrices side by side, everything else as it is."""
    if name in ("ws_gate", "ws_up"):
        s, d, f = leaf.shape
        return np.ascontiguousarray(leaf.transpose(1, 0, 2)).reshape(d, s * f)
    if name == "ws_down":
        return leaf.reshape(-1, leaf.shape[2])
    return leaf


def to_program_tree(w: dict) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.cohere2_moe`` (host
    arrays; the CPU tests' sizes)."""
    out = {name: np.asarray(leaf) for name, leaf in w.items()
           if name != "layers"}
    out["layers"] = [{name: _to_program(name, np.asarray(leaf))
                      for name, leaf in lay.items()} for lay in w["layers"]]
    return out


def make_program_tree_host(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The tree of ``rayfed_tpu.models.cohere2_moe`` as host arrays: each
    leaf is drawn on the device, brought to the host and dropped before
    the next is drawn, so the device never holds more than the largest
    leaf."""
    out = {name: jax.device_get(leaf)
           for name, leaf in _leaves(key, top_specs(model), dtype)}
    out["layers"] = [
        {name: _to_program(name, jax.device_get(leaf))
         for name, leaf in _leaves(
             _layer_key(key, i), layer_specs(model), dtype)}
        for i in range(int(model["num_hidden_layers"]))
    ]
    return out


# ---------------------------------------------------------------------------
# The program and the reference
# ---------------------------------------------------------------------------


def program_cfg(model: dict, precision: dict):
    from rayfed_tpu.models import cohere2_moe

    return cohere2_moe.Cohere2MoeConfig.from_published(
        dict(model, num_experts=router_width(model)),
        held=held_of(model),
        compute_dtype=jnp.dtype(precision.get("compute", "bfloat16")),
        param_dtype=param_dtype(precision),
    )


def program_params_host(seed: int, model: dict, precision: dict) -> dict:
    return make_program_tree_host(key_of(seed), model, param_dtype(precision))


def reference_logits_fn(seed: int, model: dict, precision: dict,
                        name: str = "cohere2_moe"):
    """``f(tokens, idx, quant=None) -> logits (len(idx), V)`` under the
    plain reference, holding the seeded canonical tree in the parameter
    type and told the same held experts as the program."""
    ref = importlib.import_module("chipbench.references." + name)
    w = make_canonical(key_of(seed), model, param_dtype(precision))
    hp = ref.hyper_of(model, held_of(model))

    def logits_at(tokens, idx, quant=None):
        tokens = jnp.pad(tokens, (0, -tokens.shape[0] % REF_GRID))
        return ref.logits_at(w, tokens, idx, hp, quant)

    return logits_at
