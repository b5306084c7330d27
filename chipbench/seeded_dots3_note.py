"""The ``dots3_note`` adapter: everything a run of that architecture draws
from ``--seed``, the program's configuration from the published keys and
the chip's share, and how its plain reference is called. The kind finds it
by the configuration's ``reference`` (``chipbench/seeded_<reference>.py``).

The benchmark makes the weights; the program and the plain reference are
each handed them. They are made in the configuration's parameter type
(bfloat16), one leaf of one layer at a time and an expert at a time, so
that no float32 draw of a large leaf is ever held: the program's tree goes
to the host leaf by leaf (``publish_from: host``), the reference's stays
on the device once the engine is gone.

**The share.** ``model`` is the configuration as run: ``n_routed_experts``
is the number of routed experts HELD (the chip's share), ``router_experts``
the router's width (all the experts it scores; absent: every expert is
held), ``held_experts_first`` the first held expert's global id (the share
is a run of consecutive ids), ``vocab_size`` the slice of the vocabulary
held, ``num_hidden_layers`` the layers held and ``layer_types`` their
kinds. The reference is handed the same weights and the same list of held
experts.

Canonical layout (what the reference reads; ``layers`` is a list, one dict
a layer, its sizes by the layer's kind; the first ``first_k_dense_replace``
hold the dense leaves, the others the expert leaves; a full layer holds
the indexer's five)::

    embed (V, d)   ln_f (d)   lm_head (V, d)
    layers[i]: ln1 ln2 (d)
               wq_a (d, rq)  q_norm (rq)  wq_b (rq, H*(dn+dr))
               wkv_a (d, rkv+dr)  kv_norm (rkv)  wkv_b (rkv, H*(dn+dv))
               wo (H*dv, d)   w_og (d, H)
       full:   wi_q (rq, J*D)  wi_k (d, D)  wi_w (d, J)  i_norm i_bias (D)
       dense:  w_gate w_up (d, fd)   w_down (fd, d)
       expert: router (d, E)   router_bias (E)
               we_gate we_up (Eh, d, f)   we_down (Eh, f, d)
               ws_gate ws_up (S, d, f)    ws_down (S, f, d)

The program's tree has the same leaves in its own shape in two places:
``wkv_b`` is two matrices, ``wk_b`` (rkv, H*dn) and ``wv_b`` (rkv, H*dv);
the ``S`` shared experts are one gated MLP.

Scales (the configuration's ``assumed.weights``): every matrix is normal
with std ``fan_in**-0.5`` (unit pre-activations: the router's sigmoid
scores, the gates, the indexer's products) but ONE a layer: ``wq_b`` is
``fan_in**-0.5`` times the factor that gives attention's scores a standard
deviation of ``SCORE_STD`` under the rescaled latents (natural weights
give 5.9 on a full layer and 4.5 on a sliding one once ``sqrt(d / rank)``
multiplies both latents; at 1, the std of unscaled random weights, a
softmax over 2,048 random keys is a mean, and swapping the selected set
would move the logits by less than bfloat16 does). Nothing else is
boosted. The selection bias is N(0, ``BIAS_STD``): it changes some of a
token's eight choices and weighs nothing. The indexer's LayerNorm has a
scale of 1 + 0.1 N(0,1) and a bias of 0.1 N(0,1). The embedding has std 1,
the head ``d**-0.5``; norm scales are 1 + 0.1 N(0,1).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops_dots3_note import FULL, sizes_of
from chipbench.seeded import key_of
from chipbench.seeded_pangu_ultra_moe import (
    held_of, param_dtype, router_width, vocab_of, wait_for_room)

# The configuration's ``seeded`` group states both (defaults for a model
# dict without one: the CPU tests').
SCORE_STD = 3.0
BIAS_STD = 0.02
# The reference's programs are keyed by the padded length of a sequence:
# lengths go up to this grid (2,400 to 33,024: at most nine programs).
REF_GRID = 4096
# Beside its tree the reference holds one sequence's activations, a full
# layer's (queries, keys) sets and a widened matrix at a time.
REFERENCE_ROOM = 5 << 30


# ---------------------------------------------------------------------------
# Leaves: name -> (shape, std, mean)
# ---------------------------------------------------------------------------


def seeded_scale(model: dict, key: str, default: float) -> float:
    """A scale the configuration's ``seeded`` group states, or the
    default."""
    return float(model.get("seeded", {}).get(key, default))


def query_factor(model: dict, kind: str) -> float:
    """What multiplies ``wq_b``'s natural std so that attention's scores
    have a standard deviation of ``SCORE_STD``: under natural weights a
    query dimension has the variance of ``s_q``'s square, a key's
    content dimension ``s_kv``'s and its positional one 1."""
    _, rq, rkv, dn, dr, _ = sizes_of(model, kind)
    d = int(model["hidden_size"])
    s_q2, s_kv2 = 1.0, 1.0
    if model.get("apply_mla_qkv_lora_rescale"):
        s_q2, s_kv2 = d / rq, d / rkv
    natural = (s_q2 * (dn * s_kv2 + dr) / (dn + dr)) ** 0.5
    return seeded_scale(model, "score_std", SCORE_STD) / natural


def layer_specs(model: dict, i: int) -> dict:
    """The canonical leaves of layer ``i``, in a fixed order (a leaf's
    index keys its draw)."""
    kind = model["layer_types"][i]
    d = int(model["hidden_size"])
    h, rq, rkv, dn, dr, dv = sizes_of(model, kind)
    nat = d ** -0.5
    norm = lambda n: ((n,), 0.1, 1.0)  # noqa: E731
    specs = {
        "ln1": norm(d), "ln2": norm(d),
        "wq_a": ((d, rq), nat, 0.0), "q_norm": norm(rq),
        "wq_b": ((rq, h * (dn + dr)),
                 query_factor(model, kind) * rq ** -0.5, 0.0),
        "wkv_a": ((d, rkv + dr), nat, 0.0), "kv_norm": norm(rkv),
        "wkv_b": ((rkv, h * (dn + dv)), rkv ** -0.5, 0.0),
        "wo": ((h * dv, d), (h * dv) ** -0.5, 0.0),
        "w_og": ((d, h), nat, 0.0),
    }
    if kind == FULL:
        j, di = int(model["index_n_heads"]), int(model["index_head_dim"])
        specs.update({
            "wi_q": ((rq, j * di), rq ** -0.5, 0.0),
            "wi_k": ((d, di), nat, 0.0), "wi_w": ((d, j), nat, 0.0),
            "i_norm": norm(di), "i_bias": ((di,), 0.1, 0.0),
        })
    if is_dense(model, i):
        fd = int(model["intermediate_size"])
        specs.update({
            "w_gate": ((d, fd), nat, 0.0), "w_up": ((d, fd), nat, 0.0),
            "w_down": ((fd, d), fd ** -0.5, 0.0),
        })
    else:
        f = int(model["moe_intermediate_size"])
        eh, s = int(model["n_routed_experts"]), int(model["n_shared_experts"])
        e = router_width(model)
        specs.update({
            "router": ((d, e), nat, 0.0),
            "router_bias": (
                (e,), seeded_scale(model, "bias_std", BIAS_STD), 0.0),
            "we_gate": ((eh, d, f), nat, 0.0),
            "we_up": ((eh, d, f), nat, 0.0),
            "we_down": ((eh, f, d), f ** -0.5, 0.0),
            "ws_gate": ((s, d, f), nat, 0.0), "ws_up": ((s, d, f), nat, 0.0),
            "ws_down": ((s, f, d), f ** -0.5, 0.0),
        })
    return specs


def top_specs(model: dict) -> dict:
    d, v = int(model["hidden_size"]), vocab_of(model)
    return {"embed": ((v, d), 1.0, 0.0), "ln_f": ((d,), 0.1, 1.0),
            "lm_head": ((v, d), d ** -0.5, 0.0)}


def is_dense(model: dict, i: int) -> bool:
    return i < int(model["first_k_dense_replace"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, mean, dtype):
    f32 = jnp.float32
    if len(shape) < 3:
        return (mean + jax.random.normal(key, shape, f32) * std).astype(dtype)
    # A stack of matrices (experts): one matrix's float32 draw at a time.
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], f32) * std).astype(dtype),
        jax.random.split(key, shape[0]))


def _leaves(key, specs: dict, dtype):
    """``(name, leaf on the device)`` one at a time, each from its own
    key."""
    for i, (name, (shape, std, mean)) in enumerate(specs.items()):
        yield name, _draw(jax.random.fold_in(key, i), shape, float(std),
                          float(mean), jnp.dtype(dtype))


def _layer_leaves(key, model: dict, i: int, dtype):
    return _leaves(jax.random.fold_in(key, 1000 + i),
                   layer_specs(model, i), dtype)


def make_canonical(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The canonical tree on the device, leaf by leaf."""
    w = dict(_leaves(key, top_specs(model), dtype))
    w["layers"] = [dict(_layer_leaves(key, model, i, dtype))
                   for i in range(int(model["num_hidden_layers"]))]
    return w


def _to_program(name: str, leaf: np.ndarray, model: dict, i: int) -> dict:
    """One canonical leaf of layer ``i`` as the program holds it."""
    if name == "wkv_b":
        _, _, rkv, dn, _, dv = sizes_of(model, model["layer_types"][i])
        heads = leaf.reshape(rkv, -1, dn + dv)
        return {
            "wk_b": np.ascontiguousarray(heads[:, :, :dn]).reshape(rkv, -1),
            "wv_b": np.ascontiguousarray(heads[:, :, dn:]).reshape(rkv, -1),
        }
    if name in ("ws_gate", "ws_up"):
        s, d, f = leaf.shape
        leaf = np.ascontiguousarray(leaf.transpose(1, 0, 2)).reshape(d, s * f)
    elif name == "ws_down":
        leaf = leaf.reshape(-1, leaf.shape[2])
    return {name: leaf}


def _program_layer(leaves, model: dict, i: int) -> dict:
    out = {}
    for name, leaf in leaves:
        out.update(_to_program(name, jax.device_get(leaf), model, i))
    return out


def to_program_tree(w: dict, model: dict) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.dots3_note`` (host
    arrays; the CPU tests' sizes)."""
    out = {name: np.asarray(leaf) for name, leaf in w.items()
           if name != "layers"}
    out["layers"] = [_program_layer(lay.items(), model, i)
                     for i, lay in enumerate(w["layers"])]
    return out


def make_program_tree_host(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The tree of ``rayfed_tpu.models.dots3_note`` as host arrays: each
    leaf is drawn on the device, brought to the host and dropped before
    the next is drawn, so the device never holds more than the largest
    leaf."""
    out = {name: jax.device_get(leaf)
           for name, leaf in _leaves(key, top_specs(model), dtype)}
    out["layers"] = [
        _program_layer(_layer_leaves(key, model, i, dtype), model, i)
        for i in range(int(model["num_hidden_layers"]))
    ]
    return out


# ---------------------------------------------------------------------------
# The program and the reference
# ---------------------------------------------------------------------------


def program_cfg(model: dict, precision: dict):
    from rayfed_tpu.models import dots3_note

    return dots3_note.Dots3NoteConfig.from_published(
        dict(model, n_routed_experts=router_width(model)),
        held=held_of(model),
        compute_dtype=jnp.dtype(precision.get("compute", "bfloat16")),
        param_dtype=param_dtype(precision),
    )


def program_params_host(seed: int, model: dict, precision: dict) -> dict:
    return make_program_tree_host(key_of(seed), model, param_dtype(precision))


def tree_bytes(model: dict, dtype) -> int:
    """Bytes of the seeded tree as held here."""
    specs = [top_specs(model)] + [
        layer_specs(model, i)
        for i in range(int(model["num_hidden_layers"]))]
    return jnp.dtype(dtype).itemsize * sum(
        int(np.prod(shape)) for spec in specs
        for shape, _, _ in spec.values())


def reference_logits_fn(seed: int, model: dict, precision: dict,
                        name: str = "dots3_note"):
    """``f(tokens, idx, quant=None) -> logits (len(idx), V)`` under the
    plain reference, holding the seeded canonical tree in the parameter
    type and told the same held experts as the program."""
    ref = importlib.import_module("chipbench.references." + name)
    dtype = param_dtype(precision)
    wait_for_room(tree_bytes(model, dtype) + REFERENCE_ROOM)
    w = make_canonical(key_of(seed), model, dtype)
    hp = ref.hyper_of(model, held_of(model))

    def logits_at(tokens, idx, quant=None):
        tokens = jnp.pad(tokens, (0, -tokens.shape[0] % REF_GRID))
        return ref.logits_at(w, tokens, idx, hp, quant)

    return logits_at
