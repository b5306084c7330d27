"""The ``pangu_ultra_moe`` adapter: everything a run of that architecture
draws from ``--seed``, the program's configuration from the published keys
and the chip's share, and how its plain reference is called. The kind finds
it by the configuration's ``reference`` (``chipbench/seeded_<reference>.py``).

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
held, ``first_k_dense_replace`` the leading dense layers held. The
reference is handed the same weights and the same list of held experts.

Canonical layout (what the reference reads; ``layers`` is a list, one dict
a layer; the first ``first_k_dense_replace`` hold the dense leaves, the
others the expert leaves)::

    embed (V, d)   ln_f (d)   lm_head (V, d)
    layers[i]: ln1 ln2 ln3 ln4 (d)
               wq_a (d, rq)  q_norm (rq)  wq_b (rq, H*(dn+dr))
               wkv_a (d, rkv+dr)  kv_norm (rkv)  wkv_b (rkv, H*(dn+dv))
               wo (H*dv, d)
       dense:  w_gate w_up (d, fd)   w_down (fd, d)
       expert: router (d, E)
               we_gate we_up (Eh, d, f)   we_down (Eh, f, d)
               ws_gate ws_up (S, d, f)    ws_down (S, f, d)

The program's tree has the same leaves in its own shape in two places:
``wkv_b`` (a head's ``[kn | v]`` columns side by side, as published) is
two matrices, ``wk_b`` (rkv, H*dn) and ``wv_b`` (rkv, H*dv), so that
neither the absorbed nor the expanded read slices a weight; the ``S``
shared experts are one gated MLP, ``ws_gate``/``ws_up`` (d, S*f) and
``ws_down`` (S*f, d).

Scales (the configuration's ``assumed``): every matrix is normal with std
``fan_in**-0.5``, so every pre-activation has unit scale (the router's
sigmoid scores spread over 0.1..0.9, attention's scores, the gates). The
sandwich norms put every sub-block's output at unit scale whatever its
matrices' scales are, so nothing is boosted; what matters is the mix
INSIDE the expert layer's output: the shared expert at scale 1 beside one
routed expert at ``2.5 * sigma / sum_8 sigma``, about 0.3 (this chip sees
about half an expert of a token's eight). A router tie, where bfloat16
takes another 8th expert than float32 at a token-layer in a hundred, then
moves a third of one of the eleven unit-scale terms of the residual
stream, not half a layer (``PERF.md`` section 6, PR 31's lesson: there a
boost of 8 on the routed down projection let no limit part bfloat16 from
fp8). ``ROUTED_DOWN`` is that scale, kept at 1. The embedding has std 1
(the token stays visible beside ten unit-scale additions), the head
``d**-0.5`` (logits of unit scale under the final norm). Norm scales are
1 + 0.1 N(0,1).
"""

from __future__ import annotations

import functools
import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.seeded import key_of

ROUTED_DOWN = 1.0
# The reference's programs are keyed by the padded length of a sequence:
# lengths go up to this grid, so that a cell whose sequences span 400 to
# 11,264 compiles at most six of them, not one per sampled request.
REF_GRID = 2048


def vocab_of(model: dict) -> int:
    return int(model["vocab_size"])


def router_width(model: dict) -> int:
    return int(model.get("router_experts", model["n_routed_experts"]))


def held_of(model: dict) -> tuple:
    first = int(model.get("held_experts_first", 0))
    return tuple(range(first, first + int(model["n_routed_experts"])))


def param_dtype(precision: dict):
    return jnp.dtype(precision.get("parameters", "bfloat16"))


# ---------------------------------------------------------------------------
# Leaves: name -> (shape, std or None for a norm's scale)
# ---------------------------------------------------------------------------


def layer_specs(model: dict, dense: bool) -> dict:
    """The canonical leaves of ONE layer, in a fixed order (a leaf's
    index keys its draw)."""
    d = int(model["hidden_size"])
    h = int(model["num_attention_heads"])
    rq, rkv = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv = int(model["v_head_dim"])
    nat = d ** -0.5
    specs = {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "ln3": ((d,), None), "ln4": ((d,), None),
        "wq_a": ((d, rq), nat), "q_norm": ((rq,), None),
        "wq_b": ((rq, h * (dn + dr)), rq ** -0.5),
        "wkv_a": ((d, rkv + dr), nat), "kv_norm": ((rkv,), None),
        "wkv_b": ((rkv, h * (dn + dv)), rkv ** -0.5),
        "wo": ((h * dv, d), (h * dv) ** -0.5),
    }
    if dense:
        fd = int(model["intermediate_size"])
        specs.update({
            "w_gate": ((d, fd), nat), "w_up": ((d, fd), nat),
            "w_down": ((fd, d), fd ** -0.5),
        })
    else:
        f = int(model["moe_intermediate_size"])
        eh, s = int(model["n_routed_experts"]), int(model["n_shared_experts"])
        specs.update({
            "router": ((d, router_width(model)), nat),
            "we_gate": ((eh, d, f), nat), "we_up": ((eh, d, f), nat),
            "we_down": ((eh, f, d), ROUTED_DOWN * f ** -0.5),
            "ws_gate": ((s, d, f), nat), "ws_up": ((s, d, f), nat),
            "ws_down": ((s, f, d), f ** -0.5),
        })
    return specs


def top_specs(model: dict) -> dict:
    d, v = int(model["hidden_size"]), vocab_of(model)
    return {"embed": ((v, d), 1.0), "ln_f": ((d,), None),
            "lm_head": ((v, d), d ** -0.5)}


def is_dense(model: dict, i: int) -> bool:
    return i < int(model["first_k_dense_replace"])


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


def _layer_leaves(key, model: dict, i: int, dtype):
    return _leaves(jax.random.fold_in(key, 1000 + i),
                   layer_specs(model, is_dense(model, i)), dtype)


def make_canonical(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The canonical tree on the device, leaf by leaf."""
    w = dict(_leaves(key, top_specs(model), dtype))
    w["layers"] = [dict(_layer_leaves(key, model, i, dtype))
                   for i in range(int(model["num_hidden_layers"]))]
    return w


def _to_program(name: str, leaf: np.ndarray, model: dict) -> dict:
    """One canonical leaf of one layer as the program holds it."""
    if name == "wkv_b":
        dn, dv = int(model["qk_nope_head_dim"]), int(model["v_head_dim"])
        rkv = leaf.shape[0]
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


def _program_layer(leaves, model: dict) -> dict:
    out = {}
    for name, leaf in leaves:
        out.update(_to_program(name, jax.device_get(leaf), model))
    return out


def to_program_tree(w: dict, model: dict) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.pangu_ultra_moe``
    (host arrays; the CPU tests' sizes)."""
    out = {name: np.asarray(leaf) for name, leaf in w.items()
           if name != "layers"}
    out["layers"] = [_program_layer(lay.items(), model)
                     for lay in w["layers"]]
    return out


def make_program_tree_host(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The tree of ``rayfed_tpu.models.pangu_ultra_moe`` as host arrays:
    each leaf is drawn on the device, brought to the host and dropped
    before the next is drawn, so the device never holds more than the
    largest leaf."""
    out = {name: jax.device_get(leaf)
           for name, leaf in _leaves(key, top_specs(model), dtype)}
    out["layers"] = [
        _program_layer(_layer_leaves(key, model, i, dtype), model)
        for i in range(int(model["num_hidden_layers"]))
    ]
    return out


# ---------------------------------------------------------------------------
# The program and the reference
# ---------------------------------------------------------------------------


def program_cfg(model: dict, precision: dict):
    from rayfed_tpu.models import pangu_ultra_moe

    return pangu_ultra_moe.PanguUltraMoeConfig.from_published(
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
        layer_specs(model, is_dense(model, i))
        for i in range(int(model["num_hidden_layers"]))]
    return jnp.dtype(dtype).itemsize * sum(
        int(np.prod(shape)) for spec in specs for shape, _ in spec.values())


def wait_for_room(need_bytes: int, timeout_s: float = 300.0) -> float:
    """Wait until the device has ``need_bytes`` free; returns the seconds
    waited. An engine that is told to stop finishes what it admitted
    first, and the harness gives the window's in-flight requests 40 s and
    the stop 30 s: an answer of 3,072 tokens that began as the window
    closed runs two minutes, and until it ends the engine holds its
    weights and its pool (13.3 GB of the chip's 15.75; the reference's
    tree beside them ran out of memory in one run of the first six). A
    backend that reports no memory (the CPU) is not waited for."""
    device = jax.devices()[0]
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        gc.collect()
        stats = device.memory_stats() or {}
        if ("bytes_limit" not in stats or stats["bytes_limit"]
                - stats.get("bytes_in_use", 0) >= need_bytes):
            break
        time.sleep(1.0)
    return time.monotonic() - t0


# Beside its tree the reference holds one sequence's activations and a
# widened matrix at a time.
REFERENCE_ROOM = 3 << 30


def reference_logits_fn(seed: int, model: dict, precision: dict,
                        name: str = "pangu_ultra_moe"):
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
