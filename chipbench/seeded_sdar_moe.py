"""The ``sdar_moe`` adapter: everything a run of that architecture draws
from ``--seed``, the program's configuration from the published keys and
the generation routine's numbers, and how its plain reference is called.
The kind finds it by the configuration's ``reference``
(``chipbench/seeded_<reference>.py``).

The benchmark makes the weights; the program and the plain reference are
each handed them. They are made in the configuration's parameter type
(bfloat16), one leaf of one layer at a time and an expert at a time
(``seeded_cohere2_moe``'s way, by import: its draws and its keys), so
that no float32 draw of a 201 M element leaf is ever held: the program's
tree goes to the host leaf by leaf (``publish_from: host``), the
reference's stays on the device once the engine is gone. Every expert is
held (``num_experts`` is the router's width and the count held).

Canonical layout, which is also the program's (``layers`` a list, one
dict a layer)::

    embed (V, d)   ln_f (d)   lm_head (V, d)
    layers[i]: ln1 ln2 (d)   q_norm k_norm (Dh)
               wq (d, H*Dh)  wk wv (d, Hkv*Dh)  wo (H*Dh, d)
               router (d, E)
               we_gate we_up (E, d, f)   we_down (E, f, d)

Scales (the configuration's ``assumed``): every matrix is normal with std
``fan_in**-0.5``, so every pre-activation has unit scale: the router's
(softmax over 128 unit-scale scores: a token's eight weights spread over
0.07..0.25), attention's scores (queries and keys are normed per head,
scales near 1), the experts' gates. ``wo`` is boosted by ``WO_BOOST`` (a
softmax's average shrinks what it averages). The routed experts' down
projection by ``ROUTED_BOOST`` = 2: a token's eight experts, all held
here, are a weighted mean of eight independent outputs (about 0.4 of one
output's scale), and there is no shared expert beside them, so at 1 the
expert branch is a quarter of attention's and a wrong routing hides under
the comparison's tolerance; at 2 the whole branch is half of attention's
while ONE expert's part (what a tie of the router at the eighth place
moves, PR 31's lesson) stays under a tenth of a layer's output. The
embedding and the head have std ``d**-0.5`` (logits of unit scale under
the final norm). Norm scales, the query/key norms' among them, are 1 +
0.1 N(0,1).

The traffic draws its ids below the mask id (``vocab_of``): the ids from
the mask id up are the tokenizer's special tokens, which no prompt holds,
and a prompt that held the mask id would ask the model to fill it in.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.seeded import key_of
from chipbench.seeded_cohere2_moe import _layer_key, _leaves, param_dtype

WO_BOOST = 3.0
ROUTED_BOOST = 2.0
# The reference's programs are keyed by the padded length of a sequence:
# a context and its block go up to this grid.
REF_GRID = 256
# What the routine's keys default to where a model dict lacks them.
ROUTINE = {"block_length": 4, "denoising_steps": 4,
           "remasking": "low_confidence_dynamic",
           "confidence_threshold": 0.9, "mask_token_id": 151669}


def routine_of(model: dict) -> dict:
    return {k: model.get(k, v) for k, v in ROUTINE.items()}


def vocab_of(model: dict) -> int:
    """The ids the traffic draws from: those below the mask id."""
    return int(routine_of(model)["mask_token_id"])


def layer_specs(model: dict) -> dict:
    """The leaves of ONE layer, in a fixed order (a leaf's index keys its
    draw)."""
    d, f = int(model["hidden_size"]), int(model["moe_intermediate_size"])
    dh = int(model["head_dim"])
    qd = int(model["num_attention_heads"]) * dh
    kvd = int(model["num_key_value_heads"]) * dh
    e = int(model["num_experts"])
    nat = d ** -0.5
    return {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "q_norm": ((dh,), None), "k_norm": ((dh,), None),
        "wq": ((d, qd), nat), "wk": ((d, kvd), nat), "wv": ((d, kvd), nat),
        "wo": ((qd, d), WO_BOOST * qd ** -0.5),
        "router": ((d, e), nat),
        "we_gate": ((e, d, f), nat), "we_up": ((e, d, f), nat),
        "we_down": ((e, f, d), ROUTED_BOOST * f ** -0.5),
    }


def top_specs(model: dict) -> dict:
    v, d = int(model["vocab_size"]), int(model["hidden_size"])
    return {"embed": ((v, d), d ** -0.5), "ln_f": ((d,), None),
            "lm_head": ((v, d), d ** -0.5)}


def make_canonical(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The canonical tree on the device, leaf by leaf."""
    w = dict(_leaves(key, top_specs(model), dtype))
    w["layers"] = [
        dict(_leaves(_layer_key(key, i), layer_specs(model), dtype))
        for i in range(int(model["num_hidden_layers"]))
    ]
    return w


def to_program_tree(w: dict) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.sdar_moe`` (host
    arrays): the same leaves."""
    out = {name: np.asarray(leaf) for name, leaf in w.items()
           if name != "layers"}
    out["layers"] = [{name: np.asarray(leaf) for name, leaf in lay.items()}
                     for lay in w["layers"]]
    return out


def make_program_tree_host(key, model: dict, dtype=jnp.bfloat16) -> dict:
    """The program's tree as host arrays: each leaf is drawn on the
    device, brought to the host and dropped before the next is drawn."""
    out = {name: jax.device_get(leaf)
           for name, leaf in _leaves(key, top_specs(model), dtype)}
    out["layers"] = [
        {name: jax.device_get(leaf) for name, leaf in _leaves(
            _layer_key(key, i), layer_specs(model), dtype)}
        for i in range(int(model["num_hidden_layers"]))
    ]
    return out


# ---------------------------------------------------------------------------
# The program and the reference
# ---------------------------------------------------------------------------


def precision_of(model: dict, precision: dict) -> dict:
    """The configuration's precision, with what the model as run states
    of its own laid over it (``precision``: the rehearsal preset runs in
    float32, where a sound run differs from the reference by the order
    of its sums alone; at its toy widths one expert is half a layer's
    output, and a tie of the router in bfloat16 would move a token as
    far as a broken mechanism does)."""
    return dict(precision, **model.get("precision", {}))


def program_cfg(model: dict, precision: dict):
    from rayfed_tpu.models import sdar_moe

    routine = routine_of(model)
    precision = precision_of(model, precision)
    return sdar_moe.SdarMoeConfig.from_published(
        model,
        block_length=int(routine["block_length"]),
        denoising_steps=int(routine["denoising_steps"]),
        remasking=routine["remasking"],
        confidence_threshold=float(routine["confidence_threshold"]),
        mask_id=int(routine["mask_token_id"]),
        compute_dtype=jnp.dtype(precision.get("compute", "bfloat16")),
        param_dtype=param_dtype(precision),
    )


def program_params_host(seed: int, model: dict, precision: dict) -> dict:
    return make_program_tree_host(
        key_of(seed), model, param_dtype(precision_of(model, precision)))


def reference_block_fn(seed: int, model: dict, precision: dict,
                       name: str = "sdar_moe"):
    """``(f(context, block, quant=None) -> logits (B, V) float32, hyper)``
    under the plain reference, holding the seeded canonical tree in the
    parameter type: one forward of a clean context and a partly masked
    block."""
    ref = importlib.import_module("chipbench.references." + name)
    w = make_canonical(
        key_of(seed), model, param_dtype(precision_of(model, precision)))
    hp = ref.hyper_of(model)

    def block_logits(context, block, quant=None):
        return np.asarray(ref.block_logits(
            w, context, block, hp, quant, grid=REF_GRID))

    return block_logits, hp
