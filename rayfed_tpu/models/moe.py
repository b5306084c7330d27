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

"""Mixture-of-experts FFN: two families of paths.

**The all-experts paths** (:func:`moe_ffn_apply`, :func:`moe_ffn_apply_topk`,
:func:`make_ep_moe_apply`; what ``transformer.py`` wires in under
``cfg.moe``): softmax routing over ungated GELU experts, every expert run
on every token and the result masked by the gates. Static shapes and no
data-dependent dispatch, at ``experts x tokens`` operations whatever the
routing chose; under ``shard_map`` each device runs its local experts for
all tokens and one ``psum`` combines. :func:`make_a2a_moe_apply` is the
capacity-based all-to-all form of the same experts (assignments over an
expert's capacity are dropped). These exist for the training-side tests
and have never been measured on a chip.

**The grouped path** (:func:`route_sigmoid_topk`, :func:`routed_experts`,
:func:`shared_experts`; what :mod:`rayfed_tpu.models.cohere2_moe`,
:mod:`rayfed_tpu.models.pangu_ultra_moe` and
:mod:`rayfed_tpu.models.sdar_moe` serve through ``fed.serve``):
sigmoid scores over ALL experts (softmax scores where the model says so:
:func:`route_softmax_topk`), the ``k`` largest normalised over those
``k`` (and scaled by the model's factor, where it has one), gated SiLU
experts, and a layer that is told
which experts it *holds* (one chip's share of a layer divided over
chips): it computes the part of the result its own experts give. Tokens
are grouped by expert, so operations and the expert weights read follow
the assignments: an expert no token chose is never touched. No capacity
factor, no dropped token. This is the only path with a cell in the
benchmark (``PERF.md``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from rayfed_tpu import utils

Params = Dict[str, Any]


def init_moe_ffn(rng, d_model: int, d_ff: int, n_experts: int,
                 dtype=jnp.float32) -> Params:
    kr, ku, kd = jax.random.split(rng, 3)
    scale = (2.0 / d_model) ** 0.5
    return {
        "router": (jax.random.normal(kr, (d_model, n_experts)) * 0.02).astype(dtype),
        "w_up": (jax.random.normal(ku, (n_experts, d_model, d_ff)) * scale).astype(dtype),
        "w_down": (
            jax.random.normal(kd, (n_experts, d_ff, d_model)) * (2.0 / d_ff) ** 0.5
        ).astype(dtype),
    }


def _router_probs(params: Params, x):
    """Router probabilities in float32 — THE routing numerics, shared by
    every gating variant (top-1, top-k, aux loss): changes to temperature,
    z-loss scaling etc. belong here and nowhere else."""
    logits = x @ params["router"]  # (..., E)
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def _gates(params: Params, x, top1: bool):
    probs = _router_probs(params, x)
    if top1:
        # argmax, not probs==max: a max-comparison can select TWO experts
        # on low-precision ties, which desyncs the dense and a2a lanes.
        mask = jax.nn.one_hot(
            jnp.argmax(probs, axis=-1), probs.shape[-1], dtype=probs.dtype
        )
        probs = probs * mask
    return probs.astype(x.dtype)


def _expert_ffn(w_up, w_down, toks):
    """THE per-expert FFN core: toks (E, T, d) -> (E, T, d). Every lane
    (dense, expert-parallel, all-to-all) routes through this one function —
    they must never diverge (the *_matches_dense tests pin equivalence)."""
    up = jnp.einsum("etd,edf->etf", toks, w_up)
    return jnp.einsum("etf,efd->etd", jax.nn.gelu(up), w_down)


def _expert_ffn_combine(w_up, w_down, x, gates):
    """Run all experts on all tokens and gate-combine (dense/EP lanes)."""
    e = w_up.shape[0]
    flat = x.reshape(-1, x.shape[-1])
    toks = jnp.broadcast_to(flat, (e,) + flat.shape)
    out = _expert_ffn(w_up, w_down, toks)          # (E, N, d)
    flat_gates = gates.reshape(-1, gates.shape[-1])
    combined = jnp.einsum("end,ne->nd", out, flat_gates)
    return combined.reshape(x.shape)


def moe_ffn_apply(params: Params, x, top1: bool = True):
    """Reference (single-device) forward: x (..., d) -> (..., d)."""
    gates = _gates(params, x, top1)  # (..., E)
    return _expert_ffn_combine(params["w_up"], params["w_down"], x, gates)


def make_ep_moe_apply(mesh: Mesh, expert_axis: str = "expert"):
    """Expert-parallel forward: expert-sharded params, replicated tokens,
    one psum to combine. Call with params whose expert-leading leaves are
    (global) full-size; shard_map slices them per device."""
    e_spec = {"router": P(), "w_up": P(expert_axis), "w_down": P(expert_axis)}

    def body(params, x):
        n_exp_local = params["w_up"].shape[0]
        idx = lax.axis_index(expert_axis)
        # Global gates, locally sliced to this device's experts.
        gates = _gates(params, x, top1=True)  # router replicated -> (.., E)
        lo = idx * n_exp_local
        local_gates = lax.dynamic_slice_in_dim(
            gates, lo, n_exp_local, axis=-1
        )
        local = _expert_ffn_combine(
            params["w_up"], params["w_down"], x, local_gates
        )
        return lax.psum(local, expert_axis)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(e_spec, P()),
        out_specs=P(),
        check_vma=False,
    )


def make_a2a_moe_apply(mesh: Mesh, expert_axis: str = "expert",
                       capacity_factor: float = 1.25, k: int = 1):
    """Capacity-based all-to-all expert dispatch (switch-style) — the
    scalable EP form: tokens are sharded over the expert axis, each device
    selects up to C (token, choice) assignments per expert, one
    ``all_to_all`` routes them to their expert's device, the FFN runs on
    E_local experts, and a second ``all_to_all`` routes results home.
    Compute per device is O(E_local * C) instead of the dense path's
    O(E * N); assignments over an expert's capacity are dropped (that
    choice contributes zero), the standard trade.

    ``k`` = experts per token: 1 reproduces switch-style top-1 routing
    (``_gates``), k>1 routes each token to its top-k experts with
    renormalized gates (``topk_gates``) — capacity scales with k so the
    expected slot load is unchanged.

    Call with token-sharded x of shape (N, d) — N divisible by the axis
    size — and full-size expert params; returns (N, d).
    """
    n_dev = mesh.shape[expert_axis]

    def body(params, x):
        n_local, d = x.shape
        e_local = params["w_up"].shape[0]
        n_experts = e_local * n_dev
        capacity = max(1, int(k * n_local * capacity_factor / n_experts))

        if k == 1:
            gates = _gates(params, x, top1=True)      # (N_local, E)
        else:
            gates = topk_gates(params, x, k)          # (N_local, E), k>0/row
        # Ranks MUST accumulate in int32: a low-precision cumsum (bf16 has
        # an 8-bit mantissa) silently collides tokens onto the same slot
        # once ranks exceed the dtype's exact-integer range.
        onehot_i = (gates > 0).astype(jnp.int32)       # (N_local, E)

        # Rank of each (token, choice) within its expert's queue; drop
        # overflow.
        pos = jnp.cumsum(onehot_i, axis=0) * onehot_i  # 1-based ranks
        keep = (pos > 0) & (pos <= capacity)
        loc = jnp.clip(pos - 1, 0, capacity - 1)

        # (N_local, E, C) dispatch tensor.
        loc_onehot = jax.nn.one_hot(loc, capacity, dtype=x.dtype)
        dispatch = (
            keep.astype(x.dtype)[..., None] * loc_onehot
        )                                              # (N, E, C)

        # Scatter tokens into per-expert slots, then route slots to the
        # expert's device: (E, C, d) -> (n_dev, e_local, C, d) a2a.
        slots = jnp.einsum("nec,nd->ecd", dispatch, x)
        slots = slots.reshape(n_dev, e_local, capacity, d)
        recv = lax.all_to_all(
            slots, expert_axis, split_axis=0, concat_axis=0, tiled=False
        )                                              # (n_dev, e_local, C, d)

        # Local experts run on tokens gathered from every device.
        toks = jnp.moveaxis(recv, 1, 0).reshape(
            e_local, n_dev * capacity, d
        )
        out = _expert_ffn(params["w_up"], params["w_down"], toks)

        # Route results back to the tokens' home devices.
        back = jnp.moveaxis(
            out.reshape(e_local, n_dev, capacity, d), 1, 0
        )                                              # (n_dev, e_local, C, d)
        home = lax.all_to_all(
            back, expert_axis, split_axis=0, concat_axis=0, tiled=False
        ).reshape(n_experts, capacity, d)

        # Combine weights = gate * dispatch: each surviving (token, choice)
        # contributes its expert's output scaled by its gate.
        combine = dispatch * gates[..., None]
        return jnp.einsum("nec,ecd->nd", combine, home)

    e_spec = {"router": P(), "w_up": P(expert_axis), "w_down": P(expert_axis)}
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(e_spec, P(expert_axis)),
        out_specs=P(expert_axis),
        check_vma=False,
    )


def topk_gates(params: Params, x, k: int = 2):
    """Top-k routing: per token, the k best experts with their softmax
    probabilities renormalized to sum to 1. Returns (..., E) gates."""
    probs = _router_probs(params, x)
    _, idx = lax.top_k(probs, k)
    mask = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype).sum(axis=-2)
    kept = probs * mask
    kept = kept / jnp.maximum(kept.sum(axis=-1, keepdims=True), 1e-9)
    return kept.astype(x.dtype)


def load_balance_loss(params: Params, x, k: int = 1):
    """Switch-transformer auxiliary load-balancing loss:
    E * sum_e f_e * P_e, where f_e is the fraction of routed assignments
    landing on expert e (over the same top-k choices the gating uses — an
    aux loss that only watches top-1 would let every second choice collapse
    onto one expert unpenalized) and P_e the mean router probability.
    Minimized (-> 1.0) by a uniform distribution; add a small multiple to
    the task loss when training MoE models so experts stay utilized. Pass
    the same ``k`` as the gating in use."""
    probs = _router_probs(params, x.reshape(-1, x.shape[-1]))
    n_experts = probs.shape[-1]
    _, idx = lax.top_k(probs, k)
    chosen = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum(axis=-2)
    f = chosen.mean(axis=0) / k   # fraction of assignments per expert
    p = probs.mean(axis=0)        # mean router probability per expert
    return n_experts * jnp.sum(f * p)


def moe_ffn_apply_topk(params: Params, x, k: int = 2):
    """Dense-compute forward with top-k routing (k experts per token)."""
    gates = topk_gates(params, x, k)
    return _expert_ffn_combine(params["w_up"], params["w_down"], x, gates)


# ---------------------------------------------------------------------------
# The grouped path: sigmoid top-k routing, held experts, shared experts
# ---------------------------------------------------------------------------

def route_sigmoid_topk(h, router, k: int, bias=None):
    """Scores ``sigmoid(h router)`` over ALL experts in float32 (operands
    as they come, float32 accumulation), the ``k`` largest, their weights
    normalised over the ``k``. ``h`` (T, d), ``router`` (d, E). Returns
    (expert ids (T, k) int32, weights (T, k) float32).

    ``bias`` (E,), where the model has one (``noaux_tc``'s learned
    ``e_score_correction_bias``), is added to the scores to CHOOSE the
    ``k`` and not to weigh them: the weights are the chosen experts' own
    scores, normalised. Without it the lines traced are those above."""
    with jax.named_scope("serve/moe_route"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", h, router.astype(h.dtype),
            preferred_element_type=jnp.float32))
        if bias is None:
            top, idx = lax.top_k(scores, k)
        else:
            _, idx = lax.top_k(scores + bias.astype(jnp.float32), k)
            top = jnp.take_along_axis(scores, idx, axis=-1)
        return idx.astype(jnp.int32), top / top.sum(-1, keepdims=True)


def route_softmax_topk(h, router, k: int):
    """:func:`route_sigmoid_topk`'s softmax sibling: scores ``softmax(h
    router)`` over ALL experts in float32, the ``k`` largest, their
    weights normalised over the ``k`` (``norm_topk_prob``). The same
    experts as the sigmoid's (both rise with the product), other
    weights."""
    with jax.named_scope("serve/moe_route"):
        scores = jax.nn.softmax(jnp.einsum(
            "td,de->te", h, router.astype(h.dtype),
            preferred_element_type=jnp.float32), axis=-1)
        top, idx = lax.top_k(scores, k)
        return idx.astype(jnp.int32), top / top.sum(-1, keepdims=True)


ROUTERS = {"sigmoid": route_sigmoid_topk, "softmax": route_softmax_topk}


# Row tile of the grouped matmul on a TPU. An expert's rows cost whole
# tiles, so the tile is small beside the 32 rows an expert of 128 sees of
# a 512-token chunk; the contraction is not tiled (one pass, no
# accumulator traffic) and the output 512 wide. On a v5e at 4096 x 4096
# experts (``PERF.md`` section 6, PR 31): 16 experts x 32 rows of 4,096
# in 0.82 ms where ``jax.lax.ragged_dot``'s own kernel, which tiles rows
# by 512, took 1.84; 11 experts hit by 16 rows of 128 in 0.54 against
# 0.71; reading the experts' matrices alone takes 0.66 and 0.45.
GROUPED_ROW_TILE = 128
GROUPED_OUT_TILE = 512
# ... up to this width: a (contraction x 512) tile of an expert's matrix
# is double-buffered in the kernel's fast memory, and at 7680 (4096 was
# the widest until PR 33) two of them no longer fit. A wider contraction
# is cut into the fewest equal tiles that are multiples of 128.
GROUPED_CONTRACT_TILE = 4096


def _contract_tile(k: int) -> int:
    if k <= GROUPED_CONTRACT_TILE:
        return k
    for parts in range(-(-k // GROUPED_CONTRACT_TILE), k // 128 + 1):
        if k % (parts * 128) == 0:
            return k // parts
    return k


def _out_tile(n: int) -> int:
    """The output tile of a product ``n`` wide: ``GROUPED_OUT_TILE``, or
    the widest multiple of 128 under it that divides ``n`` (384 of 768)."""
    if n <= GROUPED_OUT_TILE or n % GROUPED_OUT_TILE == 0:
        return min(GROUPED_OUT_TILE, n)
    return next((tn for tn in range(GROUPED_OUT_TILE - 128, 0, -128)
                 if n % tn == 0), GROUPED_OUT_TILE)


def grouped_matmul(x, w, group_sizes):
    """``x[rows of group g] @ w[g]`` for consecutive row groups of the
    given sizes: ``x`` (m, k), ``w`` (g, k, n), ``group_sizes`` (g,)
    int32 -> (m, n) float32. Rows past the last group come back
    unspecified. On a TPU the megablox kernel that ships with jax
    (``jax.experimental.pallas.ops.tpu.megablox``), tiled as above: it
    visits only (row tile, group) pairs that hold rows, so a group of
    size 0 is never read. Elsewhere ``jax.lax.ragged_dot``."""
    m, n = x.shape[0], w.shape[2]
    tm = min(GROUPED_ROW_TILE, m)
    tn = _out_tile(n)
    if utils.is_tpu_backend() and m % tm == 0 and n % tn == 0:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(x, w, group_sizes, preferred_element_type=jnp.float32,
                   tiling=(tm, _contract_tile(x.shape[1]), tn))
    return lax.ragged_dot(x, w, group_sizes,
                          preferred_element_type=jnp.float32)


def routed_experts(h, layer: Params, held: Sequence[int], k: int,
                   live=None, scale: float = 1.0, scoring: str = "sigmoid"):
    """The part of a routed-expert layer that the experts ``held`` give.

    ``h`` (T, d) in the compute dtype; ``layer`` holds ``router`` (d, E)
    over all E experts and ``we_gate``/``we_up`` (Eh, d, f), ``we_down``
    (Eh, f, d), the weights of the ``Eh = len(held)`` experts held here,
    in the order of ``held`` (global expert ids, static). Expert ``e``
    computes ``(silu(h Wg_e) * (h Wu_e)) Wd_e``. Each token's ``k``
    weights are normalised over all ``k`` chosen experts, held or not,
    and then multiplied by ``scale`` (a model's ``routed_scaling_factor``;
    1 where it has none); what an absent expert would add is left out (it
    lies on another chip). ``scoring`` names the router's scores
    (``ROUTERS``: "sigmoid", or "softmax" over all experts).
    A layer that holds ``router_bias`` (E,) chooses by ``scores +
    bias`` and weighs by the scores (:func:`route_sigmoid_topk`; sigmoid
    scoring only); a layer without the leaf routes as it always did.
    ``live`` (T,) bool names the rows that count
    (None: all):
    padding and junk rows are routed nowhere and touch no expert.

    The (token, expert) assignments are sorted by expert, those on held
    experts first, and the three matmuls are grouped over the sorted rows
    with the held experts' counts as group sizes
    (:func:`grouped_matmul`): operations follow the assignments, and an
    expert that no live token chose is not read (rows past the last
    group belong to no expert; what comes back for them is masked).
    Returns ``(y (T, d) float32, experts_hit, assignments)``: the two
    counts are int32 scalars (held experts with at least one live token;
    (token, expert) pairs on held experts).
    """
    t, d = h.shape
    n_held = len(held)
    n_experts = layer["router"].shape[-1]
    if "router_bias" in layer:
        if scoring != "sigmoid":
            raise ValueError(
                f"a selection bias is computed for sigmoid scores, not "
                f"{scoring!r}")
        idx, w = route_sigmoid_topk(h, layer["router"], k,
                                    layer["router_bias"])
    else:
        idx, w = ROUTERS[scoring](h, layer["router"], k)
    with jax.named_scope("serve/moe_experts"):
        # Global expert id -> its index among the held ones; n_held for
        # an expert that lies elsewhere.
        local_of = np.full(n_experts, n_held, np.int32)
        local_of[np.asarray(held, np.int64)] = np.arange(n_held)
        lid = jnp.asarray(local_of)[idx]
        if live is not None:
            lid = jnp.where(live[:, None], lid, n_held)
        m = t * k
        flat = lid.reshape(m)
        order = jnp.argsort(flat, stable=True)       # held first, by expert
        counts = jnp.sum(
            flat[:, None] == jnp.arange(n_held), axis=0, dtype=jnp.int32)
        xs = h[order // k]

        def grouped(x, name):
            return grouped_matmul(x, layer[name].astype(h.dtype), counts)

        act = (jax.nn.silu(grouped(xs, "we_gate"))
               * grouped(xs, "we_up")).astype(h.dtype)
        ys = grouped(act, "we_down")
        # Back to (token, choice) order; an assignment that lies
        # elsewhere adds nothing.
        mine = (jnp.arange(m) < jnp.sum(counts))[:, None]
        if scale != 1.0:
            w = w * scale
        weighted = jnp.where(mine, ys * w.reshape(m)[order][:, None], 0.0)
        y = weighted[jnp.argsort(order)].reshape(t, k, d).sum(1)
        return (y, jnp.sum(counts > 0, dtype=jnp.int32),
                jnp.sum(counts, dtype=jnp.int32))


def shared_experts(h, layer: Params, n_shared: int):
    """The mean of ``n_shared`` gated SiLU experts that every token
    passes, held as ONE gated MLP ``ws_gate``/``ws_up`` (d, n_shared * f),
    ``ws_down`` (n_shared * f, d): the sum of the experts' outputs is
    that MLP's output, and the mean is that over ``n_shared``. Returns
    (T, d) float32."""
    with jax.named_scope("serve/moe_shared"):
        gate = jnp.einsum("...d,df->...f", h, layer["ws_gate"].astype(h.dtype),
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("...d,df->...f", h, layer["ws_up"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
        return jnp.einsum(
            "...f,fd->...d", act, layer["ws_down"].astype(h.dtype),
            preferred_element_type=jnp.float32) / n_shared
