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

"""Jitted federated aggregation ops.

The reference expresses aggregation as plain user Python (the README
``aggregate`` at ``README.md:83-86``, weight averaging in
``fed/tests/test_fed_get.py:66-83``). Here aggregation is a first-class,
jit-compiled tree op so FedAvg-style reductions fuse into single XLA
programs on the party mesh (MXU-friendly: one fused elementwise pass over
each leaf, no Python loop per tensor).

Determinism note (SURVEY.md §7 "bitwise-identical aggregates"): summation
order over parties is fixed by argument order — a left-to-right fold — and
accumulation happens in ``acc_dtype`` (default float32), so the same inputs
produce bitwise-identical outputs on every party and transport.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp


def _fold_sum(leaves: Sequence[Any], acc_dtype):
    acc = leaves[0].astype(acc_dtype) if acc_dtype else leaves[0]
    for x in leaves[1:]:
        acc = acc + (x.astype(acc_dtype) if acc_dtype else x)
    return acc


@functools.partial(jax.jit, static_argnames=("acc_dtype",))
@jax.named_scope("aggregate/sum")
def _tree_sum(trees, acc_dtype: Optional[str] = "float32"):
    dtype = jnp.dtype(acc_dtype) if acc_dtype else None
    return jax.tree_util.tree_map(
        lambda *xs: _fold_sum(xs, dtype).astype(xs[0].dtype), *trees
    )


@functools.partial(jax.jit, static_argnames=("acc_dtype",))
@jax.named_scope("aggregate/mean")
def _tree_mean(trees, acc_dtype: Optional[str] = "float32"):
    n = len(trees)
    dtype = jnp.dtype(acc_dtype) if acc_dtype else None
    return jax.tree_util.tree_map(
        lambda *xs: (_fold_sum(xs, dtype) / n).astype(xs[0].dtype), *trees
    )


@functools.partial(jax.jit, static_argnames=("acc_dtype",))
@jax.named_scope("aggregate/mean")
def _tree_weighted_mean(trees, weights, acc_dtype: Optional[str] = "float32"):
    dtype = jnp.dtype(acc_dtype) if acc_dtype else None
    total = _fold_sum([jnp.asarray(w) for w in weights], dtype)

    def leaf(*xs):
        acc = xs[0] * weights[0] if dtype is None else xs[0].astype(dtype) * weights[0]
        for x, w in zip(xs[1:], weights[1:]):
            acc = acc + (x.astype(dtype) if dtype else x) * w
        return (acc / total).astype(xs[0].dtype)

    return jax.tree_util.tree_map(leaf, *trees)


def tree_sum(*trees, acc_dtype: Optional[str] = "float32"):
    """Elementwise sum of N identically-shaped pytrees (FedSum)."""
    if len(trees) == 1:
        return trees[0]
    return _tree_sum(tuple(trees), acc_dtype=acc_dtype)


def tree_mean(*trees, acc_dtype: Optional[str] = "float32"):
    """Elementwise mean of N identically-shaped pytrees (FedAvg)."""
    if len(trees) == 1:
        return trees[0]
    return _tree_mean(tuple(trees), acc_dtype=acc_dtype)


def tree_weighted_mean(trees, weights, acc_dtype: Optional[str] = "float32"):
    """Sample-count-weighted FedAvg: sum_i w_i * tree_i / sum_i w_i."""
    assert len(trees) == len(weights) and trees
    if len(trees) == 1:
        return trees[0]
    return _tree_weighted_mean(tuple(trees), tuple(weights), acc_dtype=acc_dtype)


@functools.partial(jax.jit, static_argnames=("acc_dtype",))
def _tree_mix(old, new, lr, acc_dtype: Optional[str] = "float32"):
    dtype = jnp.dtype(acc_dtype) if acc_dtype else None

    def leaf(o, n):
        oa = o.astype(dtype) if dtype is not None else o
        na = n.astype(dtype) if dtype is not None else n
        return (oa + lr * (na - oa)).astype(n.dtype)

    return jax.tree_util.tree_map(leaf, old, new)


def tree_mix(old, new, lr: float, acc_dtype: Optional[str] = "float32"):
    """Server-learning-rate mix for buffered-async rounds (FedBuff's
    server step): ``old + lr * (new - old)`` per leaf, accumulated in
    ``acc_dtype`` and cast back to the leaf dtype.

    ``lr == 1.0`` or ``old is None`` returns ``new`` UNTOUCHED — the
    async determinism contract requires the default configuration's
    published model to be bitwise the buffered mean, with no mix
    arithmetic perturbing it."""
    if old is None or lr == 1.0:
        return new
    return _tree_mix(old, new, float(lr), acc_dtype=acc_dtype)


def reduce_by_plan(
    plan,
    contributions,
    weights=None,
    acc_dtype: Optional[str] = "float32",
):
    """Fold ``{party: tree}`` following a
    :class:`~rayfed_tpu.topology.TopologyPlan`'s exact association order.

    This is the local-execution twin of ``fed_aggregate``'s distributed
    lowering: each plan step k-ary-folds its ``srcs`` partials (weighted:
    premultiplied trees + running weight totals), so the arithmetic — and
    therefore the bits — matches what the wire topology produces. Used by
    the scale bench and the bitwise-identity tests to compare topologies
    without N processes, and by :func:`elastic_weighted_mean` when a
    topology is requested.

    Returns the weighted mean over ``plan.parties``.
    """
    missing = set(plan.parties) - set(contributions)
    if missing:
        raise ValueError(
            f"plan references parties with no contribution: {sorted(missing)}"
        )
    held = {}
    totals = {}
    for p in plan.parties:
        w = 1.0 if weights is None else weights[p]
        held[p] = jax.tree_util.tree_map(
            lambda x, w=w: x * w, contributions[p]
        )
        totals[p] = w
    for level in plan.levels:
        for step in level:
            held[step.dst] = tree_sum(
                *[held[s] for s in step.srcs], acc_dtype=acc_dtype
            )
            totals[step.dst] = sum(totals[s] for s in step.srcs)
            for s in step.srcs[1:]:
                del held[s], totals[s]
    total = totals[plan.root]
    return jax.tree_util.tree_map(
        lambda x: x / total, held[plan.root]
    )


def psum_by_plan(
    plan,
    contributions,
    weights=None,
    acc_dtype: Optional[str] = "float32",
    mesh=None,
    deterministic: bool = True,
):
    """Lower a FLAT plan to one collective across the composed party
    mesh's ``party`` axis — the same weighted mean :func:`reduce_by_plan`
    computes, BITWISE-equal, in a single shard_map program instead of a
    premultiply/fold/scale chain.

    Eligibility: ``topology.plan_is_flat(plan)`` and a composed mesh
    registered for exactly ``plan.parties``
    (``mesh.compose_party_mesh``), or passed via ``mesh=``. Each party's
    contribution is premultiplied by its weight in its own dtype, stacked
    along the party axis, and reduced on device.

    ``deterministic=True`` (default) all_gathers the party slots and
    folds them in plan order in ``acc_dtype`` — the exact association
    :func:`reduce_by_plan` uses, so bit-equality holds on every backend.
    ``deterministic=False`` lowers to a raw ``jax.lax.psum``, whose
    association order is backend-defined: bitwise-equal on backends whose
    all-reduce folds linearly (the CPU simulator does), cheaper on TPU
    rings, but not a portable bit-contract.
    """
    from rayfed_tpu import mesh as mesh_mod
    from rayfed_tpu import topology as topo

    if not topo.plan_is_flat(plan):
        raise ValueError(
            f"psum_by_plan needs a flat plan; got topology="
            f"{plan.topology!r} with {plan.num_rounds} rounds"
        )
    missing = set(plan.parties) - set(contributions)
    if missing:
        raise ValueError(
            f"plan references parties with no contribution: {sorted(missing)}"
        )
    parties = plan.parties
    ws = [1.0 if weights is None else weights[p] for p in parties]
    # Premultiply in the leaf's own dtype, then total the weights the way
    # reduce_by_plan's ``sum()`` does (0 + w0 + w1 + ...): both choices
    # are part of the bit contract.
    pre = [
        jax.tree_util.tree_map(lambda x, w=w: x * w, contributions[p])
        for p, w in zip(parties, ws)
    ]
    total = sum(ws)
    if len(parties) == 1:
        return jax.tree_util.tree_map(lambda x: x / total, pre[0])
    if mesh is None:
        mesh = mesh_mod.composed_mesh_for(parties)
    if mesh is None:
        raise ValueError(
            f"no composed party mesh registered for parties {parties} "
            "(call mesh.compose_party_mesh first)"
        )
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(parties)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jax.device_put(
            jnp.stack([jnp.asarray(x) for x in xs]),
            NamedSharding(mesh, P("party")),
        ),
        *pre,
    )
    reduced = _psum_flat_fn(mesh, n, acc_dtype or "", deterministic)(stacked)
    # Every party slot holds the identical sum; slot 0 stands in. The
    # division happens HERE, outside the cached program, so changing
    # weights between rounds never recompiles — same op on the same
    # values as reduce_by_plan's final scale, so the bits still match.
    return jax.tree_util.tree_map(lambda x: x[0] / total, reduced)


@functools.lru_cache(maxsize=32)
def _psum_flat_fn(mesh, n: int, acc_dtype: str, deterministic: bool):
    """The compiled party-axis reduction for :func:`psum_by_plan`. Cached
    on (mesh, n, acc_dtype, deterministic) — repeat aggregation rounds on
    the same composed mesh reuse one XLA program instead of re-tracing
    the shard_map every call (jit's own cache handles leaf shapes)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    dtype = jnp.dtype(acc_dtype) if acc_dtype else None

    def body(local_tree):
        def leaf(x):  # x: this party's slot, shape (1, ...)
            orig = x.dtype
            if deterministic:
                g = jax.lax.all_gather(x[0], "party", axis=0)
                acc = g[0].astype(dtype) if dtype is not None else g[0]
                for i in range(1, n):
                    nxt = g[i].astype(dtype) if dtype is not None else g[i]
                    acc = acc + nxt
            else:
                acc = jax.lax.psum(
                    x[0].astype(dtype) if dtype is not None else x[0],
                    "party",
                )
            return acc.astype(orig)[None]

        return jax.tree_util.tree_map(leaf, local_tree)

    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("party"), out_specs=P("party"))
    )


def elastic_weighted_mean(
    contributions,
    weights=None,
    liveness=None,
    acc_dtype: Optional[str] = "float32",
    topology: Optional[str] = None,
    group_size: Optional[int] = None,
):
    """Degraded-mode FedAvg: the weighted mean over SURVIVING
    contributors, re-normalized so the aggregate stays an average of what
    actually arrived (docs/resilience.md).

    ``contributions`` is ``{party: tree_or_missing}``. A contributor is
    dropped when its value is absent — None or the ``fed.MISSING``
    sentinel, i.e. what ``fed.get(..., on_missing="default")`` yields for
    a lost push — or when ``liveness`` (a ``{party: state}`` view from
    ``fed.liveness_view()``) marks it DEAD. The DEAD check matters even
    when the value DID arrive: a partitioned peer's stale round-k update
    averaged into round k+n is worse than no update (the classic
    straggler-poisoning failure), so the liveness verdict wins.

    ``weights`` maps party -> sample count (uniform when None). Raises
    ``ValueError`` when no contributor survives — an empty average has no
    meaningful value, and silently returning zeros would train on them.

    Survivor fold order is party-name order, independent of which subset
    survived, so the same surviving set produces bitwise-identical
    aggregates on every party (the determinism contract above).

    ``topology`` (None = the flat left-to-right fold above) folds along a
    planned reduction shape instead — the plan is laid out over the
    surviving set (a DEAD party re-plans the topology rather than
    leaving a hole in it), and the association order matches what
    ``fed_aggregate`` produces on the wire for the same survivors.
    """
    from rayfed_tpu.resilience.degraded import MISSING
    from rayfed_tpu.resilience.liveness import DEAD

    liveness = liveness or {}
    survivors = [
        p for p in sorted(contributions)
        if contributions[p] is not None
        and contributions[p] is not MISSING
        and liveness.get(p) != DEAD
    ]
    if not survivors:
        raise ValueError(
            "no surviving contributors to aggregate: all values missing "
            "or their parties marked DEAD"
        )
    if topology is not None:
        from rayfed_tpu import topology as topo

        surv_plan = topo.plan(survivors, topology, group_size=group_size)
        return reduce_by_plan(
            surv_plan,
            {p: contributions[p] for p in survivors},
            weights=None if weights is None
            else {p: weights[p] for p in survivors},
            acc_dtype=acc_dtype,
        )
    trees = [contributions[p] for p in survivors]
    w = [1.0 if weights is None else weights[p] for p in survivors]
    return tree_weighted_mean(trees, w, acc_dtype=acc_dtype)
