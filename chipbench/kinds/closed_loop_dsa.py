"""kind ``closed_loop_dsa``: ``closed_loop_mla``'s run for an architecture
whose full layers attend the keys a learned indexer picks, beside sliding
layers of latent attention and routed experts chosen under a selection
bias. Everything is the parents', by import: the closed loop
(``closed_loop``'s ``plan`` and ``drive``), the adapter by the
configuration's ``reference``, the engine's start, the two numbers held to
the configuration's two limits (``served_logit_gap.widest`` and ``.mean``
over ``limits.sample_requests`` finished requests, the window's longest
among them), the counters around the traced part of the window, the
profile's whole programs (``closed_loop_moe.run``), and
``closed_loop_mla``'s counter list. What those kinds cannot carry, and why
this file exists: ``closed_loop_mla``'s control of the latent cache patches
ANOTHER model's projection, its ``break_route`` hands the routed layer on
without this model's selection bias (a router cut to the held experts
beside a bias as wide as all of them), neither counts what an indexer
scores and keeps nor has a control of the selection; and no file the
benchmark has may be edited by the PR that adds a cell. So this kind sets
``closed_loop_moe``'s ``STATS_DELTAS`` and ``break_route`` for the life of
its process (one process runs one kind), as ``closed_loop_mla`` does, and
calls that kind's ``run``. A ``benchmark`` issue should move the counter
list and the controls behind the adapter and fold the kinds into one
(ROADMAP Queue B).

Its own: the engine's counters for the indexer (``index_keys_scored``,
``index_keys_selected``, the decode steps' part of each) and for the
blocks held behind the sliding layers' windows (``kv_dead_blocks``), and
the controls of the mechanisms themselves, each of which must read
``correct`` false:

* ``--inject broken-index``: the selection takes the ``index_topk`` most
  recent keys (the indexer's scores are made to rise with a key's
  position), whatever the learned scores would pick;
* ``--inject broken-latent``: the positional key is cached unrotated on
  both kinds of layer (a token's latent row holds ``kr`` as projected, the
  queries stay rotated);
* ``--inject broken-route``: the layer takes its ``k`` among the held
  experts only (router and selection bias cut to them).

``--inject broken-window`` (the sliding layers attend every key) is
``closed_loop_moe``'s; ``--control fp8`` and ``--inject broken-token`` are
``closed_loop_arch``'s.
"""

from __future__ import annotations

from chipbench.kinds import closed_loop, closed_loop_mla as mla_kind, \
    closed_loop_moe as moe_kind

STATS_DELTAS = mla_kind.STATS_DELTAS + (
    "index_keys_scored", "index_keys_selected", "index_keys_scored_decode",
    "index_keys_selected_decode", "kv_dead_blocks")

plan = closed_loop.plan
drive = closed_loop.drive


def break_route():
    """``--inject broken-route``: before any program is traced, the
    routed layer is handed a router and a selection bias that know the
    held experts alone."""
    import numpy as np

    from rayfed_tpu.models import moe

    routed = moe.routed_experts

    def among_held(h, layer, held, k, live=None, scale=1.0):
        at = np.asarray(held)
        held_only = dict(layer, router=layer["router"][:, at],
                         router_bias=layer["router_bias"][at])
        return routed(h, held_only, tuple(range(len(held))),
                      min(k, len(held)), live, scale)

    moe.routed_experts = among_held


def break_latent():
    """``--inject broken-latent``: before any program is traced, the
    model's projections hand back a latent row whose positional key was
    turned back to position 0 (the inverse rotation of the one it got):
    what is cached, and what every later query scores, is unrotated."""
    import jax.numpy as jnp

    from rayfed_tpu.models import dots3_note as model
    from rayfed_tpu.models import pangu_ultra_moe as mla

    project = model.project

    def unrotated(h, layer, positions, dims):
        cq, qn, qr, c = project(h, layer, positions, dims)
        kr = mla.rope_halves(c[..., dims.kv_rank:], -positions,
                             dims.rope_theta)
        return cq, qn, qr, jnp.concatenate([c[..., :dims.kv_rank], kr], -1)

    model.project = unrotated


def break_index():
    """``--inject broken-index``: before any program is traced, the
    indexer's inputs are replaced by ones whose score is the key's
    position: key ``s`` holds ``(s // 256, s % 256)`` in its first two
    dimensions (whole numbers under 256: exact in bfloat16), the first
    index head of every query ``(256, 1)``, its weight 1 and every other
    0, so ``I[t, s] = s`` and the top-k is the most recent
    ``index_topk`` keys."""
    import jax.numpy as jnp

    from rayfed_tpu.models import dots3_note as model

    index_project = model.index_project

    def by_position(h, cq, layer, positions, cfg):
        qi, ki, w = index_project(h, cq, layer, positions, cfg)
        pos = jnp.broadcast_to(positions, ki.shape[:-1])
        ki = jnp.zeros_like(ki).at[..., 0].set(
            (pos // 256).astype(ki.dtype)).at[..., 1].set(
            (pos % 256).astype(ki.dtype))
        qi = jnp.zeros_like(qi).at[..., 0, 0].set(256).at[..., 0, 1].set(1)
        return qi, ki, jnp.zeros_like(w).at[..., 0].set(1.0)

    model.index_project = by_position


def run(ctx):
    """``closed_loop_moe.run`` with this kind's counters and controls."""
    moe_kind.STATS_DELTAS = STATS_DELTAS
    moe_kind.break_route = break_route
    if ctx.inject("broken-latent"):
        break_latent()
    if ctx.inject("broken-index"):
        break_index()
    return moe_kind.run(ctx)
