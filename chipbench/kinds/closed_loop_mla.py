"""kind ``closed_loop_mla``: ``closed_loop_moe``'s run for an architecture
with latent attention beside its routed experts. Everything is that
kind's, by import: the closed loop (``closed_loop``'s ``plan`` and
``drive``), the adapter by the configuration's ``reference``, the engine's
start, the two numbers held to the configuration's two limits
(``served_logit_gap.widest`` and ``.mean``), the counters around the
traced part of the window, the profile's whole programs. What that kind
cannot carry, and why this file exists: its ``STATS_DELTAS`` is a closed
tuple, its ``break_route`` hands the routed layer on without the weight
scale this model's layer is told, and it has no control of the latent
cache; and no file the benchmark has may be edited by the PR that adds a
cell. So this kind sets those three names in ``closed_loop_moe``'s module
for the life of its process (one process runs one kind) and calls its
``run``. A ``benchmark`` issue should move the counter list and the
controls behind the adapter and fold the kinds into one (ROADMAP Queue B).

Its own: the engine's counters for the latent read
(``decode_keys_attended``, and ``chunk_blocks_read`` for the rows a chunk
expands again) beside ``closed_loop_moe``'s, and one more control of the
mechanism itself, which must read ``correct`` false:

* ``--inject broken-latent``: the positional key is cached unrotated (a
  token's latent row holds ``kr`` as projected, the queries stay rotated).

``--inject broken-route`` is ``closed_loop_moe``'s (the layer takes its
``k`` among the held experts only), handed the scale; ``--control fp8``
and ``--inject broken-token`` are ``closed_loop_arch``'s.
"""

from __future__ import annotations

from chipbench.kinds import closed_loop, closed_loop_moe as moe_kind

STATS_DELTAS = moe_kind.STATS_DELTAS + (
    "decode_keys_attended", "chunk_blocks_read")

plan = closed_loop.plan
drive = closed_loop.drive
route_among_held = moe_kind.break_route


def break_route():
    """``closed_loop_moe.break_route``, for a layer that is told a weight
    scale: the scale multiplies what the broken layer gives."""
    from rayfed_tpu.models import moe

    route_among_held()
    among_held = moe.routed_experts

    def scaled(h, layer, held, k, live=None, scale=1.0):
        y, hit, local = among_held(h, layer, held, k, live)
        return y * scale, hit, local

    moe.routed_experts = scaled


def break_latent():
    """``--inject broken-latent``: before any program is traced, the
    model's projections hand back a latent row whose positional key was
    turned back to position 0 (the inverse rotation of the one it got):
    what is cached, and what every later query scores, is unrotated."""
    import jax.numpy as jnp

    from rayfed_tpu.models import pangu_ultra_moe as model

    project = model.project

    def unrotated(h, layer, positions, cfg):
        qn, qr, c = project(h, layer, positions, cfg)
        kr = model.rope_halves(c[..., cfg.kv_rank:], -positions,
                               cfg.rope_theta)
        return qn, qr, jnp.concatenate([c[..., :cfg.kv_rank], kr], -1)

    model.project = unrotated


def run(ctx):
    """``closed_loop_moe.run`` with this kind's counters and controls."""
    moe_kind.STATS_DELTAS = STATS_DELTAS
    moe_kind.break_route = break_route
    if ctx.inject("broken-latent"):
        break_latent()
    return moe_kind.run(ctx)
