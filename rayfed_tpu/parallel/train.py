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

"""Sharded training steps: federated data/tensor/sequence parallel in one jit.

The train step compiles once over the whole mesh:

 - ``party`` x ``data`` shard the batch — because the loss is a mean over
   the global batch, XLA's gradient all-reduce over these axes IS the
   federated aggregate (synchronized FedSGD). Multi-local-step FedAvg runs
   over the engine's push/psum lanes instead (``rayfed_tpu.collective``).
 - ``model`` shards attention heads + MLP hidden via the GSPMD rules in
   :mod:`rayfed_tpu.parallel.sharding` (tensor parallelism).
 - ``seq`` (optional) shards the sequence dim of activations; attention
   runs as ring attention over the seq axis inside ``shard_map``
   (:mod:`rayfed_tpu.parallel.ring`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rayfed_tpu.models import transformer as tfm
from rayfed_tpu.parallel import sharding as shd
from rayfed_tpu.parallel.ring import ring_attention


#: Machine-readable anchor for the static analyzer (``rayfed_tpu.lint``):
#: the fedlint rule that enforces this module's donation-aliasing
#: contract (``make_fed_train_step(donate=True)`` outputs must not be
#: returned for local by-reference consumption — see the contract
#: comment inside ``make_fed_train_step`` and docs/fedlint.md). Pinned
#: against the rule registry by ``tests/test_fedlint.py``.
FEDLINT_DONATION_RULE = "FED003"


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.01):
    return optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=weight_decay)


def make_fed_train_step(
    cfg: tfm.TransformerConfig,
    mesh: Mesh,
    *,
    party_axis: Optional[str] = "party",
    data_axis: Optional[str] = "data",
    seq_axis: Optional[str] = None,
    lr: float = 3e-4,
    remat: bool = False,
    attn: str = "auto",
    seq_parallel: str = "ring",
    accum_steps: int = 1,
    shard_opt_state: bool = False,
    donate: bool = True,
):
    """Build (init_fn, step_fn) jitted over ``mesh``.

    ``init_fn(rng, sample_tokens) -> (params, opt_state)`` places state
    according to the partition rules; ``step_fn(params, opt_state, inputs,
    targets) -> (params, opt_state, loss)`` is one synchronized federated
    step over pre-shifted (B, S) input/target blocks.

    ``attn`` selects the on-device attention: ``"flash"`` = the Pallas
    flash kernel (O(S) memory, differentiable), ``"xla"`` = the dense
    reference attention, ``"auto"`` (default) = flash on TPU backends,
    dense elsewhere (the kernel's interpret mode is test-speed only).
    When the ``seq`` axis is sharded, attention runs sequence-parallel
    over that axis; ``seq_parallel`` picks the strategy:
    ``"ring"`` (default) rotates K/V blocks via ``ppermute`` (no cap on
    the axis size, every hop overlapped with compute; with flash each
    step runs the Pallas kernels so per-device memory stays O(S_local));
    ``"a2a"`` is Ulysses-style — one all_to_all to head-sharded layout,
    the unmodified local kernel over the full sequence, one all_to_all
    back (fewer collectives at long S; needs n_heads divisible by the
    axis size).

    ``accum_steps > 1`` splits the global batch into that many
    microbatches and accumulates gradients under one ``lax.scan`` —
    activation memory scales with the microbatch while the update sees
    the full-batch gradient (mean of equal-sized microbatch means, f32
    accumulation; matches the single-pass gradient up to float
    reduction-order rounding, ~1e-5 relative).

    ``shard_opt_state=True`` additionally shards optimizer moments
    ZeRO-1 style: any moment dim the parameter rules leave unsharded is
    sharded over party x data when divisible, cutting optimizer memory by
    the dp world size; XLA inserts the per-step all-gather on the
    update path automatically.
    """
    optimizer = make_optimizer(lr)
    use_sp = seq_axis is not None and mesh.shape.get(seq_axis, 1) > 1
    if attn not in ("auto", "flash", "xla"):
        raise ValueError(f"attn must be 'auto', 'flash', or 'xla'; got {attn!r}")
    if seq_parallel not in ("ring", "a2a"):
        raise ValueError(
            f"seq_parallel must be 'ring' or 'a2a'; got {seq_parallel!r}"
        )
    if attn == "auto":
        from rayfed_tpu.utils import is_tpu_backend

        attn = "flash" if is_tpu_backend() else "xla"

    if use_sp:
        # Sequence-parallel attention: shard_map over the seq axis;
        # every other axis stays GSPMD-automatic.
        if seq_parallel == "a2a":
            from rayfed_tpu.parallel.ulysses import (
                make_ulysses_flash,
                ulysses_attention,
            )

            if cfg.n_heads % mesh.shape[seq_axis] != 0:
                raise ValueError(
                    f"seq_parallel='a2a' needs n_heads ({cfg.n_heads}) "
                    f"divisible by the '{seq_axis}' axis size "
                    f"({mesh.shape[seq_axis]}); use seq_parallel='ring'"
                )
            block_attn = (
                make_ulysses_flash(seq_axis)
                if attn == "flash"
                else functools.partial(ulysses_attention, axis_name=seq_axis)
            )
        else:
            from rayfed_tpu.parallel.ring import ring_flash_attention

            block_attn = (
                functools.partial(ring_flash_attention, axis_name=seq_axis)
                if attn == "flash"
                else functools.partial(ring_attention, axis_name=seq_axis)
            )

        def sp_attn(q, k, v):
            pspec = P(None, seq_axis, None, None)
            return shard_map(
                block_attn,
                mesh=mesh,
                in_specs=(pspec, pspec, pspec),
                out_specs=pspec,
                check_vma=False,
                axis_names={seq_axis},
            )(q, k, v)

        attn_fn = sp_attn
    elif attn == "flash":
        from rayfed_tpu.ops.flash_attention import make_flash_attn_fn

        attn_fn = make_flash_attn_fn()
        # A Mosaic custom call has no GSPMD partitioning rule: on more
        # than one real chip jax refuses to lower it ("Mosaic kernels
        # cannot be automatically partitioned"; interpret mode lowers to
        # plain HLO and hides this on the CPU). Attention is independent
        # per (batch row, head), so map the kernel over the batch axes
        # and the head axis: each chip runs its own shard, no gather.
        batch_axes = tuple(
            a for a in (party_axis, data_axis)
            if a and mesh.shape.get(a, 1) > 1
        )
        head_axis = "model" if mesh.shape.get("model", 1) > 1 else None
        if batch_axes or head_axis:
            qkv_spec = P(batch_axes or None, None, head_axis, None)
            attn_fn = shard_map(
                attn_fn,
                mesh=mesh,
                in_specs=(qkv_spec, qkv_spec, qkv_spec),
                out_specs=qkv_spec,
                check_vma=False,
            )
    else:
        attn_fn = None

    batch_pspec = shd.batch_spec(mesh, party_axis, data_axis, seq_axis)
    batch_sharding = NamedSharding(mesh, batch_pspec)
    # Chunked head+CE keeps (B, S, vocab) f32 logits out of HBM; disabled
    # when S is sharded (chunking reshapes the sequence dim).
    loss_chunk = None if use_sp else 512

    def loss_fn(params, inputs, targets):
        return tfm.lm_loss_pair(
            params, inputs, targets, cfg, attn_fn, remat=remat,
            loss_chunk=loss_chunk,
        )

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def grad_step(params, inputs, targets):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, inputs, targets)
        b, s = inputs.shape
        if b % accum_steps:
            raise ValueError(
                f"batch {b} not divisible by accum_steps={accum_steps}"
            )
        mb = b // accum_steps
        # Strided split (microbatch i = rows i::accum_steps), NOT
        # contiguous chunks: the batch dim is sharded over party x data,
        # and a contiguous microbatch would hold only some shards' rows —
        # XLA would then reshard raw token data across parties every
        # step. Strided microbatches take an equal slice of every dp
        # shard (zero-communication when mb divides by the dp extent);
        # the constraint pins that layout for GSPMD.
        mb_sharding = NamedSharding(mesh, P(None, *batch_pspec))

        def split(t):
            t = jnp.moveaxis(t.reshape(mb, accum_steps, s), 1, 0)
            return jax.lax.with_sharding_constraint(t, mb_sharding)

        xs, ts = split(inputs), split(targets)

        def body(carry, xt):
            acc_loss, acc_grads = carry
            x, t = xt
            loss, grads = jax.value_and_grad(loss_fn)(params, x, t)
            acc_grads = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), acc_grads, grads
            )
            return (acc_loss + loss, acc_grads), None

        init = (
            jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ),
        )
        (tot_loss, tot_grads), _ = jax.lax.scan(body, init, (xs, ts))
        inv = 1.0 / accum_steps
        grads = jax.tree_util.tree_map(
            lambda p, g: (g * inv).astype(p.dtype), params, tot_grads
        )
        return tot_loss * inv, grads

    def step(params, opt_state, inputs, targets):
        loss, grads = grad_step(params, inputs, targets)
        with jax.named_scope("train/optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if shard_opt_state:
        dp_axes = tuple(
            a for a in (party_axis, data_axis)
            if a and a in mesh.axis_names and mesh.shape[a] > 1
        )
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]

        def _zero1(param_spec: P, leaf) -> NamedSharding:
            # Extend the parameter's own spec (moments keep the tp layout)
            # by sharding the first unsharded, divisible dim over the dp
            # axes — ZeRO-1: each dp rank keeps 1/dp of the moments.
            spec = list(param_spec) + [None] * (leaf.ndim - len(param_spec))
            if dp_size > 1:
                for i, entry in enumerate(spec):
                    if entry is None and leaf.shape[i] and \
                            leaf.shape[i] % dp_size == 0:
                        spec[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                        break
            return NamedSharding(mesh, P(*spec))

        def _dict_path(path):
            return tuple(
                p.key for p in path
                if isinstance(p, jax.tree_util.DictKey)
            )

        def _opt_shardings(params):
            is_spec = lambda x: isinstance(x, P)  # noqa: E731
            param_specs = jax.tree_util.tree_map(
                lambda s: shd.prune_spec_to_mesh(s, mesh),
                shd.make_param_specs(params), is_leaf=is_spec,
            )
            # optax states embed param-shaped dict trees (mu/nu); an opt
            # leaf's dict-key path equals its parameter's, while non-param
            # leaves (count scalars) match nothing and replicate.
            flat_specs = {
                _dict_path(path): spec
                for path, spec in jax.tree_util.tree_flatten_with_path(
                    param_specs, is_leaf=is_spec
                )[0]
            }
            opt_shapes = jax.eval_shape(optimizer.init, params)

            def for_leaf(path, leaf):
                spec = flat_specs.get(_dict_path(path))
                if spec is None or leaf.ndim < len(spec):
                    spec = P()
                return _zero1(spec, leaf)

            return jax.tree_util.tree_map_with_path(for_leaf, opt_shapes)

    def init_fn(rng, sample_tokens):
        params = tfm.init_params(rng, cfg)
        params = shd.shard_params(mesh, params)
        if shard_opt_state:
            shardings = _opt_shardings(params)
            opt_state = jax.jit(
                optimizer.init, out_shardings=shardings
            )(params)
        else:
            # Moment tensors inherit each parameter's sharding via XLA's
            # sharding propagation — no explicit out_shardings needed.
            opt_state = jax.jit(optimizer.init)(params)
        return params, opt_state

    # ``donate=True`` (default) aliases params/opt_state buffers into the
    # update — the right memory trade on TPU. Contract (jax's own rule
    # for aliased values): buffers handed to OTHER consumers must not be
    # donated afterwards. Cross-party pushes on the socket lanes are
    # capture-protected (the engine snapshots pushed values at
    # resolution, barriers.py); under ``device_dma`` donate only after
    # the send resolves. A fed task that RETURNS its params for LOCAL
    # consumption (e.g. an actor whose result feeds fed_aggregate in the
    # same party) must pass donate=False or return a copy — zero-copy
    # local chaining hands device arrays by reference. This contract is
    # machine-checked: fedlint rule FEDLINT_DONATION_RULE (module-level
    # anchor above) flags
    # drivers that return donated step outputs (docs/fedlint.md).
    step_fn = jax.jit(
        step,
        in_shardings=(None, None, batch_sharding, batch_sharding),
        donate_argnums=(0, 1) if donate else (),
    )
    return init_fn, step_fn
