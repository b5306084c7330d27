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

"""Cross-party collective lanes: federated aggregation as XLA collectives.

SURVEY.md §7 stage 5 and the BASELINE.json north star: FedAvg weight
aggregation lowers to a cross-slice ``psum`` over a *joint* mesh whose
leading axis enumerates parties, instead of point-to-point pushes.

Two deployment shapes:

 - **Joint-process lane** (this module): every party's shard lives in one
   JAX process group (a real multi-slice pod with ``jax.distributed``, the
   driver's multi-chip dry-run, or CPU simulation). ``cross_party_mean``
   runs one ``shard_map`` program where each party's sub-mesh holds its own
   weights and one ``psum`` over the party axis produces the aggregate —
   bitwise-identical on every party because XLA reduces in a fixed ring
   order.
 - **Push lane** (the default engine path): parties in separate processes
   push weight trees over the data plane and reduce with
   :func:`rayfed_tpu.ops.aggregate.tree_mean` — same math, pinned
   accumulation dtype, deterministic fold order.

The data-perimeter asymmetry (owner pushes, SURVEY.md §7 "hard parts") is
preserved at the API layer: a party enters ``cross_party_mean`` only by
executing the same program line — exactly the multi-controller opt-in the
push lane has.
"""

# fedlint: disable-file=seq-divergence
# Role-divergent control flow is this plane's contract: the root
# party reduces while leaves push, so fed.get/send calls are
# deliberately conditioned on party identity. The wire protocol
# (one seq id per collective op, burned on every party) keeps the
# DAG aligned; FED002's same-shape-everywhere rule targets
# drivers, not this engine.

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def party_axis_mesh(n_parties: int, devices=None, inner_axes=("data",),
                    inner_shape=None):
    """Build a joint mesh with a leading ``party`` axis.

    Default: shape (n_parties, n_devices/n_parties) with one inner axis,
    e.g. 8 devices, 2 parties -> ('party': 2, 'data': 4). For multi-axis
    party sub-meshes pass matching ``inner_axes`` and ``inner_shape``, e.g.
    ``inner_axes=("data", "model"), inner_shape=(2, 2)``. Each party's
    slice is ``mesh.devices[p]``.
    """
    import math

    import numpy as np

    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if n % n_parties != 0:
        raise ValueError(f"{n} devices not divisible by {n_parties} parties")
    inner_total = n // n_parties
    if inner_shape is None:
        if len(inner_axes) != 1:
            raise ValueError(
                "inner_shape is required when inner_axes has more than one axis"
            )
        inner_shape = (inner_total,)
    if len(inner_shape) != len(inner_axes):
        raise ValueError(f"{inner_axes=} does not match {inner_shape=}")
    if math.prod(inner_shape) != inner_total:
        raise ValueError(
            f"inner_shape {inner_shape} must cover {inner_total} devices/party"
        )
    dev = np.array(devices).reshape((n_parties,) + tuple(inner_shape))
    return Mesh(dev, ("party",) + tuple(inner_axes))


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "party_axis", "op", "acc_dtype", "specs"),
)
def _cross_party_reduce(tree, mesh: Mesh, party_axis: str, op: str,
                        acc_dtype: Optional[str], specs):
    n_parties = mesh.shape[party_axis]

    def body(local_tree):
        def leaf(x):
            orig = x.dtype
            if acc_dtype is not None:
                x = x.astype(acc_dtype)
            s = jax.lax.psum(x, axis_name=party_axis)
            if op == "mean":
                s = s / n_parties
            return s.astype(orig)

        return jax.tree_util.tree_map(leaf, local_tree)

    # Party-sharded in, party-sharded (replicated value) out: every party's
    # sub-mesh ends up holding the identical aggregate. Leaves keep their
    # inner-axis sharding through the reduce.
    treedef = jax.tree_util.tree_structure(tree)
    spec_tree = jax.tree_util.tree_unflatten(treedef, list(specs))
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_tree,),
        out_specs=spec_tree,
    )(tree)


def _leaf_spec(x, mesh: Mesh, party_axis: str):
    s = getattr(x, "sharding", None)
    if (
        isinstance(s, NamedSharding)
        and len(s.spec) > 0
        and s.spec[0] == party_axis
        and all(
            n in mesh.axis_names
            for e in s.spec
            for n in ([] if e is None else [e] if isinstance(e, str) else e)
        )
    ):
        return P(*s.spec)
    return P(party_axis)


def cross_party_reduce(tree, mesh: Mesh, party_axis: str = "party",
                       op: str = "mean", acc_dtype: Optional[str] = "float32"):
    """Reduce a pytree whose leaves carry a leading party dimension sharded
    over ``party_axis``; each party's slot receives the aggregate.

    Leaves must have shape ``(n_parties, ...)`` with the leading dim sharded
    on the party axis (use :func:`stack_party_tree` to build them); inner
    dims may additionally be sharded over the mesh's other axes, and that
    layout is preserved through the reduce.
    """
    assert op in ("mean", "sum"), op
    specs = tuple(
        _leaf_spec(x, mesh, party_axis)
        for x in jax.tree_util.tree_leaves(tree)
    )
    return _cross_party_reduce(tree, mesh, party_axis, op, acc_dtype, specs)


def stack_party_tree(per_party_trees, mesh: Mesh, party_axis: str = "party"):
    """Stack per-party weight trees along a new leading axis and shard that
    axis over the party sub-meshes (host staging lane, used in simulation
    and tests; on a real pod each party's shard is already device-resident)."""
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *per_party_trees
    )
    sharding = NamedSharding(mesh, P(party_axis))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), stacked
    )


def cross_party_mean(per_party_trees, mesh: Optional[Mesh] = None,
                     party_axis: str = "party"):
    """One-call FedAvg over the joint mesh: stack, psum, unstack.

    Returns the aggregate tree (identical content in every party slot).
    """
    if mesh is None:
        mesh = party_axis_mesh(len(per_party_trees))
    stacked = stack_party_tree(per_party_trees, mesh, party_axis)
    reduced = cross_party_reduce(stacked, mesh, party_axis, op="mean")
    # Every party slot now holds the aggregate; slot 0 is representative.
    return jax.tree_util.tree_map(lambda x: x[0], reduced)


# ---------------------------------------------------------------------------
# Cross-process joint collective (VERDICT r1 #4 / SURVEY §7 hard part #1)
# ---------------------------------------------------------------------------
#
# Real parties are separate OS processes. Opt-in via
# ``fed.init(config={"collective": {"coordinator": "host:port"}})``: all
# parties join ONE jax.distributed process group (process ranks follow the
# sorted party order), after which ``fed_collective_mean`` lowers FedAvg to
# a cross-process psum over DCN/ICI — gated on a control-plane rendezvous
# so the owner-push perimeter survives: a party's shard enters the
# collective only after every peer announced the same collective id over
# the ordinary data plane, and peers that never announce fail the call
# instead of wedging XLA inside a half-entered collective.

import itertools
import threading as _threading

_joint_lock = _threading.Lock()  # fedlint: disable=global-mutable-singleton (joint collective state; clear_joint_collective() at shutdown)
_joint_mesh: Optional[Mesh] = None  # fedlint: disable=global-mutable-singleton (joint collective state; clear_joint_collective() at shutdown)
_joint_party_order = None  # fedlint: disable=global-mutable-singleton (joint collective state; clear_joint_collective() at shutdown)
_joint_self_party: Optional[str] = None  # fedlint: disable=global-mutable-singleton (joint collective state; clear_joint_collective() at shutdown)
# True iff THIS module created the jax.distributed group (the process
# group outlives fed shutdown; repeat inits may reuse it, foreign groups
# must not be mistaken for it).
_joint_group_owned = False  # fedlint: disable=global-mutable-singleton (joint collective state; clear_joint_collective() at shutdown)
_collective_seq = itertools.count(1)


def init_joint_collective(
    addresses,
    self_party: str,
    coordinator_address: str,
    inner_axes=("data",),
    inner_shape=None,
    init_timeout_s: float = 120.0,
) -> Optional[Mesh]:
    """Join the cross-party jax.distributed group and build the joint
    party mesh. Process rank = index in sorted party order; jax assigns
    global device ids by rank, so the mesh's leading ``party`` rows line
    up with each process's local devices.

    Best-effort: if the group cannot form within ``init_timeout_s`` (a
    party missing the collective config, network issues), this logs a
    warning and returns None — ``fed_collective_mean`` then negotiates
    everyone onto the push lane instead of half the parties wedging.
    """
    import logging
    import time

    global _joint_mesh, _joint_party_order, _joint_self_party
    party_order = sorted(addresses)
    rank = party_order.index(self_party)
    log = logging.getLogger(__name__)

    # Pre-flight over OUR control plane before touching jax.distributed:
    # its join (and the first jax.devices()) can block without honoring
    # timeouts when a party never arrives, so nobody enters it until every
    # party confirmed it is about to. A party missing the collective
    # config simply never confirms, and the others degrade cleanly here.
    from rayfed_tpu.proxy import barriers

    peers = [p for p in party_order if p != self_party]
    for p in peers:
        barriers.send(
            p, {"join": "collective"},
            upstream_seq_id=f"coljoin:{self_party}",
            downstream_seq_id="coljoin",
        )
    deadline = time.monotonic() + init_timeout_s
    for p in peers:
        fut = barriers.receiver_proxy().get_data(p, f"coljoin:{p}", "coljoin")
        try:
            fut.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:  # noqa: BLE001 - degrade to push lane
            log.warning(
                "party %s did not confirm joining the collective group "
                "within %.0fs; FedAvg stays on the push lane.",
                p, init_timeout_s,
            )
            return None

    global _joint_group_owned
    try:
        if jax.distributed.is_initialized():
            # A pre-existing process group is only trustworthy if WE
            # formed it (repeat fed.init in one process). Anything else —
            # a multi-host party's private group, a user's own group,
            # even one whose size coincidentally matches the party count
            # — is NOT the joint all-parties group; psumming over it
            # would silently aggregate the wrong set of processes.
            if not _joint_group_owned:
                log.warning(
                    "jax.distributed was initialized outside the "
                    "collective lane; refusing to treat it as the joint "
                    "all-parties group — FedAvg stays on the push lane.",
                )
                return None
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=len(party_order),
                process_id=rank,
                initialization_timeout=max(1, int(init_timeout_s)),
            )
            _joint_group_owned = True
    except Exception as e:  # noqa: BLE001 - degrade to push lane
        log.warning(
            "joint collective group did not form (%s); FedAvg stays on "
            "the push lane.", e,
        )
        return None
    with _joint_lock:
        _joint_mesh = party_axis_mesh(
            len(party_order), devices=jax.devices(),
            inner_axes=tuple(inner_axes), inner_shape=inner_shape,
        )
        _joint_party_order = party_order
        _joint_self_party = self_party
    return _joint_mesh


def joint_collective_ready() -> bool:
    return _joint_mesh is not None


def clear_joint_collective() -> None:
    """Forget the joint mesh (fed.shutdown). The jax.distributed group
    itself outlives the fed runtime — platform/process-group choices are
    process-wide and irreversible (see mesh.init_distributed)."""
    global _joint_mesh, _joint_party_order, _joint_self_party
    with _joint_lock:
        _joint_mesh = None
        _joint_party_order = None
        _joint_self_party = None


def _stack_local_shard(leaf, mesh: Mesh, party_axis: str):
    """Global (n_parties, ...) array whose party slots are each process's
    local tree — built from THIS process's data only (other parties' slots
    live on their devices).

    When the leaf is already a ``jax.Array`` sharded on axes the joint
    mesh shares, its tiles are re-used device-to-device and the stacked
    array keeps that inner sharding (no host round-trip, no per-device
    replication of a leaf that only fits sharded). Host/numpy leaves are
    staged once and replicated across the party's local devices.
    """
    import numpy as np

    n_parties = mesh.shape[party_axis]
    global_shape = (n_parties,) + tuple(int(d) for d in leaf.shape)

    sharding_in = getattr(leaf, "sharding", None)
    if isinstance(sharding_in, NamedSharding) and getattr(
        leaf, "is_fully_addressable", False
    ):
        inner_spec = tuple(sharding_in.spec)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

        def spec_ok(entry):
            names = (
                [] if entry is None
                else [entry] if isinstance(entry, str) else list(entry)
            )
            return all(
                n in sizes and sizes[n] == sharding_in.mesh.shape[n]
                for n in names
            )

        if all(spec_ok(e) for e in inner_spec):
            target = NamedSharding(mesh, P(party_axis, *inner_spec))

            def norm(idx, shape):
                return tuple(
                    (0 if s.start is None else int(s.start),
                     dim if s.stop is None else int(s.stop))
                    for s, dim in zip(idx, shape)
                )

            tiles = {
                norm(s.index, leaf.shape): s.data
                for s in leaf.addressable_shards
            }
            arrays = []
            for d, idx in target.addressable_devices_indices_map(
                global_shape
            ).items():
                tile = tiles.get(norm(idx[1:], leaf.shape))
                if tile is None:
                    arrays = None
                    break  # layouts disagree -> host path below
                arrays.append(jax.device_put(tile[None], d))
            if arrays is not None:
                return jax.make_array_from_single_device_arrays(
                    global_shape, target, arrays
                )

    local = np.asarray(leaf)
    sharding = NamedSharding(mesh, P(party_axis))
    slab = local[None]
    arrays = [
        jax.device_put(slab, d) for d in sharding.addressable_devices
    ]
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, arrays
    )


def fed_collective_mean(
    local_tree,
    collective_id: Optional[str] = None,
    timeout_s: float = 120.0,
    party_axis: str = "party",
    device_out: bool = False,
):
    """Cross-party FedAvg over the joint process group.

    Every party calls this with its own local tree (multi-controller, same
    program line). Control-plane gating is TWO-PHASE (announce -> all-ack
    -> enter), so entering the psum implies every peer has *committed*,
    not merely expressed intent:

      1. announce: push an intent frame for ``collective_id`` to every
         peer and wait (``timeout_s``) for all peers' intents. A peer that
         never opts in raises TimeoutError here, on the control plane,
         instead of a hang inside the collective.
      2. commit: having seen every announcement, push a commit-ack and
         wait (a fresh ``timeout_s``) for every peer's ack. A party whose
         phase-1 wait expired never acks, so a *late announcer* — one
         whose intent arrived after a peer's deadline — fails here rather
         than stranding itself inside an XLA collective the timed-out
         peer will never join. (The residual window is an ack frame
         delayed > ``timeout_s`` between two live parties that both saw
         all announcements — network-only, no application latency.)

    Without a joint group the call falls back to the push lane
    (``federated.fed_aggregate`` + broadcast), same math.

    Returns the aggregate tree (identical bytes in every party, XLA's
    fixed reduction order). With ``device_out=True`` the psum lane keeps
    each leaf as a sharded ``jax.Array`` on this party's sub-mesh (no
    host round-trip for a consumer that immediately trains on the
    aggregate); the push-lane fallback returns host arrays regardless.
    """
    from rayfed_tpu._private.global_context import get_global_context

    ctx = get_global_context()
    assert ctx is not None, "fed.init() first"
    if collective_id is None:
        collective_id = f"auto{next(_collective_seq)}"

    from rayfed_tpu.api import _get_addresses

    addresses = _get_addresses(ctx.get_job_name())
    self_party = ctx.get_current_party()
    peers = sorted(p for p in addresses if p != self_party)
    my_lane = "psum" if joint_collective_ready() else "push"

    # Phase 1 (announce): edge key (col:<id>:<sender>, col:<id>) is
    # unique per sender; both sides may arrive in any order (rendezvous
    # store). Exchanging the LANE too keeps mixed deployments convergent:
    # if any party lacks the joint group, everyone takes the push lane
    # rather than half the parties wedging inside a psum.
    acks = _gate_exchange(
        peers, "col", collective_id, self_party,
        {"collective": collective_id, "lane": my_lane},
        "collective", timeout_s,
        "never announced collective {id!r}; not entering the psum "
        "(control-plane gate)",
    )
    lanes = {self_party: my_lane}
    lanes.update(
        (p, ack.get("lane", "psum")) for p, ack in acks.items()
    )

    if any(lane != "psum" for lane in lanes.values()):
        return _push_lane_mean(local_tree)

    # Phase 2 (commit): every peer announced; tell them we are committed
    # and wait for their commitment. All parties compute the same uniform
    # lane decision, so ack frames flow iff the decision was psum.
    _gate_exchange(
        peers, "colack", collective_id, self_party,
        {"collective_ack": collective_id},
        "collective_ack", timeout_s,
        "announced but never committed to collective {id!r} (its "
        "announce wait likely timed out); not entering the psum "
        "(two-phase gate)",
    )

    mesh = _joint_mesh
    rank = _joint_party_order.index(self_party)
    stacked = jax.tree_util.tree_map(
        lambda x: _stack_local_shard(x, mesh, party_axis), local_tree
    )
    reduced = cross_party_reduce(stacked, mesh, party_axis, op="mean")
    if device_out:
        return jax.tree_util.tree_map(
            lambda x: _local_aggregate_device(x, mesh, party_axis, rank),
            reduced,
        )
    return jax.tree_util.tree_map(_local_aggregate, reduced)


def _gate_exchange(peers, prefix, collective_id, self_party, payload,
                   id_field, timeout_s, timeout_msg):
    """One gate phase: push ``payload`` to every peer under the
    (``{prefix}:<id>:<sender>``, ``{prefix}:<id>``) edge, then wait (one
    shared ``timeout_s`` deadline across all peers) for every peer's
    frame. Returns {peer: frame}; raises TimeoutError (message from
    ``timeout_msg``) or RuntimeError on id mismatch (program
    divergence)."""
    import time

    from rayfed_tpu.proxy import barriers

    for p in peers:
        barriers.send(
            p, payload,
            upstream_seq_id=f"{prefix}:{collective_id}:{self_party}",
            downstream_seq_id=f"{prefix}:{collective_id}",
        )
    waits = {
        p: barriers.receiver_proxy().get_data(
            p, f"{prefix}:{collective_id}:{p}", f"{prefix}:{collective_id}"
        )
        for p in peers
    }
    deadline = time.monotonic() + timeout_s
    frames = {}
    for p, fut in waits.items():
        try:
            frame = fut.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception as e:  # noqa: BLE001 - surfaced with context
            raise TimeoutError(
                f"party {p} " + timeout_msg.format(id=collective_id)
            ) from e
        if frame.get(id_field) != collective_id:
            raise RuntimeError(
                f"party {p} sent {frame.get(id_field)!r} for {id_field}, "
                f"expected {collective_id!r} — program divergence"
            )
        frames[p] = frame
    return frames


def _local_aggregate_device(x, mesh: Mesh, party_axis: str, rank: int):
    """This party's aggregate as a device-resident sharded ``jax.Array``
    on the party's sub-mesh: re-uses the reduced tiles in place (each
    local device already holds its (1, ...) slab of the global result),
    so no host staging happens between aggregation and the next train
    step."""
    inner_axes = tuple(n for n in mesh.axis_names if n != party_axis)
    local_mesh = Mesh(mesh.devices[rank], inner_axes)
    spec = tuple(x.sharding.spec)
    target = NamedSharding(local_mesh, P(*spec[1:]))
    shape = x.shape[1:]
    tiles = {sh.device: sh.data for sh in x.addressable_shards}
    arrays = [
        tiles[d][0]
        for d in target.addressable_devices_indices_map(shape)
    ]
    return jax.make_array_from_single_device_arrays(shape, target, arrays)


def _local_aggregate(x):
    """This party's aggregate from a reduced global (n_parties, ...) leaf:
    assembled host-side from the local (possibly inner-sharded) tiles."""
    import numpy as np

    shards = list(x.addressable_shards)
    first = np.asarray(shards[0].data)[0]
    if len(shards) == 1:
        return first
    indices = {
        tuple(
            (0 if s.start is None else s.start, s.stop)
            for s in sh.index
        )
        for sh in shards
    }
    if len(indices) == 1:
        return first  # replicated across the party's devices
    out = np.empty(x.shape[1:], x.dtype)
    for sh in shards:
        out[tuple(sh.index[1:])] = np.asarray(sh.data)[0]
    return out


def _push_lane_mean(local_tree):
    """Push-lane fallback: identity task per party -> hierarchical
    fed_aggregate -> broadcast via fed.get."""
    import rayfed_tpu as fed
    from rayfed_tpu._private.global_context import get_global_context
    from rayfed_tpu.api import _get_addresses
    from rayfed_tpu.federated import fed_aggregate

    addresses = _get_addresses(get_global_context().get_job_name())
    parties = sorted(addresses)

    @fed.remote
    def _own_tree(t):
        return t

    objs = {p: _own_tree.party(p).remote(local_tree) for p in parties}
    agg = fed_aggregate(objs, op="mean")
    return fed.get(agg)
