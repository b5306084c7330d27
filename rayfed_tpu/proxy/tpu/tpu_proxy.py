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

"""TPU data-plane transport: the TCP wire + device placement on arrival.

SURVEY.md §7 stage 4 (C5/C14 replacement): payloads already cross the wire
as raw array bytes (the ``tree`` fast path in
``rayfed_tpu/_private/serialization.py``); this backend completes the lane
by materializing received arrays **directly onto the party's device mesh**
(``jax.device_put`` onto a NamedSharding) inside the receiver's decode
worker, so the consumer task's jit sees device-resident inputs and never
pays a host round-trip at call time.

On a real multi-slice pod the same proxy pair runs per-host with DCN/ICI
underneath the sockets; cross-party *aggregation* additionally gets a
collective lane (``rayfed_tpu.collective``) that lowers FedAvg-style sums
to ``psum`` over the joint mesh instead of point-to-point pushes.
"""

from __future__ import annotations

import itertools
import logging
import threading
from collections import OrderedDict

from rayfed_tpu import tracing
from rayfed_tpu.proxy import lanes, rendezvous
from rayfed_tpu.proxy.tcp.tcp_proxy import TcpReceiverProxy, TcpSenderProxy

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Same-mesh push fast path (``same_mesh_push: true``; colocated parties)
# ---------------------------------------------------------------------------
#
# When both parties of a push share this process's composed party mesh
# (``mesh.compose_party_mesh``), the payload never needs the wire at all:
# the sender ``jax.device_put``s every leaf onto the DESTINATION party's
# sub-mesh (a device-to-device scatter over the party axis), parks the
# placed tree in this table, and ships only a tiny ``meshref`` token
# frame. The receiver's decode resolves the token back to the already-
# placed tree. Process-local by construction — the config knob documents
# that it must only be enabled for colocated deployments.

_SAME_MESH_CAP = 1024  # leak bound: failed sends evict via on_done

_same_mesh_lock = threading.Lock()  # fedlint: disable=global-mutable-singleton (same-mesh table over the per-process TPU runtime)
_same_mesh_table: "OrderedDict[int, object]" = OrderedDict()  # fedlint: disable=global-mutable-singleton (same-mesh table over the per-process TPU runtime)
_same_mesh_tokens = itertools.count(1)


def _try_post_same_mesh(value, dest_party):
    """Place ``value`` onto ``dest_party``'s sub-mesh and park it for the
    in-process receiver. Returns ("meshref", payload, on_done) or None
    when the fast path does not apply (no composed mesh for the
    destination, or a non-array leaf)."""
    import sys

    j = sys.modules.get("jax")
    if j is None or dest_party is None:
        return None
    from rayfed_tpu import tree_util
    from rayfed_tpu.mesh import party_submesh

    submesh = party_submesh(dest_party)
    if submesh is None:
        return None
    try:
        leaves, _ = tree_util.tree_flatten(value)
    except Exception:  # noqa: BLE001 - unflattenable -> wire lane
        return None
    import numpy as np

    if not leaves or not all(
        isinstance(x, (j.Array, np.ndarray)) for x in leaves
    ):
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(submesh, PartitionSpec())
    try:
        placed = j.tree_util.tree_map(
            lambda x: j.device_put(x, sharding), value
        )
    except Exception as e:  # noqa: BLE001 - placement refused -> wire lane
        logger.debug("same-mesh placement declined: %s", e)
        return None
    token = next(_same_mesh_tokens)
    with _same_mesh_lock:
        _same_mesh_table[token] = placed
        while len(_same_mesh_table) > _SAME_MESH_CAP:
            _same_mesh_table.popitem(last=False)

    def on_done(ok: bool) -> None:
        if not ok:
            with _same_mesh_lock:
                _same_mesh_table.pop(token, None)

    import msgpack

    return "meshref", msgpack.packb({"tok": token}), on_done


def _take_same_mesh(payload):
    import msgpack

    tok = msgpack.unpackb(bytes(memoryview(payload)), raw=False)["tok"]
    with _same_mesh_lock:
        placed = _same_mesh_table.pop(tok, None)
    if placed is None:
        raise ValueError(
            f"same-mesh reference {tok} not found in this process: "
            "same_mesh_push requires sender and receiver parties to be "
            "colocated (see cross_silo_comm.same_mesh_push)"
        )
    return placed


def clear_same_mesh() -> None:
    """Reset hook: drop parked same-mesh references (last-job shutdown)."""
    with _same_mesh_lock:
        _same_mesh_table.clear()


class TpuSenderProxy(TcpSenderProxy):
    """Sender side: identical wire behavior; arrays (jax or numpy) ride the
    zero-pickle tree encoding. Device→host staging happens in the encode
    worker (``np.asarray`` on a jax.Array) off the event loop.

    With ``device_dma: true`` in the comm config, all-jax-Array payloads
    skip host staging entirely: the buffers are parked on this process's
    transfer server and only a descriptor frame crosses the socket (see
    :mod:`rayfed_tpu.proxy.tpu.dma`). With ``same_mesh_push: true`` and a
    composed party mesh registered, the payload is device_put straight
    onto the destination party's sub-mesh and only a reference frame is
    sent (colocated deployments)."""

    _TRANSPORT = "tpu"  # fed_transport_send_ops_total{transport="tpu"}

    def _try_encode_special(self, value, is_error: bool, cfg,
                            dest_party=None):
        if is_error:
            return None
        if lanes.meshref_enabled(cfg):
            posted = _try_post_same_mesh(value, dest_party)
            if posted is not None:
                return posted
        if not lanes.dma_enabled(cfg):
            return None
        from rayfed_tpu.proxy.tpu import dma

        reg = dma.try_register(value, cfg.dma_listen_addr)
        if reg is None:
            return None  # not all-array / server unavailable -> socket lane
        header_fields, payload, on_done = reg
        return header_fields["pkind"], payload, on_done


def _device_placer(allowed_list, allow_pickle: bool = True,
                   max_decompressed_bytes=None, device_dma: bool = False,
                   dma_listen_addr: str = "127.0.0.1:0"):
    base = rendezvous.default_decode(
        allowed_list, allow_pickle=allow_pickle, sharded_fn=place_sharded,
        max_decompressed_bytes=max_decompressed_bytes,
    )

    def decode(header, payload):
        if header.get("pkind") == "meshref":
            # Same-mesh push: the tree is already device-resident on this
            # party's sub-mesh — resolve the in-process reference as-is.
            return _take_same_mesh(payload)
        if header.get("pkind") == "dma":
            if not device_dma:
                raise ValueError(
                    "received a device-DMA frame but device_dma is not "
                    "enabled on this party's comm config"
                )
            from rayfed_tpu.proxy.tpu import dma

            # The receiver's payload cap applies to declared DMA sizes
            # too: a tiny descriptor must not be able to command a huge
            # allocation (dma.pull validates before allocating).
            value = dma.pull(payload, dma_listen_addr,
                             max_bytes=max_decompressed_bytes)
        else:
            with tracing.phase("fed:wire:deserialize"):
                value = base(header, payload)
        mesh = _party_mesh()
        if mesh is None:
            return value
        # Ends where the host's work ends (device_put has returned), not
        # where the tree is resident: no block_until_ready is added.
        with tracing.phase("fed:wire:place"):
            return _place_tree(value, mesh)

    return decode


def _party_mesh():
    from rayfed_tpu.mesh import get_party_mesh

    return get_party_mesh()


def _place_tree(value, mesh):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    # Replicated placement by default: cross-party payloads (weights,
    # aggregates) are consumed by every device of the party mesh. Sharded
    # placement is the caller's move via pjit/with_sharding_constraint in
    # the consuming task.
    sharding = NamedSharding(mesh, PartitionSpec())

    def place(leaf):
        if isinstance(leaf, np.ndarray):
            return jax.device_put(leaf, sharding)
        return leaf

    return jax.tree_util.tree_map(place, value)


def _mirror_sharding(mesh, desc):
    """The sender's PartitionSpec re-expressed on this party's mesh, or
    None when the mesh cannot host it (missing axes / non-dividing dims)."""
    from jax.sharding import NamedSharding, PartitionSpec

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = []
    for dim, e in zip(desc["shape"], desc["spec"]):
        names = [] if e is None else ([e] if isinstance(e, str) else list(e))
        if not all(n in sizes for n in names):
            return None
        k = 1
        for n in names:
            k *= sizes[n]
        if k > 1 and dim % k != 0:
            return None
        if not names:
            entries.append(None)
        elif len(names) == 1:
            entries.append(names[0])
        else:
            entries.append(tuple(names))
    while entries and entries[-1] is None:
        entries.pop()  # PartitionSpec('x', None) != PartitionSpec('x')
    return NamedSharding(mesh, PartitionSpec(*entries))


def _extract_region(desc, payload, region):
    from rayfed_tpu._private.serialization import extract_region

    return extract_region(desc, payload, region)


def place_sharded(desc, payload):
    """Reassemble a ``sharr`` wire leaf directly onto the party mesh.

    Per-device slices are staged host-side individually and joined with
    ``jax.make_array_from_single_device_arrays`` — no host buffer of the
    global array is materialized when the local mesh mirrors the sender's
    partitioning (SURVEY §7 stage 4 north star).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from rayfed_tpu._private.serialization import assemble_global

    mesh = _party_mesh()
    if mesh is None:
        return assemble_global(desc, payload)
    shape = tuple(desc["shape"])
    target = _mirror_sharding(mesh, desc)
    if target is None:
        # Mesh can't express the sender's layout: replicate (dense path).
        return jax.device_put(
            assemble_global(desc, payload),
            NamedSharding(mesh, PartitionSpec()),
        )
    idx_map = target.addressable_devices_indices_map(shape)
    arrays = []
    for device, index in idx_map.items():
        region = [
            [0 if sl.start is None else int(sl.start),
             dim if sl.stop is None else int(sl.stop)]
            for sl, dim in zip(index, shape)
        ]
        slab = _extract_region(desc, payload, region)
        arrays.append(jax.device_put(slab, device))
    return jax.make_array_from_single_device_arrays(shape, target, arrays)


class TpuReceiverProxy(TcpReceiverProxy):
    def _make_decode_fn(self):
        return _device_placer(
            self._config.serializing_allowed_list,
            allow_pickle=self._config.allow_pickle_payloads,
            max_decompressed_bytes=self._config.effective_max_message_bytes(),
            device_dma=lanes.dma_enabled(self._config),
            dma_listen_addr=getattr(
                self._config, "dma_listen_addr", "127.0.0.1:0"
            ),
        )
