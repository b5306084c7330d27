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

"""Party device-mesh management (TPU-native; no reference equivalent).

``fed.init`` binds each party to a sub-mesh of the local devices (SURVEY.md
§3.1: "In a TPU build `init` additionally establishes the party-slice
mesh"). Party-local tasks jit onto this mesh; the TPU transport places
received arrays onto it; federated aggregation uses the joint mesh helpers
in :mod:`rayfed_tpu.collective`.

JAX is imported lazily: control-plane-only processes never pay for it.
"""

from __future__ import annotations

import logging
import math
import os
from typing import List, Optional

from rayfed_tpu.config import PartyMeshConfig

logger = logging.getLogger(__name__)

_party_mesh = None  # fedlint: disable=global-mutable-singleton (mesh cache over the per-process jax runtime; one device set per process)
_party_mesh_config: Optional[PartyMeshConfig] = None  # fedlint: disable=global-mutable-singleton (mesh cache over the per-process jax runtime; one device set per process)


def init_distributed(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    local_device_ids=None,
) -> None:
    """Join a multi-host JAX process group (real multi-host TPU slices).

    A *party* spanning several hosts calls this on each host before
    ``fed.init`` (or passes ``config['jax_distributed']``), after which
    ``jax.devices()`` spans the party's whole slice and the party mesh /
    collectives ride ICI+DCN. Cross-party traffic still flows through the
    fed transport — the process group is per-party, preserving the data
    perimeter.
    """
    import jax

    if jax.distributed.is_initialized():
        # Repeat fed.init in the same process (shutdown()+init() restart
        # pattern): the process group outlives the fed runtime.
        logger.info("jax.distributed already initialized; reusing group.")
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    logger.info(
        "Joined jax.distributed group %s as process %d/%d",
        coordinator_address, process_id, num_processes,
    )


def build_mesh(
    device_ids: Optional[List[int]] = None,
    mesh_shape: Optional[List[int]] = None,
    axis_names: Optional[List[str]] = None,
    platform: Optional[str] = None,
):
    """Create a ``jax.sharding.Mesh`` over the selected local devices.

    Defaults: all local devices, 1-D mesh on axis ``("data",)``.
    ``platform`` demands that every mesh device reports that platform
    and raises otherwise (see :class:`PartyMeshConfig`).
    """
    import jax
    import numpy as np

    devices = jax.devices()
    if device_ids is not None:
        devices = [devices[i] for i in device_ids]
    if platform is not None:
        found = sorted({d.platform for d in devices})
        if found != [platform]:
            raise RuntimeError(
                f"party mesh demands platform {platform!r} but jax "
                f"came up on {found} ({len(devices)} device(s), "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
                "refusing to run this party on another backend"
            )
    n = len(devices)
    if mesh_shape is None:
        mesh_shape = [n]
    if math.prod(mesh_shape) != n:
        raise ValueError(
            f"mesh_shape {mesh_shape} does not cover {n} devices"
        )
    if axis_names is None:
        default_names = ["data", "model", "seq", "expert"]
        axis_names = default_names[: len(mesh_shape)]
        if len(axis_names) < len(mesh_shape):
            axis_names += [f"ax{i}" for i in range(len(axis_names), len(mesh_shape))]
    from jax.sharding import Mesh

    dev_array = np.array(devices).reshape(mesh_shape)
    return Mesh(dev_array, tuple(axis_names))


def init_party_mesh(cfg: Optional[PartyMeshConfig] = None):
    """Establish this party's mesh once, at ``fed.init`` time."""
    global _party_mesh, _party_mesh_config
    cfg = cfg or PartyMeshConfig()
    _party_mesh = build_mesh(
        cfg.device_ids, cfg.mesh_shape, cfg.axis_names, cfg.platform
    )
    _party_mesh_config = cfg
    logger.info(
        "Party mesh established: shape=%s axes=%s",
        dict(zip(_party_mesh.axis_names, _party_mesh.devices.shape)),
        _party_mesh.axis_names,
    )
    return _party_mesh


def get_party_mesh():
    return _party_mesh


def get_party_mesh_config() -> Optional[PartyMeshConfig]:
    return _party_mesh_config


def clear_party_mesh() -> None:
    global _party_mesh, _party_mesh_config
    _party_mesh = None
    _party_mesh_config = None
    clear_composed_mesh()


# ---------------------------------------------------------------------------
# Composed party mesh (same-mesh fast path)
# ---------------------------------------------------------------------------
#
# When the parties of a job are colocated on one device pool — the CPU
# simulator, a single-host multi-party test rig, or a pod slice shared via
# jax.distributed — their sub-meshes compose into ONE mesh with a leading
# "party" axis (party x data x model ...). Registering that composition
# unlocks the same-mesh fast paths: pushes lower to jax.device_put onto
# the destination party's sub-mesh (no wire, no host staging) and flat
# aggregation plans lower to a single collective across the party axis
# (ops.aggregate.psum_by_plan). The registry is process-local and
# strictly opt-in; nothing engages unless it is populated.

_composed_mesh = None  # fedlint: disable=global-mutable-singleton (mesh cache over the per-process jax runtime; one device set per process)
_composed_parties: Optional[tuple] = None  # fedlint: disable=global-mutable-singleton (mesh cache over the per-process jax runtime; one device set per process)


def compose_party_mesh(parties, devices=None, inner_axes=None,
                       inner_shape=None):
    """Compose and register the job's party x data x model mesh.

    ``parties`` fixes the party-axis order (coordinate p on the "party"
    axis IS ``parties[p]``), so every process must pass the same order —
    sorted names or config order, the multi-controller contract. Inner
    axes default to this party's established mesh shape (so the composed
    mesh is party x <party mesh>), else a 1-D ``data`` axis.
    """
    global _composed_mesh, _composed_parties
    from rayfed_tpu.collective import party_axis_mesh

    parties = tuple(dict.fromkeys(parties))
    if len(parties) < 2:
        raise ValueError("composing a party mesh needs at least 2 parties")
    if inner_axes is None:
        if _party_mesh is not None:
            inner_axes = tuple(str(a) for a in _party_mesh.axis_names)
            if inner_shape is None:
                inner_shape = tuple(int(d) for d in _party_mesh.devices.shape)
        else:
            inner_axes = ("data",)
    composed = party_axis_mesh(
        len(parties), devices=devices,
        inner_axes=tuple(inner_axes), inner_shape=inner_shape,
    )
    _composed_mesh = composed
    _composed_parties = parties
    logger.info(
        "Composed party mesh registered: parties=%s shape=%s",
        parties, dict(zip(composed.axis_names, composed.devices.shape)),
    )
    return composed


def composed_mesh_for(parties):
    """The registered composed mesh iff it covers exactly ``parties`` in
    the registered order (plans index the party axis by position), else
    None."""
    if _composed_mesh is None or tuple(parties) != _composed_parties:
        return None
    return _composed_mesh


def get_composed_parties() -> Optional[tuple]:
    return _composed_parties


def party_submesh(party: str):
    """One party's inner sub-mesh of the composed mesh (its slice along
    the party axis, with the inner axes only), or None when no composed
    mesh covers it."""
    if _composed_mesh is None or party not in (_composed_parties or ()):
        return None
    from jax.sharding import Mesh

    i = _composed_parties.index(party)
    return Mesh(
        _composed_mesh.devices[i], tuple(_composed_mesh.axis_names[1:])
    )


def clear_composed_mesh() -> None:
    global _composed_mesh, _composed_parties
    _composed_mesh = None
    _composed_parties = None
