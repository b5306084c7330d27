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

"""Versioned model snapshots with atomic hot swap.

Publishing follows the send path's capture-at-resolution rule
(``barriers._capture_for_send``): the param tree is snapshotted INTO the
bank at publish time, so a trainer that immediately feeds the same
buffers into a donating jitted step cannot tear a generation that is
still decoding against them. A publish is one reference assignment under
the bank lock — a reader either sees the complete old tree or the
complete new tree, never a mix.

In-flight requests pin the version they were admitted under
(refcounted); a retired version's snapshot is dropped only after its last
request finishes, so a swap NEVER aborts or re-bases running decodes.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from rayfed_tpu import tree_util


def _casts(x: Any, dtype: Any) -> bool:
    """Whether :func:`snapshot_tree` casts leaf ``x`` for ``dtype``: a
    floating array that is not in it yet."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    return (
        dtype is not None
        and isinstance(x, (jax.Array, np.ndarray))
        and jnp.issubdtype(x.dtype, jnp.floating)
        and x.dtype != jnp.dtype(dtype)
    )


def cast_nbytes(params: Any, dtype: Any) -> int:
    """Bytes ``snapshot_tree(params, dtype)`` reads through a cast."""
    leaves, _ = tree_util.tree_flatten(params)
    return sum(int(x.nbytes) for x in leaves if _casts(x, dtype))


def snapshot_tree(params: Any, dtype: Any = None) -> Any:
    """Donation/reuse-proof, device-resident capture of a param tree.

    jax.Array leaves are device-copied, keeping their sharding (a later
    donation of the caller's tree cannot invalidate ours). NumPy leaves
    — a tree that crossed the wire on the plain socket lane — are
    uploaded ONCE, here, with ``may_alias=False`` (the host buffer may
    be a recv-pool or shm-ring chunk that is recycled once the caller
    drops it). The engine passes the snapshot into its jitted step on
    every iteration, so a leaf left on the host would be re-uploaded
    whole per decoded token. The tree structure is preserved
    leaf-for-leaf (same treedef the checkpoint lane serializes).

    ``dtype`` (an engine's bank: the dtype the model's programs compute
    in) makes the snapshot the tree those programs read: a floating leaf
    in another dtype is cast, and the cast is the new buffer the copy
    would have been, so both trees of a version are never held. Leaves
    go one at a time; an uploaded leaf's wide buffer is dropped before
    the next is uploaded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def leaf(x):
        if _casts(x, dtype):
            if isinstance(x, np.ndarray):
                wide = jax.device_put(x, may_alias=False)
                # `wide` dies with this frame: wait for its last reader.
                return jax.block_until_ready(wide.astype(dtype))
            return x.astype(dtype)
        if isinstance(x, jax.Array):
            # jnp.array(copy=True) always materializes new buffers.
            return jnp.array(x, copy=True)
        if isinstance(x, np.ndarray):
            y = jax.device_put(x, may_alias=False)
            if next(iter(y.devices())).platform == "cpu":
                # The CPU backend aliases a 64-byte-aligned host buffer
                # whatever may_alias says: copy on the device side.
                y = jnp.array(y, copy=True)
            return y
        return x

    leaves, spec = tree_util.tree_flatten(params)
    return tree_util.tree_unflatten([leaf(x) for x in leaves], spec)


class ModelBank:
    """The serving party's versioned snapshot store.

    ``publish`` assigns monotonically increasing versions starting at 1.
    ``acquire``/``release`` bracket a request's use of a version; a
    version with zero in-flight requests that is no longer current is
    retired (its snapshot dropped) so memory stays bounded at
    (current + versions still decoding).

    ``prepare(key, tree)`` takes the snapshot of every tree that enters
    the bank, by ``publish`` or by ``restore_state`` (``key`` is
    ``"params"`` or an extra's name); the default is
    :func:`snapshot_tree`, which keeps the tree as it was given. An
    engine passes its own, so that its bank holds the tree its programs
    read (``InferenceServer._make_snapshot_fn``).
    """

    def __init__(
        self, prepare: Optional[Callable[[str, Any], Any]] = None
    ):
        self._prepare = prepare or (lambda key, tree: snapshot_tree(tree))
        self._lock = threading.Lock()
        self._current: int = 0
        self._snapshots: Dict[int, Any] = {}
        self._extras: Dict[int, Dict[str, Any]] = {}
        self._refs: Dict[int, int] = {}
        self._swap_log: List[Tuple[int, float]] = []

    def publish(self, params: Any, **extras) -> int:
        """Install ``params`` as the next version; returns its number.

        The snapshot is taken OUTSIDE the lock (it device-copies or
        uploads a big tree) and the swap itself is a single assignment
        under it. ``extras`` (e.g. ``draft_params`` for speculative
        serving) are snapshotted and retired together with the version.
        """
        snap, extra_snap = self._snapshots_of(params, extras)
        with self._lock:
            version = self._current + 1
            self._snapshots[version] = snap
            self._extras[version] = extra_snap
            self._refs.setdefault(version, 0)
            self._current = version
            self._swap_log.append((version, time.perf_counter()))
            self._retire_locked()
        return version

    def _snapshots_of(self, params: Any, extras: Dict[str, Any]):
        return self._prepare("params", params), {
            k: self._prepare(k, v) for k, v in extras.items() if v is not None
        }

    def current_version(self) -> int:
        """0 until the first publish."""
        with self._lock:
            return self._current

    def acquire(self) -> Tuple[int, Any]:
        """Pin the current version for one request; returns (version,
        params). Raises if nothing was ever published."""
        with self._lock:
            if self._current == 0:
                raise RuntimeError(
                    "no model published yet — call publish() (or pass "
                    "params= to fed.serve) before submitting requests"
                )
            self._refs[self._current] += 1
            return self._current, self._snapshots[self._current]

    def get(self, version: int) -> Any:
        with self._lock:
            return self._snapshots[version]

    def get_extra(self, version: int, key: str) -> Optional[Any]:
        with self._lock:
            return self._extras.get(version, {}).get(key)

    def release(self, version: int) -> None:
        with self._lock:
            self._refs[version] -= 1
            if self._refs[version] < 0:
                raise ValueError(f"version {version} over-released")
            self._retire_locked()

    def _retire_locked(self) -> None:
        for v in list(self._snapshots):
            if v != self._current and self._refs.get(v, 0) == 0:
                del self._snapshots[v]
                self._extras.pop(v, None)
                self._refs.pop(v, None)

    def live_versions(self) -> List[int]:
        with self._lock:
            return sorted(self._snapshots)

    def swap_count(self) -> int:
        with self._lock:
            return len(self._swap_log)

    # -- state handoff (HA, docs/ha.md) -------------------------------

    def export_state(self) -> Dict[str, Any]:
        """The current version + snapshot (and its extras), for handing
        the serving role to a successor party or a checkpoint cut.
        In-flight pins and retired versions stay behind — a successor
        serves the newest generation; it cannot adopt another process's
        refcounts."""
        with self._lock:
            if self._current == 0:
                return {"version": 0, "params": None, "extras": {}}
            return {
                "version": self._current,
                "params": self._snapshots[self._current],
                "extras": dict(self._extras.get(self._current, {})),
            }

    def restore_state(self, state: Dict[str, Any]) -> int:
        """Adopt an :meth:`export_state` snapshot: install its params
        and CONTINUE its version numbering, so readers that pinned
        "version N" semantics across the handoff observe a
        monotonically increasing sequence. No-op at version 0."""
        version = int(state.get("version") or 0)
        if version <= 0 or state.get("params") is None:
            return self.current_version()
        snap, extra_snap = self._snapshots_of(
            state["params"], state.get("extras") or {}
        )
        with self._lock:
            if version <= self._current:
                return self._current
            self._snapshots[version] = snap
            self._extras[version] = extra_snap
            self._refs.setdefault(version, 0)
            self._current = version
            self._swap_log.append((version, time.perf_counter()))
            self._retire_locked()
        return version
