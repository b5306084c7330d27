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

"""Pairwise-mask secure aggregation over a fixed-point ring.

The scheme (Bonawitz et al. 2017 shape, docs/privacy.md for the full
threat model):

1. Every unordered party pair ``(i, j)`` agrees a seed ``s_ij`` over
   authenticated ``prv:`` control frames (privacy/protocol.py).
2. A contribution's float leaves are encoded into the ring
   ``Z_{2^32}`` as fixed-point words: ``q = round(x * 2^f)`` reduced
   mod ``2^32`` (``f`` = ``privacy.fixedpoint_bits``). The ring is the
   whole point: modular integer addition is EXACT and associative, so
   mask cancellation is bitwise by construction — no float-rounding
   escape hatch.
3. Party ``i`` adds, per leaf, ``+stream(s_ij)`` for every partner
   ``j > i`` and ``-stream(s_ij)`` for every ``j < i`` (mod ``2^32``).
   Each pairwise stream appears in the federation-wide sum exactly
   twice with opposite signs, so the MODULAR SUM of all masked
   contributions equals the modular sum of the plain encodings — the
   masks cancel bitwise at the root while every individual contribution
   stays one-time-pad masked on the wire.
4. The root decodes the modular sum back to the leaf dtype and applies
   the SAME scaling ops the plaintext fold applies (``x / n`` for mean,
   ``x / total`` for wmean), so whenever both arithmetics are exact —
   integer-valued updates within the documented headroom — the secure
   aggregate is bitwise-equal to the plaintext one.
5. Dropout recovery: a party that contributed masks but whose masked
   tree never arrived leaves its pairwise streams orphaned in the sum.
   Each survivor re-offers its seed with the dead party
   (``prv:recover``); the root regenerates the orphaned streams from
   those seeds and subtracts them mod ``2^32`` — again exact.

Mask streams are jax PRNG streams (`jax.random.bits`), derived per
(pair seed, domain, round, leaf index) via ``fold_in``, so both pair
members generate identical words with no extra communication, and no
stream is ever reused across rounds, sessions, or aggregation domains.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

MODULUS_BITS = 32
_MOD = 1 << MODULUS_BITS

#: Headroom bound: the TRUE integer sum over all parties must stay in
#: [-2^31, 2^31) for the centered lift at the root to recover it.
_HALF_MOD = 1 << (MODULUS_BITS - 1)


class SecAggError(ValueError):
    """A secure-aggregation contract violation (non-float leaves,
    fixed-point overflow, missing seeds)."""


# ---------------------------------------------------------------------------
# Fixed-point ring encode / decode
# ---------------------------------------------------------------------------


def _leaves(tree: Any) -> Tuple[List[Any], Any]:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def encode_tree(
    tree: Any, fixedpoint_bits: int, n_parties: int
) -> Tuple[List[np.ndarray], List[str], Any]:
    """Encode every float leaf into ``Z_{2^32}`` fixed-point words.

    Returns ``(ring_leaves, dtype_names, treedef)``. Raises
    :class:`SecAggError` on non-float leaves, or when any encoded word
    could overflow the ring's headroom once summed over ``n_parties``
    contributors (the caller sees the bound in the message — shrink the
    update or lower ``privacy.fixedpoint_bits``).
    """
    leaves, treedef = _leaves(tree)
    scale = float(1 << int(fixedpoint_bits))
    limit = _HALF_MOD / max(1, int(n_parties))
    ring: List[np.ndarray] = []
    dtypes: List[str] = []
    for idx, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        if arr.dtype.kind != "f":
            raise SecAggError(
                f"secure aggregation masks floating-point leaves only; "
                f"leaf {idx} has dtype {arr.dtype.name} (cast it or "
                f"aggregate it in a separate plaintext call)"
            )
        q = np.rint(arr.astype(np.float64) * scale)
        peak = float(np.max(np.abs(q))) if q.size else 0.0
        if peak >= limit:
            raise SecAggError(
                f"fixed-point overflow: leaf {idx} encodes to "
                f"|q|={peak:.3g} but the 2^{MODULUS_BITS} ring over "
                f"{n_parties} parties holds |q| < {limit:.3g}; shrink "
                f"the update or lower privacy.fixedpoint_bits "
                f"(currently {fixedpoint_bits})"
            )
        ring.append((q.astype(np.int64) % _MOD).astype(np.uint32))
        dtypes.append(arr.dtype.name)
    return ring, dtypes, treedef


def decode_sum(
    ring_leaves: Sequence[np.ndarray],
    dtype_names: Sequence[str],
    treedef: Any,
    fixedpoint_bits: int,
) -> Any:
    """Decode a modular sum of encodings back to the leaf dtype.

    The centered lift interprets each ring word as a signed integer in
    [-2^31, 2^31) — exact as long as the true sum respected the
    :func:`encode_tree` headroom bound — then rescales by ``2^-f`` in
    float64 (exact for any value the ring can hold) and casts to the
    original leaf dtype.
    """
    import jax

    inv_scale = 2.0 ** -float(fixedpoint_bits)
    out = []
    for words, dt in zip(ring_leaves, dtype_names):
        s = words.astype(np.int64)
        s = np.where(s >= _HALF_MOD, s - _MOD, s)
        out.append((s.astype(np.float64) * inv_scale).astype(np.dtype(dt)))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Mask streams
# ---------------------------------------------------------------------------


def _domain_tag(domain: str) -> int:
    return zlib.crc32(domain.encode("utf-8")) & 0x7FFFFFFF


def mask_stream(
    pair_seed: int, domain: str, round_index: int, leaf_index: int,
    shape: Tuple[int, ...],
) -> np.ndarray:
    """The pairwise mask words for one leaf of one round: a jax PRNG
    uint32 stream both pair members derive identically. ``domain``
    separates sync aggregation, async sessions, and tests so a seed is
    never reused on two different plaintexts."""
    import jax

    key = jax.random.PRNGKey(int(pair_seed) % (1 << 63))
    key = jax.random.fold_in(key, _domain_tag(domain))
    key = jax.random.fold_in(key, int(round_index) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, int(leaf_index))
    import jax.numpy as jnp

    return np.asarray(jax.random.bits(key, shape=tuple(shape),
                                      dtype=jnp.uint32))


def pair_sign(party: str, partner: str) -> int:
    """+1 when ``party`` adds the pair's stream, -1 when it subtracts —
    the lexicographically smaller name adds, so the two applications
    cancel mod 2^32."""
    if party == partner:
        raise SecAggError("a party has no pairwise mask with itself")
    return 1 if party < partner else -1


def apply_masks(
    ring_leaves: Sequence[np.ndarray],
    party: str,
    parties: Sequence[str],
    pair_seeds: Dict[str, int],
    domain: str,
    round_index: int,
) -> List[np.ndarray]:
    """Add this party's pairwise mask total to each ring leaf."""
    partners = [p for p in parties if p != party]
    missing = [p for p in partners if p not in pair_seeds]
    if missing:
        raise SecAggError(
            f"party {party!r} holds no pairwise seed for {missing} "
            "(the prv: seed exchange did not complete)"
        )
    out = []
    for idx, words in enumerate(ring_leaves):
        acc = words.copy()
        for partner in partners:
            stream = mask_stream(
                pair_seeds[partner], domain, round_index, idx, words.shape
            )
            if pair_sign(party, partner) > 0:
                acc += stream  # uint32: wraps mod 2^32
            else:
                acc -= stream
        out.append(acc)
    return out


def orphan_correction(
    dead_party: str,
    survivor_seeds: Dict[str, int],
    domain: str,
    round_index: int,
    shapes: Sequence[Tuple[int, ...]],
) -> List[np.ndarray]:
    """The net orphaned mask words a dead party's absence leaves in the
    survivors' modular sum: ``sum_s sign(s, dead) * stream(s_sd)`` per
    leaf, where ``s`` ranges over the survivors whose seeds were
    re-offered. Subtracting this (mod 2^32) from the survivor sum
    restores exact cancellation."""
    out = []
    for idx, shape in enumerate(shapes):
        acc = np.zeros(shape, np.uint32)
        for survivor, seed in survivor_seeds.items():
            stream = mask_stream(seed, domain, round_index, idx, shape)
            if pair_sign(survivor, dead_party) > 0:
                acc += stream
            else:
                acc -= stream
        out.append(acc)
    return out


# ---------------------------------------------------------------------------
# Modular folds: host twin and same-mesh collective
# ---------------------------------------------------------------------------


def modular_sum_host(
    contributions: Sequence[Sequence[np.ndarray]],
) -> List[np.ndarray]:
    """Leaf-wise sum mod 2^32 on the host. Modular addition is
    associative, so this is bitwise-identical to the same-mesh
    collective below regardless of fold order."""
    assert contributions, "nothing to sum"
    out = [w.copy() for w in contributions[0]]
    for contrib in contributions[1:]:
        for idx, words in enumerate(contrib):
            out[idx] += words
    return out


@functools.lru_cache(maxsize=32)
def _modsum_fn(mesh, n: int):
    """The compiled party-axis modular reduction (the secure twin of
    ``ops.aggregate._psum_flat_fn``). uint32 addition wraps mod 2^32 in
    XLA, so a raw psum IS the ring sum — no deterministic/fast split
    needed, every association order gives the same words."""
    import jax

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(local_tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x[0], "party")[None], local_tree
        )

    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("party"), out_specs=P("party"))
    )


def modular_sum_mesh(
    mesh, contributions: Sequence[Sequence[np.ndarray]]
) -> List[np.ndarray]:
    """Leaf-wise sum mod 2^32 lowered to ONE collective across the
    composed party mesh's ``party`` axis — the same-mesh lowering of
    the secure fold. Bitwise-identical to :func:`modular_sum_host`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(contributions)
    stacked = [
        jax.device_put(
            jnp.stack([jnp.asarray(c[idx]) for c in contributions]),
            NamedSharding(mesh, P("party")),
        )
        for idx in range(len(contributions[0]))
    ]
    reduced = _modsum_fn(mesh, n)(stacked)
    return [np.asarray(x[0]) for x in reduced]


def modular_sub(
    words: Sequence[np.ndarray], correction: Sequence[np.ndarray]
) -> List[np.ndarray]:
    return [a - b for a, b in zip(words, correction)]
