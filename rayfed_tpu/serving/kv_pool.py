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

"""The block-paged cache of the serving plane.

:class:`PagedKVPool` — PagedAttention-shaped block granularity (Kwon et
al. 2023) over the stacked-cache layout of
:mod:`rayfed_tpu.models.decode`: the physical cache is what the served
model declares a token keeps (``kv_spec()`` of its serving protocol):
one (L_a, 1 + num_blocks, block_size, *shape) array per declared array,
allocated at server start, where ``L_a`` is the number of the model's
layers that keep a row in THAT array (each array declares its own: a
model whose layer kinds keep different things allocates every array
for the layers that use it, and a program indexes an array by a
layer's ordinal among those). A model with keys and values declares
two arrays of (H, Dh) over all its layers, and "K/V" below means that
pair; a model with latent attention declares ONE array of (width,)
and nothing else is held for it (no second copy, no per-head K/V;
``LANES`` below for how it is allocated); a model with two kinds of
latent layers and an indexer declares three
(:mod:`rayfed_tpu.models.dots3_note`). Each of ``max_slots`` rows (a *slot*, borrowed by one
request for
its lifetime) holds an int32 *block table* mapping logical block i of
its sequence to a physical block. Blocks are granted on demand at token
boundaries and returned to a free list at release — a short generation
pins ceil(len/block_size) blocks, not a whole ``max_len`` row, so
mixed-length traffic does not strand memory. No per-request allocation
and no per-request compile: every program is shaped by the pool, not by
the set of live requests. Prefix reuse is a block-table copy plus one
boundary-block clone, and every grant/free is charged to the tenant
ledger so ``tenancy.kv_block_quota`` means actual resident blocks.

Decode: one jitted program per iteration
(:func:`rayfed_tpu.models.decode.paged_decode_step`) reads each row's K/V
through its block table, a chunk of blocks at a time under an online
softmax, writes the new token's K/V straight into its
(block, offset) and ends in the choice of each row's next token
(:mod:`rayfed_tpu.serving.sampling`): ids come back, not logits, and
stay on the device as the next step's token source (``prev_ids``: the
engine dispatches a step before it has fetched the one before); the
pool's arrays are donated and are the only cache buffers —
no contiguous (L, R, max_len+1, H, Dh) copy of the rows exists, and the
blocks read follow the longest live row, not ``max_len``. It agrees with
the plain cached forward (:func:`rayfed_tpu.models.decode.
forward_with_cache`) to rounding (the online softmax re-associates the
sum), which the parity tests hold to equal tokens. A chunk of a long
prompt is one program of the same kind (:meth:`PagedKVPool.chunk_step`):
it reads its context through the slot's block table and writes its own
K/V in place. Only the bucketed prefill still lands whole rows
(``scatter_rows``).

Sacrificial block: the pool is one block larger than ``num_blocks``. A
batched decode step always runs every pool row; rows that are free,
stalled, or pinned to a different model version than the step's params
carry position 0 under an all-zero table, so their (garbage) K/V lands
in physical block 0 — a block no table entry of a live row's granted
prefix ever names, so no real query attends to it. That keeps the step
a fixed-shape program with no O(cache) masking and makes cross-version
cache corruption structurally impossible.

Block recycling needs no zeroing: a recycled block's stale K/V lives at
positions the new request has not reached yet (the causal mask admits
k_pos <= q_pos), and every position the new request *does* attend to
was overwritten by its own prefill/decode first.

Recurrent state (a model whose ``state_spec`` is not empty, e.g.
:mod:`rayfed_tpu.models.falcon_h1`): the pool also owns one
``(L_s, max_slots, *shape)`` array per entry of the spec, donated through
the decode step like the K/V pair, where ``L_s`` is the number of the
model's layers that keep THAT entry (each entry declares its own, as each
array of ``kv_spec()`` does: a model whose layers mostly hold a state with
a full-attention layer among every few allocates the state for the former
and K/V for the latter, :mod:`rayfed_tpu.models.olmo_hybrid`, and a
program indexes a state array by a layer's ordinal among those that keep
it). Unlike K/V it is *carried*, so none
of the "stale is invisible" arguments above hold for it: a slot's state
is made zero by the prefill that starts a request in it (the bucketed
prefill computes from a zero state and :meth:`PagedKVPool.scatter_rows`
lands the result for the rows named in ``landed``; the first chunk of a
chunked prefill starts from zero whatever its slot's rows held),
and a row that sits a decode step out is handed back bit for bit
(``live``). Prefix reuse holds no such state and the engine refuses it
for such a model.

Prefix reuse ("where cheap"): a slot whose live request was prefilled
from the same (version, prompt) is a donor — its prompt region is never
rewritten while it decodes (decode writes at positions >= prompt length),
so an identical concurrent prompt skips the full prefill by sharing the
donor's fully-prompt blocks and re-running only the last prompt token.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rayfed_tpu.models import decode
from rayfed_tpu.serving import sampling


@partial(jax.jit, donate_argnums=(0,))
def _copy_block(kv, src, dst):
    """Copy physical block ``src`` over block ``dst`` in every array of
    the pool (prefix-reuse boundary clone)."""
    blocks = [jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1) for a in kv]
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(a, b, dst, axis=1)
        for a, b in zip(kv, blocks)
    )


# A TPU lays an array out in tiles whose minor dimension is this many
# values. A pool array whose rows are single vectors that are not whole
# tiles (a 576-wide latent row) would get, by the device's own default,
# ANOTHER dimension as its minor one, so that nothing is padded: for a
# pool that is its blocks, and every program that gathers blocks or
# writes rows would first copy the whole pool into an order it can work
# in (compiled for a v5e: two copies of 3.1 GB a decode step). Such an
# array is allocated with its rows padded to whole tiles (640): what the
# device would hold for a row-major array anyway. The padding is zero
# and stays zero; ``decode.paged_attention`` / ``paged_chunk_attention``
# and the writes pad what they are handed to the pool's width. (Pinning
# the unpadded array to a row-major layout through ``jax.jit``'s
# ``Format`` arguments did the same on a cold process, but a process that
# read its programs from the persistent compile cache got the pool back
# in the default order: PR 33, PERF.md section 6.)
LANES = 128


def _allocated(shape: tuple) -> tuple:
    """The per-token shape as the pool allocates it."""
    if len(shape) == 1 and shape[0] % LANES:
        return (-(-shape[0] // LANES) * LANES,)
    return tuple(shape)


# What a decode step that carries a block a row counts on the device,
# behind the model's own ``step_counters``: rows forwarded, rows whose
# forward committed their block, positions unmasked.
BLOCK_STEP_COUNTERS = ("diffusion_row_forwards", "diffusion_commit_forwards",
                       "diffusion_tokens_unmasked")


class PagedKVPool:
    """Block-granular pool of what the model's ``kv_spec()`` declares
    a token keeps (K and V rows for most models, one latent row for
    some, each array over the layers that keep a row in it):
    ``max_slots`` logical rows over ``num_blocks`` shared physical
    blocks (+ the sacrificial block 0). ``cfg`` is any config whose
    module has a ``serving_model``.

    Block tables live on the host as plain int32 numpy (they change a
    few entries per iteration; shipping them into jitted programs as
    arguments keeps every program fixed-shape). Physical block 0 is the
    junk target: ungranted table entries point at it, junk decode rows
    scatter into it, and no real query ever attends a position that
    resolves to it — so recycled blocks are never zeroed: the
    sacrificial-block argument of the module docstring.

    Tenant accounting: every fresh block grant charges one ``kv_blocks``
    unit against the constructing job's :class:`TenantResourceLedger`
    and every physical free releases it, so the quota tracks resident
    memory rather than a static slot count. Prefix-shared blocks are
    charged once (they are one physical block).
    """

    def __init__(
        self,
        cfg,
        max_slots: int,
        max_len: int,
        dtype=None,
        *,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
    ):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if max_len < 2:
            raise ValueError("max_len must be >= 2")
        if block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.block_size = int(block_size)
        # Logical blocks per full-length row; the prefill paths' rows are
        # (max_len + 1) long.
        self.row_len = max_len + 1
        self.blocks_per_row = -(-self.row_len // self.block_size)
        self.num_blocks = (
            int(num_blocks)
            if num_blocks
            else max_slots * self.blocks_per_row
        )
        if self.num_blocks < 1:
            raise ValueError("kv_blocks must be >= 1")
        self.model = decode.serving_model(cfg)
        arrays = self.model.kv_spec()
        dtype = dtype or cfg.compute_dtype
        # One array per array the model declares a token keeps, each as
        # deep as the layers that keep a row in it.
        self._kv = tuple(
            jnp.zeros(
                (layers_of_array, 1 + self.num_blocks, self.block_size,
                 *_allocated(shape)),
                dtype,
            )
            for layers_of_array, shape in arrays
        )
        # Bytes a token keeps in the pool, all arrays over the layers of
        # each, as the model declares them: a fact of the model and the
        # cache dtype (``stats()["kv_token_bytes"]``; ``nbytes`` is what
        # is allocated).
        self.token_bytes = jnp.dtype(dtype).itemsize * sum(
            layers_of_array * int(np.prod(shape))
            for layers_of_array, shape in arrays
        )
        # What a slot holds beside its paged rows (a recurrent state):
        # one (layers of the entry, max_slots, ...) array per entry of the
        # model's spec, each as deep as the layers that keep it.
        self._state = {
            name: jnp.zeros((layers_of_entry, max_slots, *shape), sdtype)
            for name, (layers_of_entry, shape, sdtype)
            in self.model.state_spec(dtype).items()
        }
        self.state_row_bytes = sum(
            int(a.nbytes) // max_slots for a in self._state.values()
        )
        # Numbers a model's decode step counts on the device (optional in
        # the protocol): they ride home behind the ids, in their array.
        self.step_counters = tuple(getattr(self.model, "step_counters", ()))
        # A model that generates by blocks (optional in the protocol: a
        # ``decode.BlockSpec``): a row of a decode step carries a block,
        # not one id, and the step counts what it did with the blocks.
        self.block = getattr(self.model, "block_spec", lambda: None)()
        if self.block is not None:
            if self.block_size % self.block.length:
                raise ValueError(
                    f"kv_block_size={self.block_size} is not a multiple of "
                    f"the model's block length {self.block.length}: a "
                    "block's positions must lie in one block of the pool"
                )
            self.step_counters += BLOCK_STEP_COUNTERS
        # Ids a decode step returns: one a row, or a block a row.
        self.ids_len = max_slots * (self.block.length if self.block else 1)
        # What the last decode step returned (the ids, the counters behind
        # them), kept on the device: see :meth:`decode_step`.
        self._ids = jnp.zeros(
            self.ids_len + len(self.step_counters), jnp.int32)
        self._lock = threading.Lock()
        self._free_slots: List[int] = list(range(max_slots))
        # pop() hands out low block ids first.
        self._free_blocks: List[int] = list(range(self.num_blocks, 0, -1))
        # Physical block refcounts (prefix sharing); index 0 unused.
        self._refcnt = [0] * (1 + self.num_blocks)
        self._tables = np.zeros(
            (max_slots, self.blocks_per_row), np.int32
        )
        # Granted logical blocks per slot (always a contiguous prefix of
        # the table).
        self._granted = [0] * max_slots
        self._prefix: Dict[int, Tuple[int, bytes]] = {}
        from rayfed_tpu.tenancy.context import current_job

        self._job = current_job()
        self._build_fns()

    # -- jitted data movement (engine thread only) -----------------------

    def _build_fns(self) -> None:
        NB = self.blocks_per_row
        bs = self.block_size
        R = self.max_slots
        model = self.model

        def landed_in(old, new, where):
            # new where a row's prefill landed, old bit for bit elsewhere.
            return {
                name: jnp.where(
                    where.reshape((1, R) + (1,) * (old[name].ndim - 2)),
                    new[name].astype(old[name].dtype), old[name],
                )
                for name in old
            }

        # The state is a pytree ({} for a model without one: no argument,
        # no output, the program it ran before there was one); what exists
        # for its sake trails and may be left out. The program ends in the
        # choice of each row's next token (serving/sampling.py): (R,) ids
        # come back, the (R, vocab) logits stay on the device. A model
        # that declares ``step_counters`` returns them as a fifth value,
        # and they follow the ids in the one int32 array. That array is
        # not donated: it is the next step's token source (``prev_ids``)
        # for every row not ``from_host``, so a step can be dispatched
        # before the host has read the one before it.
        @jax.named_scope("serve/decode_step")
        def decode_step(params, kv, tokens, positions, tables, draw,
                        prev_ids, from_host, state=None, live=None):
            tokens = jnp.where(from_host, tokens, prev_ids[:R])
            logits, kv, state, *counted = model.decode_step(
                params, kv, state or {}, tokens, positions, tables, live
            )
            ids = sampling.choose_packed(logits, draw)
            if counted:
                ids = jnp.concatenate([ids, counted[0].astype(ids.dtype)])
            return ids, kv, state

        if self.block is not None:
            decode_step = self._block_step()
        self._decode_step_fn = jax.jit(
            decode_step, donate_argnums=(1, 8)
        )

        @jax.named_scope("serve/scatter")
        def scatter_rows(kv, slabs, tables, state=None, new_state=None,
                         landed=None):
            # Write whole (R, T)-shaped prefill output back through the
            # scatter tables, one slab per array of the pool. Rows that
            # must not land (junk vmap lanes, already-live neighbours)
            # carry an all-zero table and a false `landed`. A model may
            # hand back rows shorter than T (as long as its bucket): they
            # land in the first blocks of the tables and the rest of a
            # row is left as it was.
            nb = -(-slabs[0].shape[2] // bs)
            pad = nb * bs - slabs[0].shape[2]
            if nb != NB:
                tables = tables[:, :nb]

            def land(pool, slab):
                L = pool.shape[0]             # the layers of THIS array
                slab = decode.to_width(slab, pool.shape[-1])
                if pad:
                    z = jnp.zeros((L, R, pad, *pool.shape[3:]), slab.dtype)
                    slab = jnp.concatenate([slab, z], axis=2)
                return pool.at[:, tables].set(
                    slab.reshape(L, R, nb, bs, *pool.shape[3:]))

            kv = tuple(land(pool, slab) for pool, slab in zip(kv, slabs))
            return kv, landed_in(state or {}, new_state, landed)

        self._scatter_rows_fn = jax.jit(
            scatter_rows, donate_argnums=(0, 3)
        )

    def _block_step(self):
        """The decode step of a model that generates by blocks (the
        pool's ``block``), under the same name, arguments and donation as
        the one-token step. A row carries ``B`` ids (``tokens`` (R, B)
        from the host where ``from_host`` says so, else the block the
        step before returned for it), the mask id where a position is
        still masked, and ``positions`` is each carried block's first
        position. ONE forward of every row does per live row what the
        model's routine does in one iteration, or in two: a carried block
        that still holds a mask id is DENOISED (a candidate and its
        confidence for every masked position, some unmasked:
        :func:`rayfed_tpu.serving.sampling.unmask`; its K/V are not
        kept); one that came in clean COMMITS (its K/V are written
        through the block table) and the same forward is the first
        denoising step of the block behind it, all mask ids, which the
        step returns for the row as that step left it (the model's
        ``decode_step`` forwards both blocks of every row and hands back
        the logits of the one that denoises; the sampler's index moves on
        by ``B`` and its denoising step is 0 for such a row). Which of
        the two a row does is decided here from the carried block, so the
        host need not have read the step before. Returns the (R * B) ids
        of the blocks as the step leaves them, the model's counters and
        ``BLOCK_STEP_COUNTERS`` behind them."""
        R, model, spec = self.max_slots, self.model, self.block

        @jax.named_scope("serve/decode_step")
        def decode_step(params, kv, tokens, positions, tables, draw,
                        prev_ids, from_host, state=None, live=None):
            carried = prev_ids[:self.ids_len].reshape(R, spec.length)
            tokens = jnp.where(from_host[:, None], tokens, carried)
            commit = live & jnp.all(tokens != spec.mask_id, axis=-1)
            logits, kv, state, *counted = model.decode_step(
                params, kv, state or {}, tokens, positions, tables, live,
                commit
            )
            with jax.named_scope("serve/unmask"):
                # The block each row denoises, and where it lies in the
                # output: the one behind the carried block, at its step
                # 0, where that one committed.
                draw = draw.at[2].add(jnp.where(commit, spec.length, 0))
                draw = draw.at[3].set(jnp.where(commit, 0, draw[3]))
                block, unmasked = sampling.unmask(
                    logits, jnp.where(commit[:, None], spec.mask_id, tokens),
                    draw, spec, live)
            counts = jnp.stack([
                jnp.sum(live, dtype=jnp.int32),
                jnp.sum(commit, dtype=jnp.int32), unmasked])
            ids = jnp.concatenate(
                [block.reshape(-1)]
                + [c.astype(jnp.int32) for c in counted] + [counts])
            return ids, kv, state

        return decode_step

    def decode_step(self, params, tokens, positions, tables, draw,
                    live=None, from_host=None, prev_ids=None):
        """One decode token per row through the block tables, the pool
        updated in place; junk rows carry position 0 and an all-zero
        table. A row's token is ``tokens`` (R,) from the host where
        ``from_host`` (R,) bool says so, and elsewhere the id an earlier
        step chose for it, read on the device from that step's returned
        array ``prev_ids`` (which the host need not have fetched yet);
        without ``prev_ids`` every token is the host's. ``draw`` (3, R)
        int32 is the sampler's per-row scalars
        (:func:`rayfed_tpu.serving.sampling.pack`; all zero: every row
        greedy). ``live`` (R,) bool names the rows whose recurrent state
        advances; every other row's state comes back bit for bit. A
        model without such a state takes none (one that declares
        ``step_counters`` is told which rows are live all the same: what
        it counts is over them). Returns each row's next token, (R,)
        int32, on the device, followed by the model's ``step_counters``
        where it declares any. (A model that generates by blocks: a
        block a row, ``tokens`` (R, B) and (R * B) ids back:
        :meth:`_block_step`.) The small host arrays go to
        the program as NumPy, here and in the pool's other programs: the
        jitted call uploads its own arguments, and a ``jnp.asarray``
        around each cost the engine thread 0.15 ms of dispatch apiece
        on the chip's host (``PERF.md`` §6, PR 30)."""
        wants_live = self._state or self.step_counters
        if prev_ids is None:
            # Any array of the step's own making: one call signature,
            # whichever rows read it (none here).
            prev_ids, from_host = self._ids, np.ones(self.max_slots, bool)
        self._ids, self._kv, self._state = self._decode_step_fn(
            params, self._kv, tokens, positions, tables, draw,
            prev_ids, np.asarray(from_host, bool),
            self._state, np.asarray(live, bool) if wants_live else None,
        )
        return self._ids

    def _of_state(self, value, dtype):
        """An argument that exists for the state's sake: handed on for a
        model that has one, not sent at all for one that has none."""
        return np.asarray(value, dtype) if self._state else None

    def chunk_step(self, fn, params, slot: int, toks, offset: int,
                   n_real: int, draw):
        """One chunk of ``slot``'s prompt through the engine's chunk
        program ``fn`` (``InferenceServer._get_chunk_fn``): the pool and
        the state go in donated with the slot's block table and come back
        updated in place, as in :meth:`decode_step`. Returns the token the
        program chose at the chunk's last real position, on the device."""
        with self._lock:
            table = self._tables[slot].copy()
        chosen, self._kv, self._state = fn(
            params, self._kv, self._state, table, np.int32(slot),
            toks, np.int32(offset), np.int32(n_real), draw,
        )
        return chosen

    def scatter_rows(self, *args) -> None:
        """``scatter_rows(*slabs, tables, state_rows=None, landed=None)``:
        land a round of prefilled rows, one slab (L_a, R, S, *shape) per
        array of the pool (``L_a`` that array's layers) in the order of
        ``kv_spec()`` (``k_slab,
        v_slab`` for a model with keys and values), through ``tables``;
        each row's fresh recurrent state where ``landed`` (R,) bool
        says."""
        n = len(self._kv)
        tables, state_rows, landed = (*args[n:], None, None)[:3]
        self._kv, self._state = self._scatter_rows_fn(
            self._kv, tuple(args[:n]), tables,
            self._state, state_rows or {}, self._of_state(landed, bool),
        )

    @property
    def kv(self):
        """The pool's arrays, in the order the model declares them."""
        return self._kv

    @property
    def state(self):
        """The recurrent-state arrays, name -> (layers that keep it,
        max_slots, ...)."""
        return self._state

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self._kv) + sum(
            int(a.nbytes) for a in self._state.values()
        )

    def jitted_fns(self):
        """The pool's jitted programs (compile accounting)."""
        return [self._decode_step_fn, self._scatter_rows_fn, _copy_block]

    # -- slot + block lifecycle ------------------------------------------

    def acquire(self) -> Optional[int]:
        with self._lock:
            if not self._free_slots:
                return None
            return self._free_slots.pop()

    def release(self, slot: int) -> None:
        freed = 0
        with self._lock:
            if slot in self._free_slots:
                raise ValueError(f"slot {slot} double-released")
            for i in range(self._granted[slot]):
                blk = int(self._tables[slot, i])
                self._refcnt[blk] -= 1
                if self._refcnt[blk] == 0:
                    self._free_blocks.append(blk)
                    freed += 1
            self._tables[slot] = 0
            self._granted[slot] = 0
            self._prefix.pop(slot, None)
            self._free_slots.append(slot)
        if freed:
            self._ledger_release(freed)

    def ensure_blocks(self, slot: int, pos: int) -> str:
        """Grant blocks so position ``pos`` of ``slot`` is resident.

        Returns ``"ok"``, ``"full"`` (free list empty) or ``"quota"``
        (tenant ledger refused). Grants are all-or-nothing per call:
        a partial grant is kept (it covers earlier positions and will
        satisfy a retry), never rolled back.
        """
        needed = pos // self.block_size + 1
        while True:
            with self._lock:
                if self._granted[slot] >= needed:
                    return "ok"
                if not self._free_blocks:
                    return "full"
            # Charge outside the pool lock (the ledger has its own).
            if not self._ledger_charge(1):
                return "quota"
            with self._lock:
                if not self._free_blocks:
                    charged_back = True
                else:
                    charged_back = False
                    blk = self._free_blocks.pop()
                    self._refcnt[blk] = 1
                    self._tables[slot, self._granted[slot]] = blk
                    self._granted[slot] += 1
            if charged_back:
                self._ledger_release(1)
                return "full"

    def _ledger_charge(self, n: int) -> bool:
        from rayfed_tpu.tenancy.qos import TenantQuotaExceeded, get_ledger

        try:
            get_ledger().charge(self._job, "kv_blocks", n)
            return True
        except TenantQuotaExceeded:
            return False

    def _ledger_release(self, n: int) -> None:
        from rayfed_tpu.tenancy.qos import get_ledger

        get_ledger().release(self._job, "kv_blocks", n)

    def table(self, slot: int) -> np.ndarray:
        with self._lock:
            return self._tables[slot].copy()

    def granted(self, slot: int) -> int:
        with self._lock:
            return self._granted[slot]

    @property
    def blocks_free(self) -> int:
        with self._lock:
            return len(self._free_blocks)

    @property
    def blocks_in_use(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free_blocks)

    # -- prefix reuse (block-chain sharing) ------------------------------

    def note_prefix(self, slot: int, version: int, prompt_key: bytes) -> None:
        with self._lock:
            self._prefix[slot] = (version, prompt_key)

    def lookup_prefix(self, version: int, prompt_key: bytes) -> Optional[int]:
        with self._lock:
            for slot, key in self._prefix.items():
                if key == (version, prompt_key):
                    return slot
        return None

    def adopt_prefix(self, donor: int, dst: int, plen: int) -> str:
        """Share the donor's fully-prompt blocks with ``dst`` (refcount
        bump, no data movement) and clone the boundary block when the
        prompt ends mid-block — the donor decodes into its own boundary
        copy, so sharing it would mix sequences. Returns "ok", "full" or
        "quota"; on failure the shares are rolled back and the caller
        falls through to a normal prefill.
        """
        bs = self.block_size
        full = plen // bs
        with self._lock:
            for i in range(full):
                blk = int(self._tables[donor, i])
                self._refcnt[blk] += 1
                self._tables[dst, i] = blk
            self._granted[dst] = full
        if plen % bs == 0:
            return "ok"
        status = self.ensure_blocks(dst, plen - 1)
        if status != "ok":
            with self._lock:
                for i in range(full):
                    blk = int(self._tables[dst, i])
                    self._refcnt[blk] -= 1
                self._tables[dst, :full] = 0
                self._granted[dst] = 0
            return status
        with self._lock:
            src_blk = int(self._tables[donor, full])
            dst_blk = int(self._tables[dst, full])
        self._kv = _copy_block(
            self._kv, np.int32(src_blk), np.int32(dst_blk)
        )
        return "ok"
