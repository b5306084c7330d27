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

"""The serving-party request scheduler: admission control + continuous
(iteration-level) batching with hot model swap.

Orca-style continuous batching over the KV pool
(:mod:`rayfed_tpu.serving.kv_pool`): the engine thread alternates
*admission* (pop pending requests into free slots — prefill-then-merge at
a token boundary) with *decode iterations* (ONE fixed-shape batched step
over the whole pool per live model version). A finishing sequence
releases its slot without draining the batch; a newly admitted one joins
at the next iteration. Both jitted programs are shaped by the pool, so
the engine compiles a handful of programs at startup cost and never
again, regardless of request mix.

The cache (K/V, or whatever the model's ``kv_spec()`` declares) is a
pool of blocks (:class:`~rayfed_tpu.serving.kv_pool.PagedKVPool`). A
decode iteration is ONE program (the model's ``decode_step``), batched
over rows, that reads each row's cache through its block table — as
many blocks as the longest live row holds — and writes the new token's
rows in place: the pool is the only cache buffer. Admission batches
a whole round of short-prompt prefills into ONE dispatch, splits prompts
longer than ``serving.prefill_chunk`` into fixed-size chunks merged into
the running decode iteration under a ``prefill_token_budget`` per step
(admission never stalls the live batch), and grants KV blocks on demand
at token boundaries. Every program ends in the choice of the next token
(:mod:`rayfed_tpu.serving.sampling`): the host fetches ``(R,)`` ids, never
the ``(R, vocab)`` logits, and does only bookkeeping (``eos``,
``max_new_tokens``, streams). Decode runs one step ahead: the ids stay on
the device as the next step's tokens, step t + 1 is dispatched before
step t is fetched, and the host reads every id one dispatch late
(:meth:`InferenceServer._step_groups` has the rules of that lag). When
the pool truly runs dry the engine preempts the youngest request (its
blocks return to the free list, the request re-queues and
deterministically re-runs under its pinned version), so mixed-length
traffic degrades by latency, never by abort.

A model that generates by blocks (its protocol declares ``block_spec()``:
:mod:`rayfed_tpu.models.sdar_moe`) runs through the same loop. A live row
then carries a block of ``B`` ids on the device, the mask id where a
position is still masked; a decode step forwards every row's block and
unmasks some of its positions or, where it came in clean, commits it
(keeps its K/V) and in the same forward unmasks the first positions of
the block behind it (:meth:`PagedKVPool._block_step` decides which, on
the device), so a step yields 1 to ``B`` tokens a row, no forward is
spent on a commit alone, and the host learns one fetch late what a step
did. A prompt's whole blocks are prefilled; its left-over tokens start
the first block unmasked. Tokens leave in the order of their positions,
none later than the fetch that shows its block clean; a request ends when
the block that holds its last token is clean, and what that block holds
beyond ``max_new_tokens`` is dropped. Prefix reuse, beam and speculative
requests are refused for such a model.

Token streaming: ``submit(..., stream=sink)`` attaches a sink the engine
pushes each sampled token into (never blocking — see
:mod:`rayfed_tpu.serving.stream` for the backpressure contract); the
response future still carries the complete sequence, bit-identical to
the streamed one.

Hot swap: :meth:`InferenceServer.publish` installs a new version in the
:class:`~rayfed_tpu.serving.publish.ModelBank`; requests pin the version
current at their admission and decode against it to completion — a swap
changes which params *future* admissions see, never what an in-flight
request computes (zero aborts, zero torn trees). During the handover
window the engine simply runs one batched step per live version.

Thread model: callers (fed task workers, client threads) enqueue under
the server lock; ONE engine thread owns the cache arrays and all jitted
dispatch. No device state is ever touched from two threads.
"""

from __future__ import annotations

import functools
import importlib
import itertools
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from rayfed_tpu import tracing, utils
from rayfed_tpu.config import ServingConfig
from rayfed_tpu.models import decode
from rayfed_tpu.models import transformer as tfm
from rayfed_tpu.serving import sampling
from rayfed_tpu.serving.kv_pool import PagedKVPool
from rayfed_tpu.serving.publish import (
    ModelBank,
    cast_nbytes,
    snapshot_tree,
)
from rayfed_tpu.telemetry import metrics as telemetry_metrics

logger = logging.getLogger(__name__)


class ServerOverloadedError(RuntimeError):
    """Admission control rejected the request: the pending queue is at
    ``serving.max_pending``. Back off and resubmit."""


class ServerStoppedError(RuntimeError):
    """The server was stopped before this request was admitted."""


def _default_buckets(max_len: int) -> List[int]:
    """Powers of two up to max_len (always including max_len)."""
    buckets = []
    b = 8
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return buckets


@dataclass
class _Request:
    rid: str
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int
    temperature: float
    seed: int
    mode: str                     # "generate" | "beam" | "speculative"
    n_beams: int
    future: Future
    enqueue_s: float
    version: int = 0
    slot: int = -1
    pos: int = 0                  # next cache write position (= seq length)
    out: List[int] = field(default_factory=list)
    prefix_reuse: bool = False
    timing: Dict[str, float] = field(default_factory=dict)
    extra_resp: Dict[str, Any] = field(default_factory=dict)
    stream: Any = None            # optional token sink (serving.stream)
    chunk_done: int = 0           # prompt positions chunked-prefilled so far
    stalled: bool = False         # waiting on a KV block grant
    ahead: int = 0                # steps dispatched and not yet fetched (0/1)
    # A model that generates by blocks: ``pos`` is the first position of
    # the request's current block, and the host keeps the block as the
    # last fetch showed it (the mask id where still masked).
    block: Optional[List[int]] = None
    block_step: int = 0           # denoising forwards fetched for the block
    block_out: int = 0            # its positions emitted (or the prompt's)
    block_steps: List[int] = field(default_factory=list)  # step per position
    steps_out: List[int] = field(default_factory=list)    # ... per token out


@dataclass
class _Ahead:
    """A decode step that was dispatched and not yet fetched: what it
    returned (on the device: the ids, the model's counters behind them)
    and the requests whose rows were live in it. A request that ends, is
    preempted or fails while its step is in flight is taken out of
    ``rows``: its id is dropped."""
    ids: Any
    rows: List[_Request]


class _Counter(NamedTuple):
    """One counter of the engine: its key in ``stats()`` and, where it has
    one, its series in the telemetry registry (docs/observability.md).
    ``InferenceServer._count`` bumps both."""
    key: str
    help: Optional[str]           # the series' help; None: it has no series
    series: Optional[str] = None  # where not ``fed_serving_{key}_total``
    event: bool = False           # the child ``event=key`` of that family
    # The optional member of the model protocol whose models have the key
    # and the series (None: all have them), or the key alone: the series
    # is then every server's, 0 for the others (as before this table).
    gate: Optional[str] = None
    series_for_all: bool = False


_request = functools.partial(
    _Counter, help="Serving requests by lifecycle event.",
    series="fed_serving_requests_total", event=True)

# What the engine counts. Every per-step counter counts at dispatch, a
# wasted row included: what the device was handed.
_COUNTERS = (
    _request("submitted"), _request("completed"), _request("rejected"),
    _Counter("prefix_hits", "Prefill prefix-cache hits."),
    _Counter("tokens_out", "Tokens generated.",
             series="fed_serving_tokens_total"),
    _Counter("steps", "Batched decode iterations."),
    # Run-ahead, beside "steps". steps_ahead / steps: how often the device
    # had its next step queued. A wasted row: an ``eos`` the host read one
    # step late (an end by ``max_new_tokens`` is known ahead).
    _Counter("steps_ahead",
             "Decode iterations dispatched before the previous one of "
             "their version was fetched."),
    _Counter("rows_wasted",
             "Rows live in a decode iteration dispatched after their last "
             "token."),
    _Counter("prefill_chunks",
             "Prompt chunks merged into decode iterations."),
    # (``decode.paged_chunk_is_kernel``: all of a pool's chunks, or none.)
    _Counter("prefill_chunks_kernel",
             "Prompt chunks whose read of the pool ran as a kernel."),
    # Of ``prefill_tokens``, those that went through chunks (the rest:
    # the bucketed prefill): what a chunk's least work is reckoned from.
    _Counter("chunk_tokens",
             "Real prompt tokens put through prompt chunks."),
    _Counter("streamed_tokens", "Tokens pushed to streaming sinks."),
    _Counter("preempted",
             "Requests preempted to break a KV block-pool deadlock.",
             series="fed_serving_preemptions_total"),
    # Decode: what a step has to read (``pos // block_size + 1`` a live
    # row) beside max_slots x blocks_per_row: a contiguous slab of the
    # rows.
    _Counter("kv_blocks_attended",
             "KV blocks covered by live rows' lengths, summed over paged "
             "decode steps."),
    _Counter("kv_blocks_slab",
             "KV blocks of every row at full length, summed over paged "
             "decode steps."),
    # (``decode.paged_blocks_walked``: the kernel's what each row's length
    # covers, the loop's every row of the program walked as far as the
    # longest.)
    _Counter("kv_blocks_walked",
             "KV blocks the decode read copies for the live rows (the mean "
             "over the layers), summed over paged decode steps."),
    # Recurrent state (0 for a model without one).
    _Counter("ssm_state_bytes",
             "Recurrent-state bytes read and written by live rows, summed "
             "over decode steps."),
    _Counter("state_resets",
             "Requests started from a zero recurrent state."),
    # Rows that sat a decode step out with their state kept.
    _Counter("state_rows_held", None),
    # A chunk reads its slot's state (what the chunk before handed on)
    # and writes it back: 2 x the slot's state bytes a chunk.
    _Counter("chunk_state_bytes",
             "Recurrent-state bytes read and written by prompt chunks."),
    # (0: the trees came in that dtype, or the model takes them as
    # published.)
    _Counter("publish_cast_bytes",
             "Bytes of published leaves cast to the model's serving dtype "
             "as versions were installed."),
    # 4 x max_slots a step or a prefill round, 4 a last chunk; a block a
    # row and no id of a prefill where the model generates by blocks.
    _Counter("fetch_bytes",
             "Bytes the engine thread fetched from its programs' outputs "
             "(the chosen token ids)."),
    # (The noise's branch ran.) Beside "steps".
    _Counter("draw_steps",
             "Decode steps in which at least one live row was sampled."),
    # n_layers x kv_blocks_attended for a model without windows.
    _Counter("kv_layer_blocks_attended",
             "KV blocks each layer of each live row must read (a windowed "
             "layer its window's), summed over layers and decode steps."),
    # Both prefill paths; a windowed layer's queries see at most its
    # window.
    _Counter("prefill_tokens",
             "Real prompt tokens put through the prefill programs."),
    _Counter("prefill_keys_attended",
             "(query, key) pairs the prefill programs' real tokens "
             "attended, summed over layers."),
    # Chunked prefill: what a chunk's attention gathers through the table
    # (a windowed layer's from its first query's window on) beside
    # n_layers x blocks_per_row: what a contiguous row of the slot would
    # carry each way.
    _Counter("chunk_blocks_read",
             "KV blocks holding the cached keys a prompt chunk's attention "
             "gathers, summed over layers and chunks."),
    _Counter("chunk_blocks_row",
             "KV blocks of a slot's whole row in every layer, summed over "
             "prompt chunks."),
    # Each row's own key among them, a windowed layer's at most its
    # window: times the bytes a token keeps in one layer, what a step had
    # to read of the cache.
    _Counter("decode_keys_attended",
             "Keys scored by live rows in paged decode steps, summed over "
             "layers."),
    # The indexed layers' selections, counted on the host from positions:
    # every causal key scored, ``min(k, pos + 1)`` a query kept.
    # ``*_decode``: the decode steps' part of each.
    _Counter("index_keys_scored",
             "(query, key) pairs the indexed layers' indexers scored, "
             "decode steps and prefill.", gate="layer_index_topk"),
    _Counter("index_keys_selected",
             "(query, key) pairs the indexed layers' top-k kept, "
             "decode steps and prefill.", gate="layer_index_topk"),
    _Counter("index_keys_scored_decode", None, gate="layer_index_topk"),
    _Counter("index_keys_selected_decode", None, gate="layer_index_topk"),
    # Generation by blocks. Fused: of the device's
    # ``diffusion_commit_forwards``, those the host fetched for a request
    # still running.
    _Counter("diffusion_positions_dropped",
             "Positions of requests' last blocks beyond max_new_tokens, "
             "computed and dropped (generation by blocks).",
             gate="block_spec", series_for_all=True),
    _Counter("diffusion_fused_forwards",
             "Rows whose forward committed their block and was the first "
             "denoising step of the next (generation by blocks).",
             gate="block_spec", series_for_all=True),
    # Held (one block table a slot), never read again.
    _Counter("kv_dead_blocks",
             "KV blocks live rows hold wholly behind a windowed "
             "layer's window, summed over those layers and decode "
             "steps.", gate="layer_windows"),
)


def _bump(stats: Dict[str, int], series: Dict[str, Any], key: str,
          n: int) -> None:
    """``key`` goes up by ``n`` in ``stats`` and in its series, if any."""
    stats[key] += n
    if key in series:
        series[key].inc(n)


class InferenceServer:
    """One party's serving engine. See module docstring for the model.

    Args:
        model_cfg: the served model's config (all versions published
            into this server must share it — shapes key the compiled
            programs). The engine takes its programs and the shapes of
            its cache from ``serving_model(model_cfg)`` of the module
            that defines the config (the model protocol:
            :class:`rayfed_tpu.models.decode.TransformerServing`).
        config: :class:`~rayfed_tpu.config.ServingConfig` (or dict).
        params: optional initial params (published as version 1).
        draft_cfg: optional draft-model config enabling
            ``mode="speculative"`` requests (the draft params ride each
            ``publish(..., draft_params=...)``).
        cache_dtype: pooled-cache dtype override.
    """

    def __init__(
        self,
        model_cfg: Any,
        config: Optional[ServingConfig] = None,
        *,
        params: Any = None,
        draft_cfg: Optional[tfm.TransformerConfig] = None,
        cache_dtype=None,
        name: str = "default",
    ):
        if isinstance(config, dict):
            config = ServingConfig.from_dict(config)
        self.cfg = model_cfg
        self.model = decode.serving_model(model_cfg)
        # On a TPU a decode step reads the pool through a Pallas kernel
        # (``decode.paged_attention``), and Pallas takes half a second to
        # import: begun here, on a thread of its own, it runs wherever
        # this thread and the engine's wait with the interpreter's lock
        # released (a cold start's compiles; the bank's first casts), and
        # the trace of the decode program, whose own ``import`` it is,
        # waits for what is left of it.
        if utils.is_tpu_backend() and not hasattr(self.model, "block_spec"):
            # (The chunk's kernel is a few lines on top of the same
            # Pallas: ``decode.paged_chunk_attention``.)
            threading.Thread(
                target=importlib.import_module,
                args=("rayfed_tpu.ops.paged_chunk_attention",),
                name="fed-serve-kernel-import", daemon=True,
            ).start()
        self.scfg = config or ServingConfig()
        self.draft_cfg = draft_cfg
        self.name = name
        self._cache_dtype = cache_dtype
        # A state that is carried (not masked) cannot be adopted from a
        # block chain or rolled back: refuse here, by name, rather than
        # answer wrongly later.
        self._recurrent = bool(self.model.state_spec(cache_dtype))
        if self._recurrent and self.scfg.prefix_reuse:
            raise ValueError(
                "serving.prefix_reuse adopts a donor's K/V blocks, which "
                f"says nothing of {type(model_cfg).__name__}'s recurrent "
                "state at that point (no state snapshots yet); set "
                "prefix_reuse=False"
            )
        self.pool = PagedKVPool(
            model_cfg,
            self.scfg.max_slots,
            self.scfg.max_len,
            cache_dtype,
            block_size=self.scfg.kv_block_size,
            num_blocks=self.scfg.kv_blocks,
        )
        self._buckets = sorted(
            self.scfg.prompt_buckets or _default_buckets(self.scfg.max_len)
        )
        self._chunk_buckets = sorted(
            {min(b, self.scfg.prefill_chunk) for b in _default_buckets(
                self.scfg.prefill_chunk)}
        )
        # A model that generates by blocks (``decode.BlockSpec``), or None.
        self._block = self.pool.block
        if self._block is not None:
            self._check_block_shapes()
        # Which read a decode step makes of the pool: what it walks is
        # counted by that (``kv_blocks_walked``).
        operands = decode.paged_read_operands(
            self.pool.model.kv_spec(), self.pool.kv)
        self._kernel_paged = (
            self._block is None
            and decode.paged_read_is_kernel(
                *operands, self.pool.max_slots * self.pool.blocks_per_row))
        # And which read a prompt chunk makes of it: the loop, or on a TPU
        # a kernel a trip where the slots reach far
        # (``prefill_chunks_kernel``).
        self._kernel_chunk = decode.paged_chunk_is_kernel(
            *operands, self.pool.blocks_per_row,
            block=self._block and self._block.length)
        self._prefill_fns: Dict[int, Any] = {}
        self._chunk_fns: Dict[int, Any] = {}
        self._special_fns: Dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: "deque[_Request]" = deque()
        self._active: Dict[int, _Request] = {}     # slot -> request
        self._prefilling: List[_Request] = []      # chunked prefills
        # version -> its decode step in flight (engine thread only)
        self._ahead: Dict[int, _Ahead] = {}
        self._rid_counter = itertools.count()
        self._stopping = False
        self._fatal: Optional[BaseException] = None
        # The MODEL's layers that attend (each once a token), whatever
        # arrays of the pool each of them keeps a row in, and of them the
        # windows of those that have one and the top-k of those that select
        # their keys (both optional in the protocol: without
        # ``layer_windows`` and ``layer_index_topk`` every layer attends
        # every key). A window of 0 is a layer that attends NO key (a
        # linear-attention layer: it reads no key and walks no block): the
        # counts by layer below are over the others.
        windows = getattr(self.model, "layer_windows", tuple)()
        self._n_attending = self.cfg.n_layers - sum(w == 0 for w in windows)
        self._windows = tuple(w for w in windows if w)
        self._index_topk = tuple(
            k for k in getattr(self.model, "layer_index_topk", tuple)()
            if k is not None
        )
        if self._index_topk and self.scfg.prefix_reuse:
            raise ValueError(
                "serving.prefix_reuse is not served for "
                f"{type(model_cfg).__name__} (a learned selection of keys: "
                "no test shows an adopted block chain sound under it yet); "
                "set prefix_reuse=False"
            )
        # The counters: ``_COUNTERS``' rows that this model has, then what
        # its decode step counts on the device (it declares the names;
        # none for most models), fetched behind the ids. ``_stats`` is
        # what ``stats()`` returns of them, ``_series`` each key's child in
        # the telemetry registry; :meth:`_count` bumps both.
        _reg = telemetry_metrics.get_registry()
        has = {"layer_windows": self._windows,
               "layer_index_topk": self._index_topk,
               "block_spec": self._block}
        self._stats: Dict[str, int] = {}
        self._series: Dict[str, Any] = {}
        for row in _COUNTERS + tuple(
            _Counter(key,
                     f"The served model's decode-step counter {key!r}, "
                     "counted on the device and fetched with the chosen ids.")
            for key in self.pool.step_counters
        ):
            held = row.gate is None or bool(has[row.gate])
            if held:
                self._stats[row.key] = 0
            if row.help is not None and (held or row.series_for_all):
                labels = {"server": name}
                if row.event:
                    labels["event"] = row.key
                self._series[row.key] = _reg.counter(
                    row.series or f"fed_serving_{row.key}_total", row.help,
                    labels=tuple(labels),
                ).labels(**labels)
        self._latencies_ms: "deque[float]" = deque(maxlen=4096)
        # Gauges and the latency histogram mirror live values, not
        # ``_stats``.
        self._m_pending = _reg.gauge(
            "fed_serving_pending", "Requests awaiting admission.",
            labels=("server",),
        ).labels(server=name)
        self._m_active = _reg.gauge(
            "fed_serving_active", "Requests in the decode batch.",
            labels=("server",),
        ).labels(server=name)
        self._m_latency = _reg.histogram(
            "fed_serving_latency_ms",
            "End-to-end request latency (enqueue to finish).",
            labels=("server",),
        ).labels(server=name)
        self._m_kv_in_use = _reg.gauge(
            "fed_serving_kv_blocks_in_use",
            "KV blocks resident for live requests.",
            labels=("server",),
        ).labels(server=name)
        self._m_kv_free = _reg.gauge(
            "fed_serving_kv_blocks_free",
            "KV blocks on the free list.",
            labels=("server",),
        ).labels(server=name)
        _reg.gauge(
            "fed_serving_kv_token_bytes",
            "Bytes one token keeps in the paged pool, all layers and "
            "arrays the model declares.",
            labels=("server",),
        ).labels(server=name).set(self.pool.token_bytes)
        self._update_kv_gauges()
        # Whatever way a version comes in (publish, a promoted standby's
        # state), the bank's snapshot of it is the tree the programs read.
        self.bank = ModelBank(prepare=self._make_snapshot_fn())
        if params is not None:
            self.bank.publish(params)
        self._engine = threading.Thread(
            target=self._engine_loop,
            name=f"fedtpu-serve-{name}",
            daemon=True,
        )
        self._engine.start()

    def _check_block_shapes(self) -> None:
        """What a model that generates by blocks needs of the serving
        configuration, refused by name otherwise: its K/V at a position
        depend on the block the position lies in, so a donor's boundary
        block says nothing of another request's (no prefix reuse until a
        test shows it sound at block boundaries), and every prefill
        shape must hold whole blocks (the block-causal masks are
        ``k_pos <= q_pos | (B - 1)``)."""
        b = self._block.length
        who = type(self.cfg).__name__
        if self.scfg.prefix_reuse:
            raise ValueError(
                f"serving.prefix_reuse is not served for {who} (generation "
                "by blocks: a prompt's last block is denoised with what "
                "follows it); set prefix_reuse=False"
            )
        if self._recurrent or getattr(self.model, "layer_windows", None):
            raise ValueError(
                f"{who}: generation by blocks is served without a "
                "recurrent state and without windowed layers"
            )
        shapes = {"max_len": [self.scfg.max_len],
                  "prefill_chunk": [self.scfg.prefill_chunk],
                  "prompt_buckets": self._buckets,
                  "chunk buckets": self._chunk_buckets}
        for name, sizes in shapes.items():
            if any(n % b for n in sizes):
                raise ValueError(
                    f"serving {name} {sizes} must be multiples of {who}'s "
                    f"block length {b}"
                )

    # -- jitted programs -------------------------------------------------

    def _get_prefill_rows_fn(self, bucket: int):
        """Batched prefill, compiled once per bucket length: one dispatch
        of the model's ``prefill_rows`` prefills EVERY row admitted this
        round from right-padded (bucket,) prompts (``landed`` names them;
        the other lanes scatter into the sacrificial block and nothing
        reads what comes back for them, so a model need not compute
        them). Fresh zero rows (and a zero recurrent state), not recycled
        ones. Returns (each row's first token (R,) int32, chosen from the
        logits at ``last_idx`` under ``draw``, the rows of the cache (one
        array per array of the pool), the rows' state)."""
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        import jax

        model = self.model
        row_len = self.scfg.max_len + 1
        dtype = self._cache_dtype

        @jax.named_scope("serve/prefill")
        def prefill_rows(params, prompts, last_idx, landed, draw):
            last, rows, state = model.prefill_rows(
                params, prompts, last_idx, row_len, dtype, landed
            )
            return sampling.choose_packed(last, draw), rows, state

        fn = jax.jit(prefill_rows)
        self._prefill_fns[bucket] = fn
        return fn

    def _get_chunk_fn(self, clen: int):
        """One prompt chunk of one slot at a dynamic offset, against the
        pool itself: the model's ``chunk`` reads the chunk's context
        through the slot's block table and writes its K/V (and the slot's
        rows of a recurrent state) in place; the pool and the state are
        donated (:meth:`PagedKVPool.chunk_step` makes the call). Compiled
        per padded chunk length. The write range [offset, offset + clen)
        always lies inside the prompt (the ragged remainder is chunked
        FIRST), so padding never lands on live positions. ``n_real`` of
        the chunk's positions are the prompt's, the rest padding. Returns
        (the token chosen from the last real position's logits under
        ``draw`` (3, 1): the request's first token when this is its last
        chunk, read by nobody otherwise; the pool; the state)."""
        fn = self._chunk_fns.get(clen)
        if fn is not None:
            return fn
        import jax

        model = self.model

        @jax.named_scope("serve/chunk")
        def chunk_step(params, kv, state, table, slot, toks, offset,
                       n_real, draw):
            last, kv, state = model.chunk(
                params, kv, state, table, slot, toks, offset, n_real
            )
            return sampling.choose_packed(last[None], draw)[0], kv, state

        fn = jax.jit(chunk_step, donate_argnums=(1, 2))
        self._chunk_fns[clen] = fn
        return fn

    # -- client surface --------------------------------------------------

    def _make_snapshot_fn(self):
        """The snapshot an engine's bank takes of an incoming tree
        (``ModelBank(prepare=)``): in the serving dtype of the model that
        reads it, cast once here and not in every program that is handed
        it. It captures the models and where it counts, not the engine:
        a bank never keeps its engine (and its pool) alive."""
        models = {"params": self.model}
        if self.draft_cfg is not None:
            models["draft_params"] = decode.serving_model(self.draft_cfg)
        count = functools.partial(_bump, self._stats, self._series)
        lock = self._lock

        def snapshot(key: str, tree: Any) -> Any:
            dtype = models[key].serving_dtype() if key in models else None
            nbytes = cast_nbytes(tree, dtype)
            if not nbytes:
                return snapshot_tree(tree)
            with tracing.phase("fed:serve:publish_cast"):
                snap = snapshot_tree(tree, dtype)
            with lock:
                count("publish_cast_bytes", nbytes)
            return snap

        return snapshot

    def publish(self, params: Any, *, draft_params: Any = None) -> int:
        """Atomically install a new model version; in-flight requests
        finish on the version they pinned at admission. The bank's
        snapshot is device-resident (NumPy leaves are uploaded once,
        here) and in the model's serving dtype — the jitted step
        receives it on every iteration."""
        version = self.bank.publish(params, draft_params=draft_params)
        tracing.record_request(
            f"publish-v{version}", "publish", version=version
        )
        logger.info("serving[%s]: published model version %d",
                    self.name, version)
        return version

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        seed: int = 0,
        mode: str = "generate",
        n_beams: int = 4,
        stream: Any = None,
    ) -> Future:
        """Enqueue one request; returns a Future of the response dict.

        ``stream`` optionally attaches a token sink (an object with
        ``push``/``reset``/``fail`` — see :mod:`serving.stream`); the
        engine pushes every sampled token into it without ever blocking
        on the consumer.

        Admission control is synchronous: a full pending queue raises
        :class:`ServerOverloadedError` here, on the submitter, rather
        than growing unbounded latency inside the engine.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if mode not in ("generate", "beam", "speculative"):
            raise ValueError(f"unknown request mode {mode!r}")
        if mode != "generate" and self._recurrent:
            raise ValueError(
                f"mode={mode!r} reorders or rolls back cache rows; "
                f"{type(self.cfg).__name__}'s recurrent state cannot be "
                "rolled back (only mode='generate' is served)"
            )
        if mode != "generate" and self._index_topk:
            raise ValueError(
                f"mode={mode!r} is not served for "
                f"{type(self.cfg).__name__} (a learned selection of keys: "
                "only mode='generate')"
            )
        if self._block is not None:
            if mode != "generate":
                raise ValueError(
                    f"mode={mode!r} is not served for "
                    f"{type(self.cfg).__name__} (generation by blocks: "
                    "only mode='generate')"
                )
            if np.any(prompt == self._block.mask_id):
                raise ValueError(
                    f"the prompt holds the mask id {self._block.mask_id}: "
                    "a masked position is what the model fills in"
                )
        if mode == "speculative" and self.draft_cfg is None:
            raise ValueError(
                "mode='speculative' needs a server started with draft_cfg"
            )
        max_new = int(max_new_tokens or self.scfg.max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new > self.scfg.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds serving.max_len ({self.scfg.max_len})"
            )
        if mode == "generate":
            # Worst-case resident blocks for this request (highest
            # written position is prompt + generation - 2). A request
            # that could never fit the whole pool must fail HERE, not
            # livelock admission.
            hi = prompt.size + max(0, max_new - 2)
            need = hi // self.pool.block_size + 1
            if need > self.pool.num_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks at worst but the "
                    f"pool has {self.pool.num_blocks} "
                    "(serving.kv_blocks)"
                )
        temp = self.scfg.temperature if temperature is None else temperature
        fut: Future = Future()
        now = time.perf_counter()
        with self._cond:
            if self._fatal is not None:
                raise ServerStoppedError(
                    f"serving engine died: {self._fatal!r}"
                )
            if self._stopping:
                raise ServerStoppedError("server is stopped")
            if len(self._pending) >= self.scfg.max_pending:
                self._count("rejected")
                raise ServerOverloadedError(
                    f"pending queue full ({self.scfg.max_pending}); "
                    "back off and resubmit"
                )
            rid = f"{self.name}-{next(self._rid_counter)}"
            req = _Request(
                rid=rid,
                prompt=prompt,
                max_new_tokens=max_new,
                temperature=float(temp),
                seed=int(seed),
                mode=mode,
                n_beams=int(n_beams),
                future=fut,
                enqueue_s=now,
                stream=stream,
            )
            req.timing["enqueue"] = now
            self._count("submitted")
            self._pending.append(req)
            self._m_pending.set(len(self._pending))
            self._cond.notify_all()
        tracing.record_request(rid, "enqueue", t_s=now,
                               prompt_len=int(prompt.size), mode=mode)
        return fut

    def submit_and_wait(self, prompt, **opts) -> Dict[str, Any]:
        return self.submit(prompt, **opts).result()

    def submit_stream(self, prompt, **opts):
        """Submit with an in-process token stream attached; returns
        ``(future, stream)``. Iterate the stream for tokens as they are
        sampled; the future resolves to the usual response dict."""
        from rayfed_tpu.serving.stream import LocalTokenStream

        stream = LocalTokenStream()
        fut = self.submit(prompt, stream=stream, **opts)
        return fut, stream

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self._stats)
            out["pending"] = len(self._pending)
            out["active"] = len(self._active) + len(self._prefilling)
            lats = list(self._latencies_ms)
        out["kv_blocks_in_use"] = self.pool.blocks_in_use
        out["kv_blocks_free"] = self.pool.blocks_free
        out["kv_block_size"] = self.pool.block_size
        out["kv_token_bytes"] = self.pool.token_bytes
        # Compiled variants across the engine's jitted programs: flat
        # after warm-up, or something (a new bucket, a published tree
        # with another sharding) is compiling inside the serving window.
        out["compiled_programs"] = sum(
            fn._cache_size() for fn in (
                *self._prefill_fns.values(), *self._chunk_fns.values(),
                *self.pool.jitted_fns(),
            )
        )
        out["current_version"] = self.bank.current_version()
        out["swaps"] = self.bank.swap_count()
        out["live_versions"] = self.bank.live_versions()
        if lats:
            out["p50_ms"] = float(np.percentile(lats, 50))
            out["p99_ms"] = float(np.percentile(lats, 99))
        return out

    def stop(self, timeout: float = 30.0) -> None:
        """Stop admission, finish ACTIVE requests, fail still-pending
        ones with :class:`ServerStoppedError`, and join the engine."""
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        self._engine.join(timeout)

    # -- engine ----------------------------------------------------------

    def _engine_loop(self) -> None:
        try:
            while True:
                with self._cond:
                    if self._nothing_to_do():
                        with tracing.phase("fed:serve:idle"):
                            while self._nothing_to_do():
                                self._cond.wait(0.05)
                    if self._stopping:
                        # Drain policy: admitted requests (active OR
                        # mid-chunked-prefill) complete, queued ones fail
                        # fast (they were never admitted, the no-abort
                        # guarantee starts at admission).
                        pending, self._pending = self._pending, deque()
                        if (
                            not self._active
                            and not self._prefilling
                            and not self._ahead
                            and not pending
                        ):
                            return
                    else:
                        pending = None
                if pending:
                    for req in pending:
                        exc = ServerStoppedError(
                            "server stopped before admission"
                        )
                        if req.stream is not None:
                            req.stream.fail(exc)
                        req.future.set_exception(exc)
                # Decode steps before prefill chunks: freed blocks go to
                # the oldest (already-decoding) requests first, so a
                # preemption's memory cannot be stolen by new work
                # (which would livelock the batch under block pressure).
                # A decode step is dispatched before the one before it
                # is fetched (:meth:`_step_groups`), so admission above
                # and the chunks below run with a step in flight: the
                # programs they dispatch queue behind it on the device.
                # A step dispatched is progress like a token emitted, so
                # "no progress" (the backoff, a preemption) is concluded
                # only of an iteration that found nothing in flight and
                # left nothing in flight.
                # The host phases of an iteration (fed:serve:*, on the
                # profiler's clock) tile it: none encloses another, so an
                # idle gap of the device is booked to the piece of host
                # work that filled it (docs/observability.md).
                with tracing.phase("fed:serve:admit"):
                    progressed = self._admit()
                progressed = self._step_groups() or progressed
                with tracing.phase("fed:serve:prefill_chunk"):
                    progressed = self._prefill_tick() or progressed
                self._update_kv_gauges()
                if not progressed and not self._maybe_preempt():
                    # Blocked on something external (another tenant's
                    # quota, a consumer): bounded backoff, not a hot spin.
                    with tracing.phase("fed:serve:idle"), self._cond:
                        self._cond.wait(0.005)
        except BaseException as e:  # noqa: BLE001 - fail loud, never hang
            logger.exception("serving[%s]: engine died", self.name)
            self._fail_all(e)

    def _nothing_to_do(self) -> bool:
        # Caller holds self._cond.
        return (
            not self._stopping
            and not self._pending
            and not self._active
            and not self._prefilling
            # A step whose rows all ended under it is still fetched: what
            # it counted on the device rides behind its ids.
            and not self._ahead
        )

    def _update_kv_gauges(self) -> None:
        self._m_kv_in_use.set(self.pool.blocks_in_use)
        self._m_kv_free.set(self.pool.blocks_free)

    def _fail_all(self, exc: BaseException) -> None:
        with self._cond:
            self._fatal = exc
            doomed = (
                list(self._pending)
                + list(self._active.values())
                + list(self._prefilling)
            )
            self._pending.clear()
            self._active.clear()
            self._prefilling.clear()
            self._ahead.clear()
            self._m_pending.set(0)
            self._m_active.set(0)
        for req in doomed:
            if req.stream is not None:
                req.stream.fail(exc)
            if not req.future.done():
                req.future.set_exception(exc)

    def _admit(self) -> bool:
        """Prefill-then-merge: move pending requests into free slots.
        Runs between decode iterations — a token boundary for every
        in-flight sequence. Returns True when anything was admitted."""
        admitted = 0
        batch: List[_Request] = []
        while True:
            with self._lock:
                if not self._pending:
                    break
                if any(r.stalled for r in self._active.values()) or any(
                    r.stalled for r in self._prefilling
                ):
                    # Someone admitted is starved for KV blocks: every
                    # free (or about-to-be-freed) block is spoken for.
                    # Admitting more would steal it and livelock.
                    break
                req = self._pending[0]
                if req.mode == "generate":
                    slot = self.pool.acquire()
                    if slot is None:
                        break
                else:
                    slot = -1
                self._pending.popleft()
                self._m_pending.set(len(self._pending))
            try:
                if req.mode == "generate":
                    outcome = self._admit_generate(req, slot, batch)
                    if outcome == "flush":
                        self._batched_prefill(batch)
                        batch = []
                        outcome = self._admit_generate(req, slot, batch)
                    if outcome == "blocked":
                        # Slot handed back, request re-queued at the
                        # front: nothing later in the queue can be
                        # smaller-than-FIFO-fair, stop admitting.
                        break
                    admitted += 1
                else:
                    self._admit_special(req)
                    admitted += 1
            except BaseException as e:  # noqa: BLE001 - per-request fault
                # A bad request (or a bug in its path) fails ITS future;
                # the batch and the engine keep serving everyone else.
                if slot >= 0:
                    self.pool.release(slot)
                if req.version:
                    self.bank.release(req.version)
                    req.version = 0
                if req.stream is not None:
                    req.stream.fail(e)
                if not req.future.done():
                    req.future.set_exception(e)
        self._batched_prefill(batch)
        return admitted > 0

    def _admit_special(self, req: _Request) -> None:
        """Admit a beam/speculative request: pin a version and run it
        whole (it takes no slot of the pool)."""
        req.version, params = self.bank.acquire()
        now = time.perf_counter()
        req.timing["admit"] = now
        tracing.record_request(req.rid, "admit", t_s=now,
                               version=req.version, slot=-1)
        self._run_special(req, params)

    def _post_prefill(self, req: _Request, chosen) -> None:
        """Shared admission tail (batched/chunked/donor paths): record
        the prefix donor, take the first token (``chosen``, the id the
        prefill's program picked; None for a model that generates by
        blocks), and either finish or join the decode batch."""
        plen = int(req.prompt.size)
        self.pool.note_prefix(req.slot, req.version, req.prompt.tobytes())
        now = time.perf_counter()
        req.timing["prefill"] = now
        tracing.record_request(req.rid, "prefill", t_s=now,
                               reused=req.prefix_reuse)
        if self._block is not None:
            # No token comes of a prompt (a position predicts itself):
            # the request's first block opens at the end of the prompt's
            # whole blocks, the left-over tokens already unmasked.
            spec = self._block
            left = plen % spec.length
            req.pos = plen - left
            req.block = [int(t) for t in req.prompt[req.pos:]] + [
                spec.mask_id] * (spec.length - left)
            req.block_step, req.block_out = 0, left
            req.block_steps = [-1] * left + [0] * (spec.length - left)
            with self._lock:
                self._active[req.slot] = req
                self._m_active.set(len(self._active))
            return
        tok = self._sample(chosen, req)
        req.out.append(tok)
        req.pos = plen
        now = time.perf_counter()
        req.timing["first_token"] = now
        tracing.record_request(req.rid, "first_token", t_s=now)
        self._emit_token(req, tok)
        if len(req.out) >= req.max_new_tokens or tok == self.scfg.eos_id:
            self._finish(req)
        else:
            with self._lock:
                self._active[req.slot] = req
                self._m_active.set(len(self._active))

    # -- admission / chunked prefill -------------------------------------

    def _acquire_version(self, req: _Request):
        """Pin the current version — or, for a preempted request, reuse
        the pin it kept (the deterministic re-run must see the SAME
        params, and the pin stops the bank retiring them)."""
        if req.version:
            return self.bank.get(req.version)
        req.version, params = self.bank.acquire()
        return params

    def _prefill_len(self, req: _Request) -> int:
        """The prompt positions the prefill programs are given: all of
        them, or the prompt's whole blocks for a model that generates by
        blocks (the left-over tokens start its first block)."""
        plen = int(req.prompt.size)
        if self._block is not None:
            plen -= plen % self._block.length
        return plen

    def _admit_generate(
        self, req: _Request, slot: int, batch: List[_Request]
    ) -> str:
        """Admit one generate request. Returns "ok" (admitted: into
        ``batch``, ``self._prefilling``, or already running via a prefix
        donor) or "blocked" (no KV blocks for even its first chunk —
        slot returned, request re-queued at the front)."""
        params = self._acquire_version(req)
        now = time.perf_counter()
        req.timing["admit"] = now
        tracing.record_request(req.rid, "admit", t_s=now,
                               version=req.version, slot=slot)
        req.slot = slot
        plen = self._prefill_len(req)
        prompt_key = req.prompt.tobytes()
        if self.scfg.prefix_reuse:
            donor = self.pool.lookup_prefix(req.version, prompt_key)
            if donor is None and any(
                r.version == req.version
                and r.prompt.tobytes() == prompt_key
                for r in batch
            ):
                # Our donor-to-be is sitting in the un-prefilled batch:
                # flush it first (the caller re-tries us), so identical
                # prompts admitted in one round still share blocks.
                return "flush"
            if donor is not None and donor != slot:
                # Prefix reuse is a block-table copy: share the donor's
                # fully-prompt blocks, clone only the boundary block,
                # then one single-row step re-derives the last-position
                # logits and picks the first token from them.
                status = self.pool.adopt_prefix(donor, slot, plen)
                if status == "ok":
                    req.prefix_reuse = True
                    self._count("prefix_hits")
                    self._post_prefill(req, self._step_one_row(params, req))
                    return "ok"
                # fall through: no blocks for the boundary clone — the
                # plain grant below will hit the same wall and re-queue.
        chunk = self.scfg.prefill_chunk
        if plen == 0:
            # A prompt shorter than a block: nothing to prefill.
            self._post_prefill(req, None)
            return "ok"
        if plen <= chunk:
            status = self.pool.ensure_blocks(slot, plen - 1)
            if status != "ok":
                return self._admission_blocked(req, status)
            batch.append(req)
            return "ok"
        # Chunked prefill: the ragged remainder runs FIRST so every
        # later chunk is exactly `chunk` long and ends exactly at plen.
        first = plen % chunk or chunk
        status = self.pool.ensure_blocks(slot, first - 1)
        if status != "ok":
            return self._admission_blocked(req, status)
        req.chunk_done = 0
        with self._lock:
            self._prefilling.append(req)
        return "ok"

    def _quota_hopeless(self, req: _Request) -> bool:
        """True when a "quota" grant failure can never clear: every
        kv_block charged to this tenant is already ours (``req``'s own
        grants included), so no future release can make room."""
        from rayfed_tpu.tenancy.qos import get_ledger

        own = self.pool.granted(req.slot) if req.slot >= 0 else 0
        in_use = get_ledger().in_use(self.pool._job, "kv_blocks")
        return in_use - own <= 0

    def _fail_admitted(self, req: _Request, exc: BaseException) -> None:
        """Hard-fail an already-admitted request (engine thread only)."""
        self._forget(req)
        with self._lock:
            if self._active.get(req.slot) is req:
                del self._active[req.slot]
                self._m_active.set(len(self._active))
            if req in self._prefilling:
                self._prefilling.remove(req)
        if req.slot >= 0:
            self.pool.release(req.slot)
            req.slot = -1
        if req.version:
            self.bank.release(req.version)
            req.version = 0
        if req.stream is not None:
            req.stream.fail(exc)
        if not req.future.done():
            req.future.set_exception(exc)

    def _quota_exc(self, req: _Request) -> BaseException:
        from rayfed_tpu.tenancy.qos import TenantQuotaExceeded, get_ledger

        from rayfed_tpu.tenancy.context import get_context

        job = self.pool._job
        ctx = get_context(job) if job else None
        limit = ctx.tenancy.kv_block_quota if ctx else 0
        return TenantQuotaExceeded(
            job, "kv_blocks", 1,
            get_ledger().in_use(job, "kv_blocks"), limit or 0,
        )

    def _admission_blocked(self, req: _Request, status: str) -> str:
        """No KV blocks at admission: hand the slot back and re-queue at
        the front — unless the quota can NEVER be satisfied (nothing
        else of ours is charged against it), which is a loud per-request
        failure, not a wait."""
        if status == "quota" and self._quota_hopeless(req):
            self._fail_admitted(req, self._quota_exc(req))
            return "failed"
        self.pool.release(req.slot)
        req.slot = -1
        # Keep the version pin across the wait (determinism on re-run).
        with self._cond:
            self._pending.appendleft(req)
            self._m_pending.set(len(self._pending))
        return "blocked"

    def _batched_prefill(self, batch: List[_Request]) -> None:
        """ONE prefill dispatch per (version, bucket) group for every
        short-prompt request admitted this round."""
        if not batch:
            return
        groups: Dict[tuple, List[_Request]] = {}
        for req in batch:
            plen = self._prefill_len(req)
            bucket = next(
                (b for b in self._buckets if b >= plen), self._buckets[-1]
            )
            bucket = max(bucket, plen)
            groups.setdefault((req.version, bucket), []).append(req)
        R = self.pool.max_slots
        NB = self.pool.blocks_per_row
        for version, bucket in sorted(groups):
            reqs = groups[(version, bucket)]
            try:
                params = self.bank.get(version)
                prompts = np.zeros((R, bucket), np.int32)
                last_idx = np.zeros(R, np.int32)
                tables = np.zeros((R, NB), np.int32)
                landed = np.zeros(R, bool)
                for req in reqs:
                    plen = self._prefill_len(req)
                    prompts[req.slot, :plen] = req.prompt[:plen]
                    last_idx[req.slot] = plen - 1
                    tables[req.slot] = self.pool.table(req.slot)
                    landed[req.slot] = True
                fn = self._get_prefill_rows_fn(bucket)
                ids, slabs, state_rows = fn(
                    params, prompts, last_idx, landed,
                    self._draw_inputs(reqs),
                )
                # Each landed row's recurrent state is the fresh one its
                # prefill computed from zero: this is where a recycled
                # slot's old state ends.
                self.pool.scatter_rows(*slabs, tables, state_rows, landed)
                self._count_state_resets(len(reqs))
                for req in reqs:
                    self._count_prefill(0, self._prefill_len(req))
                # (No id to read where a model generates by blocks.)
                ids = None if self._block else self._fetch(ids)
                for req in reqs:
                    self._post_prefill(
                        req, None if ids is None else ids[req.slot])
            except BaseException as e:  # noqa: BLE001 - per-group fault
                for req in reqs:
                    if req.slot >= 0:
                        self.pool.release(req.slot)
                        req.slot = -1
                    if req.version:
                        self.bank.release(req.version)
                        req.version = 0
                    if req.stream is not None:
                        req.stream.fail(e)
                    if not req.future.done():
                        req.future.set_exception(e)

    def _prefill_tick(self) -> bool:
        """Advance chunked prefills by at most ``prefill_token_budget``
        prompt tokens, merged between decode iterations so long prompts
        never stall the live batch. Returns True if any chunk ran."""
        with self._lock:
            work = list(self._prefilling)
            if any(r.stalled for r in self._active.values()):
                # A decode row is starved: leave every free block to it
                # (decode-first priority; see _engine_loop).
                return False
        if not work:
            return False
        budget = self.scfg.prefill_token_budget
        chunk = self.scfg.prefill_chunk
        ran = False
        for req in work:
            if budget < chunk:
                break
            try:
                plen = self._prefill_len(req)
                off = req.chunk_done
                if off == 0 and plen % chunk:
                    # Ragged remainder first, padded to a chunk bucket;
                    # padded writes land inside [0, plen) and are
                    # overwritten by the next chunk before any query
                    # can attend them.
                    real = plen % chunk
                    clen = next(
                        b for b in self._chunk_buckets if b >= real
                    )
                else:
                    real = clen = chunk
                status = self.pool.ensure_blocks(req.slot, off + real - 1)
                if status != "ok":
                    if status == "quota" and self._quota_hopeless(req):
                        self._fail_admitted(req, self._quota_exc(req))
                    else:
                        req.stalled = True
                    continue
                req.stalled = False
                toks = np.zeros(clen, np.int32)
                toks[:real] = req.prompt[off:off + real]
                # The first chunk (offset 0) starts the request: the
                # program zeroes the slot's recurrent state.
                chosen = self.pool.chunk_step(
                    self._get_chunk_fn(clen), self.bank.get(req.version),
                    req.slot, toks, off, real,
                    sampling.pack([req.temperature], [req.seed], [0]),
                )
                if off == 0:
                    self._count_state_resets(1)
                req.chunk_done = off + real
                budget -= clen
                ran = True
                with self._lock:
                    self._count("prefill_chunks")
                    self._count("chunk_tokens", real)
                    if self._kernel_chunk:
                        self._count("prefill_chunks_kernel")
                    self._count("chunk_state_bytes",
                                2 * self.pool.state_row_bytes)
                self._count_prefill(off, real)
                self._count_chunk_blocks(off)
                if req.chunk_done >= plen:
                    with self._lock:
                        self._prefilling.remove(req)
                    self._post_prefill(
                        req, None if self._block else self._fetch(chosen))
            except BaseException as e:  # noqa: BLE001 - per-request fault
                with self._lock:
                    if req in self._prefilling:
                        self._prefilling.remove(req)
                if req.slot >= 0:
                    self.pool.release(req.slot)
                    req.slot = -1
                if req.version:
                    self.bank.release(req.version)
                    req.version = 0
                if req.stream is not None:
                    req.stream.fail(e)
                if not req.future.done():
                    req.future.set_exception(e)
        return ran

    def _step_inputs(self, rows):
        """(tokens, positions, tables, draw, live, from_host) of one
        paged decode step from the live rows' ``(request, token,
        position)``. A row whose last token the host holds (it came from
        a prefill, or from a step already fetched) sends it in ``tokens``
        and is marked ``from_host``; a row whose last token is in the
        step in flight has ``token`` None and sends nothing: the program
        reads it from that step's ids. Every other row is junk: position
        0 under an all-zero table, so it visits no block and writes into
        the sacrificial block 0; greedy in ``draw``, so it asks for no
        noise; and not ``live``, so whatever recurrent state its slot
        holds comes back bit for bit. For a model that generates by
        blocks ``token`` is the row's block (``B`` ids, ``tokens`` (R,
        B)) and ``position`` the block's first."""
        R = self.pool.max_slots
        tokens = np.zeros(self.pool.ids_len, np.int32).reshape(R, -1)
        if self._block is None:
            tokens = tokens[:, 0]
        positions = np.zeros(R, np.int32)
        tables = np.zeros((R, self.pool.blocks_per_row), np.int32)
        live = np.zeros(R, bool)
        from_host = np.ones(R, bool)
        reqs = []
        for req, token, pos in rows:
            if token is None:
                from_host[req.slot] = False
            else:
                tokens[req.slot] = token
            positions[req.slot] = pos
            tables[req.slot] = self.pool.table(req.slot)
            live[req.slot] = True
            reqs.append(req)
        return (tokens, positions, tables,
                self._draw_inputs(reqs, self._block is not None), live,
                from_host)

    def _draw_inputs(self, reqs, blocks: bool = False) -> np.ndarray:
        """The sampler's per-row scalars for a program over all slots
        (:func:`sampling.pack`, one upload): each request's temperature,
        seed and the position in its output of the token about to be
        chosen (the tokens it has, and the one in flight), at its slot;
        zero (greedy) everywhere else. For a decode step that carries
        ``blocks``: the place in the output of the block's first position
        (below zero where the prompt's left-over tokens lead the block),
        and the denoising step of the block as a fourth row."""
        R = self.pool.max_slots
        temperature = np.zeros(R, np.float32)
        seed = [0] * R
        index = np.zeros(R, np.int32)
        step = np.zeros(R, np.int32) if blocks else None
        for req in reqs:
            temperature[req.slot] = req.temperature
            seed[req.slot] = req.seed
            if blocks:
                pos, step[req.slot] = self._next_block(req)
                index[req.slot] = pos - int(req.prompt.size)
            else:
                index[req.slot] = len(req.out) + req.ahead
        return sampling.pack(temperature, seed, index, step)

    def _next_block(self, req: _Request):
        """(first position, denoising step) of the block that the next
        step dispatched for ``req`` carries. The host knows both though
        it has not read the step in flight: that step commits exactly
        when the block the last fetch showed is clean, and is then step 0
        of the block behind it, which the next step carries a step
        further on; else the block stays, a step further on. (A clean
        block with nothing in flight is carried as it is: the device
        commits it and moves the sampler on to the next block's step 0
        itself.)"""
        if req.ahead and self._block.mask_id not in req.block:
            return req.pos + self._block.length, 1
        return req.pos, req.block_step + req.ahead

    def _ends_in_flight(self, req: _Request) -> bool:
        """True when the host knows that the step in flight ends ``req``:
        its last token is in it, or (generation by blocks) its last block
        is, with no more positions masked than that step must unmask."""
        if self._block is None:
            return len(req.out) + req.ahead >= req.max_new_tokens
        spec = self._block
        end = int(req.prompt.size) + req.max_new_tokens
        # The block the step in flight denoises: the held one, or step 0
        # of the one behind it where the held one is clean.
        pos, step = req.pos, req.block_step
        masked = req.block.count(spec.mask_id)
        if not masked:
            pos, step, masked = pos + spec.length, 0, spec.length
        return (req.ahead > 0 and pos + spec.length >= end
                and masked <= spec.quota(step))

    def _fetch(self, ids) -> np.ndarray:
        """The ids a program chose, on the host (this waits for the
        program), counted in ``fetch_bytes``."""
        ids = np.asarray(ids)
        self._count("fetch_bytes", ids.nbytes)
        return ids

    def _count(self, key: str, n: int = 1) -> None:
        """The counter ``key`` goes up by ``n``: its entry of ``stats()``
        and its series in the telemetry registry, if it has one
        (``_COUNTERS``). Takes no lock: where another thread than the
        engine's reads or writes the key, the caller holds ``_lock``."""
        _bump(self._stats, self._series, key, n)

    def _count_prefill(self, off: int, n: int) -> None:
        """``n`` real prompt tokens at positions ``off .. off + n - 1``
        went through a prefill program: count them and the keys they
        attended over the layers (the query at position q sees q + 1
        keys, at most ``window`` on a windowed layer)."""
        def seen(upto: int, window: int) -> int:
            # Keys seen by the queries at positions 0 .. upto - 1.
            ramp = min(upto, window)
            return ramp * (ramp + 1) // 2 + (upto - ramp) * window

        end = off + n                 # no window binds below this
        if self._block is not None:
            # Block-causal: a query sees every key up to its block's end
            # (``off`` and ``n`` are whole blocks).
            b = self._block.length
            lo, hi = off // b, end // b
            keys = self._n_attending * b * b * (
                hi * (hi + 1) // 2 - lo * (lo + 1) // 2)
        else:
            # (An indexed layer's query attends the ``min(k, q + 1)`` keys
            # its indexer kept: a window's count.)
            causal = seen(end, end) - seen(off, end)
            full = self._n_attending - len(self._windows) - len(self._index_topk)
            keys = full * causal + sum(
                seen(end, w) - seen(off, w)
                for w in self._windows + self._index_topk)
            self._count_index(
                len(self._index_topk) * causal,
                sum(seen(end, k) - seen(off, k) for k in self._index_topk))
        with self._lock:
            self._count("prefill_tokens", n)
            self._count("prefill_keys_attended", keys)

    def _count_index(self, scored: int, selected: int,
                     decode_step: bool = False) -> None:
        """The indexed layers' (query, key) pairs scored and kept."""
        if not self._index_topk:
            return
        with self._lock:
            self._count("index_keys_scored", scored)
            self._count("index_keys_selected", selected)
            if decode_step:
                self._count("index_keys_scored_decode", scored)
                self._count("index_keys_selected_decode", selected)

    def _count_chunk_blocks(self, off: int) -> None:
        """A chunk at offset ``off`` ran: count the blocks that hold the
        cached keys ``[lo, off)`` its attention gathers, over the layers
        (``lo`` 0, or the first key the chunk's first query sees on a
        windowed layer), beside the blocks of a whole row in every
        layer."""
        bs = self.pool.block_size
        upto = -(-off // bs)
        read = (self._n_attending - len(self._windows)) * upto + sum(
            upto - max(off - w + 1, 0) // bs for w in self._windows
        )
        row = self._n_attending * self.pool.blocks_per_row
        with self._lock:
            self._count("chunk_blocks_read", read)
            self._count("chunk_blocks_row", row)

    def _layer_blocks(self, positions, attended: int) -> int:
        """Blocks the layers of the live rows (at ``positions``) must
        read in a decode step, summed over rows and layers: every block
        up to a row's position (``attended``, summed over the rows) on a
        layer that attends every key, those that hold ``pos - window + 1
        .. pos`` on a windowed one. An indexed layer reads every block of
        its index keys (its indexer scores every key) and, of its cached
        rows, the fewest blocks that can hold the ``min(k, pos + 1)`` keys
        kept."""
        bs = self.pool.block_size
        return (self._n_attending - len(self._windows)) * attended + sum(
            pos // bs - max(pos - window + 1, 0) // bs + 1
            for window in self._windows for pos in positions
        ) + sum(
            -(-min(pos + 1, k) // bs)
            for k in self._index_topk for pos in positions
        )

    def _blocks_walked(self, positions) -> int:
        """Blocks the read of a decode step copies for the live rows (at
        ``positions``), the mean over the layers: a windowed layer's from
        its window on."""
        walked = functools.partial(
            decode.paged_blocks_walked, positions, self.pool.block_size,
            self.pool.max_slots, self.pool.blocks_per_row,
            kernel=self._kernel_paged)
        # (An indexed layer walks its index keys, by the gather loop
        # whatever the backend; the rows it then reads are single rows.)
        indexed = len(self._index_topk)
        full = self._n_attending - len(self._windows) - indexed
        by_loop = functools.partial(walked, kernel=False)
        return (full * walked() + indexed * by_loop() + sum(
            walked(window) for window in self._windows)) // self._n_attending

    def _layer_keys(self, positions) -> int:
        """Keys the layers of the live rows (at ``positions``) score in a
        decode step, summed over rows and layers: a row at position
        ``pos`` sees its ``pos`` cached keys and its own, at most
        ``window`` of them on a windowed layer."""
        b = self._block.length if self._block else 1
        # (A block's b queries each see the context and the block.)
        seen = sum(b * (pos + b) for pos in positions)
        # An indexed layer's row attends the ``min(k, pos + 1)`` keys its
        # indexer kept of those it scored: a window's count.
        narrow = self._windows + self._index_topk
        return (self._n_attending - len(narrow)) * seen + sum(
            min(pos + 1, width) for width in narrow for pos in positions
        )

    def _count_state_resets(self, n: int) -> None:
        if self._recurrent:
            with self._lock:
                self._count("state_resets", n)

    def _step_one_row(self, params, req: _Request):
        """The decode program with only ``req``'s row live, at the last
        position of its prompt (every other row is junk whatever its
        state: see :meth:`_step_inputs`). Returns the id it chose for
        that row: the request's first token. It is fetched at once and
        feeds no later step: no link of a version's run-ahead chain."""
        ids = self.pool.decode_step(params, *self._step_inputs(
            [(req, int(req.prompt[-1]), int(req.prompt.size) - 1)]
        ))
        return self._fetch(ids)[req.slot]

    def _emit_token(self, req: _Request, tok: int) -> None:
        if req.stream is None:
            return
        req.stream.push(len(req.out) - 1, [tok], False)
        with self._lock:
            self._count("streamed_tokens")

    def _maybe_preempt(self) -> bool:
        """Deadlock breaker: when an iteration made no progress and
        someone is stalled on a block grant, preempt the youngest
        admitted request — release its blocks, re-queue it, and let it
        deterministically re-run later (same version pin, and the
        sampler's key is (seed, position in the output), no state to
        rewind => bit-identical tokens, so streams just skip the replay).
        Returns True when a victim was taken (the loop should retry
        immediately rather than back off)."""
        with self._lock:
            victims = list(self._active.values()) + list(self._prefilling)
            stalled = [r for r in victims if r.stalled]
        if len(victims) < 2 or not stalled:
            # A lone stalled request has nobody to yield to it; its
            # grant can only be waiting on another tenant's release.
            return False
        victim = max(victims, key=lambda r: r.enqueue_s)
        self._preempt(victim)
        return True

    def _forget(self, req: _Request) -> None:
        """Drop the id that a step in flight holds for ``req`` (the
        request ended, was preempted or failed under it): the late fetch
        emits nothing for it. The step's write for the row went to a
        block the row held when it was dispatched, and whatever is
        dispatched from now on runs after it on the device, so the slot
        and its blocks may be released at once."""
        if req.ahead:
            req.ahead = 0
            self._ahead[req.version].rows.remove(req)

    def _preempt(self, req: _Request) -> None:
        self._forget(req)
        with self._lock:
            if self._active.get(req.slot) is req:
                del self._active[req.slot]
                self._m_active.set(len(self._active))
            if req in self._prefilling:
                self._prefilling.remove(req)
        self.pool.release(req.slot)
        req.slot = -1
        req.out = []
        req.steps_out = []
        req.block = None
        req.pos = 0
        req.chunk_done = 0
        req.stalled = False
        req.prefix_reuse = False
        if req.stream is not None:
            req.stream.reset()
        with self._cond:
            self._count("preempted")
            self._pending.appendleft(req)
            self._m_pending.set(len(self._pending))
            self._cond.notify_all()
        tracing.record_request(req.rid, "preempt")
        logger.info("serving[%s]: preempted %s to free KV blocks",
                    self.name, req.rid)

    def _step_groups(self) -> bool:
        """One decode iteration: a batched pool step per live version
        group. Params differ across groups but shapes do not, so every
        group reuses the same compiled program.

        A group keeps one step in flight. Step t + 1 is built and
        dispatched BEFORE step t is fetched: it needs nothing of t's ids
        on the host, because the rows that were live in t take their
        token on the device from t's returned array (the pool's
        ``prev_ids``), and everything else of t + 1 is host state one
        token ahead: the dispatched position ``pos + ahead`` and its
        block grant, the sampler's index ``len(out) + ahead``. Then t is
        fetched (the wait ends with step t, t + 1 queued behind it) and
        emitted. What follows from the one-step lag:

        - a row whose end the host knows ahead (``len(out) + ahead``
          reaches ``max_new_tokens``) is not put into t + 1;
        - a row that ends at t by ``eos_id`` was live in t + 1: that id
          is dropped (:meth:`_forget`, counted in ``rows_wasted``);
        - a row whose grant for t + 1 fails sits t + 1 out and is marked
          ``stalled`` once t is emitted, if it did not end there;
        - with nothing in flight (a group's first step, the step after a
          lull or after every row sat one out) the iteration is build,
          dispatch and nothing to fetch: the ids come an iteration later.

        Where the model generates by blocks the same holds with a block
        for a token: the rows that were live in t take their BLOCK from
        t's returned array, and whether t + 1 denoises it, or commits it
        and denoises the one behind it, is decided on the device from
        what t left of it. The host still knows t + 1's position and
        denoising step (:meth:`_next_block`: t commits exactly when the
        block the last fetch showed is clean, and has then opened the
        next), grants the pool's block for it, and learns one fetch late
        what t unmasked (:meth:`_take_block`). A row whose last block t
        is bound to finish is not put into t + 1
        (:meth:`_ends_in_flight`); one whose last block t finished early
        (more confidences over the threshold than the step had to unmask)
        was live in t + 1 and is counted in ``rows_wasted``.

        Returns True when a step was dispatched or a token emitted."""
        with self._lock:
            groups: Dict[int, List[_Request]] = {}
            for req in self._active.values():
                groups.setdefault(req.version, []).append(req)
        progressed = False
        for version in sorted(groups.keys() | self._ahead.keys()):
            prev = self._ahead.get(version)
            with tracing.phase("fed:serve:build"):
                # Grant each live row's next block at this token
                # boundary (one token ahead of what it has emitted, if
                # its last step is in flight); a row that cannot get one
                # sits out the iteration as junk, decode never stalls
                # the whole batch.
                live, starved = [], []
                for req in groups.get(version, ()):
                    if self._ends_in_flight(req):
                        continue      # its last token is in flight
                    if self._block is None:
                        pos = last = req.pos + req.ahead
                        held = req.out[-1]
                    else:
                        # The block the step forwards, granted whole.
                        pos = self._next_block(req)[0]
                        last = pos + self._block.length - 1
                        held = req.block
                    status = self.pool.ensure_blocks(req.slot, last)
                    if status == "ok":
                        req.stalled = False
                        live.append((
                            req, None if req.ahead else held, pos
                        ))
                    elif status == "quota" and self._quota_hopeless(req):
                        self._fail_admitted(req, self._quota_exc(req))
                    else:
                        starved.append(req)
                # Rows that are free, on another version, stalled or
                # waiting for their last token are junk in this step.
                inputs = self._step_inputs(live) if live else None
            if live:
                with tracing.phase("fed:serve:dispatch"):
                    self._ahead[version] = self._dispatch(
                        self.bank.get(version), live, inputs, prev
                    )
                progressed = True
            elif prev is not None:
                del self._ahead[version]
            if prev is not None:
                with tracing.phase("fed:serve:fetch"):
                    ids = self._fetch(prev.ids)
                    # Behind the R ids: what the model counted in that
                    # step.
                    for key, n in zip(self.pool.step_counters,
                                      ids[self.pool.ids_len:]):
                        self._count(key, int(n))
                with tracing.phase("fed:serve:emit"):
                    take = (self._take_token if self._block is None
                            else self._take_block)
                    for req in prev.rows:
                        req.ahead -= 1
                        progressed = True
                        if take(req, ids):
                            if req.ahead:
                                # Live in the step dispatched above.
                                self._forget(req)
                                self._count("rows_wasted")
                            with self._lock:
                                self._active.pop(req.slot, None)
                                self._m_active.set(len(self._active))
                            self._finish(req)
            for req in starved:
                # The late fetch has shown whether the row ended (or the
                # grant's failure failed it): only a row still admitted
                # is starved, and flags itself for the preemption check.
                req.stalled = req.slot >= 0
        return progressed

    def _take_token(self, req: _Request, ids) -> bool:
        """The token a fetched step chose for ``req``: emitted. True when
        it ends the request."""
        tok = self._sample(ids[req.slot], req)
        req.out.append(tok)
        req.pos += 1
        self._emit_token(req, tok)
        return (len(req.out) >= req.max_new_tokens
                or tok == self.scfg.eos_id)

    def _take_block(self, req: _Request, ids) -> bool:
        """What a fetched step did with ``req``'s block (generation by
        blocks). If the block the host held was clean, the step committed
        it, and what it denoised is the next block at its step 0: that
        block opens here first. The positions the step unmasked are noted
        with the step of their block, and every token that now follows
        the last one emitted without a masked position between leaves, in
        the order of the positions (so none leaves later than the fetch
        that shows its block clean). True when the request ends: an
        ``eos_id`` left, or the block that holds its last token is clean
        (what it holds beyond ``max_new_tokens`` is dropped and
        counted)."""
        spec = self._block
        n, mask = spec.length, spec.mask_id
        if mask not in req.block:
            req.pos += n
            req.block = [mask] * n
            req.block_steps = [0] * n
            req.block_step = req.block_out = 0
            # The committed block's queries were counted at the dispatch;
            # the opened block's saw that block too.
            keys = self._n_attending * n * (req.pos + n)
            self._count("diffusion_fused_forwards")
            self._count("decode_keys_attended", keys)
        new = ids[req.slot * n:(req.slot + 1) * n]
        for j in range(n):
            if req.block[j] == mask and new[j] != mask:
                req.block[j] = int(new[j])
                req.block_steps[j] = req.block_step
        req.block_step += 1
        plen = int(req.prompt.size)
        end = plen + req.max_new_tokens
        dropped = 0
        while req.block_out < n and req.block[req.block_out] != mask:
            j = req.block_out
            req.block_out += 1
            if req.pos + j < plen:
                continue
            if req.pos + j >= end:
                dropped += 1
                continue
            tok = self._sample(req.block[j], req)
            req.out.append(tok)
            req.steps_out.append(req.block_steps[j])
            if len(req.out) == 1:
                now = time.perf_counter()
                req.timing["first_token"] = now
                tracing.record_request(req.rid, "first_token", t_s=now)
            self._emit_token(req, tok)
            if tok == self.scfg.eos_id:
                return True
        if dropped:
            self._count("diffusion_positions_dropped", dropped)
        return req.block_out == n and req.pos + n >= end

    def _dispatch(self, params, live, inputs, prev) -> _Ahead:
        """Enqueue one decode step over the ``live`` rows' ``(request,
        token, position)`` and count it: the counters say what the device
        was handed, whatever the host later does with the ids."""
        ids = self.pool.decode_step(
            params, *inputs, None if prev is None else prev.ids
        )
        reqs = [req for req, _, _ in live]
        positions = [pos for _, _, pos in live]
        for req in reqs:
            req.ahead += 1
        bs = self.pool.block_size
        width = self._block.length if self._block else 1
        attended = sum((pos + width - 1) // bs + 1 for pos in positions)
        slab = self.pool.max_slots * self.pool.blocks_per_row
        by_layer = self._layer_blocks(positions, attended)
        keys = self._layer_keys(positions)
        walked = self._blocks_walked(positions)
        self._count("kv_blocks_attended", attended)
        self._count("kv_blocks_slab", slab)
        self._count("kv_blocks_walked", walked)
        self._count("kv_layer_blocks_attended", by_layer)
        self._count("decode_keys_attended", keys)
        if self._index_topk:
            self._count_index(
                len(self._index_topk) * sum(pos + 1 for pos in positions),
                sum(min(pos + 1, k)
                    for k in self._index_topk for pos in positions),
                decode_step=True)
        if self._windows:
            dead = sum(max(pos - window + 1, 0) // bs
                       for window in self._windows for pos in positions)
            self._count("kv_dead_blocks", dead)
        if any(req.temperature > 0.0 for req in reqs):
            self._count("draw_steps")
        if self._recurrent:
            # Read and written once each by every live row; held:
            # admitted rows whose state this step kept (stalled, on
            # another version, waiting for their last token, or between
            # two chunks of their prompt).
            moved = 2 * len(reqs) * self.pool.state_row_bytes
            held = len(self._active) - len(reqs) + sum(
                1 for r in self._prefilling if r.chunk_done
            )
            self._count("ssm_state_bytes", moved)
            self._count("state_rows_held", held)
        self._count("steps")
        if prev is not None:
            self._count("steps_ahead")
        return _Ahead(ids, reqs)

    @staticmethod
    def _sample(chosen, req: _Request) -> int:
        """The one place where an id fetched from a program becomes the
        request's token; nothing is sampled here (the choice was made on
        the device, :mod:`rayfed_tpu.serving.sampling`). It stays an
        attribute of this name, called as ``self._sample(chosen, req)``
        once per row by the decode step and by every first-token path,
        because the benchmark's control ``--inject broken-token`` wraps
        exactly this attribute (``chipbench/serving.py:run``) to alter a
        token where it is produced and see ``correct`` come out false; a
        ``benchmark`` issue may move that hook. Static, so that whoever
        keeps the original holds a function and not the engine: the
        control keeps it in a local across the engine's shutdown, and a
        bound method there kept the pool and the weights on the device
        under the reference that runs next."""
        return int(chosen)

    def _finish(self, req: _Request) -> None:
        if req.stream is not None:
            req.stream.push(len(req.out), [], True)
        if req.slot >= 0:
            self.pool.release(req.slot)
            req.slot = -1
        self.bank.release(req.version)
        now = time.perf_counter()
        req.timing["finish"] = now
        latency_ms = (now - req.enqueue_s) * 1e3
        with self._lock:
            self._count("completed")
            self._count("tokens_out", len(req.out))
            self._m_latency.observe(latency_ms)
            self._latencies_ms.append(latency_ms)
        tracing.record_request(req.rid, "finish", t_s=now,
                               n_new=len(req.out), version=req.version)
        resp: Dict[str, Any] = {
            "request_id": req.rid,
            "tokens": [int(t) for t in req.out],
            "prompt_len": int(req.prompt.size),
            "version": int(req.version),
            "mode": req.mode,
            "prefix_reuse": bool(req.prefix_reuse),
            "timing": {k: float(v) for k, v in req.timing.items()},
            "latency_ms": float(latency_ms),
        }
        if self._block is not None:
            # Per token, the denoising step of its block that unmasked it.
            resp["unmask_steps"] = list(req.steps_out)
        resp.update(req.extra_resp)
        req.future.set_result(resp)

    # -- beam / speculative (whole-request paths) ------------------------

    def _run_special(self, req: _Request, params) -> None:
        """Beam/speculative requests run as one whole-generation call on
        the engine thread (they have their own internal batching and do
        not join the iteration-level batch; admission still pins a
        version, so swap semantics are identical)."""
        plen = int(req.prompt.size)
        if req.mode == "beam":
            key = ("beam", req.max_new_tokens, req.n_beams, plen)
            fn = self._special_fns.get(key)
            if fn is None:
                from rayfed_tpu.models import decode

                fn = decode.make_beam_search_fn(
                    self.cfg,
                    max_new_tokens=req.max_new_tokens,
                    n_beams=req.n_beams,
                    eos_id=self.scfg.eos_id,
                )
                self._special_fns[key] = fn
            seqs, scores = fn(params, req.prompt[None])
            seqs = np.asarray(seqs)
            req.out = [int(t) for t in seqs[0, 0, plen:]]
            req.extra_resp["scores"] = [
                float(s) for s in np.asarray(scores)[0]
            ]
        else:
            draft_params = self.bank.get_extra(req.version, "draft_params")
            if draft_params is None:
                raise ValueError(
                    "mode='speculative' needs publish(..., draft_params=...)"
                )
            from rayfed_tpu.models import speculative

            key = ("spec", req.max_new_tokens, plen)
            fn = self._special_fns.get(key)
            if fn is None:
                fn = speculative.make_speculative_generate_fn(
                    self.cfg,
                    self.draft_cfg,
                    max_new_tokens=req.max_new_tokens,
                    eos_id=self.scfg.eos_id,
                )
                self._special_fns[key] = fn
            out = fn(params, draft_params, req.prompt[None])
            req.out = [int(t) for t in np.asarray(out)[0, plen:]]
        now = time.perf_counter()
        req.timing["prefill"] = now
        req.timing["first_token"] = now
        tracing.record_request(req.rid, "first_token", t_s=now)
        if req.stream is not None and req.out:
            # Whole-request paths produce everything at once; one frame.
            req.stream.push(0, list(req.out), False)
            with self._lock:
                self._count("streamed_tokens", len(req.out))
        self._finish(req)


# -- per-job server registry (one per serve() name) --------------------------

from rayfed_tpu.tenancy.context import JobScoped

_registry_lock = threading.Lock()  # fedlint: disable=global-mutable-singleton (guards the per-job server registries)
_servers: JobScoped = JobScoped("serving.servers", default_factory=dict)


def register_server(server: InferenceServer) -> None:
    with _registry_lock:
        registry = _servers.get()
        old = registry.get(server.name)
        if old is not None and old is not server:
            raise ValueError(
                f"a server named {server.name!r} is already registered; "
                "stop it first or pick another name"
            )
        registry[server.name] = server


def get_server(name: str = "default") -> InferenceServer:
    with _registry_lock:
        server = _servers.get().get(name)
    if server is None:
        raise RuntimeError(
            f"no serving engine named {name!r} on this party — "
            "fed.serve() must run (with this party as the host) first"
        )
    return server


def unregister_server(name: str) -> None:
    with _registry_lock:
        _servers.get().pop(name, None)


# -- standby replicas (ModelBank replication / promotion) --------------------
#
# A standby holds everything needed to become the serving engine for a
# name — the model/serving configs plus a ModelBank replica that tracks
# the primary's publishes — WITHOUT pinning slots or compiling anything.
# Promotion builds a real InferenceServer around the replica bank.

_standbys: JobScoped = JobScoped("serving.standbys", default_factory=dict)


def register_standby(name: str, spec: Dict[str, Any]) -> None:
    with _registry_lock:
        _standbys.get()[name] = spec


def get_standby(name: str) -> Optional[Dict[str, Any]]:
    with _registry_lock:
        return _standbys.get().get(name)


def pop_standby(name: str) -> Optional[Dict[str, Any]]:
    with _registry_lock:
        return _standbys.get().pop(name, None)


def stop_all_servers(timeout: float = 10.0) -> None:
    """Teardown hook for fed.shutdown(): stop the current job's engines."""
    with _registry_lock:
        registry = _servers.pop() or {}
        servers = list(registry.values())
    for server in servers:
        try:
            server.stop(timeout)
        except Exception:  # noqa: BLE001 - teardown best-effort
            logger.exception("serving[%s]: stop failed", server.name)
