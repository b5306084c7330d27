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

"""Lightweight tracing/profiling for cross-party transfers and tasks.

The reference has NO tracing (SURVEY.md §5.1 — only per-proxy op counters).
This module adds per-transfer spans: every send and receive records
(kind, peer, seq ids, bytes, duration) into a bounded in-process ring,
queryable via :func:`get_spans` / :func:`summary`.

Intervals that do not live on one thread (a task's time in the queue, a
value's wait for its reader) go through :func:`observe` into the same
per-name accumulators that :class:`phase` feeds; the futures that carry
their stamps are marked by :func:`stamp_done`.

Two context managers also put the program's host work on the profiler's
clock, so a device trace says what the host was doing in an idle gap:
:class:`span` (one transfer, ``fed:wire:<kind>``) and :class:`phase` (a
recurring piece of a loop, ``fed:<layer>:<phase>``). Each opens a
``jax.profiler.TraceAnnotation`` whenever a profiler session is running —
the session is the switch, ``enable()`` is not needed for it — under a
FIXED name: ids, peers and byte counts travel as the annotation's
metadata, never in its name, so a reduction by name sums a cause instead
of shattering it into one-off events (docs/observability.md).

Off (no ``enable()``, no profiler session) a span or phase costs two flag
checks and no allocation beyond the context manager itself.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _TraceAnnotation

# TraceMe's static "is a profiler session recording" check (~20 ns): the
# annotation object is only built while one is.
_profiling = _TraceAnnotation.is_enabled

_enabled = False  # fedlint: disable=global-mutable-singleton (trace buffer is process-global by contract; drained via snapshot())
_lock = threading.Lock()  # fedlint: disable=global-mutable-singleton (trace buffer is process-global by contract; drained via snapshot())
_MAX_SPANS = 10000
_spans: Deque["Span"] = deque(maxlen=_MAX_SPANS)  # fedlint: disable=global-mutable-singleton (trace buffer is process-global by contract; drained via snapshot())
# Monotonic append counter: every span gets the next index so the
# telemetry agent can harvest "spans since my last push" even though
# the ring drops old entries (rayfed_tpu/telemetry/agent.py).
_span_seq = 0  # fedlint: disable=global-mutable-singleton (trace buffer is process-global by contract; drained via snapshot())
_MAX_REQUEST_EVENTS = 20000
_request_events: Deque["RequestEvent"] = deque(maxlen=_MAX_REQUEST_EVENTS)  # fedlint: disable=global-mutable-singleton (trace buffer is process-global by contract; drained via snapshot())
# Recurring phases (``phase``) accumulate per name instead of appending to
# the span ring: {name: [count, seconds, max_s]}.
_phases: Dict[str, List[float]] = {}  # fedlint: disable=global-mutable-singleton (trace buffer is process-global by contract; drained via snapshot())


@dataclass
class Span:
    kind: str                 # "send" | "write" | "recv" | "decode"
    peer: str                 # destination or source party ("" if n/a)
    upstream_seq_id: str
    downstream_seq_id: str
    nbytes: int
    start_s: float
    duration_s: float
    ok: bool = True
    extra: Dict = field(default_factory=dict)
    idx: int = -1             # ring-append index (monotonic per process)


def enable() -> None:
    """Turn recording on: spans into the ring, request events, and the
    per-name phase accumulators. (Profiler annotations need no switch
    here: they follow the profiler session.)"""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


def clear() -> None:
    with _lock:
        _spans.clear()
        _request_events.clear()
        _phases.clear()


def get_spans(kind: Optional[str] = None) -> List[Span]:
    with _lock:
        spans = list(_spans)
    if kind is not None:
        spans = [s for s in spans if s.kind == kind]
    return spans


def spans_since(idx: int, limit: Optional[int] = None) -> List[Span]:
    """Spans with ring index > ``idx``, oldest first (optionally the
    newest ``limit`` of them). The ring is append-ordered, so walk it
    from the right and stop at the watermark instead of scanning all
    10k entries on every telemetry push."""
    out: List[Span] = []
    with _lock:
        for s in reversed(_spans):
            if s.idx <= idx:
                break
            out.append(s)
            if limit is not None and len(out) >= limit:
                break
    out.reverse()
    return out


def last_span_index() -> int:
    return _span_seq - 1


# Kinds whose spans bracket the full operation (duration is meaningful);
# "recv" spans are arrival events with no duration, except for frames of
# TIMED_RECV_MIN_BYTES and more (below) — no throughput for the kind.
# "send" runs from the staged frame's hand-over to the sender's lane to
# its ack (the wait for a slot of the lane's window, the write, the
# peer's read and its ack together); "write" is the writer's own part of
# a frame of TIMED_RECV_MIN_BYTES and more (:func:`write_t0`): its
# first byte handed to the socket -> its last. "fold"/"publish" are
# the async aggregation buffer's K-publish spans
# (rayfed_tpu/async_rounds.py; docs/async_rounds.md).
_TIMED_KINDS = {"send", "write", "decode", "fold", "publish"}

# A frame whose payload is at least this long gets a "recv" span with a
# duration: the reactor stamps the moment its payload starts to arrive
# into the frame's header under RECV_T0_KEY (never sent on the wire), and
# the rendezvous store passes the stamp to ``record`` as ``start_s``.
TIMED_RECV_MIN_BYTES = 1 << 20
RECV_T0_KEY = "_recv_t0"


def _is_timed(s: "Span") -> bool:
    return s.kind in _TIMED_KINDS or bool(s.extra.get("timed"))


def summary() -> Dict[str, Dict]:
    """Aggregate per kind: count, bytes, total duration, GB/s (timed kinds
    only — event kinds like 'recv' have no meaningful duration)."""
    out: Dict[str, Dict] = {}
    for s in get_spans():
        agg = out.setdefault(
            s.kind,
            {"count": 0, "bytes": 0, "seconds": 0.0, "errors": 0},
        )
        agg["count"] += 1
        agg["bytes"] += s.nbytes
        agg["seconds"] += s.duration_s
        if not s.ok:
            agg["errors"] += 1
    for kind, agg in out.items():
        if kind in _TIMED_KINDS and agg["seconds"] > 1e-9:
            agg["gbps"] = agg["bytes"] / (1 << 30) / agg["seconds"]
    return out


def export_chrome_trace(path: str, party: str = "") -> int:
    """Write recorded spans as a Chrome/Perfetto trace-event JSON file
    (open in ``chrome://tracing`` or ``ui.perfetto.dev``). Timed kinds
    become complete ("X") events on a per-kind track; event kinds (e.g.
    "recv" arrivals) become instant ("i") events. Returns the number of
    events written. Complements ``jax.profiler`` captures: this is the
    engine-side wire timeline, device timelines come from the profiler.
    """
    import json

    events = []
    pid = party or "rayfed_tpu"
    for s in get_spans():
        base = {
            "name": f"{s.kind} {s.peer}".strip(),
            "cat": s.kind,
            "pid": pid,
            "tid": s.kind,
            "ts": s.start_s * 1e6,  # microseconds
            "args": {
                "up": s.upstream_seq_id,
                "down": s.downstream_seq_id,
                "nbytes": s.nbytes,
                "ok": s.ok,
                **s.extra,
            },
        }
        if _is_timed(s):
            base["ph"] = "X"
            base["dur"] = max(s.duration_s, 1e-7) * 1e6
        else:
            base["ph"] = "i"
            base["s"] = "t"
        events.append(base)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(events)


def export_seq_timeline(path: str, party: str = "") -> int:
    """Write the per-seq-id timeline as machine-readable JSON — the hang
    forensics artifact and the input format of ``tools/trace_view.py``'s
    text flamegraph: when a party wedges, the last wire event per
    rendezvous edge is visible without a debugger.

    Shape::

        {"party": ..., "t0_s": <earliest span start>,
         "edges": [{"up": ..., "down": ..., "events": [
             {"kind", "peer", "t_s", "dur_s", "nbytes", "ok", ...extra},
             ...]},   # time-ordered within each edge
          ...]}       # edges ordered by first event

    Every send/write/recv/decode span plus the async aggregator's
    fold/publish spans lands here keyed by its (upstream, downstream)
    seq-id edge, so a straggling round is traceable from the driver's
    offer through the wire to the fold that consumed it. Returns the
    number of events written.

    Signal-handler safe: the span ring is snapshotted with a
    non-blocking lock attempt (a handler interrupting the recording
    thread mid-append must not deadlock on the tracing lock; deques are
    safe to iterate without it at worst losing the in-flight span)."""
    import json

    acquired = _lock.acquire(blocking=False)
    try:
        spans = list(_spans)
    finally:
        if acquired:
            _lock.release()
    edges: Dict[tuple, List[Span]] = {}
    for s in spans:
        edges.setdefault((s.upstream_seq_id, s.downstream_seq_id), []).append(s)
    edge_list = []
    n = 0
    for (up, down), group in sorted(
        edges.items(), key=lambda kv: min(s.start_s for s in kv[1])
    ):
        events = []
        for s in sorted(group, key=lambda s: s.start_s):
            events.append({
                "kind": s.kind,
                "peer": s.peer,
                "t_s": s.start_s,
                "dur_s": s.duration_s if _is_timed(s) else 0.0,
                "nbytes": s.nbytes,
                "ok": s.ok,
                **s.extra,
            })
            n += 1
        edge_list.append({"up": up, "down": down, "events": events})
    doc = {
        "party": party or "?",
        "t0_s": min((s.start_s for s in spans), default=0.0),
        "edges": edge_list,
    }
    with open(path, "w", encoding="utf-8") as f:
        # default=str: extras are caller-provided and must never be able
        # to fail the artifact (it is written from watchdog handlers).
        json.dump(doc, f, default=str)
    return n


def record(kind: str, peer: str, upstream_seq_id: str, downstream_seq_id: str,
           nbytes: int, start_s: float, ok: bool = True, **extra) -> None:
    """Directly append a span (for async paths where a context manager
    cannot bracket the operation — e.g. pipelined sends resolved by ack).
    Extra keywords land in the span's ``extra`` dict (and therefore in
    every exporter's per-event args) — the async aggregator stamps fold
    spans with the buffered round tags this way."""
    if not _enabled:
        return
    global _span_seq
    with _lock:
        _spans.append(
            Span(
                kind=kind,
                peer=peer,
                upstream_seq_id=str(upstream_seq_id),
                downstream_seq_id=str(downstream_seq_id),
                nbytes=nbytes,
                start_s=start_s,
                duration_s=time.perf_counter() - start_s,
                ok=ok,
                extra=extra,
                idx=_span_seq,
            )
        )
        _span_seq += 1


# -- per-request serving timeline (docs/serving.md) -------------------------
#
# The serving plane's observability slice (ROADMAP "production
# observability"): each request leaves a breadcrumb trail of lifecycle
# events — enqueue / admit / prefill / first_token / step / finish — in a
# second bounded ring, exportable as JSON next to the per-seq-id wire
# timeline so a slow request is diagnosable from artifacts alone (which
# phase ate the time, which model version served it, whether it waited in
# admission or in the decode batch).


@dataclass
class RequestEvent:
    request_id: str
    event: str                # "enqueue" | "prefill" | "first_token" | ...
    t_s: float                # perf_counter timestamp
    extra: Dict = field(default_factory=dict)


def record_request(request_id: str, event: str,
                   t_s: Optional[float] = None, **extra) -> None:
    """Append one lifecycle event for ``request_id`` (no-op when tracing
    is off, like every recorder in this module)."""
    if not _enabled:
        return
    if t_s is None:
        t_s = time.perf_counter()
    with _lock:
        _request_events.append(
            RequestEvent(str(request_id), event, t_s, dict(extra))
        )


def get_request_events(
    request_id: Optional[str] = None,
) -> List[RequestEvent]:
    with _lock:
        events = list(_request_events)
    if request_id is not None:
        events = [e for e in events if e.request_id == str(request_id)]
    return events


def request_timelines() -> Dict[str, List[RequestEvent]]:
    """Events grouped per request id, time-ordered within each."""
    out: Dict[str, List[RequestEvent]] = {}
    for e in get_request_events():
        out.setdefault(e.request_id, []).append(e)
    for events in out.values():
        events.sort(key=lambda e: e.t_s)
    return out


def export_request_timeline(path: str, party: str = "") -> int:
    """Write the per-request serving timeline as JSON:
    ``{"party", "requests": {id: [{"event", "t_s", ...extra}]}}`` with
    per-request events time-ordered. Returns the number of events
    written. Lives alongside :func:`export_seq_timeline` (the per-seq-id
    wire artifact); same snapshot discipline — safe to call from a watchdog
    signal handler (non-blocking lock attempt, ring iterated without it
    at worst losing the in-flight event)."""
    import json

    acquired = _lock.acquire(blocking=False)
    try:
        events = list(_request_events)
    finally:
        if acquired:
            _lock.release()
    requests: Dict[str, List[Dict]] = {}
    n = 0
    for e in sorted(events, key=lambda e: (e.request_id, e.t_s)):
        requests.setdefault(e.request_id, []).append(
            {"event": e.event, "t_s": e.t_s, **e.extra}
        )
        n += 1
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"party": party or "?", "requests": requests}, f)
    return n


class span:
    """Context manager recording one transfer-shaped span: into the ring
    when tracing is on, and as the profiler annotation ``fed:wire:<kind>``
    (peer, seq ids and bytes as metadata) when a profiler session runs."""

    __slots__ = ("_kind", "_peer", "_up", "_down", "_nbytes", "_t0",
                 "_ann", "_active")

    def __init__(self, kind: str, peer: str = "", upstream_seq_id: str = "",
                 downstream_seq_id: str = "", nbytes: int = 0):
        self._kind = kind
        self._peer = peer
        self._up = upstream_seq_id
        self._down = downstream_seq_id
        self._nbytes = nbytes
        self._ann = None
        # Latched at __enter__: a toggle of the global flag mid-span must
        # not make __exit__ disagree with __enter__.
        self._active = False

    def __enter__(self):
        if _profiling():
            self._ann = _TraceAnnotation(
                "fed:wire:" + self._kind, peer=self._peer,
                up=str(self._up), down=str(self._down), nbytes=self._nbytes,
            )
            self._ann.__enter__()
        if _enabled:
            self._active = True
            self._t0 = time.perf_counter()
        return self

    def set_nbytes(self, n: int) -> None:
        self._nbytes = n

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if not self._active:
            return False
        global _span_seq
        record = Span(
            kind=self._kind,
            peer=self._peer,
            upstream_seq_id=self._up,
            downstream_seq_id=self._down,
            nbytes=self._nbytes,
            start_s=self._t0,
            duration_s=time.perf_counter() - self._t0,
            ok=exc_type is None,
        )
        with _lock:
            record.idx = _span_seq
            _span_seq += 1
            _spans.append(record)
        return False


def write_t0(nbytes: int) -> Optional[float]:
    """For the lane about to hand a frame's first byte to the socket: the
    start of the frame's ``write`` span, which the lane closes with
    ``record("write", peer, up, down, nbytes, t0)`` under the frame's seq
    ids once its last byte is handed over: the mirror of the receiver's
    timed ``recv``, in the ring only (an annotation on the profiler's
    clock would lie over a round's first seconds on the reactor's thread
    and take the idle pieces of threads the device did wait for). None
    while tracing is off and for a frame under
    :data:`TIMED_RECV_MIN_BYTES` (the coalesced small-message lane is the
    latency path, and the ring holds 10,000 entries)."""
    if _enabled and nbytes >= TIMED_RECV_MIN_BYTES:
        return time.perf_counter()
    return None


class phase:
    """Context manager for a RECURRING piece of host work (one part of
    the serving loop, the placement of an arrival, the mean's dispatch).

    ``name`` is the fixed string ``fed:<layer>:<phase>``; keyword
    arguments become the annotation's metadata. While a profiler session
    runs the phase is a host event on the device trace's clock. While
    tracing is on its duration is added to a per-name accumulator
    (:func:`phase_summary`) — NOT to the span ring, which a loop running
    tens of phases a second would turn over in under a minute.

    Phases of one loop tile it and none encloses the others: a reduction
    cuts an idle gap at the host events over it and books each piece to
    the innermost ``fed:*`` event that covers it, whatever its thread."""

    __slots__ = ("_name", "_meta", "_ann", "_t0")

    def __init__(self, name: str, **meta):
        self._name = name
        self._meta = meta
        self._ann = None
        self._t0 = None

    def __enter__(self):
        if _profiling():
            self._ann = _TraceAnnotation(self._name, **self._meta)
            self._ann.__enter__()
        if _enabled:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._t0 is not None:
            _accumulate(self._name, time.perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def _accumulate(name: str, seconds: float) -> None:
    with _lock:
        acc = _phases.get(name)
        if acc is None:
            acc = _phases[name] = [0, 0.0, 0.0]
        acc[0] += 1
        acc[1] += seconds
        if seconds > acc[2]:
            acc[2] = seconds


def observe(name: str, seconds: float) -> None:
    """Add one measured interval to ``name``'s accumulator, the one a
    :class:`phase` of that name feeds (:func:`phase_summary`), for an
    interval that does not live on one thread and so cannot be a context
    manager: a task's time in the queue, a value's wait for its reader.
    No profiler annotation; nothing while tracing is off."""
    if _enabled:
        _accumulate(name, seconds)


# -- who resolved a future, and when -----------------------------------------
#
# While tracing is on, whoever resolves a future that a task or ``fed.get``
# may wait for stamps it just before it sets the result, in an attribute,
# the way the engine carries ``_fedtpu_steal``: ``(perf_counter(), arrived)``,
# ``arrived`` true where the value came off the wire (the rendezvous store)
# and false where a task of this party made it (the task engine). Off, no
# future is touched.

_DONE_T = "_fedtpu_done_t"
_task = threading.local()


def stamp_done(fut, arrived: bool = False) -> None:
    """Mark ``fut`` as resolved now (call before ``set_result``)."""
    if _enabled:
        setattr(fut, _DONE_T, (time.perf_counter(), arrived))


def carry_done_stamp(src, dst) -> None:
    """``dst`` resolves with ``src``'s value: it takes ``src``'s stamp."""
    stamp = getattr(src, _DONE_T, None)
    if stamp is not None:
        setattr(dst, _DONE_T, stamp)


def done_stamp(fut) -> Optional[Tuple[float, bool]]:
    """``(when, arrived)`` of ``fut``'s resolution, or None where nobody
    stamped it."""
    return getattr(fut, _DONE_T, None)


def swap_task_arg_stamps(
    stamps: Optional[List[Tuple[float, bool]]],
) -> Optional[List[Tuple[float, bool]]]:
    """The task engine's side of :func:`task_arg_stamps`: set the running
    task's stamps on this thread and return what stood there (a task run
    inline inside another's body or wait puts it back when it ends)."""
    prev = getattr(_task, "arg_stamps", None)
    _task.arg_stamps = stamps
    return prev


def task_arg_stamps() -> List[Tuple[float, bool]]:
    """From inside a task's body: the done-stamps of its future arguments
    that carry one, in argument order. Empty outside a task, while tracing
    is off, and for a task whose arguments were plain values."""
    return getattr(_task, "arg_stamps", None) or []


def phase_summary() -> Dict[str, Dict]:
    """Per phase name: ``{"count", "seconds", "max_s"}`` since the last
    :func:`clear` (recorded only while tracing is on)."""
    with _lock:
        return {
            name: {"count": int(c), "seconds": s, "max_s": m}
            for name, (c, s, m) in _phases.items()
        }
