"""From a profiler trace to numbers: the window and the device's busy time
in it, the device operations that took most of it and the named scopes
they belong to, every idle gap and what the host was doing in it, the
program's and the benchmark's own host spans, and the summed durations of
named kernels.

Two steps, so that the arithmetic can be checked on made lines and on a
small recorded trace (``chipbench/tests/data/``) without a chip:

``events_of(path)``   .xplane.pb -> ``[{"plane", "line", "events": [[name,
                      start_ns, dur_ns], ...]}, ...]`` (needs only jax); a
                      device event carries a fourth item, its scope;
``reduce(lines, ...)`` the arithmetic, on that plain form.

Device lines are the "XLA Ops" lines of planes named ``/device:TPU:<n>``.
Host lines are every line of the ``/host:CPU`` plane, one a thread: the
benchmark's own ``chipbench:*`` annotations, the program's spans
(``fed:*``), and the runtime's own TraceMes. All in the profiler's one
timeline, which is not quite one clock: in the three serving profiles kept
in PR 35 the device's lines stand 2.0-2.3, 1.9-2.2 and 0.45-0.75 ms
EARLIER than the host's (a program's first operation stands that far
before the host event that enqueues it; the offset holds through a profile
and differs from one process to the next). Over a 4 s window that is
0.06 % of ``busy_s``; a gap of that length is named by what the host did
that long before it (PERF.md section 7, B11). Nothing here corrects it.

How the numbers come about (one window since PR 35; gaps split since
PR 38):

* THE window is the host span ``chipbench:traced``, which
  ``common.DeviceTrace`` opens once the profiler runs and closes before it
  stops: ``window_s`` is its length. The profile itself runs a few ms past
  it at both ends, and a device that never idles is busy there too, so
  nothing outside the window is counted. Where the span is absent (a
  recorded trace, plain made lines) the window is the device's own span,
  first start to last end.
* ``busy_s``: every device interval is clipped to the window, then the
  union is taken (a ``while`` holds its children on the same line), per
  device, averaged over the devices. So 0 <= busy_s <= window_s whatever
  the program does.
* The idle gaps are the complement of the FIRST device's union inside the
  window, the gap before the first operation and after the last included:
  on one device busy_s + all gaps == window_s to the nanosecond. A gap
  under ``MIN_GAP_NS`` (0.1 ms: between back-to-back operations, no host
  event explains it) goes into one number, ``idle_small_s``. Every other
  gap is CUT at the starts and ends of the host events that overlap it,
  and each piece is booked to ONE name: the innermost (shortest)
  ``fed:*`` event that covers the piece; where there is none, the
  innermost ``chipbench:*`` event (the window's own span apart); where
  none, the innermost other host event of at least 10 us; else
  ``no host span``, or ``window edge`` where the uncovered piece touches
  an edge of the window (the profile holds no host event that was open
  when the profiler started or stopped: a wait for a request that began
  before the window is not in it). So a span the benchmark holds open
  around a whole round (``chipbench:wait_aggregate``) keeps only what no
  span of the program covers, and a gap inside ``fed:serve:fetch`` and
  the runtime's ``np.asarray`` is the fetch's. The names are summed:
  ``idle_by_cause`` holds all of them, ``idle_gaps`` the ten largest for
  the result's ``breakdown``. A name's sum is the time the device sat
  idle in pieces booked to it, not a duration of that host event.
  WHAT THE RULE CANNOT TELL: spans of different threads are open at once
  (a reactor's ``fed:wire:recv`` while another thread dispatches train
  steps), and a piece goes to the innermost whichever thread the device
  waited for. ``idle_under`` is there to measure that: for every
  ``chipbench:*`` span, the pieces that lie inside it by the name they
  were booked to (``{span: {name: [runs, seconds]}}``, a run being
  adjacent pieces of one gap under one name).
* ``host_spans``: for every ``fed:*`` and ``chipbench:*`` name, of its
  events clipped to the window: ``count``, ``seconds``, ``self_s`` (the
  duration less what its child events on the same line, of any name,
  cover), ``p50_ms``, ``max_ms``. ``program_spans`` says whether any is
  named ``fed:*``, i.e. whether the program opens spans at all (no commit
  before PR 24 does). ``idle_share(trace, counted)`` below, which the
  ``idle_share.*`` readers share, returns None only there and without a
  trace; with spans and no piece of its names it returns 0.0. The serving
  readers' names partition every cause the engine has, so
  idle_share.schedule + idle_share.unnamed + 100 * idle_small_s / window_s
  == 100 * (1 - busy_s / window_s) on one device, and so do
  idle_share.wire + .agg + .wait in a federated round.
* ``device_ops`` sums the clipped durations by operation (containers left
  out), each named ``<scope>:<op>``; ``device_by_scope`` sums the same
  time by scope alone (all of them; ``device_scopes`` the ten largest).
  The scope is the ``jax.named_scope`` the program put around the code an
  operation came from, read by ``event_scopes`` from the event's metadata
  (the HLO ``op_name``, stat ``tf_op``; a fusion has one, its root's) and
  cut to its innermost two-part name by ``scope_of``: ``serve/attn_latent``, ``train/forward``; the
  backward of a scope (``transpose(jvp(train/forward))``) is
  ``bwd(train/forward)``; an operation under no scope is ``(no scope)``.
  ``kernels`` are NOT clipped: seconds and calls of whole events wherever
  the profile holds them, because their reader (``flash_roofline``)
  divides one by the other. ``device_span_s`` is the device's own span,
  unclipped.
"""

from __future__ import annotations

import glob
import heapq
import os
import re

from chipbench.common import percentile

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 100_000          # gaps under 0.1 ms are between back-to-back ops
WINDOW_SPAN = "chipbench:traced"
EDGE = "window edge"
NO_SPAN = "no host span"
NO_SCOPE = "(no scope)"
PROGRAM, BENCH = "fed:", "chipbench:"
TOP = 10
# Ops that only contain others (their children are on the same line).
CONTAINERS = ("while", "conditional", "call")
# The stat of a device event's metadata that holds its HLO op_name, the
# path ``jit(step)/transpose(jvp(train/forward))/while/body/dot_general:``.
SCOPE_STAT = "tf_op"
# Parts of such a path that are jax's own and no part of a named scope.
JAX_PARTS = frozenset((
    "while", "body", "cond", "checkpoint", "rematted_computation", "remat",
    "closed_call", "core_call", "pjit", "scan", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "shard_map"))
_WORD = re.compile(r"^[A-Za-z_][A-Za-z0-9_.\-]*$")


def op_name(name):
    """A device event is named by its whole HLO line, ``%fusion.436 = bf16[..]
    fusion(...)``: keep what stands before the ``=``, without the ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _parts(path):
    """``path`` cut at the slashes that stand outside every bracket."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def _is_scope_part(part):
    return bool(_WORD.match(part)) and part not in JAX_PARTS \
        and not part.startswith("branch_")


def scope_of(path):
    """The innermost two-part named scope of an HLO ``op_name`` path, or
    ``NO_SCOPE``. A scope's name holds one slash (``serve/attn_latent``:
    docs/observability.md), so it is two adjacent plain parts of the path
    that are not jax's own (``while/body``) and not its last part, the
    primitive's. A transformation wraps the scope it was taken under
    (``transpose(jvp(train/forward))``): the backward pass reads
    ``bwd(train/forward)``, any other wrapping the scope itself."""
    parts = _parts(path or "")
    found = NO_SCOPE
    for i, part in enumerate(parts[:-1]):
        if "(" in part:
            inner = part[part.rfind("(") + 1:part.find(")")]
            pair = inner.split("/")
            if len(pair) == 2 and all(map(_is_scope_part, pair)):
                found = f"bwd({inner})" if part.startswith(
                    "transpose(") else inner
        elif i + 2 < len(parts) and _is_scope_part(part) \
                and _is_scope_part(parts[i + 1]):
            found = part + "/" + parts[i + 1]
    return found


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """The fields of one protobuf message in ``buf[start:end]``: (number,
    value), a length-delimited value as its (start, end) in ``buf``, which
    is what lets a million events be stepped over in one jump a line."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i} of an xplane")
        yield key >> 3, value


def event_scopes(path):
    """``{plane: {event name: op_name path}}`` for the device planes of an
    .xplane.pb. The path stands in the event's METADATA (``XEventMetadata``,
    one a distinct operation: its stat ``SCOPE_STAT``, a string or a
    reference to a ``XStatMetadata`` whose name is the string), which
    ``jax.profiler.ProfileData`` does not hand out: it gives an event's own
    stats only (found on the chip in PR 38). So the file's wire format is
    walked here, by the field numbers of tsl's xplane.proto (XSpace.planes
    1; XPlane.name 2, .event_metadata 4, .stat_metadata 5; the maps' key 1
    and value 2; XEventMetadata.name 2, .display_name 4, .stats 5;
    XStat.metadata_id 1, .str_value 5, .ref_value 7; XStatMetadata.name
    2), stepping over the lines."""
    with open(path, "rb") as f:
        buf = f.read()
    text = lambda span: buf[span[0]:span[1]].decode("utf-8", "replace")  # noqa: E731
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for num, value in _fields(buf, *plane):
            if num == 2:
                name = text(value)
            elif num in (4, 5):
                entry = dict(_fields(buf, *value))
                if 2 not in entry:
                    continue
                if num == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry.get(1, 0)] = next(
                        (text(v) for n, v in _fields(buf, *entry[2])
                         if n == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        wanted = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        scopes = out[name] = {}
        for span in events:
            names, found = [], ""
            for num, value in _fields(buf, *span):
                if num in (2, 4):
                    names.append(text(value))
                elif num == 5:
                    stat = dict(_fields(buf, *value))
                    if stat.get(1) in wanted:
                        found = text(stat[5]) if 5 in stat else \
                            stat_names.get(stat.get(7), "")
            for n in names:
                scopes[n] = found
    return out


def events_of(path):
    from jax.profiler import ProfileData

    out = []
    paths = event_scopes(path)
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not (is_dev or plane.name.startswith("/host:")):
            continue
        known = {}      # a device event's whole name -> (operation, scope)
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            evs = []
            for e in line.events:
                name = e.name
                if not is_dev:
                    evs.append([name[:80], int(e.start_ns),
                                int(e.duration_ns)])
                    continue
                if name not in known:
                    known[name] = (op_name(name), scope_of(
                        paths.get(plane.name, {}).get(name, "")))
                op, scope = known[name]
                evs.append([op, int(e.start_ns), int(e.duration_ns), scope])
            if evs:
                out.append({"plane": plane.name, "line": line.name,
                            "events": evs})
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clipped(events, w0, w1):
    for ev in events:
        s, e = max(ev[1], w0), min(ev[1] + ev[2], w1)
        if e > s:
            yield ev[0], s, e


def _rank(name):
    return 0 if name.startswith(PROGRAM) else \
        1 if name.startswith(BENCH) else 2


def _book(gaps, host_events, window=None):
    """``({name: ns}, {bench span: {name: [runs, ns]}})``: every gap cut at
    the starts and ends of the host events that overlap it, each piece to
    the innermost ``fed:`` event that covers it, else the innermost
    ``chipbench:`` one, else the innermost other, else ``NO_SPAN`` (``EDGE``
    where the piece touches an edge of ``window``). Both lists are sorted
    by start; one sweep, keeping the events that still reach the gap at
    hand, and inside a gap a heap whose top is the piece's name."""
    by_cause, under, live, nxt = {}, {}, [], 0
    for g0, g1 in gaps:
        live = [ev for ev in live if ev[1] > g0]
        while nxt < len(host_events) and host_events[nxt][0] < g1:
            if host_events[nxt][1] > g0:
                live.append(host_events[nxt])
            nxt += 1
        cuts = sorted({g0, g1, *(t for s, e, _ in live for t in (s, e)
                                 if g0 < t < g1)})
        best, bench, i, run = [], [], 0, None
        for a, b in zip(cuts, cuts[1:]):
            while i < len(live) and live[i][0] <= a:
                s, e, name = live[i]
                rank = _rank(name)
                heapq.heappush(best, (rank, e - s, e, name))
                if rank == 1:
                    heapq.heappush(bench, (e - s, e, name))
                i += 1
            # Every end is a cut, so an event that reaches past ``a``
            # covers the whole piece.
            while best and best[0][2] <= a:
                heapq.heappop(best)
            while bench and bench[0][1] <= a:
                heapq.heappop(bench)
            if best:
                name = best[0][3]
            elif window and (a == window[0] or b == window[1]):
                name = EDGE
            else:
                name = NO_SPAN
            by_cause[name] = by_cause.get(name, 0) + b - a
            if bench:
                slot = under.setdefault(bench[0][2], {}).setdefault(
                    name, [0, 0])
                slot[0] += run != (bench[0][2], name)
                slot[1] += b - a
            run = (bench[0][2], name) if bench else None
    return by_cause, under


def _book_whole(gaps, host_events, window=None):
    """The rule of before PR 38, for ``reduce(window_s=)`` alone: each gap
    WHOLE to the host event that overlaps it most, at equal overlap the
    shortest; a gap at an edge of ``window`` that no event covers by half
    goes to ``EDGE``."""
    by_cause, active, nxt = {}, [], 0
    for g0, g1 in gaps:
        active = [ev for ev in active if ev[1] > g0]
        while nxt < len(host_events) and host_events[nxt][0] < g1:
            if host_events[nxt][1] > g0:
                active.append(host_events[nxt])
            nxt += 1
        best, best_key = NO_SPAN, (0, 0)
        for s, e, name in active:
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                key = (overlap, -(e - s))
                if key > best_key:
                    best, best_key = name, key
        if window and (g0 == window[0] or g1 == window[1]) \
                and 2 * best_key[0] < g1 - g0:
            best = EDGE
        by_cause[best] = by_cause.get(best, 0) + g1 - g0
    return by_cause, {}


def _host_spans(host, w0, w1):
    """``{name: {count, seconds, self_s, p50_ms, max_ms}}`` for the
    ``fed:`` and ``chipbench:`` events, clipped to the window. A line is a
    thread, so its events nest: an event's children are those that begin
    inside it, and its self time is its length less its direct children's
    (theirs hold their own children's)."""
    acc = {}

    def close(item):
        s, e, name, covered = item
        if name.startswith((PROGRAM, BENCH)):
            slot = acc.setdefault(name, [[], 0])
            slot[0].append(e - s)
            slot[1] += e - s - covered

    for ln in host:
        if not any(ev[0].startswith((PROGRAM, BENCH)) for ev in ln["events"]):
            continue
        stack = []
        for name, s, e in sorted(_clipped(ln["events"], w0, w1),
                                 key=lambda t: (t[1], -t[2])):
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                e = min(e, stack[-1][1])
                stack[-1][3] += e - s
            stack.append([s, e, name, 0])
        while stack:
            close(stack.pop())
    return {name: {"count": len(durs), "seconds": sum(durs) / 1e9,
                   "self_s": self_ns / 1e9,
                   "p50_ms": percentile(durs, 50) / 1e6,
                   "max_ms": max(durs) / 1e6}
            for name, (durs, self_ns) in acc.items()}


def reduce(lines, window_s=None, kernels=()):
    """See the module docstring. ``kernels``: name prefixes whose events'
    durations and counts are summed (per device, then averaged).
    ``window_s`` is what callers of before PR 35 passed and is not read as
    a window: the window comes from the trace. The one caller that still
    passes it (``tests/test_tracing_phases.py``, which no benchmark PR may
    edit) pins the booking of before PR 38 and gets it: each gap whole to
    one name (``_book_whole``). Both go with the PR that may edit it."""
    book = _book if window_s is None else _book_whole
    dev = [ln for ln in lines if DEVICE_PLANE.match(ln["plane"])]
    host = [ln for ln in lines if not DEVICE_PLANE.match(ln["plane"])]
    n_dev = len({ln["plane"] for ln in dev})
    if not n_dev:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0,
                "device_ops": [], "device_scopes": [], "device_by_scope": {},
                "idle_gaps": [], "idle_by_cause": {}, "idle_under": {},
                "idle_small_s": 0.0, "host_spans": {},
                "program_spans": False, "kernels": {}}
    lo = min(e[1] for ln in dev for e in ln["events"])
    hi = max(e[1] + e[2] for ln in dev for e in ln["events"])
    span = next(((e[1], e[1] + e[2]) for ln in host for e in ln["events"]
                 if e[0] == WINDOW_SPAN), None)
    w0, w1 = span or (lo, hi)
    busy_ns, by_op, kern = 0, {}, {}
    first_plane = sorted({ln["plane"] for ln in dev})[0]
    first_busy = []
    for ln in dev:
        intervals = []
        for ev in ln["events"]:
            s, e = max(ev[1], w0), min(ev[1] + ev[2], w1)
            if e <= s:
                continue
            intervals.append((s, e))
            if not ev[0].startswith(CONTAINERS):
                key = (ev[3] if len(ev) > 3 else NO_SCOPE, ev[0])
                by_op[key] = by_op.get(key, 0) + e - s
        merged = _union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        if ln["plane"] == first_plane:
            first_busy = merged
        for ev in ln["events"] if kernels else ():
            for k in kernels:
                if ev[0].startswith(k):
                    slot = kern.setdefault(k, {"seconds": 0.0, "calls": 0})
                    slot["seconds"] += ev[2] / 1e9 / n_dev
                    slot["calls"] += 1.0 / n_dev
    by_scope = {}
    for (scope, _), ns in by_op.items():
        by_scope[scope] = by_scope.get(scope, 0) + ns
    edges = [w0] + [t for s, e in first_busy for t in (s, e)] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    small_ns = sum(b - a for a, b in gaps if b - a < MIN_GAP_NS)
    host_events = sorted((e[1], e[1] + e[2], e[0])
                         for ln in host for e in ln["events"]
                         if e[2] >= MIN_GAP_NS // 10 and e[0] != WINDOW_SPAN)
    by_cause, under = book([g for g in gaps if g[1] - g[0] >= MIN_GAP_NS],
                           host_events, window=(w0, w1) if span else None)
    ranked = lambda d, per=1: [[k, v / 1e9 / per] for k, v in  # noqa: E731
                               sorted(d.items(), key=lambda kv: -kv[1])]
    causes, scopes = ranked(by_cause), ranked(by_scope, n_dev)
    spans = _host_spans(host, w0, w1)
    return {
        "devices": n_dev,
        "busy_s": busy_ns / 1e9 / n_dev,
        "window_s": (w1 - w0) / 1e9,
        "window_from": WINDOW_SPAN if span else "device span",
        "device_span_s": (hi - lo) / 1e9,
        "device_ops": ranked({f"{scope}:{op}": ns for (scope, op), ns
                              in by_op.items()}, n_dev)[:TOP],
        "device_scopes": scopes[:TOP],
        "device_by_scope": dict(scopes),
        "idle_gaps": causes[:TOP],
        "idle_by_cause": dict(causes),
        "idle_under": {outer: {name: [runs, ns / 1e9]
                               for name, (runs, ns) in inner.items()}
                       for outer, inner in under.items()},
        "idle_small_s": small_ns / 1e9,
        "host_spans": spans,
        "program_spans": any(name.startswith(PROGRAM) for name in spans),
        "kernels": kern,
    }


def idle_share(trace, counted):
    """What an ``idle_share.*`` reader returns: the share (%) of the window
    the device sat idle in pieces of gaps booked to the names ``counted``
    accepts. None without a trace and for a program that opens no span;
    0.0 where it has spans and nothing is theirs. Facts that hold only
    ``idle_gaps`` (a result of before PR 35) are read as they were then."""
    if not trace or not trace.get("window_s"):
        return None
    by_cause = trace.get("idle_by_cause")
    if by_cause is None:
        by_cause = dict(trace.get("idle_gaps") or [])
    spans = trace.get("program_spans")
    if spans is None:
        spans = any(name.startswith(PROGRAM) for name in by_cause)
    if not spans:
        return None
    idle_s = sum(s for name, s in by_cause.items() if counted(name))
    return 100.0 * idle_s / trace["window_s"]
