"""From a profiler trace to numbers: the window and the device's busy time
in it, the device operations that took most of it, every idle gap and what
the host was doing in it, and the summed durations of named kernels.

Two steps, so that the arithmetic can be checked on made lines and on a
small recorded trace (``chipbench/tests/data/``) without a chip:

``events_of(path)``   .xplane.pb -> ``[{"plane", "line", "events": [[name,
                      start_ns, dur_ns], ...]}, ...]`` (needs only jax);
``reduce(lines, ...)`` the arithmetic, on that plain form.

Device lines are the "XLA Ops" lines of planes named ``/device:TPU:<n>``.
Host lines are every line of the ``/host:CPU`` plane: the benchmark's own
``chipbench:*`` annotations, the program's spans where it has any
(``fed:*``), and the runtime's own TraceMes. All in the profiler's one
timeline, which is not quite one clock: in the three serving profiles kept
in PR 35 the device's lines stand 2.0-2.3, 1.9-2.2 and 0.45-0.75 ms
EARLIER than the host's (a program's first operation stands that far
before the host event that enqueues it, its last operation that far plus
a few tenths before the runtime's completion callback; the offset holds
through a profile and differs from one process to the next). Over a 4 s
window that is 0.06 % of ``busy_s``; to a gap of an iteration's length
(2 ms) it can be the whole gap: such a gap is named by what the host did
that long before it (PERF.md sections 6 and 7). Nothing here corrects it.

How the numbers come about (one window, since PR 35):

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
  gap is booked WHOLE to one name: the host event of at least 10 us that
  overlaps it most, at equal overlap the shortest (so the innermost), or
  ``no host span``. One exception, at the window's two edges: the profile
  holds no host event that was open when the profiler started or stopped
  (a wait for a request that began before the window is not in it), so
  the gap that touches an edge, where no event of the profile covers half
  of it, is booked to ``window edge`` and not to whatever brushes its
  other end. The names are summed: ``idle_by_cause`` holds all of them,
  ``idle_gaps`` the ten largest for the result's ``breakdown``. A name's
  sum is the time the device sat idle in gaps booked to it, not a duration
  of that host event.
* ``program_spans``: whether any host event named ``fed:*`` lies in the
  window, i.e. whether the program opens spans at all (no commit before
  PR 24 does). ``idle_share(trace, counted)`` below, which the
  ``idle_share.*`` readers share, returns None only there and without a
  trace; with spans and no gap of its names it returns 0.0. The readers'
  names partition every cause the serving engine has, so
  idle_share.schedule + idle_share.unnamed + 100 * idle_small_s / window_s
  == 100 * (1 - busy_s / window_s) on one device; a gap booked to a
  ``fed:`` span of another layer (``fed:wire:*``, ``fed:agg:*``) would be
  in neither.
* ``device_ops`` sums the clipped durations by operation (containers
  left out). ``kernels`` are NOT clipped: seconds and calls of whole
  events wherever the profile holds them, because their reader
  (``flash_roofline``) divides one by the other. ``device_span_s`` is the
  device's own span, unclipped.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 100_000          # gaps under 0.1 ms are between back-to-back ops
WINDOW_SPAN = "chipbench:traced"
EDGE = "window edge"
TOP = 10
# Ops that only contain others (their children are on the same line).
CONTAINERS = ("while", "conditional", "call")


def op_name(name):
    """A device event is named by its whole HLO line, ``%fusion.436 = bf16[..]
    fusion(...)``: keep what stands before the ``=``, without the ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def events_of(path):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not (is_dev or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            evs = [[op_name(e.name) if is_dev else e.name[:80],
                    int(e.start_ns), int(e.duration_ns)]
                   for e in line.events]
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
    for name, start, dur in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            yield name, s, e


def _book(gaps, host_events, window=None):
    """``{name: ns}``: each gap whole to the host event that overlaps it
    most, at equal overlap the shortest; a gap at an edge of ``window``
    that no event covers by half goes to ``EDGE``. Both lists are sorted
    by start; one sweep, keeping the events that still reach the gap at
    hand."""
    by_cause, active, nxt = {}, [], 0
    for g0, g1 in gaps:
        active = [ev for ev in active if ev[1] > g0]
        while nxt < len(host_events) and host_events[nxt][0] < g1:
            if host_events[nxt][1] > g0:
                active.append(host_events[nxt])
            nxt += 1
        best, best_key = "no host span", (0, 0)
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
    return by_cause


def reduce(lines, window_s=None, kernels=()):
    """See the module docstring. ``kernels``: name prefixes whose events'
    durations and counts are summed (per device, then averaged).
    ``window_s`` is what callers of before PR 35 passed and is not read:
    the window comes from the trace."""
    del window_s
    dev = [ln for ln in lines if DEVICE_PLANE.match(ln["plane"])]
    host = [ln for ln in lines if not DEVICE_PLANE.match(ln["plane"])]
    n_dev = len({ln["plane"] for ln in dev})
    if not n_dev:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0,
                "device_ops": [], "idle_gaps": [], "idle_by_cause": {},
                "idle_small_s": 0.0, "program_spans": False, "kernels": {}}
    lo = min(e[1] for ln in dev for e in ln["events"])
    hi = max(e[1] + e[2] for ln in dev for e in ln["events"])
    span = next(((e[1], e[1] + e[2]) for ln in host for e in ln["events"]
                 if e[0] == WINDOW_SPAN), None)
    w0, w1 = span or (lo, hi)
    busy_ns, by_name, kern = 0, {}, {}
    first_plane = sorted({ln["plane"] for ln in dev})[0]
    first_busy = []
    for ln in dev:
        clipped = list(_clipped(ln["events"], w0, w1))
        merged = _union([(s, e) for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in merged)
        if ln["plane"] == first_plane:
            first_busy = merged
        for name, s, e in clipped:
            if not name.startswith(CONTAINERS):
                by_name[name] = by_name.get(name, 0) + e - s
        for name, _, dur in ln["events"]:
            for k in kernels:
                if name.startswith(k):
                    slot = kern.setdefault(k, {"seconds": 0.0, "calls": 0})
                    slot["seconds"] += dur / 1e9 / n_dev
                    slot["calls"] += 1.0 / n_dev
    edges = [w0] + [t for s, e in first_busy for t in (s, e)] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    small_ns = sum(b - a for a, b in gaps if b - a < MIN_GAP_NS)
    host_events = sorted((e[1], e[1] + e[2], e[0])
                         for ln in host for e in ln["events"]
                         if e[2] >= MIN_GAP_NS // 10 and e[0] != WINDOW_SPAN)
    by_cause = _book([g for g in gaps if g[1] - g[0] >= MIN_GAP_NS],
                     host_events, window=(w0, w1) if span else None)
    ranked = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                        sorted(d.items(), key=lambda kv: -kv[1])]
    causes = ranked(by_cause)
    return {
        "devices": n_dev,
        "busy_s": busy_ns / 1e9 / n_dev,
        "window_s": (w1 - w0) / 1e9,
        "window_from": WINDOW_SPAN if span else "device span",
        "device_span_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n_dev] for k, v in ranked(by_name)[:TOP]],
        "idle_gaps": causes[:TOP],
        "idle_by_cause": dict(causes),
        "idle_small_s": small_ns / 1e9,
        "program_spans": any(
            e[0].startswith("fed:") and e[1] < w1 and e[1] + e[2] > w0
            for ln in host for e in ln["events"]),
        "kernels": kern,
    }


def idle_share(trace, counted):
    """What an ``idle_share.*`` reader returns: the share (%) of the window
    the device sat idle in gaps booked to the names ``counted`` accepts.
    None without a trace and for a program that opens no span; 0.0 where
    it has spans and no gap is theirs. Facts that hold only ``idle_gaps``
    (a result of before PR 35) are read as they were then."""
    if not trace or not trace.get("window_s"):
        return None
    by_cause = trace.get("idle_by_cause")
    if by_cause is None:
        by_cause = dict(trace.get("idle_gaps") or [])
    spans = trace.get("program_spans")
    if spans is None:
        spans = any(name.startswith("fed:") for name in by_cause)
    if not spans:
        return None
    idle_s = sum(s for name, s in by_cause.items() if counted(name))
    return 100.0 * idle_s / trace["window_s"]
