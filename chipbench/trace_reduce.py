"""From a profiler trace to numbers: device busy time, the device
operations that took most time, the longest idle gaps and what the host
was doing in them, and the summed durations of named kernels.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``chipbench/tests/data/``) without a chip:

``events_of(path)``   .xplane.pb -> ``[{"plane", "line", "events": [[name,
                      start_ns, dur_ns], ...]}, ...]`` (needs only jax);
``reduce(lines, ...)`` the arithmetic, on that plain form.

Device lines are the "XLA Ops" lines of planes named ``/device:TPU:<n>``.
Host lines are every line of the ``/host:CPU`` plane: the benchmark's own
``chipbench:*`` annotations, the program's spans where it has any, and the
runtime's own TraceMes.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 100_000          # gaps under 0.1 ms are between back-to-back ops
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


def reduce(lines, window_s=None, kernels=()):
    """See the module docstring. ``kernels``: name prefixes whose events'
    durations and counts are summed (per device, then averaged)."""
    dev = [ln for ln in lines if DEVICE_PLANE.match(ln["plane"])]
    host = [ln for ln in lines if not DEVICE_PLANE.match(ln["plane"])]
    n_dev = len({ln["plane"] for ln in dev})
    if not n_dev:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s or 0.0,
                "device_ops": [], "idle_gaps": [], "kernels": {}}
    busy_ns, by_name, kern = 0, {}, {}
    first_plane = sorted({ln["plane"] for ln in dev})[0]
    first_busy = []
    lo = min(e[1] for ln in dev for e in ln["events"])
    hi = max(e[1] + e[2] for ln in dev for e in ln["events"])
    for ln in dev:
        merged = _union([(e[1], e[1] + e[2]) for e in ln["events"]])
        busy_ns += sum(e - s for s, e in merged)
        if ln["plane"] == first_plane:
            first_busy = merged
        for name, _, dur in ln["events"]:
            if not name.startswith(CONTAINERS):
                by_name[name] = by_name.get(name, 0) + dur
            for k in kernels:
                if name.startswith(k):
                    slot = kern.setdefault(k, {"seconds": 0.0, "calls": 0})
                    slot["seconds"] += dur / 1e9 / n_dev
                    slot["calls"] += 1.0 / n_dev
    # Idle gaps on the first device, longest first, each named by the host
    # event that covers most of it (the shortest such, so the innermost).
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(first_busy, first_busy[1:])
                   if b[0] - a[1] >= MIN_GAP_NS), reverse=True)[:150]
    host_events = sorted((e[1], e[1] + e[2], e[0])
                         for ln in host for e in ln["events"]
                         if e[2] >= MIN_GAP_NS // 10)
    by_cause = {}
    for length, g0, g1 in gaps:
        best, best_key = "no host span", (0, 0)
        for s, e, name in host_events:
            if s >= g1:
                break
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                key = (overlap, -(e - s))
                if key > best_key:
                    best, best_key = name, key
        by_cause[best] = by_cause.get(best, 0) + length
    span_s = (hi - lo) / 1e9
    top = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "devices": n_dev,
        "busy_s": busy_ns / 1e9 / n_dev,
        "window_s": window_s if window_s else span_s,
        "device_span_s": span_s,
        "device_ops": [[k, v / n_dev] for k, v in top(by_name)],
        "idle_gaps": top(by_cause),
        "kernels": kern,
    }
