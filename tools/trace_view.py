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

"""Text flamegraph over a ``tracing.export_seq_timeline`` JSON artifact.

Usage::

    python tools/trace_view.py bench_artifacts/alice.seq.json [--width 100]

One row per (upstream, downstream) seq-id edge, time on the x axis over
the artifact's full window. Timed spans (send / decode / task / fold /
publish) render as bars, arrival events (recv), membership events
(join / evict / epoch-bump, glyph ``M`` — the epoch boundaries) and
failover events (depose / takeover / handoff, glyph ``V`` — the term
boundaries) as single ticks, failed spans as ``x``. The point is hang forensics WITHOUT a debugger or a
Perfetto upload: the recurring gRPC-lane ``_fedavg_party`` wedge — and
any async-mode straggler — shows up as the edge whose last mark sits far
left of everyone else's.

Dependency-free (stdlib only): it must run on the bare CI host that just
watched a bench party get killed.
"""

from __future__ import annotations

import argparse
import json
import sys

# One glyph per span kind; kinds not listed render as '?'.
_GLYPHS = {
    "send": "s",
    "recv": "r",
    "decode": "d",
    "write": "w",
    "fold": "F",
    "publish": "P",
    "hb": "h",
    "membership": "M",
    "failover": "V",
    "control": "c",
    "fault": "!",
}


def _render_edge(edge: dict, t0: float, window: float, width: int) -> str:
    lane = ["."] * width
    scale = (width - 1) / window if window > 0 else 0.0

    def col(t: float) -> int:
        return max(0, min(width - 1, int((t - t0) * scale)))

    for ev in edge["events"]:
        glyph = "x" if not ev.get("ok", True) else _GLYPHS.get(ev["kind"], "?")
        start, end = col(ev["t_s"]), col(ev["t_s"] + ev.get("dur_s", 0.0))
        for c in range(start, end + 1):
            # Later events overwrite earlier dots, never earlier failures.
            if lane[c] != "x":
                lane[c] = glyph
    return "".join(lane)


def render(doc: dict, width: int = 100, out=sys.stdout) -> int:
    """Render one timeline document; returns the number of edges drawn."""
    edges = doc.get("edges", [])
    events = [ev for e in edges for ev in e["events"]]
    if not events:
        out.write("(empty timeline: no spans recorded)\n")
        return 0
    t0 = min(ev["t_s"] for ev in events)
    t1 = max(ev["t_s"] + ev.get("dur_s", 0.0) for ev in events)
    window = max(t1 - t0, 1e-9)
    out.write(
        f"party={doc.get('party', '?')} edges={len(edges)} "
        f"window={window * 1e3:.1f}ms  "
        f"[{' '.join(f'{g}={k}' for k, g in _GLYPHS.items())} x=failed]\n"
    )
    label_w = max(
        (len(f"{e['up']}->{e['down']}") for e in edges), default=0
    )
    label_w = min(label_w, 28)
    for edge in edges:
        label = f"{edge['up']}->{edge['down']}"[:label_w]
        last = max(
            ev["t_s"] + ev.get("dur_s", 0.0) for ev in edge["events"]
        )
        out.write(
            f"{label:<{label_w}} |{_render_edge(edge, t0, window, width)}| "
            f"n={len(edge['events'])} last=+{(last - t0) * 1e3:.1f}ms\n"
        )
    return len(edges)


def render_fleet(doc: dict, width: int = 100, out=sys.stdout) -> int:
    """Render a collector fleet trace (``fed.export_fleet_trace``): one
    swim-lane per party over the shared wall-clock window, then the
    per-edge rows. A party's lane carries every span the collector
    harvested from it — membership epoch bumps surface as ``M`` ticks, so
    a roster change reads as a vertical seam across the lanes."""
    edges = doc.get("edges", [])
    events = [ev for e in edges for ev in e["events"]]
    if not events:
        out.write("(empty fleet timeline: no spans harvested)\n")
        return 0
    t0 = min(ev["t_s"] for ev in events)
    t1 = max(ev["t_s"] + ev.get("dur_s", 0.0) for ev in events)
    window = max(t1 - t0, 1e-9)
    parties = list(doc.get("parties") or sorted(
        {ev.get("party", "?") for ev in events}
    ))
    out.write(
        f"fleet job={doc.get('job', '?')} collector="
        f"{doc.get('collector', '?')} parties={len(parties)} "
        f"edges={len(edges)} window={window * 1e3:.1f}ms  "
        f"[{' '.join(f'{g}={k}' for k, g in _GLYPHS.items())} x=failed]\n"
    )
    label_w = max([len(p) for p in parties]
                  + [min(len(f"{e['up']}->{e['down']}"), 28) for e in edges])
    for party in parties:
        lane = {
            "events": [ev for ev in events if ev.get("party") == party]
        }
        out.write(
            f"{party:<{label_w}} |{_render_edge(lane, t0, window, width)}| "
            f"n={len(lane['events'])}\n"
        )
    out.write("-" * (label_w + width + 3) + "\n")
    for edge in edges:
        label = f"{edge['up']}->{edge['down']}"[:label_w]
        last = max(
            ev["t_s"] + ev.get("dur_s", 0.0) for ev in edge["events"]
        )
        out.write(
            f"{label:<{label_w}} |{_render_edge(edge, t0, window, width)}| "
            f"n={len(edge['events'])} last=+{(last - t0) * 1e3:.1f}ms\n"
        )
    return len(edges)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="text flamegraph for tracing.export_seq_timeline JSON"
    )
    parser.add_argument("paths", nargs="+", help="seq-timeline JSON file(s)")
    parser.add_argument(
        "--width", type=int, default=100, help="columns in the time axis"
    )
    parser.add_argument(
        "--fleet", action="store_true",
        help="render a collector fleet trace (fed.export_fleet_trace) "
        "with per-party swim-lanes; auto-detected from the document",
    )
    args = parser.parse_args(argv)
    for path in args.paths:
        if len(args.paths) > 1:
            print(f"== {path} ==")
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if args.fleet or doc.get("fleet"):
            render_fleet(doc, width=args.width)
        else:
            render(doc, width=args.width)
    return 0


if __name__ == "__main__":
    sys.exit(main())
