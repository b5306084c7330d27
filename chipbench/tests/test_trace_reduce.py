"""The trace reduction's arithmetic on made lines (no jax, no chip): one
window, everything clipped to it, every gap booked, and what the
``idle_share.*`` readers return when. ISSUE 35 asked for this file under
``tests/``; a benchmark PR adds files only under ``chipbench/``."""

from __future__ import annotations

import gzip
import json
import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402
from chipbench.run import load_reader  # noqa: E402

MS = 1_000_000
US = 1_000
READERS = ("idle_share.schedule", "idle_share.unnamed")
# Twelve names a serving run books gaps to: the engine's phases, the wait
# for the step's ids, and the runtime's own TraceMes.
NAMES = ["fed:serve:" + p for p in ("admit", "build", "dispatch", "emit",
                                    "prefill_chunk", "idle", "fetch")] + [
    "np.asarray(jax.Array)", "ReadSyncFlag", "PjitFunction(decode_step)",
    "TpuExecute", "BufferFromHostBuffer"]


def dev(events):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "events": events}


def host(events, line="engine"):
    return {"plane": "/host:CPU", "line": line, "events": events}


def window(start, end):
    return host([[trace_reduce.WINDOW_SPAN, start, end - start]], "tracer")


def shares(out):
    facts = {"trace": out}
    return [load_reader(name)(facts) for name in READERS]


@pytest.mark.parametrize("overhang_us", [0, 136, 5000])
def test_a_device_busy_from_before_the_window_to_after_it(overhang_us):
    """PR 34's falconh1 run: programs back to back through a window the
    profile overhangs at both ends. The parent's reducer read busy_s 136 us
    over window_s there, and None from the readers."""
    w0, w1 = 10 * MS, 4010 * MS
    step = 15 * MS
    start = w0 - overhang_us * US - 3 * step
    ops = [["while.1", start, 3 * step]]
    t = start
    while t < w1 + overhang_us * US:
        ops.append(["fusion.1", t, step // 3])
        ops.append(["fusion.2", t + step // 3, step - step // 3])
        t += step
    out = trace_reduce.reduce([
        dev(ops), window(w0, w1),
        host([["fed:serve:fetch", w0 + i * step, 900 * US]
              for i in range(200)])])
    assert out["busy_s"] == out["window_s"] == 4.0
    assert out["idle_gaps"] == [] and out["idle_by_cause"] == {}
    assert out["idle_small_s"] == 0.0 and out["program_spans"] is True
    assert out["device_span_s"] >= out["window_s"]
    assert sum(s for _, s in out["device_ops"]) == pytest.approx(4.0)
    assert shares(out) == [0.0, 0.0]


def made_trace(seed, n_gaps=400):
    """400 gaps in a window of their own, a twelfth of them under
    MIN_GAP_NS, each under one host event of NAMES (drawn from the seed),
    with the window's own leading and trailing gap besides."""
    rng = random.Random(seed)
    t = w0 = 7 * MS
    t += 250 * US                                   # the leading gap
    ops, events, want, small = [], [], {}, 0
    for i in range(n_gaps):
        busy = rng.randrange(50 * US, 9 * MS)
        ops.append([f"fusion.{i % 17}", t, busy])
        t += busy
        if i % 12 == 5:
            gap = rng.randrange(1 * US, trace_reduce.MIN_GAP_NS)
            small += gap
        else:
            gap = rng.randrange(trace_reduce.MIN_GAP_NS, 3 * MS)
            name = NAMES[rng.randrange(len(NAMES))]
            want[name] = want.get(name, 0) + gap
            # The event overhangs its gap into the busy time around it.
            events.append([name, t - 20 * US, gap + 40 * US])
        t += gap
    ops.append(["fusion.last", t, 2 * MS])
    t += 2 * MS + 300 * US                          # the trailing gap
    want[trace_reduce.EDGE] = (250 + 300) * US     # no event reaches them
    lines = [dev(ops), host(events), window(w0, t)]
    return lines, want, small, t - w0


@pytest.mark.parametrize("seed", [1, 2147483655, 35])
def test_every_gap_is_booked_and_the_readers_add_up_to_the_idle_share(seed):
    lines, want, small, window_ns = made_trace(seed)
    out = trace_reduce.reduce(lines)
    assert out["window_from"] == trace_reduce.WINDOW_SPAN
    assert out["window_s"] == window_ns / 1e9
    assert len(want) == 13 and len(out["idle_gaps"]) == trace_reduce.TOP
    assert {k: round(v * 1e9) for k, v in out["idle_by_cause"].items()} == want
    assert out["idle_small_s"] == small / 1e9 > 0
    # busy + every gap == the window, to the nanosecond.
    assert round(1e9 * (out["busy_s"] + out["idle_small_s"]
                        + sum(out["idle_by_cause"].values()))) == window_ns
    schedule, unnamed = shares(out)
    assert schedule > 0 and unnamed > 0
    assert schedule + unnamed + 100 * out["idle_small_s"] / out["window_s"] \
        == pytest.approx(100 * (1 - out["busy_s"] / out["window_s"]),
                         abs=1e-9)


def test_booking_is_the_parents_rule_gap_by_gap():
    """The sweep against the rescan it replaced (chipbench/trace_reduce.py
    before PR 35), on host events that nest, overlap and leave holes."""
    rng = random.Random(35)
    gaps, t = [], 0
    for _ in range(300):
        t += rng.randrange(1, 5 * MS)
        g = rng.randrange(trace_reduce.MIN_GAP_NS, 4 * MS)
        gaps.append((t, t + g))
        t += g
    events = sorted(
        (s, s + rng.choice((20 * US, 1 * MS, 30 * MS, 900 * MS)), f"e{i % 9}")
        for i, s in enumerate(rng.randrange(0, t) for _ in range(2000)))
    want = {}
    for g0, g1 in gaps:
        best, best_key = "no host span", (0, 0)
        for s, e, name in events:
            if s >= g1:
                break
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0 and (overlap, -(e - s)) > best_key:
                best, best_key = name, (overlap, -(e - s))
        want[best] = want.get(best, 0) + g1 - g0
    assert trace_reduce._book(gaps, events) == want


CASES = {
    # spans and no gap: the device never idles, the readers say so.
    "spans_no_gap": ([["fed:serve:fetch", 1 * MS, 2 * MS]], [], [0.0, 0.0]),
    # spans, and the one gap is the fetch's.
    "spans_one_gap": ([["fed:serve:fetch", 4 * MS, 3 * MS]],
                      [(5 * MS, 6 * MS)], [0.0, 10.0]),
    # no span of the program in the window: nothing for them to read.
    "no_spans": ([["np.asarray(jax.Array)", 4 * MS, 3 * MS]],
                 [(5 * MS, 6 * MS)], [None, None]),
    # a span of the program, but outside the window.
    "spans_outside": ([["fed:serve:idle", 11 * MS, 3 * MS]],
                      [(5 * MS, 6 * MS)], [None, None]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_what_the_readers_return_when(case):
    events, holes, want = CASES[case]
    edges = [0] + [t for hole in holes for t in hole] + [10 * MS]
    ops = [["fusion.1", a, b - a] for a, b in zip(edges[::2], edges[1::2])]
    out = trace_reduce.reduce([dev(ops), host(events), window(0, 10 * MS)])
    assert shares(out) == want


@pytest.mark.parametrize("facts", [{}, {"trace": None},
                                   {"trace": {"devices": 0, "window_s": 0.0}}])
def test_no_trace_gives_none(facts):
    assert [load_reader(name)(facts) for name in READERS] == [None, None]


def test_leading_and_trailing_gaps_count_and_the_window_span_names_none():
    # Busy [2, 8] ms in a window [0, 10] ms under one long host event.
    out = trace_reduce.reduce([
        dev([["fusion.1", 2 * MS, 6 * MS]]), window(0, 10 * MS),
        host([["fed:serve:idle", 0, 3 * MS]])])
    assert out["busy_s"] == 0.006 and out["window_s"] == 0.010
    assert out["idle_by_cause"] == {"fed:serve:idle": 0.002,
                                    trace_reduce.EDGE: 0.002}


@pytest.mark.parametrize("admit_from_ms, want", [
    # llm7b-chat-steady, seed 3500000102 on the chip: the engine waited for
    # a request when the profiler started, so its fed:serve:idle is not in
    # the profile, and the admission that ended the lull brushes 2 ms of it.
    (3098, trace_reduce.EDGE),
    # An event of the profile that covers most of the gap names it.
    (1000, "fed:serve:admit"),
])
def test_a_lull_cut_by_the_windows_edge_is_not_named_by_what_ends_it(
        admit_from_ms, want):
    first = 3100 * MS
    out = trace_reduce.reduce([
        dev([["fusion.1", first, 900 * MS]]), window(0, 4000 * MS),
        host([["fed:serve:admit", admit_from_ms * MS,
               first + 20 * MS - admit_from_ms * MS]])])
    assert out["idle_by_cause"] == {want: 3.1}


def test_the_recorded_trace_reduces_as_before_pr35():
    """No chipbench:traced span in it: the window is the device's span, and
    busy_s, kernels and the gaps are the parent's values (its own run of
    reduce at commit 68a9e21, written here)."""
    path = os.path.join(HERE, "data", "fedround_v5e_events.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    out = trace_reduce.reduce(recorded, kernels=(
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    assert out["window_from"] == "device span"
    assert out["busy_s"] == 0.219482156
    assert out["window_s"] == out["device_span_s"] == 0.227082909
    assert out["kernels"] == {
        "flash_fwd": {"seconds": 0.011019287, "calls": 8.0},
        "flash_bwd_dq": {"seconds": 0.001458362, "calls": 1.0},
        "flash_bwd_dkv": {"seconds": 0.002755433, "calls": 1.0}}
    assert out["idle_gaps"] == [["chipbench:local_steps", 0.006312687],
                                ["chipbench:wait_aggregate", 0.001274945]]
    assert out["device_ops"][0] == ["flash_fwd.16", 0.009621721]
    assert out["program_spans"] is False
    assert out["busy_s"] + out["idle_small_s"] + sum(
        out["idle_by_cause"].values()) == pytest.approx(out["window_s"],
                                                        abs=1e-12)
