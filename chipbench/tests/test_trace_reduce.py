"""The trace reduction's arithmetic on made lines (no jax, no chip): one
window, everything clipped to it, every gap cut at the host events over it
and each piece booked to the innermost span, the host spans' own times,
device time by named scope, and what the readers of a traced run return
when. ISSUE 35 asked for this file under ``tests/``; a benchmark PR adds
files only under ``chipbench/``."""

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
ROUND = ("idle_share.wire", "idle_share.agg", "idle_share.wait")
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


def nested_events(seed):
    """300 gaps and 2,000 host events that nest, overlap and leave holes,
    of the three classes the rule ranks."""
    rng = random.Random(seed)
    gaps, t = [], 0
    for _ in range(300):
        t += rng.randrange(1, 5 * MS)
        g = rng.randrange(trace_reduce.MIN_GAP_NS, 4 * MS)
        gaps.append((t, t + g))
        t += g
    # The better the class, the shorter its events: each wins somewhere.
    classes = (("fed:x:", (20 * US, 1 * MS)),
               ("chipbench:", (20 * US, 1 * MS, 30 * MS)),
               ("rt", (20 * US, 1 * MS, 30 * MS, 900 * MS)))
    events = sorted(
        (s, s + rng.choice(classes[i % 3][1]),
         f"{classes[i % 3][0]}e{i % 9}")
        for i, s in enumerate(rng.randrange(0, t) for _ in range(2000)))
    return gaps, events, t


def test_booking_is_the_rule_nanosecond_by_nanosecond():
    """The sweep against the rule read off plainly: a piece between two
    neighbouring cuts goes to the covering event of the best class, the
    shortest of them, found by scanning every event."""
    gaps, events, end = nested_events(38)
    gaps = [(0, gaps[0][0] // 2)] + gaps        # one at the window's start
    window = (0, end)
    want = {}
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {t for s, e, _ in events for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(trace_reduce._rank(n), e - s, e, n)
                     for s, e, n in events if s <= a and e >= b]
            name = min(cover)[3] if cover else (
                trace_reduce.EDGE if a == 0 else trace_reduce.NO_SPAN)
            want[name] = want.get(name, 0) + b - a
    got, under = trace_reduce._book(gaps, events, window)
    assert got == want
    assert sum(got.values()) == sum(b - a for a, b in gaps)
    assert any(n.startswith("rt") for n in got) and trace_reduce.EDGE in got
    # What lies under a span of the benchmark, by the name it went to.
    assert all(outer.startswith("chipbench:") for outer in under)
    assert sum(ns for inner in under.values()
               for _, ns in inner.values()) <= sum(got.values())


def test_the_whole_gap_rule_of_before_pr38_is_kept_for_its_one_caller():
    """``reduce(window_s=)``: tests/test_tracing_phases.py pins that rule."""
    gaps, events, _ = nested_events(35)
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
    assert trace_reduce._book_whole(gaps, events) == (want, {})
    lines = [dev([["fusion.1", 0, 10 * MS], ["fusion.2", 20 * MS, 10 * MS]]),
             host([["fed:serve:fetch", 8 * MS, 3 * MS],
                   ["fed:serve:build", 11 * MS, 9 * MS]])]
    assert trace_reduce.reduce(lines, window_s=0.03)["idle_by_cause"] == {
        "fed:serve:build": 0.010}
    assert trace_reduce.reduce(lines)["idle_by_cause"] == {
        "fed:serve:build": 0.009, "fed:serve:fetch": 0.001}


# Busy [0, 10] and [30, 40] ms: one gap of 20 ms, in a window [0, 40] ms.
ONE_GAP = [["fusion.1", 0, 10 * MS], ["fusion.2", 30 * MS, 10 * MS]]
SPLITS = {
    # The benchmark's span around the round, two spans of the program in
    # it: three ways, and the enclosing span keeps what neither covers.
    "encloser_and_two_inner": (
        [["chipbench:wait_aggregate", 1 * MS, 38 * MS],
         ["fed:wire:recv", 5 * MS, 12 * MS],
         ["fed:agg:reduce", 22 * MS, 4 * MS]],
        {"fed:wire:recv": 0.007, "fed:agg:reduce": 0.004,
         "chipbench:wait_aggregate": 0.009}),
    # A span of the program beats a shorter event of the runtime in it,
    # and a shorter span of the benchmark.
    "fed_beats_shorter": (
        [["fed:serve:fetch", 9 * MS, 22 * MS],
         ["np.asarray(jax.Array)", 10 * MS, 20 * MS],
         ["chipbench:local_steps", 12 * MS, 3 * MS]],
        {"fed:serve:fetch": 0.020}),
    # Of two spans of the program the innermost (shortest).
    "innermost_fed": (
        [["fed:wire:decode", 5 * MS, 30 * MS],
         ["fed:wire:place", 14 * MS, 6 * MS]],
        {"fed:wire:decode": 0.014, "fed:wire:place": 0.006}),
    # No span of program or benchmark: the runtime's, the innermost; what
    # nothing covers is no host span (it touches no edge of the window).
    "runtime_and_hole": (
        [["ReadSyncFlag", 12 * MS, 10 * MS],
         ["TpuExecute", 14 * MS, 2 * MS], ["tiny", 25 * MS, 5 * US]],
        {"ReadSyncFlag": 0.008, "TpuExecute": 0.002, "no host span": 0.010}),
}


@pytest.mark.parametrize("case", sorted(SPLITS))
def test_a_gap_is_split_among_the_innermost_spans(case):
    events, want = SPLITS[case]
    out = trace_reduce.reduce([dev(ONE_GAP), host(events),
                               window(0, 40 * MS)])
    got = {k: round(v * 1e9) for k, v in out["idle_by_cause"].items()}
    assert got == {k: round(v * 1e9) for k, v in want.items()}
    assert sum(got.values()) == 20 * MS
    assert out["busy_s"] == 0.020 and out["window_s"] == 0.040


def test_what_lies_under_a_span_of_the_benchmark_by_the_name_booked():
    """Two gaps between steps under ``chipbench:local_steps``: one inside
    another thread's ``fed:wire:recv``, one in no span of the program."""
    ops = [["fusion.1", 0, 10 * MS], ["fusion.2", 12 * MS, 10 * MS],
           ["fusion.3", 25 * MS, 10 * MS]]
    out = trace_reduce.reduce([
        dev(ops), window(0, 35 * MS),
        host([["chipbench:local_steps", 1 * MS, 33 * MS],
              ["PjitFunction(step)", 10 * MS, 1 * MS],
              ["PjitFunction(step)", 23 * MS, 1 * MS]], "trainer"),
        host([["fed:wire:recv", 2 * MS, 15 * MS]], "reactor")])
    assert out["idle_by_cause"] == {"fed:wire:recv": 0.002,
                                    "chipbench:local_steps": 0.003}
    # The second gap is cut by the runtime's event and is still ONE run.
    assert out["idle_under"] == {"chipbench:local_steps": {
        "fed:wire:recv": [1, 0.002], "chipbench:local_steps": [1, 0.003]}}


def test_self_time_is_a_span_less_its_children_on_the_same_line():
    out = trace_reduce.reduce([
        dev([["fusion.1", 0, 100 * MS]]), window(0, 100 * MS),
        host([["fed:wire:decode", 10 * MS, 60 * MS],
              ["fed:wire:deserialize", 12 * MS, 20 * MS],
              ["Transpose::Execute", 14 * MS, 10 * MS],   # a grandchild
              ["fed:wire:place", 40 * MS, 25 * MS],
              ["fed:wire:decode", 80 * MS, 40 * MS]]),     # clipped to 20
        # Another thread's span over the same time takes nothing off.
        host([["fed:wire:recv", 0, 90 * MS]], "reactor")])
    spans = out["host_spans"]
    assert spans["fed:wire:decode"] == {
        "count": 2, "seconds": 0.080, "self_s": 0.035, "p50_ms": 40.0,
        "max_ms": 60.0}
    assert spans["fed:wire:deserialize"]["self_s"] == 0.010
    assert spans["fed:wire:place"]["self_s"] == 0.025
    assert spans["fed:wire:recv"] == {
        "count": 1, "seconds": 0.090, "self_s": 0.090, "p50_ms": 90.0,
        "max_ms": 90.0}
    assert spans[trace_reduce.WINDOW_SPAN]["count"] == 1
    assert "Transpose::Execute" not in spans
    assert out["program_spans"] is True


PATHS = {
    "jit(step)/jit(main)/train/forward/dot_general": "train/forward",
    "jit(step)/jit(main)/transpose(jvp(train/forward))/while/body/"
    "checkpoint/rematted_computation/dot_general": "bwd(train/forward)",
    "jit(step)/jit(main)/jvp(train/forward)/while/body/mul": "train/forward",
    "jit(decode_step)/jit(main)/serve/decode_step/while/body/serve/"
    "attn_latent/dot_general": "serve/attn_latent",
    "jit(decode_step)/jit(main)/serve/decode_step/while/body/add":
        "serve/decode_step",
    "jit(decode_step)/jit(main)/serve/decode_step/serve/moe_experts/"
    "jit(_take)/gather": "serve/moe_experts",
    "jit(_tree_mean)/jit(main)/aggregate/mean/div": "aggregate/mean",
    # As the chip's profile writes them (PR 38): a colon after the last part.
    "jit(step)/train/optimizer/sub:": "train/optimizer",
    "jit(step)/transpose(jvp(train/forward))/dot_general:":
        "bwd(train/forward)",
    "jit(step)/while:": trace_reduce.NO_SCOPE,
    "jit(f)/jit(main)/cond/branch_1_fun/train/optimizer/mul":
        "train/optimizer",
    "jit(f)/jit(main)/while/body/add": trace_reduce.NO_SCOPE,
    "jit(convert_element_type)/jit(main)/convert_element_type":
        trace_reduce.NO_SCOPE,
    "": trace_reduce.NO_SCOPE,
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_scope_of_an_op_name_path(path):
    assert trace_reduce.scope_of(path) == PATHS[path]


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def pb(*fields):
    """A protobuf message from (number, value) pairs: an int is a varint, a
    float a fixed64, bytes or str length-delimited."""
    import struct

    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def made_xplane(tmp_path):
    """An XSpace as the chip's profiler writes it (tsl's xplane.proto): a
    device plane whose operations carry their op_name path in the event
    METADATA, once as a string and once as a reference to a stat's
    metadata, beside stats of other wire types; two lines of events."""
    entry = lambda key, msg: pb((1, key), (2, msg))  # noqa: E731
    attn = "jit(decode_step)/jit(main)/serve/decode_step/while/body/" \
        "serve/attn_latent/dot_general"
    bwd = "jit(step)/transpose(jvp(train/forward))/while/body/mul"
    ops = {7: ("%fusion.885 = bf16[8]{0} fusion(bf16[8]{0} %p)", [
               pb((1, 2), (5, "convolution")), pb((1, 1), (5, attn)),
               pb((1, 300), (3, 1 << 40))]),
           900: ("%fusion.12 = f32[] fusion()", [
               pb((1, 300), (2, 2.5)), pb((1, 1), (7, 3))]),
           901: ("%copy.3 = f32[] copy(f32[] %q)", []),
           902: ("%while.1 = () while()", [pb((1, 1), (5, attn))])}
    events = [(902, 0, 30), (7, 0, 10), (7, 10, 10), (900, 30, 15),
              (901, 50, 4), (7, 95, 10)]
    line = lambda i, name, evs: pb(  # noqa: E731
        (1, i), (2, name), (3, 1_000_000), *[
            (4, pb((1, m), (2, s * 1_000_000_000), (3, d * 1_000_000_000),
                   (4, pb((1, 300), (2, 1.0))))) for m, s, d in evs])
    device = pb(
        (1, 1), (2, "/device:TPU:0"),
        (3, line(1, "XLA Ops", events)),
        (3, line(2, "XLA Modules", [(902, 0, 105)])),
        *[(4, entry(i, pb((1, i), (2, name), *[(5, s) for s in stats])))
          for i, (name, stats) in ops.items()],
        (5, entry(1, pb((1, 1), (2, "tf_op")))),
        (5, entry(2, pb((1, 2), (2, "hlo_category")))),
        (5, entry(3, pb((1, 3), (2, bwd)))),
        (5, entry(300, pb((1, 300), (2, "flops")))))
    host = pb((1, 2), (2, "/host:CPU"),
              (3, pb((1, 1), (2, "engine"), (3, 1_000_000), (4, pb(
                  (1, 1), (2, 5_000_000_000), (3, 50_000_000_000))))),
              (4, entry(1, pb((1, 1), (2, "fed:serve:fetch")))))
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(pb((1, device), (1, host), (4, "vm")))
    return str(path), attn, bwd


def test_the_scope_is_read_from_the_events_metadata(tmp_path):
    path, attn, bwd = made_xplane(tmp_path)
    assert trace_reduce.event_scopes(path) == {"/device:TPU:0": {
        "%fusion.885 = bf16[8]{0} fusion(bf16[8]{0} %p)": attn,
        "%fusion.12 = f32[] fusion()": bwd,
        "%copy.3 = f32[] copy(f32[] %q)": "", "%while.1 = () while()": attn}}
    pytest.importorskip("jax.profiler")
    lines = trace_reduce.events_of(path)
    assert [(ln["plane"], ln["line"]) for ln in lines] == [
        ("/device:TPU:0", "XLA Ops"), ("/host:CPU", "engine")]
    ms = 1_000_000      # the made line begins at 1 ms
    assert lines[0]["events"] == [
        ["while.1", 1 * ms, 30 * ms, "serve/attn_latent"],
        ["fusion.885", 1 * ms, 10 * ms, "serve/attn_latent"],
        ["fusion.885", 11 * ms, 10 * ms, "serve/attn_latent"],
        ["fusion.12", 31 * ms, 15 * ms, "bwd(train/forward)"],
        ["copy.3", 51 * ms, 4 * ms, "(no scope)"],
        ["fusion.885", 96 * ms, 10 * ms, "serve/attn_latent"]]
    assert lines[1]["events"] == [["fed:serve:fetch", 6 * ms, 50 * ms]]
    out = trace_reduce.reduce(lines)
    assert out["device_by_scope"] == {
        "serve/attn_latent": 0.030, "bwd(train/forward)": 0.015,
        "(no scope)": 0.004}
    assert out["idle_by_cause"] == {"fed:serve:fetch": 0.006,
                                    "no host span": 0.040}


def test_device_time_is_summed_by_scope():
    """A container's children are on its line and it is left out; the
    backward of a scope has a name of its own; an operation of before the
    scopes (three items) and one under none are ``(no scope)``; everything
    is clipped to the window."""
    ops = [["while.3", 0, 30 * MS, "serve/attn_latent"],
           ["fusion.885", 0, 10 * MS, "serve/attn_latent"],
           ["fusion.886", 10 * MS, 20 * MS, "serve/attn_latent"],
           ["fusion.12", 30 * MS, 15 * MS, "bwd(train/forward)"],
           ["fusion.12", 45 * MS, 5 * MS, "train/forward"],
           ["copy.1", 50 * MS, 4 * MS, trace_reduce.NO_SCOPE],
           ["copy.2", 54 * MS, 6 * MS],
           ["fusion.885", 95 * MS, 10 * MS, "serve/attn_latent"]]
    out = trace_reduce.reduce([dev(ops), window(0, 100 * MS)])
    assert out["device_by_scope"] == {
        "serve/attn_latent": 0.035, "bwd(train/forward)": 0.015,
        "(no scope)": 0.010, "train/forward": 0.005}
    assert out["device_scopes"][0] == ["serve/attn_latent", 0.035]
    assert out["device_ops"][:3] == [
        ["serve/attn_latent:fusion.886", 0.020],
        ["serve/attn_latent:fusion.885", 0.015],
        ["bwd(train/forward):fusion.12", 0.015]]
    assert sum(out["device_by_scope"].values()) == pytest.approx(
        out["busy_s"])


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


def test_a_rounds_three_readers_add_up_to_the_idle_share():
    """A round as fedround's: steps under ``chipbench:local_steps`` while
    the reactor's ``fed:wire:recv`` is open, then the wait for the
    aggregate around ``fed:wire:decode`` > ``:place`` and
    ``fed:agg:reduce``; ``.wire + .agg + .wait + idle_small`` is the
    device's idle share, and the enclosing span keeps only what no span
    of the program covers."""
    ops, t = [], 2 * MS
    for i in range(8):                      # 8 steps, 1 ms between two
        ops.append([f"fusion.{i}", t, 49 * MS])
        t += 50 * MS
    ops += [["fusion.mean", 520 * MS, 6 * MS],
            ["fusion.tiny", 526 * MS + 40 * US, 1 * MS]]
    lines = [
        dev(ops), window(0, 540 * MS),
        host([["chipbench:wait_aggregate", 1 * MS, 535 * MS],
              ["fed:agg:reduce", 515 * MS, 6 * MS]], "main"),
        host([["chipbench:local_steps", 1 * MS, 402 * MS]], "trainer"),
        host([["fed:wire:recv", 100 * MS, 360 * MS]], "reactor"),
        host([["fed:wire:decode", 462 * MS, 30 * MS],
              ["fed:wire:place", 470 * MS, 20 * MS]], "rendezvous")]
    out = trace_reduce.reduce(lines)
    ns = {k: round(v * 1e9) for k, v in out["idle_by_cause"].items()}
    assert ns == {
        trace_reduce.EDGE: 1 * MS + 4 * MS,           # [0, 1], [536, 540]
        "chipbench:local_steps": 2 * MS,              # [1, 2], [51, 52]
        "fed:wire:recv": 6 * MS + 59 * MS,            # 6 gaps; [401, 460]
        "chipbench:wait_aggregate":                   # what nothing covers
            2 * MS + 23 * MS + 8 * MS + 960 * US,
        "fed:wire:decode": 8 * MS + 2 * MS, "fed:wire:place": 20 * MS,
        "fed:agg:reduce": 5 * MS}
    assert out["idle_small_s"] == 40e-6
    facts = {"trace": out}
    wire, agg, wait = (load_reader(name)(facts) for name in ROUND)
    assert wire == pytest.approx(100 * 95e-3 / 0.540)
    assert agg == pytest.approx(100 * 5e-3 / 0.540)
    assert wire + agg + wait + 100 * out["idle_small_s"] / out["window_s"] \
        == pytest.approx(100 * (1 - out["busy_s"] / out["window_s"]),
                         abs=1e-9)
    # The steps' gaps inside the recv beside those outside one.
    assert out["idle_under"]["chipbench:local_steps"] == {
        "chipbench:local_steps": [2, 0.002], "fed:wire:recv": [7, 0.008]}


PROGRAM = {
    "rounds": 8,
    "phases": {"fed:wire:encode": {"count": 16, "seconds": 3.2, "max_s": 0.3},
               "fed:serve:admit": {"count": 9, "seconds": 0.5, "max_s": 0.1},
               "fed:serve:build": {"count": 9, "seconds": 0.25, "max_s": 0.1},
               "fed:serve:dispatch": {"count": 9, "seconds": 1.0, "max_s": 0.1},
               "fed:serve:emit": {"count": 9, "seconds": 0.125, "max_s": 0.1},
               "fed:serve:prefill_chunk": {"count": 9, "seconds": 0.125,
                                           "max_s": 0.1},
               "fed:serve:fetch": {"count": 9, "seconds": 30.0, "max_s": 0.1},
               "fed:serve:idle": {"count": 9, "seconds": 9.0, "max_s": 3.0}},
    "spans": [{"kind": "recv", "nbytes": 2_000_000_000, "duration_s": 2.0,
               "timed": True},
              {"kind": "recv", "nbytes": 1_500_000_000, "duration_s": 2.0,
               "timed": True},
              {"kind": "recv", "nbytes": 3_000_000_000, "duration_s": 2.0,
               "timed": True},
              {"kind": "recv", "nbytes": 4_000_000, "duration_s": 0.0,
               "timed": False},
              {"kind": "send", "nbytes": 2_000_000_000, "duration_s": 0.5,
               "timed": True}],
    "stats": {"steps": 1000, "steps_ahead": 994, "kv_blocks_attended": 300,
              "kv_blocks_slab": 1200},
}
NOTHING = {"rounds": 8, "phases": {}, "spans": [],
           "stats": {"steps": 10, "steps_ahead": 0, "kv_blocks_attended": 0,
                     "kv_blocks_slab": 40}}
# reader -> (on PROGRAM, on NOTHING: a number still, 0.0)
RECORD_READERS = {
    "wire_recv_gbps": 1.0, "wire_encode_ms": 400.0,
    "steps_ahead_share": 99.4, "steps_ahead_share.chat": 99.4,
    "kv_blocks_share": 25.0, "kv_blocks_share.chat": 25.0,
    "host_iter_ms": 2.0, "host_iter_ms.chat": 2.0,
}


@pytest.mark.parametrize("name", sorted(RECORD_READERS))
def test_a_reader_of_the_programs_record_on_made_facts(name):
    """A number where the record holds what it reads, 0.0 where the window
    held none of it, None only without the record (an untraced run)."""
    read = load_reader(name)
    assert read({"program": PROGRAM}) == pytest.approx(RECORD_READERS[name])
    assert read({"program": NOTHING}) == 0.0
    assert read({"program": None}) is None and read({}) is None


@pytest.mark.parametrize("name", ROUND)
def test_a_rounds_reader_without_a_trace_or_a_span(name):
    read = load_reader(name)
    assert read({}) is None and read({"trace": None}) is None
    out = trace_reduce.reduce([dev(ONE_GAP), window(0, 40 * MS), host(
        [["chipbench:wait_aggregate", 0, 40 * MS]])])
    assert read({"trace": out}) is None          # a program without spans
    out = trace_reduce.reduce([dev(ONE_GAP), window(0, 40 * MS), host(
        [["fed:serve:idle", 0, 40 * MS]])])
    assert read({"trace": out}) == (50.0 if name.endswith("wait") else 0.0)


def test_every_metric_of_the_benchmark_has_its_reader_and_its_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    judged = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert callable(load_reader(m["name"]))
        assert set(m["workloads"]) <= cells
        # Every cell a metric lists reports the end-to-end metric it moves.
        assert set(m["workloads"]) <= set(judged[m["moves"]] or cells)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["push_place_ms"]["source"] == "host_clock"
    assert len(by_name) == len(bench["per_layer"]) == 42


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


@pytest.mark.parametrize("admit_from_ms", [
    # llm7b-chat-steady, seed 3500000102 on the chip: the engine waited for
    # a request when the profiler started, so its fed:serve:idle is not in
    # the profile, and the admission that ended the lull brushes 2 ms of it.
    3098,
    # An event of the profile that covers most of the gap: its part.
    1000,
])
def test_a_lull_cut_by_the_windows_edge_is_not_named_by_what_ends_it(
        admit_from_ms):
    first = 3100 * MS
    out = trace_reduce.reduce([
        dev([["fusion.1", first, 900 * MS]]), window(0, 4000 * MS),
        host([["fed:serve:admit", admit_from_ms * MS,
               first + 20 * MS - admit_from_ms * MS]])])
    assert out["idle_by_cause"] == {
        trace_reduce.EDGE: admit_from_ms / 1e3,
        "fed:serve:admit": (3100 - admit_from_ms) / 1e3}


def test_the_recorded_trace_reduces_as_before_but_for_the_split():
    """No chipbench:traced span in it: the window is the device's span, and
    busy_s and kernels are the values of reduce at commit 68a9e21 (before
    PR 35), written here. The gaps' sum is that commit's too; their split
    is PR 38's: ``chipbench:local_steps`` (the shorter, so the innermost)
    takes the pieces it covers of gaps that went whole to
    ``chipbench:wait_aggregate`` (0.006312687 and 0.001274945 then). The
    recording holds no span of the program and no scope."""
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
    assert out["idle_gaps"] == [["chipbench:local_steps", 0.007263599],
                                ["chipbench:wait_aggregate", 0.000324033]]
    assert 7263599 + 324033 == 6312687 + 1274945
    assert out["device_ops"][0] == ["(no scope):flash_fwd.16", 0.009621721]
    assert list(out["device_by_scope"]) == ["(no scope)"]
    assert out["program_spans"] is False
    # chipbench:wait_push began after the recorded window's end.
    assert sorted(out["host_spans"]) == [
        "chipbench:local_steps", "chipbench:wait_aggregate"]
    assert round(1e9 * (out["busy_s"] + out["idle_small_s"] + sum(
        out["idle_by_cause"].values()))) == round(1e9 * out["window_s"])
    assert out["busy_s"] + out["idle_small_s"] + sum(
        out["idle_by_cause"].values()) == pytest.approx(out["window_s"],
                                                        abs=1e-12)
