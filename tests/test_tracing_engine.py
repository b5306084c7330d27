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

"""What a federated round waited for, measured inside the program
(docs/observability.md): the task engine's time in the queue, the
straggler's lag at the reducer, ``fed.get``'s lag, the writer's ``write``
span, all off by default; and the benchmark's four readers of them."""

from __future__ import annotations

import os
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import rayfed_tpu as fed  # noqa: E402
from rayfed_tpu import tracing  # noqa: E402
from rayfed_tpu._private import executor  # noqa: E402
from rayfed_tpu.proxy.tcp import reactor  # noqa: E402
from tests.utils import FAST_COMM_CONFIG, get_addresses, run_parties  # noqa: E402

DELAY = 0.1


@pytest.fixture
def traced():
    tracing.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.clear()


def _late(delay=DELAY, value=3):
    """A future that somebody else resolves ``delay`` seconds from now."""
    dep = Future()
    threading.Timer(delay, dep.set_result, (value,)).start()
    return dep


# ---------------------------------------------------------------------------
# The primitive


def test_observe_feeds_the_accumulator_a_phase_feeds(traced):
    tracing.observe("fed:x:lag", 0.25)
    tracing.observe("fed:x:lag", 0.5)
    with tracing.phase("fed:x:lag"):
        pass
    got = tracing.phase_summary()["fed:x:lag"]
    assert got["count"] == 3 and got["max_s"] == 0.5
    assert 0.75 <= got["seconds"] < 0.76
    assert set(got) == {"count", "seconds", "max_s"}
    assert tracing.get_spans() == []          # an accumulator, not the ring
    tracing.clear()
    assert tracing.phase_summary() == {}
    tracing.disable()
    tracing.observe("fed:x:lag", 1.0)
    assert tracing.phase_summary() == {}


def test_write_is_a_timed_kind_and_task_is_not(traced):
    t0 = time.perf_counter() - 0.5
    tracing.record("write", "bob", "1", "2", 1 << 30, t0)
    tracing.record("task", "", "", "", 1 << 30, t0)
    summary = tracing.summary()
    assert 1.5 < summary["write"]["gbps"] <= 2.0
    assert "gbps" not in summary["task"]


# ---------------------------------------------------------------------------
# (a) The task engine: lane, eager inline, pool, stolen


@pytest.mark.parametrize("path", ["lane", "eager", "pool", "stolen"])
def test_a_task_through_each_submit_path_leaves_its_time_in_the_queue(path):
    ex = executor.LocalExecutor(max_workers=1)
    tracing.clear()
    blocker = gate = None
    if path == "stolen":
        # The pool's one worker is held, untraced, so that only a thief
        # can start the task.
        gate = threading.Event()
        blocker = ex.submit(gate.wait, (5,), eager=False)
    tracing.enable()
    try:
        def body(x):
            time.sleep(0.02)
            return x + 1

        if path == "lane":
            fut = ex.submit(body, (_late(),), lane=ex.new_lane())
        elif path == "eager":
            fut = ex.submit(body, (3,))
            assert fut.done()               # ran inside submit
        elif path == "pool":
            fut = ex.submit(body, (_late(),))
        else:
            fut = ex.submit(body, (_late(),), eager=False)
            time.sleep(0.03)                # it sits in the queue
        assert executor.result_stealing(fut, 5) == 4
        phases = tracing.phase_summary()
    finally:
        tracing.disable()
        if gate is not None:
            gate.set()
            blocker.result(5)
        ex.shutdown()
        tracing.clear()
    # The one accumulator of the engine: what the task waited for inside
    # (its arguments, its body) is the caller's to measure where it matters
    # (fed:agg:straggle, fed:get:lag).
    assert list(phases) == ["fed:task:queued"]
    queued = phases["fed:task:queued"]
    assert queued["count"] == 1
    if path == "eager":
        assert queued["seconds"] == 0.0
    else:
        # It started before its late dependency was resolved; the stolen
        # one only when the thief came for it.
        assert (0.03 if path == "stolen" else 0.0) <= queued["seconds"]
        assert queued["seconds"] < DELAY - 0.01
    # Whoever resolved the future stamped it, before it set the result.
    assert tracing.done_stamp(fut) is not None


def test_a_task_body_reads_its_arguments_stamps_and_an_inner_task_keeps_them(
        traced):
    ex = executor.LocalExecutor(max_workers=2)
    seen = {}

    def inner(x):
        seen["inner"] = tracing.task_arg_stamps()
        return x

    def outer(a, nested, plain):
        seen["before"] = tracing.task_arg_stamps()
        ex.submit(inner, (1,)).result()        # eager: runs inside this body
        seen["after"] = tracing.task_arg_stamps()
        return a + nested["b"] + plain

    try:
        first = ex.submit(lambda: 1)
        second = ex.submit(lambda: 2)
        assert ex.submit(outer, (first, {"b": second}, 4)).result(5) == 7
    finally:
        ex.shutdown()
    assert seen["before"] == [tracing.done_stamp(first),
                              tracing.done_stamp(second)]
    # (when, arrived): both were made by tasks of this party.
    assert [came for _, came in seen["before"]] == [False, False]
    assert seen["inner"] == [] and seen["after"] == seen["before"]
    assert tracing.task_arg_stamps() == []      # outside a task


# ---------------------------------------------------------------------------
# (b), (c) The straggler's lag at the root; fed.get's lag at the driver

HOLD = 0.15
CONFIG = {"cross_silo_comm": dict(FAST_COMM_CONFIG)}


@fed.remote
def _contribution(v):
    return {"w": np.full((8,), v, np.float32)}


@fed.remote
def _held_back(first, v):
    # Starts when the other party's contribution has reached this one, and
    # holds its own back: the root sees it HOLD (and a wire) later.
    time.sleep(HOLD)
    return {"w": np.full((8,), v, np.float32)}


def _run_straggle_and_lag(party, addresses):
    from rayfed_tpu.federated import fed_aggregate

    fed.init(addresses=addresses, party=party, config=CONFIG)
    # A first round, untraced: connections up, both drivers in step.
    fed.get(fed_aggregate({p: _contribution.party(p).remote(1.0)
                           for p in ("alice", "bob")}))
    tracing.clear()
    tracing.enable()
    mine = _contribution.party("alice").remote(1.0)
    objs = {"alice": mine,
            "bob": _held_back.party("bob").remote(mine, 3.0)}
    agg = fed_aggregate(objs, op="mean")
    t0 = time.perf_counter()
    out = fed.get(agg)
    blocked = time.perf_counter() - t0
    np.testing.assert_allclose(np.asarray(out["w"]), np.full(8, 2.0))
    phases = tracing.phase_summary()
    lag = phases["fed:get:lag"]
    # (c) the value was not ready when get was called: one observation,
    # no longer than get was blocked.
    assert lag["count"] == 1 and 0.0 <= lag["seconds"] <= blocked
    fed.get(agg)                                 # ready now: not observed
    assert tracing.phase_summary()["fed:get:lag"]["count"] == 1
    if party == "alice":
        # (b) the root's reducer saw bob's tree HOLD after its own; the
        # scale has one future argument and observes nothing.
        straggle = phases["fed:agg:straggle"]
        assert straggle["count"] == 1
        assert HOLD <= straggle["seconds"] < HOLD + 1.0
        assert phases["fed:agg:reduce"]["count"] == 2
        assert phases["fed:task:queued"]["count"] >= 3
    else:
        assert "fed:agg:straggle" not in phases
        assert "fed:agg:reduce" not in phases
    tracing.disable()
    fed.shutdown()


def test_two_party_aggregate_leaves_the_stragglers_lag_at_the_root_only():
    run_parties(_run_straggle_and_lag, ["alice", "bob"])


OWN, CAME = False, True


@pytest.mark.parametrize("stamps, want", [
    # The reducer at the lead: its own tree, then the peer's off the wire.
    ([(5.0, OWN), (5.4, CAME)], 0.4),
    # The peer's tree was there before the lead's steps ended: the round
    # did not wait for the wire, and a faster wire cannot raise the number.
    ([(5.4, OWN), (5.0, CAME)], 0.0),
    # The last arrival against the last of the party's own.
    ([(5.0, OWN), (5.1, OWN), (5.05, CAME), (5.3, CAME)], 0.2),
    # An inner node that only sums its children's: from the first arrival.
    ([(5.0, CAME), (5.4, CAME), (5.1, CAME)], 0.4),
    # Nothing arrived, or fewer than two (the scale), or none at all.
    ([(5.0, OWN), (5.4, OWN)], None),
    ([(5.0, CAME)], None),
    ([], None),
])
def test_straggle_is_the_last_arrival_less_the_last_of_the_partys_own(
        stamps, want, traced):
    from rayfed_tpu import federated

    outer = tracing.swap_task_arg_stamps(stamps)
    try:
        federated._observe_straggle()
    finally:
        tracing.swap_task_arg_stamps(outer)
    got = tracing.phase_summary().get("fed:agg:straggle")
    if want is None:
        assert got is None
    else:
        assert got["count"] == 1 and got["seconds"] == pytest.approx(want)


# ---------------------------------------------------------------------------
# (d) The writer's span: a frame of 1 MiB leaves one, a frame of 1 KiB none

needs_reactor = pytest.mark.skipif(
    not reactor.available(), reason="epoll not available on this platform"
)
FAST = {"retry_policy": {"max_attempts": 8, "initial_backoff_ms": 100}}
BIG = np.arange(1 << 18, dtype=np.float32)             # 1 MiB payload
SMALL = np.arange(1 << 8, dtype=np.float32)            # 1 KiB


def _push_both(config, tls=None):
    from rayfed_tpu.proxy.tcp.tcp_proxy import (TcpReceiverProxy,
                                                TcpSenderProxy)

    addr = get_addresses(["bob"])
    rp = TcpReceiverProxy(addr["bob"], "bob", "job", tls and tls["bob"],
                          dict(config))
    rp.start()
    ok, err = rp.is_ready()
    assert ok, err
    sp = TcpSenderProxy(addr, "alice", "job", tls and tls["alice"],
                        dict(config))
    sp.start()
    try:
        for seq, value in ((1, BIG), (2, SMALL)):
            fut = rp.get_data("alice", f"{seq}#0", seq)
            assert sp.send("bob", value, f"{seq}#0", seq).result(timeout=60)
            np.testing.assert_array_equal(fut.result(timeout=60), value)
    finally:
        sp.stop()
        rp.stop()


@pytest.mark.parametrize("engine", [
    pytest.param("reactor", marks=needs_reactor), "pipelined", "half_duplex"])
def test_a_large_frame_leaves_one_write_span_and_a_small_one_none(
        engine, traced, tmp_path):
    tls = None
    if engine == "half_duplex":              # the TLS path of tcp_proxy
        from tools.generate_tls_certs import generate, tls_config_for

        generate(str(tmp_path), ["alice", "bob"])
        tls = {p: tls_config_for(str(tmp_path), p) for p in ("alice", "bob")}
    _push_both(dict(FAST, use_reactor=engine == "reactor"), tls)
    (write,) = tracing.get_spans("write")
    assert (write.peer, write.upstream_seq_id, write.downstream_seq_id) == (
        "bob", "1#0", "1")
    assert write.nbytes >= BIG.nbytes and write.ok and write.extra == {}
    # The mirror of the receiver's timed recv of the same frame: same seq
    # ids, same bytes; the send span of the frame encloses the write.
    recv = {s.upstream_seq_id: s for s in tracing.get_spans("recv")}["1#0"]
    assert recv.nbytes == write.nbytes
    # (only the reactor's reader times a frame's arrival)
    assert recv.extra == ({"timed": True} if engine == "reactor" else {})
    send = {s.upstream_seq_id: s for s in tracing.get_spans("send")}
    assert sorted(send) == ["1#0", "2#0"]
    assert 0 < write.duration_s <= send["1#0"].duration_s
    assert send["1#0"].start_s <= write.start_s
    assert tracing.summary()["write"]["gbps"] > 0


@needs_reactor
def test_a_write_mark_follows_its_frame_through_partial_flushes(traced):
    # The ring is one byte stream: a small frame ahead of two large ones, a
    # flush that ends inside the first, one that ends it and carries the
    # second's first bytes, one that ends that.
    lane = reactor.ReactorLane.__new__(reactor.ReactorLane)
    lane._marks, lane._woff, lane._dest = reactor.deque(), 0, "bob"
    lane._t_flush = 0.0
    lane._outbox = reactor.deque([memoryview(b"x" * 100)])
    lane._lock, lane._inline_busy = threading.Lock(), False
    size = 50 + (1 << 20)
    for up in ("7", "9"):
        job = reactor._Inflight(Future(), {"up": up, "down": "8"}, None, 1,
                                nbytes=1 << 20)
        chunks = [memoryview(b"h" * 50), memoryview(bytes(1 << 20))]
        with lane._lock:
            lane._mark_write(job, chunks)
        lane._outbox.extend(chunks)
    first, second = lane._marks
    assert (first.start, first.end) == (100, 100 + size)
    assert (second.start, second.end) == (100 + size, 100 + 2 * size)

    def flush(nbytes):
        lane.pending_chunks()               # the reactor, before a writev
        issued = lane._t_flush
        time.sleep(0.002)
        with lane._lock:
            lane._advance_marks(nbytes)     # on_flushed, after it
        return issued

    flush(100)
    assert first.t0 is None                 # the small frame went alone
    began = flush(1 << 19)
    assert first.t0 == began and tracing.get_spans("write") == []
    carried = flush(size - (1 << 19) + 10)
    # The second frame's first bytes left in the writev that ended the
    # first: its span starts when that writev was issued, not after it.
    assert second.t0 == carried and list(lane._marks) == [second]
    flush(size - 10)
    assert not lane._marks
    one, two = tracing.get_spans("write")
    assert [(w.upstream_seq_id, w.downstream_seq_id, w.nbytes, w.ok)
            for w in (one, two)] == [("7", "8", 1 << 20, True),
                                     ("9", "8", 1 << 20, True)]
    assert (one.start_s, two.start_s) == (began, carried)
    assert one.duration_s >= 0.004 and two.duration_s >= 0.004
    # Off, a large frame is not marked at all.
    tracing.disable()
    assert tracing.write_t0(1 << 20) is None
    tracing.enable()
    assert tracing.write_t0(1 << 20) is not None
    assert tracing.write_t0((1 << 20) - 1) is None


# ---------------------------------------------------------------------------
# (e) Off: nothing of it


def test_off_no_name_no_stamp_no_span():
    tracing.disable()
    tracing.clear()
    ex = executor.LocalExecutor(max_workers=2)
    seen = []
    try:
        futs = [ex.submit(lambda x: seen.append(tracing.task_arg_stamps())
                          or x, (_late(0.02),)),
                ex.submit(lambda: 1),
                ex.submit(lambda x: x, (2,), lane=ex.new_lane())]
        assert [f.result(5) for f in futs] == [3, 1, 2]
    finally:
        ex.shutdown()
    if reactor.available():
        _push_both(FAST)
    assert seen == [[]]
    assert all(tracing.done_stamp(f) is None for f in futs)
    assert not any(hasattr(f, "_fedtpu_done_t") for f in futs)
    assert tracing.phase_summary() == {} and tracing.get_spans() == []
    assert getattr(tracing._task, "arg_stamps", None) is None


# ---------------------------------------------------------------------------
# (f) The benchmark's four readers, loaded by path as the benchmark does

PROGRAM = {
    "rounds": 4,
    "phases": {
        "fed:agg:straggle": {"count": 4, "seconds": 2.0, "max_s": 0.7},
        "fed:get:lag": {"count": 4, "seconds": 1.0, "max_s": 0.4},
        "fed:task:queued": {"count": 40, "seconds": 0.1, "max_s": 0.06},
    },
    "spans": [
        {"kind": "write", "nbytes": 2_000_000_000, "duration_s": 2.0,
         "timed": True},
        {"kind": "write", "nbytes": 2_000_000_000, "duration_s": 1.0,
         "timed": True},
        {"kind": "write", "nbytes": 2_000_000_000, "duration_s": 4.0,
         "timed": True},
        {"kind": "recv", "nbytes": 2_000_000_000, "duration_s": 0.1,
         "timed": True},
        {"kind": "send", "nbytes": 2_000_000_000, "duration_s": 0.1,
         "timed": True},
    ],
}
READER_FACTS = {
    "recorded": {"kind": "fedround", "program": PROGRAM},
    "never_recorded": {"kind": "fedround", "program": {
        "rounds": 4, "phases": {"fed:wire:encode": {
            "count": 4, "seconds": 2.0, "max_s": 0.6}}, "spans": []}},
    "untraced": {"kind": "fedround", "program": None},
}
# By hand: 2.0 s / 4 rounds; 1.0 s / 4 rounds; 0.1 s / 40 tasks (not the
# 60 ms maximum); the median of 1, 2 and 0.5 GB/s.
READER_WANT = {"agg_straggle_ms": 500.0, "get_lag_ms": 250.0,
               "task_queued_ms.mean": 2.5, "wire_write_gbps": 1.0}


@pytest.mark.parametrize("facts", sorted(READER_FACTS))
@pytest.mark.parametrize("name", sorted(READER_WANT))
def test_round_readers_on_made_facts(name, facts):
    from chipbench.run import load_reader

    got = load_reader(name)(READER_FACTS[facts])
    if facts == "recorded":
        assert got == pytest.approx(READER_WANT[name])
    elif facts == "never_recorded":
        assert got == 0.0
    else:
        assert got is None


def test_the_four_readers_are_in_the_benchmark_for_the_fedround_cell():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READER_WANT:
        entry = per_layer[name]
        assert entry["workloads"] == ["coder1b-fedround-k16"]
        assert entry["moves"] == "round_tokens_per_s"
        assert entry["source"] == "program_span"
