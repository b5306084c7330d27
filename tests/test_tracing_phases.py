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

"""Program spans on the profiler's clock (docs/observability.md).

What is pinned here: ``tracing.phase`` accumulates per name and stays out
of the span ring; annotation names are fixed strings whatever the ids;
the serving loop's phases tile an iteration and leave its tokens alone; an
arriving tree yields deserialize / place / a timed recv; the benchmark's
``idle_share.*`` readers and the reducer's naming rule they rest on.
"""

from __future__ import annotations

import glob
import os
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rayfed_tpu import tracing  # noqa: E402
from rayfed_tpu.config import ServingConfig  # noqa: E402
from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.proxy.tcp import reactor  # noqa: E402
from rayfed_tpu.serving.server import InferenceServer  # noqa: E402
from tests.utils import get_addresses  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CFG = tfm.tiny_config(compute_dtype=jnp.float32)
PARAMS = tfm.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture
def traced():
    tracing.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.clear()


# ---------------------------------------------------------------------------
# The primitive


def test_phase_off_leaves_no_accumulator_and_no_ring_entry():
    tracing.clear()
    assert not tracing.is_enabled()
    with tracing.phase("fed:test:off", nbytes=3) as p:
        # No profiler session either: no annotation object is built.
        assert p._ann is None
    assert tracing.phase_summary() == {}
    assert tracing.get_spans() == []


def test_phase_on_accumulates_and_leaves_the_ring_alone(traced):
    before = tracing.last_span_index()
    for pause in (0.0, 0.02, 0.0):
        with tracing.phase("fed:test:tick", peer="bob"):
            time.sleep(pause)
    with tracing.phase("fed:test:other"):
        pass
    got = tracing.phase_summary()
    assert set(got) == {"fed:test:tick", "fed:test:other"}
    tick = got["fed:test:tick"]
    assert tick["count"] == 3
    assert 0.02 <= tick["max_s"] <= tick["seconds"] < 1.0
    assert got["fed:test:other"]["count"] == 1
    # Not a span: the 10,000-span ring and the telemetry agent's harvest
    # never see a phase.
    assert tracing.get_spans() == []
    assert tracing.spans_since(before) == []
    assert tracing.last_span_index() == before
    assert tracing.summary() == {}


def test_phase_records_when_the_body_raises(traced):
    with pytest.raises(KeyError):
        with tracing.phase("fed:test:raises"):
            raise KeyError("x")
    assert tracing.phase_summary()["fed:test:raises"]["count"] == 1


def test_clear_and_the_tenancy_reset_hook_drop_the_accumulators(traced):
    from rayfed_tpu.tenancy import reset

    with tracing.phase("fed:test:a"):
        pass
    tracing.clear()
    assert tracing.phase_summary() == {}
    with tracing.phase("fed:test:a"):
        pass
    reset._hook_tracing()
    assert tracing.phase_summary() == {}


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fed:"):
                    names.setdefault(e.name, []).append(dict(e.stats))
    return names


def test_annotation_names_are_fixed_whatever_the_ids(tmp_path):
    """The profiler session is the switch (tracing stays OFF here), and a
    host event's name comes back bare: peers, seq ids and byte counts are
    metadata, so a reduction by name sums one cause."""
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i, peer in enumerate(("bob", "carol", "dave")):
            with tracing.span("decode", peer, f"e{i}:{10 + i}", 20 + i,
                              nbytes=1000 * (i + 1)):
                with tracing.phase("fed:wire:place", nbytes=7 + i,
                                   peer=peer):
                    time.sleep(0.001)
        with tracing.phase("fed:serve:emit"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert set(names) == {"fed:wire:decode", "fed:wire:place",
                          "fed:serve:emit"}
    assert len(names["fed:wire:decode"]) == len(names["fed:wire:place"]) == 3
    assert sorted(m["peer"] for m in names["fed:wire:decode"]) == [
        "bob", "carol", "dave"]
    assert sorted(m["nbytes"] for m in names["fed:wire:place"]) == [7, 8, 9]
    # Tracing was off: the session alone made the annotations.
    assert tracing.phase_summary() == {} and tracing.get_spans() == []


def test_span_goes_to_the_ring_as_before(traced):
    with tracing.span("decode", "bob", "1", "2", nbytes=5) as s:
        s.set_nbytes(9)
    (got,) = tracing.get_spans("decode")
    assert (got.peer, got.upstream_seq_id, got.nbytes, got.ok) == (
        "bob", "1", 9, True)
    assert tracing.spans_since(got.idx - 1) == [got]
    assert tracing.phase_summary() == {}


# ---------------------------------------------------------------------------
# The serving loop

PROMPTS = [[1, 2, 3, 4], [5, 6, 7, 8, 9, 10, 11], [3, 1, 4, 1, 5, 9, 2, 6, 5],
           [2, 7]]
PER_STEP = ("dispatch", "fetch", "emit")


def _serve_mixed():
    """A mixed greedy / sampled batch; a 9-token prompt over a 4-token
    chunk makes the engine run chunked prefill as well."""
    scfg = ServingConfig(max_slots=4, max_len=32, max_new_tokens=6,
                         prefill_chunk=4, prefill_token_budget=8)
    srv = InferenceServer(CFG, scfg, params=PARAMS)
    try:
        futs = [srv.submit(p, temperature=0.0 if i % 2 else 0.8, seed=40 + i)
                for i, p in enumerate(PROMPTS)]
        out = [f.result(timeout=120)["tokens"] for f in futs]
        time.sleep(0.08)  # the engine falls idle: its wait is a phase too
        return out, srv.stats()
    finally:
        srv.stop()


def test_engine_phases_tile_the_iteration_and_keep_the_tokens():
    tracing.clear()
    plain, _ = _serve_mixed()
    assert tracing.phase_summary() == {}
    tracing.enable()
    try:
        again, stats = _serve_mixed()
        phases = tracing.phase_summary()
    finally:
        tracing.disable()
        tracing.clear()
    assert again == plain
    assert stats["steps"] > 0
    # The token is chosen inside the step's program: no host phase samples.
    assert "fed:serve:sample" not in phases
    # Every step is dispatched once and, an iteration later, fetched and
    # emitted once; an iteration that only drains the step in flight (its
    # rows' ends known ahead) builds and dispatches nothing.
    for name in PER_STEP:
        assert phases["fed:serve:" + name]["count"] == stats["steps"], name
    assert phases["fed:serve:build"]["count"] > stats["steps"]
    assert 0 < stats["steps_ahead"] < stats["steps"]
    for name in ("admit", "prefill_chunk", "idle"):
        assert phases["fed:serve:" + name]["count"] >= 1, name
    assert not [n for n in phases if not n.startswith("fed:serve:")]
    assert stats["prefill_chunks"] >= 2


# ---------------------------------------------------------------------------
# Arrival and placement, encode, the mean

needs_reactor = pytest.mark.skipif(
    not reactor.available(), reason="epoll not available on this platform"
)
FAST = {"retry_policy": {"max_attempts": 8, "initial_backoff_ms": 100}}


@needs_reactor
def test_loopback_push_yields_deserialize_place_and_a_timed_recv(
        monkeypatch, traced):
    from jax.sharding import Mesh

    from rayfed_tpu import mesh as mesh_mod
    from rayfed_tpu.proxy.tpu.tpu_proxy import (TpuReceiverProxy,
                                                TpuSenderProxy)

    monkeypatch.setattr(mesh_mod, "_party_mesh",
                        Mesh(np.array(jax.devices()[:2]), ("data",)))
    addr = get_addresses(["bob"])
    rp = TpuReceiverProxy(addr["bob"], "bob", "job", None, dict(FAST))
    rp.start()
    ok, err = rp.is_ready()
    assert ok, err
    sp = TpuSenderProxy(addr, "alice", "job", None, dict(FAST))
    sp.start()
    big = {"w": np.arange(1 << 19, dtype=np.float32)}      # 2 MiB payload
    small = {"w": np.arange(16, dtype=np.float32)}
    try:
        for seq, tree in ((1, big), (2, small)):
            fut = rp.get_data("alice", f"{seq}#0", seq)
            assert sp.send("bob", tree, f"{seq}#0", seq).result(timeout=60)
            got = fut.result(timeout=60)
            assert isinstance(got["w"], jax.Array)
            np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])
    finally:
        sp.stop()
        rp.stop()
    phases = tracing.phase_summary()
    assert phases["fed:wire:deserialize"]["count"] == 2
    assert phases["fed:wire:place"]["count"] == 2
    assert phases["fed:wire:recv"]["count"] == 1       # the 2 MiB frame only
    recvs = {s.upstream_seq_id: s for s in tracing.get_spans("recv")}
    assert recvs["1#0"].extra == {"timed": True}
    assert recvs["1#0"].duration_s >= phases["fed:wire:recv"]["seconds"] > 0
    assert recvs["2#0"].extra == {} and recvs["2#0"].duration_s < 1e-3
    # The decode span encloses its two children and stays in the ring.
    assert len(tracing.get_spans("decode")) == 2
    # The stamp never reaches a consumer of the header.
    assert tracing.RECV_T0_KEY not in recvs["1#0"].extra


def test_a_recv_stamp_from_the_wire_is_not_believed(traced):
    from rayfed_tpu.proxy import rendezvous

    store = rendezvous.RendezvousStore("job", lambda header, payload: payload)
    header = {"job": "job", "src": "mallory", "up": "7", "down": "8",
              tracing.RECV_T0_KEY: "not a clock"}
    code, _ = store.offer(header, memoryview(b"abc"))
    assert code == 200
    (recv,) = tracing.get_spans("recv")
    assert recv.extra == {} and recv.duration_s < 1e-3
    assert tracing.RECV_T0_KEY not in header


def test_a_timed_recv_has_its_duration_in_the_exports(tmp_path, traced):
    import json

    t0 = time.perf_counter() - 0.5
    tracing.record("recv", "bob", "1", "2", 1 << 21, t0, timed=True)
    tracing.record("recv", "bob", "3", "4", 10, time.perf_counter())
    tracing.export_seq_timeline(str(tmp_path / "seq.json"))
    edges = {e["up"]: e["events"][0]
             for e in json.load(open(tmp_path / "seq.json"))["edges"]}
    assert edges["1"]["dur_s"] >= 0.5 and edges["3"]["dur_s"] == 0.0
    tracing.export_chrome_trace(str(tmp_path / "chrome.json"))
    phs = sorted(e["ph"] for e in json.load(
        open(tmp_path / "chrome.json"))["traceEvents"])
    assert phs == ["X", "i"]


def test_encode_and_reduce_phases(traced):
    from rayfed_tpu import federated
    from rayfed_tpu.proxy import barriers

    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    staged = barriers._capture_for_send("bob", tree)
    assert isinstance(staged["w"], np.ndarray)
    total = federated._agg_kary_sum._func_body(tree, tree)
    mean = federated._scale._func_body(total, 2.0)
    np.testing.assert_array_equal(np.asarray(mean["w"]),
                                  np.asarray(tree["w"]))
    phases = tracing.phase_summary()
    assert phases["fed:wire:encode"]["count"] == 1
    assert phases["fed:agg:reduce"]["count"] == 2


def _scopes_of(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_named_scopes_are_metadata_on_the_lowered_programs():
    from rayfed_tpu.ops import aggregate
    from rayfed_tpu.serving.kv_pool import PagedKVPool

    tokens = jnp.zeros((2, 8), jnp.int32)
    text = _scopes_of(
        lambda p: jax.grad(lambda q: tfm.lm_loss_pair(q, tokens, tokens, CFG))(
            p), PARAMS)
    for scope in ("train/forward", "train/loss_head",
                  "transpose(jvp(train/forward))"):
        assert scope in text, scope
    tree = {"w": jnp.ones(4)}
    assert "aggregate/mean" in aggregate._tree_mean.lower(
        (tree, tree)).as_text(debug_info=True)
    assert "aggregate/sum" in aggregate._tree_sum.lower(
        (tree, tree)).as_text(debug_info=True)
    pool = PagedKVPool(CFG, max_slots=2, max_len=8, block_size=4)
    # The chunk program holds its own read and write of the pool: no
    # serve/gather or serve/scatter program stands around it.
    srv = InferenceServer(
        CFG, ServingConfig(max_slots=2, max_len=8, max_new_tokens=2,
                           kv_block_size=4), params=PARAMS)
    try:
        chunk_fn = srv._get_chunk_fn(4)
        text = chunk_fn.lower(
            PARAMS, srv.pool.kv, {},
            jnp.zeros((srv.pool.blocks_per_row,), jnp.int32), jnp.int32(0),
            jnp.zeros((4,), jnp.int32), jnp.int32(0), jnp.int32(4),
            jnp.zeros((3, 1), jnp.int32),
        ).as_text(debug_info=True)
    finally:
        srv.stop()
    assert "serve/chunk" in text
    assert "serve/gather" not in text and "serve/scatter" not in text
    assert chunk_fn.__name__ == "chunk_step"
    rows = jnp.zeros((2,), jnp.int32)
    assert "serve/decode_step" in pool._decode_step_fn.lower(
        PARAMS, pool.kv, rows, rows,
        jnp.zeros((2, pool.blocks_per_row), jnp.int32),
        jnp.zeros((3, 2), jnp.int32), rows, jnp.ones((2,), bool), {}, None,
    ).as_text(debug_info=True)
    # A decorator, not a wrapper program: the jitted functions keep the
    # names the profile and `compiled_programs` know them by.
    assert pool._scatter_rows_fn.__name__ == "scatter_rows"
    assert pool._decode_step_fn.__name__ == "decode_step"


# ---------------------------------------------------------------------------
# The benchmark's side: the idle_share.* readers and the reducer's rule


GAPS = [["fed:serve:sample", 0.40], ["np.asarray(jax.Array)", 0.10],
        ["fed:serve:build", 0.02], ["fed:serve:idle", 0.06],
        ["no host span", 0.02], ["fed:serve:fetch", 0.30],
        ["fed:serve:emit", 0.04], ["fed:wire:place", 0.08]]
FACTS = {
    "mixed": {"trace": {"window_s": 4.0, "idle_gaps": GAPS}},
    "runtime_only": {"trace": {"window_s": 4.0, "idle_gaps": [
        ["np.asarray(jax.Array)", 0.7], ["no host span", 0.1]]}},
    "empty": {"trace": {"window_s": 4.0, "idle_gaps": []}},
    "no_trace": {"kind": "open_loop", "trace": None},
}
# By hand: 0.40 / 4; (0.02 + 0.06 + 0.04) / 4; (0.10 + 0.02 + 0.30) / 4:
# the fetch is the wait for the device, not host work, and counts with the
# unnamed. A span of another layer is in none of the three.
WANT = {"idle_share.sample": 10.0, "idle_share.schedule": 3.0,
        "idle_share.unnamed": 10.5}


@pytest.mark.parametrize("facts", sorted(FACTS))
@pytest.mark.parametrize("name", sorted(WANT))
def test_idle_share_readers_on_synthetic_facts(name, facts):
    # Loaded by path from chipbench/layers/, as the benchmark does.
    from chipbench.run import load_reader

    got = load_reader(name)(FACTS[facts])
    if facts == "mixed":
        assert got == pytest.approx(WANT[name])
    else:
        # No trace, no gaps, or a program without spans (the parent
        # commit): nothing to read, and nothing raised.
        assert got is None


MS = 1_000_000


def _lines(host_events):
    # Device busy [0, 10] ms and [20, 30] ms: one 10 ms gap.
    return [
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": [
            ["fusion.1", 0, 10 * MS], ["fusion.2", 20 * MS, 10 * MS]]},
        {"plane": "/host:CPU", "line": "engine", "events": host_events},
    ]


TILED = [["fed:serve:fetch", 8 * MS, 3 * MS],
         ["np.asarray(jax.Array)", 8 * MS, 3 * MS - 1000],
         ["fed:serve:sample", 11 * MS, 6 * MS],
         ["fed:serve:emit", 17 * MS, 1 * MS],
         ["fed:serve:build", 18 * MS, 1 * MS],
         ["fed:serve:dispatch", 19 * MS, 2 * MS]]


@pytest.mark.parametrize("enclosed", [False, True])
def test_the_reducer_names_a_gap_by_tiled_phases_not_by_an_encloser(enclosed):
    """The rule the engine's spans rest on: a gap goes to the host event
    that overlaps it most, so phases that tile the loop name it by its
    largest piece, and one enclosing span would take every gap."""
    from chipbench import trace_reduce

    host = list(TILED)
    if enclosed:
        host.append(["fed:serve:iteration", 5 * MS, 20 * MS])
    out = trace_reduce.reduce(_lines(host), window_s=0.03)
    want = "fed:serve:iteration" if enclosed else "fed:serve:sample"
    assert out["idle_gaps"] == [[want, pytest.approx(0.010)]]
    assert out["busy_s"] == pytest.approx(0.020)


@pytest.mark.parametrize("asarray_starts_ms, want", [
    (9.001, "np.asarray(jax.Array)"),   # gap inside both: the shorter wins
    (10.5, "fed:serve:fetch"),          # gap opens before the inner TraceMe
])
def test_the_reducer_between_the_fetch_and_the_traceme_inside_it(
        asarray_starts_ms, want):
    """At equal overlap the shortest wins, so a gap wholly inside the
    runtime's np.asarray TraceMe is booked to it; a gap that opens before
    that TraceMe does (what the chip shows) goes to the fetch span."""
    from chipbench import trace_reduce

    start = int(asarray_starts_ms * MS)
    host = [["fed:serve:fetch", 9 * MS, 12 * MS],
            ["np.asarray(jax.Array)", start, 21 * MS - start - 1000]]
    out = trace_reduce.reduce(_lines(host), window_s=0.03)
    assert out["idle_gaps"] == [[want, pytest.approx(0.010)]]
