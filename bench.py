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

"""Headline benchmark: cross-party push throughput on 100MB tensors.

Prints ONE JSON line:
    {"metric": ..., "value": <GB/s native>, "unit": "GB/s",
     "vs_baseline": <native GB/s / reference-parity gRPC GB/s>}

The baseline is self-measured (the reference publishes no numbers —
BASELINE.md): the same two-party push workload over this repo's
``transport='grpc'`` lane, which reproduces the reference's wire behavior
(one unary RPC per object, payload cloudpickled inside the request,
ref ``fed/proxy/grpc/grpc_proxy.py:193-220``). The native lane is the
binary TCP protocol with the zero-pickle array fast path.

Workload (BASELINE.json config #2): 2 parties on localhost, alice pushes
N x 100MB float32 gradient tensors to bob via ``@fed.remote`` consumers;
bob measures arrival throughput.
"""

from __future__ import annotations

import contextlib
import json
import multiprocessing
import os
import socket
import sys
import tempfile
import time

# gRPC-core WARNING logs (retry_service_config.cc's maxAttempts clamp
# note among them) come from channels jaxlib and the parties create
# internally. Set at MODULE level so it covers the driver AND every
# spawned child — spawn re-imports this module, and subprocesses
# inherit the driver's env — not just the _party_entry trampoline
# (BENCH_r05's tails still carried the clamp spam from the psum/serve/
# MFU children, which bypass _party_entry).
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")

PAYLOAD_MB = 100
ROUNDS = 5
REPS = 8  # best-of-N inside one job (single-core hosts are noisy)
# The paired-ceiling stage records more pairs: its headline is a MEDIAN
# ratio, and hypervisor steal bursts (one per ~30s observed) each poison
# a pair — 12 pairs keep the median in the steady-state regime.
PAIRED_REPS = 12

_FAST_RETRY = {
    "retry_policy": {
        "max_attempts": 20,
        "initial_backoff_ms": 200,
        "max_backoff_ms": 2000,
        "backoff_multiplier": 1.5,
    }
}

# Set by _run_two_party in the parent; spawned parties overwrite their
# mark file at each phase boundary so a hang is diagnosable (a party
# terminated by the timeout can't report anything itself — BENCH_r05
# recorded exactly such an undiagnosable "bench party hung").
_PROGRESS_DIR_VAR = "FEDTPU_BENCH_PROGRESS_DIR"


def _progress(party: str, phase: str) -> None:
    d = os.environ.get(_PROGRESS_DIR_VAR)
    if not d:
        return
    try:
        with open(os.path.join(d, f"{party}.progress"), "w") as f:
            f.write(phase)
    except OSError:
        pass  # diagnostics must never fail the measurement


def _party_entry(target, party, *rest):
    """Spawn trampoline: arm a SIGUSR1 all-thread stack dump into the
    progress dir before the party body runs, so the parent's watchdog
    can capture WHERE a hung party is stuck — not just the last phase
    mark (BENCH_r05's "bench party hung" had no stack to go on)."""
    # gRPC-core WARNING logs (retry_service_config.cc's maxAttempts clamp
    # note among them) come from channels jaxlib creates internally, not
    # from this repo's pre-clamped config — silence them below ERROR so
    # bench stderr stays parseable (see test_grpc_channel_options).
    os.environ.setdefault("GRPC_VERBOSITY", "ERROR")
    d = os.environ.get(_PROGRESS_DIR_VAR)
    if d:
        try:
            import faulthandler
            import signal

            from rayfed_tpu import tracing

            # Span ring on: the hang artifact below needs per-seq-id
            # send/recv/ack events to reconstruct which edge wedged.
            tracing.enable()

            def _dump_timeline(signum, frame):
                # Python-level chained handler: best-effort (only runs
                # when the main thread re-enters the interpreter loop);
                # the C-level faulthandler stacks below always land.
                try:
                    # Feed to tools/trace_view.py for a per-seq-id text
                    # flamegraph of the wedge.
                    tracing.export_seq_timeline(
                        os.path.join(d, f"{party}.seq.json"), party
                    )
                except OSError:
                    pass

            signal.signal(signal.SIGUSR1, _dump_timeline)
            # Keep the file object referenced: faulthandler holds only
            # the fd, and a collected file object would close it.
            _party_entry._stacks_file = open(
                os.path.join(d, f"{party}.stacks"), "w"
            )
            # chain=True: the C handler dumps all-thread stacks first,
            # then invokes the timeline handler installed above.
            faulthandler.register(
                signal.SIGUSR1, file=_party_entry._stacks_file,
                all_threads=True, chain=True,
            )
        except (OSError, ValueError, AttributeError):
            pass  # diagnostics must never fail the measurement
    target(party, *rest)


def _party_main(party, addresses, transport, result_path, device_dma=False,
                pair_ceiling=False, num_streams=0, sharded=False,
                shm=False):
    import numpy as np

    import rayfed_tpu as fed

    comm = dict(_FAST_RETRY)
    if os.environ.get("FEDTPU_BENCH_WINDOW"):
        comm["send_window"] = int(os.environ["FEDTPU_BENCH_WINDOW"])
    if device_dma:
        comm["device_dma"] = True
    if num_streams:
        comm["num_streams"] = num_streams
    if shm:
        # Same-host zero-copy lane: payload bytes ride a /dev/shm ring,
        # only descriptor frames cross the socket (proxy/lanes.py).
        # Ring = in-flight payload budget: adoption is zero-copy, so all
        # ROUNDS pipelined tensors pin their chunks until the driver's
        # FedObjects die at the end of the rep — and with SPMD skew the
        # receiver still holds rep N's tensors while rep N+1's burst is
        # pushing, so the ring must cover TWO reps or pushes wait out
        # shm_push_timeout_ms and fall back to the socket mid-rep.
        comm["shm_enabled"] = True
        comm["shm_ring_mb"] = 2 * ROUNDS * PAYLOAD_MB + 64
    fed.init(
        addresses=addresses,
        party=party,
        config={"cross_silo_comm": comm, "transport": transport},
        job_name=f"bench-{transport}",
        logging_level="error",
    )

    n_elem = PAYLOAD_MB * 1024 * 1024 // 4

    if device_dma:
        # Device-resident payloads: the DMA lane parks live jax buffers
        # on the transfer server and ships only a descriptor over the
        # socket; the receiver pulls through the transfer engine's bulk
        # transport (ICI/DCN on a pod, its socket transport in CPU sim).
        import jax.numpy as jnp

        @fed.remote
        def produce(i):
            import jax

            return jax.block_until_ready(
                jnp.full((n_elem,), float(i), dtype=jnp.float32)
            )
    elif sharded:
        # Sharded-pipeline lane: a 4-way sharded jax.Array (spawned under
        # a forced multi-device CPU backend). The encode worker overlaps
        # per-shard D2H and the stripe planner splits at shard extents,
        # so the payload rides K lanes as parallel stripe frames.
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        nshards = min(4, len(jax.devices()))
        sh = NamedSharding(
            Mesh(np.array(jax.devices()[:nshards]), ("data",)),
            PartitionSpec("data"),
        )

        @fed.remote
        def produce(i):
            import jax

            return jax.block_until_ready(
                jax.device_put(
                    jnp.full((n_elem,), float(i), dtype=jnp.float32), sh
                )
            )
    elif num_streams:
        # Multi-leaf pytree: stripes split only at buffer (leaf/shard)
        # boundaries, so one dense tensor cannot engage striping; 16
        # chunks give the planner balanced extents for any lane count.
        chunks = 16
        per = n_elem // chunks

        @fed.remote
        def produce(i):
            return [
                np.full((per,), float(i), dtype=np.float32)
                for _ in range(chunks)
            ]
    else:

        @fed.remote
        def produce(i):
            # Fresh tensor per round (dedup would skip repeat pushes).
            return np.full((n_elem,), float(i), dtype=np.float32)

    @fed.remote
    def consume(x):
        if isinstance(x, (list, tuple)):
            return float(x[0][0]) + float(x[-1][-1])
        shards = list(getattr(x, "addressable_shards", None) or ())
        if len(shards) > 1:
            import jax

            first = jax.device_get(shards[0].data)
            last = jax.device_get(shards[-1].data)
            return float(first[0]) + float(last[-1])
        return float(x[0]) + float(x[-1])

    @fed.remote
    def barrier(*xs):
        return len(xs)

    @fed.remote
    def tell_port(p):
        return p

    # Connection warmup (the measurement loop below carries its own
    # discarded warmup cycles).
    _progress(party, "init done; connection warmup")
    w = consume.party("bob").remote(produce.party("alice").remote(-1.0))
    assert fed.get(w) == -2.0
    _progress(party, "warmup done")

    # Paired-ceiling rig: a dedicated raw socket between the SAME two
    # party processes. Each rep runs a raw sendall/recv_into window
    # immediately before the lane window, so every lane sample gets a
    # ceiling sample measured seconds apart under the same host regime —
    # on this class of shared VM, throughput swings 2-3x on a seconds
    # timescale (hypervisor steal), so a ceiling probed minutes away
    # (round-4 methodology) calibrates a different regime than the stage
    # it normalizes. pct_of_ceiling is the MEDIAN of per-rep ratios.
    # The rig is best-effort: any failure here or in a raw window below
    # degrades to lane-only reps (no ceiling keys) — the diagnostic
    # ceiling must never abort the headline measurement. A failure on
    # one side closes the raw socket, which breaks the peer's blocked
    # window immediately (RST on close-with-unread-data), so both sides
    # fall back in the same rep without desyncing the fed loop.
    raw_sock = None
    raw_nbytes = PAYLOAD_MB * 1024 * 1024
    if pair_ceiling:
        try:
            if party == "bob":
                raw_srv = socket.socket()
                raw_srv.bind(("127.0.0.1", 0))
                raw_srv.listen(1)
                raw_srv.settimeout(60)
                raw_port = raw_srv.getsockname()[1]
            else:
                raw_port = 0
        except OSError:
            raw_port = -1
        # Multi-controller port exchange: the task runs at bob with bob's
        # local value; alice's argument is a placeholder.
        port_obj = tell_port.party("bob").remote(raw_port)
        raw_port = fed.get(port_obj)
        try:
            if raw_port < 0:
                raise OSError("peer has no raw listener")
            if party == "alice":
                raw_sock = socket.create_connection(
                    ("127.0.0.1", raw_port), timeout=60
                )
                raw_sock.settimeout(None)
                _tune(raw_sock)
                raw_buf = bytearray(raw_nbytes)
            else:
                raw_sock, _ = raw_srv.accept()
                raw_srv.close()
                raw_sock.settimeout(None)
                _tune(raw_sock)
                raw_view = memoryview(bytearray(raw_nbytes))
        except OSError as e:
            print(f"paired ceiling rig unavailable: {e!r}", file=sys.stderr)
            raw_sock = None

    # Negative reps are warmup cycles with the IDENTICAL per-rep
    # structure (produce, barrier, raw window, lane window), discarded
    # from the stats. Measured: the lane needs ~3 full cycles before its
    # allocator/scheduler steady state — single-push warmups left the
    # first 2-3 timed reps 2-5x slow in every run on this host class.
    samples = []
    raw_samples = []
    warmup_reps = 3
    n_reps = PAIRED_REPS if pair_ceiling else REPS
    for rep in range(-warmup_reps, n_reps):
        _progress(party, f"rep {rep}/{n_reps}")
        # Materialize all tensors at alice BEFORE the timed window so the
        # measurement is transport throughput, not producer memset speed.
        base = 100.0 * rep
        tensors = [produce.party("alice").remote(base + i) for i in range(ROUNDS)]
        ready = barrier.party("alice").remote(*tensors)
        assert fed.get(ready) == ROUNDS

        if raw_sock is not None:
            # Raw window: same bytes, same window structure, same two
            # processes, right before the lane window it calibrates. Uses
            # the strongest IO primitive available (the C++ fastwire
            # calls — one GIL-released call per payload) so the ceiling
            # is a true best-possible socket loop, not a Python recv_into
            # loop the native lane can beat.
            try:
                if party == "alice":
                    for _ in range(ROUNDS):
                        _raw_send(raw_sock, raw_buf)
                else:
                    t0 = time.perf_counter()
                    for _ in range(ROUNDS):
                        _raw_recv(raw_sock, raw_view)
                    if rep >= 0:
                        raw_samples.append(
                            ROUNDS * PAYLOAD_MB / 1024
                            / (time.perf_counter() - t0)
                        )
            except (OSError, ConnectionError, TimeoutError) as e:
                print(
                    f"paired ceiling dropped mid-run: {e!r}", file=sys.stderr
                )
                try:
                    raw_sock.close()
                except OSError:
                    pass
                raw_sock = None
                raw_samples = []  # partial pairing would skew the ratio
        if pair_ceiling:
            # Barrier before the lane window: alice's _raw_send returns
            # with up to ~2x SO_SNDBUF still unread in kernel buffers;
            # starting the lane push then would overlap bob's raw-timer
            # tail with lane work, deflating the ceiling sample in the
            # lane's favor. A bob-owned no-op resolves only after bob's
            # program has finished its raw window. Runs UNCONDITIONALLY
            # under pair_ceiling (cheap no-op when the rig is down):
            # gating it on the per-process raw_sock would deadlock both
            # parties the moment a rig failure is asymmetric — one side
            # waiting at this barrier for a peer that skipped it.
            fed.get(tell_port.party("bob").remote(rep))

        t0 = time.perf_counter()
        outs = [consume.party("bob").remote(t) for t in tensors]
        checks = fed.get(outs)
        dt = time.perf_counter() - t0
        assert checks == [2.0 * (base + i) for i in range(ROUNDS)], checks
        if rep >= 0:
            samples.append(ROUNDS * PAYLOAD_MB / 1024 / dt)

    if raw_sock is not None:
        try:
            raw_sock.close()
        except OSError:
            pass
    # Peak-of-reps: throughput capability, same rule for both lanes.
    gbps = max(samples)
    if party == "bob":
        with open(result_path, "w") as f:
            json.dump(
                {"gbps": gbps, "samples": samples,
                 "raw_samples": raw_samples},
                f,
            )
    _progress(party, "reps done; shutting down")
    fed.shutdown()


def _cpu_forced():
    """Spawned children come up on the CPU jax backend inside this
    context: a chip belongs to one process, so two party processes
    cannot share one (env is inherited by spawn)."""
    from unittest import mock

    return mock.patch.dict(os.environ, {"JAX_PLATFORMS": "cpu"})


def _raw_send(sock, buf) -> None:
    try:
        from rayfed_tpu import _fastwire

        _fastwire.sendv(sock.fileno(), -1, [buf])
    except ImportError:
        sock.sendall(buf)


def _raw_recv(sock, view) -> None:
    try:
        from rayfed_tpu import _fastwire

        _fastwire.recv_exact(sock.fileno(), -1, view)
    except ImportError:
        n = view.nbytes
        got = 0
        while got < n:
            k = sock.recv_into(view[got:], n - got)
            if not k:
                raise ConnectionError("raw ceiling sender died")
            got += k


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def run_transport(transport: str, device_dma: bool = False,
                  pair_ceiling: bool = False, num_streams: int = 0,
                  sharded: bool = False, shm: bool = False) -> dict:
    res = _run_two_party(
        _party_main, transport,
        (device_dma, pair_ceiling, num_streams, sharded, shm),
        timeout_s=600,
    )
    import statistics

    # max = capability (continuity with earlier rounds); median is
    # robust to the start-clock skew between the two party processes,
    # which can inflate individual short timed windows.
    out = {
        "max": res["gbps"],
        "median": statistics.median(res["samples"]),
        "samples": res["samples"],
    }
    raw = res.get("raw_samples") or []
    if raw and len(raw) == len(res["samples"]):
        ratios = [s / r for s, r in zip(res["samples"], raw) if r > 0]
        out["raw_median"] = statistics.median(raw)
        out["raw_spread"] = [min(raw), max(raw)]
        out["paired_ratio_median"] = statistics.median(ratios)
    return out


def _tune(sock) -> None:
    """Apply the transport's own socket tuning to the ceiling probe —
    without this the 'ceiling' uses default buffer sizes and the tuned
    native lane can beat it (a >100% pct_of_ceiling is a measurement
    artifact, not physics)."""
    try:
        from rayfed_tpu.proxy.tcp import sockio

        sockio.tune_socket(sock)
    except Exception:  # noqa: BLE001 - probe still works untuned
        pass


def _lane_stats(out: dict, key: str, res: dict) -> None:
    """Record a lane's max (capability, the headline) plus median and
    min/max spread of the same rep samples — one lucky rep on this class
    of shared VM can double "max", and a gating script needs the robust
    statistic next to it."""
    out[key] = round(res["max"], 3)
    out[f"{key}_median"] = round(res["median"], 3)
    out[f"{key}_spread"] = [
        round(min(res["samples"]), 3), round(max(res["samples"]), 3)
    ]


def _try_tpu_lanes() -> dict:
    """The ``transport='tpu'`` lanes, CPU-forced (a chip belongs to one
    process; two party processes cannot share it) — host numbers:

    - ``tpu_lane_gbps``: the full TPU transport — native socket wire +
      device placement on arrival (decode lands arrays via device_put,
      native pooled receive buffers are 64-byte aligned for XLA
      ingestion). On a pod the same lane runs per-host over DCN.
    - ``dma_cpu_gbps``: the device-DMA lane (descriptor over the socket,
      buffers pulled through the jax transfer engine). Its CPU-sim bound
      is the engine itself (~0.6 GB/s bare-engine, one-core host);
      on a pod the engine rides ICI.

    Each key comes with ``_median`` and ``_spread`` companions.
    Best-effort: records nothing when the backend is unavailable."""
    out = {}
    with _cpu_forced():
        try:
            _lane_stats(out, "tpu_lane_gbps", run_transport("tpu"))
        except Exception as e:  # noqa: BLE001
            print(f"tpu-lane bench skipped: {e!r}", file=sys.stderr)
        try:
            _lane_stats(
                out, "dma_cpu_gbps", run_transport("tpu", device_dma=True)
            )
        except Exception as e:  # noqa: BLE001
            print(f"dma bench skipped: {e!r}", file=sys.stderr)
    return out


_MULTISTREAM_LANES = 4


@contextlib.contextmanager
def _cpu_devices(n: int = 8):
    """:func:`_cpu_forced` plus a forced multi-device host platform —
    the sharded-pipeline and psum lanes need >1 device per process."""
    flag = f"--xla_force_host_platform_device_count={n}"
    saved = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = f"{saved} {flag}" if saved else flag
    try:
        with _cpu_forced():
            yield
    finally:
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved


def _psum_agg_entry(result_path, n_parties, rounds, payload_elems):
    """Spawned child: flat-plan aggregation lowered to one collective
    across a composed party mesh (ops.aggregate.psum_by_plan), checked
    bitwise against the reduce_by_plan fold it replaces, then timed."""
    import statistics

    import jax
    import numpy as np

    from rayfed_tpu import mesh as mesh_mod
    from rayfed_tpu import topology as topo
    from rayfed_tpu.ops.aggregate import psum_by_plan, reduce_by_plan

    parties = [f"p{i}" for i in range(n_parties)]
    mesh_mod.compose_party_mesh(parties)
    plan = topo.plan(parties, "flat")
    rng = np.random.default_rng(7)
    contributions = {
        p: {"w": rng.standard_normal(payload_elems).astype(np.float32)}
        for p in parties
    }

    def timed(fn):
        dts = []
        out = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            out = fn(plan, contributions)
            jax.block_until_ready(jax.tree_util.tree_leaves(out))
            dts.append((time.perf_counter() - t0) * 1000)
        return out, dts

    ref, _ = timed(reduce_by_plan)  # warmup (compiles both folds)
    got, _ = timed(psum_by_plan)
    leaves = zip(jax.tree_util.tree_leaves(got),
                 jax.tree_util.tree_leaves(ref))
    assert all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in leaves
    ), "psum_by_plan diverged from reduce_by_plan bits"
    _, psum_dts = timed(psum_by_plan)
    _, fold_dts = timed(reduce_by_plan)
    with open(result_path, "w") as f:
        json.dump(
            {
                "psum_agg_ms": round(statistics.median(psum_dts), 3),
                "psum_agg_ms_spread": [
                    round(min(psum_dts), 3), round(max(psum_dts), 3)
                ],
                "fold_agg_ms": round(statistics.median(fold_dts), 3),
            },
            f,
        )


def _run_psum_agg() -> dict:
    mp = multiprocessing.get_context("spawn")
    with tempfile.TemporaryDirectory() as tmp:
        result_path = os.path.join(tmp, "psum.json")
        p = mp.Process(
            target=_psum_agg_entry,
            args=(
                result_path,
                int(os.environ.get("FEDTPU_BENCH_PSUM_PARTIES", 4)),
                int(os.environ.get("FEDTPU_BENCH_PSUM_ROUNDS", 20)),
                int(os.environ.get("FEDTPU_BENCH_PSUM_ELEMS", 1 << 20)),
            ),
        )
        p.start()
        p.join(timeout=300)
        if p.is_alive():
            p.kill()
            p.join(timeout=30)
            raise RuntimeError("psum agg child hung")
        if p.exitcode != 0 or not os.path.exists(result_path):
            raise RuntimeError(f"psum agg child failed rc={p.exitcode}")
        with open(result_path) as f:
            return json.load(f)


def _try_data_plane() -> dict:
    """The sharded multi-stream data plane:

    - ``multistream_gbps``: the tpu transport with
      ``num_streams=_MULTISTREAM_LANES`` reactor lanes and a chunked
      payload — stripe frames ride K sockets in parallel and the
      receiver reassembles them (tools/dma_check.py gates this against
      ``dma_cpu_gbps``).
    - ``shard_pipeline_gbps``: same lanes, payload a 4-way sharded
      jax.Array on a forced 8-device CPU backend — the shard-extent
      striping + per-shard async D2H pipeline end to end.
    - ``psum_agg_ms``: flat-plan aggregation as ONE collective across a
      composed 4-party mesh, bitwise-checked against reduce_by_plan
      (+ ``fold_agg_ms``, the host fold it replaces, for the ratio).

    Best-effort, like :func:`_try_tpu_lanes`."""
    out = {}
    with _cpu_forced():
        try:
            _lane_stats(
                out, "multistream_gbps",
                run_transport("tpu", num_streams=_MULTISTREAM_LANES),
            )
        except Exception as e:  # noqa: BLE001
            print(f"multistream bench skipped: {e!r}", file=sys.stderr)
    with _cpu_devices(8):
        try:
            _lane_stats(
                out, "shard_pipeline_gbps",
                run_transport(
                    "tpu", num_streams=_MULTISTREAM_LANES, sharded=True
                ),
            )
        except Exception as e:  # noqa: BLE001
            print(f"shard pipeline bench skipped: {e!r}", file=sys.stderr)
        try:
            out.update(_run_psum_agg())
        except Exception as e:  # noqa: BLE001
            print(f"psum agg bench skipped: {e!r}", file=sys.stderr)
    return out


def _paired_baseline_party(party, addresses, transport, result_path,
                           port_plan, pairs):
    """Paired vs_baseline windows: for each pair k, a native-lane window
    and a reference-parity gRPC window run back-to-back in the SAME two
    party processes (fresh fed job per window on preallocated ports).
    The headline vs_baseline is the median of per-pair ratios, so both
    sides of every ratio share the host regime they were measured in —
    the unpaired ratio compares windows minutes apart, and loopback
    throughput on this VM class swings 2-3x on a seconds timescale.
    The outer ``addresses``/``transport`` of the harness are unused:
    every window inits its own job from ``port_plan``."""
    import numpy as np

    import rayfed_tpu as fed

    n_elem = PAYLOAD_MB * 1024 * 1024 // 4
    gbps = {"tcp": [], "grpc": []}
    for k in range(pairs):
        for lane in ("tcp", "grpc"):
            _progress(party, f"pair {k}/{pairs} lane {lane}")
            fed.init(
                addresses=port_plan[f"{k}-{lane}"],
                party=party,
                config={"cross_silo_comm": dict(_FAST_RETRY),
                        "transport": lane},
                job_name=f"bench-pair-{k}-{lane}",
                logging_level="error",
            )

            @fed.remote
            def produce(i):
                return np.full((n_elem,), float(i), dtype=np.float32)

            @fed.remote
            def consume(x):
                return float(x[0]) + float(x[-1])

            @fed.remote
            def barrier(*xs):
                return len(xs)

            # One discarded cycle (connection + allocator warmup), one
            # timed cycle — identical treatment for both lanes, so the
            # ratio cancels any residual cold-start cost.
            for rep in (-1, 0):
                base = 100.0 * rep + k
                tensors = [
                    produce.party("alice").remote(base + i)
                    for i in range(ROUNDS)
                ]
                assert fed.get(
                    barrier.party("alice").remote(*tensors)
                ) == ROUNDS
                t0 = time.perf_counter()
                outs = [consume.party("bob").remote(t) for t in tensors]
                checks = fed.get(outs)
                dt = time.perf_counter() - t0
                assert checks == [2.0 * (base + i) for i in range(ROUNDS)]
                if rep >= 0:
                    gbps[lane].append(ROUNDS * PAYLOAD_MB / 1024 / dt)
            _progress(party, f"pair {k} lane {lane} done; shutting down")
            fed.shutdown()
    if party == "bob":
        with open(result_path, "w") as f:
            json.dump(gbps, f)


def _run_paired_baseline() -> dict:
    """Run the paired vs_baseline stage (see _paired_baseline_party).
    Raises on failure — the caller treats this stage as best-effort and
    falls back to the unpaired ratio."""
    import statistics

    pairs = int(os.environ.get("FEDTPU_BENCH_PAIRS", 3))
    port_plan = {}
    for k in range(pairs):
        for lane in ("tcp", "grpc"):
            p1, p2 = _free_ports(2)
            port_plan[f"{k}-{lane}"] = {
                "alice": f"127.0.0.1:{p1}",
                "bob": f"127.0.0.1:{p2}",
            }
    res = _run_two_party(
        _paired_baseline_party, "tcp", (port_plan, pairs), timeout_s=600
    )
    ratios = [t / g for t, g in zip(res["tcp"], res["grpc"]) if g > 0]
    if not ratios:
        raise RuntimeError("no paired windows completed")
    return {
        "vs_baseline": round(statistics.median(ratios), 3),
        "vs_baseline_pairs": [round(r, 3) for r in ratios],
    }


def _tiny_party(party, addresses, transport, result_path, rounds):
    import rayfed_tpu as fed

    fed.init(
        addresses=addresses,
        party=party,
        config={"cross_silo_comm": dict(_FAST_RETRY), "transport": transport},
        job_name=f"bench-tiny-{transport}",
        logging_level="error",
    )

    @fed.remote
    def inc(x):
        return x + 1

    @fed.remote
    def aggregate(a, b):
        return a + b

    # Warmup (connection + executor spin-up).
    _progress(party, "init done; warmup")
    fed.get(aggregate.party("alice").remote(
        inc.party("alice").remote(0), inc.party("bob").remote(0)))

    _progress(party, "timed rounds")
    t0 = time.perf_counter()
    acc = 0
    for _ in range(rounds):
        a = inc.party("alice").remote(acc)
        b = inc.party("bob").remote(acc)
        acc = fed.get(aggregate.party("alice").remote(a, b))
    dt = time.perf_counter() - t0
    _progress(party, "rounds done; shutting down")
    # 3 fed tasks + 1 get per round (the reference harness's accounting,
    # ref benchmarks/many_tiny_tasks_benchmark.py:48-59).
    if party == "alice":
        with open(result_path, "w") as f:
            json.dump({"per_task_ms": dt / rounds / 3 * 1000}, f)
    fed.shutdown()


def _fedavg_party(party, addresses, transport, result_path, rounds):
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.federated import FedAvgTrainer

    fed.init(
        addresses=addresses,
        party=party,
        config={"cross_silo_comm": dict(_FAST_RETRY), "transport": transport},
        job_name=f"bench-fedavg-{transport}",
        logging_level="error",
    )

    dim, classes, batch = 784, 10, 128  # MNIST logreg shapes (BASELINE #3)

    @fed.remote
    class Worker:
        def __init__(self, seed):
            rng = np.random.default_rng(seed)
            self.w = np.zeros((dim, classes), np.float32)
            self.b = np.zeros((classes,), np.float32)
            self.x = rng.normal(size=(batch, dim)).astype(np.float32)
            self.y = np.eye(classes, dtype=np.float32)[
                rng.integers(0, classes, size=(batch,))
            ]

        def train(self, global_params):
            if global_params is not None:
                self.w, self.b = global_params
            for _ in range(3):  # local epochs (plain numpy: the round
                # latency under measurement is orchestration + transport)
                logits = self.x @ self.w + self.b
                logits -= logits.max(axis=1, keepdims=True)
                p = np.exp(logits)
                p /= p.sum(axis=1, keepdims=True)
                g = (p - self.y) / batch
                self.w -= 0.1 * (self.x.T @ g)
                self.b -= 0.1 * g.sum(axis=0)
            return (self.w, self.b)

    trainer = FedAvgTrainer(
        Worker, ["alice", "bob"],
        worker_args={"alice": (1,), "bob": (2,)},
    )
    # Warmup round (actor init, first push).
    _progress(party, "init done; warmup round")
    global_params = fed.get(trainer.run(1))
    _progress(party, "timed rounds")
    t0 = time.perf_counter()
    final = fed.get(trainer.run(rounds, global_params))
    dt = time.perf_counter() - t0
    _progress(party, "rounds done; shutting down")
    assert np.isfinite(np.asarray(final[0]).sum())
    if party == "alice":
        with open(result_path, "w") as f:
            json.dump({"round_ms": dt / rounds * 1000}, f)
    fed.shutdown()


def _run_two_party(target, transport, extra_args, timeout_s=300,
                   parties=("alice", "bob")) -> dict:
    """Generic N-party spawn harness: run ``target(party, addresses,
    transport, result_path, *extra_args)`` once per party; return the
    result dict the writer party left at result_path."""
    ports = _free_ports(len(parties))
    addresses = {
        party: f"127.0.0.1:{port}" for party, port in zip(parties, ports)
    }
    mp = multiprocessing.get_context("spawn")
    with tempfile.TemporaryDirectory() as tmp:
        result_path = os.path.join(tmp, "result.json")
        procs = [
            mp.Process(
                target=_party_entry,
                args=(target, party, addresses, transport, result_path)
                + extra_args,
            )
            for party in parties
        ]
        # Children inherit the env at spawn; each party overwrites
        # {tmp}/{party}.progress at phase boundaries (_progress) so a
        # hang below can say WHICH phase each party last reached.
        os.environ[_PROGRESS_DIR_VAR] = tmp
        try:
            for p in procs:
                p.start()
        finally:
            os.environ.pop(_PROGRESS_DIR_VAR, None)
        for p in procs:
            p.join(timeout=timeout_s)
        hung = [p for p in procs if p.is_alive()]
        if hung:
            # Ask each hung party for an all-thread stack dump BEFORE the
            # kill (a terminated process can't report anything itself);
            # _party_entry armed faulthandler on SIGUSR1 at spawn.
            import signal

            usr1 = getattr(signal, "SIGUSR1", None)
            if usr1 is not None:
                for p in hung:
                    try:
                        os.kill(p.pid, usr1)
                    except OSError:
                        pass
                time.sleep(2.0)  # let faulthandler finish writing
        for p in hung:
            p.terminate()
            p.join(timeout=30)
        if hung:
            marks = {}
            stacks = {}
            for party in parties:
                try:
                    with open(os.path.join(tmp, f"{party}.progress")) as f:
                        marks[party] = f.read().strip() or "no mark"
                except OSError:
                    marks[party] = "no mark"
                try:
                    with open(os.path.join(tmp, f"{party}.stacks")) as f:
                        s = f.read().strip()
                    if s:
                        stacks[party] = s[-4000:]
                except OSError:
                    pass
            detail = "".join(
                f"\n--- {party} stacks at kill ---\n{s}"
                for party, s in stacks.items()
            )
            raise RuntimeError(
                f"bench party hung; terminated (last phase marks: {marks})"
                + detail
            )
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(f"bench party failed ({p.exitcode})")
        with open(result_path) as f:
            return json.load(f)


# Stage failure diagnostics, keyed "<party_fn>[<key>]". A hung stage's
# faulthandler stacks and phase marks land HERE and then in the headline
# JSON line's "diagnostics" field — BENCH_r05's "bench party hung;
# terminated" left nothing to root-cause with because the dump only went
# to a stderr stream nobody kept.
_DIAGNOSTICS: dict = {}


def _record_diag(stage: str, err: BaseException) -> None:
    msg = str(err)
    head, sep, stacks = msg.partition("\n--- ")
    entry = {"error": head.strip()[:500]}
    if sep:
        # The all-thread faulthandler dumps _run_two_party appended to
        # the hang error, bounded so the JSON line stays printable.
        entry["stacks_tail"] = ("--- " + stacks)[-4000:]
    _DIAGNOSTICS[stage] = entry


def _bench_stage(party_fn, res_field, env_var, default_rounds, keys, *,
                 cpu_force=False, parties=("alice", "bob"), timeout_s=300,
                 digits=2, extra_fields=None) -> dict:
    """Run one two-to-N-party workload per (transport, result-key) pair.

    ``cpu_force`` wraps the spawned parties in :func:`_cpu_forced` —
    required whenever the workload jits (two processes cannot share one
    chip). ``extra_fields`` maps additional result fields to output
    keys (recorded when present; single-key stages only — the output key
    does not vary by transport). Best-effort: on failure the keys
    gathered so far are kept and the rest are skipped with a stderr
    note — the headline JSON line always prints."""
    out = {}
    try:
        with _cpu_forced() if cpu_force else contextlib.nullcontext():
            rounds = int(os.environ.get(env_var, default_rounds))
            for transport, key in keys:
                # One retry per phase: the recurring gRPC-lane hang
                # (BENCH_r05 "_fedavg_party bench skipped") is a
                # once-per-run wedge, so a surviving second window keeps
                # the key populated instead of dropping it.
                for attempt in (1, 2):
                    try:
                        res = _run_two_party(
                            party_fn, transport, (rounds,),
                            timeout_s=timeout_s, parties=parties,
                        )
                        break
                    except Exception as e:  # noqa: BLE001 - retried once
                        if "bench party hung" in str(e):
                            # The watchdog already burned timeout_s on
                            # this window; a wedged stage hangs the same
                            # way on retry and burns it AGAIN (BENCH_r05
                            # paid 2x the budget for one dead key).
                            # Capture the stacks and skip with reason.
                            _record_diag(f"{party_fn.__name__}[{key}]", e)
                            raise
                        if attempt == 2:
                            _record_diag(f"{party_fn.__name__}[{key}]", e)
                            raise
                        print(
                            f"{party_fn.__name__} [{key}] window failed "
                            f"({e!r}); retrying the phase once",
                            file=sys.stderr,
                        )
                out[key] = round(res[res_field], digits)
                for rf, out_key in (extra_fields or {}).items():
                    v = res.get(rf)
                    if isinstance(v, list):
                        out[out_key] = [round(x, digits) for x in v]
                    elif isinstance(v, (int, float)):
                        out[out_key] = round(v, digits)
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        print(f"{party_fn.__name__} bench skipped: {e!r}", file=sys.stderr)
    return out


_HIER4 = ("alice", "bob", "carol", "dave")


def _hier4_party(party, addresses, transport, result_path, rounds):
    """4-party hierarchical aggregation tree (BASELINE config #4): each
    party contributes a 4MB gradient tree per round; ``fed_aggregate``
    reduces pairwise (2 rounds of 2-way reduces), so the coordinator's
    fan-in is halved versus an all-to-root star."""
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.federated import fed_aggregate

    fed.init(
        addresses=addresses,
        party=party,
        config={"cross_silo_comm": dict(_FAST_RETRY), "transport": transport},
        job_name=f"bench-hier4-{transport}",
        logging_level="error",
    )
    n_elem = 1 << 20  # 4MB float32 per party per round

    @fed.remote
    def contrib(seed):
        return {"g": np.full((n_elem,), float(seed), np.float32)}

    def one_round(r):
        objs = {
            p: contrib.party(p).remote(float(r * 10 + i))
            for i, p in enumerate(_HIER4)
        }
        agg = fed_aggregate(objs, op="mean")
        out = fed.get(agg)
        expect = sum(r * 10 + i for i in range(4)) / 4.0
        assert float(np.asarray(out["g"])[0]) == expect
        return out

    _progress(party, "init done; warmup round")
    one_round(-1)  # warmup (connections, executor)
    _progress(party, "timed rounds")
    dts = []
    for r in range(rounds):
        t0 = time.perf_counter()
        one_round(r)
        dts.append((time.perf_counter() - t0) * 1000)
    _progress(party, "rounds done; shutting down")
    if party == "alice":
        import statistics

        # Mean keeps continuity with earlier rounds' round_ms; the
        # median and [min, max] spread qualify how noisy the stage was
        # (4 parties on a shared VM — a single steal burst can double
        # the mean without touching the median).
        with open(result_path, "w") as f:
            json.dump(
                {
                    "round_ms": sum(dts) / len(dts),
                    "round_ms_median": statistics.median(dts),
                    "round_ms_spread": [min(dts), max(dts)],
                },
                f,
            )
    fed.shutdown()


# --- N-party scale sweep (reactor transport + topology planner) -----------
#
# Spawning 64 real party processes on a shared 1-2 core CI VM measures the
# scheduler, not the transport. Instead the sweep simulates N parties in
# ONE process: each party is a real TcpSenderProxy + TcpReceiverProxy pair
# (real sockets, real frames, real acks — all riding the shared reactor
# loops), and each round executes a planned hierarchical reduction whose
# edges are actual wire transfers. What's simulated is only process
# isolation; the transport path is the production one.

_SCALE_NS = (8, 16, 32, 64)


def _simulated_hier_round(n_parties: int, rounds: int,
                          payload_elems: int = 16384,
                          topology: str = "hier") -> dict:
    """Median round latency for an N-party planned reduction where every
    reduce edge is a real proxy-to-proxy transfer. Returns
    {"round_ms_median", "round_ms_spread", "rounds"}."""
    import statistics
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from rayfed_tpu import topology as topo
    from rayfed_tpu.proxy.tcp.tcp_proxy import (
        TcpReceiverProxy,
        TcpSenderProxy,
    )

    parties = [f"p{i:02d}" for i in range(n_parties)]
    ports = _free_ports(n_parties)
    addresses = {p: f"127.0.0.1:{port}" for p, port in zip(parties, ports)}
    cfg = {
        "timeout_in_ms": 30000,
        "connect_timeout_in_ms": 5000,
        "retry_policy": {
            "max_attempts": 3,
            "initial_backoff_ms": 50,
            "max_backoff_ms": 500,
            "backoff_multiplier": 2.0,
        },
        "num_reactors": 4,
    }
    plan = topo.plan(parties, topology)
    receivers, senders = {}, {}
    try:
        for p in parties:
            rp = TcpReceiverProxy(addresses[p], p, "bench-scale", None,
                                  dict(cfg))
            rp.start()
            ok, err = rp.is_ready()
            if not ok:
                raise RuntimeError(f"receiver for {p} not ready: {err}")
            receivers[p] = rp
        for p in parties:
            sp = TcpSenderProxy(addresses, p, "bench-scale", None, dict(cfg))
            sp.start()
            senders[p] = sp

        base = {
            p: np.full((payload_elems,), float(i + 1), np.float32)
            for i, p in enumerate(parties)
        }
        expect = float(sum(range(1, n_parties + 1))) / n_parties

        def one_round(r: int) -> None:
            held = dict(base)
            for li, level in enumerate(plan.levels):
                def do_step(step):
                    futs = []
                    for s in step.srcs[1:]:
                        seq = f"r{r}L{li}:{s}>{step.dst}"
                        futs.append(
                            (receivers[step.dst].get_data(s, seq, seq),
                             senders[s].send(step.dst, held[s], seq, seq))
                        )
                    acc = held[step.srcs[0]].astype(np.float32)
                    for recv_fut, send_fut in futs:
                        send_fut.result(60)
                        acc = acc + np.asarray(recv_fut.result(60),
                                               np.float32)
                    return step.dst, acc
                with ThreadPoolExecutor(
                    max_workers=max(1, min(32, len(level)))
                ) as pool:
                    for dst, acc in pool.map(do_step, level):
                        held[dst] = acc
            out = held[plan.root] / float(n_parties)
            # Integer-valued contributions: the planned fold is exact, so
            # a wrong aggregate is a transport bug, not float noise.
            assert float(out[0]) == expect, (float(out[0]), expect)

        one_round(-1)  # warmup: dial every edge, prime the reactor rings
        dts = []
        for r in range(rounds):
            t0 = time.perf_counter()
            one_round(r)
            dts.append((time.perf_counter() - t0) * 1000)
        return {
            "round_ms_median": statistics.median(dts),
            "round_ms_spread": [min(dts), max(dts)],
            "rounds": rounds,
        }
    finally:
        for sp in senders.values():
            try:
                sp.stop()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        for rp in receivers.values():
            try:
                rp.stop()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass


def _run_scale_sweep() -> dict:
    """``hierN_round_ms`` for N in 8/16/32/64 + ``parties_sustained``
    (largest N whose sweep completed). Median-of-rounds (same noise
    treatment as hier4) so the keys are CI-gateable."""
    out = {}
    rounds = int(os.environ.get("FEDTPU_BENCH_SCALE_ROUNDS", 5))
    ns = [
        int(x) for x in os.environ.get(
            "FEDTPU_BENCH_SCALE_NS",
            ",".join(str(n) for n in _SCALE_NS),
        ).split(",") if x
    ]
    sustained = 0
    for n in ns:
        # Small-N rounds are cheap: take more of them so the median the
        # scaling ratio divides by sits in the steady-state regime (a
        # lucky 5-round N=8 window can halve the denominator on this
        # class of shared VM).
        n_rounds = max(rounds, min(160 // max(1, n), 20))
        try:
            res = _simulated_hier_round(n, n_rounds)
        except Exception as e:  # noqa: BLE001 - keep smaller-N keys
            print(f"scale bench skipped at N={n}: {e!r}", file=sys.stderr)
            break
        out[f"hier{n}_round_ms"] = round(res["round_ms_median"], 2)
        out[f"hier{n}_round_ms_spread"] = [
            round(x, 2) for x in res["round_ms_spread"]
        ]
        sustained = n
    if sustained:
        out["parties_sustained"] = sustained
    return out


def _tenant_bench_entry(result_path, window_s, push_mb, inline_kb):
    """Child-process body of the tenant stage: two jobs share one
    listener (the piggyback path), both keep bulk backlog through the
    weighted-fair gate at weights 4:1, while the victim job's inline
    serving-class round trips are latency-sampled. Emits the two keys
    tools/tenant_check.py gates: ``tenant_fairness_ratio`` (weight-
    normalized bulk byte ratio, 1.0 = perfectly fair) and
    ``multitenant_victim_p99_ms``."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import threading

    import numpy as np

    from rayfed_tpu.proxy.tcp.tcp_proxy import (
        TcpReceiverProxy,
        TcpSenderProxy,
    )
    from rayfed_tpu.tenancy import qos as tenancy_qos
    from rayfed_tpu.tenancy.context import TenancyConfig

    fast = {"retry_policy": {"max_attempts": 10, "initial_backoff_ms": 100}}
    sched = tenancy_qos.get_scheduler()
    sched.register("victim", TenancyConfig(weight=4, fair_window_mb=2))
    sched.register("noisy", TenancyConfig(weight=1, fair_window_mb=2))

    (port,) = _free_ports(1)
    addrs = {"bob": f"127.0.0.1:{port}"}
    receivers = {
        job: TcpReceiverProxy(addrs["bob"], "bob", job, None, dict(fast))
        for job in ("victim", "noisy")
    }
    senders = {
        job: TcpSenderProxy(addrs, "alice", job, None, dict(fast))
        for job in ("victim", "noisy")
    }
    for p in list(receivers.values()) + list(senders.values()):
        p.start()

    deadline = time.monotonic() + window_s
    bulk_payload = np.arange((push_mb << 20) // 4, dtype=np.uint32)
    inline_payload = np.arange((inline_kb << 10), dtype=np.uint8)
    errors = []

    def bulk_loop(job, base):
        try:
            i = 0
            while time.monotonic() < deadline:
                seq = base + 2 * i
                fut = receivers[job].get_data("alice", f"{seq}#0", seq + 1)
                senders[job].send(
                    "bob", bulk_payload, f"{seq}#0", seq + 1
                ).result(60)
                fut.result(60)
                i += 1
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(f"{job} bulk: {e!r}")

    latencies = []

    def inline_loop():
        try:
            i = 0
            while time.monotonic() < deadline:
                seq = 1 + 2 * i  # odd ids: disjoint from the bulk range
                fut = receivers["victim"].get_data(
                    "alice", f"{seq}#0", seq + 1
                )
                t0 = time.monotonic()
                senders["victim"].send(
                    "bob", inline_payload, f"{seq}#0", seq + 1
                )
                fut.result(60)
                latencies.append((time.monotonic() - t0) * 1e3)
                i += 1
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(f"victim inline: {e!r}")

    threads = [
        threading.Thread(target=bulk_loop, args=("noisy", 1_000_000)),
        threading.Thread(target=bulk_loop, args=("victim", 2_000_000)),
        threading.Thread(target=inline_loop),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=window_s + 120)
    for p in list(senders.values()) + [receivers["noisy"],
                                       receivers["victim"]]:
        try:
            p.stop()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
    if errors or not latencies:
        raise RuntimeError(f"tenant bench failed: {errors or 'no samples'}")
    ratio = sched.fairness_ratio("victim", "noisy")
    lat = sorted(latencies)
    out = {
        "tenant_fairness_ratio": round(ratio, 3) if ratio else None,
        "multitenant_victim_p99_ms": round(
            lat[int(0.99 * (len(lat) - 1))], 2
        ),
        "multitenant_victim_p50_ms": round(lat[len(lat) // 2], 2),
        "tenant_inline_samples": len(lat),
        "tenant_bulk_mb": {
            job: round(
                sched.bytes_sent(job, tenancy_qos.TC_BULK) / (1 << 20), 1
            )
            for job in ("victim", "noisy")
        },
    }
    with open(result_path, "w") as f:
        json.dump(out, f)


def _run_tenant_bench() -> dict:
    """Tenant-fairness stage (docs/multitenancy.md); spawned CPU-forced
    child, same isolation rationale as the psum stage."""
    mp = multiprocessing.get_context("spawn")
    with _cpu_forced(), tempfile.TemporaryDirectory() as tmp:
        result_path = os.path.join(tmp, "tenant.json")
        p = mp.Process(
            target=_tenant_bench_entry,
            args=(
                result_path,
                float(os.environ.get("FEDTPU_BENCH_TENANT_WINDOW_S", 6)),
                int(os.environ.get("FEDTPU_BENCH_TENANT_PUSH_MB", 4)),
                int(os.environ.get("FEDTPU_BENCH_TENANT_INLINE_KB", 4)),
            ),
        )
        p.start()
        p.join(timeout=300)
        if p.is_alive():
            p.kill()
            p.join(timeout=30)
            raise RuntimeError("tenant bench child hung")
        if p.exitcode != 0 or not os.path.exists(result_path):
            raise RuntimeError(f"tenant bench child failed rc={p.exitcode}")
        with open(result_path) as f:
            return json.load(f)


def _cnn_party(party, addresses, transport, result_path, rounds):
    """2-party federated CNN round at CIFAR-10 shapes (BASELINE config
    #5): per-party data shards, local jitted train steps, FedAvg of the
    full parameter tree each round."""
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.federated import FedAvgTrainer

    fed.init(
        addresses=addresses,
        party=party,
        config={"cross_silo_comm": dict(_FAST_RETRY), "transport": transport},
        job_name=f"bench-cnn-{transport}",
        logging_level="error",
    )

    @fed.remote
    class CnnWorker:
        def __init__(self, seed):
            import jax

            from rayfed_tpu.models.cnn import cnn_loss, init_cnn

            self.params = init_cnn(jax.random.PRNGKey(0))
            rng = np.random.default_rng(seed)
            self.x = rng.normal(size=(32, 32, 32, 3)).astype(np.float32)
            self.y = rng.integers(0, 10, size=(32,))

            def step(params, x, y):
                loss, grads = jax.value_and_grad(cnn_loss)(params, x, y)
                return jax.tree_util.tree_map(
                    lambda p, g: p - 0.05 * g, params, grads
                ), loss

            self._step = jax.jit(step)

        def train(self, global_params):
            if global_params is not None:
                self.params = global_params
            for _ in range(2):  # local steps
                self.params, _ = self._step(self.params, self.x, self.y)
            return self.params

    trainer = FedAvgTrainer(
        CnnWorker, ["alice", "bob"],
        worker_args={"alice": (1,), "bob": (2,)},
    )
    # Warmup round absorbs actor init + the jit compile.
    _progress(party, "init done; warmup round (jit compile)")
    global_params = fed.get(trainer.run(1))
    _progress(party, "timed rounds")
    t0 = time.perf_counter()
    final = fed.get(trainer.run(rounds, global_params))
    dt = time.perf_counter() - t0
    _progress(party, "rounds done; shutting down")
    assert all(
        np.isfinite(np.asarray(leaf)).all()
        for leaf in (final["head"]["w"], final["dense"]["w"])
    )
    if party == "alice":
        with open(result_path, "w") as f:
            json.dump({"round_ms": dt / rounds * 1000}, f)
    fed.shutdown()


_ASYNC3 = ("alice", "bob", "carol")


def _async_party(party, addresses, transport, result_path, rounds):
    """Straggler-proof sustained throughput (docs/async_rounds.md): 3
    parties, every frame carol sends delayed by a seeded fault schedule
    (``resilience.inject``). Each repetition runs the same contribution
    workload through two windows: lock-step ``fed_aggregate`` rounds
    (every round waits out carol's delay — the stall async mode exists
    to remove) and buffered-async rounds (``fed.async_round``,
    buffer_k=2: alice+bob publish immediately; carol's late pushes fold
    in with staleness decay). ``async_rounds_s`` vs ``sync_rounds_s`` is
    the headline ratio tools/async_check.py gates (>= 3x)."""
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.async_rounds import async_session_stats
    from rayfed_tpu.federated import fed_aggregate

    delay_ms = int(os.environ.get("FEDTPU_BENCH_ASYNC_DELAY_MS", "400"))
    reps = int(os.environ.get("FEDTPU_BENCH_ASYNC_REPS", "2"))
    fed.init(
        addresses=addresses,
        party=party,
        config={
            "cross_silo_comm": dict(_FAST_RETRY),
            "transport": transport,
            "resilience": {
                "fault_schedule": {
                    "seed": 9,
                    "rules": [{
                        "fault": "delay",
                        "src": "carol",
                        "prob": 1.0,
                        "max_delay_ms": delay_ms,
                    }],
                },
            },
        },
        job_name=f"bench-async-{transport}",
        logging_level="error",
    )
    n_elem = 1 << 14  # 64KB float32 gradient tree per contribution
    seeds = {"alice": 1.0, "bob": 2.0, "carol": 3.0}

    @fed.remote
    def contrib(seed, r):
        return {"g": np.full((n_elem,), float(seed + r), np.float32)}

    def sync_window(tag):
        t0 = time.perf_counter()
        for r in range(rounds):
            objs = {
                p: contrib.party(p).remote(seeds[p], r) for p in _ASYNC3
            }
            val = fed.get(fed_aggregate(objs, op="mean"))
            assert np.isfinite(np.asarray(val["g"]).sum())
        return time.perf_counter() - t0

    def async_window(tag):
        session = f"bench{tag}"
        handles = []
        t0 = time.perf_counter()
        for r in range(rounds):
            objs = {
                p: contrib.party(p).remote(seeds[p], r) for p in _ASYNC3
            }
            handles.append(fed.async_round(
                objs, round_tag=r, buffer_k=2, session=session,
                fetch_model=False,
            ))
        # The window ends when `rounds` K-publishes landed — alice+bob
        # fill each buffer without waiting for carol. Every driver polls
        # the SAME broadcast stats, so every driver exits the loop on
        # the same iteration (multi-controller contract).
        deadline = t0 + max(60.0, rounds * delay_ms / 1000.0 * 3)
        while True:
            stats = fed.get(async_session_stats("alice", session))
            if stats["publishes"] >= rounds:
                break
            if time.perf_counter() > deadline:
                raise RuntimeError(f"async window stalled: {stats}")
            time.sleep(0.02)
        dt = time.perf_counter() - t0
        assert stats["version"] >= rounds
        # Drain carol's in-flight straggler offers BEFORE any party
        # reaches fed.shutdown(): the delayed frames ride daemon timer
        # threads, so a party exiting early would strand alice's
        # pending offer tasks on blocked pool workers (exit-time hang).
        # Outside the timed window — the window ends at the K-publish.
        for h in handles:
            fed.get(list(h.offers.values()))
        return dt

    # Warmup round: dial + jit of the fold programs, outside both windows.
    _progress(party, "init done; warmup")
    warm = {p: contrib.party(p).remote(seeds[p], 0) for p in _ASYNC3}
    fed.get(fed_aggregate(warm, op="mean"))
    sync_s, async_s = [], []
    for rep in range(reps):
        _progress(party, f"rep {rep + 1}/{reps}: sync window")
        sync_s.append(rounds / sync_window(rep))
        _progress(party, f"rep {rep + 1}/{reps}: async window")
        async_s.append(rounds / async_window(rep))
    _progress(party, "windows done; shutting down")
    if party == "alice":
        best_async, best_sync = max(async_s), max(sync_s)
        with open(result_path, "w") as f:
            json.dump({
                "async_rounds_s": best_async,
                "sync_rounds_s": best_sync,
                "async_rounds_s_spread": async_s,
                "sync_rounds_s_spread": sync_s,
                "async_vs_sync": best_async / best_sync,
                "straggler_delay_ms": delay_ms,
            }, f)
    fed.shutdown()


_CHURN5 = ("alice", "bob", "carol", "dave", "erin")


def _churn_party(party, addresses, transport, result_path, rounds):
    """Elastic-membership churn lifecycle (docs/membership.md): a
    4-party FedAvg where dave is crash-killed mid-round by an injected
    fault, evicted at the next sync by the liveness monitor's DEAD
    verdict, and erin joins as its replacement mid-training via
    ``fed.join``. Headline metrics tools/churn_check.py gates:

      churn_join_ms    — fed.join() call to the joiner's FIRST completed
                         contribution round (handshake + admission bump
                         + one elastic round).
      churn_rounds_lost — rounds that aggregated zero contributors on
                         the coordinator (must be 0: churn must degrade
                         rounds, never lose them).
    """
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.ops.aggregate import elastic_weighted_mean
    from rayfed_tpu.resilience.liveness import DEAD

    crash_round = 3  # dave pushes to 3 peers/round; 10th push crashes
    join_trigger = 4  # erin dials in while the eviction is in flight
    marker_dir = os.path.dirname(result_path)
    bases = {"alice": 1.0, "bob": 2.0, "carol": 3.0, "dave": 4.0,
             "erin": 5.0}
    comm = {
        "retry_policy": {
            "max_attempts": 2,
            "initial_backoff_ms": 50,
            "max_backoff_ms": 100,
        },
        "timeout_in_ms": 2000,
        "recv_timeout_in_ms": 2000,
        "send_deadline_in_ms": 4000,
    }
    resilience = {
        "liveness": {
            "interval_ms": 100, "suspect_after": 2, "dead_after": 4,
            "timeout_ms": 300,
        },
    }
    membership = {
        "coordinator": "alice",
        "auth_token": "bench-churn",
        "evict_dead": True,
        "sync_timeout_s": 30.0,
    }
    job_name = f"bench-churn-{transport}"

    @fed.remote
    def contrib(base, r):
        return {"g": np.full((1 << 12,), base * (r + 1), np.float32)}

    def one_round(r, view):
        roster = sorted(view.roster)
        objs = {p: contrib.party(p).remote(bases[p], r) for p in roster}
        got = fed.get([objs[p] for p in roster], timeout=3.0,
                      on_missing="default")
        contribs = dict(zip(roster, got))
        live = fed.liveness_view()
        agg = elastic_weighted_mean(contribs, liveness=live)
        assert np.isfinite(np.asarray(agg["g"]).sum())
        return [p for p in roster
                if contribs[p] is not fed.MISSING and live.get(p) != DEAD]

    if party == "erin":
        trigger = os.path.join(marker_dir, f"round-{join_trigger}")
        deadline = time.monotonic() + 120
        while not os.path.exists(trigger):
            if time.monotonic() > deadline:
                raise RuntimeError("founders never reached the join round")
            time.sleep(0.05)
        from rayfed_tpu.membership.manager import get_membership_manager

        t_join = time.monotonic()
        fed.join(
            address=addresses["erin"],
            party="erin",
            coordinator="alice",
            coordinator_address=addresses["alice"],
            config={
                "cross_silo_comm": dict(comm),
                "transport": transport,
                "resilience": dict(resilience),
                "membership": dict(membership),
            },
            job_name=job_name,
            logging_level="error",
            timeout=90.0,
        )
        entry = get_membership_manager().sync_index() - 1
        join_ms = None
        for r in range(entry, rounds):
            view = (fed.membership_view() if r == entry
                    else fed.membership_sync(timeout=30.0))
            one_round(r, view)
            if join_ms is None:
                join_ms = (time.monotonic() - t_join) * 1e3
            time.sleep(0.25)
        # Sidecar for the coordinator's result merge (atomic: alice may
        # already be polling for it).
        tmp = result_path + ".erin.tmp"
        with open(tmp, "w") as f:
            json.dump({"churn_join_ms": join_ms, "entry": entry}, f)
        os.replace(tmp, result_path + ".erin")
        fed.shutdown()
        return

    founders = {p: a for p, a in addresses.items() if p != "erin"}
    config = {
        "barrier_on_initializing": True,
        "cross_silo_comm": dict(comm),
        "transport": transport,
        "resilience": dict(resilience),
        "membership": dict(membership),
    }
    if party == "dave":
        config["cross_silo_comm"]["exit_on_sending_failure"] = True
        config["resilience"]["fault_schedule"] = {
            "seed": 7,
            "rules": [{"fault": "crash", "src": "dave",
                       "after": 3 * crash_round}],
        }
    fed.init(
        addresses=founders,
        party=party,
        config=config,
        job_name=job_name,
        logging_level="error",
        sending_failure_handler=(
            (lambda e: os._exit(0)) if party == "dave" else None
        ),
    )
    per_round = []
    last_view = None
    try:
        for r in range(rounds):
            view = fed.membership_sync(timeout=30.0)
            last_view = view
            contributors = one_round(r, view)
            per_round.append(contributors)
            if party == "alice":
                with open(os.path.join(marker_dir, f"round-{r}"), "w"):
                    pass
            time.sleep(0.25)
    except BaseException:
        if party == "dave" and len(per_round) >= crash_round - 1:
            os._exit(0)  # expected death throes after the injected crash
        raise
    if party == "dave":
        raise AssertionError("dave survived its own crash schedule")
    if party == "alice":
        erin_path = result_path + ".erin"
        deadline = time.monotonic() + 60
        while not os.path.exists(erin_path):
            if time.monotonic() > deadline:
                raise RuntimeError("joiner never reported its sidecar")
            time.sleep(0.1)
        with open(erin_path) as f:
            erin_res = json.load(f)
        final_roster = sorted(last_view.roster)
        replaced = ("erin" in final_roster and "dave" not in final_roster
                    and "erin" in per_round[-1])
        with open(result_path, "w") as f:
            json.dump({
                "churn_join_ms": erin_res["churn_join_ms"],
                "churn_rounds_lost": sum(
                    1 for c in per_round if not c
                ),
                "churn_replaced": int(replaced),
                "churn_epoch": last_view.epoch,
                "churn_entry_round": erin_res["entry"],
                "churn_rounds": rounds,
            }, f)
    fed.shutdown()


_HA3 = ("alice", "bob", "carol")


def _ha_party(party, addresses, transport, result_path, rounds):
    """Control-plane HA stage (docs/ha.md): a 3-party FedAvg where the
    CONFIGURED COORDINATOR (alice) is crash-killed mid-sync-broadcast by
    an injected fault; the deterministic successor (bob) deposes it on
    the liveness DEAD verdict, adopts term 1, and takes over the sync
    point — re-broadcasting the retained views so the member whose recv
    the crash orphaned (carol) converges on the same roster. Headline
    metrics tools/ha_check.py gates:

      coordinator_failover_ms — the longest membership_sync wait the
                         successor paid across the run: the round stall
                         the takeover cost (DEAD verdict + deterministic
                         election + takeover re-broadcast).
      ha_rounds_lost   — rounds that aggregated zero contributors on the
                         successor (must be 0: failover must degrade
                         rounds, never lose them).
      ha_failed_over   — the successor actually holds the coordinator
                         role at a term >= 1 when the run ends.
    """
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.membership.manager import get_membership_manager
    from rayfed_tpu.ops.aggregate import elastic_weighted_mean
    from rayfed_tpu.resilience.liveness import DEAD

    crash_round = 2  # alice makes 4 data sends per healthy round (the
    #                  sync broadcast to each member, then its update
    #                  push to each consumer); after=9 kills it MID the
    #                  round-2 sync broadcast — one member holds sync 3,
    #                  the other waits for the takeover re-broadcast.
    bases = {"alice": 1.0, "bob": 2.0, "carol": 3.0}
    comm = {
        "retry_policy": {
            "max_attempts": 2,
            "initial_backoff_ms": 50,
            "max_backoff_ms": 100,
        },
        "timeout_in_ms": 2000,
        "recv_timeout_in_ms": 2000,
        "send_deadline_in_ms": 4000,
    }
    config = {
        "barrier_on_initializing": True,
        "cross_silo_comm": dict(comm),
        "transport": transport,
        "resilience": {
            "liveness": {
                "interval_ms": 100, "suspect_after": 2, "dead_after": 4,
                "timeout_ms": 300,
            },
        },
        "membership": {
            "coordinator": "alice",
            "evict_dead": True,
            "sync_timeout_s": 30.0,
            "failover": {"takeover_timeout_s": 0.5, "resync_window": 8},
        },
    }
    if party == "alice":
        config["cross_silo_comm"]["exit_on_sending_failure"] = True
        config["resilience"]["fault_schedule"] = {
            "seed": 11,
            "rules": [{"fault": "crash", "src": "alice",
                       "after": 4 * crash_round + 1}],
        }
    fed.init(
        addresses=addresses,
        party=party,
        config=config,
        job_name=f"bench-ha-{transport}",
        logging_level="error",
        sending_failure_handler=(
            (lambda e: os._exit(0)) if party == "alice" else None
        ),
    )

    @fed.remote
    def contrib(base, r):
        return {"g": np.full((1 << 12,), base * (r + 1), np.float32)}

    per_round = []
    max_sync_ms = 0.0
    try:
        for r in range(rounds):
            t0 = time.monotonic()
            view = fed.membership_sync(timeout=30.0)
            max_sync_ms = max(max_sync_ms, (time.monotonic() - t0) * 1e3)
            roster = sorted(view.roster)
            objs = {p: contrib.party(p).remote(bases[p], r) for p in roster}
            got = fed.get([objs[p] for p in roster], timeout=3.0,
                          on_missing="default")
            contribs = dict(zip(roster, got))
            live = fed.liveness_view()
            agg = elastic_weighted_mean(contribs, liveness=live)
            assert np.isfinite(np.asarray(agg["g"]).sum())
            per_round.append([
                p for p in roster
                if contribs[p] is not fed.MISSING and live.get(p) != DEAD
            ])
            time.sleep(0.2)
    except BaseException:
        if party == "alice" and len(per_round) >= crash_round - 1:
            os._exit(0)  # expected death throes after the injected crash
        raise
    if party == "alice":
        raise AssertionError("alice survived its own crash schedule")
    if party == "bob":
        mgr = get_membership_manager()
        stats = fed.membership_stats()
        failed_over = (
            mgr.coordinator() == "bob"
            and stats.get("term", 0) >= 1
            and stats.get("takeovers", 0) >= 1
        )
        with open(result_path, "w") as f:
            json.dump({
                "coordinator_failover_ms": max_sync_ms,
                "ha_rounds_lost": sum(1 for c in per_round if not c),
                "ha_failed_over": int(failed_over),
                "ha_rounds": rounds,
            }, f)
    fed.shutdown()


_WAN3 = ("alice", "bob", "carol")


def _wan_party(party, addresses, transport, result_path, rounds):
    """WAN-emulation stage (docs/resilience.md): a 3-party FedAvg where
    every edge rides a netem-style emulated 50ms/100Mbit link (the
    in-proxy LinkProfile shaper — deterministic latency + token-bucket
    pacing, no root netem needed), with frame crc and adaptive deadlines
    on: the self-healing transport's steady-state WAN posture. Headline
    metrics tools/wan_check.py gates:

      wan_round_ms — median FedAvg round latency over the shaped link
                     (floor: ~2 x 50ms one-way latency per round trip).
      link_rtt_ms  — worst per-peer smoothed RTT the LinkHealth
                     estimator converged to (liveness ping round-trips
                     through the shaper): must see the emulated
                     latency, or adaptive deadlines are flying blind.
    """
    import statistics

    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.ops.aggregate import elastic_weighted_mean
    from rayfed_tpu.resilience import linkhealth

    bases = {"alice": 1.0, "bob": 2.0, "carol": 3.0}
    fed.init(
        addresses=addresses,
        party=party,
        config={
            "barrier_on_initializing": True,
            "cross_silo_comm": dict(
                _FAST_RETRY,
                frame_crc=True,
                adaptive_timeouts=True,
                recv_timeout_in_ms=20000,
            ),
            "transport": transport,
            "resilience": {
                "fault_schedule": {
                    "seed": 17,
                    "links": [{"latency_ms": 50, "rate_mbit": 100}],
                },
                "liveness": {
                    "interval_ms": 250, "suspect_after": 4,
                    "dead_after": 8, "timeout_ms": 2000,
                },
            },
        },
        job_name=f"bench-wan-{transport}",
        logging_level="error",
    )

    @fed.remote
    def contrib(base, r):
        # 256KB per contribution: ~2ms of 100Mbit pipe per edge, so the
        # round is latency-bound (the WAN regime), not bandwidth-bound.
        return {"g": np.full((1 << 16,), base * (r + 1), np.float32)}

    per_round_ms = []
    for r in range(rounds):
        t0 = time.perf_counter()
        objs = {p: contrib.party(p).remote(bases[p], r) for p in _WAN3}
        got = fed.get([objs[p] for p in _WAN3], timeout=60.0)
        agg = elastic_weighted_mean(dict(zip(_WAN3, got)))
        assert np.isfinite(np.asarray(agg["g"]).sum())
        if r > 0:  # round 0 pays actor init + first-push setup
            per_round_ms.append((time.perf_counter() - t0) * 1e3)
    _progress(party, "rounds done; shutting down")
    if party == "alice":
        health = linkhealth.get_health().get_stats()
        link_rtt_ms = max(
            (s["srtt_ms"] for s in health.values()), default=0.0
        )
        with open(result_path, "w") as f:
            json.dump({
                "round_ms": statistics.median(per_round_ms),
                "link_rtt_ms": link_rtt_ms,
                "wan_rounds": rounds,
            }, f)
    fed.shutdown()


_OBS3 = ("alice", "bob", "carol")


def _obs_party(party, addresses, transport, result_path, rounds):
    """3-party telemetry-plane stage (docs/observability.md): paired
    telemetry-off / telemetry-on windows of the same tiny-aggregate
    round, toggled at identical program points on every party, measure
    what the metrics registry + agent pushes cost the training loop —
    ``metrics_overhead_pct`` is the median over the pairs, so a host
    regime shift poisons one pair, not the headline. A final
    telemetry-on window lets alice (the collector) scrape its own HTTP
    endpoint: ``fleet_scrape_ms``, the core-series roll call, and the
    cross-party stitched-trace check that tools/obs_check.py gates."""
    import statistics
    import urllib.request

    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu import telemetry
    from rayfed_tpu.federated import fed_aggregate
    from rayfed_tpu.telemetry.config import TelemetryConfig

    job = f"bench-obs-{transport}"
    fed.init(
        addresses=addresses,
        party=party,
        config={"cross_silo_comm": dict(_FAST_RETRY), "transport": transport},
        job_name=job,
        logging_level="error",
    )

    @fed.remote
    def contrib(seed, r):
        rng = np.random.default_rng(seed + r)
        return {"w": rng.standard_normal(2048).astype(np.float32)}

    @fed.remote
    def barrier(x):
        return True

    seeds = {p: i for i, p in enumerate(_OBS3)}

    def window(n):
        # Median per-round ms, not window mean: one GC pause or
        # scheduler hiccup in a 100ms window would otherwise swamp the
        # few-percent effect this stage exists to measure.
        times = []
        for r in range(n):
            t0 = time.perf_counter()
            objs = {
                p: contrib.party(p).remote(seeds[p], r) for p in _OBS3
            }
            agg = fed_aggregate(objs, op="mean")
            fed.get(barrier.party("alice").remote(agg))
            times.append((time.perf_counter() - t0) * 1000.0)
        return statistics.median(times)

    cfg = TelemetryConfig(
        collector="alice", push_interval_ms=250, http_port=0
    )

    _progress(party, "warmup")
    window(max(2, rounds // 4))

    # 5 pairs, order alternating OFF-first / ON-first: a monotone host
    # drift (load ramping up or down across the stage) then biases half
    # the pairs each way and the median cancels it, instead of every
    # pair charging the drift to the on-window.
    off_ms, on_ms = [], []
    for i in range(5):
        _progress(party, f"pair {i}")

        def on_window():
            telemetry.start(job, party, dict(addresses), cfg)
            ms = window(rounds)
            telemetry.stop()
            return ms

        if i % 2 == 0:
            off_ms.append(window(rounds))
            on_ms.append(on_window())
        else:
            on_ms.append(on_window())
            off_ms.append(window(rounds))

    # Scrape window: telemetry back on, a short burst of rounds, then a
    # couple of push intervals of settle time so every party's delta
    # lands before the collector is read.
    _progress(party, "scrape window")
    telemetry.start(job, party, dict(addresses), cfg)
    window(max(2, rounds // 4))
    time.sleep(1.0)

    if party == "alice":
        core = [
            "fed_transport_send_ops_total",
            "fed_transport_recv_ops_total",
            "fed_transport_inline_sends_total",
            "fed_telemetry_pushes_total",
            "fed_telemetry_party_stale",
            "fed_telemetry_fleet_epoch",
            "fed_driver_aggregates_total",
        ]
        url = telemetry.http_url()
        t0 = time.perf_counter()
        with urllib.request.urlopen(url + "/fleet", timeout=10) as resp:
            fleet = json.loads(resp.read().decode("utf-8"))
        fleet_scrape_ms = (time.perf_counter() - t0) * 1000.0
        with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
            text = resp.read().decode("utf-8")
        lines = text.splitlines()
        missing = [
            n for n in core
            if not any(ln.startswith(n) for ln in lines)
        ]
        with urllib.request.urlopen(url + "/trace", timeout=10) as resp:
            trace = json.loads(resp.read().decode("utf-8"))
        stitched = any(
            len({ev["party"] for ev in e["events"]}) >= 2
            for e in trace.get("edges", [])
        )
        overhead = statistics.median(
            (on - off) / off * 100.0 for off, on in zip(off_ms, on_ms)
        )
        with open(result_path, "w") as f:
            json.dump({
                "metrics_overhead_pct": overhead,
                "fleet_scrape_ms": fleet_scrape_ms,
                "obs_off_ms": off_ms,
                "obs_on_ms": on_ms,
                "obs_series_missing": missing,
                "obs_stitched": int(stitched),
                "obs_parties_reporting": len(fleet.get("parties", {})),
            }, f)
    telemetry.stop()
    fed.shutdown()


_SECAGG3 = ("alice", "bob", "carol")


def _secagg_party(party, addresses, transport, result_path, rounds):
    """3-party privacy-plane stage (docs/privacy.md): paired plaintext /
    secure windows of the same integer-valued FedAvg round price the
    masking path (fixed-point encode + pairwise PRNG streams at each
    party, ring unmask at the root) — ``secure_agg_overhead_pct`` is the
    median over the pairs. Every secure round is also bitwise-compared
    against the locally recomputed plaintext fold
    (``secagg_bitwise_equal``: the mask-cancellation witness
    tools/privacy_check.py gates). A final window owner-pushes int8
    error-feedback-quantized trees across the wire and prices them in
    ORIGINAL float bytes per second: ``quantized_push_gbps``."""
    import statistics

    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu import topology as topo
    from rayfed_tpu.federated import fed_aggregate
    from rayfed_tpu.ops.aggregate import reduce_by_plan

    fed.init(
        addresses=addresses,
        party=party,
        config={
            "cross_silo_comm": dict(_FAST_RETRY),
            "transport": transport,
            "privacy": {"secure_aggregation": True, "mask_seed": 97},
        },
        job_name=f"bench-secagg-{transport}",
        logging_level="error",
    )

    leafs = 8192

    def local_tree(seed, r):
        rng = np.random.default_rng(seed * 1000 + r)
        return {"w": rng.integers(-1000, 1000, (leafs,)).astype(np.float32)}

    @fed.remote
    def contrib(seed, r):
        return local_tree(seed, r)

    seeds = {p: i + 1 for i, p in enumerate(_SECAGG3)}
    plan = topo.plan(list(_SECAGG3), "flat")
    bitwise_ok = [True]

    def window(n, secure):
        # Median per-round ms (one GC pause must not swamp the few-
        # percent masking cost); every party fed.gets the aggregate, so
        # the fetch doubles as the round barrier in both windows.
        times = []
        for r in range(n):
            t0 = time.perf_counter()
            objs = {
                p: contrib.party(p).remote(seeds[p], r) for p in _SECAGG3
            }
            val = fed.get(fed_aggregate(objs, op="mean", secure=secure))
            times.append((time.perf_counter() - t0) * 1000.0)
            if secure and party == "alice":
                expect = reduce_by_plan(
                    plan, {p: local_tree(seeds[p], r) for p in _SECAGG3}
                )
                if np.asarray(val["w"]).tobytes() != \
                        np.asarray(expect["w"]).tobytes():
                    bitwise_ok[0] = False
        return statistics.median(times)

    _progress(party, "warmup")
    window(max(2, rounds // 4), secure=False)
    window(max(2, rounds // 4), secure=True)  # seed exchange + jit

    # 5 pairs, alternating plain-first / secure-first so a monotone host
    # drift biases half the pairs each way and the median cancels it.
    plain_ms, secure_ms = [], []
    for i in range(5):
        _progress(party, f"pair {i}")
        if i % 2 == 0:
            plain_ms.append(window(rounds, secure=False))
            secure_ms.append(window(rounds, secure=True))
        else:
            secure_ms.append(window(rounds, secure=True))
            plain_ms.append(window(rounds, secure=False))

    # Quantized-push window: int8 error-feedback trees cross the wire
    # (1/4 the bytes), priced in original float bytes per second.
    _progress(party, "quantized push window")
    push_mb = 32
    push_reps = 4

    @fed.remote
    def make_packed(r):
        n = push_mb * (1 << 20) // 4
        rng = np.random.default_rng(r)
        tree = {"w": rng.standard_normal(n).astype(np.float32)}
        return _secagg_quantizer().quantize("alice", tree)

    @fed.remote
    def sink(packed):
        from rayfed_tpu.privacy.quantize import dequantize_tree

        t = dequantize_tree(packed)
        return float(np.asarray(t["w"]).flat[0])

    fed.get(sink.party("bob").remote(make_packed.party("alice").remote(0)))
    t0 = time.perf_counter()
    for r in range(push_reps):
        fed.get(
            sink.party("bob").remote(make_packed.party("alice").remote(r + 1))
        )
    dt = time.perf_counter() - t0
    quant_gbps = push_reps * push_mb * (1 << 20) / dt / 1e9

    if party == "alice":
        overhead = statistics.median(
            (s - p) / p * 100.0 for p, s in zip(plain_ms, secure_ms)
        )
        with open(result_path, "w") as f:
            json.dump({
                "secure_agg_overhead_pct": overhead,
                "secagg_bitwise_equal": int(bitwise_ok[0]),
                "quantized_push_gbps": quant_gbps,
                "plain_round_ms": plain_ms,
                "secure_round_ms": secure_ms,
            }, f)
    fed.shutdown()


# Executor-process singleton for the quantized-push window: the error-
# feedback residual must persist ACROSS make_packed tasks (that is the
# contract being priced), so it cannot live inside the task closure.
# Built lazily — bench.py must stay importable without rayfed_tpu.
_secagg_ef = None


def _secagg_quantizer():
    global _secagg_ef
    if _secagg_ef is None:
        from rayfed_tpu.privacy.quantize import ErrorFeedbackQuantizer

        _secagg_ef = ErrorFeedbackQuantizer()
    return _secagg_ef


def _try_build_fastwire() -> None:
    """Best-effort build of the native C++ IO lane; the transport falls
    back to pure-Python sockets if this fails."""
    import glob
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    if glob.glob(os.path.join(here, "rayfed_tpu", "_fastwire*.so")):
        return
    try:
        subprocess.run(
            [sys.executable, "setup.py", "build_ext", "--inplace"],
            cwd=here, capture_output=True, timeout=120, check=False,
        )
    except Exception:
        pass


def _train_mfu() -> dict:
    """Flagship train-step tokens/s and MFU on the local accelerator —
    the one stage that needs a chip. It runs only when asked for
    (``python bench.py --train-mfu``), and a stage that was asked for and
    cannot produce its number RAISES, so bench.py exits non-zero (four
    driver rounds lost this number to a "skipped" line and exit 0).

    Runs in a killable subprocess (the parent stays off jax: a process
    that has touched jax holds the chip) supervised by a progress
    watchdog: the child prints ``BACKEND_UP`` once jax's device init
    returns and ``COMPILED`` when the warmup step finishes. A backend
    that never comes up is killed after ``FEDTPU_MFU_BACKEND_DEADLINE``;
    once it is up, a cold-cache XLA compile may use the full hard cap.
    The child keeps its compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else in the checkout's ``.jax_cache``
    (``rayfed_tpu.utils.enable_compilation_cache``)."""
    import subprocess
    import threading

    here = os.path.dirname(os.path.abspath(__file__))
    backend_deadline = int(os.environ.get("FEDTPU_MFU_BACKEND_DEADLINE", 240))
    hard_cap = int(os.environ.get("FEDTPU_MFU_HARD_CAP", 900))
    # Full per-layer remat + Pallas flash attention at batch 12
    # (remat='attn' keeps the attention outputs, but compiles
    # pathologically slowly around the Pallas custom_vjp under scan).
    batch = int(os.environ.get("FEDTPU_MFU_BATCH", 12))
    steps = int(os.environ.get("FEDTPU_MFU_STEPS", 10))
    remat = os.environ.get("FEDTPU_MFU_REMAT", "1")
    remat_arg = "'attn'" if remat == "attn" else str(remat == "1")
    code = (
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(here, 'benchmarks')!r})\n"
        "from contextlib import redirect_stdout\n"
        "from transformer_train_benchmark import FLAGSHIP\n"
        "from transformer_train_benchmark import run as train_run\n"
        "with redirect_stdout(sys.stderr):\n"
        "    r = train_run(FLAGSHIP['d_model'], FLAGSHIP['n_layers'], "
        f"FLAGSHIP['seq'], batch={batch}, steps={steps}, "
        f"vocab=FLAGSHIP['vocab'], remat={remat_arg})\n"
        "print(json.dumps({'train_tokens_per_s': round(r['tokens_per_s']),"
        "'train_mfu': round(r['mfu'], 4),"
        "'train_n_params': r['n_params'], 'train_seq': r['seq'],"
        "'train_platform': r['backend'],"
        "'train_device_kind': r['device_kind'],"
        "'train_devices': r['devices']}))\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-u", "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=here,
    )
    stderr_lines = []

    def _drain():
        for line in proc.stderr:
            stderr_lines.append(line)

    t = threading.Thread(target=_drain, daemon=True)
    t.start()
    t0 = time.monotonic()
    why = None
    while proc.poll() is None:
        elapsed = time.monotonic() - t0
        backend_up = any("BACKEND_UP" in ln for ln in stderr_lines)
        if not backend_up and elapsed > backend_deadline:
            why = f"backend init made no progress in {backend_deadline}s"
            break
        if elapsed > hard_cap:
            why = f"exceeded hard cap {hard_cap}s"
            break
        time.sleep(2.0)
    if why is not None:
        proc.kill()
        proc.wait(timeout=30)
    stdout = proc.stdout.read()
    t.join(timeout=10)
    if why is None and proc.returncode != 0:
        why = f"rc={proc.returncode}: " + "".join(stderr_lines)[-500:]
    if why is not None:
        raise RuntimeError(f"train MFU stage was asked for and failed: {why}")
    return json.loads(stdout.strip().splitlines()[-1])


def main() -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--train-mfu", action="store_true",
        help="also run the flagship train-step stage on the local TPU; "
             "bench.py fails if that stage cannot produce its number",
    )
    args = parser.parse_args()
    _try_build_fastwire()
    mfu = _train_mfu() if args.train_mfu else None
    # The ceiling is PAIRED: each native rep is preceded by a raw-socket
    # window between the same two party processes (see _party_main), so
    # lane and ceiling samples share the host regime they were measured
    # in. On this class of shared VM, loopback throughput swings 2-3x on
    # a seconds timescale — round 4's bracketing probes (minutes away
    # from the stage they calibrated) produced a 77.5% ratio from regime
    # mismatch alone; the paired median ratio is stable.
    native = run_transport("tcp", pair_ceiling=True)
    baseline = run_transport("grpc")
    # Same-host zero-copy shm lane, same workload/processes layout as
    # the tcp stage so tools/shm_check.py can gate the ratio against
    # tcp_loopback_gbps (both keys from this run, same host regime).
    shm_lane = {}
    try:
        _lane_stats(shm_lane, "shm_push_gbps", run_transport("tcp", shm=True))
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        print(f"shm bench skipped: {e!r}", file=sys.stderr)
    tpu_lanes = _try_tpu_lanes()
    result = {
        "metric": "2-party cross-party push throughput, 100MB float32 tensors",
        "value": round(native["max"], 3),
        "unit": "GB/s",
        "vs_baseline_unpaired": round(native["max"] / baseline["max"], 3),
        "value_median": round(native["median"], 3),
        "baseline_grpc_cloudpickle_gbps": round(baseline["max"], 3),
        "rounds": ROUNDS,
        "payload_mb": PAYLOAD_MB,
    }
    if native.get("raw_median"):
        result["loopback_ceiling_gbps"] = round(native["raw_median"], 3)
        result["loopback_ceiling_spread"] = [
            round(x, 3) for x in native["raw_spread"]
        ]
        result["pct_of_ceiling"] = round(
            100.0 * native["paired_ratio_median"], 1
        )
    # Paired vs_baseline: per-pair tcp/grpc window ratios measured
    # seconds apart in the same processes. Best-effort — on failure the
    # unpaired ratio (max-of-run over max-of-run, windows minutes apart)
    # keeps the key populated for continuity.
    try:
        result.update(_run_paired_baseline())
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        print(f"paired baseline skipped: {e!r}", file=sys.stderr)
    result.setdefault("vs_baseline", result["vs_baseline_unpaired"])
    # The socket-lane number the shm gate normalizes by (median: robust
    # to the one lucky rep "max" keeps for continuity).
    result["tcp_loopback_gbps"] = round(native["median"], 3)
    result.update(shm_lane)
    result.update(tpu_lanes)
    result.update(_try_data_plane())
    if mfu:
        result.update(mfu)
    # BASELINE.json configs #1/#3/#4/#5 as driver keys; #1 and #3 also
    # measured on the reference-parity gRPC lane for the ratio.
    result.update(_bench_stage(
        _tiny_party, "per_task_ms", "FEDTPU_BENCH_TINY_ROUNDS", 300,
        [("tcp", "tiny_task_overhead_ms"),
         ("grpc", "tiny_task_overhead_grpc_ms")],
        digits=3,
    ))
    result.update(_bench_stage(
        _fedavg_party, "round_ms", "FEDTPU_BENCH_FEDAVG_ROUNDS", 20,
        [("tcp", "fedavg_round_ms"), ("grpc", "fedavg_round_grpc_ms")],
        cpu_force=True,
    ))
    result.update(_bench_stage(
        _hier4_party, "round_ms", "FEDTPU_BENCH_HIER4_ROUNDS", 20,
        [("tcp", "hier4_round_ms")], cpu_force=True, parties=_HIER4,
        extra_fields={
            "round_ms_median": "hier4_round_ms_median",
            "round_ms_spread": "hier4_round_ms_spread",
        },
    ))
    result.update(_bench_stage(
        _cnn_party, "round_ms", "FEDTPU_BENCH_CNN_ROUNDS", 5,
        [("tcp", "fedavg_cnn_round_ms")], cpu_force=True, timeout_s=420,
    ))
    # Straggler-proof async rounds (docs/async_rounds.md): carol's sends
    # delayed by a seeded fault schedule; sync stalls, buffered-async
    # sustains. tools/async_check.py gates the ratio.
    result.update(_bench_stage(
        _async_party, "async_rounds_s", "FEDTPU_BENCH_ASYNC_ROUNDS", 12,
        [("tcp", "async_rounds_s")], cpu_force=True, parties=_ASYNC3,
        timeout_s=420,
        extra_fields={
            "sync_rounds_s": "sync_rounds_s",
            "async_rounds_s_spread": "async_rounds_s_spread",
            "sync_rounds_s_spread": "sync_rounds_s_spread",
            "async_vs_sync": "async_vs_sync",
        },
    ))
    # Elastic-membership churn (docs/membership.md): dave crash-killed
    # mid-round, liveness-evicted at the next sync, erin joins as its
    # replacement mid-training. tools/churn_check.py gates join latency
    # and rounds lost.
    result.update(_bench_stage(
        _churn_party, "churn_join_ms", "FEDTPU_BENCH_CHURN_ROUNDS", 12,
        [("tcp", "churn_join_ms")], cpu_force=True, parties=_CHURN5,
        timeout_s=300, digits=1,
        extra_fields={
            "churn_rounds_lost": "churn_rounds_lost",
            "churn_replaced": "churn_replaced",
            "churn_epoch": "churn_epoch",
            "churn_entry_round": "churn_entry_round",
            "churn_rounds": "churn_rounds",
        },
    ))
    # Control-plane HA (docs/ha.md): the configured coordinator is
    # crash-killed mid-sync-broadcast; the deterministic successor
    # deposes it at the liveness verdict and takes over the sync point
    # under term 1. tools/ha_check.py gates the failover stall and
    # rounds lost.
    result.update(_bench_stage(
        _ha_party, "coordinator_failover_ms", "FEDTPU_BENCH_HA_ROUNDS", 8,
        [("tcp", "coordinator_failover_ms")], cpu_force=True, parties=_HA3,
        timeout_s=300, digits=1,
        extra_fields={
            "ha_rounds_lost": "ha_rounds_lost",
            "ha_failed_over": "ha_failed_over",
            "ha_rounds": "ha_rounds",
        },
    ))
    # WAN emulation (docs/resilience.md): 3-party FedAvg over an
    # in-proxy 50ms/100Mbit shaped link with frame crc + adaptive
    # deadlines on. tools/wan_check.py gates the round latency and the
    # LinkHealth estimator's convergence on the emulated RTT.
    result.update(_bench_stage(
        _wan_party, "round_ms", "FEDTPU_BENCH_WAN_ROUNDS", 8,
        [("tcp", "wan_round_ms")], cpu_force=True, parties=_WAN3,
        timeout_s=300, digits=1,
        extra_fields={
            "link_rtt_ms": "link_rtt_ms",
            "wan_rounds": "wan_rounds",
        },
    ))
    # Telemetry plane (docs/observability.md): paired on/off windows
    # price the metrics registry + agent pushes; tools/obs_check.py
    # gates the overhead and the collector's fleet/trace endpoints.
    result.update(_bench_stage(
        _obs_party, "metrics_overhead_pct", "FEDTPU_BENCH_OBS_ROUNDS", 60,
        [("tcp", "metrics_overhead_pct")], cpu_force=True, parties=_OBS3,
        timeout_s=420,
        extra_fields={
            "fleet_scrape_ms": "fleet_scrape_ms",
            "obs_stitched": "obs_stitched",
        },
    ))
    # Privacy plane (docs/privacy.md): paired plaintext/secure FedAvg
    # windows price the masking path, every secure round is bitwise-
    # checked against the plaintext fold, and a quantized-push window
    # prices int8 error-feedback trees on the wire.
    # tools/privacy_check.py gates all three.
    result.update(_bench_stage(
        _secagg_party, "secure_agg_overhead_pct",
        "FEDTPU_BENCH_SECAGG_ROUNDS", 20,
        [("tcp", "secure_agg_overhead_pct")], cpu_force=True,
        parties=_SECAGG3, timeout_s=420,
        extra_fields={
            "secagg_bitwise_equal": "secagg_bitwise_equal",
            "quantized_push_gbps": "quantized_push_gbps",
            "plain_round_ms": "secagg_plain_round_ms",
            "secure_round_ms": "secagg_secure_round_ms",
        },
    ))
    # N-party scale sweep (in-process simulated parties, real wire edges).
    try:
        result.update(_run_scale_sweep())
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        print(f"scale sweep skipped: {e!r}", file=sys.stderr)
    # Tenancy plane: weighted-fair sharing between two jobs on one
    # shared listener + the victim's inline p99 under a noisy neighbor
    # (docs/multitenancy.md; tools/tenant_check.py gates both keys).
    try:
        result.update(_run_tenant_bench())
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        print(f"tenant bench skipped: {e!r}", file=sys.stderr)
    if _DIAGNOSTICS:
        result["diagnostics"] = _DIAGNOSTICS
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
