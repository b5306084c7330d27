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

"""The quickest proof that the system still starts on the chip.

Drives the main path once through the public entry points, at the full
width of the dense flagship model: ``fed.init`` -> ``@fed.remote`` trainer
actors on ``make_fed_train_step`` -> ``fed_aggregate(op="mean",
publish_to=handle)`` -> ``fed.serve`` requests under load across the
hot swap -> ``fed.shutdown``. Depth is cut (``--layers``), weights and
data come from seeds, nothing is read from an earlier run.

    python chip_smoke.py                       # one TPU chip (the default)
    python chip_smoke.py --chips-per-party 4   # a four-chip host, one owner
    python chip_smoke.py --chip-parties 2 --chips-per-party 2   # ... split
    python chip_smoke.py --platform cpu --layers 2 --d-model 128 --heads 4 \\
        --d-ff 352 --vocab 512 --seq 64 --batch 2     # CPU rehearsal, tiny

One process per chip set: this launcher never imports jax (a parent that
has touched jax holds the chip). It builds the native wire engine, then
starts one OS process per party, each told its party, addresses and
platform by arguments and environment, waits under a hard time limit and
exits non-zero if any child failed, timed out or printed no result.

* one chip party (the default): ``alice`` owns the chips
  (``JAX_PLATFORMS=<platform>``: a busy or absent chip is an error, never
  a CPU backend), trains, hosts the aggregate and the serving engine.
  ``bob`` is a declared CPU party: it never jits the model, pushes a
  seeded full-width parameter tree as its contribution and originates
  some of the serving requests.
* ``--chip-parties 2``: each party process is restricted to its own
  ``--chips-per-party`` chips before it imports jax, and both train.
* more than one chip per party: the party mesh is data x model.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the chip
party's device as jax reports it. No rate or utilisation is printed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PARTIES = ("alice", "bob")
SERVER = "alice"

# Serving prompts, by origin party: one per prefill bucket (8/16/32) and
# one longer than serving.prefill_chunk (32), which takes the chunked path.
PROMPT_LENGTHS = {"alice": [5, 24], "bob": [12, 40]}
# agg vs NumPy (a+b)/2 on the host: one f32 add (exact rounding on both
# sides) and a division by 2 the chip may do by reciprocal — 1 ulp.
AGG_RTOL = 1e-6
# Engine prefill logits vs tfm.forward, both bf16 compute on the same
# params: the engine pads the prompt to a bucket and reads K/V back
# through the cache, so reductions associate differently. Logits of the
# seeded model have std 0.25; on the chip the two differ by 3e-3 at 12
# layers and bf16 differs from an f32 forward by 9e-3 (PR 21, v5e). A
# wrong position, mask or version is O(0.25) off.
LOGITS_ATOL = 2e-2
# Mosaic flash kernels vs dense f32 attention at matmul precision
# "highest", bf16 inputs, error relative to the largest reference value:
# 4e-3 measured on the chip (PR 21, v5e); bf16 has 8 mantissa bits.
KERNEL_ATOL = 2e-2
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# What a TPU VM's environment says about the host as a whole.
HOST_WIDE_TPU_ENV = (
    "TPU_TOPOLOGY", "TPU_TOPOLOGY_WRAP", "TPU_TOPOLOGY_ALT",
    "TPU_ACCELERATOR_TYPE", "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
    "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "TPU_RUNTIME_METRICS_PORTS",
)
# How two processes share a four-chip v5e host (2x2), by chips per process:
# (TPU_VISIBLE_CHIPS of each process, TPU_CHIPS_PER_PROCESS_BOUNDS). Found
# on the chip (PR 21): a process may own one chip, the ICI pair (0,3) or
# (1,2), or all four. Any other pair — (0,1), (2,3), (0,2), (1,3), with
# either bounds — dies in libtpu with "Mesh build failed, duplicate
# coordinate assignment" or "Mesh build was incomplete, unassigned nodes".
V5E_HOST_SPLITS = {1: (("0", "1"), "1,1,1"), 2: (("0,3", "1,2"), "1,2,1")}
# Waves of requests kept in flight while the aggregate is computed and
# published; a failed aggregate never serves and ends the run here.
MAX_WAVES = 500


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--platform", default="tpu",
                   help="platform the chip parties must come up on")
    p.add_argument("--chips-per-party", type=int, default=1)
    p.add_argument("--chip-parties", type=int, default=1, choices=(1, 2),
                   help="parties that own chips; the rest are CPU parties")
    # Widths are the FLAGSHIP shape of benchmarks/transformer_train_benchmark
    # (with run()'s derivation of heads and d_ff); only depth is cut: at 12
    # layers the aggregating party's trees do not fit one 16 GB chip.
    p.add_argument("--layers", type=int, default=6)
    p.add_argument("--d-model", type=int, default=2048)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--d-ff", type=int, default=5632)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--steps", type=int, default=3,
                   help="train steps after the first (post-compile) one")
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--timeout", type=float, default=1100.0,
                   help="hard limit for the whole run, seconds")
    p.add_argument("--log-dir", default=None,
                   help="also write each party's full output here")
    p.add_argument("--inject-failure", default=None,
                   choices=("train", "aggregate", "serve"),
                   help="make that phase fail (tests: the run must end "
                        "non-zero)")
    # Set by the launcher for its children.
    p.add_argument("--party", default=None, help=argparse.SUPPRESS)
    p.add_argument("--addresses", default=None, help=argparse.SUPPRESS)
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# Launcher (no jax, no rayfed_tpu)
# ---------------------------------------------------------------------------


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _party_env(args, index):
    """Environment of party ``index``: its platform and, on a shared
    host, its own chips — set BEFORE the child imports jax, because a
    process that initialises the TPU backend takes every chip it sees."""
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    n = args.chips_per_party
    platform = args.platform if index < args.chip_parties else "cpu"
    env["JAX_PLATFORMS"] = platform
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    if args.platform == "cpu" and index < args.chip_parties and n > 1:
        flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    if platform == "tpu" and args.chip_parties > 1:
        # The host's own description (topology, bounds, accelerator type
        # of ALL its chips) does not hold for a process that owns some of
        # them: drop it and state this process's chips and bounds only.
        for key in HOST_WIDE_TPU_ENV:
            env.pop(key, None)
        chips, bounds = V5E_HOST_SPLITS[n]
        env.update(
            TPU_VISIBLE_CHIPS=chips[index],
            TPU_CHIPS_PER_PROCESS_BOUNDS=bounds,
            TPU_PROCESS_BOUNDS="1,1,1",
            TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{8476 + index}",
            TPU_MESH_CONTROLLER_PORT=str(8476 + index),
        )
    return env


def _pump(stream, sink, prefix, lines, log):
    for raw in stream:
        line = raw.rstrip("\n")
        lines.append(line)
        print(f"[{prefix}] {line}", file=sink, flush=True)
        if log is not None:
            log.write(raw)
            log.flush()


def launch(args) -> int:
    if (args.platform == "tpu" and args.chip_parties > 1
            and args.chips_per_party not in V5E_HOST_SPLITS):
        print(f"chip_smoke: no known way to give each of two processes "
              f"{args.chips_per_party} chips of one host; known: "
              f"{sorted(V5E_HOST_SPLITS)}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(HERE, "rayfed_tpu")):
        print(f"chip_smoke: no rayfed_tpu package beside {__file__}; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    # The native wire engine is built here, from what git tracks; a build
    # failure fails the smoke (the transport would quietly run in Python).
    build = subprocess.run(
        [sys.executable, "setup.py", "build_ext", "--inplace"],
        cwd=HERE, capture_output=True, text=True, timeout=600,
    )
    if build.returncode != 0:
        print(build.stdout[-2000:], build.stderr[-4000:], file=sys.stderr)
        print("chip_smoke: building rayfed_tpu/_fastwire failed",
              file=sys.stderr)
        return 2
    ports = _free_ports(len(PARTIES))
    addresses = {p: f"127.0.0.1:{port}" for p, port in zip(PARTIES, ports)}
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    procs, outs, pumps, logs = {}, {}, [], []
    deadline = time.monotonic() + args.timeout
    try:
        for i, party in enumerate(PARTIES):
            cmd = [sys.executable, os.path.abspath(__file__),
                   *sys.argv[1:], "--party", party,
                   "--addresses", json.dumps(addresses)]
            proc = subprocess.Popen(
                cmd, cwd=HERE, env=_party_env(args, i), text=True,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                start_new_session=True,
            )
            procs[party] = proc
            outs[party] = []
            log = None
            if args.log_dir:
                log = open(os.path.join(args.log_dir, f"{party}.log"), "w")
                logs.append(log)
            for stream, sink, keep in (
                (proc.stdout, sys.stdout, outs[party]),
                (proc.stderr, sys.stderr, []),
            ):
                t = threading.Thread(
                    target=_pump, args=(stream, sink, party, keep, log),
                    daemon=True,
                )
                t.start()
                pumps.append(t)
        failed = None
        while failed is None and any(
            p.poll() is None for p in procs.values()
        ):
            for party, p in procs.items():
                if p.poll() not in (None, 0):
                    failed = f"party {party} exited with code {p.returncode}"
            if time.monotonic() > deadline:
                failed = f"timed out after {args.timeout:.0f}s"
            time.sleep(0.2)
        for party, p in procs.items():
            if failed is None and p.returncode != 0:
                failed = f"party {party} exited with code {p.returncode}"
    finally:
        for p in procs.values():
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            p.wait()
        for t in pumps:
            t.join(timeout=10)
        for log in logs:
            log.close()
    results = {}
    for party, lines in outs.items():
        for line in lines:
            if line.startswith("RESULT "):
                results[party] = json.loads(line[len("RESULT "):])
    if failed is None:
        missing = [p for p in PARTIES if p not in results]
        if missing:
            failed = f"no result from {missing}"
    if failed is not None:
        print(f"chip_smoke: FAILED: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": results[SERVER]["device"]}))
    return 0


# ---------------------------------------------------------------------------
# Party process (owns its chips alone)
# ---------------------------------------------------------------------------


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


_ME = None  # this process's party, set by party_main


def say(phase, at=None, **evidence):
    """Print one phase's evidence. Both drivers see every value (results
    are broadcast); ``at`` names the one party that prints it."""
    if at not in (None, _ME):
        return
    body = " ".join(f"{k}={v}" for k, v in evidence.items())
    print(f"{phase}: {body}", flush=True)


def _shard_shape(x):
    """What one device holds of ``x``: (global shape, one shard's shape)."""
    return f"{tuple(x.shape)}->{tuple(x.addressable_shards[0].data.shape)}"


def _device_report():
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return out


def _tree_placement(tree):
    """(all leaves are jax.Arrays, platforms, widest device set)."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    arrays = [x for x in leaves if isinstance(x, jax.Array)]
    platforms = sorted({d.platform for x in arrays for d in x.devices()})
    widest = max((len(x.devices()) for x in arrays), default=0)
    return len(arrays) == len(leaves), platforms, widest


def _model_cfg(model):
    from rayfed_tpu.models import transformer as tfm

    return tfm.TransformerConfig(**model)


def _define_tasks(fed):
    """The fed tasks and the trainer actor of the smoke. Defined inside a
    function so that the launcher never imports rayfed_tpu."""
    import numpy as np

    @fed.remote
    class Trainer:
        def __init__(self, model, batch, seq, seed):
            import jax
            from jax.sharding import NamedSharding

            from rayfed_tpu.mesh import get_party_mesh
            from rayfed_tpu.parallel import sharding as shd
            from rayfed_tpu.parallel.train import make_fed_train_step

            self.cfg = _model_cfg(model)
            self.mesh = get_party_mesh()
            # donate=False: params() hands the trained tree to the local
            # aggregate by reference (examples/federated_transformer.py).
            # lr: small enough that four AdamW steps on one fixed batch
            # descend monotonically (at 1e-3 the full-width model
            # memorises the batch in one step and then bounces).
            self._init_fn, self._step_fn = make_fed_train_step(
                self.cfg, self.mesh, party_axis=None, lr=1e-4, remat=True,
                attn="auto", donate=False,
            )
            tokens = np.random.default_rng(seed).integers(
                0, self.cfg.vocab, size=(batch, seq + 1)
            )
            sharding = NamedSharding(
                self.mesh, shd.batch_spec(self.mesh, party_axis=None)
            )
            self.inputs = jax.device_put(tokens[:, :-1], sharding)
            self.targets = jax.device_put(tokens[:, 1:], sharding)
            # Every party starts from the same global model (FedAvg);
            # only the data differs.
            self._params, self._opt_state = self._init_fn(
                jax.random.PRNGKey(0), self.inputs
            )

        def train(self, steps, platform, inject):
            import jax

            if inject:
                raise RuntimeError("injected train failure")
            lowered = self._step_fn.lower(
                self._params, self._opt_state, self.inputs, self.targets)
            kernels = re.findall(
                r'@tpu_custom_call.*?kernel_name = "(\w+)"',
                lowered.as_text(),
            )
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            # What each chip runs: the Mosaic calls' result shapes in the
            # partitioned program, (B_local * H_local, S, Dh).
            per_chip = sorted(set(re.findall(
                r"= \(?(\w+\[[\d,]+\])[^\n]*"
                r'custom_call_target="tpu_custom_call"',
                compiled.as_text(),
            )))
            mem = compiled.memory_analysis()
            losses, step_s = [], []
            for _ in range(steps + 1):
                t0 = time.perf_counter()
                self._params, self._opt_state, loss = compiled(
                    self._params, self._opt_state, self.inputs, self.targets
                )
                losses.append(float(loss))  # waits for the device
                step_s.append(round(time.perf_counter() - t0, 4))
            report = {
                "devices": len(jax.devices()),
                "mesh": dict(self.mesh.shape),
                "kernels": kernels,
                "per_chip_kernel_shapes": per_chip,
                "compile_s": round(compile_s, 2),
                "first_step_s": step_s[0],
                "step_s": step_s[1:],
                "losses": losses,
                "wq_spec": str(self._params["layers"]["wq"].sharding.spec),
                "wq_shard": _shard_shape(self._params["layers"]["wq"]),
                "adam_mu_wq_shard": _shard_shape(
                    self._opt_state[0].mu["layers"]["wq"]),
                # peak_bytes_in_use counts live buffers, not a program's
                # temp: the step itself needs arguments + outputs + temp.
                "xla_step_bytes": {
                    "arguments": mem.argument_size_in_bytes,
                    "outputs": mem.output_size_in_bytes,
                    "temp": mem.temp_size_in_bytes,
                },
                "memory": _device_report(),
            }
            if platform == "tpu":
                report["kernel_vs_dense"] = _flash_vs_dense()
            return report

        def params(self):
            return self._params

    @fed.remote
    def seeded_update(model, seed, inject):
        # The CPU party's contribution: a full-width f32 tree made with
        # NumPy from a seed. Shapes come from eval_shape — nothing is
        # jitted or executed on a device.
        import jax

        from rayfed_tpu.models import transformer as tfm

        if inject:
            raise RuntimeError("injected aggregate failure")
        shapes = jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), _model_cfg(model))
        )
        rng = np.random.default_rng(seed)
        return jax.tree_util.tree_map(
            lambda s: 0.02 * rng.standard_normal(s.shape, dtype=np.float32),
            shapes,
        )

    @fed.remote
    def make_prompts(vocab, seed, lengths):
        rng = np.random.default_rng(seed)
        return [rng.integers(1, vocab, size=n).tolist() for n in lengths]

    @fed.remote
    def engine_probe(name):
        # Device-side facts about the serving engine, read in the process
        # that owns it.
        from rayfed_tpu.serving.server import get_server

        srv = get_server(name)
        version = srv.bank.current_version()
        all_arrays, platforms, widest = _tree_placement(srv.bank.get(version))
        kv_arrays, kv_platforms, kv_widest = _tree_placement(srv.pool.kv)
        return {
            "version": version,
            "params_all_jax_arrays": all_arrays,
            "params_platforms": platforms,
            "params_widest_device_set": widest,
            "kv_all_jax_arrays": kv_arrays,
            "kv_platforms": kv_platforms,
            "kv_widest_device_set": kv_widest,
            "kv_shard": _shard_shape(srv.pool.kv[0]),
            "params_wq_shard": _shard_shape(
                srv.bank.get(version)["layers"]["wq"]),
            "compiled_programs": srv.stats()["compiled_programs"],
            "memory": _device_report(),
        }

    @fed.remote
    def prefill_logits_check(name, prompt):
        # The engine's prefill on the engine's own params, against the
        # plain forward pass on the same params.
        import jax
        import jax.numpy as jnp

        from rayfed_tpu.models import transformer as tfm
        from rayfed_tpu.serving.server import get_server

        srv = get_server(name)
        version = srv.bank.current_version()
        params = srv.bank.get(version)
        plen = len(prompt)
        bucket = next(b for b in srv._buckets if b >= plen)
        rows = srv.pool.max_slots
        prompts = np.zeros((rows, bucket), np.int32)
        prompts[0, :plen] = prompt
        last_idx = np.zeros(rows, np.int32)
        last_idx[0] = plen - 1
        # The model's member the engine's program wraps: the engine's own
        # ends in the choice of the token and returns no logits.
        last, *_ = jax.jit(
            lambda p, toks, idx, landed: srv.model.prefill_rows(
                p, toks, idx, srv.scfg.max_len + 1, srv._cache_dtype, landed)
        )(params, jnp.asarray(prompts), jnp.asarray(last_idx),
          jnp.arange(rows) == 0)
        ref = jax.jit(lambda p, t: tfm.forward(p, t, srv.cfg))(
            params, jnp.asarray([prompt], jnp.int32)
        )[0, -1]
        got, ref = np.asarray(last[0]), np.asarray(ref)
        return {
            "version": version,
            "bucket": bucket,
            "finite": bool(np.isfinite(got).all()),
            "max_abs_diff": float(np.abs(got - ref).max()),
            "ref_abs_max": float(np.abs(ref).max()),
            "argmax_equal": bool(got.argmax() == ref.argmax()),
        }

    return (Trainer, seeded_update, make_prompts, engine_probe,
            prefill_logits_check)


def _flash_vs_dense():
    """The three Mosaic kernels against dense f32 attention, on the chip,
    at a shape that crosses the block diagonal (two 512-blocks)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rayfed_tpu.models import transformer as tfm
    from rayfed_tpu.ops.flash_attention import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    shape = (1, 1024, 2, 128)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False)
        return (out.astype(jnp.float32) * w).sum(), out

    def dense_loss(q, k, v):
        out = tfm.causal_attention(q, k, v)
        return (out * w.astype(jnp.float32)).sum(), out

    got = jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2), has_aux=True))(
        q, k, v
    )
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            jax.value_and_grad(dense_loss, (0, 1, 2), has_aux=True)
        )(*(x.astype(jnp.float32) for x in (q, k, v)))
    (_, out_g), grads_g = got
    (_, out_r), grads_r = ref
    errs = {}
    for name, a, b in zip(
        ("out", "dq", "dk", "dv"), (out_g, *grads_g), (out_r, *grads_r)
    ):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs[name] = round(float(np.abs(a - b).max() / max(
            1.0, np.abs(b).max())), 5)
    return errs


def _come_up(args, party, chip_parties):
    """Bring jax up on the platform the launcher asked for
    (``JAX_PLATFORMS``), print the device before any work, and refuse
    anything else. Returns (device, compile cache dir, cache counters)."""
    from rayfed_tpu.utils import enable_compilation_cache

    asked = os.environ["JAX_PLATFORMS"]

    cache_dir = enable_compilation_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke[{party}]: asked for platform {asked!r}, "
              f"jax found none: {e}", file=sys.stderr)
        sys.exit(3)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    role = "chip" if party in chip_parties else "cpu-pusher"
    say("device", party=party, role=role, platform=device["platform"],
        device_kind=repr(device["kind"]), count=device["count"],
        compile_cache=cache_dir)
    check(device["platform"] == asked,
          f"launcher asked for {asked!r}, jax came up on "
          f"{device['platform']!r}")
    want = args.chips_per_party if party in chip_parties else 1
    check(device["count"] == want,
          f"launcher gave this party {want} device(s), jax sees "
          f"{device['count']}")
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            cache_events["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return device, cache_dir, cache_events


def _fed_init(fed, args, party, addresses, device):
    n_devices = device["count"]
    model_par = 2 if n_devices % 2 == 0 else 1
    fed.init(
        addresses=addresses, party=party, transport="tpu",
        logging_level="warning",
        config={
            "cross_silo_comm": {
                # A flagship-width tree is one multi-GB message; the
                # default cap is 500 MB and the default send timeout 60 s.
                "messages_max_size_in_bytes": 32 << 30,
                "timeout_in_ms": 600_000,
                "retry_policy": {"max_attempts": 120,
                                 "initial_backoff_ms": 500,
                                 "max_backoff_ms": 2000},
            },
            # platform: a party configured for the chip fails here, at
            # fed.init, if its mesh is on anything else.
            "party_mesh": {"mesh_shape": [n_devices // model_par, model_par],
                           "axis_names": ["data", "model"],
                           "platform": os.environ["JAX_PLATFORMS"]},
            "serving": {"max_slots": 8, "max_len": 128,
                        "max_new_tokens": args.max_new},
        },
    )


def _phase_train(fed, args, party, Trainer, model, chip_parties):
    import numpy as np

    workers = {
        p: Trainer.party(p).remote(model, args.batch, args.seq, i)
        for i, p in enumerate(chip_parties)
    }
    reports = fed.get([
        workers[p].train.remote(
            args.steps, args.platform, args.inject_failure == "train")
        for p in chip_parties
    ])
    for p, r in zip(chip_parties, reports):
        losses = r["losses"]
        say("train", at=p, **{k: r[k] for k in (
            "devices", "mesh", "compile_s", "first_step_s", "step_s",
            "losses", "kernels", "per_chip_kernel_shapes", "wq_spec",
            "wq_shard", "adam_mu_wq_shard")})
        say("train-memory", at=p, xla_step_bytes=r["xla_step_bytes"],
            per_device=r["memory"])
        check(len(losses) >= 4, "fewer than 3 steps after the first")
        check(all(np.isfinite(losses)), f"non-finite loss {losses}")
        check(all(b <= a for a, b in zip(losses, losses[1:])),
              f"loss rose on a fixed batch: {losses}")
        if args.platform != "tpu":
            continue
        # attn="auto" must have resolved to the Pallas kernels: all three
        # Mosaic calls in the lowered step, none interpreted (interpret
        # mode and causal_attention lower to plain HLO, not custom calls).
        check(set(FLASH_KERNELS) <= set(r["kernels"]),
              f"Mosaic kernels missing from the train step: {r['kernels']}")
        dp, tp = r["mesh"]["data"], r["mesh"]["model"]
        lead = (args.batch // dp) * (args.heads // tp)
        check(r["per_chip_kernel_shapes"] and all(
            shape.split("[")[1].split(",")[0] == str(lead)
            for shape in r["per_chip_kernel_shapes"]),
            f"a chip runs more than its (batch/{dp}) x (heads/{tp}) shard: "
            f"{r['per_chip_kernel_shapes']}")
        say("train-kernels", at=p, vs_dense_rel_err=r["kernel_vs_dense"],
            atol=KERNEL_ATOL)
        check(max(r["kernel_vs_dense"].values()) <= KERNEL_ATOL,
              f"flash kernels disagree with dense attention: "
              f"{r['kernel_vs_dense']}")
    say("phase", name="train", passed=True)
    return workers


def _phase_serve(fed, args, cfg, tasks, contributions):
    """Engine at alice; version 1 is alice's trained tree; the aggregate
    is computed and hot-published while requests from both parties are in
    flight. Returns (handle, aggregate FedObject)."""
    from rayfed_tpu.federated import fed_aggregate

    _, _, make_prompts, engine_probe, prefill_logits_check = tasks
    handle = fed.serve(SERVER, cfg)
    check(fed.get(handle.publish(contributions[SERVER])) == 1,
          "first publish is not version 1")
    prompts = []
    for i, p in enumerate(PARTIES):
        prompts += fed.get(make_prompts.party(p).remote(
            args.vocab, 100 + i, PROMPT_LENGTHS[p]))
    if args.inject_failure == "serve":
        prompts[0] = prompts[0] * 64  # longer than serving.max_len

    def wave():
        return fed.get([handle.submit(pr, max_new_tokens=args.max_new)
                        for pr in prompts])

    responses = wave() + wave()  # warm-up: every bucket the prompts hit
    warm = fed.get(engine_probe.party(SERVER).remote(handle.name))
    say("serve-warm", at=SERVER, prompt_lengths=[len(p) for p in prompts],
        origins=PROMPT_LENGTHS, compiled_programs=warm["compiled_programs"])

    agg = fed_aggregate(contributions, op="mean", publish_to=handle)
    waves = 0
    while max(r["version"] for r in responses) < 2:
        check(waves < MAX_WAVES, "the published aggregate never served")
        responses += wave()
        waves += 1
    responses += wave()
    hot = fed.get(engine_probe.party(SERVER).remote(handle.name))

    tokens_by_key = {}
    for i, r in enumerate(responses):
        check(len(r["tokens"]) == args.max_new, f"short response {r}")
        key = (r["version"], tuple(prompts[i % len(prompts)]))
        tokens_by_key.setdefault(key, set()).add(tuple(r["tokens"]))
    versions = sorted({v for v, _ in tokens_by_key})
    say("serve", at=SERVER, responses=len(responses),
        waves_during_aggregate=waves,
        responses_by_version={v: sum(r["version"] == v for r in responses)
                              for v in versions},
        compiled_programs_after_warmup=(
            hot["compiled_programs"] - warm["compiled_programs"]))
    check(versions == [1, 2], f"expected versions 1 and 2, got {versions}")
    check(all(len(toks) == 1 for toks in tokens_by_key.values()),
          "one prompt gave different tokens under one version")
    check(hot["compiled_programs"] == warm["compiled_programs"],
          f"the engine compiled after warm-up: {warm['compiled_programs']}"
          f" -> {hot['compiled_programs']} programs")
    say("serve-engine", at=SERVER,
        **{k: v for k, v in hot.items() if k != "memory"})
    say("serve-memory", at=SERVER, per_device=hot["memory"])
    check(hot["version"] == 2, "engine is not on the aggregate")
    check(hot["params_all_jax_arrays"] and hot["kv_all_jax_arrays"],
          "engine params or KV pool are not jax.Arrays")
    check(hot["params_platforms"] == hot["kv_platforms"] == [args.platform],
          f"engine state is on {hot['params_platforms']} / "
          f"{hot['kv_platforms']}, not {args.platform}")
    logits = fed.get(prefill_logits_check.party(SERVER).remote(
        handle.name, prompts[1]))
    say("serve-logits", at=SERVER, atol=LOGITS_ATOL, **logits)
    check(logits["finite"] and logits["max_abs_diff"] <= LOGITS_ATOL,
          f"engine prefill logits disagree with tfm.forward: {logits}")
    say("phase", name="serve", passed=True)
    return handle, agg


def _phase_aggregate(fed, party, device, agg, contributions):
    """On BOTH parties: the aggregate against a NumPy mean of the inputs
    on the host, and where the trees this party holds live."""
    import jax
    import numpy as np

    names = sorted(contributions)
    agg_val, *inputs = fed.get([agg] + [contributions[p] for p in names])
    all_arrays, platforms, widest = _tree_placement(agg_val)
    arrived = inputs[names.index("bob" if party == SERVER else SERVER)]
    arr_arrays, arr_platforms, arr_widest = _tree_placement(arrived)
    max_err, ref_max, digest, nbytes = 0.0, 0.0, 0.0, 0
    leaves = [jax.tree_util.tree_leaves(t) for t in (agg_val, *inputs)]
    for leaf, *parts in zip(*leaves):
        ref = np.asarray(parts[0], np.float32)
        for x in parts[1:]:
            ref = ref + np.asarray(x, np.float32)
        ref = ref / np.float32(len(parts))
        leaf = np.asarray(leaf)
        max_err = max(max_err, float(np.abs(leaf - ref).max()))
        ref_max = max(ref_max, float(np.abs(ref).max()))
        digest += float(leaf.sum(dtype=np.float64))
        nbytes += leaf.nbytes
    tolerance = AGG_RTOL * max(1.0, ref_max)
    say("aggregate", at=party, update_bytes=nbytes, digest=round(digest, 6),
        max_abs_err_vs_numpy_mean=max_err, tolerance=tolerance,
        aggregate_leaves_jax_arrays=all_arrays,
        aggregate_platforms=platforms, aggregate_widest_device_set=widest,
        arrived_leaves_jax_arrays=arr_arrays,
        arrived_platforms=arr_platforms, arrived_widest_device_set=arr_widest)
    check(np.isfinite(digest), "aggregate is not finite")
    check(max_err <= tolerance,
          f"aggregate differs from the NumPy mean by {max_err}")
    check(all_arrays and platforms == [device["platform"]],
          f"aggregate leaves are on {platforms}, not {device['platform']}")
    # Placement on arrival: the peer's tree came over the socket lane and
    # must be on this party's whole mesh, not in host memory.
    check(arr_arrays and arr_platforms == [device["platform"]]
          and arr_widest == device["count"],
          f"the peer's tree arrived on {arr_platforms} over {arr_widest} "
          f"device(s); this party's mesh is {device['count']} x "
          f"{device['platform']}")
    say("phase", name="aggregate", passed=True)


def party_main(args) -> int:
    global _ME
    _ME = party = args.party
    chip_parties = PARTIES[:args.chip_parties]
    device, cache_dir, cache_events = _come_up(args, party, chip_parties)

    import rayfed_tpu as fed
    from benchmarks.transformer_train_benchmark import FLAGSHIP
    from rayfed_tpu.proxy.tcp import sockio

    wire = "native" if sockio._fastwire is not None else "python"
    say("wire", at=party, engine=wire)
    check(wire == "native", "the launcher built _fastwire but the "
          "transport fell back to the Python engine")
    model = dict(vocab=args.vocab, d_model=args.d_model, n_heads=args.heads,
                 n_layers=args.layers, d_ff=args.d_ff)
    cfg = _model_cfg(model)
    say("model", at=SERVER, depth=args.layers,
        flagship_depth=FLAGSHIP["n_layers"], d_model=args.d_model,
        heads=args.heads, head_dim=cfg.head_dim, d_ff=args.d_ff,
        vocab=args.vocab, seq=args.seq, batch=args.batch,
        widths_equal_flagship=(
            args.d_model == FLAGSHIP["d_model"]
            and args.vocab == FLAGSHIP["vocab"]
            and args.seq == FLAGSHIP["seq"]
            and args.heads == max(2, FLAGSHIP["d_model"] // 128)
            and args.d_ff == int(FLAGSHIP["d_model"] * 2.75) // 16 * 16
        ))

    _fed_init(fed, args, party, json.loads(args.addresses), device)
    tasks = _define_tasks(fed)
    Trainer, seeded_update = tasks[:2]
    workers = _phase_train(fed, args, party, Trainer, model, chip_parties)
    contributions = {p: workers[p].params.remote() for p in chip_parties}
    if len(chip_parties) == 1:
        contributions["bob"] = seeded_update.party("bob").remote(
            model, 1234, args.inject_failure == "aggregate")
    handle, agg = _phase_serve(fed, args, cfg, tasks, contributions)
    _phase_aggregate(fed, party, device, agg, contributions)
    check(fed.get(handle.shutdown()) is True, "engine did not stop")
    fed.shutdown()
    say("memory", at=party, per_device=_device_report())
    say("compile-cache", at=party, dir=cache_dir, **cache_events)
    say("phase", name="shutdown", passed=True)
    print("RESULT " + json.dumps({"party": party, "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    _args = parse_args()
    sys.exit(party_main(_args) if _args.party else launch(_args))
