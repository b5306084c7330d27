"""One cell, once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process tree per run: this launcher never imports jax or
``rayfed_tpu`` (a parent that has touched jax holds the chip). It resolves
the cell's configuration, mix, kind and per-layer readers BY NAME from
``BENCHMARK.json`` and the files under ``chipbench/``, builds the native
wire engine once into the checkout, starts one OS process per party of the
mix, each in its own session, and waits under deadlines. The last line of
its stdout is the one JSON object of the benchmark's contract.

What it guarantees about a run, whatever happens inside it:

* every non-zero exit says why: the failing party, its exit code or the
  deadline that passed, and the last 40 lines of that party's log, printed
  here before exiting; every party's whole output is kept under
  ``<out>/<workload>/<seed>-<pid>/<party>.log``;
* party ports are drawn outside the kernel's ephemeral range (no outbound
  connection can take one) and probed; a party that still dies on
  EADDRINUSE has the whole start-up retried, at most twice, inside set-up;
  nothing else is retried;
* on every exit path (success, failure, deadline, SIGTERM/SIGINT) each
  party's process group is killed and every child waited for before this
  process returns, so the chip is free for the next run;
* no chip, no number: without ``--rehearse`` the parties demand the TPU
  and the run fails if jax comes up on anything else. ``--rehearse`` runs
  the cell's tiny ``rehearsal`` preset on the CPU, names the platform in
  ``device`` and prints no device metric.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TAIL_LINES = 40
# A first run in a checkout compiles every program and may take 1200 s;
# the launcher cannot know which run it is, so its hard limit is the first
# run's, less room to report.
HARD_LIMIT_S = 1120.0
# After every party has written its result: how long engine stop and
# fed.shutdown() may take before the launcher ends them itself.
SHUTDOWN_GRACE_S = 45.0
# Ports for the parties: below the ephemeral range (32768-60999), so the
# parties' own outbound connections can never draw one.
PORT_RANGE = (20000, 32000)
# What a TPU VM's environment says about the host as a whole (dropped for
# a process that owns only some chips), and how two processes share a 2x2
# v5e host: proven on the chip by chip_smoke.py (PR 21), copied here.
HOST_WIDE_TPU_ENV = (
    "TPU_TOPOLOGY", "TPU_TOPOLOGY_WRAP", "TPU_TOPOLOGY_ALT",
    "TPU_ACCELERATOR_TYPE", "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
    "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "TPU_RUNTIME_METRICS_PORTS",
)
V5E_HOST_SPLITS = {1: (("0", "1"), "1,1,1"), 2: (("0,3", "1,2"), "1,2,1")}
# Which two chips share an ICI link is not the same on every host: PR 21's
# host paired (0,3)/(1,2); PR 23's died there with "Mesh build failed,
# duplicate coordinate assignment". A start-up that dies so is retried with
# the next pairing (inside set-up, and said on an earlier line).
V5E_PAIRINGS = (("0,3", "1,2"), ("0,1", "2,3"), ("0,2", "1,3"))


class RunFailed(Exception):
    """A run that ends non-zero, with the cause it prints."""

    def __init__(self, cause, party=None, retry=False):
        super().__init__(cause)
        self.party, self.retry = party, retry


def say(msg):
    print(f"chipbench: {msg}", flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window "
                        "(default: run_seconds of BENCHMARK.json)")
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--rehearse", action="store_true",
                   help="the cell's tiny rehearsal preset on the CPU")
    p.add_argument("--out", default=None,
                   help="where run directories go (default: chiprun_out/)")
    p.add_argument("--control", default=None, choices=("bf16", "fp8"),
                   help="also read the lower-precision control's numbers "
                        "(not part of a benchmark run)")
    p.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                   help="override a key of the mix (sweeps; not part of a "
                        "benchmark run)")
    # Fault injection for the tests: 'exit:<party>' makes that party exit 3
    # mid-window; 'broken-step' / 'broken-token' break the timed path so
    # that `correct` must come out false.
    p.add_argument("--inject", default=None, help=argparse.SUPPRESS)
    p.add_argument("--ports", default=None, help=argparse.SUPPRESS)
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# Resolution by name
# ---------------------------------------------------------------------------


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(workload, rehearse, overrides=()):
    """The cell, its configuration as run, its mix and its metrics, all
    found by the names in BENCHMARK.json."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (known: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(HERE, "mixes", cell["traffic"] + ".json"))
    for item in overrides:
        key, _, value = item.partition("=")
        mix[key] = json.loads(value)
    # The model as run: the published keys, with the layout the mix names
    # laid over them (depth only), or the tiny rehearsal preset.
    model = {k: v for k, v in config.items()
             if k not in ("layouts", "rehearsal", "assumed", "departures",
                          "reduced", "source", "precision", "reference")}
    model.update(config["layouts"][mix["layout"]]["model"])
    if rehearse:
        model.update(config["rehearsal"])
        mix.update(mix.get("rehearsal", {}))
    mix.pop("rehearsal", None)

    def reported_here(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "model": model,
        "reference": config.get("reference", "dense_mha_swiglu"),
        "precision": config.get("precision", {}),
        "mix": mix,
        "run_seconds": bench["run_seconds"],
        "end_to_end": [m for m in bench["end_to_end"] if reported_here(m)],
        "per_layer": [m for m in bench["per_layer"] if reported_here(m)],
    }


def load_reader(name):
    """``chipbench/layers/<name>.py``: one reader per per-layer metric."""
    path = os.path.join(HERE, "layers", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# Processes
# ---------------------------------------------------------------------------


def build_fastwire():
    """Build ``rayfed_tpu/_fastwire`` into the checkout once; later runs
    find it there. A failed build fails the run (the transport would
    quietly fall back to its Python engine)."""
    if not os.path.isdir(os.path.join(ROOT, "rayfed_tpu")):
        raise RunFailed(f"no rayfed_tpu package in {ROOT}: nothing to measure")
    src = os.path.join(ROOT, "native", "fastwire.cc")
    built = glob.glob(os.path.join(ROOT, "rayfed_tpu", "_fastwire*.so"))
    if built and os.path.getmtime(built[0]) >= os.path.getmtime(src):
        return 0.0
    t0 = time.time()
    build = subprocess.run(
        [sys.executable, "setup.py", "build_ext", "--inplace"], cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    if build.returncode != 0:
        print(build.stdout[-2000:], build.stderr[-4000:], flush=True)
        raise RunFailed("building rayfed_tpu/_fastwire failed "
                        f"(exit {build.returncode})")
    return time.time() - t0


def draw_ports(n, taken=()):
    """``n`` free ports outside the ephemeral range, each probed by a
    bind with the listener's own options."""
    rng = random.Random(int.from_bytes(os.urandom(8), "big"))
    ports = []
    for _ in range(2000):
        port = rng.randrange(*PORT_RANGE)
        if port in ports or port in taken:
            continue
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        ports.append(port)
        if len(ports) == n:
            return ports
    raise RunFailed("found no free ports for the parties")


def party_env(party, index_among_chip, n_chip_parties, platform, mesh_ports,
              attempt=0):
    """Environment of one party: its platform and, on a shared host, its
    own chips, set BEFORE the child imports jax (a process that brings the
    TPU backend up takes every chip it sees)."""
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    chips = int(party.get("chips", 1))
    is_chip = party["role"] == "chip"
    env["JAX_PLATFORMS"] = platform if is_chip else "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    if platform == "cpu" and is_chip and chips > 1:
        flags.append(f"--xla_force_host_platform_device_count={chips}")
    env["XLA_FLAGS"] = " ".join(flags)
    if platform == "tpu" and is_chip and n_chip_parties > 1:
        for key in HOST_WIDE_TPU_ENV:
            env.pop(key, None)
        visible, bounds = V5E_HOST_SPLITS[chips]
        if chips == 2:
            visible = V5E_PAIRINGS[attempt % len(V5E_PAIRINGS)]
        port = mesh_ports[index_among_chip]
        env.update(
            TPU_VISIBLE_CHIPS=visible[index_among_chip],
            TPU_CHIPS_PER_PROCESS_BOUNDS=bounds,
            TPU_PROCESS_BOUNDS="1,1,1",
            TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{port}",
            TPU_MESH_CONTROLLER_PORT=str(port),
        )
    return env


def share_cores(parties):
    """Which cores each party may run on. A cross-silo peer is another
    machine; here it is a process on the chip party's host, so it gets
    cores of its own (the last quarter) and does not take the chip
    party's: the cores left are split among the chip parties."""
    cores = sorted(os.sched_getaffinity(0))
    cpu = [p["name"] for p in parties if p["role"] != "chip"]
    chip = [p["name"] for p in parties if p["role"] == "chip"]
    if not cpu or len(cores) < 8:
        return {p["name"]: cores for p in parties}
    cut = len(cores) - max(2, len(cores) // 4)
    out = {name: cores[cut:] for name in cpu}
    per = cut // len(chip)
    for i, name in enumerate(chip):
        out[name] = cores[i * per:(i + 1) * per]
    return out


def tail(path, n=TAIL_LINES):
    try:
        with open(path, errors="replace") as f:
            return f.readlines()[-n:]
    except OSError as e:
        return [f"(no log: {e})\n"]


class Children:
    """The party processes of one attempt. ``close`` kills every process
    group and waits for every child; it is safe to call twice."""

    def __init__(self):
        self.procs, self.logs = {}, {}

    def start(self, name, cmd, env, log_path):
        log = open(log_path, "ab")
        self.logs[name] = (log, log_path)
        self.procs[name] = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.DEVNULL, stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def close(self):
        for proc in self.procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        for proc in self.procs.values():
            proc.wait()
            # A grandchild in the party's session outlives the party: the
            # group is signalled once more after its leader is reaped.
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for log, _ in self.logs.values():
            log.close()


def diagnose(children, run_dir, failure):
    """Item 1: one cause, printed here and kept on disk."""
    say(f"FAILED: {failure}")
    parties = [failure.party] if failure.party else list(children.procs)
    for name in parties:
        proc = children.procs.get(name)
        code = None if proc is None else proc.returncode
        log_path = children.logs[name][1] if name in children.logs else "?"
        say(f"party {name}: exit code {code}; last {TAIL_LINES} lines of "
            f"{os.path.relpath(log_path, ROOT)}:")
        for line in tail(log_path):
            print(f"  [{name}] {line.rstrip()}", flush=True)
    with open(os.path.join(run_dir, "FAILED.txt"), "w") as f:
        f.write(f"{failure}\n")


def one_attempt(args, plan, run_dir, attempt, ports, deadline):
    parties = plan["mix"]["parties"]
    chip_parties = [p for p in parties if p["role"] == "chip"]
    platform = "cpu" if args.rehearse else "tpu"
    n_ports = len(parties) + len(chip_parties)
    if ports is None:
        ports = draw_ports(n_ports)
    addresses = {p["name"]: f"127.0.0.1:{port}"
                 for p, port in zip(parties, ports)}
    spec = dict(
        plan, workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse, control=args.control,
        inject=args.inject, platform=platform, addresses=addresses,
        run_dir=run_dir, t0=T0, root=ROOT, attempt=attempt,
        cores=share_cores(parties),
        hard_deadline=deadline,
    )
    spec_path = os.path.join(run_dir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f, indent=1)
    children = Children()
    results = {}
    failure = None
    try:
        for party in parties:
            name = party["name"]
            idx = chip_parties.index(party) if party in chip_parties else 0
            children.start(
                name,
                [sys.executable, os.path.join(HERE, "party.py"),
                 "--spec", spec_path, "--party", name],
                party_env(party, idx, len(chip_parties), platform,
                          ports[len(parties):], attempt),
                os.path.join(run_dir, f"{name}.log"))
        with open(os.path.join(run_dir, "pids.json"), "w") as f:
            json.dump({n: p.pid for n, p in children.procs.items()}, f)
        results_in = None
        while True:
            now = time.time()
            for name in list(children.procs):
                path = os.path.join(run_dir, f"{name}.result.json")
                if name not in results and os.path.exists(path):
                    results[name] = load_json(path)
            codes = {n: p.poll() for n, p in children.procs.items()}
            for name, code in codes.items():
                if code not in (None, 0) and name not in results:
                    text = "".join(tail(children.logs[name][1], 200))
                    again = next((why for why in (
                        "Address already in use", "failed to bind",
                        "Mesh build failed") if why in text), None)
                    raise RunFailed(
                        f"party {name} exited with code {code} before its "
                        f"result" + (f" ({again})" if again else ""),
                        party=name, retry=again is not None)
                if code == 0 and name not in results and not os.path.exists(
                        os.path.join(run_dir, f"{name}.result.json")):
                    raise RunFailed(f"party {name} exited 0 without a "
                                    f"result", party=name)
            if len(results) == len(parties):
                if results_in is None:
                    results_in = now
                if all(c is not None for c in codes.values()):
                    break
                if now - results_in > SHUTDOWN_GRACE_S:
                    # The measurement is complete and on disk; what hangs
                    # is the program's shutdown. Logged with its cause;
                    # the children are ended below like any others.
                    slow = [n for n, c in codes.items() if c is None]
                    say(f"SHUTDOWN-FAULT: {slow} still alive "
                        f"{SHUTDOWN_GRACE_S:.0f}s after every result was "
                        f"written; ending them. Last lines:")
                    for n in slow:
                        for line in tail(children.logs[n][1], 10):
                            print(f"  [{n}] {line.rstrip()}", flush=True)
                    break
            if now > deadline:
                alive = [n for n, c in codes.items() if c is None]
                raise RunFailed(
                    f"the run's hard limit of {HARD_LIMIT_S:.0f}s passed "
                    f"with {alive} still running and results from "
                    f"{sorted(results)}", party=alive[0] if alive else None)
            time.sleep(0.1)
    except RunFailed as e:
        failure = e
    finally:
        children.close()
    if failure is not None:
        if failure.retry and attempt < 2:
            say(f"attempt {attempt}: {failure}: a port taken between the "
                f"probe and the bind, or a chip pairing this host does not "
                f"link. Retrying the whole start-up (inside set-up, at most "
                f"twice), with new ports and the next pairing.")
            return None
        diagnose(children, run_dir, failure)
        raise failure
    bad = {n: p.returncode for n, p in children.procs.items()
           if p.returncode not in (0, -signal.SIGKILL)}
    if bad:
        say(f"SHUTDOWN-FAULT: exit codes after a complete run: {bad}")
    return results


def main(argv=None):
    args = parse_args(argv)

    def on_signal(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_signal)
    plan = resolve(args.workload, args.rehearse, args.set)
    if args.seconds is None:
        args.seconds = float(plan["run_seconds"])
    out = os.path.abspath(args.out or os.path.join(ROOT, "chiprun_out"))
    run_dir = os.path.join(out, args.workload, f"{args.seed}-{os.getpid()}")
    os.makedirs(run_dir, exist_ok=True)
    deadline = T0 + HARD_LIMIT_S
    try:
        built_s = build_fastwire()
        ports = ([int(x) for x in args.ports.split(",")]
                 if args.ports else None)
        results = None
        for attempt in range(3):
            results = one_attempt(args, plan, run_dir, attempt,
                                  ports if attempt == 0 else None, deadline)
            if results is not None:
                break
    except RunFailed as e:
        if not os.path.exists(os.path.join(run_dir, "FAILED.txt")):
            say(f"FAILED: {e}")
        return 1
    except KeyboardInterrupt as e:
        say(f"FAILED: interrupted ({e}); children ended")
        return 130
    lead = next(p["name"] for p in plan["mix"]["parties"]
                if p["role"] == "chip")
    res = results[lead]
    if built_s:
        say(f"built rayfed_tpu/_fastwire in {built_s:.1f}s (first run in "
            f"this checkout)")
    say("setup_s is made of: " + json.dumps(res.get("setup_parts", {})))
    for check in res.get("checks", []):
        say("check " + json.dumps(check))
    for note in res.get("notes", []):
        say("note " + note)
    facts = res.get("facts", {})
    sys.path.insert(0, ROOT)
    metrics = {}
    if args.trace == 0:
        for m in plan["end_to_end"]:
            if m["name"] in res["end_to_end"]:
                metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in plan["per_layer"]:
            try:
                value = load_reader(m["name"])(facts)
            except KeyError as e:
                if not args.rehearse:
                    raise
                say(f"reader {m['name']} refuses a CPU run: {e}")
                continue
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearse:
        # A CPU run gives no device number: the times above are the
        # host's at a toy size and appear under no device metric's name.
        say("rehearsal on the CPU: the numbers above are not device "
            "numbers: " + json.dumps(metrics))
        metrics = {k: v for k, v in metrics.items() if k == "setup_s"}
    line = {
        "correct": bool(res["correct"]),
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        "metrics": metrics,
        "device": res["device"],
    }
    if args.trace == 1 and res.get("breakdown"):
        line["breakdown"] = res["breakdown"]
    # Each number compared beside its limit: the last key of the result's
    # line and the last lines of standard error, which is what the
    # driver's record keeps of a run that is not correct.
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"],
                            "ok": c["ok"]}
                for c in res.get("checks", [])}
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"chipbench: compared {name} = {c['value']} (limit "
              f"{c['limit']}): {'ok' if c['ok'] else 'OVER ITS LIMIT'}",
              file=sys.stderr)
    print(f"chipbench: correct = {line['correct']} (workload "
          f"{args.workload}, seed {args.seed}, trace {args.trace})",
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
