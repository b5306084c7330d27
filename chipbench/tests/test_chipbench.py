"""CPU tests of the benchmark's own harness, at the tiny ``rehearsal``
presets. Run them with ``python -m pytest chipbench/tests -q``; every
subprocess has its own time limit."""

from __future__ import annotations

import gzip
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
FED_CELL = next(c for c in CELLS if "fedround" in c)
SERVE_CELL = next(c for c in CELLS if "fedround" not in c)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(tmp_path, *args, root=ROOT, timeout=240, check=True):
    cmd = [sys.executable, os.path.join(root, "chipbench", "run.py"),
           "--rehearse", "--out", str(tmp_path / "out"), *args]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=timeout, cwd=root)
    if check:
        assert run.returncode == 0, run.stdout[-4000:] + run.stderr[-2000:]
    return run


def last_line(run):
    return json.loads(run.stdout.strip().splitlines()[-1])


def session_members(sid):
    out = subprocess.run(["pgrep", "-s", str(sid)], capture_output=True,
                         text=True)
    return out.stdout.split()


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_the_contracts_line(tmp_path, cell, trace):
    run = run_cell(tmp_path, "--workload", cell, "--seed", "2147483655",
                   "--seconds", "2", "--trace", str(trace))
    line = last_line(run)
    assert set(line) - {"breakdown", "compared"} == KEYS
    # Each number compared beside its limit: the line's last key, and the
    # last lines of standard error.
    assert list(line)[-1] == "compared" and line["compared"]
    for name, c in line["compared"].items():
        assert set(c) == {"value", "limit", "ok"} and c["ok"] is True
        assert f"chipbench: compared {name} = " in run.stderr
    assert run.stderr.strip().splitlines()[-1].startswith(
        "chipbench: correct = True")
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # A CPU run prints no device metric, and its profile has no device
    # plane: busy_s and window_s come from a chip or not at all.
    assert set(line["metrics"]) <= {"setup_s"}
    assert not {"busy_s", "window_s"} & set(line["device"])
    assert "setup_s is made of" in run.stdout


def test_a_party_that_exits_is_diagnosed_and_nothing_survives(tmp_path):
    run = run_cell(tmp_path, "--workload", FED_CELL, "--seed", "3",
                   "--seconds", "3", "--inject", "exit:bob", check=False)
    assert run.returncode == 1
    assert "party bob exited with code 3" in run.stdout
    assert "last 40 lines" in run.stdout and "injected exit" in run.stdout
    assert not run.stdout.strip().splitlines()[-1].startswith("{")
    run_dir = next((tmp_path / "out" / FED_CELL).iterdir())
    assert (run_dir / "FAILED.txt").read_text().startswith("party bob")
    for sid in json.load(open(run_dir / "pids.json")).values():
        assert session_members(sid) == []


def test_sigterm_to_the_launcher_ends_every_child(tmp_path):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
           "--out", str(tmp_path / "out"), "--workload", FED_CELL,
           "--seed", "4", "--seconds", "60"]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    pids_file = None
    for _ in range(300):
        found = list((tmp_path / "out").glob("*/*/pids.json"))
        if found:
            pids_file = found[0]
            break
        time.sleep(0.1)
    assert pids_file is not None
    time.sleep(3.0)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 130 and "interrupted" in out
    for sid in json.load(open(pids_file)).values():
        assert session_members(sid) == []


def test_a_held_port_is_survived(tmp_path):
    with socket.socket() as held, socket.socket() as probe:
        held.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        held.bind(("127.0.0.1", 0))
        held.listen(1)
        probe.bind(("127.0.0.1", 0))
        other = probe.getsockname()[1]
        probe.close()
        ports = f"{held.getsockname()[1]},{other},{other + 1}"
        run = run_cell(tmp_path, "--workload", FED_CELL, "--seed", "5",
                       "--seconds", "2", "--ports", ports)
    assert "Retrying the whole start-up" in run.stdout
    assert last_line(run)["correct"] is True


@pytest.mark.parametrize("cell,fault", [(FED_CELL, "broken-step"),
                                        (SERVE_CELL, "broken-token")])
def test_a_broken_timed_path_is_not_correct(tmp_path, cell, fault):
    """Skips only the look for a chip (--rehearse) and drives the rest of
    a run with the timed path broken underneath: a step that returns its
    state unchanged; a token altered where it is produced."""
    run = run_cell(tmp_path, "--workload", cell, "--seed", "6",
                   "--seconds", "2", "--inject", fault)
    assert last_line(run)["correct"] is False
    assert '"ok": false' in run.stdout


def test_same_seed_same_traffic_other_seed_other_order():
    from chipbench import traffic

    mix = json.load(open(os.path.join(BENCH, "mixes", "chat-steady.json")))
    a = traffic.requests(mix, 11, 1000, 64)
    b = traffic.requests(mix, 11, 1000, 64)
    c = traffic.requests(mix, 12, 1000, 64)
    assert a == b and a != c
    sizes = lambda rs: sorted(len(r["prompt"]) for r in rs)  # noqa: E731
    assert sizes(a) == sizes(c)          # the same work, in another order
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in c)
    due_a, due_c = traffic.arrivals(mix, 11, 64), traffic.arrivals(mix, 12, 64)
    assert list(due_a) == list(traffic.arrivals(mix, 11, 64))
    assert list(due_a) != list(due_c)
    assert abs(due_a[-1] - due_c[-1]) < 1.5 / mix["rate_per_s"] * 8
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert all(lo <= n <= hi for n in sizes(a))


def test_added_by_files_alone(tmp_path):
    """A configuration, a mix, a per-layer reader and a cell are added by
    adding files and one entry each to BENCHMARK.json; nothing that is
    there is edited."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("rayfed_tpu", "native", "setup.py"):
        os.symlink(os.path.join(ROOT, name), root / name)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(root / "chipbench/configs/deepseek-llm-7b.json"))
    cfg["rehearsal"]["num_hidden_layers"] = 3
    json.dump(cfg, open(root / "chipbench/configs/added-model.json", "w"))
    mix = json.load(open(root / "chipbench/mixes/complete-closed16.json"))
    mix["rehearsal"]["clients"] = 3
    json.dump(mix, open(root / "chipbench/mixes/added-mix.json", "w"))
    (root / "chipbench/layers/added_metric.py").write_text(
        "def read(facts):\n    return float(facts['steps'])\n")
    bench["configs"].append({
        "name": "added-model", "source": "https://example.org/added",
        "file": "chipbench/configs/added-model.json",
        "reduced": ["num_hidden_layers"], "why": "added by files"})
    bench["workloads"].append({
        "name": "added-cell", "config": "added-model",
        "traffic": "added-mix", "chips": 1, "why": "added by files"})
    bench["per_layer"].append({
        "name": "added_metric", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_tokens_per_s", "workloads": ["added-cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("added-cell")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    run = run_cell(tmp_path, "--workload", "added-cell", "--seed", "7",
                   "--seconds", "2", "--trace", "1", root=str(root))
    assert '"added_metric"' in run.stdout
    assert last_line(run)["correct"] is True


def test_trace_reduction_on_made_and_recorded_traces():
    from chipbench import trace_reduce

    ms = 1_000_000
    lines = [
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": [
            ["while.1", 0, 10 * ms], ["flash_fwd.3", 0, 2 * ms],
            ["fusion.1", 2 * ms, 2 * ms], ["flash_fwd.3", 6 * ms, 2 * ms],
            ["fusion.2", 12 * ms, 1 * ms]]},
        {"plane": "/host:CPU", "line": "main", "events": [
            ["chipbench:wait_push", 9 * ms, 4 * ms],
            ["outer", 0, 20 * ms]]},
    ]
    out = trace_reduce.reduce(lines, kernels=("flash_fwd",))
    assert out["busy_s"] == pytest.approx(0.011)       # [0,10] and [12,13]
    # PR 35: the window is read from the trace (without the span
    # chipbench:traced it is the device's own 13 ms), not handed in.
    assert out["window_s"] == 0.013 and out["devices"] == 1
    assert out["kernels"] == {"flash_fwd": {"seconds": pytest.approx(0.004),
                                            "calls": 2.0}}
    # PR 38: an operation is named with its scope; a made line has none.
    assert out["device_ops"][0] == ["(no scope):flash_fwd.3",
                                    pytest.approx(0.004)]
    assert all(":while" not in n for n, _ in out["device_ops"])
    assert out["idle_gaps"] == [["chipbench:wait_push",
                                 pytest.approx(0.002)]]
    path = os.path.join(HERE, "data", "fedround_v5e_events.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    out = trace_reduce.reduce(recorded, kernels=(
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    assert out["devices"] == 1
    assert 0 < out["busy_s"] <= out["device_span_s"]
    assert set(out["kernels"]) == {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"}
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


@pytest.mark.parametrize("cell", [FED_CELL, SERVE_CELL, CELLS[-1]])
def test_a_traced_rehearsal_passes_the_programs_record_on(tmp_path, cell):
    """``facts["program"]`` (``common.ProgramRecord``): what the program
    measured of itself over the window, in every kind; an untraced run
    holds none of it."""
    run_cell(tmp_path, "--workload", cell, "--seed", "38", "--seconds", "3",
             "--trace", "1")
    run_dir = next((tmp_path / "out" / cell).iterdir())
    facts = json.load(open(run_dir / "alice.result.json"))["facts"]
    program = facts["program"]
    assert {"phases", "spans"} <= set(program)
    for phase in program["phases"].values():
        assert set(phase) == {"count", "seconds", "max_s"}
    if cell == FED_CELL:
        assert program["rounds"] >= 3
        assert {"fed:wire:encode", "fed:wire:recv", "fed:wire:place",
                "fed:agg:reduce"} <= set(program["phases"])
        recv = [s for s in program["spans"] if s["kind"] == "recv"]
        assert recv and all(s["timed"] and s["nbytes"] >= 1 << 20
                            and s["duration_s"] > 0 for s in recv)
        assert {s["kind"] for s in program["spans"]} >= {"send", "decode"}
    else:
        assert {"fed:serve:build", "fed:serve:dispatch",
                "fed:serve:fetch"} <= set(program["phases"])
        # Every integer counter of stats(), not a chosen few.
        assert {"steps", "steps_ahead", "rows_wasted", "kv_blocks_attended",
                "kv_blocks_slab", "fetch_bytes", "publish_cast_bytes",
                "chunk_blocks_read"} <= set(program["stats"])
        assert 0 < program["stats"]["steps_ahead"] <= program["stats"]["steps"]
        assert program["spans"] == []
    # The readers of the record find what they read, whatever the platform.
    from chipbench.run import load_reader
    names = [m["name"] for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"] if cell in m["workloads"]
        and m["name"].split(".")[0] in (
            "wire_recv_gbps", "wire_encode_ms", "steps_ahead_share",
            "kv_blocks_share", "host_iter_ms")]
    assert len(names) == (2 if cell == FED_CELL else 3)
    for name in names:
        assert load_reader(name)(facts) > 0, name
    run_cell(tmp_path, "--workload", cell, "--seed", "38", "--seconds", "2",
             "--trace", "0", "--out", str(tmp_path / "untraced"))
    run_dir = next((tmp_path / "untraced" / cell).iterdir())
    facts = json.load(open(run_dir / "alice.result.json"))["facts"]
    assert facts["program"] is None
    assert [load_reader(name)(facts) for name in names] == [None] * len(names)


def test_flops_against_hand_worked_counts():
    from chipbench import flops

    coder = json.load(open(os.path.join(BENCH, "configs",
                                        "deepseek-coder-1.3b.json")))
    llm = json.load(open(os.path.join(BENCH, "configs",
                                      "deepseek-llm-7b.json")))
    c = flops.param_counts(coder)
    assert c["layer_matmul"] == 4 * 2048 * 2048 + 3 * 2048 * 5504 == 50593792
    assert c["embed"] + c["head"] == 2 * 32256 * 2048
    assert round(c["total"] / 1e9, 3) == 1.346
    assert round(flops.param_counts(llm)["total"] / 1e9, 2) == 6.91
    # 3.23 GFLOP a token at depth 8 and S 4096 (ISSUE 23); 2.87 at depth 7.
    at8 = dict(coder, num_hidden_layers=8)
    assert flops.train_flops_per_token(at8, 4096) == (
        6 * (8 * 50593792 + 2048 * 32256) + 12 * 8 * 2048 * 4096 * 0.5)
    assert round(flops.train_flops_per_token(at8, 4096) / 1e9, 2) == 3.23
    ops, nbytes = flops.flash_call("flash_fwd", 32, 4096, 128)
    assert ops == 2 * 2 * 4096 * 4096 * 128 * 0.5 * 32
    assert nbytes == 4 * 4096 * 128 * 2 * 32
    peak = flops.peaks("TPU v5 lite")
    assert flops.least_time(ops, nbytes, peak)[1] == "compute"
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
