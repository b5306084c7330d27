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

"""``chip_smoke.py`` rehearsed on the CPU (on-chip-measurement guide §1).

The same command the chip runs, at tiny size with the CPU asked for
explicitly, from a copy that holds only what the smoke needs from git —
so the native wire engine is built there, by the smoke itself, and the
checkout this suite runs from is left as it was.
"""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--platform", "cpu", "--layers", "2", "--d-model", "128", "--heads",
        "4", "--d-ff", "352", "--vocab", "512", "--seq", "64", "--batch", "2",
        "--timeout", "300"]
PHASES = ("train", "serve", "aggregate", "shutdown")


@pytest.fixture(scope="module")
def smoke_tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("smoke_tree")
    ignore = shutil.ignore_patterns("__pycache__", "*.so", "*.pyc")
    for name in ("rayfed_tpu", "native", "benchmarks"):
        shutil.copytree(os.path.join(REPO, name), root / name, ignore=ignore)
    for name in ("chip_smoke.py", "setup.py"):
        shutil.copy(os.path.join(REPO, name), root / name)
    return root


def _run(cwd, *args, timeout=400):
    env = dict(os.environ)
    # One cache for the suite and the smoke's party processes.
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_test_cache")
    )
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_rehearsal_on_cpu_passes_every_phase(smoke_tree):
    proc = _run(smoke_tree, *TINY)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    for party in ("alice", "bob"):
        for phase in PHASES:
            assert f"[{party}] phase: name={phase} passed=True" in out, out
        # Each party says, before any work, what it came up on.
        assert f"[{party}] device: party={party}" in out, out
    assert "role=cpu-pusher platform=cpu" in out, out
    assert "[alice] wire: engine=native" in out, out
    assert "compiled_programs_after_warmup=0" in out, out
    assert glob.glob(str(smoke_tree / "rayfed_tpu" / "_fastwire*.so"))
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"  # the rehearsal says so
    assert last["device"]["count"] == 1


def test_tpu_demanded_without_a_chip_fails_and_says_what_it_found(smoke_tree):
    if glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*"):
        pytest.skip("this machine has an accelerator")
    proc = _run(smoke_tree, "--timeout", "120")  # defaults: platform tpu
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout), proc.stdout
    assert "asked for platform 'tpu', jax found none" in proc.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(tmp_path, *TINY)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout), proc.stdout


def _assert_injected_failure_ends_nonzero(smoke_tree, phase):
    # No phase's failure may be caught and passed over: the actor error
    # envelope (train), a poisoned contribution (aggregate) and a request
    # the engine rejects (serve) must each end the whole run non-zero.
    proc = _run(smoke_tree, *TINY, "--inject-failure", phase)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout), proc.stdout
    assert "chip_smoke: FAILED" in proc.stderr


def test_injected_train_failure_ends_nonzero(smoke_tree):
    _assert_injected_failure_ends_nonzero(smoke_tree, "train")


@pytest.mark.parametrize("phase", ["aggregate", "serve"])
def test_injected_late_phase_failure_ends_nonzero(smoke_tree, phase):
    _assert_injected_failure_ends_nonzero(smoke_tree, phase)
