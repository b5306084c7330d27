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

"""Driver-contract regression tests for ``__graft_entry__.py``.

The driver imports ``__graft_entry__`` and calls ``dryrun_multichip(n)``
directly — possibly in a process where jax already came up with one
device and no device-count flag. These tests exercise exactly that call
path: a fresh subprocess whose environment carries no CPU forcing, which
imports jax first and then calls ``dryrun_multichip``. Touching jax and
then re-exec'ing is fine on the CPU and must never be copied to a chip
path (a parent that has touched jax holds the chip).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dirty_env():
    """An env like the driver's: no CPU forcing, no device-count flag."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("_RAYFED_TPU_DRYRUN_CHILD", None)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f
        for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    return env


def test_dryrun_multichip_under_driver_conditions():
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax; jax.devices(); "  # driver may touch jax first
            "import __graft_entry__; "
            "__graft_entry__.dryrun_multichip(8)",
        ],
        cwd=REPO,
        env=_dirty_env(),
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "dryrun_multichip OK" in proc.stdout, proc.stdout
    # The optional sections degrade to "<name> section skipped: ..." on
    # backends that lack them — the CPU sim has them all, so a skip here
    # is a regression (round-3 failure mode: the dma section crashed on a
    # try_register signature change and the dryrun still said OK).
    assert "section skipped" not in proc.stdout, proc.stdout
    assert "dma(pull=True)" in proc.stdout, proc.stdout
    assert "decode(tp-sharded=True)" in proc.stdout, proc.stdout
    # The composed flagship step must also be attested with a real
    # (>1) data axis — at n=8 the primary factoring has data=1, so a
    # second party=2 x data=2 section carries it (VERDICT r4 #5).
    assert "dp-composed(party=2, data=2, loss=" in proc.stdout, proc.stdout
    assert "sp_a2a=True" in proc.stdout, proc.stdout


def test_entry_compiles_and_runs():
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax, numpy as np\n"
            "import __graft_entry__\n"
            "fn, args = __graft_entry__.entry()\n"
            "out = jax.jit(fn)(*args)\n"
            "assert np.all(np.isfinite(np.asarray(out))), 'non-finite'\n"
            "print('ENTRY OK', out.shape)",
        ],
        cwd=REPO,
        env=_dirty_env(),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ENTRY OK" in proc.stdout, proc.stdout
