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

"""On-hardware MFU tuning sweep for the flagship train step.

Run this ON the TPU host:

    python tools/mfu_tune.py            # sweep and print the winner

Each candidate runs in its own subprocess (a chip belongs to one process;
a config that OOMs or wedges must not kill the sweep) with the persistent
compilation cache enabled (``rayfed_tpu.utils.enable_compilation_cache``:
``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's git-ignored
``.jax_cache``) — so the sweep doubles as the cache pre-warm for
bench.py's MFU stage on the same machine. Pass the winner to that stage
through ``FEDTPU_MFU_BATCH`` / ``FEDTPU_MFU_REMAT``."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Candidate grid, cheapest-risk first: the proven r2 config leads, then
# batch pushes (HBM headroom probes), then attn-remat (fast steps, slow
# compile — acceptable here because the sweep's cache warm makes the
# driver's repeat compile free).
CANDIDATES = [
    {"batch": 12, "remat": "1"},
    {"batch": 16, "remat": "1"},
    {"batch": 24, "remat": "1"},
    {"batch": 8, "remat": "1"},
    {"batch": 12, "remat": "attn"},
    {"batch": 16, "remat": "attn"},
]


def run_candidate(cfg: dict, steps: int, timeout_s: int) -> dict | None:
    code = (
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(HERE, 'benchmarks')!r})\n"
        "from transformer_train_benchmark import run\n"
        "from contextlib import redirect_stdout\n"
        "from transformer_train_benchmark import FLAGSHIP\n"
        "remat = CFGREMAT\n"
        "with redirect_stdout(sys.stderr):\n"
        "    r = run(FLAGSHIP['d_model'], FLAGSHIP['n_layers'], "
        f"FLAGSHIP['seq'], batch=CFGBATCH, steps={steps}, "
        "vocab=FLAGSHIP['vocab'], remat=remat)\n"
        "print(json.dumps({'mfu': r['mfu'], 'tokens_per_s': r['tokens_per_s']}))\n"
    ).replace(
        "CFGREMAT", "'attn'" if cfg["remat"] == "attn" else str(cfg["remat"] == "1")
    ).replace("CFGBATCH", str(cfg["batch"]))
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout_s, cwd=HERE,
        )
    except subprocess.TimeoutExpired:
        print(f"  {cfg}: TIMEOUT ({timeout_s}s)", flush=True)
        return None
    if proc.returncode != 0:
        tail = proc.stderr.strip().splitlines()[-1:] or ["?"]
        print(f"  {cfg}: rc={proc.returncode} ({tail[0][:120]})", flush=True)
        return None
    try:
        out = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 - one bad candidate != dead sweep
        print(f"  {cfg}: unparsable output ({e!r})", flush=True)
        return None
    print(
        f"  {cfg}: MFU {out['mfu'] * 100:.1f}% "
        f"({out['tokens_per_s']:,.0f} tok/s)", flush=True,
    )
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=6)
    parser.add_argument("--timeout", type=int, default=900,
                        help="per-candidate budget (cold compiles included)")
    args = parser.parse_args()

    best, best_cfg = None, None
    for cfg in CANDIDATES:
        out = run_candidate(cfg, args.steps, args.timeout)
        if out and (best is None or out["mfu"] > best["mfu"]):
            best, best_cfg = out, cfg
    if best is None:
        print("no candidate completed (accelerator down?)", file=sys.stderr)
        return 1
    print(
        f"winner: {best_cfg} (measured_mfu {best['mfu']:.4f}) — run the "
        f"bench stage with FEDTPU_MFU_BATCH={best_cfg['batch']} "
        f"FEDTPU_MFU_REMAT={best_cfg['remat']} python bench.py --train-mfu"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
