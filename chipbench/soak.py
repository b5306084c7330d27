"""Run one cell N times back to back, seeds 0..N-1, and stop at the first
run that exits non-zero or prints no result, showing what that run said.

    python3 chipbench/soak.py --workload <name> --runs 30 --rehearse --seconds 3
    python3 chipbench/soak.py --workload <name> --runs 8        # on the chip

One line per run: seed, exit code, seconds, ``correct`` and the metrics.
A one-in-ten fault is invisible to a handful of runs and near certain in
the driver's two sets of six: soak first, prove afterwards.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--runs", type=int, default=30)
    p.add_argument("--first-seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=None)
    a = p.parse_args(argv)
    for seed in range(a.first_seed, a.first_seed + a.runs):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               a.workload, "--seed", str(seed), "--trace", str(a.trace)]
        if a.seconds is not None:
            cmd += ["--seconds", str(a.seconds)]
        if a.rehearse:
            cmd.append("--rehearse")
        if a.out:
            cmd += ["--out", a.out]
        t0 = time.time()
        run = subprocess.run(cmd, capture_output=True, text=True)
        lines = run.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if run.returncode == 0 else None
        except (IndexError, ValueError):
            result = None
        ok = result is not None and result.get("correct") is True
        print(json.dumps({
            "seed": seed, "exit": run.returncode,
            "seconds": round(time.time() - t0, 1),
            "correct": None if result is None else result["correct"],
            "metrics": None if result is None else {
                k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)
        if not ok:
            print(f"soak: run with seed {seed} failed; what it said:",
                  flush=True)
            print(run.stdout[-12000:], flush=True)
            print(run.stderr[-4000:], file=sys.stderr, flush=True)
            return 1
    print(f"soak: {a.runs} runs of {a.workload}, seeds {a.first_seed}.."
          f"{a.first_seed + a.runs - 1}: every one exited 0 and was correct",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
