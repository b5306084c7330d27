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

"""Data-plane regression gate: striped multi-stream vs device-DMA.

Runs bench.py's 2-party TPU-transport push (real spawned parties, real
sockets) twice — once with ``num_streams`` reactor lanes carrying stripe
frames, once over the device-DMA descriptor lane — and FAILS LOUDLY
(exit 1) when the multi-stream lane no longer beats the DMA lane's
CPU-sim throughput. The DMA lane's bound here is the jax transfer
engine itself, so this gate asks the load-bearing question for the
sharded data plane: does striping across K sockets still out-run the
single-stream engine path it exists to replace? A change that quietly
serializes the stripe lanes (one lane doing all the bytes), breaks the
stripe planner's balancing, or re-adds a full-payload staging copy
turns the build red.

Gating is on the MAX-of-reps of both lanes ("can the code still go this
fast"), measured minutes apart at worst — the ratio budget leaves room
for host-regime swings, and a wall-clock cap turns a hang into a fast
failure instead of a CI-job timeout.

Knobs:

  FEDTPU_DMA_RATIO          default 1.0 — required multistream/dma
                            throughput ratio (the steady-state measured
                            ratio is ~2.5x on the 1-core CI host class;
                            the acceptance bar on a multi-device mesh is
                            2.0 — tighten there).
  FEDTPU_DMA_WALL_BUDGET_S  default 600 — hard cap on the whole check.
"""

from __future__ import annotations

import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import bench  # noqa: E402


def main() -> int:
    ratio_budget = float(os.environ.get("FEDTPU_DMA_RATIO", "1.0"))
    wall_budget_s = float(os.environ.get("FEDTPU_DMA_WALL_BUDGET_S", "600"))
    t0 = time.monotonic()

    with bench._cpu_forced():
        ms = bench.run_transport(
            "tpu", num_streams=bench._MULTISTREAM_LANES
        )
        print(
            f"multistream ({bench._MULTISTREAM_LANES} lanes): "
            f"max={ms['max']:.3f} GB/s median={ms['median']:.3f}",
            flush=True,
        )
        if time.monotonic() - t0 > wall_budget_s:
            print(
                f"DMA GATE WALL-CLOCK BREACH: the multistream stage alone "
                f"ate the {wall_budget_s:.0f}s budget — a hung party or "
                f"stuck dial, not just a slow host.",
                file=sys.stderr,
            )
            return 1
        dma = bench.run_transport("tpu", device_dma=True)
        print(
            f"device-dma: max={dma['max']:.3f} GB/s "
            f"median={dma['median']:.3f}",
            flush=True,
        )

    if time.monotonic() - t0 > wall_budget_s:
        print(
            f"DMA GATE WALL-CLOCK BREACH: {time.monotonic() - t0:.0f}s "
            f"elapsed exceeds the {wall_budget_s:.0f}s budget.",
            file=sys.stderr,
        )
        return 1

    ratio = ms["max"] / dma["max"] if dma["max"] > 0 else float("inf")
    print(
        f"multistream/dma ratio {ratio:.2f} (budget {ratio_budget:.2f})"
    )
    if ratio < ratio_budget:
        print(
            f"DATA-PLANE REGRESSION: multistream_gbps {ms['max']:.3f} is "
            f"only {ratio:.2f}x dma_cpu_gbps {dma['max']:.3f} (budget "
            f"{ratio_budget:.2f}x). The stripe lane is the usual suspect: "
            f"check that num_streams still opens K reactor lanes, that "
            f"serialization.plan_stripes still balances the payload across "
            f"them (stripes split at buffer boundaries — a single-leaf "
            f"payload never stripes), and that the receiver's "
            f"StripeAssembler completes groups instead of timing out.",
            file=sys.stderr,
        )
        return 1
    print(f"dma gate passed in {time.monotonic() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
