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

"""GPipe vs 1F1B: compiled peak temp memory and step time vs microbatch count.

The 1F1B schedule's reason to exist is its memory bound: in-flight
activations per stage stay O(stage depth) regardless of how many
microbatches fill the pipeline, while GPipe's autodiff-through-the-scan
keeps every microbatch's forward activations alive until its backward
runs — so GPipe's activation high-water grows linearly with the
microbatch count (``rayfed_tpu/parallel/pipeline.py:131-150``).

This benchmark turns that claim into numbers using XLA's own accounting:
``jit(...).lower(...).compile().memory_analysis().temp_size_in_bytes`` is
the compiled program's peak scratch (activation) memory, exact and
deterministic — no device allocator sampling, works identically on the
CPU-sim mesh and on TPU. Step wall time is measured too (CPU sim: treat
as smoke, not as a perf claim).

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       JAX_PLATFORMS=cpu python benchmarks/pipeline_memory_benchmark.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    # A CPU-sim benchmark: compiled memory analysis needs no chip.
    os.environ["JAX_PLATFORMS"] = "cpu"


def run(n_stages=4, micro_counts=(4, 8, 16), d_model=64, n_layers=4,
        seq=64, vocab=256, steps=3):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from rayfed_tpu.models import transformer as tfm
    from rayfed_tpu.parallel.pipeline import (
        make_1f1b_loss_and_grad,
        make_pp_loss_fn,
        schedule_1f1b,
    )

    cfg = tfm.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=4, n_layers=n_layers,
        d_ff=d_model * 4, compute_dtype=jnp.float32,
    )
    mesh = Mesh(np.array(jax.devices()[:n_stages]).reshape(n_stages),
                ("stage",))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    rows = []
    for m in micro_counts:
        batch = m  # one sequence per microbatch: isolate schedule memory
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab
        )
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        def measure(fn):
            jitted = jax.jit(fn)
            compiled = jitted.lower(params, inputs, targets).compile()
            mem = compiled.memory_analysis()
            out = jitted(params, inputs, targets)  # warmup
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = jitted(params, inputs, targets)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / steps
            return mem.temp_size_in_bytes, dt

        gpipe_mem, gpipe_dt = measure(
            jax.value_and_grad(make_pp_loss_fn(cfg, mesh, n_microbatches=m))
        )
        f1b_mem, f1b_dt = measure(
            make_1f1b_loss_and_grad(cfg, mesh, n_microbatches=m)
        )
        _, _, _, ring = schedule_1f1b(n_stages, m)
        rows.append({
            "micro": m,
            "gpipe_temp_mb": gpipe_mem / 2**20,
            "f1b_temp_mb": f1b_mem / 2**20,
            "ratio": gpipe_mem / f1b_mem,
            "ring": ring,
            "gpipe_ms": gpipe_dt * 1e3,
            "f1b_ms": f1b_dt * 1e3,
        })
        print(
            f"stages={n_stages} micro={m:3d}: "
            f"GPipe temp {rows[-1]['gpipe_temp_mb']:8.1f} MB, "
            f"1F1B temp {rows[-1]['f1b_temp_mb']:8.1f} MB "
            f"(ring={ring}), ratio {rows[-1]['ratio']:.2f}x | "
            f"step {rows[-1]['gpipe_ms']:.0f} / {rows[-1]['f1b_ms']:.0f} ms",
            flush=True,
        )
    return rows


if __name__ == "__main__":
    run()
