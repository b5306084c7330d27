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

"""One decode step of the gated delta rule over a stacked float32 state
at the shape of ``olmohybrid-assist-closed48`` (12 linear layers, 32
slots, 30 heads of 192 x 96), the plain step (``olmo_hybrid.delta_step``
between a slice of the stack and its write-back, as every other backend
runs it) against the Pallas kernel, on the chip::

    python benchmarks/delta_step_microbench.py [--step-bytes 4194304,..]
        [--idle-rows 1] [--calls 20] [--out chiprun_out/delta_step.jsonl]

One jitted program walks the layers (a ``fori_loop``, the layer's ordinal
a runtime value, the state donated and carried) so that a call is twelve
steps. Times are the host's clock around ``calls`` calls, the last
awaited, a layer. ``least_us`` is one read and one write of a layer's
``S`` as the device tiles it (96 columns in 128 lanes) at the chip's
published 819 GB/s; ``counted_least_us`` the same of the bytes as counted
(what ``chipbench/flops_olmo_hybrid`` counts). ``--step-bytes`` (a comma
list) sweeps what a grid step of the kernel holds
(``delta_rule.STEP_BYTES``). It needs the chip: the kernel does not run
elsewhere (``--rehearse``: toy shapes, interpret mode, the control flow
on a CPU; its times mean nothing).

What it does not say: the plain step timed here is one layer's
recurrence alone; inside the decode program the compiler fuses and lays
out around it differently (PERF.md section 6, PR 45's lesson). The
kernel's own parameters are this file's to set; what the kernel earns is
the cell's to say.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rayfed_tpu.models import olmo_hybrid  # noqa: E402

HBM_BYTES_PER_S = 819e9   # one v5e chip (chipbench/peaks.json)


def walk(kernel):
    """The jitted walk of every layer of the stack, one step each."""
    from rayfed_tpu.ops import delta_rule

    def layer(i, carry, live, q, k, v, g, beta):
        delta, acc = carry
        if kernel:
            o, delta = delta_rule.delta_state_step(
                delta, i, live, q, k, v, g, beta)
        else:
            st = jax.lax.dynamic_index_in_dim(delta, i, 0, keepdims=False)
            o, new = olmo_hybrid.delta_step(q, k, v, g, beta, st)
            delta = jax.lax.dynamic_update_index_in_dim(
                delta, jnp.where(live[:, None, None, None], new, st), i, 0)
        return delta, acc + o

    def run(delta, *step):
        return jax.lax.fori_loop(
            0, delta.shape[0], lambda i, c: layer(i, c, *step),
            (delta, jnp.zeros_like(step[3])))

    return jax.jit(run, donate_argnums=0)


def timed(fn, delta, step, calls):
    delta, o = fn(delta, *step)
    o.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(calls):
        delta, o = fn(delta, *step)
    o.block_until_ready()
    return (time.perf_counter() - t0) / calls / delta.shape[0], delta, o


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="12,32,30,192,96",
                    help="layers, rows, heads, dv, dk")
    ap.add_argument("--step-bytes", default="")
    ap.add_argument("--idle-rows", type=int, default=1,
                    help="rows that sit the step out")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/delta_step.jsonl")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from rayfed_tpu.ops import delta_rule

    shape = tuple(int(x) for x in args.shape.split(","))
    if args.rehearse:
        shape = (2, 3, 5, 16, 24)
        compiled = delta_rule.delta_state_step.__wrapped__
        delta_rule.delta_state_step = jax.jit(
            lambda *a: compiled(*a, interpret=True))
    elif jax.default_backend() != "tpu":
        raise SystemExit("the kernel runs on a TPU only: "
                         f"backend {jax.default_backend()!r}")
    _, rows, heads, dv, dk = shape
    rng = np.random.default_rng(49)

    def arr(*s):
        return jnp.asarray(rng.standard_normal(s, np.float32))

    step = (jnp.arange(rows) >= args.idle_rows,
            olmo_hybrid._l2(arr(rows, heads, dk)) * dk ** -0.5,
            olmo_hybrid._l2(arr(rows, heads, dk)), arr(rows, heads, dv),
            -jnp.exp(arr(rows, heads) - 3.0), 2 * jax.nn.sigmoid(
                arr(rows, heads)))
    start = np.asarray(arr(*shape))
    t_plain, want_s, want_o = timed(
        walk(False), jnp.asarray(start), step, args.calls)
    layer_bytes = rows * heads * dv * 4
    least = 2 * layer_bytes * -(-dk // 128) * 128 / HBM_BYTES_PER_S
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as sink:
        for step_bytes in [int(x) for x in args.step_bytes.split(",") if x
                           ] or [delta_rule.STEP_BYTES]:
            delta_rule.STEP_BYTES = step_bytes
            jax.clear_caches()
            t_kernel, got_s, got_o = timed(
                walk(True), jnp.asarray(start), step, args.calls)
            line = dict(
                shape=shape, step_bytes=step_bytes,
                heads_a_step=delta_rule.heads_a_step(heads, dv, dk),
                plain_us=t_plain * 1e6, kernel_us=t_kernel * 1e6,
                least_us=least * 1e6,
                counted_least_us=2 * layer_bytes * dk / HBM_BYTES_PER_S * 1e6,
                kernel_share_of_least=least / t_kernel,
                widest_gap_state=float(jnp.max(jnp.abs(got_s - want_s))),
                # (Of the rows that took the step: the others' ``o`` is
                # discarded by the caller, and zero from the kernel.)
                widest_gap_o=float(jnp.max(jnp.abs(
                    (got_o - want_o)[args.idle_rows:]))),
                widest_o=float(jnp.max(jnp.abs(want_o))),
                device=jax.devices()[0].device_kind)
            print(json.dumps(line), flush=True)
            sink.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
