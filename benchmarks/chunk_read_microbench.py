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

"""One layer's read of the pool by a 512-token prompt chunk
(``decode.paged_chunk_attention``) at the shapes of the three
long-context cells, the loop against the Pallas kernel, on the chip::

    python benchmarks/chunk_read_microbench.py [--forms dots3-full,...]
        [--contexts 2048,8192,32768] [--trip 1024] [--block-q 512]
        [--calls 20] [--out chiprun_out/chunk_read.jsonl]

Random bfloat16 pools under a scattered block table; a latent pool's
``expand`` is the models' own (``pangu_ultra_moe.expand``); the
selection of dots3's full layers is 2,048 random keys a query. Times are
the host's clock around ``calls`` calls of one jitted layer, the last
awaited. ``peak_share`` is the products the read needs (scores and values
over the keys a query may attend, the chunk's own causal half, and
``expand`` over the context once) at the MXU's published peak, over the
time. ``--trip`` and ``--block-q`` (comma lists) sweep the kernel's trip
and the queries a grid step holds. It needs the chip: the kernel
does not run elsewhere.

What it does not say: the LOOP timed here is one layer alone, and alone
the compiler gives it a faster program than inside a model's chunk
program (dots3's full layer at 8k: 7.4 ms here, 15.8 ms there, where the
kernel reads 6.35 and 5.6: ``PERF.md`` section 6, PR 45). The kernel's
own parameters are this file's to set; what the kernel earns beside the
loop is the cell's to say.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rayfed_tpu.models import decode, pangu_ultra_moe  # noqa: E402

PEAK_FLOPS = 197e12      # one v5e chip, bfloat16 (chipbench/peaks.json)
C, BS = 512, 16
# heads, K/V heads (None: a latent pool of `rank` + `rope` columns kept
# `width` wide), widths of the keys' two parts and of the values, the
# slot's reach, window, keys a query selects
FORMS = {
    "dots3-full": dict(heads=128, rank=512, rope=64, width=640, nope=128,
                       dv=128, reach=33024, window=None, topk=2048),
    "dots3-sliding": dict(heads=64, rank=1024, rope=64, width=1152,
                          nope=192, dv=128, reach=33024, window=513,
                          topk=None),
    "pangu": dict(heads=128, rank=512, rope=64, width=640, nope=128, dv=128,
                  reach=11264, window=None, topk=None),
    "commandaplus-full": dict(heads=128, kv_heads=8, dh=128, reach=12800,
                              window=None, topk=None),
    "commandaplus-sliding": dict(heads=128, kv_heads=8, dh=128, reach=12800,
                                 window=4096, topk=None),
}


def build(form, rng):
    """(operands of one layer's read, needed flops as a function of the
    context)."""
    f = FORMS[form]
    bf = jnp.bfloat16
    n_blocks = f["reach"] // BS
    n_phys = 1 + n_blocks

    def arr(*shape, scale=1.0):
        return jnp.asarray(
            rng.standard_normal(shape, np.float32) * scale, bf)

    table = jnp.asarray(1 + rng.permutation(n_blocks), jnp.int32)
    h = f["heads"]
    if "rank" in f:
        rank, rope, nope, dv = f["rank"], f["rope"], f["nope"], f["dv"]
        pool = arr(1, n_phys, BS, f["width"]).at[..., rank + rope:].set(0)
        wk = arr(rank, h * nope, scale=rank ** -0.5)
        wv = arr(rank, h * dv, scale=rank ** -0.5)

        dims = types.SimpleNamespace(
            compute_dtype=bf, kv_rank=rank, cache_width=rank + rope,
            n_heads=h, d_nope=nope, d_rope=rope, d_v=dv)

        def expand(rows, wk, wv):
            return pangu_ultra_moe.expand(
                rows, {"wk_b": wk, "wv_b": wv}, dims)

        own = arr(C, 1, f["width"]).at[..., rank + rope:].set(0)
        ops = dict(pk=pool, pv=None, expand=expand, weights=(wk, wv),
                   kv=expand(own, wk, wv), q=arr(C, h, nope + rope))
        d_qk, hk = nope + rope, h
        expand_flops = 2 * rank * h * (nope + dv)
    else:
        hk, dh = f["kv_heads"], f["dh"]
        ops = dict(pk=arr(1, n_phys, BS, hk, dh), pv=arr(1, n_phys, BS, hk, dh),
                   expand=None, weights=(),
                   kv=(arr(C, hk, dh), arr(C, hk, dh)),
                   q=arr(C, h, dh))
        d_qk = dv = dh
        expand_flops = 0
    ops.update(table=table, window=f["window"], topk=f["topk"],
               reach=f["reach"])

    def flops(context):
        attended = context
        if f["window"] is not None:
            attended = min(context, f["window"] - 1)
        if f["topk"] is not None:
            attended = min(context, f["topk"])
        pairs = C * attended + C * (C + 1) // 2
        return 2 * pairs * h * (d_qk + dv) + expand_flops * (
            context if f["window"] is None else min(context, f["window"] + C))

    return ops, flops


def layer(ops, kernel_path):
    """The jitted read of one layer at a runtime offset."""

    def read(pk, pv, table, offset, q, k, v, seen, weights):
        attend = decode.paged_chunk_attention(
            pk, pv, table, offset, jnp.int32(C), window=ops["window"])
        expand = ops["expand"] and (
            lambda rows: ops["expand"](rows, *weights))
        return attend(q, k, v, 0, expand, seen)

    decode.paged_chunk_is_kernel = lambda *a, **kw: kernel_path
    return jax.jit(read)


def selection(ops, context, rng):
    if ops["topk"] is None:
        return None
    width = ops["reach"] + C
    scores = rng.random((C, width), np.float32)
    pos = context + np.arange(C)
    scores[np.arange(width)[None, :] > pos[:, None]] = -1.0
    kth = -np.partition(-scores, ops["topk"] - 1, axis=1)[:, ops["topk"] - 1]
    return jnp.asarray((scores >= np.maximum(kth, 0.0)[:, None]))


def timed(fn, args, calls):
    out = fn(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / calls, out


def rehearse(kernel):
    """Shrink everything and interpret the kernel."""
    global C, BS
    C, BS = 16, 4
    for f in FORMS.values():
        f.update(heads=4, reach=400, topk=f["topk"] and 32,
                 window=f["window"] and 21)
        if "rank" in f:
            f.update(rank=32, rope=8, width=48, nope=16, dv=16)
        else:
            f.update(kv_heads=2, dh=16)
    kernel.KEY_TILE = 8
    compiled = kernel.chunk_trip.__wrapped__
    kernel.chunk_trip = jax.jit(
        lambda *a, **kw: compiled(*a, **kw, interpret=True),
        static_argnames=("scale",))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--contexts", default="2048,8192,32768")
    ap.add_argument("--trip", default=str(decode.CHUNK_KERNEL_TRIP_KEYS))
    ap.add_argument("--block-q", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/chunk_read.jsonl")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes, the kernel in interpret mode: the "
                    "control flow on a CPU; its times mean nothing")
    args = ap.parse_args()
    from rayfed_tpu.ops import paged_chunk_attention as kernel

    if args.rehearse:
        rehearse(kernel)
    elif jax.default_backend() != "tpu":
        raise SystemExit("the kernel runs on a TPU only: "
                         f"backend {jax.default_backend()!r}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    block_qs = [int(x) for x in args.block_q.split(",") if x] or [
        kernel.BLOCK_Q]
    trips = [int(x) for x in args.trip.split(",")]
    with open(args.out, "a") as sink:
        for form in args.forms.split(","):
            rng = np.random.default_rng(45)
            ops, flops = build(form, rng)
            contexts = sorted({min(int(x), ops["reach"] - C) // BS * BS + 5
                               for x in args.contexts.split(",")})
            loop = layer(ops, False)
            base = {}
            for context in contexts:
                seen = selection(ops, context, rng)
                call = (ops["pk"], ops["pv"], ops["table"],
                        jnp.int32(context), ops["q"], *ops["kv"], seen,
                        ops["weights"])
                base[context] = (call, *timed(loop, call, args.calls))
            for trip, block_q in itertools.product(trips, block_qs):
                decode.CHUNK_KERNEL_TRIP_KEYS = trip
                kernel.BLOCK_Q = block_q
                jax.clear_caches()
                fn = layer(ops, True)
                for context in contexts:
                    call, t_loop, want = base[context]
                    t_kernel, got = timed(fn, call, args.calls)
                    gap = float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want.astype(jnp.float32))))
                    line = dict(
                        form=form, context=context, trip=trip,
                        block_q=block_q,
                        loop_ms=t_loop * 1e3,
                        kernel_ms=t_kernel * 1e3,
                        loop_peak_share=flops(context) / PEAK_FLOPS / t_loop,
                        kernel_peak_share=flops(context) / PEAK_FLOPS
                        / t_kernel,
                        widest_gap=gap,
                        widest_output=float(jnp.max(jnp.abs(want))),
                        device=jax.devices()[0].device_kind)
                    print(json.dumps(line), flush=True)
                    sink.write(json.dumps(line) + "\n")
                    sink.flush()


if __name__ == "__main__":
    main()
