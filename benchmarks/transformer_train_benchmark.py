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

"""Flagship-model training throughput + MFU on the local accelerator.

Measures tokens/second and model-FLOPs utilization for the transformer LM
train step (bf16 compute, f32 params/optimizer) — the party-local compute
half of federated training, complementing the cross-party transport
benchmarks. On TPU the step uses the Pallas flash-attention kernel and
per-layer rematerialization by default.

Model FLOPs per token = 6*N + 12*L*d_model*S*0.5 (causal attention),
the standard accounting (PaLM appendix B convention). Peak chip FLOPs for
the MFU denominator is looked up by ``device_kind`` in
:data:`PEAK_BF16_TFLOPS`; a device that is not in the table is an error,
not a default (so the benchmark fails on the CPU before it measures).

Usage: python benchmarks/transformer_train_benchmark.py [d_model] [layers] [seq]
Env: REMAT=0/1 (default 1 on TPU), ATTN=auto|flash|xla, BATCH, STEPS.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# The flagship measurement shape shared by bench.py's MFU stage,
# tools/mfu_tune.py and chip_smoke.py (which cuts only its depth).
FLAGSHIP = {"d_model": 2048, "n_layers": 12, "seq": 2048, "vocab": 32768}


# Published bf16 peak of one chip, keyed by ``jax.devices()[0].device_kind``
# as the installation reports it. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16).
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def peak_tflops_for(device_kind: str) -> float:
    try:
        return PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}; known: "
            f"{sorted(PEAK_BF16_TFLOPS)} — add it with its source, do not "
            "default it"
        ) from None


def run(d_model=512, n_layers=8, seq=1024, batch=8, steps=20, remat=None,
        attn="auto", vocab=8192):
    from rayfed_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from rayfed_tpu.models import transformer as tfm
    from rayfed_tpu.parallel import sharding as shd
    from rayfed_tpu.parallel.train import make_fed_train_step
    from rayfed_tpu.utils import is_tpu_backend

    on_tpu = is_tpu_backend()
    # Progress marker: a supervising process (bench.py's watchdog) reads
    # this to distinguish "wedged accelerator" from "long XLA compile".
    print(f"BACKEND_UP {jax.default_backend()}", flush=True)
    if remat is None:
        remat = on_tpu  # memory-for-FLOPs is the right default on the chip

    # head_dim 128 fills the TPU's 128-lane tiling exactly — head_dim 64
    # arrays get lane-padded 2x in HBM (memory AND bandwidth waste).
    cfg = tfm.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=max(2, d_model // 128),
        n_layers=n_layers, d_ff=int(d_model * 2.75) // 16 * 16,
    )
    devices = jax.devices()
    peak_tflops = peak_tflops_for(devices[0].device_kind)
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("data",))
    init_fn, step_fn = make_fed_train_step(
        cfg, mesh, party_axis=None, data_axis="data", lr=1e-3, remat=remat,
        attn=attn,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (batch, seq + 1), 0, cfg.vocab
    )
    sharding = NamedSharding(mesh, shd.batch_spec(mesh, party_axis=None))
    inputs = jax.device_put(tokens[:, :-1], sharding)
    targets = jax.device_put(tokens[:, 1:], sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(0), inputs)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # PaLM appendix-B convention: the embedding table is a gather, not a
    # matmul — excluded from the 6N FLOPs term (lm_head stays in).
    n_matmul_params = n_params - params["embed"].size
    # Warmup/compile.
    t_c = time.perf_counter()
    params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
    float(loss)
    print(f"COMPILED {time.perf_counter() - t_c:.1f}s", flush=True)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, inputs, targets)
    loss = float(loss)
    dt = time.perf_counter() - t0
    tok_s = steps * batch * seq / dt
    # 6N covers fwd+bwd matmuls on the params; the attention term is
    # 12*L*d*S per token halved for causality.
    flops_per_token = 6 * n_matmul_params + 12 * n_layers * d_model * seq * 0.5
    mfu = tok_s * flops_per_token / (peak_tflops * 1e12 * len(devices))
    result = {
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
        "n_params": n_params,
        "batch": batch,
        "seq": seq,
        "remat": bool(remat),
        "attn": attn,
        "tokens_per_s": tok_s,
        "ms_per_step": dt / steps * 1000,
        "mfu": mfu,
        "peak_tflops": peak_tflops,
        "loss": loss,
    }
    print(
        f"{result['backend']} x{result['devices']}: {n_params/1e6:.1f}M params, "
        f"batch {batch} x seq {seq} (attn={attn}, remat={remat}): "
        f"{tok_s:,.0f} tokens/s ({result['ms_per_step']:.1f} ms/step), "
        f"MFU {mfu*100:.1f}% (peak {peak_tflops} TF/chip), loss {loss:.3f}"
    )
    return result


def main():
    args = [int(a) for a in sys.argv[1:4]]
    remat_env = os.environ.get("REMAT")
    # REMAT accepts 0/1/attn: "attn" = checkpoint layers but save each
    # layer's attention output, so the backward never re-runs the flash
    # kernel (see transformer.hidden_states).
    if remat_env is None:
        remat = None
    elif remat_env == "attn":
        remat = "attn"
    else:
        remat = remat_env == "1"
    run(
        *args,
        batch=int(os.environ.get("BATCH", 8)),
        steps=int(os.environ.get("STEPS", 20)),
        remat=remat,
        attn=os.environ.get("ATTN", "auto"),
        vocab=int(os.environ.get("VOCAB", 8192)),
    )


if __name__ == "__main__":
    main()
