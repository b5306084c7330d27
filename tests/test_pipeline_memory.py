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

"""The 1F1B memory property as a measured number, not a comment.

XLA's compiled ``memory_analysis().temp_size_in_bytes`` is the program's
peak scratch (activation) high-water — deterministic, allocator-free.
GPipe's autodiff-through-the-scan must keep every microbatch's forward
activations alive until its backward, so its peak temp grows linearly
with the microbatch count; the hand-scheduled 1F1B lane stashes only a
ring of O(stage depth) activations (``pipeline.py::schedule_1f1b``), so
its peak temp must stay flat. Full sweep with step times:
``benchmarks/pipeline_memory_benchmark.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.parallel.pipeline import (  # noqa: E402
    make_1f1b_loss_and_grad,
    make_pp_loss_fn,
)


def _temp_bytes(fn, params, inputs, targets):
    compiled = jax.jit(fn).lower(params, inputs, targets).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_1f1b_temp_memory_flat_while_gpipe_grows():
    n_stages = 4
    cfg = tfm.tiny_config(n_layers=4, compute_dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:n_stages]).reshape(n_stages),
                ("stage",))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    temps = {}
    for m in (4, 16):
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (m, 33), 0, cfg.vocab
        )
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        temps[("gpipe", m)] = _temp_bytes(
            jax.value_and_grad(make_pp_loss_fn(cfg, mesh, n_microbatches=m)),
            params, inputs, targets,
        )
        temps[("1f1b", m)] = _temp_bytes(
            make_1f1b_loss_and_grad(cfg, mesh, n_microbatches=m),
            params, inputs, targets,
        )

    gpipe_growth = temps[("gpipe", 16)] / temps[("gpipe", 4)]
    f1b_growth = temps[("1f1b", 16)] / temps[("1f1b", 4)]
    # 4x the microbatches: GPipe's activation high-water must grow
    # substantially; 1F1B's must stay bounded by stage depth.
    assert gpipe_growth > 1.8, temps
    assert f1b_growth < 1.3, temps
    # And at the larger count 1F1B must be the clear winner.
    assert temps[("gpipe", 16)] > 4 * temps[("1f1b", 16)], temps
