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

"""Federated LM training: each party runs a dp/tp(/sp)-sharded train step
on its own device mesh; weight trees cross per round via the push lane.

Run once per party (CPU simulation shown; on a TPU host each party process
must first be restricted to its own chips — examples/README.md, and
chip_smoke.py is this path at the flagship width on the chip):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/federated_transformer.py alice 127.0.0.1:9111 127.0.0.1:9112
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/federated_transformer.py bob 127.0.0.1:9111 127.0.0.1:9112
"""

import sys

import numpy as np

import rayfed_tpu as fed
from rayfed_tpu.federated import fed_aggregate

ROUNDS = 3


@fed.remote
class LmWorker:
    def __init__(self, seed):
        import jax
        from jax.sharding import Mesh, NamedSharding

        from rayfed_tpu.models import transformer as tfm
        from rayfed_tpu.parallel import sharding as shd
        from rayfed_tpu.parallel.train import make_fed_train_step

        self.cfg = tfm.tiny_config(vocab=512, d_model=128, n_heads=4,
                                   n_layers=2, d_ff=352)
        # Party-local mesh: all local devices, data x model.
        n = jax.device_count()
        model_par = 2 if n % 2 == 0 else 1
        mesh = Mesh(
            np.array(jax.devices()).reshape(n // model_par, model_par),
            ("data", "model"),
        )
        # donate=False: this worker RETURNS self.params each round for
        # local aggregation (fed_aggregate consumes it in-party by
        # reference); donating those buffers into the next step would
        # invalidate them under the consumer (see make_fed_train_step).
        # fedlint FED003 (donation-aliasing) flags the donate=True
        # variant of this pattern — docs/fedlint.md.
        self._init_fn, self._step_fn = make_fed_train_step(
            self.cfg, mesh, party_axis=None, lr=1e-2, donate=False
        )
        rng = np.random.default_rng(seed)
        tokens = rng.integers(0, self.cfg.vocab, size=(8, 65))
        sharding = NamedSharding(mesh, shd.batch_spec(mesh, party_axis=None))
        self.inputs = jax.device_put(tokens[:, :-1], sharding)
        self.targets = jax.device_put(tokens[:, 1:], sharding)
        import jax.random as jrandom

        self.params, self.opt_state = self._init_fn(
            jrandom.PRNGKey(0), self.inputs
        )

    def train(self, global_params):
        if global_params is not None:
            import jax

            self.params = jax.tree_util.tree_map(
                lambda old, new: jax.device_put(new, old.sharding),
                self.params, global_params,
            )
        self.params, self.opt_state, loss = self._step_fn(
            self.params, self.opt_state, self.inputs, self.targets
        )
        self._loss = float(loss)
        return self.params

    def loss(self):
        return self._loss


def main():
    party, addr_a, addr_b = sys.argv[1], sys.argv[2], sys.argv[3]
    fed.init(
        addresses={"alice": addr_a, "bob": addr_b},
        party=party,
        config={
            "cross_silo_comm": {
                "retry_policy": {"max_attempts": 30, "initial_backoff_ms": 500}
            }
        },
    )
    workers = {p: LmWorker.party(p).remote(seed=i)
               for i, p in enumerate(["alice", "bob"])}
    global_params = None
    for r in range(ROUNDS):
        locals_ = {p: workers[p].train.remote(global_params)
                   for p in workers}
        global_params = fed_aggregate(locals_, op="mean")
        my_loss = fed.get(workers[party].loss.remote())
        print(f"[{party}] round {r}: local loss {my_loss:.4f}")
    final = fed.get(global_params)
    digest = float(sum(np.asarray(x).sum() for x in
                       __import__("jax").tree_util.tree_leaves(final)))
    print(f"[{party}] final aggregate digest {digest:.6f}")
    fed.shutdown()


if __name__ == "__main__":
    main()
