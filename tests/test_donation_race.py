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

"""Donated-buffer capture semantics: a pushed task result must be
captured at resolution (the reference's object-store snapshot, Ray
serializes a result when the task completes) so the producer may donate
the same buffers to its next jitted step while the asynchronous
cross-party send is still in flight. Regression for a real race
("Array has been deleted") observed in examples/federated_transformer.py
— train-step N's pushed params donated by step N+1 on the same actor."""

import numpy as np

import rayfed_tpu as fed
from tests.utils import FAST_COMM_CONFIG, run_parties

STEPS = 4
N = 4096
# A tree of several leaves over 1 MiB (a timed, segmented frame): the lane
# may not move on before the last leaf is on the host.
N_LARGE = 1 << 19


@fed.remote
class DonatingTrainer:
    """Each step donates the previous step's params into a jitted update
    — the exact pattern that invalidates in-flight send buffers without
    capture-at-resolution."""

    def __init__(self, n=N):
        import jax
        import jax.numpy as jnp

        self.step_fn = jax.jit(
            lambda p: jax.tree_util.tree_map(lambda x: x + 1.0, p),
            donate_argnums=0)
        self.params = (jnp.zeros((n,), jnp.float32) if n == N else
                       {"a": jnp.zeros((n,), jnp.float32),
                        "b": [jnp.zeros((n,), jnp.float32),
                              jnp.zeros((n // 2,), jnp.float32)]})
        _ = jax.block_until_ready(self.params)

    def train(self):
        self.params = self.step_fn(self.params)
        return self.params


@fed.remote
def check(step, tree):
    import jax

    for arr in jax.tree_util.tree_leaves(tree):
        got = np.asarray(arr)
        np.testing.assert_array_equal(
            got, np.full(got.shape, float(step), np.float32))
    return float(got[0])


def run_donation_race(party, addresses, n=N):
    fed.init(
        addresses=addresses, party=party,
        config={"cross_silo_comm": dict(FAST_COMM_CONFIG),
                "transport": "tcp"},
    )
    trainer = DonatingTrainer.party("alice").remote(n)
    outs = []
    for step in range(1, STEPS + 1):
        params = trainer.train.remote()
        # The push to bob races step N+1's donation of the same buffers
        # UNLESS the engine captured the value at resolution; submitting
        # the next train immediately (no fed.get between) keeps the
        # window open on every iteration.
        outs.append(check.party("bob").remote(step, params))
    assert fed.get(outs) == [float(s) for s in range(1, STEPS + 1)]
    fed.shutdown()


def test_pushed_result_survives_producer_donation():
    run_parties(run_donation_race, ["alice", "bob"])


def test_a_large_pushed_tree_survives_a_lane_that_donates_at_once():
    run_parties(run_donation_race, ["alice", "bob"], extra_args=(N_LARGE,))
