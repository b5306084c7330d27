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

"""Transport-matrix tests: the same two-party program over 'tcp', 'grpc'
(reference-parity lane) and 'tpu' (device placement on arrival).
Mirrors ref ``fed/tests/test_transport_proxy.py`` in intent, plus the
transport pluggability of ``fed.init`` (ref api.py:73-75)."""

import numpy as np

import rayfed_tpu as fed
from tests.utils import FAST_COMM_CONFIG, run_parties


@fed.remote
def produce(values):
    return np.asarray(values, dtype=np.float32)


@fed.remote
def aggregate(a, b):
    return a + b


def run_matrix(party, addresses, transport):
    config = {"cross_silo_comm": dict(FAST_COMM_CONFIG), "transport": transport}
    if transport == "grpc":
        # gRPC clamps a channel's retries at 5 (under a second at this
        # policy): a peer whose receiver comes up later than that, on a
        # loaded host, would refuse the first send.
        config["barrier_on_initializing"] = True
    fed.init(addresses=addresses, party=party, config=config)
    a = produce.party("alice").remote([1.0, 2.0])
    b = produce.party("bob").remote([3.0, 4.0])
    total = aggregate.party("bob").remote(a, b)
    np.testing.assert_array_equal(
        fed.get(total), np.array([4.0, 6.0], np.float32)
    )
    fed.shutdown()


def test_tcp_transport():
    run_parties(run_matrix, ["alice", "bob"], extra_args=("tcp",))


def test_grpc_transport():
    run_parties(run_matrix, ["alice", "bob"], extra_args=("grpc",))


def run_tpu_transport(party, addresses):
    # Parties split the 8 simulated devices: alice 0-3, bob 4-7
    # (SURVEY.md §4: parties = processes pinned to disjoint device subsets).
    device_ids = {"alice": [0, 1, 2, 3], "bob": [4, 5, 6, 7]}[party]
    config = {
        "cross_silo_comm": dict(FAST_COMM_CONFIG),
        "transport": "tpu",
        "party_mesh": {"device_ids": device_ids, "axis_names": ["data"]},
    }
    fed.init(addresses=addresses, party=party, config=config)

    import jax

    @fed.remote
    def grads():
        return {"w": np.arange(8.0, dtype=np.float32), "step": 1}

    @fed.remote
    def consume(g):
        # Received arrays must already be jax Arrays on the party mesh.
        assert isinstance(g["w"], jax.Array), type(g["w"])
        assert len(g["w"].sharding.device_set) == 4
        return float(jax.numpy.sum(g["w"]))

    g = grads.party("alice").remote()
    out = consume.party("bob").remote(g)
    assert fed.get(out) == 28.0
    fed.shutdown()


def test_tpu_transport_places_arrays_on_party_mesh():
    run_parties(run_tpu_transport, ["alice", "bob"])


def run_big_payload(party, addresses, transport):
    config = {"cross_silo_comm": dict(FAST_COMM_CONFIG), "transport": transport}
    fed.init(addresses=addresses, party=party, config=config)

    @fed.remote
    def big():
        return np.ones((1024, 1024), dtype=np.float32)  # 4MB

    @fed.remote
    def total(x):
        return float(x.sum())

    assert fed.get(total.party("bob").remote(big.party("alice").remote())) == 1024 * 1024
    fed.shutdown()


def test_big_payload_tcp():
    run_parties(run_big_payload, ["alice", "bob"], extra_args=("tcp",))
