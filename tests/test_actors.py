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

"""Fed actor tests (mirror of ref
``fed/tests/test_pass_fed_objects_in_containers_in_actor.py`` and the actor
paths of ``fed/_private/fed_actor.py``)."""

import numpy as np
import pytest

import rayfed_tpu as fed
from tests.utils import FAST_COMM_CONFIG, run_parties

CONFIG = {"cross_silo_comm": dict(FAST_COMM_CONFIG)}


@fed.remote
class Trainer:
    def __init__(self, scale):
        self.scale = scale
        self.steps = 0

    def train(self, weights):
        self.steps += 1
        return weights * self.scale

    def train_tree(self, payload):
        return {"nested": [payload["nested"][0] * self.scale]}

    def get_steps(self):
        return self.steps


@fed.remote
def make_weights():
    return np.ones(4, dtype=np.float32)


def run_actor_state(party, addresses):
    fed.init(addresses=addresses, party=party, config=CONFIG)
    trainer = Trainer.party("alice").remote(2.0)
    w = make_weights.party("alice").remote()
    w1 = trainer.train.remote(w)
    w2 = trainer.train.remote(w1)
    np.testing.assert_array_equal(fed.get(w2), np.full(4, 4.0, np.float32))
    assert fed.get(trainer.get_steps.remote()) == 2
    fed.shutdown()


def test_actor_state_and_ordering():
    run_parties(run_actor_state, ["alice", "bob"])


def run_cross_party_actor(party, addresses):
    fed.init(addresses=addresses, party=party, config=CONFIG)
    # Actor lives at bob; alice's data feeds it; alice consumes results.
    trainer = Trainer.party("bob").remote(3.0)
    w = make_weights.party("alice").remote()
    out = trainer.train_tree.remote({"nested": [w]})

    @fed.remote
    def unpack(d):
        return d

    # Actor method receives containers holding foreign FedObjects
    # (ref test_pass_fed_objects_in_containers_in_actor.py)... but the
    # container itself crosses: bob resolves alice's w inside the dict.
    with_result = unpack.party("alice").remote(out)
    result = fed.get(with_result)
    np.testing.assert_array_equal(result["nested"][0], np.full(4, 3.0, np.float32))
    fed.shutdown()


def test_cross_party_actor_with_containers():
    run_parties(run_cross_party_actor, ["alice", "bob"])


def run_actor_error(party, addresses):
    fed.init(
        addresses=addresses,
        party=party,
        config={
            "cross_silo_comm": {
                **FAST_COMM_CONFIG,
                "expose_error_trace": True,
            }
        },
    )

    @fed.remote
    class Broken:
        def __init__(self):
            raise RuntimeError("ctor failed")

        def method(self):
            return 1

    b = Broken.party("alice").remote()
    out = b.method.remote()
    if party == "alice":
        with pytest.raises(RuntimeError, match="ctor failed"):
            fed.get(out)
        # Peer waits on our broadcast of `out`; the failed send substitutes
        # a FedRemoteError envelope — give the drain a moment, then leave.
    else:
        with pytest.raises(fed.FedRemoteError):
            fed.get(out)
    fed.shutdown()


def test_actor_constructor_error_propagates():
    run_parties(run_actor_error, ["alice", "bob"])


def run_actor_error_peer_late(party, addresses):
    import time

    if party == "bob":
        # alice fails, gets her own error and shuts down before bob's
        # receiver exists: the envelope that replaces her failed push is
        # still in its connect retries when her drain starts.
        time.sleep(1.0)
    run_actor_error(party, addresses)


def test_error_envelope_outlives_the_producers_shutdown():
    """The envelope is sent from inside the shutdown drain; it used to be
    untracked there (the global context was popped first), dropped with
    the proxies, and the late peer waited forever."""
    run_parties(run_actor_error_peer_late, ["alice", "bob"], timeout=60)


def run_kill(party, addresses):
    import time

    from rayfed_tpu.exceptions import FedActorKilledError

    fed.init(addresses=addresses, party=party, config=CONFIG)

    @fed.remote
    class Slow:
        def work(self, t):
            time.sleep(t)
            return "done"

    s = Slow.party(party).remote()
    first = s.work.remote(0.5)
    queued = s.work.remote(0.0)
    time.sleep(0.1)  # let `first` start executing
    fed.kill(s)
    # Queued-but-unstarted methods fail fast instead of hanging consumers.
    with pytest.raises(FedActorKilledError):
        fed.get(queued)
    # The in-flight call may complete; both outcomes are acceptable.
    try:
        fed.get(first)
    except FedActorKilledError:
        pass
    fed.shutdown()


def test_kill_fails_pending_methods():
    run_parties(run_kill, ["alice"])
