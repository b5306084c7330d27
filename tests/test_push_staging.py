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

"""Which thread stages a pushed value, and how (``barriers._capture_for_send``,
``_host_snapshot``). ``fed.get`` does not wait for the staging of what it
broadcasts: it steals the queued producer of an owned object and runs it
inline, as ever, and the value's done-callbacks, the capture of its push among
them, run on its thread: a small value is staged there, in front of ``get``'s
return; the host staging of a large device-resident tree goes to a thread of
its own. And the staging keeps two device -> host transfers of large leaves in
flight, not all of them."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

import rayfed_tpu as fed
from rayfed_tpu.proxy import barriers
from tests.utils import FAST_COMM_CONFIG, run_parties

WAIT = 30
SMALL = 16              # float32 elements
LARGE = 1 << 18         # 1 MiB: the wire's line for a large frame

_go = threading.Event()
_seen = {}


@fed.remote
class Gate:
    def value(self):
        assert _go.wait(WAIT)       # on the actor's lane
        return 1.0


@fed.remote
def produce(x, n):
    _seen["body"] = threading.current_thread().name
    return {"w": jnp.full((n,), x, jnp.float32)}


def _queued_producer(party, n):
    """At ``party``: a ``produce`` task that waits in the pool's queue
    behind workers that are all held, its argument still pending; the
    returned event frees the workers."""
    free = threading.Event()
    if party == "alice":
        from rayfed_tpu._private.global_context import get_global_context

        pool = get_global_context().get_executor()._pool
        for _ in range(pool._max_workers):
            pool.submit(free.wait, WAIT)
    gate = Gate.party("alice").remote()
    obj = produce.party("alice").remote(gate.value.remote(), n)
    if party == "alice":
        snapshot = barriers._host_snapshot

        def recording_snapshot(value):
            _seen["capture"] = threading.current_thread().name
            return snapshot(value)

        barriers._host_snapshot = recording_snapshot
        threading.Timer(0.3, _go.set).start()
    return obj, free


def _run_get_of_a_broadcast_object(party, addresses, n):
    fed.init(addresses=addresses, party=party,
             config={"cross_silo_comm": dict(FAST_COMM_CONFIG)})
    obj, free = _queued_producer(party, n)
    # Every worker is held: the task runs where somebody steals it.
    value = fed.get(obj)
    assert not free.is_set()
    free.set()
    np.testing.assert_array_equal(value["w"], np.full((n,), 1.0, np.float32))
    if party == "alice":
        me = threading.current_thread().name
        assert _seen["body"] == me, _seen
        fed.shutdown()              # the push is drained: the capture ran
        if n == LARGE:
            assert _seen["capture"] == "fedtpu-capture", _seen
        else:
            assert _seen["capture"] == me, _seen
    else:
        fed.shutdown()


@pytest.mark.parametrize("n", [SMALL, LARGE], ids=["small", "large"])
def test_get_of_a_broadcast_object_stages_only_a_small_value_itself(n):
    run_parties(_run_get_of_a_broadcast_object, ["alice", "bob"],
                extra_args=(n,), timeout=120)


def _run_get_of_an_object_nobody_is_sent(party, addresses):
    fed.init(addresses=addresses, party=party,
             config={"cross_silo_comm": dict(FAST_COMM_CONFIG)})
    obj, free = _queued_producer(party, LARGE)
    # Nobody is sent this value: get steals the queued task and runs it on
    # its own thread; the workers are freed only after it has returned.
    value = fed.get(obj)
    assert not free.is_set()
    free.set()
    np.testing.assert_array_equal(value["w"],
                                  np.full((LARGE,), 1.0, np.float32))
    assert _seen["body"] == threading.current_thread().name, _seen
    assert "capture" not in _seen
    fed.shutdown()


def test_get_of_an_object_nobody_is_sent_still_steals():
    run_parties(_run_get_of_an_object_nobody_is_sent, ["alice"], timeout=120)


def test_a_lanes_own_result_is_captured_on_the_lane_whatever_its_size():
    """The donation guarantee: a lane never steals its own product, so a
    large tree resolved by the lane is staged before the lane moves on."""
    from concurrent.futures import Future

    staged_on = []
    snapshot = barriers._host_snapshot

    def recording_snapshot(value):
        staged_on.append(threading.current_thread().name)
        return snapshot(value)

    class _NoDma:
        def get_proxy_config(self, dest):
            raise KeyError(dest)

    barriers._sender_proxies.set(_NoDma())
    barriers._host_snapshot = recording_snapshot
    try:
        data = Future()
        staged = barriers._capture_for_send("bob", data)
        lane = threading.Thread(
            target=data.set_result, name="a-lane",
            args=({"w": jnp.ones((LARGE,), jnp.float32)},))
        lane.start()
        lane.join(WAIT)
        assert staged.done() and staged_on == ["a-lane"]
        assert isinstance(staged.result()["w"], np.ndarray)
    finally:
        barriers._host_snapshot = snapshot
        barriers._sender_proxies.pop()


class _Leaf:
    """What ``_gather_to_host`` touches of a single-device jax.Array."""

    def __init__(self, i, nbytes, log):
        self.i, self.nbytes, self.log = i, nbytes, log

    def copy_to_host_async(self):
        self.log.append(("start", self.i))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("gather", self.i))
        return np.zeros(1, np.float32)


MB = 1 << 20


@pytest.mark.parametrize("sizes, most_large_in_flight, first_wave", [
    ([300 * MB] * 5, 2, 2),                 # GB-scale leaves: two at a time
    ([MB] * 12, 0, 12),                     # small leaves: one wave
    ([300 * MB, MB, MB, 300 * MB, MB], 1, 3),
    ([40 * MB] * 6, 0, 3),                  # 40 + 40 behind, 40 in the window
    ([300 * MB], 1, 1),
    ([], 0, 0),
], ids=["large", "small", "mixed", "medium", "one", "none"])
def test_transfers_are_started_a_window_ahead_of_the_gather(
        sizes, most_large_in_flight, first_wave):
    log = []
    leaves = [_Leaf(i, n, log) for i, n in enumerate(sizes)]
    got = list(barriers._gather_to_host(leaves))
    assert len(got) == len(sizes)
    starts = [i for what, i in log if what == "start"]
    gathers = [i for what, i in log if what == "gather"]
    assert starts == gathers == list(range(len(sizes)))    # once, in order
    wave = [what for what, _ in log[:first_wave + 1]]
    assert wave == ["start"] * first_wave + ["gather"] * bool(sizes)
    in_flight, most = set(), 0
    for what, i in log:
        if what == "start":
            in_flight.add(i)
        else:
            assert i in in_flight           # started before it is gathered
            in_flight.discard(i)
        large = [k for k in in_flight
                 if sizes[k] > barriers._D2H_AHEAD_BYTES]
        most = max(most, len(large))
    assert most == most_large_in_flight
