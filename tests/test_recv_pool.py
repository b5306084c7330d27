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

"""The receive pool's budget follows the traffic (``BufferPool.expect``):
a frame that the budget cannot hold twice raises it to the buffers of two
such frames, as far as the ceiling goes (``FEDTPU_RECV_POOL_MB``, where the
operator set it), so that a GB-scale tree is read into recycled buffers and
not into fresh ones (page faults on every frame); the raise lapses when such
frames stop."""

import gc
import time

import numpy as np
import pytest

from rayfed_tpu.proxy.tcp import reactor, sockio
from rayfed_tpu.proxy.tcp.tcp_proxy import TcpReceiverProxy, TcpSenderProxy
from tests.utils import get_addresses

FAST = {"retry_policy": {"max_attempts": 5, "initial_backoff_ms": 100}}
SEGMENTS = [3000, 3000, 2000]      # one frame of 8000 bytes


def _read_frame(pool, segments=SEGMENTS):
    pool.expect(sum(segments))
    return [pool.take(n) for n in segments]


def _blocks(views):
    return [id(v.base) for v in views]


def _wait_for(cond, what, limit=10.0):
    t_end = time.monotonic() + limit
    while not cond():
        assert time.monotonic() < t_end, what
        time.sleep(0.01)


def test_a_budget_under_one_frame_recycles_nothing_of_it():
    pool = sockio.BufferPool(max_bytes=4096, min_size=16, ceiling=4096)
    first = _blocks(_read_frame(pool))
    gc.collect()
    again = _blocks(_read_frame(pool))
    # The cap evicted most of the first frame's blocks as they were made.
    assert len(set(first) & set(again)) <= 1
    assert pool._max_bytes == 4096 and pool._lapse_timer is None


def test_a_budget_that_follows_the_traffic_holds_two_frames():
    pool = sockio.BufferPool(max_bytes=4096, min_size=16)
    one = _read_frame(pool)
    assert pool._max_bytes == 16000
    two = _read_frame(pool)             # the first is still in use
    assert not set(_blocks(one)) & set(_blocks(two))
    assert pool._total == 16000 and len(pool._entries) == 6
    first = _blocks(one)
    del one
    three = _read_frame(pool)           # the first frame's buffers again
    assert sorted(_blocks(three)) == sorted(first)
    assert pool._total == 16000 and len(pool._entries) == 6
    pool.expect(100)                    # a smaller frame takes nothing away
    assert pool._max_bytes == 16000
    del two, three
    pool.trim()                         # and a transport's stop ends it
    assert pool._entries == [] and pool._total == 0
    assert pool._max_bytes == 4096 and pool._lapse_timer is None


@pytest.mark.parametrize("base, ceiling, frame, budget", [
    (4096, 4096, 1 << 30, 4096),        # a bound that is the budget: hard
    (1024, 4096, 1 << 30, 4096),        # as far as the ceiling, no further
    (1024, 4096, 1500, 3000),           # two frames, under the ceiling
    (4096, None, 2000, 4096),           # the budget holds it twice already
    (0, None, 1 << 30, 0),              # pooling is off: it stays off
    (0, 4096, 1 << 30, 0),
])
def test_the_ceiling_bounds_what_a_frame_may_raise(base, ceiling, frame,
                                                   budget):
    pool = sockio.BufferPool(max_bytes=base, min_size=16, ceiling=ceiling)
    pool.expect(frame)
    assert pool._max_bytes == budget
    assert (pool._lapse_timer is not None) == (budget > base)
    if not budget:
        assert pool.take(1024).base is None
    pool.trim()


def test_the_raise_lapses_when_the_large_frames_stop():
    pool = sockio.BufferPool(max_bytes=4096, min_size=16)
    pool.GROWN_TTL_S = 0.2
    held = _read_frame(pool)
    free = _blocks(_read_frame(pool))
    assert pool._max_bytes == 16000 and pool._total == 16000
    # Nothing more is read: the timer alone takes the raise back. The
    # free blocks over the budget go, the busy ones are only untracked.
    _wait_for(lambda: pool._max_bytes == 4096, "the raise did not lapse")
    assert pool._lapse_timer is None
    assert pool._total <= 4096 and len(pool._entries) <= 1
    assert not {id(e) for e in pool._entries} & set(free[:-1])
    np.asarray(held[0])[:] = 7          # a busy block is still its holder's
    small = _read_frame(pool, [1000, 1000])     # fits the budget twice
    assert pool._max_bytes == 4096 and pool._lapse_timer is None
    del small
    again = _read_frame(pool)           # and the next large one raises it
    assert pool._max_bytes == 16000 and pool._lapse_timer is not None
    del again
    pool.trim()


def test_a_frame_that_needs_the_raise_holds_it():
    pool = sockio.BufferPool(max_bytes=4096, min_size=16)
    pool.GROWN_TTL_S = 0.3
    t_end = time.monotonic() + 0.9
    while time.monotonic() < t_end:     # three lifetimes of the raise
        pool.expect(8000)
        assert pool._max_bytes == 16000
        pool.expect(3000)               # a smaller one does not hold it
        time.sleep(0.05)
    _wait_for(lambda: pool._max_bytes == 4096, "the raise did not lapse")


@pytest.mark.parametrize("mb, ceiling", [
    (None, None), ("2048", 2 << 30), ("64", 64 << 20), ("0", 0),
    ("many", None),
])
def test_the_processes_pool_takes_the_variable_as_its_ceiling(
        monkeypatch, mb, ceiling):
    if mb is None:
        monkeypatch.delenv("FEDTPU_RECV_POOL_MB", raising=False)
    else:
        monkeypatch.setenv("FEDTPU_RECV_POOL_MB", mb)
    pool = sockio._make_recv_pool()
    assert pool._ceiling == ceiling
    whole = (2 << 30) if ceiling is None else ceiling
    assert pool._base in (whole, whole // 4)    # beside the native engine
    # The documented default, set, starts where an unset variable does.
    assert (mb != "2048") or pool._base == _unset_base(monkeypatch)


def _unset_base(monkeypatch):
    monkeypatch.delenv("FEDTPU_RECV_POOL_MB")
    return sockio._make_recv_pool()._base


@pytest.mark.skipif(not reactor.available(), reason="epoll not available")
def test_the_reactors_reader_reads_the_next_tree_into_the_same_buffers(
        monkeypatch):
    """End to end: two pushes of a 3 MiB tree through a pool whose budget
    starts under one leaf. The second is read into the first one's blocks
    once its value is dropped."""
    pool = sockio.BufferPool(max_bytes=1 << 19, min_size=1 << 16)
    monkeypatch.setattr(sockio, "_RECV_POOL", pool)
    tree = {"a": np.arange(1 << 18, dtype=np.float32),
            "b": np.ones((1 << 19,), np.float32)}
    addr = get_addresses(["bob"])
    rp = TcpReceiverProxy(addr["bob"], "bob", "job", None, dict(FAST))
    rp.start()
    sp = TcpSenderProxy(dict(addr, alice="127.0.0.1:1"), "alice", "job",
                        None, dict(FAST))
    try:
        seen = []
        for seq in (1, 2):
            got = rp.get_data("alice", str(seq), seq)
            assert sp.send("bob", tree, str(seq), seq).result(timeout=30)
            value = got.result(timeout=30)
            np.testing.assert_array_equal(value["b"], tree["b"])
            seen.append(sorted(id(e) for e in pool._entries))
            del value, got
            gc.collect()
        assert pool._max_bytes == 2 * (tree["a"].nbytes + tree["b"].nbytes)
        assert len(seen[0]) == 2 and seen[0] == seen[1]
    finally:
        sp.stop()
        rp.stop()


def _many_leaves():
    import jax.numpy as jnp

    leaf = 1 << 18          # float32 elements: 1 MiB a leaf, a segment each
    return {"layers": [jnp.full((leaf,), float(i), jnp.float32)
                       for i in range(4)],
            "host": np.arange(leaf + 7, dtype=np.float32),
            "bias": jnp.ones((3,), jnp.float32)}


@pytest.mark.skipif(not reactor.available(), reason="epoll not available")
@pytest.mark.parametrize("config", [
    {},
    {"frame_crc": True},    # checksum.payload_buffers over read segments
    {"payload_compression": "zlib"},
    {"num_streams": 2},
    {"use_reactor": False},
], ids=lambda c: "-".join(c) or "plain")
def test_a_tree_read_in_segments_arrives_as_sent(config):
    """A pushed tree of several MiB-scale leaves, device and host, through
    ``barriers.send`` from a future, under each option of the wire."""
    import threading
    from concurrent.futures import Future

    import jax

    from rayfed_tpu.proxy import barriers

    tree = _many_leaves()
    addr = get_addresses(["bob"])
    rp = TcpReceiverProxy(addr["bob"], "bob", "job", None,
                          dict(FAST, **config))
    rp.start()
    sp = TcpSenderProxy(dict(addr, alice="127.0.0.1:1"), "alice", "job",
                        None, dict(FAST, **config))
    barriers._sender_proxies.set(sp)
    try:
        got = rp.get_data("alice", "1", 2)
        value = Future()
        done = barriers.send("bob", value, "1", 2)
        threading.Thread(target=value.set_result, args=(tree,)).start()
        assert done.result(timeout=30) is True
        arrived = got.result(timeout=30)
        sent, struct = jax.tree_util.tree_flatten(tree)
        back, struct_back = jax.tree_util.tree_flatten(arrived)
        assert struct == struct_back
        for a, b in zip(sent, back):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        barriers._sender_proxies.pop()
        sp.stop()
        rp.stop()
