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

"""Multi-stream (striped) data plane: plan -> wire -> reassembly.

Property under test: a sharded pytree round-trips BYTE-IDENTICAL through
K parallel stripe lanes for K in {1, 2, 4}, stripes may arrive in any
order over any connection, duplicates (ack-lost resends) are absorbed,
and a mid-transfer stream drop is resumed by the per-lane
resend-after-reconnect path without corrupting the reassembled payload.
"""

import random
import socket
import threading

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from rayfed_tpu._private import serialization as ser
from rayfed_tpu._private.constants import CODE_INTERNAL_ERROR, CODE_OK
from rayfed_tpu.proxy import rendezvous
from rayfed_tpu.proxy.tcp import reactor
from tests.utils import get_addresses

FAST = {"retry_policy": {"max_attempts": 8, "initial_backoff_ms": 100}}


def _mesh(n, axes=("data",), shape=None):
    devs = np.array(jax.devices()[:n])
    return Mesh(devs.reshape(shape or (n,)), axes)


def _sharded(arr, mesh, spec):
    return jax.device_put(arr, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# plan_stripes
# ---------------------------------------------------------------------------


def test_plan_stripes_tiles_and_balances(monkeypatch):
    monkeypatch.setattr(ser, "STRIPE_MIN_BYTES", 1)
    buffers = [b"a" * 100, b"b" * 300, b"", b"c" * 250, b"d" * 50, b"e" * 300]
    plan = ser.plan_stripes(buffers, 3)
    assert plan is not None and len(plan) == 3
    pos = 0
    for soff, bufs, nbytes, segs in plan:
        assert soff == pos  # contiguous tiling, zero-size buffers skipped
        assert nbytes == sum(len(b) for b in bufs)
        assert nbytes > 0
        assert sum(segs) == nbytes  # per-stripe scatter plan covers it
        pos += nbytes
    assert pos == sum(len(b) for b in buffers)
    # Splits land only at buffer boundaries: reassembling the stripes'
    # buffer lists must give back the non-empty originals in order.
    flat = [b for _, bufs, _, _ in plan for b in bufs]
    assert flat == [b for b in buffers if b]


def test_plan_stripes_declines_when_pointless(monkeypatch):
    monkeypatch.setattr(ser, "STRIPE_MIN_BYTES", 1)
    assert ser.plan_stripes([b"x" * 4096], 4) is None  # one buffer
    assert ser.plan_stripes([b"x" * 4096, b"y"], 1) is None  # one lane
    monkeypatch.setattr(ser, "STRIPE_MIN_BYTES", 1 << 20)
    assert ser.plan_stripes([b"x" * 4096, b"y" * 4096], 4) is None  # small


# ---------------------------------------------------------------------------
# StripeAssembler
# ---------------------------------------------------------------------------


def _stripe_frames(k, tree=None, monkeypatch=None):
    """Encode a pytree and cut it into stripe frames the way the sender
    does, returning (frames, meta_bytes, flat_payload_bytes)."""
    if tree is None:
        tree = {f"p{i}": np.arange(1024, dtype=np.float32) + i for i in range(8)}
    kind, meta, buffers = ser.encode_payload(tree)
    assert kind == "tree"
    plan = ser.plan_stripes(buffers, k)
    assert plan is not None
    base = {"job": "job", "src": "alice", "up": "1#0", "down": "2",
            "is_error": False, "pkind": "tree", "pmeta": meta}
    frames = []
    n = len(plan)
    total = sum(ser.buffer_nbytes(b) for b in buffers)
    for i, (soff, bufs, nbytes, segs) in enumerate(plan):
        h = dict(base)
        h["pkind"] = "stripe"
        h["sd"] = {"i": i, "n": n, "off": soff, "tot": total, "segs": segs}
        if i == 0:
            h["pk"] = "tree"
        else:
            h["pmeta"] = b""
        frames.append((h, bytes(ser.concat_buffers(bufs))))
    return frames, meta, bytes(ser.concat_buffers(buffers))


def test_assembler_reassembles_any_arrival_order(monkeypatch):
    monkeypatch.setattr(ser, "STRIPE_MIN_BYTES", 1)
    frames, meta, flat = _stripe_frames(4)
    for seed in range(3):
        order = list(range(len(frames)))
        random.Random(seed).shuffle(order)
        captured = []

        def offer(header, payload):
            captured.append((header, payload))
            return CODE_OK, "stored"

        asm = rendezvous.StripeAssembler(offer)
        for j in order[:-1]:
            code, msg = asm.offer(dict(frames[j][0]), frames[j][1])
            assert (code, msg) == (CODE_OK, "stripe buffered")
        code, msg = asm.offer(dict(frames[order[-1]][0]), frames[order[-1]][1])
        assert (code, msg) == (CODE_OK, "stored")  # inner verdict surfaced
        (header, payload), = captured
        assert header["pkind"] == "tree"
        assert header["pmeta"] == meta
        assert "sd" not in header and "pk" not in header
        assert isinstance(payload, ser.SegmentedPayload)
        assert payload.tobytes() == flat


def test_assembler_duplicates_and_late_arrivals(monkeypatch):
    monkeypatch.setattr(ser, "STRIPE_MIN_BYTES", 1)
    frames, _, _ = _stripe_frames(2)
    hits = []
    asm = rendezvous.StripeAssembler(
        lambda h, p: hits.append(1) or (CODE_OK, "stored")
    )
    assert asm.offer(dict(frames[0][0]), frames[0][1])[1] == "stripe buffered"
    # Resent stripe (lost ack) before completion: absorbed, not double-counted.
    assert asm.offer(dict(frames[0][0]), frames[0][1])[1] == "duplicate stripe"
    assert asm.offer(dict(frames[1][0]), frames[1][1])[1] == "stored"
    # Resent stripe after completion: acked OK so the sender's retry ends.
    assert asm.offer(dict(frames[1][0]), frames[1][1])[1] == (
        "duplicate stripe group"
    )
    assert hits == [1]


def test_assembler_rejects_inconsistent_descriptors(monkeypatch):
    monkeypatch.setattr(ser, "STRIPE_MIN_BYTES", 1)
    frames, _, _ = _stripe_frames(2)
    asm = rendezvous.StripeAssembler(lambda h, p: (CODE_OK, "stored"))
    assert asm.offer(dict(frames[0][0]), frames[0][1])[0] == CODE_OK
    bad = dict(frames[1][0])
    bad["sd"] = dict(bad["sd"], tot=bad["sd"]["tot"] + 1)
    code, msg = asm.offer(bad, frames[1][1])
    assert code == CODE_INTERNAL_ERROR and "disagrees" in msg
    # Oversized declared total is refused before buffering a byte.
    big = dict(frames[0][0], up="9#9")
    big["sd"] = dict(big["sd"], tot=1 << 40)
    small_cap = rendezvous.StripeAssembler(
        lambda h, p: (CODE_OK, "stored"), max_payload_bytes=1 << 20
    )
    code, msg = small_cap.offer(big, frames[0][1])
    assert code == CODE_INTERNAL_ERROR and "exceeding" in msg


def test_assembler_passthrough_non_stripe():
    seen = []
    asm = rendezvous.StripeAssembler(
        lambda h, p: seen.append((h, p)) or (CODE_OK, "stored")
    )
    h = {"pkind": "tree", "pmeta": b"m"}
    assert asm.offer(h, b"payload") == (CODE_OK, "stored")
    assert seen == [(h, b"payload")]


# ---------------------------------------------------------------------------
# End-to-end: K-lane round trip over real proxies
# ---------------------------------------------------------------------------

needs_reactor = pytest.mark.skipif(
    not reactor.available(), reason="epoll not available on this platform"
)


def _big_tree(pmesh):
    # "w": 2 MB sharded 4-way -> four 512 KB shard buffers (stripes split
    # at these boundaries); "p": committed to one device (not a
    # NamedSharding, so it rides the dense leaf path); "b": tiny dense.
    host_w = np.arange(4 * 131072, dtype=np.float32).reshape(4, 131072)
    host_p = np.arange(4 * 4096, dtype=np.float32).reshape(4, 4096)
    host_b = np.arange(16, dtype=np.float32)
    tree = {
        "w": _sharded(host_w, pmesh, PartitionSpec("data")),
        "p": jax.device_put(host_p, jax.devices()[1]),
        "b": _sharded(host_b, pmesh, PartitionSpec()),
    }
    return tree, {"w": host_w, "p": host_p, "b": host_b}


@needs_reactor
@pytest.mark.parametrize("streams", [1, 2, 4])
def test_multistream_roundtrip_byte_identical(monkeypatch, streams):
    from rayfed_tpu import mesh as mesh_mod
    from rayfed_tpu.proxy.tcp import sockio
    from rayfed_tpu.proxy.tpu.tpu_proxy import TpuReceiverProxy, TpuSenderProxy

    pmesh = _mesh(4)
    monkeypatch.setattr(mesh_mod, "_party_mesh", pmesh)
    # Force scatter reads so stripe segment plans are exercised too.
    monkeypatch.setattr(sockio, "_SEGMENT_THRESHOLD", 1)

    cfg = dict(FAST, num_streams=streams)
    addr = get_addresses(["bob"])
    rp = TpuReceiverProxy(addr["bob"], "bob", "job", None, dict(cfg))
    rp.start()
    ok, err = rp.is_ready()
    assert ok, err
    sp = TpuSenderProxy(addr, "alice", "job", None, dict(cfg))
    sp.start()
    try:
        tree, hosts = _big_tree(pmesh)
        for rnd in range(2):  # second round reuses the warm lanes
            fut = rp.get_data("alice", f"{rnd}#0", rnd + 1)
            assert sp.send("bob", tree, f"{rnd}#0", rnd + 1).result(timeout=60)
            got = fut.result(timeout=60)
            for k, host in hosts.items():
                out = np.asarray(got[k])
                assert out.dtype == host.dtype
                assert out.tobytes() == host.tobytes()  # byte-identical
            assert got["w"].sharding.spec == PartitionSpec("data")
        if streams > 1:
            worker = sp._workers["bob"]
            assert len(worker._lanes) == streams
    finally:
        sp.stop()
        rp.stop()


class _FlakyForwarder:
    """TCP forwarder that kills its Nth accepted connection (both sides)
    after relaying a few KB client->server — a mid-transfer stream drop
    on exactly one of the sender's stripe lanes. Later connections relay
    cleanly, so the lane's redial succeeds and resends unacked frames."""

    def __init__(self, target, drop_conn_index=2, drop_after=4096):
        self._target = target
        self._drop_index = drop_conn_index
        self._drop_after = drop_after
        self.conn_count = 0
        self.dropped = threading.Event()
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self.addr = "{}:{}".format(*self._srv.getsockname())
        self._stopped = False
        threading.Thread(target=self._accept_loop, daemon=True).start()

    def _accept_loop(self):
        while not self._stopped:
            try:
                client, _ = self._srv.accept()
            except OSError:
                return
            self.conn_count += 1
            doomed = self.conn_count == self._drop_index
            host, port = self._target.rsplit(":", 1)
            try:
                upstream = socket.create_connection((host, int(port)), timeout=10)
            except OSError:
                client.close()
                continue
            budget = [self._drop_after] if doomed else None
            for src, dst in ((client, upstream), (upstream, client)):
                threading.Thread(
                    target=self._pump,
                    args=(src, dst, budget if src is client else None,
                          (client, upstream)),
                    daemon=True,
                ).start()

    def _pump(self, src, dst, budget, pair):
        try:
            while True:
                chunk = src.recv(65536)
                if not chunk:
                    break
                if budget is not None:
                    take = min(len(chunk), budget[0])
                    if take:
                        dst.sendall(chunk[:take])
                    budget[0] -= take
                    if budget[0] <= 0:
                        self.dropped.set()
                        break
                    continue
                dst.sendall(chunk)
        except OSError:
            pass
        finally:
            for s in pair:
                try:
                    s.close()
                except OSError:
                    pass

    def close(self):
        self._stopped = True
        try:
            self._srv.close()
        except OSError:
            pass


@needs_reactor
def test_midtransfer_stream_drop_resumed_by_resend(monkeypatch):
    from rayfed_tpu import mesh as mesh_mod
    from rayfed_tpu.proxy.tpu.tpu_proxy import TpuReceiverProxy, TpuSenderProxy

    pmesh = _mesh(4)
    monkeypatch.setattr(mesh_mod, "_party_mesh", pmesh)

    addr = get_addresses(["bob"])
    rp = TpuReceiverProxy(addr["bob"], "bob", "job", None, dict(FAST))
    rp.start()
    ok, err = rp.is_ready()
    assert ok, err
    fwd = _FlakyForwarder(addr["bob"], drop_conn_index=2, drop_after=4096)
    cfg = dict(FAST, num_streams=2)
    sp = TpuSenderProxy({"bob": fwd.addr}, "alice", "job", None, dict(cfg))
    sp.start()
    try:
        tree, hosts = _big_tree(pmesh)
        fut = rp.get_data("alice", "1#0", 2)
        assert sp.send("bob", tree, "1#0", 2).result(timeout=90)
        got = fut.result(timeout=90)
        for k, host in hosts.items():
            assert np.asarray(got[k]).tobytes() == host.tobytes()
        assert fwd.dropped.is_set()  # the drop actually happened
        assert fwd.conn_count >= 3  # and a redial followed it
    finally:
        sp.stop()
        rp.stop()
        fwd.close()
