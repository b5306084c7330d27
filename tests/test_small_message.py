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

"""Small-message fast path: compact codec fidelity, the syscall-level
frame coalescer, threshold boundaries, and end-to-end round-trips over
every transport lane with the fast path on and off."""

from __future__ import annotations

import socket

import numpy as np
import pytest

import rayfed_tpu as fed
from rayfed_tpu._private import serialization
from rayfed_tpu.proxy.tcp import sockio, wire
from tests.utils import FAST_COMM_CONFIG, run_parties

# ---------------------------------------------------------------------------
# Compact ("mp") codec: exact-type round-trips and strict fallbacks
# ---------------------------------------------------------------------------

_CLEAN_VALUES = [
    0,
    -1,
    2**63 - 1,
    -(2**63),
    2**64 - 1,
    True,
    False,
    None,
    1.5,
    -0.0,
    "héllo",
    b"\x00\xff" * 8,
    [],
    {},
    [1, "two", 3.0, None, [True, b"x"]],
    {"a": 1, "b": {"c": [1, 2, 3]}, 7: "int-key"},
]


@pytest.mark.parametrize("value", _CLEAN_VALUES, ids=repr)
def test_compact_roundtrip_exact_types(value):
    blob = serialization.try_encode_compact(value, 64 * 1024)
    assert blob is not None
    out = serialization.decode_compact(blob)
    assert out == value
    assert type(out) is type(value)
    # bool/int must not blur into each other through msgpack.
    if isinstance(value, bool):
        assert out is value


_DIRTY_VALUES = [
    (1, 2),                      # tuple would come back as a list
    np.int64(3),                 # numpy scalar would come back as int
    np.arange(4),                # arrays ride the tree lane
    2**64,                       # beyond msgpack uint64
    {"k": (1,)},                 # nested tuple
    {(1, 2): "v"},               # non-str/int key
    type("DictSub", (dict,), {})({"a": 1}),  # subclass loses its type
]


@pytest.mark.parametrize("value", _DIRTY_VALUES, ids=lambda v: repr(v)[:40])
def test_compact_declines_unclean(value):
    assert serialization.try_encode_compact(value, 64 * 1024) is None


def test_compact_declines_over_depth_and_size():
    deep = [1]
    for _ in range(64):
        deep = [deep]
    assert serialization.try_encode_compact(deep, 1 << 20) is None
    big = "x" * 1024
    assert serialization.try_encode_compact(big, 16) is None
    assert serialization.try_encode_compact(big, 0) is None


def test_encode_payload_routes_by_threshold():
    clean = {"weights": [1.0, 2.0], "step": 3}
    kind, meta, bufs = serialization.encode_payload(clean, small_threshold=65536)
    assert kind == "mp" and meta == b""
    assert serialization.decode_payload(kind, meta, bufs[0]) == clean
    # Threshold 0 disables the compact lane entirely.
    kind, _, _ = serialization.encode_payload(clean, small_threshold=0)
    assert kind != "mp"
    # Unclean payloads fall through to the tree lane even when enabled.
    kind, meta, bufs = serialization.encode_payload(
        {"w": np.arange(4, dtype=np.float32)}, small_threshold=65536
    )
    assert kind == "tree"


def test_quick_payload_bound_is_conservative():
    small = {"a": 1, "b": [2.0, "three"]}
    assert serialization.quick_payload_bound(small, 65536)
    blob = serialization.try_encode_compact(small, 65536)
    # When the probe says yes, the encoded blob genuinely fits.
    assert len(blob) <= 65536
    assert not serialization.quick_payload_bound(small, 0)
    assert not serialization.quick_payload_bound("x" * 100, 50)
    # Unknown leaf types must decline (under-estimation is the only
    # correctness hazard: it would overrun the inline lane).
    assert not serialization.quick_payload_bound(object(), 65536)
    arr = np.zeros(16, np.float32)
    bound_ok = serialization.quick_payload_bound({"w": arr}, 65536)
    assert bound_ok  # array-like leaves are sized by .nbytes + margin
    assert not serialization.quick_payload_bound({"w": arr}, arr.nbytes)


# ---------------------------------------------------------------------------
# Frame coalescer: N small frames -> one vectored write, fully parseable
# ---------------------------------------------------------------------------

def _recv_n_frames(sock, n):
    out = []
    for _ in range(n):
        ftype, header, payload = sockio.recv_frame(sock)
        out.append((ftype, header, bytes(serialization.payload_bytes(payload))
                    if payload is not None else b""))
    return out


@pytest.mark.parametrize("force_python", [False, True])
def test_send_frames_coalesces_batch(monkeypatch, force_python):
    if force_python:
        monkeypatch.setattr(sockio, "_fastwire", None)
    a, b = socket.socketpair()
    try:
        a.settimeout(10)
        b.settimeout(10)
        frames = [
            (wire.FTYPE_DATA, {"up": str(i), "pkind": "mp", "pmeta": b""},
             [bytes([i]) * (i + 1)])
            for i in range(5)
        ]
        sockio.send_frames(a, frames)
        got = _recv_n_frames(b, 5)
        for i, (ftype, header, payload) in enumerate(got):
            assert ftype == wire.FTYPE_DATA
            assert header["up"] == str(i)
            assert payload == bytes([i]) * (i + 1)
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize(
    "nbytes", [0, 1, sockio.SMALL_FRAME_MAX, sockio.SMALL_FRAME_MAX + 1]
)
def test_frame_roundtrip_at_threshold_boundary(nbytes):
    """Frames at and just past the small-combine receive path must both
    round-trip, and the received payload must be writable (decode paths
    may decompress / cast in place)."""
    a, b = socket.socketpair()
    try:
        a.settimeout(10)
        b.settimeout(10)
        payload = np.random.default_rng(nbytes).integers(
            0, 256, nbytes, np.uint8
        ).tobytes()
        sockio.send_frames(
            a, [(wire.FTYPE_DATA, {"up": "x", "pmeta": b""},
                 [payload] if nbytes else [])]
        )
        ftype, header, got = sockio.recv_frame(b)
        assert ftype == wire.FTYPE_DATA and header["up"] == "x"
        raw = serialization.payload_bytes(got) if got is not None else b""
        assert bytes(raw) == payload
        if nbytes:
            memoryview(got)[0:1] = b"\x00"  # writable buffer contract
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# End-to-end round-trips per transport lane, fast path on and off
# ---------------------------------------------------------------------------

_PAYLOADS = [
    {"lr": 0.01, "step": 7, "tags": ["a", "b"]},   # rides the mp lane
    (1, 2, 3),                                     # tuple: tree/pickle lane
    np.arange(6, dtype=np.float32),                # array: tree lane
    "x" * (80 * 1024),                             # over threshold: queued path
]


def _run_roundtrip(party, addresses, transport, threshold):
    comm = dict(FAST_COMM_CONFIG)
    comm["small_message_threshold"] = threshold
    config = {"cross_silo_comm": comm, "transport": transport}
    if transport == "grpc":
        # gRPC clamps a channel's retries at 5 (under a second at this
        # policy): a peer whose receiver comes up later than that, on a
        # loaded host, would refuse the first send.
        config["barrier_on_initializing"] = True
    fed.init(addresses=addresses, party=party, config=config)

    @fed.remote
    def produce(i):
        return _PAYLOADS[i]

    @fed.remote
    def check(i, v):
        expected = _PAYLOADS[i]
        if isinstance(expected, np.ndarray):
            np.testing.assert_array_equal(np.asarray(v), expected)
        else:
            assert v == expected, (v, expected)
        return i

    for i in range(len(_PAYLOADS)):
        out = check.party("bob").remote(i, produce.party("alice").remote(i))
        assert fed.get(out) == i
    fed.shutdown()


@pytest.mark.parametrize("threshold", [65536, 0], ids=["fast", "disabled"])
def test_tcp_roundtrip_small_messages(threshold):
    run_parties(
        _run_roundtrip, ["alice", "bob"], extra_args=("tcp", threshold)
    )


def test_grpc_roundtrip_small_messages():
    run_parties(
        _run_roundtrip, ["alice", "bob"], extra_args=("grpc", 65536)
    )


def _run_tpu_roundtrip(party, addresses):
    device_ids = {"alice": [0, 1, 2, 3], "bob": [4, 5, 6, 7]}[party]
    comm = dict(FAST_COMM_CONFIG)
    comm["small_message_threshold"] = 65536
    fed.init(
        addresses=addresses,
        party=party,
        config={
            "cross_silo_comm": comm,
            "transport": "tpu",
            "party_mesh": {"device_ids": device_ids, "axis_names": ["data"]},
        },
    )

    @fed.remote
    def metrics():
        # Scalars-only control message: the exact shape the mp lane exists
        # for (loss reports, step counters) alongside a device payload.
        return {"loss": 0.125, "step": 3}

    @fed.remote
    def check(m):
        assert m == {"loss": 0.125, "step": 3}
        return True

    assert fed.get(check.party("bob").remote(metrics.party("alice").remote()))
    fed.shutdown()


@pytest.mark.slow
def test_tpu_roundtrip_small_messages():
    run_parties(_run_tpu_roundtrip, ["alice", "bob"])
