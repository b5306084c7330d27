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

"""Blocking-socket frame IO for the FTP1 wire protocol.

The data plane runs on dedicated threads with blocking sockets:
``sendall`` over memoryviews on the way out, ``recv_into`` a preallocated
``bytearray`` on the way in — one copy each side, measured ~20x faster than
asyncio streams on this workload (loopback ceiling ~2.9 GB/s vs ~0.13 GB/s
through StreamReader). Frame layout is defined in
:mod:`rayfed_tpu.proxy.tcp.wire`.
"""

from __future__ import annotations

import os
import socket
import ssl
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import msgpack

from rayfed_tpu.proxy.tcp import wire

try:  # native C++ lane (build with `make native`); Python IO is the fallback
    from rayfed_tpu import _fastwire
except ImportError:  # pragma: no cover - environment-dependent
    _fastwire = None

_SOCK_BUF = 8 * 1024 * 1024

# Small-message fast path (receive-side IO shaping): frames whose payload
# fits within this bound are received in one window — native builds pull
# prefix+header+payload inside a single GIL release, the Python/TLS path
# combines the header and payload reads. Independent of the *sender's*
# configurable ``small_message_threshold``: this is a local buffering
# decision, not a wire-format knob, so the two need not agree.
SMALL_FRAME_MAX = 64 * 1024

# Coalesced sends at or below this total are joined into one buffer for a
# single ``sendall`` on the Python/TLS path — one copy beats N syscalls
# (and keeps TLS to one record per batch). Larger batches send
# sequentially rather than double-buffer a big payload.
_COALESCE_COPY_MAX = 256 * 1024

# Sentinel for "caller did not pass a fastwire snapshot" — distinct from
# None, which legitimately means "no native engine".
_UNSET = object()


def _native_ok(sock, fw=_UNSET) -> bool:
    # The fastwire path works on raw fds only; TLS stays on the ssl module.
    # Callers on a multi-step path pass their own snapshot of ``_fastwire``
    # so one frame never sees the module global change mid-frame (tests
    # swap it to force the Python path; see test_sockio.py).
    if fw is _UNSET:
        fw = _fastwire
    return fw is not None and not isinstance(sock, ssl.SSLSocket)


def _timeout_ms(sock: socket.socket) -> int:
    t = sock.gettimeout()
    return -1 if t is None else int(t * 1000)


def tune_socket(sock: socket.socket) -> None:
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, _SOCK_BUF)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, _SOCK_BUF)
    except OSError:  # pragma: no cover - platform-specific
        pass


def send_frames(sock: socket.socket,
                frames: List[Tuple[int, Dict, Optional[List]]]) -> None:
    """Send one or more complete frames in a single vectored write.

    ``frames`` is a list of (ftype, header, buffers). On native plaintext
    sockets every prefix, header and payload buffer of the whole batch
    goes out through one ``sendv`` (writev) call; the Python/TLS fallback
    joins small batches into one ``sendall``. This is the syscall-level
    half of the small-message coalescer: N queued small frames to the
    same peer cost one syscall, not 2N.
    """
    fw = _fastwire
    chunks: List = []
    for ftype, header, buffers in frames:
        buffers = buffers or []
        payload_len = sum(memoryview(b).nbytes for b in buffers)
        chunks.append(
            wire.encode_prefix_and_header(ftype, header, payload_len)
        )
        for b in buffers:
            v = wire.as_byte_view(b)
            if v.nbytes:
                chunks.append(v)
    if _native_ok(sock, fw):
        try:
            fw.sendv(sock.fileno(), _timeout_ms(sock), chunks)
            return
        except TimeoutError:
            raise socket.timeout("fastwire send timed out") from None
        except ValueError:
            # Stale v1 extension build: sendv capped at 64 iovecs ("too
            # many buffers") and nothing has been written yet — fall
            # through to the Python sendall path.
            pass
    total = sum(memoryview(c).nbytes for c in chunks)
    if len(chunks) > 1 and total <= _COALESCE_COPY_MAX:
        sock.sendall(b"".join(chunks))
        return
    for chunk in chunks:
        sock.sendall(chunk)


def send_frame(sock: socket.socket, ftype: int, header: Dict,
               buffers: Optional[List] = None) -> None:
    send_frames(sock, [(ftype, header, buffers)])


def _recv_exact_into(sock: socket.socket, view: memoryview,
                     fw=_UNSET) -> None:
    if fw is _UNSET:
        fw = _fastwire
    if _native_ok(sock, fw):
        try:
            fw.recv_exact(sock.fileno(), _timeout_ms(sock), view)
            return
        except TimeoutError:
            raise socket.timeout("fastwire recv timed out") from None
    got = 0
    total = view.nbytes
    while got < total:
        n = sock.recv_into(view[got:])
        if n == 0:
            raise ConnectionError("peer closed connection mid-frame")
        got += n


def _recv_exact(sock: socket.socket, n: int, fw=_UNSET) -> bytearray:
    buf = bytearray(n)
    _recv_exact_into(sock, memoryview(buf), fw)
    return buf


# Tree payloads at least this large are scatter-read into per-buffer
# segments (so a sharded array never lands in one global-size host buffer).
_SEGMENT_THRESHOLD = 1 << 20


def _effective_cap(max_payload: Optional[int]) -> int:
    return wire._MAX_PAYLOAD if max_payload is None else min(
        max_payload, wire._MAX_PAYLOAD
    )


def _segment_sizes(header: Dict, plen: int):
    """Per-segment byte lengths for a scatter-read, or None when the
    frame is received into one contiguous buffer. Shared by the Python
    and native receive paths — the segmentation rule must never diverge
    between them (TLS rides the Python path, plaintext the native)."""
    if plen >= _SEGMENT_THRESHOLD and "comp" not in header:
        pkind = header.get("pkind")
        if pkind == "tree":
            from rayfed_tpu._private import serialization

            lengths = serialization.tree_segment_lengths(
                header.get("pmeta", b""), plen
            )
            if lengths is not None and len(lengths) > 1:
                return lengths
        elif pkind == "stripe":
            # Stripe frames carry their own pre-validated segment plan
            # (the sender computed it from the same coalescing rule).
            from rayfed_tpu._private import serialization

            return serialization.stripe_segment_lengths(
                header.get("sd") or {}, plen
            )
    return None


class BufferPool:
    """Recycles large receive buffers across frames.

    A fresh ``np.empty`` per 100MB frame costs ~40% of loopback throughput
    on this class of host: glibc serves big allocations from per-thread
    arenas that always mmap >64MB requests, so every frame pays page
    faults on first touch plus munmap on free. Delivered arrays are
    zero-copy views of the receive buffer, so a buffer is safe to reuse
    exactly when every consumer view has died — detected by its refcount
    dropping back to the pool's own reference.

    The budget follows the traffic. ``max_bytes`` is what the pool keeps
    whatever is read. A reader announces each frame (:meth:`expect`), and
    a frame whose buffers the budget cannot hold twice raises it to TWO
    such frames, as far as ``ceiling`` goes (None: as far as the frames
    ask, and a reader announces only what it has held against the
    receiver's size cap): the value a frame delivered usually lives until
    the next one has replaced it, and a budget under that recycles
    nothing. A 1.945 GB tree read into fresh buffers pays half a million
    page faults: the same loopback stream carried 0.84 GB/s so and 2.87
    recycled (TPU v5e host, PERF.md section 6, PR 40). The raise lapses
    ``GROWN_TTL_S`` after the last frame that needed all of it: the
    budget is ``max_bytes`` again and the free blocks over it go back to
    the allocator, also in a process that reads nothing more.
    """

    GROWN_TTL_S = 60.0

    def __init__(
        self, max_bytes: int, min_size: int = 1 << 20, max_entries: int = 64,
        ceiling: Optional[int] = None,
    ):
        # Free detection relies on exact refcounts; a free-threaded
        # interpreter biases/defers them, so pooling must stand down
        # there (plain allocation, no dead-weight cache).
        if not getattr(sys, "_is_gil_enabled", lambda: True)():
            max_bytes = 0  # pragma: no cover - nogil builds only
        self._base = max_bytes
        self._ceiling = ceiling
        self._max_bytes = max_bytes  # the budget now: _base, or raised
        self._grown_until = 0.0  # monotonic; when the raise lapses
        self._lapse_timer: Optional[threading.Timer] = None
        self._min_size = min_size
        # Bounds the O(entries) refcount scan every take() pays under the
        # lock (and with it, worst-case lock hold time).
        self._max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: List = []  # np.ndarray blocks, oldest first
        self._total = 0  # running sum of tracked bytes

    # refs to a free entry at the getrefcount() call site: the pool's
    # list slot + getrefcount's argument. Any live consumer view (ndarray
    # slice / memoryview chains back to the block) adds more.
    _FREE_RC = 2

    def expect(self, frame_bytes: int) -> None:
        """A frame of ``frame_bytes`` is about to be read into buffers of
        this pool (the reader knows it from the frame's prefix, which it
        has already held against the receiver's size cap)."""
        want = 2 * frame_bytes
        if self._ceiling is not None:
            want = min(want, self._ceiling)
        # A pool that is off (0) stays off.
        if want <= self._base or not self._base:
            return
        with self._lock:
            if want < self._max_bytes:
                return  # a smaller frame neither raises nor holds a raise
            self._max_bytes = want
            self._grown_until = time.monotonic() + self.GROWN_TTL_S
            if self._lapse_timer is None:
                self._arm_lapse(self.GROWN_TTL_S)

    def _arm_lapse(self, delay: float) -> None:
        # Caller holds self._lock. One timer while the budget is raised.
        self._lapse_timer = threading.Timer(delay, self._lapse)
        self._lapse_timer.daemon = True
        self._lapse_timer.start()

    def _lapse(self) -> None:
        with self._lock:
            self._lapse_timer = None
            left = self._grown_until - time.monotonic()
            if left > 0:
                self._arm_lapse(left)  # a frame has held the raise since
                return
            self._max_bytes = self._base
            evicted = self._evict_over()
        del evicted  # freed outside the lock

    def _evict_over(self) -> List:
        """Untrack blocks, oldest first, while the pool is over its
        budget or its entry count; returns them so that the caller frees
        them after the lock (a busy block is merely untracked and is
        freed by GC once its consumers drop their views). Caller holds
        self._lock."""
        evicted = []
        while len(self._entries) > 1 and (
            self._total > self._max_bytes
            or len(self._entries) > self._max_entries
        ):
            self._total -= self._entries[0].nbytes
            evicted.append(self._entries.pop(0))
        return evicted

    def take(self, n: int):
        """A writable 1-d uint8 array of exactly ``n`` bytes."""
        import numpy as np

        if n < self._min_size:
            return np.empty(n, dtype=np.uint8)
        with self._lock:
            pooled = n <= self._max_bytes
            best = -1
            for i in range(len(self._entries)) if pooled else ():
                nbytes = self._entries[i].nbytes
                # <=4n bound: don't burn a huge block on a small frame.
                if (
                    n <= nbytes <= (n << 2)
                    and sys.getrefcount(self._entries[i]) == self._FREE_RC
                    and (best < 0 or nbytes < self._entries[best].nbytes)
                ):
                    best = i
            if best >= 0:
                block = self._entries.pop(best)
                self._entries.append(block)  # LRU: reused = most recent
                return block[:n] if block.nbytes > n else block[:]
        # Allocate outside the lock: mmap + page faults of a GB-scale
        # block must not stall other receiver threads' pool hits.
        block = np.empty(n, dtype=np.uint8)
        if not pooled:
            return block
        with self._lock:
            self._entries.append(block)
            self._total += block.nbytes
            evicted = self._evict_over()
        del evicted  # munmap of evicted blocks happens after lock release
        return block[:]

    def trim(self) -> None:
        """Drop every currently-free block (busy blocks stay tracked) and
        end a raise of the budget.

        Transports call this at shutdown so a burst of large frames does
        not pin pool memory for the rest of the process's life."""
        dropped = []
        keep = []
        with self._lock:
            for block in self._entries:
                # refs at the check: list slot + loop var + getrefcount
                # arg = 3 for a free block; consumer views add more.
                (keep if sys.getrefcount(block) > 3 else dropped).append(block)
            self._entries = keep
            self._total = sum(b.nbytes for b in keep)
            self._max_bytes = self._base
            if self._lapse_timer is not None:
                self._lapse_timer.cancel()
                self._lapse_timer = None
        del dropped  # frees outside the lock


def trim_recv_pool() -> None:
    """Release the module pool's free blocks (called on transport stop)."""
    _RECV_POOL.trim()
    if _fastwire is not None and hasattr(_fastwire, "pool_trim"):
        _fastwire.pool_trim()


_DEFAULT_POOL_BYTES = 2 << 30


def _pool_env_bytes() -> Optional[int]:
    """``FEDTPU_RECV_POOL_MB`` in bytes; None where it is not set (or
    not a number)."""
    mb = os.environ.get("FEDTPU_RECV_POOL_MB")
    if mb is None:
        return None
    try:
        return max(0, int(mb)) << 20
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "ignoring malformed FEDTPU_RECV_POOL_MB=%r (want integer MB)", mb
        )
        return None


def _make_recv_pool() -> BufferPool:
    """The process's receive pool. It serves the reactor's reader (the
    default plaintext path), the TLS connections and the Python
    ``recv_frame``; the native extension's C-side pool serves the blocking
    plaintext receive (``use_reactor: false``) and reads the same variable
    for itself.

    ``FEDTPU_RECV_POOL_MB`` is the CEILING of this pool: its budget starts
    at a quarter of it beside a loaded native engine (at all of it
    without), which is what it keeps whatever is read, and follows the
    traffic up to it (``BufferPool.expect``), so the process retains at
    most twice the variable, and that only if both receive paths carry
    bulk. Unset, the start is the same share of 2 GiB and the ceiling is
    the traffic's own: two frames of ``messages_max_size_in_bytes`` at the
    most, which a receiver that reads one such frame while the last one's
    value is alive holds anyway. A start under two frames of a GB-scale
    tree recycles a leaf or two of it and page-faults on the rest, every
    frame."""
    ceiling = _pool_env_bytes()
    budget = _DEFAULT_POOL_BYTES if ceiling is None else ceiling
    if _fastwire is not None and hasattr(_fastwire, "recv_prefix_header"):
        budget //= 4
    return BufferPool(budget, ceiling=ceiling)


_RECV_POOL = _make_recv_pool()


def recv_frame(
    sock: socket.socket,
    max_payload: Optional[int] = None,
):
    """Blocking read of one frame. Size caps are enforced before the
    payload is buffered, so an oversized frame costs no memory — the
    connection is torn down instead of answered. Payload is a writable
    buffer view, or a :class:`serialization.SegmentedPayload` when a
    large ``tree`` frame is scatter-read into leaf/shard-aligned buffers.

    On plaintext sockets with the native extension available, the whole
    receive path (prefix+header read, validation, pooled payload buffers,
    scatter readv) runs in C++ (the role gRPC's C-core plays for the
    reference's data plane). Frames whose payload fits SMALL_FRAME_MAX
    ride a one-window fast lane: the native engine pulls prefix, header
    and payload inside a single GIL release; the Python path combines
    the header+payload reads into one recv."""
    fw = _fastwire  # snapshot: one frame never mixes native/Python steps
    if _native_ok(sock, fw) and hasattr(fw, "recv_prefix_header"):
        return _recv_frame_native(sock, max_payload, fw)
    prefix = _recv_exact(sock, wire.PREFIX_LEN, fw)
    magic, version, ftype, hlen, plen = wire._PREFIX.unpack(bytes(prefix))
    if magic != wire.WIRE_MAGIC:
        raise wire.WireError(f"bad magic {magic!r}")
    if version != wire.WIRE_VERSION:
        raise wire.WireError(f"unsupported wire version {version}")
    if hlen > wire._MAX_HEADER:
        raise wire.WireError(f"header length {hlen} exceeds cap")
    cap = _effective_cap(max_payload)
    if plen > cap:
        raise wire.WireError(f"payload length {plen} exceeds cap {cap}")
    if plen and plen <= SMALL_FRAME_MAX:
        # Small frame: header + payload in one read (2 recv windows per
        # frame instead of 3; the payload view stays writable).
        buf = memoryview(_recv_exact(sock, hlen + plen, fw))
        header = msgpack.unpackb(bytes(buf[:hlen]), raw=False)
        return ftype, header, buf[hlen:]
    header = msgpack.unpackb(bytes(_recv_exact(sock, hlen, fw)), raw=False)
    if not plen:
        return ftype, header, memoryview(b"")
    # Buffers come from the recycling pool (np.empty also skips the
    # zero-fill a bytearray would pay — pure waste since recv_into
    # overwrites every byte); the returned view stays writable.
    from rayfed_tpu._private import serialization

    _RECV_POOL.expect(plen)
    sizes = _segment_sizes(header, plen)
    if sizes is not None:
        segments = []
        pos = 0
        for n in sizes:
            buf = _RECV_POOL.take(n)
            _recv_exact_into(sock, memoryview(buf), fw)
            segments.append((pos, buf))
            pos += n
        return ftype, header, serialization.SegmentedPayload(segments)

    payload = _RECV_POOL.take(plen)
    _recv_exact_into(sock, memoryview(payload), fw)
    return ftype, header, memoryview(payload)


def _recv_frame_native(sock: socket.socket, max_payload: Optional[int], fw):
    """Native (C++) receive path. Small frames (payload within
    SMALL_FRAME_MAX): ONE GIL window for the whole frame via
    ``recv_frame_small``. Large frames: one window for prefix+header
    (validation before allocation), one for the payload scatter-read into
    C-pooled buffers. ``fw`` is the caller's snapshot of the fastwire
    module — taken once per frame so a concurrent swap of the module
    global (tests forcing the Python path) cannot split one frame across
    engines."""
    timeout_ms = _timeout_ms(sock)
    fd = sock.fileno()
    small = None
    try:
        if hasattr(fw, "recv_frame_small"):
            ftype, plen, hbytes, small = fw.recv_frame_small(
                fd, timeout_ms, wire.WIRE_MAGIC, wire.WIRE_VERSION,
                wire._MAX_HEADER, _effective_cap(max_payload),
                SMALL_FRAME_MAX,
            )
        else:  # stale extension build without the small-frame lane
            ftype, plen, hbytes = fw.recv_prefix_header(
                fd, timeout_ms, wire.WIRE_MAGIC, wire.WIRE_VERSION,
                wire._MAX_HEADER, _effective_cap(max_payload),
            )
    except TimeoutError:
        raise socket.timeout("fastwire recv timed out") from None
    except ValueError as e:  # protocol violation detected in C
        raise wire.WireError(str(e)) from None
    header = msgpack.unpackb(hbytes, raw=False)
    if not plen:
        return ftype, header, memoryview(b"")
    if small is not None:
        return ftype, header, memoryview(small)
    from rayfed_tpu._private import serialization

    sizes = _segment_sizes(header, plen)
    try:
        bufs = fw.recv_scatter(fd, timeout_ms, sizes or [plen])
    except TimeoutError:
        raise socket.timeout("fastwire recv timed out") from None
    if sizes is None:
        return ftype, header, memoryview(bufs[0])
    segments = []
    pos = 0
    for n, buf in zip(sizes, bufs):
        segments.append((pos, buf))
        pos += n
    return ftype, header, serialization.SegmentedPayload(segments)
