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

"""Shared helpers for multi-party tests.

The canonical multi-party-without-a-cluster trick from the reference test
suite (``fed/tests/test_fed_get.py:50-95``): one OS process per party, all
parties share localhost addresses, asserts run inside the children, and the
parent checks exit codes.
"""

from __future__ import annotations

import multiprocessing
import socket
from typing import Callable, Dict, List, Optional

# 'spawn' gives each party a pristine interpreter (no inherited JAX/global
# context), matching the reference's per-party Ray clusters in spirit.
MP = multiprocessing.get_context("spawn")

# Fast retry policy for tests: peers come up within milliseconds of each
# other; the reference-parity default (5s initial backoff) only slows CI.
FAST_COMM_CONFIG = {
    "retry_policy": {
        "max_attempts": 20,
        "initial_backoff_ms": 100,
        "max_backoff_ms": 1000,
        "backoff_multiplier": 1.5,
    }
}


def get_addresses(parties: List[str]) -> Dict[str, str]:
    """Pick a free localhost port per party."""
    addresses = {}
    socks = []
    for party in parties:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        addresses[party] = f"127.0.0.1:{s.getsockname()[1]}"
    for s in socks:
        s.close()
    return addresses


def run_parties(
    target: Callable,
    parties: List[str],
    timeout: float = 240,  # generous: 1-core CI hosts stall under compile load
    extra_args: tuple = (),
    addresses: Optional[Dict[str, str]] = None,
) -> None:
    """Spawn ``target(party, addresses, *extra_args)`` per party; assert all
    exit 0."""
    addresses = addresses or get_addresses(parties)
    procs = {
        party: MP.Process(
            target=target, args=(party, addresses) + extra_args, name=f"party-{party}"
        )
        for party in parties
    }
    for p in procs.values():
        p.start()
    for party, p in procs.items():
        p.join(timeout=timeout)
        if p.is_alive():
            for q in procs.values():
                q.terminate()
            raise AssertionError(f"party {party} timed out after {timeout}s")
    bad = {party: p.exitcode for party, p in procs.items() if p.exitcode != 0}
    assert not bad, f"party processes failed with exit codes: {bad}"


def step_logits(pool, params, tokens, positions, tables, live=None):
    """The (R, vocab) logits of one paged decode step, which the pool's own
    program never returns (it ends in the choice of each row's token): the
    model-protocol member that program wraps, on the pool's arrays, the
    pool left as ``PagedKVPool.decode_step`` leaves it."""
    import jax
    import jax.numpy as jnp

    live = None if live is None or not pool._state else jnp.asarray(live)
    logits, pool._kv, pool._state, *_ = jax.jit(
        pool.model.decode_step, donate_argnums=(1, 2)
    )(params, pool._kv, pool._state, jnp.asarray(tokens),
      jnp.asarray(positions), jnp.asarray(tables), live)
    return logits


def slot_rows(pool, slot):
    """One slot's rows of every array of the pool (K and V for most
    models) as contiguous (L, row_len, *shape) NumPy rows, read through
    its block table (no program of the pool builds such a row)."""
    import numpy as np

    table = pool.table(slot)

    def row(a):
        a = np.asarray(a)[:, table]
        return a.reshape(a.shape[0], -1, *a.shape[3:])[:, :pool.row_len]

    return tuple(row(a) for a in pool.kv)


def land_row(pool, slot, k_row, v_row):
    """Write contiguous (L, T, Hkv, Dh) rows into ``slot``'s blocks (a
    one-row ``scatter_rows``; the other rows go to the sacrificial
    block)."""
    import jax.numpy as jnp
    import numpy as np

    tables = np.zeros((pool.max_slots, pool.blocks_per_row), np.int32)
    tables[slot] = pool.table(slot)

    def slab(row):
        shape = (row.shape[0], pool.max_slots, *row.shape[1:])
        return jnp.zeros(shape, row.dtype).at[:, slot].set(row)

    pool.scatter_rows(slab(k_row), slab(v_row), tables)


def record_logits(monkeypatch):
    """Every logits row the engine's programs choose a token from, by the
    request's seed (greedy: the seed only marks its rows) and the token's
    position in the output. The sampler is looked up when a program is
    traced, so engines built after this call record; a chunk that is not
    a prompt's last also reaches the sampler at position 0, and the last
    one, which comes last, is the one kept."""
    import jax
    import numpy as np

    from rayfed_tpu.serving import sampling

    seen = {}
    choose = sampling.choose_tokens

    def record(logits, seeds, index):
        for row in np.flatnonzero(seeds):
            seen.setdefault(int(seeds[row]), {})[int(index[row])] = np.array(
                logits[row])

    def spy(logits, temperature, seeds, index):
        jax.debug.callback(record, logits, seeds, index, ordered=True)
        return choose(logits, temperature, seeds, index)

    monkeypatch.setattr(sampling, "choose_tokens", spy)
    return seen


# ``stats()`` keys that are live values or facts, not counters.
STATS_NOT_COUNTERS = {
    "pending", "active", "kv_blocks_in_use", "kv_blocks_free",
    "kv_block_size", "kv_token_bytes", "compiled_programs",
    "current_version", "swaps", "live_versions", "p50_ms", "p99_ms",
}


def assert_counters_agree(srv, st):
    """``st`` (the stopped engine's ``stats()``) against the telemetry
    registry and ``server._COUNTERS``: every counter key of ``stats()`` is
    a row of the table (or one of the model's ``step_counters``), and every
    row with a series reads there, under this server's label, what
    ``stats()`` says. Returns the rows' keys that ``stats()`` holds."""
    from rayfed_tpu.serving import server
    from rayfed_tpu.telemetry import metrics as telemetry_metrics

    reg = telemetry_metrics.get_registry()
    rows = {row.key: row for row in server._COUNTERS}
    for key in srv.pool.step_counters:
        rows[key] = server._Counter(key, "the model's own")
    counters = set(st) - STATS_NOT_COUNTERS
    assert counters <= set(rows), counters - set(rows)
    for key in counters:
        row = rows[key]
        if row.help is None:
            assert key not in srv._series
            continue
        labels = {"server": srv.name}
        if row.event:
            labels["event"] = key
        series = reg.get(row.series or f"fed_serving_{key}_total")
        assert series.labels(**labels).value() == st[key], key
        assert isinstance(st[key], int), key
    return counters
