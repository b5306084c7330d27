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

"""Serving-plane tests (docs/serving.md).

The load-bearing guarantees:
 - a hot swap mid-decode never aborts an in-flight request;
 - every response is produced entirely by exactly one model version
   (proved by matching each response bit-for-bit against a single-version
   reference generation);
 - fixed-seed output is bitwise-stable when no swap occurs;
 - continuous batching and the slot pool never mix rows (a request's
   output is independent of what shares its batch).
"""

from __future__ import annotations

import gc
import glob
import json
import os
import threading
import time
import weakref

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rayfed_tpu import tracing  # noqa: E402
from rayfed_tpu.config import ServingConfig  # noqa: E402
from rayfed_tpu.models import decode  # noqa: E402
from rayfed_tpu.models import transformer as tfm  # noqa: E402
from rayfed_tpu.serving import kv_pool, sampling  # noqa: E402
from rayfed_tpu.serving.kv_pool import PagedKVPool  # noqa: E402
from rayfed_tpu.serving.publish import ModelBank  # noqa: E402
from rayfed_tpu.telemetry import metrics as telemetry_metrics  # noqa: E402
from rayfed_tpu.serving.server import (  # noqa: E402
    InferenceServer,
    ServerOverloadedError,
    ServerStoppedError,
)

CFG = tfm.tiny_config(compute_dtype=jnp.float32)
PARAMS_A = tfm.init_params(jax.random.PRNGKey(0), CFG)
PARAMS_B = tfm.init_params(jax.random.PRNGKey(1), CFG)


def _server(**overrides):
    kwargs = dict(max_slots=4, max_len=32, max_new_tokens=8)
    kwargs.update(overrides)
    return InferenceServer(CFG, ServingConfig(**kwargs), params=PARAMS_A)


def _reference(params, prompt, max_new):
    gen = decode.make_generate_fn(CFG, max_new_tokens=max_new)
    out = np.asarray(gen(params, np.asarray(prompt, np.int32)[None]))
    return [int(t) for t in out[0, len(prompt):]]


# ---------------------------------------------------------------------------
# KV pool


def test_pool_acquire_release_cycle():
    pool = PagedKVPool(CFG, max_slots=2, max_len=8, block_size=4)
    a, b = pool.acquire(), pool.acquire()
    assert {a, b} == {0, 1}
    assert pool.acquire() is None
    pool.release(a)
    assert pool.acquire() == a
    with pytest.raises(ValueError):
        pool.release(b) or pool.release(b)


def test_pool_prefix_index_dropped_on_release():
    pool = PagedKVPool(CFG, max_slots=2, max_len=8, block_size=4)
    slot = pool.acquire()
    pool.note_prefix(slot, 1, b"abc")
    assert pool.lookup_prefix(1, b"abc") == slot
    assert pool.lookup_prefix(2, b"abc") is None  # version-scoped
    pool.release(slot)
    assert pool.lookup_prefix(1, b"abc") is None


def test_pool_block_zero_is_sacrificial_and_never_granted():
    pool = PagedKVPool(CFG, max_slots=2, max_len=8, block_size=4)
    k, _ = pool.kv
    # One block beyond num_blocks (rows of max_len + 1 = 9 positions
    # take 3 blocks of 4 each): block 0, where junk rows write.
    assert pool.num_blocks == 6 and k.shape[1:3] == (7, 4)
    slots = [pool.acquire(), pool.acquire()]
    for slot in slots:
        assert pool.ensure_blocks(slot, 8) == "ok"
    granted = np.concatenate([pool.table(slot) for slot in slots])
    assert sorted(granted) == [1, 2, 3, 4, 5, 6]
    assert pool.blocks_free == 0
    pool.release(slots[0])
    # A released slot's table points at block 0 again: ungranted.
    assert not pool.table(slots[0]).any() and pool.blocks_free == 3


# ---------------------------------------------------------------------------
# Model bank


def test_bank_swap_is_atomic_and_refcounted():
    bank = ModelBank()
    with pytest.raises(RuntimeError):
        bank.acquire()
    v1 = bank.publish(PARAMS_A)
    ver, params = bank.acquire()
    assert (v1, ver) == (1, 1)
    v2 = bank.publish(PARAMS_B)
    assert v2 == 2
    # v1 pinned by the in-flight request: still resolvable.
    assert bank.live_versions() == [1, 2]
    np.testing.assert_array_equal(
        np.asarray(bank.get(1)["embed"]), np.asarray(params["embed"])
    )
    bank.release(1)
    assert bank.live_versions() == [2]


def test_bank_snapshot_survives_caller_donation():
    bank = ModelBank()
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    bank.publish(tree)
    # The trainer immediately feeds the same buffers to a donating step;
    # the bank's snapshot must not alias them.
    jax.jit(lambda x: {"w": x["w"] * 0}, donate_argnums=0)(tree)
    _, snap = bank.acquire()
    np.testing.assert_array_equal(
        np.asarray(snap["w"]), np.arange(8, dtype=np.float32)
    )


# ---------------------------------------------------------------------------
# Engine: correctness of continuous batching


def test_single_request_matches_generate_fn():
    srv = _server()
    try:
        prompt = list(range(5, 15))
        resp = srv.submit_and_wait(prompt, max_new_tokens=6)
        assert resp["tokens"] == _reference(PARAMS_A, prompt, 6)
        assert resp["version"] == 1
        assert resp["prompt_len"] == 10
    finally:
        srv.stop()


def test_batched_rows_do_not_mix():
    """Distinct concurrent prompts each match their own solo reference —
    the vmapped pool step keeps rows independent."""
    srv = _server()
    try:
        prompts = [list(range(i, i + 6)) for i in range(1, 9)]
        futs = [srv.submit(p, max_new_tokens=5) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=120)["tokens"] == _reference(
                PARAMS_A, p, 5
            )
        assert srv.stats()["completed"] == 8
    finally:
        srv.stop()


def test_eos_exits_early_without_draining_batch():
    prompt = list(range(5, 15))
    ref = _reference(PARAMS_A, prompt, 8)
    eos = ref[2]  # greedy path is deterministic, so this token WILL appear
    srv = _server(eos_id=eos)
    try:
        resp = srv.submit_and_wait(prompt, max_new_tokens=8)
        first_eos = ref.index(eos)
        assert resp["tokens"] == ref[: first_eos + 1]
        assert len(resp["tokens"]) < 8
    finally:
        srv.stop()


def test_fixed_seed_output_bitwise_stable_without_swap():
    """Same workload, same seeds, two engine lifetimes -> identical
    tokens, byte for byte (the acceptance-criteria determinism claim)."""
    prompts = [list(range(i, i + 8)) for i in range(1, 7)]

    def run_once():
        srv = _server(temperature=0.7)
        try:
            futs = [
                srv.submit(p, max_new_tokens=6, seed=17 + i)
                for i, p in enumerate(prompts)
            ]
            return [f.result(timeout=120)["tokens"] for f in futs]
        finally:
            srv.stop()

    assert run_once() == run_once()


def test_prefix_reuse_hits_and_matches_full_prefill():
    srv = _server()
    try:
        prompt = list(range(7, 17))
        futs = [srv.submit(prompt, max_new_tokens=6) for _ in range(4)]
        outs = [f.result(timeout=120) for f in futs]
        ref = _reference(PARAMS_A, prompt, 6)
        for resp in outs:
            assert resp["tokens"] == ref
        assert srv.stats()["prefix_hits"] >= 1
        assert any(r["prefix_reuse"] for r in outs)
    finally:
        srv.stop()


def test_admission_control_rejects_when_full():
    # max_slots=1 + tiny queue: flood and expect loud rejections.
    srv = _server(max_slots=1, max_pending=2)
    try:
        futs, rejected = [], 0
        for i in range(30):
            try:
                futs.append(srv.submit([1, 2, 3, 4], max_new_tokens=8))
            except ServerOverloadedError:
                rejected += 1
        assert rejected >= 1
        for f in futs:
            f.result(timeout=120)
        assert srv.stats()["rejected"] == rejected
    finally:
        srv.stop()


def test_submit_after_stop_raises():
    srv = _server()
    srv.stop()
    with pytest.raises(ServerStoppedError):
        srv.submit([1, 2, 3])


def test_bad_request_fails_its_future_not_the_engine():
    srv = _server()
    try:
        with pytest.raises(ValueError):
            srv.submit([], max_new_tokens=4)          # empty prompt
        with pytest.raises(ValueError):
            srv.submit(list(range(30)), max_new_tokens=8)  # over max_len
        # Engine still serves.
        resp = srv.submit_and_wait([1, 2, 3], max_new_tokens=3)
        assert len(resp["tokens"]) == 3
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Hot swap under load


def test_swap_mid_decode_never_aborts_and_never_mixes_versions():
    """The tentpole guarantee: publish lands while 8+ requests are in
    flight; every request completes, and each one's tokens equal the
    single-version reference for the version it pinned at admission —
    any torn tree or cross-version cache/params mixing would break the
    bit-for-bit match."""
    srv = _server(max_slots=4, max_len=48, max_new_tokens=16)

    def wait_admitted(n, timeout=60):
        # Publish only once >= n requests were ADMITTED (slot claimed,
        # version pinned) so the swap provably lands mid-decode — the
        # engine races the publisher, and a publish that wins before any
        # admission would let every request pin the newest version.
        deadline = time.time() + timeout
        while time.time() < deadline:
            s = srv.stats()
            if s["active"] + s["completed"] >= n:
                return
            time.sleep(0.002)
        raise AssertionError("engine never admitted the load")

    try:
        prompt = list(range(3, 13))
        futs = [
            srv.submit(prompt, max_new_tokens=12, seed=i) for i in range(8)
        ]
        # Land swaps while the batch decodes.
        wait_admitted(1)  # someone pinned v1
        v2 = srv.publish(PARAMS_B)
        futs += [
            srv.submit(prompt, max_new_tokens=12, seed=50 + i)
            for i in range(8)
        ]
        wait_admitted(9)  # someone from the second wave pinned v2
        v3 = srv.publish(PARAMS_A)
        futs += [srv.submit(prompt, max_new_tokens=12, seed=99)]
        assert (v2, v3) == (2, 3)

        resps = [f.result(timeout=240) for f in futs]  # zero aborts
        assert len(resps) == 17
        refs = {
            1: _reference(PARAMS_A, prompt, 12),
            2: _reference(PARAMS_B, prompt, 12),
            3: _reference(PARAMS_A, prompt, 12),
        }
        seen = set()
        for resp in resps:
            assert resp["tokens"] == refs[resp["version"]], resp["version"]
            seen.add(resp["version"])
        assert len(seen) >= 2, "swap window never overlapped the load"
        # Retirement: nothing pins v1/v2 anymore.
        assert srv.bank.live_versions() == [3]
        assert srv.stats()["swaps"] == 3
    finally:
        srv.stop()


def test_concurrent_publishers_and_clients():
    """Swaps from a foreign thread while client threads hammer submit:
    exercises the admission/publish locking. Every response must still
    match one single-version reference exactly."""
    srv = _server(max_slots=4, max_len=48, max_new_tokens=16,
                  max_pending=256)
    try:
        prompt = list(range(4, 12))
        refs = {
            1: _reference(PARAMS_A, prompt, 8),
            2: _reference(PARAMS_B, prompt, 8),
            3: _reference(PARAMS_A, prompt, 8),
        }
        results, errors = [], []

        def client(n):
            try:
                for _ in range(n):
                    results.append(srv.submit_and_wait(prompt,
                                                       max_new_tokens=8))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(4,))
                   for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        srv.publish(PARAMS_B)
        time.sleep(0.3)
        srv.publish(PARAMS_A)
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors
        assert len(results) == 32
        for resp in results:
            assert resp["tokens"] == refs[resp["version"]]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Whole-request modes ride the same swap semantics


def test_beam_request_matches_beam_search_fn():
    srv = _server(max_len=48)
    try:
        prompt = list(range(5, 15))
        resp = srv.submit_and_wait(prompt, max_new_tokens=4, mode="beam",
                                   n_beams=3)
        fn = decode.make_beam_search_fn(CFG, max_new_tokens=4, n_beams=3)
        seqs, scores = fn(PARAMS_A, np.asarray(prompt, np.int32)[None])
        assert resp["tokens"] == [
            int(t) for t in np.asarray(seqs)[0, 0, len(prompt):]
        ]
        assert resp["scores"] == pytest.approx(
            [float(s) for s in np.asarray(scores)[0]]
        )
    finally:
        srv.stop()


def test_speculative_request_served():
    draft_cfg = tfm.tiny_config(
        compute_dtype=jnp.float32, d_model=32, n_heads=2, n_layers=1,
        d_ff=64,
    )
    draft_params = tfm.init_params(jax.random.PRNGKey(7), draft_cfg)
    srv = InferenceServer(
        CFG,
        ServingConfig(max_slots=2, max_len=48, max_new_tokens=8),
        draft_cfg=draft_cfg,
    )
    try:
        srv.publish(PARAMS_A, draft_params=draft_params)
        prompt = list(range(5, 15))
        resp = srv.submit_and_wait(prompt, max_new_tokens=6,
                                   mode="speculative")
        # Greedy speculative decode is bit-for-bit the target's greedy.
        assert resp["tokens"] == _reference(PARAMS_A, prompt, 6)
    finally:
        srv.stop()


def test_speculative_without_draft_rejected_at_submit():
    srv = _server()
    try:
        with pytest.raises(ValueError, match="draft_cfg"):
            srv.submit([1, 2, 3], mode="speculative")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Request timeline tracing


def test_request_timeline_export(tmp_path):
    tracing.clear()
    tracing.enable()
    try:
        srv = _server()
        try:
            resp = srv.submit_and_wait(list(range(5, 12)),
                                       max_new_tokens=4)
        finally:
            srv.stop()
        rid = resp["request_id"]
        events = [e.event for e in tracing.get_request_events(rid)]
        for needed in ("enqueue", "admit", "prefill", "first_token",
                       "finish"):
            assert needed in events, (needed, events)
        timeline = tracing.request_timelines()[rid]
        times = [e.t_s for e in timeline]
        assert times == sorted(times)

        path = str(tmp_path / "requests.json")
        n = tracing.export_request_timeline(path, party="alice")
        assert n >= 5
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["party"] == "alice"
        assert [e["event"] for e in doc["requests"][rid]] == events
    finally:
        tracing.disable()
        tracing.clear()


def test_request_timeline_noop_when_disabled():
    tracing.clear()
    srv = _server()
    try:
        srv.submit_and_wait([1, 2, 3], max_new_tokens=2)
        assert tracing.get_request_events() == []
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Retired options: refused by name, and the benchmark's own files build


@pytest.mark.parametrize("build", [
    lambda: ServingConfig(kv_layout="slab"),
    lambda: ServingConfig.from_dict({"kv_layout": "slab"}),
], ids=["init", "from_dict"])
def test_slab_layout_is_refused_by_name(build):
    with pytest.raises(ValueError, match="kv_layout.*removed in PR 29"):
        build()
    assert ServingConfig.from_dict({"kv_layout": "paged"}).max_slots == 8


def test_mode_is_an_unknown_serving_key():
    with pytest.raises(ValueError, match="unknown serving config key 'mode'"):
        ServingConfig.from_dict({"mode": "continuous"})


def _mix_serving_blocks():
    root = os.path.join(os.path.dirname(__file__), "..", "chipbench", "mixes")
    for path in sorted(glob.glob(os.path.join(root, "*.json"))):
        with open(path) as f:
            mix = json.load(f)
        for where, block in (("top", mix), ("rehearsal", mix.get("rehearsal"))):
            if block and "serving" in block:
                yield pytest.param(
                    block["serving"],
                    id=f"{os.path.basename(path)[:-5]}-{where}",
                )


@pytest.mark.parametrize("serving", _mix_serving_blocks())
def test_benchmark_mix_serving_blocks_build(serving):
    # The mix files are the benchmark's (no PR but a `benchmark` one may
    # edit them) and from_dict is strict: a key retired here while they
    # still pass it would fail every serving cell.
    scfg = ServingConfig.from_dict(serving)
    assert scfg.kv_layout == "paged"


# ---------------------------------------------------------------------------
# Executor opt-out (the serving submit path depends on it)


def test_executor_eager_false_goes_to_pool():
    from rayfed_tpu._private.executor import LocalExecutor

    ex = LocalExecutor(max_workers=2)
    try:
        started = threading.Event()
        release = threading.Event()

        def blocker():
            started.set()
            release.wait(30)
            return "done"

        # eager=True would run this inline and deadlock the caller here;
        # eager=False must return a pending future immediately.
        fut = ex.submit(blocker, (), {}, eager=False)
        assert started.wait(10)
        assert not fut.done()
        release.set()
        assert fut.result(10) == "done"
    finally:
        ex.shutdown()


# ---------------------------------------------------------------------------
# Two-party e2e: fed.serve on alice, submits from both drivers, a hot
# swap whose params arrive as an owner-push over the wire from bob.

from tests.utils import FAST_COMM_CONFIG, get_addresses, run_parties  # noqa: E402

import rayfed_tpu as fed  # noqa: E402

CONFIG = {
    "cross_silo_comm": dict(FAST_COMM_CONFIG),
    "serving": {"max_slots": 4, "max_len": 48, "max_new_tokens": 8},
}


@fed.remote
def _fresh_params(seed):
    return tfm.init_params(jax.random.PRNGKey(seed), CFG)


def run_serve_two_party(party, addresses):
    fed.init(addresses=addresses, party=party, config=CONFIG)
    handle = fed.serve("alice", CFG, params=PARAMS_A)
    prompt = list(range(5, 13))

    futs = [handle.submit(prompt, max_new_tokens=6, seed=i)
            for i in range(4)]
    # Swap mid-flight; the new tree is produced AT BOB, so the publish is
    # an owner-push of the param tree over the bulk lane.
    v2 = handle.publish(_fresh_params.party("bob").remote(1))
    futs += [handle.submit(prompt, max_new_tokens=6, seed=10 + i)
             for i in range(2)]

    resps = [fed.get(f) for f in futs]
    assert fed.get(v2) == 2
    refs = {
        1: _reference(PARAMS_A, prompt, 6),
        2: _reference(PARAMS_B, prompt, 6),
    }
    for resp in resps:  # zero aborts; one version end to end, each
        assert resp["tokens"] == refs[resp["version"]], resp["version"]

    stats = fed.get(handle.stats())
    assert stats["completed"] >= 6
    assert stats["current_version"] == 2
    assert fed.get(handle.shutdown()) is True
    fed.shutdown()


def test_serve_two_party_e2e():
    run_parties(run_serve_two_party, ["alice", "bob"])


# ---------------------------------------------------------------------------
# The paged engine. The bitwise contract — a request's output depends only
# on (version, prompt, seed), never on what shares its batch — holds among
# its own schedules. Against the plain cached forward, one request at a
# time, the step agrees to rounding (its online softmax re-associates the
# sum); the six seeded prompts below still sample the same tokens.


_CHOOSE = jax.jit(sampling.choose_packed)


def _choose(logits, temperature, seed, index):
    """The engine's own sampler on a batch of rows, outside any engine;
    a scalar stands for every row."""
    rows = len(logits)
    scalars = (np.broadcast_to(np.asarray(x, object), (rows,))
               for x in (temperature, seed, index))
    return np.asarray(_CHOOSE(
        jnp.asarray(logits, jnp.float32), sampling.pack(*scalars)))


def _follow_alone(params, prompt, n, max_len, choose):
    """``decode.forward_with_cache``, one request alone: ``n`` tokens,
    token ``i`` being ``choose(its float32 logits (V,), i)``. Returns
    (the tokens, the logits each was chosen from)."""
    cache = decode.init_cache(CFG, 1, max_len + 1)
    logits, cache = decode.forward_with_cache(
        params, jnp.asarray([prompt], jnp.int32), cache, 0, CFG
    )
    row, toks, rows = logits[0, len(prompt) - 1], [], []
    for i in range(n):
        rows.append(np.asarray(row, np.float32))
        toks.append(int(choose(rows[-1], i)))
        if i + 1 < n:
            logits, cache = decode.forward_with_cache(
                params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
                len(prompt) + i, CFG,
            )
            row = logits[0, 0]
    return toks, np.stack(rows)


def _sampled_reference(params, prompt, max_new, temperature, seed, max_len):
    """The plain cached forward, one request alone, each token chosen by
    the device rule: the engine's own sampler (``serving/sampling.py``) on
    that position's logits, keyed by (seed, position in the output)."""
    return _follow_alone(
        params, prompt, max_new, max_len,
        lambda row, i: _choose(row[None], temperature, seed, i)[0])[0]


def test_mixed_lengths_match_the_plain_reference():
    rng = np.random.default_rng(7)
    prompts = [
        [int(t) for t in rng.integers(1, 255, size=n)]
        for n in (3, 9, 14, 5, 12, 7)
    ]
    srv = _server(temperature=0.8)
    try:
        futs = [
            srv.submit(p, max_new_tokens=8, seed=i)
            for i, p in enumerate(prompts)
        ]
        outs = [f.result(timeout=120)["tokens"] for f in futs]
    finally:
        srv.stop()
    assert outs == [
        _sampled_reference(PARAMS_A, p, 8, 0.8, i, max_len=32)
        for i, p in enumerate(prompts)
    ]


def test_greedy_is_argmax_of_the_plain_references_float32_logits():
    rng = np.random.default_rng(5)
    prompts = [
        [int(t) for t in rng.integers(1, 255, size=n)] for n in (4, 11, 7)
    ]
    srv = _server()
    try:
        futs = [srv.submit(p, max_new_tokens=8) for p in prompts]
        outs = [f.result(timeout=120)["tokens"] for f in futs]
    finally:
        srv.stop()
    for p, toks in zip(prompts, outs):
        # Teacher-forced along the served tokens: the logits each came from.
        _, logits = _follow_alone(PARAMS_A, p, len(toks), 32,
                                  lambda row, i: toks[i])
        assert toks == [int(t) for t in np.argmax(logits, -1)]


@pytest.mark.parametrize("neighbour_temperature", [0.0, 0.8],
                         ids=["all_greedy", "beside_a_sampled_row"])
def test_a_greedy_tie_goes_to_the_first_index(neighbour_temperature):
    """``np.argmax``'s rule, on either branch of the sampler: whether or
    not another row of the batch makes the noise run."""
    logits = np.random.default_rng(2).normal(size=(3, 64)).astype(np.float32)
    logits[0, [9, 30, 51]] = logits[0].max() + 1.0      # a three-way tie
    logits[1, 63], logits[1, 0] = 7.0, 7.0
    got = _choose(logits, [0.0, 0.0, neighbour_temperature], 4, 0)
    assert list(got[:2]) == [9, 0] == list(np.argmax(logits[:2], -1))


SAMPLED = dict(max_new_tokens=8, temperature=0.8, seed=2**33 + 77)


def _sampled_alone(prompt, **server):
    srv = _server(**server)
    try:
        return srv.submit(prompt, **SAMPLED).result(timeout=120)["tokens"]
    finally:
        srv.stop()


@pytest.mark.parametrize(
    "schedule", ["full_mixed_batch", "chunked_prefill", "preempted"])
def test_a_sampled_request_serves_the_same_tokens_whatever_the_schedule(
        schedule):
    """The key of a draw is (seed, position in the output): not the slot,
    not the neighbours, not how the prompt went in, and a preemption's
    re-run has no generator state to rewind."""
    rng = np.random.default_rng(13)
    prompt = [int(t) for t in rng.integers(1, 255, size=12)]
    others = [[int(t) for t in rng.integers(1, 255, size=n)]
              for n in (8, 8, 8, 8, 8)]
    alone = _sampled_alone(prompt)
    assert alone == _sampled_reference(
        PARAMS_A, prompt, SAMPLED["max_new_tokens"], SAMPLED["temperature"],
        SAMPLED["seed"], max_len=32)
    if schedule == "chunked_prefill":
        srv = _server(prefill_chunk=8, prefill_token_budget=8)
        others = []
    elif schedule == "preempted":
        # Four rows in lockstep need a 4th block each with none free
        # (test_preemption_under_block_pressure_...): the youngest, the
        # sampled request submitted last, is preempted and re-run.
        prompt, others = prompt[:8], others[:3]
        alone = _sampled_alone(prompt)
        srv = _server(kv_block_size=4, kv_blocks=12)
    else:
        srv = _server()
    try:
        # Neighbours greedy and sampled, the same seed among them.
        futs = [srv.submit(p, max_new_tokens=8, temperature=0.9 * (i % 2),
                           seed=SAMPLED["seed"])
                for i, p in enumerate(others)]
        got = srv.submit(prompt, **SAMPLED).result(timeout=120)["tokens"]
        for f in futs:
            f.result(timeout=120)
        st = srv.stats()
    finally:
        srv.stop()
    assert got == alone
    if schedule == "chunked_prefill":
        assert st["prefill_chunks"] >= 2
    if schedule == "preempted":
        assert st["preempted"] >= 1


@pytest.mark.parametrize("axis", ["seed", "index"])
def test_draws_follow_softmax_of_logits_over_temperature(axis):
    """512 draws at fixed keys against the exact distribution of a
    vocabulary of 8, along either half of the key; the tolerance is 3.5
    standard deviations of the likeliest token's frequency."""
    n, temperature = 512, 0.7
    logits = np.array([2.0, 1.0, 0.5, 0.0, -0.5, 1.5, -2.0, 0.2], np.float32)
    z = logits.astype(np.float64) / temperature
    want = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
    seed, index = (np.arange(n), 3) if axis == "seed" else (11, np.arange(n))
    toks = _choose(np.tile(logits, (n, 1)), temperature, seed, index)
    got = np.bincount(toks, minlength=8) / n
    assert np.abs(got - want).max() < 3.5 * np.sqrt(0.25 / n)
    assert len(set(toks)) >= 6


def test_fetch_bytes_are_the_ids_and_nothing_else():
    """A prefill round and a decode step each fetch ``max_slots`` int32,
    a last chunk one."""
    srv = _server(max_len=48, prefill_chunk=8, prefill_token_budget=16)
    try:
        srv.submit_and_wait(list(range(1, 7)), max_new_tokens=5)
        srv.submit_and_wait(list(range(1, 22)), max_new_tokens=4)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["steps"] == 4 + 3 and st["prefill_chunks"] == 3
    assert st["fetch_bytes"] == 4 * 4 * (st["steps"] + 1) + 4
    reg = telemetry_metrics.get_registry()
    assert reg.get("fed_serving_fetch_bytes_total").labels(
        server="default").value() >= st["fetch_bytes"]


def test_no_program_of_the_engine_returns_a_vocabulary_sized_axis():
    srv = _server(max_len=40)           # no shape of the pool is 256
    try:
        pool, R = srv.pool, srv.pool.max_slots
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        L, _, _, H, Dh = pool.kv[0].shape
        slab = jax.ShapeDtypeStruct((L, R, pool.row_len, H, Dh),
                                    pool.kv[0].dtype)
        tables = i32(R, pool.blocks_per_row)
        outs = {
            "decode_step": jax.eval_shape(
                pool._decode_step_fn, PARAMS_A, pool.kv, i32(R), i32(R),
                tables, i32(3, R), i32(R),
                jax.ShapeDtypeStruct((R,), bool), {}, None),
            "prefill_rows": jax.eval_shape(
                srv._get_prefill_rows_fn(16), PARAMS_A, i32(R, 16), i32(R),
                jax.ShapeDtypeStruct((R,), bool), i32(3, R)),
            "chunk_step": jax.eval_shape(
                srv._get_chunk_fn(8), PARAMS_A, pool.kv, {},
                i32(pool.blocks_per_row), i32(), i32(8), i32(), i32(),
                i32(3, 1)),
            "scatter_rows": jax.eval_shape(
                pool._scatter_rows_fn, pool.kv, (slab, slab), tables, {},
                {}, None),
            "copy_block": jax.eval_shape(
                kv_pool._copy_block, pool.kv, i32(), i32()),
        }
        assert len(outs) == len(pool.jitted_fns()) + 2
    finally:
        srv.stop()
    for name, out in outs.items():
        for leaf in jax.tree_util.tree_leaves(out):
            assert CFG.vocab not in leaf.shape, (name, leaf.shape)
    ids = [jax.tree_util.tree_leaves(outs[n])[0]
           for n in ("decode_step", "prefill_rows", "chunk_step")]
    assert [(x.shape, x.dtype) for x in ids] == [
        ((4,), jnp.int32), ((4,), jnp.int32), ((), jnp.int32)]


@pytest.mark.parametrize("temperatures, drew", [
    ((0.0, 0.0, 0.0), False), ((0.0, 0.8, 0.0), True),
], ids=["all_greedy", "one_sampled"])
def test_draw_steps_count_the_steps_in_which_a_live_row_was_sampled(
        temperatures, drew):
    srv = _server()
    try:
        futs = [srv.submit(list(range(3 + i, 9 + i)), max_new_tokens=6,
                           temperature=t, seed=i)
                for i, t in enumerate(temperatures)]
        for f in futs:
            f.result(timeout=120)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["steps"] >= 5
    assert st["draw_steps"] == (st["steps"] if drew else 0)


def test_every_counter_is_one_row_bumped_once_in_stats_and_registry():
    """A fixed run on the dense engine: greedy and sampled requests, one
    streamed, one prompt long enough for chunks, a prefix hit, one
    rejected. Every counter key of ``stats()`` is a row of
    ``server._COUNTERS`` and reads the same in the registry's series for
    this server; the rows gated by an optional protocol member are
    absent, the keys without a series have none."""
    from rayfed_tpu.serving import server
    from tests.utils import assert_counters_agree

    srv = InferenceServer(
        CFG, ServingConfig(max_slots=4, max_len=48, max_new_tokens=8,
                           prefill_chunk=8, prefill_token_budget=16),
        params=PARAMS_A, name="counters-dense")
    rng = np.random.default_rng(5)
    short, long = ([int(t) for t in rng.integers(1, 255, size=n)]
                   for n in (6, 21))
    try:
        srv.submit_and_wait(short, max_new_tokens=5)
        fut, stream = srv.submit_stream(
            short[:5], max_new_tokens=6, temperature=0.8, seed=7)
        assert len(list(stream)) == 6 == len(fut.result()["tokens"])
        srv.submit_and_wait(long, max_new_tokens=4)
        # (The donor is still running when its twin is admitted.)
        futs = [srv.submit(short, max_new_tokens=30) for _ in range(2)]
        for fut in futs:
            fut.result()
        srv.scfg.max_pending = 0
        with pytest.raises(ServerOverloadedError):
            srv.submit(short)
        st = srv.stats()
    finally:
        srv.stop()
    counters = assert_counters_agree(srv, st)
    gated = {row.key for row in server._COUNTERS if row.gate}
    assert counters == {row.key for row in server._COUNTERS} - gated
    assert (st["submitted"], st["completed"], st["rejected"]) == (5, 5, 1)
    assert st["tokens_out"] == 5 + 6 + 4 + 30 + 30
    assert st["streamed_tokens"] == 6 and st["draw_steps"] >= 5
    assert st["prefill_chunks"] == 3 and st["prefix_hits"] >= 1
    assert st["prefill_tokens"] >= 6 + 5 + 21
    assert st["state_rows_held"] == st["ssm_state_bytes"] == 0
    # A block row's series is every server's; an index row's is not.
    snap = telemetry_metrics.get_registry().snapshot()

    def servers(name):
        return {s["labels"]["server"]
                for s in snap.get(name, {}).get("series", [])}

    assert "counters-dense" in servers(
        "fed_serving_diffusion_positions_dropped_total")
    assert "counters-dense" not in servers(
        "fed_serving_index_keys_scored_total")


def test_wrapping_the_sample_seam_alters_tokens_and_keeps_no_engine_alive():
    """What the benchmark's ``--inject broken-token`` does: every token
    goes through ``_sample``, and the original kept in a local is a
    function, not a bound method that would hold the stopped engine (its
    pool, its weights) on the device."""
    prompt = list(range(5, 15))
    srv = _server(prefix_reuse=False)
    sample_fn = srv._sample
    seen = []

    def broken(chosen, req):
        seen.append(int(chosen))
        return (sample_fn(chosen, req) + 1) % CFG.vocab

    srv._sample = broken
    alive = weakref.ref(srv)
    try:
        out = srv.submit_and_wait(prompt, max_new_tokens=5)["tokens"]
    finally:
        srv.stop()
    assert out == [(t + 1) % CFG.vocab for t in seen] and len(seen) == 5
    assert out[0] == (_reference(PARAMS_A, prompt, 1)[0] + 1) % CFG.vocab
    del srv, broken
    gc.collect()
    assert sample_fn(7, None) == 7 and alive() is None


def test_chunked_prefill_matches_reference():
    srv = _server(max_len=48, prefill_chunk=8, prefill_token_budget=16)
    try:
        rng = np.random.default_rng(3)
        prompt = [int(t) for t in rng.integers(1, 255, size=21)]
        resp = srv.submit_and_wait(prompt, max_new_tokens=6)
        assert resp["tokens"] == _reference(PARAMS_A, prompt, 6)
        # 21 tokens at chunk 8: ragged 5 first, then 8 + 8.
        assert srv.stats()["prefill_chunks"] >= 3
    finally:
        srv.stop()


def test_preemption_under_block_pressure_matches_unconstrained():
    rng = np.random.default_rng(11)
    prompts = [
        [int(t) for t in rng.integers(1, 255, size=8)] for _ in range(6)
    ]

    def run(**kw):
        srv = _server(max_slots=4, kv_block_size=4, **kw)
        try:
            futs = [
                srv.submit(p, max_new_tokens=8, seed=i)
                for i, p in enumerate(prompts)
            ]
            out = [f.result(timeout=120)["tokens"] for f in futs]
            return out, srv.stats()
        finally:
            srv.stop()

    base, _ = run()
    # The 4 rows decode in lockstep and each grows to 3 blocks by
    # position 8 — exactly the pool's 12 grantable blocks. At position
    # 12 all four need a 4th block with zero free and none finished: a
    # true deadlock only preemption can break. The preempt-and-replay
    # must be invisible in the output.
    tight, st = run(kv_blocks=12)
    assert tight == base
    assert st["preempted"] >= 1
    assert st["completed"] == len(prompts)
    assert st["kv_blocks_in_use"] == 0


def test_mixed_length_fragmentation_shorts_overtake_long_prompt():
    """16 short requests race one 1024-token prompt: chunked prefill
    must interleave the long prompt's chunks with live decode so the
    shorts finish first instead of queueing behind a monolithic
    prefill."""
    long_len = 1024
    srv = _server(
        max_slots=8, max_len=long_len + 16, max_new_tokens=16,
        max_pending=64, prompt_buckets=[16, long_len],
    )
    try:
        rng = np.random.default_rng(42)
        long_prompt = np.asarray(
            rng.integers(1, 255, size=long_len), np.int32
        )
        done_at = {}
        lock = threading.Lock()
        t0 = time.perf_counter()

        def short_client(ci):
            r = np.random.default_rng(100 + ci)
            p = [int(t) for t in r.integers(1, 255, size=int(r.integers(4, 13)))]
            srv.submit_and_wait(p, max_new_tokens=8)
            with lock:
                done_at[ci] = time.perf_counter() - t0

        long_fut = srv.submit(long_prompt, max_new_tokens=8)
        threads = [
            threading.Thread(target=short_client, args=(i,))
            for i in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        long_resp = long_fut.result(timeout=300)
        long_done = time.perf_counter() - t0
        assert len(long_resp["tokens"]) == 8
        st = srv.stats()
        assert st["prefill_chunks"] >= long_len // 32
        # The long prompt needs >= 32 budgeted chunk steps; every short
        # (8 tokens of decode) must land well inside that window.
        assert sum(1 for dt in done_at.values() if dt < long_done) >= 8
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Token streaming (in-process; the wire path is covered by the e2e below)


def test_stream_matches_complete_response():
    srv = _server()
    try:
        prompt = list(range(5, 15))
        fut, stream = srv.submit_stream(prompt, max_new_tokens=8)
        streamed = list(stream)
        resp = fut.result(timeout=120)
        assert streamed == resp["tokens"] == _reference(PARAMS_A, prompt, 8)
        assert stream.first_token_s is not None
        assert srv.stats()["streamed_tokens"] >= len(streamed)
    finally:
        srv.stop()


def test_slow_stream_consumer_never_blocks_engine():
    srv = _server()
    try:
        prompt = list(range(5, 15))
        fut, stream = srv.submit_stream(prompt, max_new_tokens=8)
        # NOBODY consumes the stream; the engine must still finish this
        # request, free its KV blocks, and keep serving others.
        resp = fut.result(timeout=120)
        others = [
            srv.submit(list(range(2, 10)), max_new_tokens=6, seed=i)
            for i in range(4)
        ]
        for f in others:
            f.result(timeout=120)
        assert srv.stats()["kv_blocks_in_use"] == 0
        # The unread tokens are still there once the consumer catches up.
        assert stream.tokens() == resp["tokens"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Run-ahead: a decode step is dispatched before the one before it is
# fetched (its rows' tokens stay on the device), and the host reads every
# id one dispatch late. The result is the same work; what the lag changes
# is spelled out below, one property a test. Every wait is bounded.

WAIT_S = 120


def _wait_for(what, holds, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if holds():
            return
        time.sleep(0.002)
    raise AssertionError(f"never saw: {what}")


def _decode_keys(cfg, prompts, outs):
    """``decode_keys_attended`` of exactly the steps these outputs need:
    a request's token ``i >= 1`` comes from a step at position ``len(prompt)
    + i - 1`` that scores ``position + 1`` keys a layer. A row that was
    live in one step more reads above this."""
    return cfg.n_layers * sum(
        len(p) + i for p, toks in zip(prompts, outs)
        for i in range(1, len(toks)))


def _dense_engine():
    def want(prompt, toks, temperature, seed):
        return _sampled_reference(PARAMS_A, prompt, len(toks), temperature,
                                  seed, max_len=32)

    return (lambda **kw: _server(max_slots=3, **kw)), want, CFG


def _recurrent_engine():
    import test_falcon_h1 as fh_t       # its tiny config, weights, reference

    def want(prompt, toks, temperature, seed):
        # Teacher-forced along what was served: each token must be the
        # sampler's choice on the plain reference's logits there.
        logits = fh_t._ref_logits(prompt + toks[:-1])[len(prompt) - 1:]
        return [int(t) for t in _choose(logits, temperature, seed,
                                        np.arange(len(toks)))]

    return (lambda **kw: fh_t._server(max_slots=3, max_len=32, **kw)), want, \
        fh_t.CFG


@pytest.mark.parametrize("engine", [_dense_engine, _recurrent_engine],
                         ids=["dense", "recurrent_state"])
def test_run_ahead_serves_the_plain_references_tokens(engine):
    """Greedy and sampled rows of unequal lengths over fewer slots than
    requests: rows join a batch whose step is in flight and leave it
    while the next one is."""
    make, want, cfg = engine()
    rng = np.random.default_rng(17)
    prompts = [[int(t) for t in rng.integers(1, 255, size=n)]
               for n in (5, 9, 3, 12, 7, 4, 10)]
    lengths = [9, 3, 6, 2, 8, 5, 7]
    temps = [0.0, 0.8, 0.0, 0.8, 0.7, 0.0, 0.9]
    srv = make()
    try:
        futs = [srv.submit(p, max_new_tokens=n, temperature=t, seed=100 + i)
                for i, (p, n, t) in enumerate(zip(prompts, lengths, temps))]
        outs = [f.result(timeout=WAIT_S)["tokens"] for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    for i, (p, n, t, toks) in enumerate(zip(prompts, lengths, temps, outs)):
        assert len(toks) == n and toks == want(p, toks, t, 100 + i), i
    assert st["steps_ahead"] > 0 and st["rows_wasted"] == 0
    assert st["decode_keys_attended"] == _decode_keys(cfg, prompts, outs)


def test_an_eos_read_one_step_late_drops_the_id_of_the_step_ahead():
    """A row that ends by ``eos_id`` was already live in the step ahead:
    that id never reaches its output, the step is counted as dispatched
    (``rows_wasted``, the keys it read), and the slot's next tenant, who
    is admitted behind that step, decodes what it decodes alone."""
    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(1, 255, size=n)]
               for n in (10, 6, 8, 5)]
    refs = [_reference(PARAMS_A, p, 8) for p in prompts]
    eos = refs[0][2]

    def cut(ref):
        return ref[:ref.index(eos) + 1] if eos in ref else ref

    srv = _server(max_slots=2, eos_id=eos)
    try:
        futs = [srv.submit(p, max_new_tokens=8) for p in prompts]
        outs = [f.result(timeout=WAIT_S)["tokens"] for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    assert outs == [cut(ref) for ref in refs]
    assert outs[0][-1] == eos and len(outs[0]) <= 3
    # Wasted: ended by eos at a token a decode step chose (not the
    # prefill's), with room left under max_new_tokens.
    wasted = [(p, toks) for p, toks in zip(prompts, outs)
              if toks[-1] == eos and 1 < len(toks) < 8]
    assert st["rows_wasted"] == len(wasted) >= 1
    assert st["decode_keys_attended"] == _decode_keys(CFG, prompts, outs) + \
        CFG.n_layers * sum(len(p) + len(toks) for p, toks in wasted)
    assert st["completed"] == 4 and st["kv_blocks_in_use"] == 0


@pytest.mark.parametrize("lengths", [(5,), (2, 6, 4), (1, 3)],
                         ids=["alone", "three_rows", "ends_at_its_prefill"])
def test_a_row_at_max_new_tokens_is_not_in_the_step_ahead(lengths):
    """The host knows that end ahead: no row is live in a step after its
    last token, so the steps read exactly the keys the outputs need."""
    prompts = [list(range(3 + i, 9 + 2 * i)) for i in range(len(lengths))]
    srv = _server()
    try:
        futs = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, lengths)]
        outs = [f.result(timeout=WAIT_S)["tokens"] for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    assert [len(toks) for toks in outs] == list(lengths)
    assert st["rows_wasted"] == 0
    assert st["decode_keys_attended"] == _decode_keys(CFG, prompts, outs)
    if len(lengths) == 1:
        assert st["steps"] == lengths[0] - 1


@pytest.mark.parametrize("how", ["block_pressure", "with_its_id_in_flight"])
def test_a_preemption_with_a_step_in_flight_replays_to_the_same_tokens(how):
    """The victim's in-flight id is dropped with its blocks; the replay
    serves identical tokens and a stream skips it."""
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(1, 255, size=8)]
               for _ in range(4)]
    refs = [_reference(PARAMS_A, p, 8) for p in prompts]
    if how == "block_pressure":
        # test_preemption_under_block_pressure_...'s deadlock: the grants
        # that fail are those of the step ahead.
        srv = _server(kv_block_size=4, kv_blocks=12)
    else:
        # Scripted on the engine thread, between two iterations: the
        # youngest row is preempted while the step it is live in has
        # been dispatched and not fetched.
        srv = _server(kv_block_size=4)
        tick, hit = srv._prefill_tick, []

        def tick_then_preempt():
            ran = tick()
            rows = [r for r in srv._active.values()
                    if r.ahead and len(r.out) >= 3]
            if rows and not hit:
                hit.append(max(rows, key=lambda r: r.enqueue_s))
                srv._preempt(hit[0])
            return ran

        srv._prefill_tick = tick_then_preempt
    try:
        pairs = [srv.submit_stream(p, max_new_tokens=8) for p in prompts]
        outs = [f.result(timeout=WAIT_S)["tokens"] for f, _ in pairs]
        streamed = [s.tokens() for _, s in pairs]
        st = srv.stats()
    finally:
        srv.stop()
    assert outs == refs == streamed
    assert st["preempted"] >= 1 and st["steps_ahead"] > 0
    assert st["rows_wasted"] == 0 and st["kv_blocks_in_use"] == 0


@pytest.mark.parametrize("how", ["stop", "fault_at_dispatch",
                                 "fault_at_fetch"])
def test_stop_and_a_fault_with_a_step_in_flight_settle_every_future(how):
    """``stop()`` finishes what was admitted (the step in flight is
    fetched, not abandoned); an engine fault fails it. No future hangs."""
    prompts = [list(range(2 + i, 12 + i)) for i in range(6)]
    srv = _server(max_slots=2, max_new_tokens=16)

    def boom(*args, **kwargs):
        raise RuntimeError("injected engine fault")

    try:
        futs = [srv.submit(p, max_new_tokens=16) for p in prompts]
        _wait_for("a step dispatched ahead",
                  lambda: srv.stats()["steps_ahead"] >= 2)
        if how == "fault_at_dispatch":
            srv.pool.decode_step = boom
        elif how == "fault_at_fetch":
            srv._fetch = boom
        else:
            srv.stop(timeout=WAIT_S)
        srv._engine.join(WAIT_S)
        assert not srv._engine.is_alive()
        done, failed = [], []
        for f in futs:
            try:
                done.append(f.result(timeout=WAIT_S)["tokens"])
            except (ServerStoppedError, RuntimeError) as e:
                failed.append(e)
    finally:
        srv.stop()
    assert len(done) + len(failed) == len(futs) and not srv._ahead
    if how == "stop":
        # Admitted requests complete; the queued ones fail fast.
        assert done and done == [
            _reference(PARAMS_A, p, 16) for p in prompts[:len(done)]]
        assert all(isinstance(e, ServerStoppedError) for e in failed)
    else:
        assert failed and all("injected" in str(e) for e in failed)
        with pytest.raises(ServerStoppedError):
            srv.submit(prompts[0])


def test_two_versions_across_a_publish_each_run_their_own_chain():
    """While two versions are live each has its own step in flight, and a
    row takes its token from its own version's ids."""
    srv = _server(max_slots=4, max_len=48)
    dispatch, in_flight = srv._dispatch, []

    def spy(*args):
        ahead = dispatch(*args)
        in_flight.append(sorted(srv._ahead))
        return ahead

    srv._dispatch = spy
    try:
        prompts = [list(range(3 + i, 13 + i)) for i in range(4)]
        futs = [srv.submit(p, max_new_tokens=30) for p in prompts[:2]]
        _wait_for("version 1 decoding", lambda: srv.stats()["steps"] >= 2)
        assert srv.publish(PARAMS_B) == 2
        futs += [srv.submit(p, max_new_tokens=12) for p in prompts[2:]]
        resps = [f.result(timeout=WAIT_S) for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    assert [r["version"] for r in resps] == [1, 1, 2, 2]
    for p, r in zip(prompts, resps):
        assert r["tokens"] == _reference(
            PARAMS_A if r["version"] == 1 else PARAMS_B, p, len(r["tokens"]))
    assert [1, 2] in in_flight
    assert st["rows_wasted"] == 0 and st["steps_ahead"] > 0


def test_steps_ahead_are_the_steps_less_those_with_nothing_in_flight():
    """One request at a time, each after the last has finished: a
    request's first decode step finds nothing in flight, every other was
    dispatched before the one before it was fetched."""
    lengths = (5, 4, 7)
    srv = _server()
    try:
        for i, n in enumerate(lengths):
            srv.submit(list(range(4 + i, 12 + i)),
                       max_new_tokens=n).result(timeout=WAIT_S)
        st = srv.stats()
    finally:
        srv.stop()
    assert st["steps"] == sum(n - 1 for n in lengths)
    assert st["steps_ahead"] == st["steps"] - len(lengths)
    reg = telemetry_metrics.get_registry()
    for name in ("steps_ahead", "rows_wasted"):
        assert reg.get(f"fed_serving_{name}_total").labels(
            server="default").value() >= st[name]


def test_every_emitted_token_passes_the_sample_seam_exactly_once():
    """The benchmark's control wraps ``_sample``: an id dropped with the
    step ahead never passes it, every served token does, once, and what
    the wrapper makes of it is what is served."""
    rng = np.random.default_rng(29)
    prompts = [[int(t) for t in rng.integers(1, 255, size=n)]
               for n in (6, 9, 4, 7, 5)]

    def run(eos_id):
        srv = _server(max_slots=2, eos_id=eos_id)
        sample_fn, seen = srv._sample, {}

        def altered(chosen, req):
            seen.setdefault(req.rid, []).append(int(chosen))
            return (sample_fn(chosen, req) + 1) % CFG.vocab

        srv._sample = altered
        try:
            # The first alone (an altered token feeds the next step only
            # where nothing was in flight: its schedule is fixed), then
            # the rest over two slots.
            resps = [srv.submit(prompts[0], max_new_tokens=8).result(WAIT_S)]
            futs = [srv.submit(p, max_new_tokens=8) for p in prompts[1:]]
            resps += [f.result(timeout=WAIT_S) for f in futs]
            return resps, seen, srv.stats()
        finally:
            srv.stop()

    first = run(None)[0][0]["tokens"]
    eos = first[2]
    assert eos not in first[:2]
    resps, seen, st = run(eos)
    for r in resps:
        assert r["tokens"] == [(t + 1) % CFG.vocab
                               for t in seen[r["request_id"]]]
    assert resps[0]["tokens"] == first[:3] and st["rows_wasted"] >= 1
    assert sum(map(len, seen.values())) == st["tokens_out"]


# ---------------------------------------------------------------------------
# Per-block-grant tenancy accounting


def test_paged_kv_quota_trip_fails_request_and_cleans_ledger():
    from rayfed_tpu.tenancy import context as tenancy
    from rayfed_tpu.tenancy import qos as tenancy_qos
    from rayfed_tpu.tenancy.context import TenancyConfig, TenantQuotaExceeded

    ctx = tenancy.create_context(
        "quota_paged", "alice", tenancy=TenancyConfig(kv_block_quota=2)
    )
    try:
        with tenancy.use_context(ctx):
            srv = _server(kv_block_size=4)
            try:
                # 8-token prompt + 8 new needs 4 blocks; the quota of 2
                # covers the prefill grant but the first decode-step
                # grant can NEVER succeed (no other tenant request holds
                # blocks to release), so the engine fails fast instead
                # of stalling.
                fut = srv.submit(list(range(1, 9)), max_new_tokens=8)
                with pytest.raises(TenantQuotaExceeded) as exc:
                    fut.result(timeout=120)
                assert exc.value.resource == "kv_blocks"
                # A request that fits under quota still serves.
                resp = srv.submit_and_wait([1, 2, 3], max_new_tokens=2)
                assert len(resp["tokens"]) == 2
            finally:
                srv.stop()
            assert tenancy_qos.get_ledger().in_use(
                "quota_paged", "kv_blocks"
            ) == 0
    finally:
        tenancy.remove_context("quota_paged")


# ---------------------------------------------------------------------------
# Device-resident snapshots + ModelBank replication


def test_publish_numpy_tree_yields_device_arrays():
    # A tree that crossed the wire on the plain socket lane is NumPy.
    # The engine hands bank.get(version) to its jitted step on every
    # iteration, so the snapshot must live on the device from publish on
    # (uploaded once), and must not alias the caller's host buffer.
    host = {"w": np.arange(1024, dtype=np.float32),
            "b": np.ones(4, np.float32)}
    bank = ModelBank()
    bank.publish(host)
    host["w"][:] = -1.0  # a recycled recv buffer
    _, snap = bank.acquire()
    device = jax.devices()[0]
    for leaf in jax.tree_util.tree_leaves(snap):
        assert isinstance(leaf, jax.Array), type(leaf)
        assert leaf.devices() == {device}
    np.testing.assert_array_equal(
        np.asarray(snap["w"]), np.arange(1024, dtype=np.float32)
    )


def test_bank_export_restore_preserves_version_and_monotonicity():
    bank = ModelBank()
    bank.publish(PARAMS_A)
    bank.publish(PARAMS_B)
    replica = ModelBank()
    replica.restore_state(bank.export_state())
    ver, params = replica.acquire()
    assert ver == 2
    np.testing.assert_array_equal(
        np.asarray(params["embed"]), np.asarray(PARAMS_B["embed"])
    )
    replica.release(ver)
    # Version numbers keep counting from the restored point: a promoted
    # standby can never reissue a version id the fleet has seen.
    assert replica.publish(PARAMS_A) == 3


# ---------------------------------------------------------------------------
# Two-party e2e: token streaming over the wire — bob consumes alice's
# engine output incrementally and the stream equals the full response.


def run_serve_stream_two_party(party, addresses):
    fed.init(addresses=addresses, party=party, config=CONFIG)
    handle = fed.serve("alice", CFG, params=PARAMS_A)
    prompt = list(range(5, 13))
    resp, stream = handle.submit(prompt, max_new_tokens=6, stream_to="bob")
    streamed = None
    if party == "bob":
        streamed = []
        for tok in stream:
            streamed.append(tok)
            assert stream.first_token_s is not None  # set AT first token
    tokens = fed.get(resp)["tokens"]
    assert tokens == _reference(PARAMS_A, prompt, 6)
    if party == "bob":
        assert streamed == tokens
    assert fed.get(handle.shutdown()) is True
    fed.shutdown()


def test_serve_streaming_two_party_e2e():
    run_parties(run_serve_stream_two_party, ["alice", "bob"])


# ---------------------------------------------------------------------------
# Three-party chaos: the ModelBank holder crashes mid-window. The
# standby's replica (fed by publish-time replication) is promoted and
# every request the crash orphaned is re-served — zero aborted.

BC_PARTIES = ["alice", "bob", "carol"]
BC_PROMPT = list(range(5, 13))
BC_N = 8


def _bc_comm(extra=None):
    # Few retries + a short send deadline so sends to the dead primary
    # fail fast, but a LONG recv window: survivors legitimately skew by
    # tens of seconds while timing out their orphaned gets, and the
    # promote result must survive that skew.
    cfg = {
        "retry_policy": {
            "max_attempts": 2,
            "initial_backoff_ms": 50,
            "max_backoff_ms": 100,
        },
        "timeout_in_ms": 2000,
        "recv_timeout_in_ms": 60000,
        "send_deadline_in_ms": 4000,
    }
    cfg.update(extra or {})
    return cfg


def _run_bank_crash_party(party, addresses, workdir):
    config = {
        "cross_silo_comm": _bc_comm(
            {"exit_on_sending_failure": True} if party == "alice" else None
        ),
        "serving": {"max_slots": 4, "max_len": 48, "max_new_tokens": 8},
    }
    if party == "alice":
        # Replicating v2 to carol is alice's first data send; the crash
        # then lands while response pushes are still streaming out, so
        # some of the window is orphaned mid-flight.
        config["resilience"] = {"fault_schedule": {
            "seed": 7,
            "rules": [{"fault": "crash", "src": "alice", "after": 6}],
        }}
    fed.init(
        addresses=addresses, party=party, config=config,
        sending_failure_handler=(
            (lambda e: os._exit(0)) if party == "alice" else None
        ),
    )
    try:
        handle = fed.serve(
            "alice", CFG, params=PARAMS_A, standby=("carol",)
        )
        handle.publish(PARAMS_B)  # v2, replicated to carol's bank
        futs = [
            handle.submit(BC_PROMPT, max_new_tokens=6, seed=i)
            for i in range(BC_N)
        ]
        got = [fed.get(f, timeout=3.0, on_missing="default") for f in futs]
    except BaseException:
        if party == "alice":
            os._exit(0)  # expected death throes past the crash point
        raise
    if party == "alice":
        # The injected crash fires on a transport thread as the response
        # pushes drain; wait for it rather than racing it.
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            time.sleep(0.1)
        os._exit(1)  # crash never fired: fail the test
    missing = [i for i, r in enumerate(got) if r is fed.MISSING]
    assert missing, "crash landed after the window drained"
    promoted = fed.get(handle.promote("carol"), timeout=60.0)
    assert promoted == 2  # the replica held the crashed primary's version
    # Resubmit the WHOLE window: each driver must trace the identical
    # program, and the per-party missing sets differ (the crash orphans
    # different pushes per consumer) — per-party resubmission would
    # diverge the seq space and deadlock the survivors. Originals that
    # did land are preferred; the redo fills the holes.
    redo = [
        handle.submit(BC_PROMPT, max_new_tokens=6, seed=i)
        for i in range(BC_N)
    ]
    redo_got = [
        fed.get(f, timeout=60.0, on_missing="default") for f in redo
    ]
    refs = {
        1: _reference(PARAMS_A, BC_PROMPT, 6),
        2: _reference(PARAMS_B, BC_PROMPT, 6),
    }
    aborted, versions = 0, {}
    for i, r in enumerate(got):
        if r is fed.MISSING:
            r = redo_got[i]
        if r is fed.MISSING:
            aborted += 1
            continue
        assert r["tokens"] == refs[r["version"]]
        versions[str(i)] = r["version"]
    assert aborted == 0
    with open(os.path.join(workdir, f"{party}.json"), "w") as f:
        json.dump(
            {"missing": missing, "promoted": promoted,
             "versions": versions},
            f, sort_keys=True,
        )
    fed.shutdown()


def test_modelbank_crash_promote_serves_all_requests(tmp_path):
    run_parties(
        _run_bank_crash_party, BC_PARTIES, timeout=200,
        extra_args=(str(tmp_path),), addresses=get_addresses(BC_PARTIES),
    )
    for p in ("bob", "carol"):
        doc = json.loads((tmp_path / f"{p}.json").read_text())
        assert doc["promoted"] == 2
        assert doc["missing"]  # the crash DID orphan part of the window
        assert len(doc["versions"]) == BC_N  # ...and every request served
