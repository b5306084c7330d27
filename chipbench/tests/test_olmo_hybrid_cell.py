"""CPU tests of the ``olmohybrid-assist-closed48`` cell at its rehearsal
preset: the cell end to end through the launcher, the control of the
limit (the reference in float8 with a bfloat16 state) and of the
mechanism (a slot whose state is not reset), the ``*.linear`` readers and
the accepted ``*.hybrid`` ones on recorded facts, the configuration's keys
against the catalog's row, and the parameter and byte functions against
the tree's leaves and counts worked by hand. ``python -m pytest chipbench/tests -q``."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import flops_olmo_hybrid as fo  # noqa: E402
from chipbench.run import load_reader  # noqa: E402
from chipbench.tests.test_chipbench import last_line, run_cell  # noqa: E402

CELL = "olmohybrid-assist-closed48"
COUNTER_READERS = ("state_bytes_share.linear",)
TRACE_READERS = ("chunk_roofline.linear", "linear_device_share.linear",
                 "delta_device_share.linear")
# The readers of ``falconh1-chat-closed48``: they key on the kind and on
# ``flops_<reference>``, so they read this cell as they stand.
HYBRID_TRACE_READER = "decode_hbm_roofline.hybrid"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config():
    return json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "olmo-hybrid-7b.json")))


def published():
    c = config()
    return dict(c, **c["layouts"]["serve"]["model"]), c["precision"]


def result_of(tmp_path):
    run_dir = next((tmp_path / "out" / CELL).iterdir())
    return json.load(open(run_dir / "alice.result.json"))


def test_the_cell_rehearses_and_its_readers_read_the_recorded_facts(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "2147483655",
                   "--seconds", "3", "--trace", "1", timeout=600)
    line = last_line(run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    result = result_of(tmp_path)
    facts = result["facts"]
    assert facts["kind"] == "closed_loop_arch"
    assert facts["reference"] == "olmo_hybrid"
    assert facts["stats"]["ssm_state_bytes"] > 0
    assert facts["stats"]["state_resets"] > 0
    # Both prefill paths ran, and the chunks handed their state on.
    counted = facts["program"]["stats"]
    assert facts["prefill_chunks"] > 0 and counted["chunk_tokens"] > 0
    assert counted["chunk_state_bytes"] == (
        counted["prefill_chunks"] * 2 * fo.state_bytes_per_row(
            facts["model"], {"delta_state": "float32",
                             "kv_cache": "bfloat16"})["total"])
    # The rehearsal's stack: 6 linear layers keep the state, 2 full ones
    # the K/V, and the counts by layer are over those 2.
    assert counted["kv_layer_blocks_attended"] == (
        2 * counted["kv_blocks_attended"])
    # The readers of the engine's counters give numbers ...
    assert load_reader("decode_step_ms.hybrid")(facts) == pytest.approx(
        1e3 * facts["window_s"] / facts["steps"])
    assert 0 < load_reader("slot_occupancy.hybrid")(facts) <= 100
    share = load_reader("state_bytes_share.linear")(facts)
    parts = fo.window_least_bytes(facts)
    assert share == pytest.approx(100 * parts["state"] / parts["total"])
    assert 0 < share < 100
    # ... those of the device's profile nothing where there is no device
    # plane, as the others; neither raises.
    assert facts["programs"] == {}
    for name in TRACE_READERS + (HYBRID_TRACE_READER,):
        assert load_reader(name)(facts) is None, name
    # As on the chip: 40 traced steps of 17 ms and 12 chunks of 22 ms on
    # a v5e, a tenth of the busy time under the recurrence.
    traced = dict(
        facts, device_kind="TPU v5e",
        programs={"jit_decode_step": {"seconds": 40 * 0.017, "calls": 40},
                  "jit_chunk_step": {"seconds": 12 * 0.022, "calls": 12}},
        trace={"busy_s": 2.0, "window_s": 2.1, "device_by_scope": {
            "serve/linear_attn": 0.8, "serve/conv": 0.1,
            "serve/delta_rule": 0.2, "serve/attn_full": 0.3}})
    assert load_reader(HYBRID_TRACE_READER)(traced) == pytest.approx(
        100 * parts["total"] / facts["steps"] / 819e9 / 0.017)
    least = fo.chunk_least_seconds(traced)
    assert load_reader("chunk_roofline.linear")(traced) == pytest.approx(
        100 * least["seconds"] / 0.022)
    assert load_reader("linear_device_share.linear")(traced) == \
        pytest.approx(55.0)
    assert load_reader("delta_device_share.linear")(traced) == \
        pytest.approx(10.0)
    # A profile that booked nothing under the scopes reads 0, not None.
    bare = dict(traced, trace={"busy_s": 2.0, "device_by_scope": {}})
    assert load_reader("delta_device_share.linear")(bare) == 0.0
    # The parent's program (no such counters) gives the chunk's reader
    # nothing to read; another kind's or another architecture's facts are
    # not theirs.
    old = dict(traced, program={"stats": {
        k: v for k, v in counted.items()
        if k not in ("chunk_tokens", "chunk_state_bytes")}})
    assert load_reader("chunk_roofline.linear")(old) is None
    for name in COUNTER_READERS + TRACE_READERS:
        assert load_reader(name)(dict(traced, kind="closed_loop")) is None
        assert load_reader(name)(
            dict(traced, reference="falcon_h1")) is None
    assert any(n.startswith("least bytes of a decode step")
               and "state" in n for n in result["notes"])


def test_a_slot_that_is_not_reset_reads_not_correct(tmp_path):
    """The control of the mechanism itself through this model's 6-deep
    state (``--inject broken-state``): a round of bucketed rows lands its
    K/V but not its fresh ``S`` and tail, so those requests decode on
    from the slot's last occupant's. (A prompt longer than a chunk zeroes
    its own state in its first chunk and is not touched by it.)"""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "23",
                   "--seconds", "3", "--trace", "0", "--inject",
                   "broken-state", timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    gap = by_name["served_logit_gap.widest"]
    assert gap["ok"] is False and gap["value"] > gap["limit"]


def test_the_fp8_control_reads_not_correct(tmp_path):
    """The control of the limit, through the harness's own comparison: the
    tokens the reference puts first with its matmul operands in float8
    and its state in bfloat16 are held to the limit of the served ones,
    and fail it; the served ones pass. (The bfloat16 control, a bfloat16
    state under bfloat16 operands, reads UNDER the served tokens' own gap,
    here and on the chip: the program computes in bfloat16 as the
    configuration states, and that moves the logits more than the state's
    rounding does. What holds the state's precision is tier-1's float32
    comparison, ``tests/test_olmo_hybrid.py``.)"""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "29",
                   "--seconds", "3", "--trace", "0", "--control", "fp8",
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    assert by_name["served_logit_gap.widest"]["ok"] is True
    control = by_name["control[fp8].served_logit_gap.widest"]
    assert control["ok"] is False and control["value"] > control["limit"]


def test_the_configuration_holds_the_catalogs_keys_unchanged():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Olmo-Hybrid-7B")
    c = config()
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers"]
    serve = c["layouts"]["serve"]
    assert serve["model"] == {"num_hidden_layers": 16}
    assert serve["reduced"] == ["num_hidden_layers"]
    assert c["reference"] == "olmo_hybrid"
    assert c["precision"]["delta_state"] == "float32"
    # The rehearsal keeps every ratio: dv = 2 dk, 3 linear to 1 full.
    tiny = dict(c, **c["rehearsal"])
    assert tiny["linear_value_head_dim"] == 2 * tiny["linear_key_head_dim"]
    kinds = tiny["layer_types"][:tiny["num_hidden_layers"]]
    assert kinds.count("linear_attention") == 3 * kinds.count(
        "full_attention") == 6
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in bench["configs"] if e["name"] == "olmo-hybrid-7b")
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"]


def test_the_limit_is_calibrated_and_the_configurations_own():
    from chipbench.kinds import closed_loop_arch as kind

    limits = config()["limits"]

    class Ctx:
        model = {"limits": limits}

    assert kind.limits_of(Ctx)["served_logit_gap"] == limits[
        "served_logit_gap"]
    assert "on the chip" in limits["calibrated"]


def test_a_program_that_keeps_the_state_in_another_type_is_refused():
    """The logit comparison cannot see a bfloat16 ``S`` (the limit's
    ``calibrated``): the adapter holds the type the model declares to the
    pool to the configuration's ``precision.delta_state``."""
    from chipbench import seeded_olmo_hybrid as seeded

    model, precision = published()
    model = dict(model, **config()["rehearsal"])
    assert seeded.program_cfg(model, precision).n_linear == 6
    with pytest.raises(SystemExit, match="delta_state bfloat16"):
        seeded.program_cfg(model, dict(precision, delta_state="bfloat16"))


def test_parameter_counts_are_the_trees_leaves_at_the_published_widths():
    """Shapes only: nothing at the published widths is drawn here."""
    import jax

    from chipbench import seeded_olmo_hybrid as seeded

    model, _ = published()
    shapes = jax.eval_shape(lambda: seeded.make_canonical(
        jax.random.PRNGKey(0), model))
    size = lambda t: sum(  # noqa: E731
        int(a.size) for a in jax.tree_util.tree_leaves(t))
    c = fo.param_counts(model)
    assert (c["n_linear"], c["n_full"]) == (12, 4)
    assert size(shapes["linear"]) == 12 * c["linear_layer"]
    assert size(shapes["full"]) == 4 * c["full_layer"]
    assert size(shapes) == c["total"]
    # The hand-worked ones: 3840 x (2880 + 2880 + 5760 + 5760) + 5760 x
    # 3840 + 3840 x 60 + 4 x 11520 = 88.75 M, and the small leaves.
    assert c["linear_mixer"] == (3840 * 17280 + 5760 * 3840 + 3840 * 60
                                 + 4 * 11520 + 60 + 192)
    assert round(c["linear_mixer"] / 1e6, 2) == 88.75
    assert c["full_mixer"] == 4 * 3840 * 3840 + 2 * 3840
    assert c["mlp"] == 3 * 3840 * 11008 == 126_812_160
    assert round(c["linear_layer"] / 1e6, 2) == 215.57
    assert round(c["full_layer"] / 1e6, 2) == 185.81
    assert round((3 * c["linear_layer"] + c["full_layer"]) / 1e6, 1) == 832.5
    assert c["embed"] + c["head"] == 2 * 100352 * 3840
    assert round(c["total"] / 1e9, 3) == 4.101


def test_byte_counts_against_the_hand_worked_ones():
    model, precision = published()
    st = fo.state_bytes_per_row(model, precision)
    # 30 heads x 192 x 96 float32 = 2.21 MB a linear layer, the tail 3 x
    # 11,520 bfloat16: 2,280,960 B a layer, 27.37 MB over the 12.
    assert st["delta"] == 12 * 30 * 192 * 96 * 4
    assert st["conv"] == 12 * 3 * 11520 * 2
    assert st["total"] == 12 * 2_280_960 == 27_371_520
    # K/V over the FULL layers only: 61,440 B a token.
    assert fo.kv_bytes_per_block(model, precision, 16) == 16 * 61440
    # The 16 layers and the head once a step: 7.43 GB in bfloat16.
    w = fo.weight_bytes_per_step(model, precision)
    assert round(w / 1e9, 2) == 7.43
    facts = {"model": model, "precision": precision, "kv_block_size": 16,
             "stats": {"steps": 10, "kv_blocks_attended": 10 * 32 * 38,
                       "ssm_state_bytes": 10 * 32 * 2 * st["total"]}}
    parts = fo.window_least_bytes(facts)
    assert parts["total"] == parts["weights"] + parts["kv"] + parts["state"]
    # 32 live rows at a mean context of 600: 10.4 GB a step, the state a
    # sixth of it and more than the K/V.
    assert round(parts["total"] / 10 / 1e9, 1) == 10.4
    assert 16.0 < 100 * parts["state"] / parts["total"] < 17.5
    assert parts["state"] > parts["kv"]
    # The recurrence: 2 x (3 dk dv + 64 (2.5 dk + 1.5 dv)) a token a head,
    # 64 being the sub-chunk the program solves at once.
    from rayfed_tpu.models import olmo_hybrid

    assert fo.DELTA_CHUNK == olmo_hybrid.DELTA_CHUNK == 64
    assert fo.delta_rule_ops_per_token(model) == 12 * 30 * 2 * (
        3 * 96 * 192 + 64 * (2.5 * 96 + 1.5 * 192))
    # A chunk of 256 real tokens: 1.72 T operations (8.7 ms at the MXU's
    # peak) under 7.5 GB (9.2 ms at HBM's): bound by memory, a little.
    facts = dict(facts, device_kind="TPU v5e", program={"stats": {
        "prefill_chunks": 4, "chunk_tokens": 4 * 256,
        "chunk_state_bytes": 4 * 2 * st["total"],
        "chunk_blocks_read": 4 * 4 * 16}})
    least = fo.chunk_least_seconds(facts)
    assert least["bound"] == "memory"
    assert 9.0e-3 < least["seconds"] < 9.4e-3
    assert 8.5e-3 < sum(least["ops"].values()) / 4 / 197e12 < 8.9e-3
    assert least["bytes"]["state"] == 4 * 2 * st["total"]
    assert least["bytes"]["kv"] == 4 * 16 * 16 * 61440


@pytest.mark.parametrize("name", COUNTER_READERS + TRACE_READERS)
def test_readers_return_nothing_from_another_cells_facts(name):
    assert load_reader(name)({"kind": "closed_loop_arch", "steps": 0}) is None
    assert load_reader(name)({"kind": "closed_loop_arch", "steps": 5,
                              "reference": "falcon_h1"}) is None
