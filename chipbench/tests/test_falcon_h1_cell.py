"""CPU tests of the ``falconh1-chat-closed48`` cell at its rehearsal
preset: the cell end to end, the two controls (of the mechanism itself,
and of the limit: the reference in the precision below), the ``*.hybrid``
readers on recorded facts, and the byte functions against counts worked
by hand. ``python -m pytest chipbench/tests -q``."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import flops_falcon_h1 as ff  # noqa: E402
from chipbench.run import load_reader  # noqa: E402
from chipbench.tests.test_chipbench import last_line, run_cell  # noqa: E402

CELL = "falconh1-chat-closed48"
READERS = ("decode_step_ms.hybrid", "slot_occupancy.hybrid",
           "decode_hbm_roofline.hybrid")


def published():
    config = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "falcon-h1-34b.json")))
    model = dict(config, **config["layouts"]["serve"]["model"])
    return model, config["precision"]


def facts_of(tmp_path):
    run_dir = next((tmp_path / "out" / CELL).iterdir())
    return json.load(open(run_dir / "alice.result.json"))["facts"]


def test_the_cell_rehearses_and_its_readers_read_the_recorded_facts(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "2147483655",
                   "--seconds", "3", "--trace", "1", timeout=600)
    line = last_line(run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    facts = facts_of(tmp_path)
    assert facts["kind"] == "closed_loop_arch"
    assert facts["stats"]["ssm_state_bytes"] > 0
    assert facts["stats"]["state_resets"] > 0
    # Both prefill paths ran: bucketed rounds and chunks.
    assert facts["prefill_chunks"] > 0
    # The two that every run gives are their ``.complete`` twins' numbers.
    assert load_reader("decode_step_ms.hybrid")(facts) == pytest.approx(
        1e3 * facts["window_s"] / facts["steps"])
    assert 0 < load_reader("slot_occupancy.hybrid")(facts) <= 100
    # The roofline share wants the device's time in the step's program,
    # which only a profile from the chip holds: nothing here, and nothing
    # from a program without the counters (the parent); neither raises.
    assert facts["programs"] == {}
    roofline = load_reader("decode_hbm_roofline.hybrid")
    assert roofline(facts) is None
    # As on the chip: 40 traced steps of 15.5 ms on a v5e.
    traced = dict(facts, device_kind="TPU v5e", programs={
        "jit_decode_step": {"seconds": 40 * 0.0155, "calls": 40}})
    parts = ff.window_least_bytes(facts)
    assert roofline(traced) == pytest.approx(
        100 * parts["total"] / facts["steps"] / 819e9 / 0.0155)
    old = dict(traced, stats={k: v for k, v in facts["stats"].items()
                              if k == "steps"})
    assert roofline(old) is None
    # Another kind's facts are not theirs to read.
    assert all(load_reader(n)(dict(traced, kind="closed_loop")) is None
               for n in READERS)
    # The state's share of a step's least bytes is a fact in the notes.
    result = json.load(open(next(
        (tmp_path / "out" / CELL).iterdir()) / "alice.result.json"))
    assert any(n.startswith("least bytes of a decode step")
               and "state" in n for n in result["notes"])


def test_the_end_to_end_metric_is_measured(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "17",
                   "--seconds", "3", "--trace", "0", timeout=600)
    assert last_line(run)["correct"] is True
    assert '"serve_tokens_per_s"' in run.stdout


def test_a_slot_that_is_not_reset_reads_not_correct(tmp_path):
    """The control of the mechanism itself: prefilled rows land their K/V
    but not their fresh recurrent state, so requests decode on from the
    slot's last occupant's."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "23",
                   "--seconds", "3", "--trace", "0", "--inject",
                   "broken-state", timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    assert '"served_logit_gap.widest"' in run.stdout


def test_the_fp8_control_reads_not_correct(tmp_path):
    """The control of the limit, through the harness's own comparison: the
    tokens the reference puts first in float8 are held to the limit of
    the served ones, and fail it; the served ones pass."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "29",
                   "--seconds", "3", "--trace", "0", "--control", "fp8",
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    result = json.load(open(next(
        (tmp_path / "out" / CELL).iterdir()) / "alice.result.json"))
    by_name = {c["name"]: c for c in result["checks"]}
    assert by_name["served_logit_gap.widest"]["ok"] is True
    control = by_name["control[fp8].served_logit_gap.widest"]
    assert control["ok"] is False and control["value"] > control["limit"]


def test_the_limit_is_the_configurations_own():
    from chipbench.kinds import closed_loop_arch as kind

    config = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "falcon-h1-34b.json")))

    class Ctx:
        model = {"limits": config["limits"]}

    assert kind.limits_of(Ctx)["served_logit_gap"] == 0.15
    Ctx.model = {}
    with pytest.raises(SystemExit, match="limits.served_logit_gap"):
        kind.limits_of(Ctx)
    assert not hasattr(kind, "LIMITS")


def test_parameter_counts_against_the_hand_worked_ones():
    model, _ = published()
    c = ff.param_counts(model)
    # q 5120x2560 + k, v 2 x 5120x512 + o 2560x5120
    assert c["attention"] == 5120 * 2560 * 2 + 2 * 5120 * 512 == 31_457_280
    # in_proj 5120 x 9248 + out_proj 4096 x 5120 + conv 4x5120 + bias 5120
    # + dt_bias, A_log, D 3 x 32 + norm 4096
    assert c["mixer"] == (5120 * 9248 + 4096 * 5120 + 5 * 5120 + 96 + 4096)
    assert c["mlp"] == 3 * 5120 * 21504 == 330_301_440
    assert round(c["layer"] / 1e6, 1) == 430.1
    assert c["embed"] + c["head"] == 2 * 261120 * 5120
    assert round((c["embed"] + c["head"]) / 1e9, 3) == 2.674
    assert c["total"] == 6 * c["layer"] + 2 * 261120 * 5120 + 5120


def test_byte_counts_against_the_hand_worked_ones():
    model, precision = published()
    st = ff.state_bytes_per_row(model, precision)
    # 32 heads x 128 x 256 float32 = 4.19 MB a layer, 25.2 MB at depth 6;
    # the tail 3 x 5120 bfloat16 a layer.
    assert st["ssm"] == 6 * 32 * 128 * 256 * 4 == 25_165_824
    assert st["conv"] == 6 * 3 * 5120 * 2
    assert st["total"] == 25_350_144 and round(st["ssm"] / 1e6, 1) == 25.2
    # 2 KB a token a layer of K/V (4 heads of 128, K and V, bfloat16).
    assert ff.kv_bytes_per_block(model, precision, 16) == 6 * 16 * 2048
    # The layers and the head once a step: 7.83 GB in bfloat16.
    w = ff.weight_bytes_per_step(model, precision)
    assert round(w / 1e9, 2) == 7.84
    facts = {"model": model, "precision": precision, "kv_block_size": 16,
             "stats": {"steps": 10, "kv_blocks_attended": 10 * 32 * 14,
                       "ssm_state_bytes": 10 * 32 * 2 * st["total"]}}
    parts = ff.window_least_bytes(facts)
    assert parts["total"] == parts["weights"] + parts["kv"] + parts["state"]
    # All 32 rows live: the state is about a sixth of a step's least bytes.
    assert 16.0 < 100 * parts["state"] / parts["total"] < 17.5


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_steps(name):
    assert load_reader(name)({"kind": "closed_loop_arch", "steps": 0}) is None
