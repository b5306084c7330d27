"""CPU tests of the ``sdar30b-gen-closed128`` cell at its rehearsal preset:
the cell end to end, the controls (of the limits: the reference in the
precision below; of the mechanisms: K/V kept from a denoising forward, a
causal mask inside the block, the first masked position unmasked and not
the surest, sigmoid weights for softmax ones, a token altered where it
leaves), the ``*.diffusion`` readers on recorded facts,
and the byte and operation functions against counts worked by hand.
``python -m pytest chipbench/tests -q``."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import flops_sdar_moe as fm  # noqa: E402
from chipbench.run import load_reader, resolve  # noqa: E402
from chipbench.tests.test_chipbench import last_line, run_cell  # noqa: E402

CELL = "sdar30b-gen-closed128"
READERS = ("decode_step_ms.diffusion", "slot_occupancy.diffusion",
           "tokens_per_forward.diffusion", "commit_forward_share.diffusion",
           "experts_hit_share.diffusion", "decode_roofline.diffusion")
GAPS = ("served_logit_gap.widest", "served_logit_gap.mean",
        "unmask_logconf_gap.mean")


def published():
    plan = resolve(CELL, rehearse=False)
    return plan["model"], plan["precision"]


def result_of(tmp_path):
    run_dir = next((tmp_path / "out" / CELL).iterdir())
    return json.load(open(run_dir / "alice.result.json"))


def test_the_cell_rehearses_and_its_readers_read_the_recorded_facts(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "2147483655",
                   "--seconds", "3", "--trace", "1", timeout=600)
    line = last_line(run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and set(GAPS) <= set(line["compared"])
    result = result_of(tmp_path)
    facts, stats = result["facts"], result["facts"]["stats"]
    assert facts["kind"] == "closed_loop_diffusion"
    # Both prefill paths ran; every row's forward either denoised or
    # committed; a block of 4 in 4 steps yields 0.8 to 2 tokens a forward.
    assert facts["prefill_chunks"] > 0
    assert stats["prefill_tokens"] > stats["prefill_chunks"]
    forwards = stats["diffusion_row_forwards"]
    assert 0 < stats["diffusion_commit_forwards"] < forwards
    assert 0.8 <= stats["diffusion_tokens_unmasked"] / forwards <= 2.0
    assert stats["diffusion_positions_dropped"] > 0    # 10 tokens, blocks of 4
    assert 0 < stats["moe_experts_hit"] <= stats["moe_assignments_local"]
    # 3 rows x 4 ids, the model's two counters and the step's three (a
    # step may be in flight as the window closes).
    assert abs(stats["fetch_bytes"] - 68 * stats["steps"]) <= 3 * 68
    read = {name: load_reader(name) for name in READERS}
    # Decode runs one step ahead with a block a row as with a token (the
    # accepted reader, which lists this cell too).
    assert load_reader("steps_ahead_share")(facts) > 90
    assert read["decode_step_ms.diffusion"](facts) == pytest.approx(
        1e3 * facts["window_s"] / facts["steps"])
    assert read["slot_occupancy.diffusion"](facts) == pytest.approx(
        100 * forwards / (3 * facts["steps"]))
    assert read["tokens_per_forward.diffusion"](facts) == pytest.approx(
        stats["diffusion_tokens_unmasked"] / forwards)
    assert read["commit_forward_share.diffusion"](facts) == pytest.approx(
        100 * stats["diffusion_commit_forwards"] / forwards)
    assert read["experts_hit_share.diffusion"](facts) == pytest.approx(
        100 * stats["moe_experts_hit"] / (8 * 3 * facts["steps"]))
    # The roofline share wants the device's time in the program, which
    # only a profile from the chip holds: nothing here, and it does not
    # raise. The traced part's counters are there all the same.
    assert facts["programs"] == {}
    counted = facts["traced_stats"]
    assert counted["steps"] > 0 and counted["diffusion_row_forwards"] > 0
    decode = read["decode_roofline.diffusion"]
    assert decode(facts) is None
    # As on the chip: the traced steps at 20 ms, the chunks at 15 ms.
    traced = dict(facts, device_kind="TPU v5e", programs={
        "jit_decode_step": {"seconds": counted["steps"] * 0.02,
                            "calls": counted["steps"]},
        "jit_chunk_step": {"seconds": 0.015 * 7, "calls": 7}})
    assert decode(traced) == pytest.approx(
        100 * fm.decode_least_seconds(traced)["seconds"]
        / (counted["steps"] * 0.02))
    assert 0 < decode(traced) < 100
    # The prefill programs' least time is computed (no reader divides it:
    # a 4 s profile seldom holds one of the cell's prefill bursts).
    assert 0 <= fm.chunk_least_seconds(traced)["seconds"] < 0.015 * 7
    # A program without the counters (the parent) gives them nothing.
    old = dict(traced, traced_stats={"steps": counted["steps"]},
               stats={"steps": facts["steps"]})
    assert all(read[n](old) is None for n in READERS[1:])
    # Another kind's facts are not theirs to read.
    assert all(read[n](dict(traced, kind="closed_loop_moe")) is None
               for n in READERS)
    assert any(n.startswith("least bytes of a decode step") and "experts" in n
               for n in result["notes"])


def test_the_end_to_end_metric_is_measured(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "17",
                   "--seconds", "3", "--trace", "0", timeout=600)
    assert last_line(run)["correct"] is True
    assert '"serve_tokens_per_s"' in run.stdout


@pytest.mark.parametrize("fault", ["broken-commit", "broken-blockmask",
                                   "broken-unmask", "broken-route",
                                   "broken-token"])
def test_a_broken_mechanism_reads_not_correct(tmp_path, fault):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "23",
                   "--seconds", "3", "--trace", "0", "--inject", fault,
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    assert any(not by_name[name]["ok"] for name in GAPS)
    if fault == "broken-unmask":
        # The candidates are the model's own; only WHICH position was
        # unmasked is wrong, and the number of that fails.
        assert by_name["served_logit_gap.mean"]["ok"]
        assert not by_name["unmask_logconf_gap.mean"]["ok"]
        assert "the widest" in by_name["unmask_logconf_gap.mean"]["why"]
    assert by_name["compiles_in_window"]["ok"] is True


def test_the_fp8_control_reads_not_correct(tmp_path):
    """The control of the limits, through the harness's own comparison:
    what the reference in float8 would have unmasked at each replayed
    step is held to the limits of what was served, and fails one; the
    served trajectory passes all four."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "29",
                   "--seconds", "3", "--trace", "0", "--control", "fp8",
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    assert all(by_name[name]["ok"] for name in GAPS)
    assert any(not by_name["control[fp8]." + name]["ok"] for name in GAPS)


def test_the_configuration_holds_the_published_keys_and_states_its_cut():
    config = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "sdar-30b-a3b-chat.json")))
    entry = json.loads([
        line for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if "SDAR-30B-A3B-Chat" in line][0]) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if entry:
        assert config["source"] == entry["source_url"]
        assert all(config[k] == v for k, v in entry["config"].items())
    assert config["reduced"] == ["num_hidden_layers"]
    model, _ = published()
    assert model["num_hidden_layers"] == 6 and model["num_experts"] == 128
    assert model["vocab_size"] == 151936 and model["block_length"] == 4
    for key in ("served_logit_gap", "served_logit_gap_mean",
                "unmask_logconf_gap_mean"):
        assert config["limits"][key] > 0
    # The widest unmask_logconf_gap is recorded, not judged (PERF.md
    # section 2): no limit without a reading above it.
    assert "unmask_logconf_gap" not in config["limits"]
    for key in ("block_length", "denoising_steps", "remasking",
                "mask_token_id", "logits", "prefill", "qk_norm",
                "positions", "deployment"):
        assert key in config["assumed"]


def test_parameter_and_byte_counts_against_the_hand_worked_ones():
    model, precision = published()
    c = fm.param_counts(model)
    assert c["attention"] == 2048 * 4096 * 2 + 2 * 2048 * 512
    assert c["expert"] == 3 * 2048 * 768
    assert c["layer"] == c["attention"] + 2048 * 128 + 4352 + 128 * c["expert"]
    assert round(c["layer"] / 1e6, 1) == 623.1
    assert round(c["total"] / 1e9, 3) == 4.361
    # A step that hits every expert: 6 layers whole and the head.
    facts = {"model": model, "precision": precision, "kv_block_size": 16,
             "device_kind": "TPU v5e",
             "stats": {"steps": 1, "moe_experts_hit": 6 * 128,
                       "kv_layer_blocks_attended": 0}}
    least = fm.window_least_bytes(facts)
    assert round(least["total"] / 1e9, 2) == 8.1
    assert least["experts"] == 6 * 128 * 3 * 2048 * 768 * 2
    # 96 rows of a block at a context of 256: operations by part.
    stats = {"steps": 1, "moe_experts_hit": 768,
             "kv_layer_blocks_attended": 6 * 96 * 17,
             "diffusion_row_forwards": 96,
             "moe_assignments_local": 96 * 4 * 8 * 6,
             "decode_keys_attended": 6 * 96 * 4 * 260}
    ops = fm.window_ops(dict(facts, stats=stats))
    assert ops["experts"] == 2.0 * 96 * 4 * 8 * 6 * c["expert"]
    assert ops["head"] == 2.0 * 384 * 151936 * 2048
    assert ops["attention"] == 4.0 * 4096 * 6 * 96 * 4 * 260
    least = fm.decode_least_seconds(dict(facts, traced_stats=stats))
    assert least["bound"] == "memory"
    assert 9.9e-3 < least["seconds"] < 10.4e-3


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_steps(name):
    read = load_reader(name)
    assert read({}) is None
    assert read({"kind": "closed_loop_diffusion", "steps": 0, "stats": {},
                 "traced_stats": {}, "programs": {}}) is None
