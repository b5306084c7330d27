"""CPU tests of the ``commandaplus-docs-closed24`` cell at its rehearsal
preset: the cell end to end, the three controls (of the limit: the
reference in the precision below; of the two mechanisms: a wrong share, a
window that never closes), the ``*.moe`` readers on recorded facts, and the
byte and operation functions against counts worked by hand.
``python -m pytest chipbench/tests -q``."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import flops_cohere2_moe as fm  # noqa: E402
from chipbench.run import load_reader, resolve  # noqa: E402
from chipbench.tests.test_chipbench import last_line, run_cell  # noqa: E402

CELL = "commandaplus-docs-closed24"
READERS = ("decode_step_ms.moe", "slot_occupancy.moe",
           "decode_hbm_roofline.moe", "chunk_roofline.moe",
           "experts_hit_share.moe", "kv_window_read_share.moe")


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
    assert line["attempted"] > 0
    facts = result_of(tmp_path)["facts"]
    stats = facts["stats"]
    assert facts["kind"] == "closed_loop_moe"
    # Both prefill paths ran, experts were chosen and the window bit.
    assert facts["prefill_chunks"] > 0
    assert stats["prefill_tokens"] > stats["prefill_chunks"]
    assert 0 < stats["moe_experts_hit"] <= stats["moe_assignments_local"]
    assert (stats["kv_layer_blocks_attended"]
            < 4 * stats["kv_blocks_attended"])
    # R ids and the model's two counters a step, 4 B a prompt's last chunk.
    assert stats["fetch_bytes"] >= 4 * (3 + 2) * stats["steps"]
    assert load_reader("decode_step_ms.moe")(facts) == pytest.approx(
        1e3 * facts["window_s"] / facts["steps"])
    assert 0 < load_reader("slot_occupancy.moe")(facts) <= 100
    assert load_reader("experts_hit_share.moe")(facts) == pytest.approx(
        100 * stats["moe_experts_hit"] / (4 * 4 * facts["steps"]))
    assert load_reader("kv_window_read_share.moe")(facts) == pytest.approx(
        25 * stats["kv_layer_blocks_attended"] / stats["kv_blocks_attended"])
    # The two roofline shares want the device's time in the programs,
    # which only a profile from the chip holds: nothing here, and neither
    # raises. The traced part's counters are there all the same.
    assert facts["programs"] == {}
    assert facts["traced_stats"]["steps"] > 0
    decode, chunk = (load_reader("decode_hbm_roofline.moe"),
                     load_reader("chunk_roofline.moe"))
    assert decode(facts) is None and chunk(facts) is None
    # As on the chip: the traced steps at 20 ms, the chunks at 15 ms.
    counted = facts["traced_stats"]
    traced = dict(facts, device_kind="TPU v5e", programs={
        "jit_decode_step": {"seconds": counted["steps"] * 0.02,
                            "calls": counted["steps"]},
        "jit_chunk_step": {"seconds": 0.015 * 7, "calls": 7}})
    least = fm.window_least_bytes(dict(facts, stats=counted))["total"]
    assert decode(traced) == pytest.approx(
        100 * least / 819e9 / (counted["steps"] * 0.02))
    assert chunk(traced) == pytest.approx(
        100 * fm.chunk_least_seconds(traced)["seconds"] / (0.015 * 7))
    # A program without the counters (the parent) gives them nothing.
    old = dict(traced, traced_stats={"steps": counted["steps"]},
               stats={"steps": facts["steps"]})
    assert all(load_reader(n)(old) is None for n in READERS[2:])
    # Another kind's facts are not theirs to read.
    assert all(load_reader(n)(dict(traced, kind="closed_loop_arch")) is None
               for n in READERS)
    assert any(n.startswith("least bytes of a decode step") and "experts" in n
               for n in result_of(tmp_path)["notes"])


def test_the_end_to_end_metric_is_measured(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "17",
                   "--seconds", "3", "--trace", "0", timeout=600)
    assert last_line(run)["correct"] is True
    assert '"serve_tokens_per_s"' in run.stdout


@pytest.mark.parametrize("fault", ["broken-route", "broken-window"])
def test_a_broken_mechanism_reads_not_correct(tmp_path, fault):
    """The controls of the mechanisms themselves: a layer that takes its
    experts among the held ones only; sliding layers that read every
    key."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "23",
                   "--seconds", "3", "--trace", "0", "--inject", fault,
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    for name in ("served_logit_gap.widest", "served_logit_gap.mean"):
        gap = by_name[name]
        assert gap["ok"] is False and gap["value"] > gap["limit"], name
    assert by_name["compiles_in_window"]["ok"] is True


def test_the_fp8_control_reads_not_correct(tmp_path):
    """The control of the limit, through the harness's own comparison: the
    tokens the reference puts first in float8 are held to the limit of
    the served ones, and fail it; the served ones pass."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "29",
                   "--seconds", "3", "--trace", "0", "--control", "fp8",
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    assert by_name["served_logit_gap.widest"]["ok"] is True
    assert by_name["served_logit_gap.mean"]["ok"] is True
    # By the limit on the mean: the one a lower precision must fail (the
    # widest gap is the coarse net, for a broken mechanism).
    control = by_name["control[fp8].served_logit_gap.mean"]
    assert control["ok"] is False and control["value"] > control["limit"]


def test_the_configuration_holds_the_published_keys_and_states_its_cut():
    config = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "command-a-plus-05-2026.json")))
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (32, 128, 262144)
    serve = config["layouts"]["serve"]
    assert serve["published"] == {"num_hidden_layers": 32,
                                  "num_experts": 128, "vocab_size": 262144}
    assert sorted(config["reduced"]) == sorted(serve["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    model, _ = published()
    # One whole period, an eighth of the experts and of the vocabulary;
    # no width, head count, window or experts-per-token touched.
    assert model["layer_types"] == config["layer_types"][:4]
    assert model["num_experts"] * 8 == model["router_experts"] == 128
    assert model["vocab_size"] * 8 == 262144
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "num_shared_experts",
                "sliding_window"):
        assert model[key] == config[key], key
    # The mean's limit is set for a sample of this many requests, those
    # whose gaps sum highest left out, and the kind reads all three here.
    assert (0 < config["limits"]["served_logit_gap_mean"]
            < config["limits"]["served_logit_gap"] < 1)
    # The limits are set for a sample of this many requests, those whose
    # gaps sum highest left out, and the kind reads both here.
    assert config["limits"]["sample_requests"] > 6
    assert 0 < config["limits"]["worst_requests"] < 4


ROWS = [{"gap": 1.12, "mean": 0.005, "n": 229, "c_gap": 0.3, "c_mean": 0.02},
        {"gap": 0.28, "mean": 0.0737, "n": 115, "c_gap": 0.5, "c_mean": 0.1},
        {"gap": 0.03, "mean": 0.0001, "n": 449, "c_gap": 0.4, "c_mean": 0.03},
        {"gap": 0.0, "mean": 0.0, "n": 203, "c_gap": 0.9, "c_mean": 0.02}]


@pytest.mark.parametrize("worst, less, ok, widest, mean", [
    (None, "", False, 1.12, (0.005 * 229 + 0.0737 * 115 + 0.0001 * 449) / 996),
    (1, "_less_worst", False, 1.12, (0.005 * 229 + 0.0001 * 449) / 881),
    (2, "_less_worst", True, 0.03, 0.0001 * 449 / 652),
    (4, "_less_worst", False, None, None),
])
def test_the_gaps_with_and_without_the_worst_requests(worst, less, ok,
                                                      widest, mean):
    """One request of near-tied tokens is most of a sample's sum and one
    token on a router's tie its widest gap: over every request a sound
    sample fails both limits, less those two requests it passes; a control
    that moves every request fails either way, and ITS worst requests are
    the ones left out of its numbers."""
    from chipbench.kinds.closed_loop_moe import gap_checks

    limits = {"served_logit_gap": 0.8, "served_logit_gap_mean": 0.0015}
    if worst is not None:
        limits["worst_requests"] = worst
    checks = gap_checks(ROWS, limits)
    assert [c["name"] for c in checks] == [
        f"served_logit_gap.widest{less}", f"served_logit_gap.mean{less}"]
    assert all(c["ok"] for c in checks) is ok
    assert checks[0]["value"] == widest
    assert checks[1]["value"] == (mean if mean is None
                                  else pytest.approx(mean))
    control = gap_checks(ROWS, limits, "control[fp8].", "c_gap", "c_mean")
    assert control[0]["name"] == f"control[fp8].served_logit_gap.widest{less}"
    assert control[1]["ok"] is False
    if worst == 2:
        # The control's sums: 4.58, 11.5, 13.47, 4.06: the middle two go.
        assert control[0]["value"] == 0.9
        assert control[1]["value"] == pytest.approx(
            (0.02 * 229 + 0.02 * 203) / 432)


def test_parameter_counts_against_the_hand_worked_ones():
    model, _ = published()
    c = fm.param_counts(model)
    # q and o 4096 x 16384 each, k and v 4096 x 1024 each.
    assert c["attention"] == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert c["shared"] == 4 * 3 * 4096 * 4096 == 201_326_592
    assert c["router"] == 4096 * 128
    assert c["expert"] == 3 * 4096 * 4096 == 50_331_648
    assert round(c["layer_whole"] / 1e6, 1) == 344.5
    assert round(c["layer"] / 1e6, 1) == 1149.8
    assert c["embed"] == 32768 * 4096 == 134_217_728
    assert round(c["total"] / 1e9, 2) == 4.73


def test_byte_counts_against_the_hand_worked_ones():
    model, precision = published()
    # The layers' whole parts and the head: 3.0 GB in bfloat16.
    whole = fm.whole_bytes_per_call(model, precision)
    assert whole == 2 * (4 * 344_461_312 + 134_217_728 + 4096)
    assert round(whole / 1e9, 2) == 3.02
    assert fm.expert_bytes(model, precision) == 100_663_296
    # 4,096 B a token a layer of K/V (8 heads of 128, K and V, bfloat16).
    assert fm.kv_bytes_per_layer_block(model, precision, 16) == 16 * 4096
    facts = {"model": model, "precision": precision, "kv_block_size": 16,
             "device_kind": "TPU v5e", "pushed_tokens": 0, "first_tokens": 0,
             "stats": {"steps": 10, "moe_experts_hit": 10 * 41,
                       "kv_layer_blocks_attended": 10 * 16 * 800}}
    parts = fm.window_least_bytes(facts)
    assert parts["total"] == parts["weights"] + parts["experts"] + parts["kv"]
    # The issue's step: 64 % of 64 held experts hit, 53 MB of K/V a row.
    assert round(parts["total"] / 10 / 1e9, 1) == 8.0
    assert 50 < 100 * parts["experts"] / parts["total"] < 53
    # A traced second of prefill: 40 chunks of 512 tokens at a mean
    # context of 4,000 keys on the full layer, 2,300 on the sliding ones.
    facts.update(
        programs={"jit_chunk_step": {"calls": 40, "seconds": 1.0}},
        traced_stats={"prefill_tokens": 40 * 512,
                      "prefill_keys_attended": 40 * 512 * (4000 + 3 * 2300)})
    least = fm.chunk_least_seconds(facts)
    assert least["ops"]["whole"] == 2.0 * 40 * 512 * 4 * 344_457_216
    # An eighth of a token's eight pairs falls on held experts.
    assert least["ops"]["experts"] == pytest.approx(
        2.0 * 40 * 512 * 4 * 50_331_648)
    assert least["ops"]["attention"] == 4.0 * 128 * 128 * 40 * 512 * 10900
    # 512 tokens touch every held expert of every layer.
    assert least["bytes"]["experts"] == pytest.approx(
        40 * 64 * 100_663_296, rel=1e-6)
    assert least["bytes"]["whole"] == 40 * whole
    assert least["seconds"] == pytest.approx(max(
        sum(least["ops"].values()) / 197e12,
        sum(least["bytes"].values()) / 819e9))


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_steps(name):
    assert load_reader(name)({"kind": "closed_loop_moe", "steps": 0}) is None
