"""CPU tests of the ``pangu718b-reason-closed72`` cell at its rehearsal
preset: the cell end to end, the three controls (of the limits: the
reference in the precision below; of the two mechanisms: a positional key
cached unrotated, a wrong share), the ``*.mla`` readers on recorded facts,
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

from chipbench import flops_pangu_ultra_moe as fm  # noqa: E402
from chipbench.run import load_reader, resolve  # noqa: E402
from chipbench.tests.test_chipbench import last_line, run_cell  # noqa: E402

CELL = "pangu718b-reason-closed72"
READERS = ("decode_step_ms.mla", "slot_occupancy.mla",
           "experts_hit_share.mla", "latent_read_share.mla",
           "decode_roofline.mla", "chunk_roofline.mla")


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
    assert facts["kind"] == "closed_loop_mla"
    # Both prefill paths ran, experts were chosen, latent rows were read.
    assert facts["prefill_chunks"] > 0
    assert stats["prefill_tokens"] > stats["prefill_chunks"]
    assert 0 < stats["moe_experts_hit"] <= stats["moe_assignments_local"]
    assert stats["decode_keys_attended"] > 3 * stats["steps"]
    assert stats["chunk_blocks_read"] > 0
    assert load_reader("decode_step_ms.mla")(facts) == pytest.approx(
        1e3 * facts["window_s"] / facts["steps"])
    assert 0 < load_reader("slot_occupancy.mla")(facts) <= 100
    # 4 held experts in each of the 2 expert layers (1 of 3 is dense).
    assert load_reader("experts_hit_share.mla")(facts) == pytest.approx(
        100 * stats["moe_experts_hit"] / (4 * 2 * facts["steps"]))
    parts = fm.window_least_bytes(facts)
    assert load_reader("latent_read_share.mla")(facts) == pytest.approx(
        100 * parts["latent"] / parts["total"])
    # (16 + 8) values x 2 B a key a layer at the rehearsal's widths.
    assert parts["latent"] == stats["decode_keys_attended"] * 48
    # The two roofline shares want the device's time in the programs,
    # which only a profile from the chip holds: nothing here, and neither
    # raises. The traced part's counters are there all the same.
    assert facts["programs"] == {}
    assert facts["traced_stats"]["steps"] > 0
    decode, chunk = (load_reader("decode_roofline.mla"),
                     load_reader("chunk_roofline.mla"))
    assert decode(facts) is None and chunk(facts) is None
    # As on the chip: the traced steps at 20 ms, the chunks at 15 ms.
    counted = facts["traced_stats"]
    traced = dict(facts, device_kind="TPU v5e", programs={
        "jit_decode_step": {"seconds": counted["steps"] * 0.02,
                            "calls": counted["steps"]},
        "jit_chunk_step": {"seconds": 0.015 * 7, "calls": 7}})
    assert decode(traced) == pytest.approx(
        100 * fm.decode_least_seconds(traced)["seconds"]
        / (counted["steps"] * 0.02))
    assert chunk(traced) == pytest.approx(
        100 * fm.chunk_least_seconds(traced)["seconds"] / (0.015 * 7))
    # A program without the counters (the parent) gives them nothing.
    old = dict(traced, traced_stats={"steps": counted["steps"]},
               stats={"steps": facts["steps"]})
    assert all(load_reader(n)(old) is None for n in READERS[2:])
    # Another kind's facts are not theirs to read.
    assert all(load_reader(n)(dict(traced, kind="closed_loop_moe")) is None
               for n in READERS)
    assert any(n.startswith("least bytes of a decode step") and "latent" in n
               for n in result_of(tmp_path)["notes"])


def test_the_end_to_end_metric_is_measured(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "17",
                   "--seconds", "3", "--trace", "0", timeout=600)
    assert last_line(run)["correct"] is True
    assert '"serve_tokens_per_s"' in run.stdout


@pytest.mark.parametrize("fault", ["broken-latent", "broken-route"])
def test_a_broken_mechanism_reads_not_correct(tmp_path, fault):
    """The controls of the mechanisms themselves: a latent row whose
    positional key is cached unrotated; a layer that takes its experts
    among the held ones only."""
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
    """The control of the limits, through the harness's own comparison:
    the tokens the reference puts first in float8 are held to the limits
    of the served ones, and fail; the served ones pass."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "29",
                   "--seconds", "3", "--trace", "0", "--control", "fp8",
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    assert by_name["served_logit_gap.widest"]["ok"] is True
    assert by_name["served_logit_gap.mean"]["ok"] is True
    control = by_name["control[fp8].served_logit_gap.mean"]
    assert control["ok"] is False and control["value"] > control["limit"]


def test_the_configuration_holds_the_published_keys_and_states_its_cut():
    config = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "openpangu-ultra-moe-718b.json")))
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == (
        61, 3, 256, 153600)
    serve = config["layouts"]["serve"]
    assert serve["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 153600}
    assert sorted(config["reduced"]) == sorted(serve["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    model, _ = published()
    # One leading dense layer and four expert layers, a sixteenth of the
    # experts, an eighth of the vocabulary; no width, head count, rank or
    # experts-per-token touched.
    assert (model["num_hidden_layers"], model["first_k_dense_replace"]) == (
        5, 1)
    assert model["n_routed_experts"] * 16 == model["router_experts"] == 256
    assert model["vocab_size"] * 8 == 153600
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "rope_theta"):
        assert model[key] == config[key], key
    assert (0 < config["limits"]["served_logit_gap_mean"]
            < config["limits"]["served_logit_gap"] < 1)


def test_parameter_counts_against_the_hand_worked_ones():
    model, _ = published()
    c = fm.param_counts(model)
    assert c["attention"] == (7680 * 1536 + 1536 * 24576 + 7680 * 576
                              + 512 * 32768 + 16384 * 7680) == 196_575_232
    assert c["dense"] == 3 * 7680 * 18432 == 424_673_280
    assert c["expert"] == c["shared"] == 3 * 7680 * 2048 == 47_185_920
    assert c["router"] == 7680 * 256
    assert c["head"] == 19200 * 7680 == 147_456_000
    assert c["expert_layers"] == 4
    assert round(c["total"] / 1e9, 2) == 4.92


def test_byte_and_operation_counts_against_the_hand_worked_ones():
    model, precision = published()
    # The layers' whole parts and the head: 3.5 GB in bfloat16.
    whole = fm.whole_bytes_per_call(model, precision)
    assert round(whole / 1e9, 2) == 3.50
    assert fm.expert_bytes(model, precision) == 94_371_840
    # 1,152 B a token a layer: one 576-wide row in bfloat16.
    assert fm.latent_bytes_per_key(model, precision) == 1152
    steps, rows, context = 10, 48, 3500
    facts = {"model": model, "precision": precision, "kv_block_size": 16,
             "device_kind": "TPU v5e", "slots": 48, "steps": steps,
             "pushed_tokens": steps * rows, "first_tokens": 0,
             "stats": {"steps": steps, "moe_experts_hit": steps * 50,
                       "moe_assignments_local": steps * rows * 2,
                       "decode_keys_attended": steps * rows * context * 5}}
    parts = fm.window_least_bytes(facts)
    assert parts["total"] == (parts["weights"] + parts["experts"]
                              + parts["latent"])
    # The issue's step: 3.50 GB + 50 x 94.4 MB + 48 x 3.5 k x 5,760 B.
    assert round(parts["total"] / steps / 1e9, 1) == 9.2
    assert parts["latent"] == steps * rows * context * 5760
    least = fm.decode_least_seconds(dict(facts, traced_stats=facts["stats"]))
    assert least["bound"] == "memory"
    assert least["ops"]["attention"] == (
        2.0 * 128 * (2 * 512 + 64) * steps * rows * context * 5)
    assert least["ops"]["experts"] == 2.0 * steps * rows * 2 * 47_185_920
    assert least["seconds"] == pytest.approx(parts["total"] / 819e9)
    # A traced second of prefill: 40 chunks of 512 tokens at a mean
    # context of 2,000 keys on each of the five layers.
    facts.update(
        programs={"jit_chunk_step": {"calls": 40, "seconds": 1.0}},
        traced_stats={"prefill_tokens": 40 * 512,
                      "prefill_keys_attended": 40 * 512 * 5 * 2000,
                      "chunk_blocks_read": 40 * 5 * 125})
    least = fm.chunk_least_seconds(facts)
    c = fm.param_counts(model)
    assert least["ops"]["whole"] == 2.0 * 40 * 512 * c["whole_matmul"]
    # A sixteenth of a token's eight pairs (half a pair a layer) falls on
    # held experts.
    assert least["ops"]["experts"] == pytest.approx(
        2.0 * 40 * 512 * 0.5 * 4 * 47_185_920)
    assert least["ops"]["attention"] == (
        2.0 * 128 * (128 + 64 + 128) * 40 * 512 * 5 * 2000)
    assert least["ops"]["expand"] == (
        2.0 * 512 * 128 * 256 * 40 * 5 * 125 * 16)
    # 512 tokens touch every held expert of every expert layer.
    assert least["bytes"]["experts"] == pytest.approx(
        40 * 64 * 94_371_840, rel=1e-6)
    assert least["bytes"]["whole"] == 40 * whole
    assert least["seconds"] == pytest.approx(max(
        sum(least["ops"].values()) / 197e12,
        sum(least["bytes"].values()) / 819e9))


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_steps(name):
    assert load_reader(name)({"kind": "closed_loop_mla", "steps": 0}) is None
