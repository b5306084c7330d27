"""CPU tests of the ``dots3-longdocs-closed16`` cell at its rehearsal
preset: the cell end to end, every control (of the limits: the reference
in the precision below; of the mechanisms: a selection that takes the most
recent keys, sliding layers that read every key, a positional key cached
unrotated, a wrong share, a wrong token), the ``*.dsa`` readers on recorded
facts, and the byte and operation functions against counts worked by hand.
``python -m pytest chipbench/tests -q``."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import flops_dots3_note as fm, traffic  # noqa: E402
from chipbench.run import load_reader, resolve  # noqa: E402
from chipbench.tests.test_chipbench import last_line, run_cell  # noqa: E402

CELL = "dots3-longdocs-closed16"
READERS = ("decode_step_ms.dsa", "slot_occupancy.dsa",
           "experts_hit_share.dsa", "index_selected_share.dsa",
           "index_device_share.dsa", "decode_roofline.dsa",
           "chunk_roofline.dsa")
FULL, SLIDING = "full_attention", "sliding_attention"


def published():
    plan = resolve(CELL, rehearse=False)
    return plan["model"], plan["precision"]


def result_of(tmp_path):
    run_dir = next((tmp_path / "out" / CELL).iterdir())
    return json.load(open(run_dir / "alice.result.json"))


def test_the_cell_rehearses_and_its_readers_read_the_recorded_facts(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "2147483655",
                   "--seconds", "4", "--trace", "1", timeout=600)
    line = last_line(run)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    facts = result_of(tmp_path)["facts"]
    stats = facts["stats"]
    assert facts["kind"] == "closed_loop_dsa"
    # Chunks ran, experts were chosen, the indexers scored and kept: every
    # prompt is longer than the preset's ``index_topk`` (32) and window (33).
    assert facts["prefill_chunks"] > 0
    assert 0 < stats["moe_experts_hit"] <= stats["moe_assignments_local"]
    assert 0 < stats["index_keys_selected"] < stats["index_keys_scored"]
    assert 0 < stats["index_keys_selected_decode"] \
        < stats["index_keys_scored_decode"] < stats["index_keys_scored"]
    assert stats["kv_dead_blocks"] > 0
    # Past 32 keys every query keeps 32 on each of the two full layers.
    rows = fm.decode_rows(stats, facts["model"])
    assert rows == stats["index_keys_selected_decode"] / (2 * 32)
    assert stats["decode_keys_attended"] == rows * (2 * 32 + 3 * 33)
    assert load_reader("decode_step_ms.dsa")(facts) == pytest.approx(
        1e3 * facts["window_s"] / facts["steps"])
    assert 0 < load_reader("slot_occupancy.dsa")(facts) <= 100
    # 4 held experts in each of the 4 expert layers (1 of 5 is dense).
    assert load_reader("experts_hit_share.dsa")(facts) == pytest.approx(
        100 * stats["moe_experts_hit"] / (4 * 4 * facts["steps"]))
    share = load_reader("index_selected_share.dsa")(facts)
    assert share == pytest.approx(
        100 * stats["index_keys_selected"] / stats["index_keys_scored"])
    assert 0 < share < 100
    parts = fm.window_least_bytes(facts)
    # (16 + 8) and (32 + 8) values x 2 B a row, 16 x 2 B an index key, at
    # the rehearsal's widths.
    assert parts["selected_rows"] == stats["index_keys_selected_decode"] * 48
    assert parts["index_keys"] == stats["index_keys_scored_decode"] * 32
    assert parts["window_rows"] == rows * 3 * 33 * 80
    # The device's shares want a profile from the chip: nothing here, and
    # none raises. The traced part's counters are there all the same.
    assert facts["programs"] == {}
    assert facts["traced_stats"]["steps"] > 0
    decode, chunk, device = (load_reader("decode_roofline.dsa"),
                             load_reader("chunk_roofline.dsa"),
                             load_reader("index_device_share.dsa"))
    assert decode(facts) is None and chunk(facts) is None
    assert device(facts) is None
    # As on the chip: the traced steps at 20 ms, the chunks at 15 ms, and
    # a profile whose scopes hold the two new ones.
    counted = facts["traced_stats"]
    traced = dict(facts, device_kind="TPU v5e", programs={
        "jit_decode_step": {"seconds": counted["steps"] * 0.02,
                            "calls": counted["steps"]},
        "jit_chunk_step": {"seconds": 0.015 * 7, "calls": 7}},
        trace={"busy_s": 2.0, "device_by_scope": {
            "serve/attn_index": 0.5, "serve/attn_sparse": 0.3,
            "serve/attn_window_latent": 0.2, "serve/moe_experts": 1.0}})
    assert decode(traced) == pytest.approx(
        100 * fm.decode_least_seconds(traced)["seconds"]
        / (counted["steps"] * 0.02))
    assert chunk(traced) == pytest.approx(
        100 * fm.chunk_least_seconds(traced)["seconds"] / (0.015 * 7))
    assert device(traced) == pytest.approx(40.0)
    # A program without the counters or the scopes (the parent) gives
    # them nothing.
    old = dict(traced, traced_stats={"steps": counted["steps"]},
               stats={"steps": facts["steps"]},
               trace={"busy_s": 2.0, "device_by_scope": {
                   "serve/attn_latent": 1.0}})
    assert all(load_reader(n)(old) is None for n in READERS[2:])
    # Another kind's facts are not theirs to read.
    assert all(load_reader(n)(dict(traced, kind="closed_loop_mla")) is None
               for n in READERS)
    assert any(n.startswith("least bytes of a decode step")
               and "selected_rows" in n
               for n in result_of(tmp_path)["notes"])


def test_the_end_to_end_metric_is_measured(tmp_path):
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "17",
                   "--seconds", "4", "--trace", "0", timeout=600)
    assert last_line(run)["correct"] is True
    assert '"serve_tokens_per_s"' in run.stdout


@pytest.mark.parametrize("fault", [
    "broken-index", "broken-window", "broken-latent", "broken-route",
    "broken-token"])
def test_a_broken_mechanism_reads_not_correct(tmp_path, fault):
    """The controls of the mechanisms themselves: a selection that takes
    the most recent keys; sliding layers that read every key; a latent
    row whose positional key is cached unrotated; a layer that takes its
    experts among the held ones only; a token off by one."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "23",
                   "--seconds", "4", "--trace", "0", "--inject", fault,
                   timeout=600)
    line = last_line(run)
    assert line["correct"] is False and line["failed"] == 0
    by_name = {c["name"]: c for c in result_of(tmp_path)["checks"]}
    gap = by_name["served_logit_gap.mean"]
    assert gap["ok"] is False and gap["value"] > gap["limit"]
    assert by_name["compiles_in_window"]["ok"] is True


def test_the_fp8_control_reads_not_correct(tmp_path):
    """The control of the limits, through the harness's own comparison:
    the tokens the reference puts first in float8 are held to the limits
    of the served ones, and fail; the served ones pass."""
    run = run_cell(tmp_path, "--workload", CELL, "--seed", "29",
                   "--seconds", "4", "--trace", "0", "--control", "fp8",
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
        ROOT, "chipbench", "configs", "dots3-note-prev.json")))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        entry = next(row for row in map(json.loads, open(catalog))
                     if row["name"] == "dots3-note-prev")
        assert config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["index_topk"],
            config["sliding_window_size"]) == (46, 256, 152064, 2048, 513)
    assert config["layer_types"].count(FULL) == 13
    serve = config["layouts"]["serve"]
    assert sorted(config["reduced"]) == sorted(serve["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    model, _ = published()
    # The leading dense layer and one whole period, an eighth of the
    # experts and of the vocabulary; no width, head count, rank, window,
    # index size or experts-per-token touched.
    assert model["num_hidden_layers"] == 5
    assert model["layer_types"] == config["layer_types"][:5] == [
        FULL, FULL, SLIDING, SLIDING, SLIDING]
    assert model["first_k_dense_replace"] == 1
    assert model["n_routed_experts"] * 8 == model["router_experts"] == 256
    assert model["vocab_size"] * 8 == 152064
    changed = set(serve["model"]) - {"router_experts", "held_experts_first"}
    assert changed == {"num_hidden_layers", "layer_types",
                       "n_routed_experts", "vocab_size"}
    for key, value in config.items():
        if key not in changed and key not in (
                "layouts", "rehearsal", "assumed", "departures", "reduced",
                "source", "precision", "reference"):
            assert model[key] == value, key
    assert (0 < config["limits"]["served_logit_gap_mean"]
            < config["limits"]["served_logit_gap"])
    # The rehearsal's selection is far below its contexts.
    mix = resolve(CELL, rehearse=True)["mix"]
    assert config["rehearsal"]["index_topk"] * 2 < mix["prompt_len"]["lo"]
    real = resolve(CELL, rehearse=False)["mix"]
    assert real["prompt_len"]["lo"] > config["index_topk"]
    assert real["serving"]["max_len"] == (
        real["prompt_len"]["hi"] + real["output_len"]["hi"])
    # The traffic ISSUE 44 names, to the letter; a cycle of the pool's
    # lengths holds the longest prompt the deployment admits.
    assert real["prompt_len"] == {"median": 8192, "sigma": 0.7, "lo": 2304,
                                  "hi": 32768}
    assert (real["clients"], real["serving"]["max_slots"]) == (16, 12)
    lengths = traffic.lognormal_quantiles(real["prompt_len"], real["pool"])
    assert (min(lengths), max(lengths)) == (2304, 32768)


def test_parameter_counts_against_the_hand_worked_ones():
    model, _ = published()
    c = fm.param_counts(model)
    assert c[FULL] == (
        5120 * 1024 + 1024 * 24576 + 5120 * 576 + 512 * 32768
        + 16384 * 5120 + 5120 * 128
        + 1024 * 8192 + 5120 * 128 + 5120 * 64) == 144_048_128
    assert c[SLIDING] == (
        5120 * 1024 + 1024 * 16384 + 5120 * 1088 + 1024 * 20480
        + 8192 * 5120 + 5120 * 64) == 90_832_896
    assert c["dense"] == 3 * 5120 * 13824 == 212_336_640
    assert c["expert"] == c["shared"] == 3 * 5120 * 1536 == 23_592_960
    assert c["router"] == 5120 * 256
    assert c["head"] == 19008 * 5120
    assert c["expert_layers"] == 4
    assert round(c["total"] / 1e9, 3) == 4.087


def test_byte_and_operation_counts_against_the_hand_worked_ones():
    model, precision = published()
    whole = fm.whole_bytes_per_call(model, precision)
    # Two full and three sliding attentions, the dense SwiGLU, four
    # shared experts and routers, the head: 1.94 GB in bfloat16.
    assert round(whole / 1e9, 2) == 1.94
    assert fm.expert_bytes(model, precision) == 47_185_920
    assert fm.row_bytes(model, precision, FULL) == 1152
    assert fm.row_bytes(model, precision, SLIDING) == 2176
    assert fm.index_key_bytes(model, precision) == 256
    assert fm.index_pair_ops(model) == 2 * 64 * 128
    # One query a row: absorbed; a chunk's queries share an expansion.
    assert fm.pair_ops(model, FULL, 1.0) == 2 * 128 * (2 * 512 + 64)
    assert fm.pair_ops(model, SLIDING, 1.0) == 2 * 64 * (2 * 1024 + 64)
    assert fm.pair_ops(model, FULL, float("inf")) == 2 * 128 * 320
    assert fm.pair_ops(model, SLIDING, float("inf")) == 2 * 64 * 384
    steps, rows, context = 10, 12, 10000
    stats = {
        "steps": steps, "moe_experts_hit": steps * 40,
        "moe_assignments_local": steps * rows * 4,
        "index_keys_scored_decode": steps * rows * 2 * context,
        "index_keys_selected_decode": steps * rows * 2 * 2048,
        "decode_keys_attended": steps * rows * (2 * 2048 + 3 * 513),
    }
    facts = {"model": model, "precision": precision, "kv_block_size": 16,
             "device_kind": "TPU v5e", "slots": 12, "steps": steps,
             "stats": stats, "traced_stats": stats}
    assert fm.decode_rows(stats, model) == steps * rows
    parts = fm.window_least_bytes(facts)
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    assert parts["weights"] == steps * whole
    assert parts["index_keys"] == steps * rows * 2 * context * 256
    assert parts["selected_rows"] == steps * rows * 2 * 2048 * 1152
    assert parts["window_rows"] == steps * rows * 3 * 513 * 2176
    # A step: 1.94 GB + 40 x 47.2 MB + 12 rows x (5.1 + 4.7 + 3.3) MB.
    assert round(parts["total"] / steps / 1e9, 2) == 3.99
    least = fm.decode_least_seconds(facts)
    assert least["bound"] == "memory"
    assert least["ops"]["index"] == 16384.0 * steps * rows * 2 * context
    assert least["ops"]["selected"] == (
        278528.0 * steps * rows * 2 * 2048)
    assert least["ops"]["experts"] == 2.0 * steps * rows * 4 * 23_592_960
    assert least["seconds"] == pytest.approx(parts["total"] / 819e9)
    # A traced second of prefill: 40 chunks of 512 tokens at a mean
    # context of 8,000 keys.
    tokens = 40 * 512
    counted = dict(
        stats, prefill_tokens=tokens,
        index_keys_scored=stats["index_keys_scored_decode"]
        + tokens * 2 * 8000,
        index_keys_selected=stats["index_keys_selected_decode"]
        + tokens * 2 * 2048,
        prefill_keys_attended=tokens * (2 * 2048 + 3 * 513))
    facts.update(
        programs={"jit_chunk_step": {"calls": 40, "seconds": 1.0}},
        traced_stats=counted)
    least = fm.chunk_least_seconds(facts)
    c = fm.param_counts(model)
    assert least["ops"]["whole"] == 2.0 * tokens * c["whole_matmul"]
    # An eighth of a token's eight pairs (one pair a layer) falls on held
    # experts: what the decode steps above measured (4 of 4 x 8).
    assert fm.local_share(facts) == pytest.approx(1 / 8)
    assert least["ops"]["experts"] == pytest.approx(
        2.0 * tokens * 1.0 * 4 * 23_592_960)
    assert least["ops"]["index"] == 16384.0 * tokens * 2 * 8000
    assert least["ops"]["selected"] == 81920.0 * tokens * 2 * 2048
    assert least["ops"]["window"] == 49152.0 * tokens * 3 * 513
    # 512 tokens touch every held expert of every expert layer.
    assert least["bytes"]["experts"] == pytest.approx(
        40 * 4 * 32 * 47_185_920, rel=1e-6)
    assert least["bytes"]["whole"] == 40 * whole
    # 512 tokens a call against 8.0 GB of weights: the chunk is bound by
    # the weights' bytes (9.7 ms a call) before its operations (6.7 ms).
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(
        sum(least["bytes"].values()) / 819e9)
    assert sum(least["ops"].values()) / 197e12 == pytest.approx(
        0.7 * least["seconds"], rel=0.05)
    # At the median context the two new kinds of attention are a quarter
    # of a token's operations in the cheaper form (two fifths absorbed).
    attention = sum(least["ops"][k] for k in ("index", "selected", "window"))
    assert 0.2 < attention / sum(least["ops"].values()) < 0.35


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_steps(name):
    assert load_reader(name)({"kind": "closed_loop_dsa", "steps": 0}) is None
