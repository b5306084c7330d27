"""Train step: model FLOP/s utilisation. Tokens per step over the step's
time, times the operations a token needs (chipbench/flops.py; recomputed
operations are not counted), over chips x the bf16 peak of the device kind
(chipbench/peaks.json; an unknown device is an error)."""

from chipbench import flops


def read(facts):
    if facts.get("kind") != "fedround" or not facts.get("train_s"):
        return None
    step_s = sum(facts["train_s"]) / (len(facts["train_s"])
                                      * facts["local_steps"])
    tokens = facts["batch"] * facts["seq"]
    per_token = flops.train_flops_per_token(facts["model"], facts["seq"])
    peak = flops.peaks(facts["device_kind"])["bf16_flops_per_s"]
    chips_per_party = facts["chips"] // max(1, facts.get("chip_parties", 1))
    return 100.0 * tokens / step_s * per_token / (chips_per_party * peak)
