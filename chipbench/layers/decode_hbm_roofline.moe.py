"""Serving engine, the routed-experts closed-loop cell: the decode step's
share of its memory roofline. Least time of the steps in the traced part
of the window: the bytes that must cross HBM whatever implements the step
(``chipbench/flops_<reference>.py: window_least_bytes``: the weights held
whole once a step, the three matrices of every held expert that a live
row chose, per layer the K/V blocks that layer must read: a sliding
layer its window's) from what the engine counted between the profile's
start and its stop, at the device kind's peak bandwidth. Over the
device's own time in the step's program, ``jit_decode_step``, in that
profile. Memory-bound by a wide margin (16 rows: a few operations a
byte), so bytes alone set the least time. Nothing between two steps is in
it: that is ``decode_step_ms.moe``."""

import importlib

STEP_PROGRAM = "jit_decode_step"


def read(facts):
    step = (facts.get("programs") or {}).get(STEP_PROGRAM)
    counted = facts.get("traced_stats") or {}
    if (facts.get("kind") != "closed_loop_moe" or not step
            or not step["seconds"] or not counted.get("steps")
            or "moe_experts_hit" not in counted):
        return None
    from chipbench import flops

    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    least_s = (arch.window_least_bytes(dict(facts, stats=counted))["total"]
               / flops.peaks(facts["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / step["seconds"]
