"""Serving engine, the hybrid closed-loop cell: the decode step's share of
its memory roofline. Least time of a step: the bytes that must cross HBM
(chipbench/flops_<reference>.py: weights once a step, the live rows'
recurrent state read and written, the K/V blocks attended; the window's
mean, from the engine's counters) at the device kind's peak bandwidth.
Over the device's own time in the step's program, ``jit_decode_step``,
from the profile of a traced run: the mean of its executions in the traced
part of the window. Memory-bound by a wide margin (a step is a few
operations a byte), so bytes alone set the least time. Nothing between
two steps (prefill, the host) is in it: that is ``decode_step_ms.hybrid``."""

import importlib

STEP_PROGRAM = "jit_decode_step"


def read(facts):
    step = (facts.get("programs") or {}).get(STEP_PROGRAM)
    if (facts.get("kind") != "closed_loop_arch" or not facts.get("steps")
            or not step or not step["calls"]
            or "ssm_state_bytes" not in facts.get("stats", {})):
        return None
    from chipbench import flops

    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    least_s = (arch.window_least_bytes(facts)["total"] / facts["steps"]
               / flops.peaks(facts["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s * step["calls"] / step["seconds"]
