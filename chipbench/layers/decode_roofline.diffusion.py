"""Serving engine, the block-diffusion closed-loop cell: the decode step's
share of its roofline. Least time of the steps in the traced part of the
window (``chipbench/flops_<reference>.py: decode_least_seconds``: the
larger of the operations at the MXU's peak and the bytes at HBM's, from
what the engine counted between the profile's start and its stop: the
weights held whole once a step, the three matrices of every expert a
live position chose, the K/V blocks read once for a row's ``B`` queries;
``B`` positions a row through attention, eight experts and the head) over
the device's own time in the step's program, ``jit_decode_step``, in that
profile. Nothing between two steps is in it: that is
``decode_step_ms.diffusion``."""

import importlib


def read(facts):
    counted = facts.get("traced_stats") or {}
    if (facts.get("kind") != "closed_loop_diffusion"
            or not counted.get("steps")
            or "diffusion_row_forwards" not in counted):
        return None
    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    step = (facts.get("programs") or {}).get(arch.STEP_PROGRAM)
    if not step or not step["seconds"]:
        return None
    return (100.0 * arch.decode_least_seconds(facts)["seconds"]
            / step["seconds"])
