"""Serving engine, the learned-sparse-attention closed-loop cell: the
decode step's share of its roofline. Least seconds of the steps in the
traced part of the window (``chipbench/flops_<reference>.py:
decode_least_seconds``: the LARGER of the operations at the MXU's peak and
the bytes at HBM's: the weights held whole once a step, the three matrices
of every held expert a live row chose, one index key for every pair the
indexers scored, one latent row for every pair they kept and for every key
a sliding layer's window holds) from what the engine counted between the
profile's start and its stop. Over the device's own time in the step's
program, ``jit_decode_step``, in that profile. Nothing between two steps
is in it: that is ``decode_step_ms.dsa``."""

import importlib


def read(facts):
    counted = facts.get("traced_stats") or {}
    if (facts.get("kind") != "closed_loop_dsa" or not counted.get("steps")
            or not counted.get("index_keys_selected_decode")
            or "moe_experts_hit" not in counted):
        return None
    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    step = (facts.get("programs") or {}).get(arch.STEP_PROGRAM)
    if not step or not step["seconds"]:
        return None
    return 100.0 * arch.decode_least_seconds(facts)["seconds"] \
        / step["seconds"]
