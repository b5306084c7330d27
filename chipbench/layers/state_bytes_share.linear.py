"""Serving engine, the linear-attention closed-loop cell: the recurrent
state's part of a decode step's least bytes: ``stats()["ssm_state_bytes"]``
(live rows x the slot's state bytes x 2, read and written, over the
window's steps) over ``chipbench/flops_olmo_hybrid.py:
window_least_bytes``' total (weights once a step, that state, the K/V
blocks the full layers attend). The mechanism in one number: what a step
pays for keeping a state a slot in place of a row a token; about a sixth
at 32 live rows. From the engine's counters alone (a traced run, as every
per-layer metric; no device profile needed)."""

import importlib


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_arch"
            or facts.get("reference") != "olmo_hybrid"
            or "ssm_state_bytes" not in stats):
        return None
    if not stats.get("steps"):
        return 0.0
    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    parts = arch.window_least_bytes(facts)
    return 100.0 * parts["state"] / parts["total"]
