"""Serving engine, the learned-sparse-attention closed-loop cell: the
prefill programs' share of their roofline. Least seconds of their
executions in the traced part of the window
(``chipbench/flops_<reference>.py: chunk_least_seconds``: the LARGER of the
operations at the MXU's peak and the bytes at HBM's, from the real prompt
tokens put through, the (query, key) pairs the indexers scored, those they
kept and those the sliding layers' windows hold, as the engine counted
them between the profile's start and its stop: the least any program must
do for that work, so a program that reads every key and masks reads
lower) over the device's own time in ``jit_chunk_step`` and
``jit_prefill_rows`` in that profile."""

import importlib


def read(facts):
    counted = facts.get("traced_stats") or {}
    if (facts.get("kind") != "closed_loop_dsa"
            or not counted.get("prefill_tokens")
            or "index_keys_scored_decode" not in counted):
        return None
    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    programs = facts.get("programs") or {}
    seconds = sum(programs.get(name, {}).get("seconds", 0.0)
                  for name in arch.PREFILL_PROGRAMS)
    if not seconds:
        return None
    return 100.0 * arch.chunk_least_seconds(facts)["seconds"] / seconds
