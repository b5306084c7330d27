"""Serving engine, the linear-attention closed-loop cell: a prompt
chunk's share of its roofline. Least seconds of one chunk, the window's
mean (``chipbench/flops_olmo_hybrid.py: chunk_least_seconds``: the LARGER
of its operations at the MXU's peak (two a matmul parameter a real token,
the head once, the recurrence in the chunked form) and its bytes at HBM's
(every weight once, the slot's recurrent state read and written, the
cached K/V blocks the full layers gather), from the growth of the
engine's counters over the window: ``chunk_tokens``, ``prefill_chunks``,
``chunk_state_bytes``, ``chunk_blocks_read``) over the device's own mean
time in ``jit_chunk_step`` in the profile of a traced run. The bucketed
prefill of the prompts under a chunk is another program and in neither
side. None without a device profile or from a program without those
counters."""

import importlib


def read(facts):
    counted = (facts.get("program") or {}).get("stats") or {}
    if (facts.get("kind") != "closed_loop_arch"
            or facts.get("reference") != "olmo_hybrid"
            or "chunk_state_bytes" not in counted
            or "chunk_tokens" not in counted):
        return None
    arch = importlib.import_module("chipbench.flops_" + facts["reference"])
    chunk = (facts.get("programs") or {}).get(arch.CHUNK_PROGRAM)
    if not chunk or not chunk["calls"]:
        return None
    if not counted.get("prefill_chunks"):
        return 0.0
    return (100.0 * arch.chunk_least_seconds(facts)["seconds"]
            * chunk["calls"] / chunk["seconds"])
