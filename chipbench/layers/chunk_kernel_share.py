"""Serving engine: of the prompt chunks the window ran
(``stats()["prefill_chunks"]``), the share whose read of the pool ran its
trips as the Pallas kernel of ``rayfed_tpu/ops/paged_chunk_attention.py``
(``["prefill_chunks_kernel"]``, PR 45: the engine decides it once a pool,
``decode.paged_chunk_is_kernel``: on a TPU, where a slot reaches thousands
of keys). 100 in the three long-context cells, whose every chunk reads
that way; a pool that keeps the loop would read 0.

From ``facts["program"]["stats"]`` (``common.ProgramRecord``: the growth
of every counter over the window): None without it (an untraced run),
where the window ran no chunk, or where the program has no such counter
(before PR 45)."""


def read(facts):
    stats = (facts.get("program") or {}).get("stats")
    if (not stats or not stats.get("prefill_chunks")
            or "prefill_chunks_kernel" not in stats):
        return None
    return 100.0 * stats["prefill_chunks_kernel"] / stats["prefill_chunks"]
