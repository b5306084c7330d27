"""Serving engine, the learned-sparse-attention closed-loop cell: of the
(query, key) pairs the full layers' indexers scored over the window
(every causal key of every query, decode steps and prompt chunks:
``stats()["index_keys_scored"]``), the share their top-k kept and
attention then read (``["index_keys_selected"]``: ``min(index_topk, pos +
1)`` a query). A fact of the traffic's lengths (2,048 over the mean
context a query stands at), not of the program: it says how much of the
dense read the mechanism spares, and it falls as contexts grow."""


def read(facts):
    stats = facts.get("stats") or {}
    if (facts.get("kind") != "closed_loop_dsa"
            or not stats.get("index_keys_scored")
            or "index_keys_selected" not in stats):
        return None
    return 100.0 * stats["index_keys_selected"] / stats["index_keys_scored"]
