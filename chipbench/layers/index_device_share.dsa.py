"""Serving engine, the learned-sparse-attention closed-loop cell: of the
device's busy time in the traced part of the window, the share under the
scopes ``serve/attn_index`` (the indexer's projections, its scores
against the cached index keys and the exact top-k) and
``serve/attn_sparse`` (the read of the chosen rows and their attention),
in all three serving programs (``trace.device_by_scope`` over
``trace.busy_s``). Whether the mechanism is most of the work: reported
whatever it reads."""

SCOPES = ("serve/attn_index", "serve/attn_sparse")


def read(facts):
    trace = facts.get("trace") or {}
    by_scope = trace.get("device_by_scope") or {}
    if (facts.get("kind") != "closed_loop_dsa" or not trace.get("busy_s")
            or not any(scope in by_scope for scope in SCOPES)):
        return None
    return 100.0 * sum(by_scope.get(scope, 0.0) for scope in SCOPES) \
        / trace["busy_s"]
