"""Serving engine, the linear-attention closed-loop cell: of the device's
busy time in the traced part of the window, the share under the scope
``serve/delta_rule`` alone: the gated delta rule's recurrence, in its
one-step form (decode) and its chunked form (both prefill programs),
plain ``jnp`` today. What a kernel of the recurrence would attack, and
the most it could win (``trace.device_by_scope`` over ``trace.busy_s``).
0.0 where the profile booked nothing under it; None without a device
profile."""

SCOPE = "serve/delta_rule"


def read(facts):
    trace = facts.get("trace") or {}
    if (facts.get("kind") != "closed_loop_arch"
            or facts.get("reference") != "olmo_hybrid"
            or not trace.get("busy_s")):
        return None
    by_scope = trace.get("device_by_scope") or {}
    return 100.0 * by_scope.get(SCOPE, 0.0) / trace["busy_s"]
