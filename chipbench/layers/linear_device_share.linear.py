"""Serving engine, the linear-attention closed-loop cell: of the device's
busy time in the traced part of the window, the share under the linear
layers' mixer in all three serving programs: the scope
``serve/linear_attn`` (projections, the gated norm, ``Wo``) and the two
it holds, ``serve/conv`` (the depthwise convolution and its tail) and
``serve/delta_rule`` (the recurrence, in its step and its chunked form):
``trace.device_by_scope`` names an operation by its innermost scope, so
the three are summed, over ``trace.busy_s``. Whether the mechanism is
most of the work. 0.0 where the profile booked nothing under them; None
without a device profile."""

SCOPES = ("serve/linear_attn", "serve/conv", "serve/delta_rule")


def read(facts):
    trace = facts.get("trace") or {}
    if (facts.get("kind") != "closed_loop_arch"
            or facts.get("reference") != "olmo_hybrid"
            or not trace.get("busy_s")):
        return None
    by_scope = trace.get("device_by_scope") or {}
    return 100.0 * sum(by_scope.get(scope, 0.0) for scope in SCOPES) \
        / trace["busy_s"]
