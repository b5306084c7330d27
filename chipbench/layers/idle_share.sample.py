"""Serving engine: the share of the traced window in which the device was
idle while the engine sampled tokens on the host (``fed:serve:sample``:
``InferenceServer._sample`` over the live rows of a decode iteration,
float64 softmax + inverse-CDF draw over the whole vocabulary per sampled
row). What sampling on the device (ROADMAP S3) should take to zero.

How the number comes about (chipbench/trace_reduce.py): the reducer takes
the 150 longest gaps of at least 0.1 ms between device operations in the
traced part of the window, books each WHOLE gap to one name (the host
event of at least 10 us that overlaps it most; at equal overlap the
shortest) and keeps the ten largest names as ``idle_gaps``. So this is
the share of the traced window the device sat idle in gaps booked to
these names, not a duration of the phase itself. The three
``idle_share.*`` add up to 100 x (1 - busy_s/window_s) within a point:
gaps beyond the 150th and names beyond the tenth are in none of them,
and the gaps are taken over the device's span, which runs a few ms past
the host's window.

Returns None without a trace, and where no gap carries a ``fed:`` name at
all: a program that opens no spans (any commit before PR 24) has nothing
for this reader to read."""


def counted(name):
    return name == "fed:serve:sample"


def read(facts):
    trace = facts.get("trace") or {}
    gaps = trace.get("idle_gaps") or []
    if not trace.get("window_s") or not any(
            name.startswith("fed:") for name, _ in gaps):
        return None
    idle_s = sum(seconds for name, seconds in gaps if counted(name))
    return 100.0 * idle_s / trace["window_s"]
