"""Serving engine: the share of the traced window in which the device was
idle in gaps that no HOST WORK of the program explains: every cause that
does not start with ``fed:`` (runtime TraceMes such as
``np.asarray(jax.Array)``, ``no host span``), and ``fed:serve:fetch``, the
engine's wait for the step's logits. The fetch is counted here because on
the chip its span, not the runtime's ``np.asarray`` TraceMe inside it,
takes those gaps (PR 24: it opens a little earlier and so overlaps more):
they are the device's own launch gaps between an iteration's programs
while the host waits. What moves it is the fetch itself; and it is a
guard: it rises when a refactor drops a span, because the runtime's names
then take the gaps back.

One name per gap, the 150 longest gaps, the ten largest names, and None
for a program without spans: all as the docstring of
chipbench/layers/idle_share.sample.py says."""


def counted(name):
    return name == "fed:serve:fetch" or not name.startswith("fed:")


def read(facts):
    trace = facts.get("trace") or {}
    gaps = trace.get("idle_gaps") or []
    if not trace.get("window_s") or not any(
            name.startswith("fed:") for name, _ in gaps):
        return None
    idle_s = sum(seconds for name, seconds in gaps if counted(name))
    return 100.0 * idle_s / trace["window_s"]
