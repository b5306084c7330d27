"""Serving engine: the share of the traced window in which the device was
idle in gaps that no HOST WORK of the program explains: every cause that
does not start with ``fed:`` (runtime TraceMes such as
``np.asarray(jax.Array)``, ``no host span``, and ``window edge``: a lull
that the traced window cuts, whose span the profile does not hold), and
``fed:serve:fetch``, the engine's wait for the step's token ids.

On the chip the gap after a decode step is booked to the fetch or to the
``np.asarray`` TraceMe inside it (whichever the gap lies wholly inside: the
shorter), because the device's lines stand 0.5-2.3 ms before the host's
in the profile (chipbench/trace_reduce.py): on the host's clock most of
that gap is the dispatch of the next step, which ``idle_share.schedule``
counts. Until the reducer aligns the two, read this with ``.schedule`` as
one sum, the idle time of an iteration's host work; the split between the
two moves with the skew. It is also a guard: it rises when a refactor
drops a span, because the runtime's names then take the gaps.

Every gap of the window booked to one name, all names read; 0.0 where the
program has spans and no gap is theirs, None only without a trace or for a
program without spans: all as the docstring of chipbench/trace_reduce.py
says."""

from chipbench.trace_reduce import idle_share


def counted(name):
    return name == "fed:serve:fetch" or not name.startswith("fed:")


def read(facts):
    return idle_share(facts.get("trace"), counted)
