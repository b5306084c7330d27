"""Serving engine: the share of the traced window in which the device was
idle while the engine did its own bookkeeping between two dispatches:
``fed:serve:admit`` (admission and the prefill it dispatches), ``:build``
(block grants, tables, tokens, positions), ``:dispatch`` (the three
enqueues), ``:emit`` (token push, finish), ``:prefill_chunk`` and ``:idle``
(waiting for a request). What preparing iteration t+1 while t runs
(ROADMAP S3) took to near zero in PR 36; what is left is a lull
(``:idle``) and a last chunk's fetch (``:prefill_chunk``).

Its value changed by definition in PR 38: a gap is cut at the host events
over it and each piece goes to the innermost ``fed:`` span that covers it
(``chipbench/trace_reduce.py``), where a gap went whole to the event that
overlapped it most. So a gap that begins in ``:emit`` and ends in
``:dispatch`` is shared between them, and a piece inside ``:dispatch`` and
the runtime's ``PjitFunction(decode_step)`` is the dispatch's (here), one
inside ``fed:serve:fetch`` and ``np.asarray`` the fetch's
(``idle_share.unnamed``). The 0.45-2.3 ms by which the device's lines
stand before the host's in a profile (ROADMAP B11) still shift which phase
a short gap falls under: the two readers' sum is exact, their split is not.

Every gap of the window booked, all names read; 0.0 where the program has
spans and nothing is theirs, None only without a trace or for a program
without spans: all as the docstring of chipbench/trace_reduce.py says."""

from chipbench.trace_reduce import idle_share

PHASES = frozenset("fed:serve:" + p for p in (
    "admit", "build", "dispatch", "emit", "prefill_chunk", "idle"))


def counted(name):
    return name in PHASES


def read(facts):
    return idle_share(facts.get("trace"), counted)
