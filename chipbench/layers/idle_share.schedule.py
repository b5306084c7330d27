"""Serving engine: the share of the traced window in which the device was
idle while the engine did its own bookkeeping between two dispatches:
``fed:serve:admit`` (admission and the prefill it dispatches), ``:build``
(block grants, tables, tokens, positions), ``:dispatch`` (the three
enqueues), ``:emit`` (token push, finish), ``:prefill_chunk`` and ``:idle``
(waiting for a request). What preparing iteration t+1 while t runs
(ROADMAP S3) should take to zero. On the chip most of an iteration's gap
reads under ``idle_share.unnamed`` instead, by the profile's clocks (that
reader's docstring): read the two as one sum.

Every gap of the window booked to one name, all names read; 0.0 where the
program has spans and no gap is theirs, None only without a trace or for a
program without spans: all as the docstring of chipbench/trace_reduce.py
says."""

from chipbench.trace_reduce import idle_share

PHASES = frozenset("fed:serve:" + p for p in (
    "admit", "build", "dispatch", "emit", "prefill_chunk", "idle"))


def counted(name):
    return name in PHASES


def read(facts):
    return idle_share(facts.get("trace"), counted)
