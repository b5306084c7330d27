"""Serving engine: the share of the traced window in which the device was
idle while the engine did its own bookkeeping between two dispatches:
``fed:serve:admit`` (admission and the prefill it dispatches), ``:build``
(block grants, tables, tokens, positions), ``:dispatch`` (the three
enqueues), ``:emit`` (token push, finish), ``:prefill_chunk`` and ``:idle``
(waiting for a request). What preparing iteration t+1 while t runs
(ROADMAP S3) should take to zero.

One name per gap, the 150 longest gaps, the ten largest names, and None
for a program without spans: all as the docstring of
chipbench/layers/idle_share.sample.py says."""


PHASES = frozenset("fed:serve:" + p for p in (
    "admit", "build", "dispatch", "emit", "prefill_chunk", "idle"))


def counted(name):
    return name in PHASES


def read(facts):
    trace = facts.get("trace") or {}
    gaps = trace.get("idle_gaps") or []
    if not trace.get("window_s") or not any(
            name.startswith("fed:") for name, _ in gaps):
        return None
    idle_s = sum(seconds for name, seconds in gaps if counted(name))
    return 100.0 * idle_s / trace["window_s"]
