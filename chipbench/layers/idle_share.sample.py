"""NOT a metric of the benchmark since PR 35: no span is named
``fed:serve:sample`` since PR 30 (the next token is chosen on the device),
so this read 0 by construction and left ``BENCHMARK.json``. The file stays
only because ``tests/test_tracing_phases.py`` loads it by path and a
benchmark PR may not touch ``tests/``: it goes with those cases (PERF.md
section 7). The arithmetic is ``chipbench/trace_reduce.py:idle_share``."""

from chipbench.trace_reduce import idle_share


def counted(name):
    return name == "fed:serve:sample"


def read(facts):
    return idle_share(facts.get("trace"), counted)
