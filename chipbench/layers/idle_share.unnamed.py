"""Serving engine: the share of the traced window in which the device was
idle in gaps that no HOST WORK of the program explains: every cause that
does not start with ``fed:`` (runtime TraceMes such as
``np.asarray(jax.Array)``, ``no host span``, and ``window edge``: a lull
that the traced window cuts, whose span the profile does not hold), and
``fed:serve:fetch``, the engine's wait for the step's token ids.

Its value changed by definition in PR 38 (``idle_share.schedule``'s
docstring has the rule): a piece of a gap inside ``fed:serve:fetch`` and
the runtime's ``np.asarray(jax.Array)`` is now the fetch's and not the
shorter TraceMe's (it counts here either way); a piece inside a phase of
``idle_share.schedule`` and a runtime event (``:dispatch`` and
``PjitFunction(decode_step)``) is now that phase's and left this reader.
What stays here beside the fetch: a lull that the traced window cuts
(``window edge``) and time no span of the program covers. It is also a
guard: it rises when a refactor drops a span, because the runtime's names
then take the pieces. The profile's clocks (ROADMAP B11) still move short
gaps between this reader and ``.schedule``: their sum is exact.

Every gap of the window booked, all names read; 0.0 where the program has
spans and nothing is theirs, None only without a trace or for a program
without spans: all as the docstring of chipbench/trace_reduce.py says."""

from chipbench.trace_reduce import idle_share


def counted(name):
    return name == "fed:serve:fetch" or not name.startswith("fed:")


def read(facts):
    return idle_share(facts.get("trace"), counted)
