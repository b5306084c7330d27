"""Small shared pieces of the party-side harness (jax is imported lazily:
``run.py`` must be able to import nothing of this)."""

from __future__ import annotations

import os
import time


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation; a missing
    sample (a refused or failed request) is ``inf`` and sorts last."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    if vals[hi] == float("inf"):
        return vals[hi] if pos > lo else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def check(name, value, limit, why, exact=False):
    """One number compared, beside its limit. ``ok`` when value <= limit
    (or == limit for an exact comparison)."""
    ok = (value == limit) if exact else (value is not None and value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok),
            "why": why}


def memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def placement(tree):
    """(every leaf is a jax.Array, platforms, widest device set)."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    arrays = [x for x in leaves if isinstance(x, jax.Array)]
    platforms = sorted({d.platform for x in arrays for d in x.devices()})
    widest = max((len(x.devices()) for x in arrays), default=0)
    return len(arrays) == len(leaves), platforms, widest


class DeviceTrace:
    """The profiler around a part of the window, in the process that owns
    the chip; reduced by ``trace_reduce`` after the window has closed."""

    def __init__(self, ctx):
        self.dir = os.path.join(ctx.run_dir, f"trace-{ctx.party}")
        self.t0 = self.t1 = None

    def start(self):
        import jax

        # Host TraceMes (ours among them) yes, the Python tracer no: it
        # makes the trace hundreds of MB and slows the host it measures.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, kernels=()):
        from chipbench import trace_reduce

        path = trace_reduce.find_xplane(self.dir)
        if path is None or self.t0 is None:
            return None
        lines = trace_reduce.events_of(path)
        if os.environ.get("CHIPBENCH_KEEP_EVENTS"):
            # For the recorded trace under chipbench/tests/data/.
            import gzip
            import json

            keep = int(os.environ["CHIPBENCH_KEEP_EVENTS"])
            small = [dict(ln, events=ln["events"][:keep]) for ln in lines]
            with gzip.open(os.path.join(self.dir, "events.json.gz"),
                           "wt") as f:
                json.dump(small, f)
        out = trace_reduce.reduce(lines, window_s=self.t1 - self.t0,
                                  kernels=kernels)
        try:  # the raw trace is large; the reduction is what is kept
            os.remove(path)
        except OSError:
            pass
        return out


def annotate(name):
    """A host span on the profiler's clock, from the benchmark's files."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench:" + name)
