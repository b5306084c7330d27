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
    the chip; reduced by ``trace_reduce`` after the window has closed.
    Between ``start`` and ``stop`` the host span ``chipbench:traced`` is
    open, inside the profile at both ends: it is the window that
    ``trace_reduce.reduce`` clips everything to, on the profiler's clock."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.dir = os.path.join(ctx.run_dir, f"trace-{ctx.party}")
        self.window = None

    def start(self):
        import jax

        # Host TraceMes (ours among them) yes, the Python tracer no: it
        # makes the trace hundreds of MB and slows the host it measures.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.window = annotate("traced")
        self.window.__enter__()

    def stop(self):
        import jax

        self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, kernels=()):
        """The reduction, or None where there is nothing to reduce: no
        profile, or one without a device plane (a CPU run), so that
        ``device`` gets ``busy_s`` and ``window_s`` only from a chip."""
        from chipbench import trace_reduce

        path = trace_reduce.find_xplane(self.dir)
        if path is None or self.window is None:
            return None
        t0 = time.perf_counter()
        lines = trace_reduce.events_of(path)
        read_s = time.perf_counter() - t0
        if os.environ.get("CHIPBENCH_KEEP_EVENTS"):
            # For the recorded trace under chipbench/tests/data/.
            import gzip
            import json

            keep = int(os.environ["CHIPBENCH_KEEP_EVENTS"])
            small = [dict(ln, events=ln["events"][:keep]) for ln in lines]
            with gzip.open(os.path.join(self.dir, "events.json.gz"),
                           "wt") as f:
                json.dump(small, f)
        t0 = time.perf_counter()
        out = trace_reduce.reduce(lines, kernels=kernels)
        self.ctx.say(
            "trace reduced", events=sum(len(ln["events"]) for ln in lines),
            read_s=round(read_s, 3),
            reduce_s=round(time.perf_counter() - t0, 3),
            **{k: out.get(k) for k in ("devices", "window_from", "window_s",
                                       "busy_s", "idle_small_s",
                                       "program_spans")})
        try:  # the raw trace is large; the reduction is what is kept
            os.remove(path)
        except OSError:
            pass
        return out if out["devices"] else None


def breakdown_of(reduced):
    """The result line's ``breakdown`` from a reduction (None without one):
    the ten largest of the device's operations (each ``<scope>:<op>``), of
    its named scopes and of the idle gaps' causes."""
    if not reduced:
        return None
    return {key: reduced[key]
            for key in ("device_ops", "device_scopes", "idle_gaps")}


class ProgramRecord:
    """What the program measured of itself over the window, for
    ``facts["program"]``: its phases' durations (``tracing.phase``), its
    wire spans of 1 MiB and more (``tracing.span`` / ``record``) and, for a
    kind that hands them in, the growth of every counter of the engine's
    ``stats()``. Every kind fills it the same way: ``open()`` before the
    window (the record starts empty, ``tracing.enable()``), ``close()`` as
    the window closes (``tracing.disable()``), ``facts(...)`` after it.
    Off (``--trace 0``) each call does nothing, so an untraced run
    executes nothing of this."""

    SPAN_MIN_BYTES = 1 << 20

    def __init__(self, on):
        self.on = bool(on)
        self.phases, self.spans = {}, []

    def open(self):
        if not self.on:
            return
        from rayfed_tpu import tracing

        tracing.clear()
        tracing.enable()

    def close(self):
        """Read the record where the kind reads its counters, and stop
        recording (a drain may follow the window; it is in no number)."""
        if not self.on:
            return
        from rayfed_tpu import tracing

        tracing.disable()
        self.phases = tracing.phase_summary()
        self.spans = [
            {"kind": s.kind, "nbytes": int(s.nbytes),
             "duration_s": s.duration_s,
             # A "recv" is an arrival event without a duration, but for
             # frames of 1 MiB and more, which the program marks.
             "timed": s.kind != "recv" or bool(s.extra.get("timed"))}
            for s in tracing.get_spans()
            if s.nbytes >= self.SPAN_MIN_BYTES]

    def facts(self, before=None, after=None, **more):
        """``{"phases", "spans"}``, with ``stats`` where the engine's
        counters before and after the window are handed in (the growth of
        every integer key, not a chosen few), and whatever else the kind
        knows of the recorded stretch (``rounds``). None when off."""
        if not self.on:
            return None
        out = dict(more, phases=self.phases, spans=self.spans)
        if before is not None and after is not None:
            # Counters are integers; a gauge among them (``pending``,
            # ``kv_blocks_free``) has a difference that means nothing.
            out["stats"] = {
                k: v - before.get(k, 0) for k, v in after.items()
                if isinstance(v, int) and not isinstance(v, bool)}
        return out


def annotate(name):
    """A host span on the profiler's clock, from the benchmark's files."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench:" + name)
