"""One party of one run: a process of its own, owning its chips alone.

Started by ``run.py`` with the run's spec and this party's name. Brings
jax up on the platform the launcher asked for (anything else is an error,
never a fallback), joins the federation, hands over to the mix's kind
(``chipbench/kinds/<kind>.py``), writes its result file BEFORE any
shutdown begins, then stops the federation under a deadline of its own.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SHUTDOWN_DEADLINE_S = 30.0


class Ctx:
    """What a kind gets: the spec, this party, its device, and the log."""

    def __init__(self, spec, party):
        self.spec, self.party = spec, party
        self.mix, self.model = spec["mix"], spec["model"]
        self.seed, self.seconds = int(spec["seed"]), float(spec["seconds"])
        self.trace, self.rehearse = bool(spec["trace"]), bool(spec["rehearse"])
        self.platform = spec["platform"]
        self.run_dir = spec["run_dir"]
        self.parties = [p["name"] for p in self.mix["parties"]]
        self.chip_parties = [p["name"] for p in self.mix["parties"]
                             if p["role"] == "chip"]
        self.lead = self.chip_parties[0]
        self.me = next(p for p in self.mix["parties"] if p["name"] == party)
        self.is_chip = self.me["role"] == "chip"
        self.is_lead = party == self.lead
        self.device = None
        self.fed = None
        self.setup_parts = {}
        self.compiles = 0       # backend compilations seen so far
        self._mark = time.time()

    def say(self, what, **evidence):
        body = " ".join(f"{k}={v}" for k, v in evidence.items())
        print(f"[{time.time() - self.spec['t0']:8.2f}s] {what}: {body}",
              flush=True)

    def part(self, name):
        """Close one part of set-up; they add up to ``setup_s``."""
        now = time.time()
        self.setup_parts[name] = round(
            self.setup_parts.get(name, 0.0) + now - self._mark, 3)
        self._mark = now

    def inject(self, what):
        return self.spec.get("inject") == what


def load_kind(name):
    path = os.path.join(HERE, "kinds", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_kind_" + name,
                                                  path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def come_up(ctx):
    """Bring jax up on the asked platform, print the device before any
    work, refuse anything else (exit 3)."""
    from rayfed_tpu.utils import enable_compilation_cache

    asked = os.environ["JAX_PLATFORMS"]
    cache_dir = enable_compilation_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chipbench[{ctx.party}]: asked for platform {asked!r}, jax "
              f"found none: {e}", flush=True)
        sys.exit(3)
    ctx.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    want = int(ctx.me.get("chips", 1)) if ctx.is_chip else 1
    ctx.say("device", party=ctx.party, role=ctx.me["role"],
            compile_cache=cache_dir, **ctx.device)
    if ctx.device["platform"] != asked or ctx.device["count"] != want:
        print(f"chipbench[{ctx.party}]: the launcher asked for {want} x "
              f"{asked!r}, jax came up on {ctx.device}", flush=True)
        sys.exit(3)

    def on_duration(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            ctx.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def fed_init(ctx):
    import rayfed_tpu as fed
    from rayfed_tpu.proxy.tcp import sockio

    if sockio._fastwire is None:
        raise RuntimeError("the launcher built _fastwire but the transport "
                           "fell back to its Python engine")
    n = ctx.device["count"]
    model_par = 2 if n % 2 == 0 else 1
    config = {
        "cross_silo_comm": {
            # A tree at published widths is one multi-GB message; the
            # defaults (500 MB, 60 s) are deployment settings (PR 21).
            "messages_max_size_in_bytes": 32 << 30,
            "timeout_in_ms": 600_000,
            "retry_policy": {"max_attempts": 120, "initial_backoff_ms": 250,
                             "max_backoff_ms": 1000},
        },
        "party_mesh": {"mesh_shape": [n // model_par, model_par],
                       "axis_names": ["data", "model"],
                       "platform": os.environ["JAX_PLATFORMS"]},
    }
    fed.init(addresses=ctx.spec["addresses"], party=ctx.party,
             transport="tpu", logging_level="warning", config=config)
    ctx.fed = fed


def write_result(ctx, result):
    path = os.path.join(ctx.run_dir, f"{ctx.party}.result.json")
    with open(path + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(path + ".tmp", path)


def shutdown(ctx):
    """fed.shutdown() under a deadline. The result is already on disk: a
    fault here is logged with its cause and ends this process; it cannot
    change the numbers."""
    done = threading.Event()
    fault = []

    def stop():
        try:
            ctx.fed.shutdown()
        except BaseException as e:  # noqa: BLE001 - logged, not hidden
            fault.append(repr(e))
        done.set()

    t = threading.Thread(target=stop, name="chipbench-shutdown", daemon=True)
    t0 = time.time()
    t.start()
    if not done.wait(SHUTDOWN_DEADLINE_S):
        ctx.say("SHUTDOWN-FAULT", cause=f"fed.shutdown() still running "
                f"after {SHUTDOWN_DEADLINE_S:.0f}s; leaving it")
    elif fault:
        ctx.say("SHUTDOWN-FAULT", cause=fault[0])
    else:
        ctx.say("shutdown", seconds=round(time.time() - t0, 2))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--spec", required=True)
    p.add_argument("--party", required=True)
    a = p.parse_args()
    with open(a.spec) as f:
        spec = json.load(f)
    sys.path.insert(0, spec["root"])
    sys.path.insert(0, os.path.dirname(HERE))
    os.sched_setaffinity(0, spec["cores"][a.party])
    ctx = Ctx(spec, a.party)
    ctx.setup_parts["launcher"] = round(ctx._mark - spec["t0"], 3)
    come_up(ctx)
    ctx.part("import_and_device")
    fed_init(ctx)
    ctx.part("fed_init")
    result = load_kind(ctx.mix["kind"]).run(ctx)
    result.setdefault("device", {}).update(ctx.device)
    write_result(ctx, result)
    ctx.say("result written")
    shutdown(ctx)
    sys.stdout.flush()
    # Daemon threads of the transport must not keep a finished party.
    os._exit(0)


if __name__ == "__main__":
    main()
