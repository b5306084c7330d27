"""kind ``fedround``: FedAvg rounds over the normal path.

Every round, as in ``examples/federated_transformer.py``:
``Trainer.party(p).train.remote(global)`` for K local AdamW steps on every
party, ``fed_aggregate(locals, op="mean")``, the aggregate handed to the
next round's ``train``. A chip party trains with ``make_fed_train_step``
on its party mesh; a declared CPU party is a cross-silo peer as the chip
party sees it: its ``train`` takes the aggregate (so it is pushed there
every round) and returns a seeded full-width tree, made once in set-up, by
a new task each round, so that it crosses the wire and is placed on the
chip party's mesh every round.

A round runs from the first local step's dispatch to the aggregate being
resident on the lead's mesh. The window is the whole rounds that end
inside ``--seconds``; whether another round starts is the lead's decision,
taken by a task there and broadcast, so that every driver lays out the
same DAG.

``correct`` (contract: "How correct is decided", training): the warm-up
round is the window's own ``train`` call on the window's own object; its
first three steps are followed by the plain reference after the window
has closed and the program's state is freed: each step's loss, the first
gradient's norm as the optimizer got it (from AdamW's first moment after
one step), the norm of the parameters' change after three steps, both by
the worst leaf. Besides: the last round's aggregate against a NumPy mean,
where the arrival lives, and compilations inside the window (none).

A traced run (``--trace 1``) also turns the program's own recording on at
the lead for the window (``common.ProgramRecord``: ``tracing.enable()``
before it, ``tracing.disable()`` after) and passes what it recorded on as
``facts["program"]``: the phases' durations over the window's rounds
(``fed:wire:encode``, ``:recv``, ``:deserialize``, ``:place``,
``fed:agg:reduce``), the wire's spans of 1 MiB and more, and how many
rounds they cover. An untraced run executes nothing of it.
"""

from __future__ import annotations

import functools
import gc
import os
import time

from chipbench import common, compare, seeded

_LOCAL = {}          # this process's actors and clocks, for its own driver
PROBE_STEPS = 3

# Limits of the comparison; readings they were set from: PERF.md section 2.
LIMITS = {
    # |loss - reference loss| per step. Held against a part of the batch
    # left out, which moves the loss at seeded weights by half the
    # difference between two rows (about 5e-3): three times the largest of
    # 24 sound seeds on the chip (5.6e-4). The fp8 control reads 1.7e-3 up.
    "loss_abs": 1.7e-3,
    # worst-leaf gap of the first gradient's norm. 24 sound seeds on the
    # chip read 3.8e-4..1.53e-3; the fp8 control's smallest of 3 seeds is
    # 4.0e-3. This is the number the control must fail.
    "grad_norm_gap": 2.5e-3,
    # worst-leaf gap of the three-step change's norm. AdamW's first steps
    # are lr * sign(g): precision hardly moves it. Held against a step
    # that returns its state unchanged (gap 1.0): three times the largest
    # of 24 sound seeds (1.8e-4). The fp8 control reads 6.8e-4 up.
    "change_norm_gap": 5e-4,
    # aggregate vs NumPy (a+b)/2 in f32: one add and one division by two.
    "aggregate_rel": 1e-6,
}


@functools.lru_cache(maxsize=None)
def _change_norms_of(dims):
    """Leaf norms of a canonical tree less the seeded start, fused."""
    return compare.change_norms_fn(
        lambda key: seeded.canonical_weights(key, dims))


def _program_cfg(model):
    from rayfed_tpu.models import transformer as tfm

    v, d, h, _, f, n = seeded.dims_of(model)
    return tfm.TransformerConfig(
        vocab=v, d_model=d, n_heads=h, n_layers=n, d_ff=f,
        rope_theta=float(model["rope_theta"]))


def _define(ctx):
    import jax
    import numpy as np

    fed, mix, model = ctx.fed, ctx.mix, ctx.model
    dims = seeded.dims_of(model)
    K, B, S = mix["local_steps"], mix["batch"], mix["seq"]
    opt = mix["optimizer"]

    @fed.remote
    class Trainer:
        def __init__(self, index):
            from rayfed_tpu.mesh import get_party_mesh
            from rayfed_tpu.parallel import sharding as shd
            from rayfed_tpu.parallel.train import (make_fed_train_step,
                                                   make_optimizer)

            self.index, self.steps_done = index, 0
            self.mesh = get_party_mesh()
            # donate=False: train() hands the trained tree to the local
            # aggregate by reference (examples/federated_transformer.py).
            _, self.step = make_fed_train_step(
                _program_cfg(model), self.mesh, party_axis=None,
                lr=opt["lr"], remat=mix["remat"], attn=mix["attn"],
                donate=False)
            # Every party starts from the same global model; data differ.
            self.params = shd.shard_params(
                self.mesh, seeded.make_program_tree(seeded.key_of(ctx.seed),
                                                    dims))
            self.opt_state = jax.jit(make_optimizer(opt["lr"]).init)(
                self.params)
            self.train_s, self.probe = [], {}
            _LOCAL["trainer"] = self

        def train(self, global_params, probe):
            if global_params is not None:
                self.params = jax.tree_util.tree_map(
                    lambda old, new: jax.device_put(new, old.sharding),
                    self.params, global_params)
            losses = []
            t0 = time.perf_counter()
            with common.annotate("local_steps"):
                for i in range(K):
                    inputs, targets = seeded.make_batch(
                        seeded.batch_key(ctx.seed, self.index,
                                         self.steps_done), B, S, dims[0])
                    params, opt_state, loss = self.step(
                        self.params, self.opt_state, inputs, targets)
                    if not ctx.inject("broken-step"):
                        self.params, self.opt_state = params, opt_state
                    del params, opt_state
                    # One step at a time, as the repo's own trainers do
                    # (float(loss) per step): with the state not donated,
                    # dispatch that runs ahead holds three generations of
                    # it, nine trees, and the allocator decides the rest.
                    loss.block_until_ready()
                    self.steps_done += 1
                    if probe and i < PROBE_STEPS:
                        losses.append(loss)
                        if i == 0:
                            self.probe["grad_norms"] = self._grad_norms()
                        if i == PROBE_STEPS - 1:
                            self.probe["change_norms"] = self._change_norms()
                jax.block_until_ready(self.params)
            self.train_s.append(time.perf_counter() - t0)
            if probe:
                self.probe["losses"] = [float(x) for x in losses]
            return self.params

        def _grad_norms(self):
            # AdamW's first moment after one step is (1 - b1) * g.
            mu = seeded.from_program_tree(self.opt_state[0].mu, dims)
            norms = compare.to_host(compare.leaf_norms(mu))
            return {k: [x / (1.0 - opt["b1"]) for x in v]
                    for k, v in norms.items()}

        def _change_norms(self):
            return compare.to_host(_change_norms_of(dims)(
                seeded.from_program_tree(self.params, dims),
                seeded.key_of(ctx.seed)))

        def free(self):
            self.params = self.opt_state = None
            gc.collect()
            return True

    @fed.remote
    class Peer:
        def __init__(self, index):
            # The CPU peer's contribution: a full-width f32 tree made with
            # NumPy from the seed; nothing is jitted or run on a device.
            shapes = jax.eval_shape(
                lambda: seeded.make_program_tree(seeded.key_of(0), dims))
            rng = np.random.default_rng([ctx.seed, index])
            self.tree = jax.tree_util.tree_map(
                lambda s: mix["peer_tree_std"] * rng.standard_normal(
                    s.shape, dtype=np.float32), shapes)

        def train(self, global_params, probe):
            return self.tree

        def free(self):
            return True

    @fed.remote
    def landed(tree):
        # At the lead: the peer's tree is a resident jax.Array here.
        with common.annotate("wait_push"):
            jax.block_until_ready(tree)
        _LOCAL.setdefault("landed", []).append(time.perf_counter())
        return True

    @fed.remote
    def proceed():
        return bool(_LOCAL["go"]())

    @fed.remote
    def check_round(agg, *locals_):
        """At the lead: the aggregate against a NumPy mean of the
        contributions on the host, leaf by leaf, and where they live."""
        all_arrays, platforms, widest = common.placement(agg)
        arr_arrays, arr_platforms, arr_widest = common.placement(locals_[-1])
        worst, ref_max, nbytes = 0.0, 0.0, 0
        leaves = [jax.tree_util.tree_leaves(t) for t in (agg, *locals_)]
        for leaf, *parts in zip(*leaves):
            ref = np.asarray(parts[0], np.float32)
            for x in parts[1:]:
                ref = ref + np.asarray(x, np.float32)
            ref = ref / np.float32(len(parts))
            got = np.asarray(leaf)
            worst = max(worst, float(np.abs(got - ref).max()))
            ref_max = max(ref_max, float(np.abs(ref).max()))
            nbytes += got.nbytes
        return {"max_abs_err": worst, "ref_abs_max": ref_max,
                "update_bytes": nbytes,
                "aggregate_on": [all_arrays, platforms, widest],
                "arrival_on": [arr_arrays, arr_platforms, arr_widest]}

    @fed.remote
    def finish(payload):
        return payload

    return Trainer, Peer, landed, proceed, check_round, finish


def _reference_readings(ctx, quant=None):
    """The plain reference over the probe steps, after the program's
    state is freed. Returns (losses, grad norms, change norms, seconds)."""
    import importlib

    ref = importlib.import_module("chipbench.references."
                                  + ctx.spec["reference"])
    mix, model = ctx.mix, ctx.model
    dims = seeded.dims_of(model)
    t0 = time.perf_counter()
    batches = [seeded.make_batch(seeded.batch_key(ctx.seed, 0, i),
                                 mix["batch"], mix["seq"], dims[0])
               for i in range(PROBE_STEPS)]
    key = seeded.key_of(ctx.seed)
    out = ref.train_readings(
        lambda: seeded.make_canonical(key, dims),
        lambda w: _change_norms_of(dims)(w, key), batches, dims[2], float(model["rope_theta"]),
        float(model["rms_norm_eps"]), mix["optimizer"], quant)
    return (*out, time.perf_counter() - t0)


def _limits(ctx):
    """The limits above; the tiny rehearsal preset, where a leaf is a few
    thousand numbers and rounding does not average out, states its own."""
    assert ctx.rehearse or "limits" not in ctx.mix, "limits are not data"
    return dict(LIMITS, **(ctx.mix.get("limits", {}) if ctx.rehearse else {}))


def _train_checks(probe, ref, LIMITS, label=""):
    losses, grad_norms, change_norms, _ = ref
    loss_gap = max(abs(a - b) for a, b in zip(probe["losses"], losses))
    grad_gap, grad_leaf = compare.worst_leaf_gap(probe["grad_norms"],
                                                 grad_norms)
    chg_gap, chg_leaf = compare.worst_leaf_gap(probe["change_norms"],
                                               change_norms)
    return [
        common.check(label + "loss_abs_gap.max3", loss_gap,
                     LIMITS["loss_abs"],
                     f"program {probe['losses']} vs reference {losses}"),
        common.check(label + "grad_norm_gap.worst_leaf", grad_gap,
                     LIMITS["grad_norm_gap"], f"at {grad_leaf}"),
        common.check(label + "change_norm_gap.worst_leaf", chg_gap,
                     LIMITS["change_norm_gap"], f"at {chg_leaf}"),
    ]


def _step_gaps_note(reduced):
    """What the booking rule cannot tell (``trace_reduce.py``): the idle
    time between two train steps, all of it inside ``chipbench:local_steps``,
    by the name it was booked to: the pieces that lie inside a wire span of
    another thread beside those that lie in none, so that the next issue
    sees whether the wire slows the steps it overlaps."""
    inside = reduced["idle_under"].get("chipbench:local_steps", {})
    return "idle between train steps (inside chipbench:local_steps), " \
        "by the span booked: " + (", ".join(
            f"{name} {runs} x {1e3 * s / runs:.3f} ms"
            for name, (runs, s) in sorted(inside.items())) or "none")


def run(ctx):
    import jax

    from rayfed_tpu.federated import fed_aggregate

    fed, mix = ctx.fed, ctx.mix
    K, B, S = mix["local_steps"], mix["batch"], mix["seq"]
    dims = seeded.dims_of(ctx.model)
    Trainer, Peer, landed, proceed, check_round, finish = _define(ctx)
    lead, parties = ctx.lead, ctx.parties
    workers = {}
    for p in parties:
        cls = Trainer if p in ctx.chip_parties else Peer
        workers[p] = cls.party(p).remote(parties.index(p))
    peers = [p for p in parties if p != lead]

    held = {}     # the last round's contributions, for the check after

    def one_round(global_params, probe=False):
        # Nothing of the round before may outlive its aggregate: at these
        # sizes two forgotten trees are the difference to running out.
        held.clear()
        gc.collect()
        t0 = time.perf_counter()
        locals_ = {p: workers[p].train.remote(global_params, probe)
                   for p in parties}
        held.update(locals_)
        for p in peers:
            landed.party(lead).remote(locals_[p])
        agg = fed_aggregate(locals_, op="mean")
        with common.annotate("wait_aggregate"):
            value = fed.get(agg)
            if ctx.is_lead:
                jax.block_until_ready(value)
        t1 = time.perf_counter()
        del value, locals_
        return agg, t0, t1

    # ---- set-up: state from the seed, then two warm-up rounds: the first
    # is the probe (from the seeded weights), the second is the first to
    # take an aggregate in, as every round of the window does.
    agg, t0, t1 = one_round(None, probe=True)
    ctx.part("weights_compile_first_round")
    agg, t0, t1 = one_round(agg)
    ctx.part("warmup_round")
    trainer = _LOCAL.get("trainer")
    if ctx.is_lead:
        step = trainer.step
        ctx.say("warm-up round", seconds=round(t1 - t0, 3),
                train_s=round(trainer.train_s[-1], 3),
                step_programs=step._cache_size(),
                bytes_in_use=common.memory_peak_bytes())
    est = [t1 - t0]
    window_t0 = time.perf_counter()
    deadline = window_t0 + ctx.seconds
    _LOCAL["go"] = lambda: time.perf_counter() + 0.9 * est[0] < deadline
    compiles_before = ctx.compiles
    n_train_before = len(trainer.train_s) if trainer else 0
    n_landed_before = len(_LOCAL.get("landed", []))
    setup_s = time.time() - ctx.spec["t0"]
    trace = common.DeviceTrace(ctx) if (ctx.trace and ctx.is_lead) else None
    record = common.ProgramRecord(ctx.trace and ctx.is_lead)
    record.open()

    # ---- the window -----------------------------------------------------
    rounds, overran = [], 0
    n = 0
    while fed.get(proceed.party(lead).remote()):
        if trace and n == 1:
            trace.start()
        agg, t0, t1 = one_round(agg)
        if trace and n == 1:
            trace.stop()
        n += 1
        if ctx.inject(f"exit:{ctx.party}"):
            ctx.say("injected exit", code=3)
            os._exit(3)
        if t1 <= deadline:
            rounds.append((t0, t1))
            est[0] = min(t1 - t0 for t0, t1 in rounds)
        else:
            overran += 1
    window_s = time.perf_counter() - window_t0
    record.close()
    compiles_in_window = ctx.compiles - compiles_before
    peak = common.memory_peak_bytes()

    # ---- after the window: checks --------------------------------------
    round_check = fed.get(check_round.party(lead).remote(
        agg, *[held[p] for p in parties]))
    fed.get([workers[p].free.remote() for p in parties])
    del agg
    held.clear()
    gc.collect()
    if not ctx.is_lead:
        fed.get(finish.party(lead).remote(None))
        return {"correct": True, "attempted": 0, "failed": 0,
                "end_to_end": {}, "facts": {}}

    if len(rounds) < mix["min_rounds"]:
        raise RuntimeError(
            f"only {len(rounds)} whole round(s) ended inside the "
            f"{ctx.seconds:.0f}s window ({overran} overran): a failed run")
    train_s = trainer.train_s[n_train_before:n_train_before + len(rounds)]
    land = _LOCAL.get("landed", [])[n_landed_before:]
    round_s = [t1 - t0 for t0, t1 in rounds]
    tokens_per_round = K * B * S * len(ctx.chip_parties)
    rate = tokens_per_round * len(rounds) / sum(round_s)
    ctx.say("window", rounds=len(rounds), overran=overran,
            round_s=[round(x, 3) for x in round_s[:8]],
            train_s=[round(x, 3) for x in train_s[:8]],
            compiles_in_window=compiles_in_window,
            update_bytes=round_check["update_bytes"])

    checks, notes = [], []
    tol = LIMITS["aggregate_rel"] * max(1.0, round_check["ref_abs_max"])
    checks.append(common.check(
        "aggregate_vs_numpy_mean.max_abs", round_check["max_abs_err"], tol,
        "last round of the window, every leaf"))
    want = [True, [ctx.device["platform"]], ctx.device["count"]]
    checks.append(common.check(
        "arrival_off_mesh", 0 if round_check["arrival_on"] == want
        and round_check["aggregate_on"] == want else 1, 0,
        f"arrival {round_check['arrival_on']} aggregate "
        f"{round_check['aggregate_on']} wanted {want}", exact=True))
    checks.append(common.check(
        "compiles_in_window", compiles_in_window, 0,
        "backend compilations between window start and end", exact=True))
    ref = _reference_readings(ctx)
    checks += _train_checks(trainer.probe, ref, _limits(ctx))
    notes.append(f"reference followed {PROBE_STEPS} steps in "
                 f"{ref[3]:.1f}s (outside setup_s and the window)")
    if ctx.spec.get("control"):
        ctl = _reference_readings(ctx, ctx.spec["control"])
        probe_ctl = {"losses": ctl[0], "grad_norms": ctl[1],
                     "change_norms": ctl[2]}
        for c in _train_checks(probe_ctl, ref, _limits(ctx),
                               f"control[{ctx.spec['control']}]."):
            notes.append("control " + repr(c))
    reduced = trace.reduce(kernels=("flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv")) if trace else None
    device = {"memory_peak_bytes": peak}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        notes.append(_step_gaps_note(reduced))
    facts = {
        "kind": "fedround", "round_s": round_s, "train_s": train_s,
        "push_place_s": [t - r[0] for t, r in zip(land, rounds)],
        "local_steps": K, "batch": B, "seq": S,
        "chips": ctx.device["count"] * len(ctx.chip_parties),
        "device_kind": ctx.device["kind"], "model": ctx.model,
        "update_bytes": round_check["update_bytes"],
        "trace": reduced, "traced_rounds": 1 if reduced else 0,
        "program": record.facts(rounds=n),
        "chip_parties": len(ctx.chip_parties),
        # flash's call shape on one chip: the batch rows, and the heads of
        # the party mesh's "model" axis (2 wide where the chips are even).
        "rows_per_chip": B, "heads": dims[2] // (
            2 if ctx.device["count"] % 2 == 0 else 1),
        "head_dim": dims[3],
    }
    result = {
        "correct": all(c["ok"] for c in checks),
        "attempted": len(rounds) + overran, "failed": 0,
        "end_to_end": {"round_tokens_per_s": rate, "setup_s": setup_s},
        "facts": facts, "checks": checks, "notes": notes,
        "setup_parts": ctx.setup_parts, "device": device,
        "breakdown": common.breakdown_of(reduced),
    }
    fed.get(finish.party(lead).remote(None))
    return result
