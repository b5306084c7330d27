"""kind ``closed_loop_diffusion``: ``closed_loop_moe``'s run for an
architecture that generates by diffusion over blocks (SDAR-MoE). The
closed loop (``closed_loop``'s ``plan`` and ``drive``), the adapter by
the configuration's ``reference``, the engine's start, the counters around
the traced part of the window and the profile's whole programs are that
kind's, by import. What it cannot carry, and why this file exists: its
comparison replays ONE causal forward over a prompt and its served
tokens, which is not this model (a served token was chosen with part of
its own block still masked, and with the block's later positions in
view); its warm-up wave sorts prompts into programs by their whole
length, where this model prefills a prompt's whole blocks only; its
``STATS_DELTAS`` is a closed tuple; and no file the benchmark has may be
edited by the PR that adds a cell. So this kind sets those names in
``closed_loop_moe``'s (and ``serving``'s) module for the life of its
process (one process runs one kind), as ``closed_loop_mla`` does, and
calls its ``run``. A ``benchmark`` issue should move the counter list,
the comparison and the controls behind the adapter (ROADMAP Queue B).

**How ``correct`` is decided** (``reference_gaps``, ``gap_checks``). Every
response carries, per served token, the denoising step of its block at
which it was unmasked (``unmask_steps``). For ``limits.sample_requests``
finished greedy requests (the window's longest among them) and, in each,
``limits.sample_blocks`` whole blocks (the first generated, the last
whole one, seeded picks between), and for the ``limits.short_requests``
finished requests of shortest prompts, in each its first
``limits.short_blocks`` generated blocks (contexts of 32 to some 50
positions, where the block's own keys are a tenth of what a query sees:
behind 500 cached keys a wrong mask among the block's four moves the
logits no further than bfloat16 does, PERF.md section 6), the plain
reference **replays the engine's own trajectory**: for each step of the block, the block as the
engine had it (what was unmasked before that step, the mask id elsewhere)
over the clean served context, one ``block_logits``. Two numbers an
unmasking; the first is held as widest and as mean, the second as mean,
each to a limit of the configuration's file (the second's widest is
recorded and not judged: over 26 sound runs on the chip it reads up to
0.133, the fp8 control 0.15-0.20 and ``--inject broken-unmask`` 0.22 to
0.30, too close for a limit with room on both sides, where the means
read up to 0.0051, 0.012-0.018 and 0.027-0.033):

* ``served_logit_gap``: the reference's best logit at the position (the
  mask id left out) less the reference's logit of the served token;
* ``unmask_logconf_gap``: how far the reference's log-confidence at the
  position the engine unmasked lies below the reference's n-th largest
  among the positions masked at that step, n the number the engine
  unmasked there (0 where the reference would have unmasked it too).

Under ``--control fp8`` the same two numbers are read for what the
reference in float8 would have done at each replayed step (its n
positions, its tokens) and held to the same limits as checks of their
own: a control that a limit catches reads ``correct`` false.

Controls of the mechanisms, each of which must read ``correct`` false:

* ``--inject broken-commit``: the K/V kept for a block are those of its
  denoising forwards (the last one's stay), mask tokens among their
  inputs; the forward of the clean block writes nothing;
* ``--inject broken-blockmask``: a causal mask inside the block in the
  decode step (a position no longer sees the block's later ones);
* ``--inject broken-unmask``: the rule unmasks the first masked position
  of a block, not the most confident (what ``unmask_logconf_gap.mean`` is
  held against);
* ``--inject broken-route``: PR 31's name. Its mistake (the ``k`` taken
  among the held experts only) changes nothing where every expert is
  held, so here the layer forgets the scoring it was told: sigmoid
  weights over the same eight experts where softmax ones are due;
* ``--inject broken-token`` is ``closed_loop_arch``'s
  (``InferenceServer._sample``, called once per emitted token).
"""

from __future__ import annotations

import numpy as np

from chipbench import common, serving
from chipbench.kinds import closed_loop, closed_loop_moe as moe_kind

STATS_DELTAS = moe_kind.STATS_DELTAS + (
    "decode_keys_attended", "rows_wasted", "diffusion_row_forwards",
    "diffusion_commit_forwards", "diffusion_tokens_unmasked",
    "diffusion_positions_dropped")
SAMPLE_BLOCKS = 4
SHORT_BLOCKS = 3
# The window's records as ``drive`` returned them: ``closed_loop_moe.run``
# hands ``reference_gaps`` its sample only, and the requests of shortest
# prompts are picked from all that finished.
_WINDOW = {"records": []}

plan = closed_loop.plan


class _Submits:
    """The engine's ``submit``, with the futures kept in the order of the
    calls (``closed_loop.drive`` appends a record before each)."""

    def __init__(self, srv):
        self.srv, self.futures = srv, []

    def submit(self, *args, **kw):
        self.futures.append(None)
        self.futures[-1] = self.srv.submit(*args, **kw)
        return self.futures[-1]


def drive(ctx, srv, plan, Sink):
    """``closed_loop.drive``, and each finished request's record told at
    which step of its block every served token was unmasked."""
    calls = _Submits(srv)
    win = closed_loop.drive(ctx, calls, plan, Sink)
    for rec, fut in zip(win["records"], calls.futures, strict=True):
        if rec["tokens"] is not None:
            rec["unmask_steps"] = fut.result()["unmask_steps"]
    _WINDOW["records"] = win["records"]
    return win


def warm_up(ctx, srv, vocab, lengths):
    """``serving.warm_up`` for an engine that prefills a prompt's whole
    blocks: one wave that hits exactly the prompt buckets and chunk
    shapes of the mix's own lengths as the ENGINE sorts them
    (``InferenceServer._prefill_len``), and the decode step through a
    commit."""
    block = srv._block.length
    chunk = srv.scfg.prefill_chunk
    classes = {}
    for n in lengths:
        whole = n - n % block
        if whole == 0:
            key = ("none", 0)
        elif whole <= chunk:
            key = ("prefill", next(b for b in srv._buckets if b >= whole))
        else:
            first = whole % chunk or chunk
            key = ("chunk", next(b for b in srv._chunk_buckets if b >= first))
        classes.setdefault(key, n)
    rng = np.random.default_rng(12345)
    futs = [srv.submit(rng.integers(1, vocab, size=n).tolist(),
                       max_new_tokens=2 * block, temperature=0.0,
                       stream=serving.Sink())
            for n in classes.values()]
    futs.append(srv.submit(rng.integers(1, vocab, size=min(lengths)).tolist(),
                           max_new_tokens=2 * block, temperature=0.8, seed=1,
                           stream=serving.Sink()))
    for f in futs:
        f.result(timeout=1000)
    return sorted(classes)


# ---------------------------------------------------------------------------
# Controls of the mechanisms
# ---------------------------------------------------------------------------


def break_route():
    """``--inject broken-route``: before any program is traced, the routed
    layer drops the scoring it is told (sigmoid weights for softmax)."""
    from rayfed_tpu.models import moe

    routed = moe.routed_experts

    def unscored(h, layer, held, k, live=None, scale=1.0, scoring=None):
        return routed(h, layer, held, k, live, scale)

    moe.routed_experts = unscored


def break_commit():
    """``--inject broken-commit``: the decode step's one write lands the
    rows that do NOT commit (every denoising forward's K/V, computed from
    a block that still holds mask tokens, the last one's staying) and
    skips those that do."""
    from rayfed_tpu.models import decode

    write = decode.paged_block_write
    decode.paged_block_write = (
        lambda pk, pv, k, v, positions, tables, commit: write(
            pk, pv, k, v, positions, tables, ~commit))


def break_blockmask():
    """``--inject broken-blockmask``: the decode step's read masks the
    block's own keys causally. The program's read has no such mask to
    set, so each query of a row goes through it alone, with the block's
    keys up to its own."""
    import jax.numpy as jnp

    from rayfed_tpu.models import decode

    read = decode.paged_block_attention

    def causal(pk, pv, positions, tables):
        attend = read(pk, pv, positions, tables)
        return lambda q, kb, vb, base: jnp.concatenate(
            [attend(q[:, i:i + 1], kb[:, :i + 1], vb[:, :i + 1], base)
             for i in range(q.shape[1])], axis=1)

    decode.paged_block_attention = causal


def break_unmask():
    """``--inject broken-unmask``: the rule ranks a block's masked
    positions by their place and not by their confidence, so a step
    unmasks the FIRST masked position (left to right, as a decoder of one
    token a step would) whatever the model is surest of. The candidates
    themselves are the model's: ``served_logit_gap`` reads as in a sound
    run, and ``unmask_logconf_gap.mean`` is the number this must fail."""
    import jax.numpy as jnp

    from rayfed_tpu.serving import sampling

    choose = sampling.choose_with_confidence

    def by_place(logits, temperature, seed, index):
        ids, conf = choose(logits, temperature, seed, index)
        return ids, -jnp.arange(conf.shape[0], dtype=conf.dtype)

    sampling.choose_with_confidence = by_place


# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------


def _picked_blocks(rng, starts, n):
    """``n`` of the block starts: the first, the last, seeded picks of
    those between."""
    if len(starts) <= n:
        return list(starts)
    between = rng.permutation(len(starts) - 2)[:n - 2] + 1
    return sorted({starts[0], starts[-1], *(starts[i] for i in between)})


def _step_gaps(logits, final, step_of, k, hp, chosen=None):
    """The two numbers of every unmasking of step ``k``: ``logits`` (B, V)
    the reference's for the block as it was before the step, ``final`` the
    served block, ``step_of`` the step each position was unmasked at (-1:
    the prompt's). ``chosen`` (positions, tokens) replaces what the engine
    unmasked (the control's choice). Returns ((served gaps), (confidence
    gaps), the reference's lead of its best over its second there)."""
    logits = np.array(logits, np.float32)
    logits[:, hp.mask_id] = -np.inf
    best = logits.max(-1)
    logconf = -np.log(np.exp(logits - best[:, None]).sum(-1))
    masked = [j for j, s in enumerate(step_of) if s >= k]
    if chosen is None:
        at = [j for j in masked if step_of[j] == k]
        tokens = [final[j] for j in at]
    else:
        at, tokens = chosen
    bar = sorted((logconf[j] for j in masked), reverse=True)[len(at) - 1]
    served = [float(best[j] - logits[j, t]) for j, t in zip(at, tokens)]
    conf = [float(max(0.0, bar - logconf[j])) for j in at]
    lead = [float(best[j] - np.partition(logits[j], -2)[-2]) for j in at]
    return served, conf, lead


def _lower_choice(low, step_of, k, n, hp):
    """What the reference in a lower precision would unmask at step
    ``k``: its ``n`` most confident masked positions (the first at a tie)
    and its tokens there."""
    low = np.array(low, np.float32)
    low[:, hp.mask_id] = -np.inf
    conf = low.max(-1) - np.log(np.exp(
        low - low.max(-1, keepdims=True)).sum(-1))
    masked = [j for j, s in enumerate(step_of) if s >= k]
    at = sorted(sorted(masked, key=lambda j: -conf[j])[:n])
    return at, [int(low[j].argmax()) for j in at]


def _shortest(sample, n):
    """The ``n`` finished greedy requests of the window with the shortest
    prompts (the earliest first at a tie) that ``sample`` does not hold."""
    rest = [r for r in _WINDOW["records"]
            if r.get("tokens") is not None and r["temperature"] <= 0
            and r["in_window"] and not any(r is s for s in sample)]
    return sorted(rest, key=lambda r: len(r["prompt"]))[:n]


def reference_gaps(ctx, adapter, sample, quant=None):
    """Per followed request the widest and the mean of both numbers over
    the unmaskings of its picked blocks (module docstring): the sample's
    requests, then the window's requests of shortest prompts by their
    first blocks; with ``quant`` also of what the lower precision would
    have done at each replayed step."""
    block_logits, hp = adapter.reference_block_fn(
        ctx.seed, ctx.model, ctx.spec["precision"])
    limits = ctx.model.get("limits") or {}

    def knob(name, default):
        return int(ctx.mix.get(name, limits.get(name, default)))

    n_blocks = knob("sample_blocks", SAMPLE_BLOCKS)
    short_blocks = knob("short_blocks", SHORT_BLOCKS)
    short = _shortest(sample, knob("short_requests", 0)) if sample else []
    rng = np.random.default_rng([ctx.seed, 11])
    out = []
    for r, is_short in [(r, False) for r in sample] + [
            (r, True) for r in short]:
        prompt, plen = list(r["prompt"]), len(r["prompt"])
        seq = prompt + list(r["tokens"])
        steps = [-1] * plen + list(r["unmask_steps"])
        first = plen - plen % hp.block
        # Whole blocks only: what a cut last block held beyond the
        # request's length was dropped, and its trajectory with it.
        starts = [s for s in range(first, len(seq), hp.block)
                  if s + hp.block <= len(seq)]
        picked = (starts[:short_blocks] if is_short
                  else _picked_blocks(rng, starts, n_blocks))
        served, conf, lead, low_served, low_conf = [], [], [], [], []
        off = []
        for s in picked:
            final = seq[s:s + hp.block]
            step_of = steps[s:s + hp.block]
            for k in sorted({x for x in step_of if x >= 0}):
                block = [t if x < k else hp.mask_id
                         for t, x in zip(final, step_of)]
                logits = block_logits(seq[:s], block)
                a, b, c = _step_gaps(logits, final, step_of, k, hp)
                off += [(s + j, k, round(g, 4)) for j, g in zip(
                    (j for j, x in enumerate(step_of) if x == k), a) if g > 0]
                served, conf, lead = served + a, conf + b, lead + c
                if quant:
                    chosen = _lower_choice(
                        block_logits(seq[:s], block, quant), step_of, k,
                        len(a), hp)
                    a, b, _ = _step_gaps(logits, final, step_of, k, hp,
                                         chosen)
                    low_served, low_conf = low_served + a, low_conf + b
        n = len(served)
        row = {"prompt_len": plen, "n": n,
               "gap": max(served, default=0.0),
               "mean": float(np.mean(served)) if n else 0.0,
               "conf_gap": max(conf, default=0.0),
               "conf_mean": float(np.mean(conf)) if n else 0.0,
               "agree": float(np.mean(np.asarray(served) == 0)) if n else 1.0,
               "lead": float(np.median(lead)) if n else 0.0, "off": off}
        if quant:
            row.update(
                control_gap=max(low_served, default=0.0),
                control_mean=float(np.mean(low_served)) if n else 0.0,
                control_conf_gap=max(low_conf, default=0.0),
                control_conf_mean=float(np.mean(low_conf)) if n else 0.0,
                control_off=[])
        out.append(row)
    return out


def gap_checks(rows, limits, prefix="", key="gap", mean="mean", why=""):
    """The three numbers held to the configuration's three limits: the
    widest and the mean of ``served_logit_gap``, and the mean of
    ``unmask_logconf_gap``, over the sample's unmaskings (``key`` and
    ``mean`` name the rows' served pair; the confidence pair is named
    after them). The WIDEST ``unmask_logconf_gap`` is recorded in that
    check's ``why`` and not judged: on the chip a rule that unmasks the
    wrong position reads only twice a sound run's (module docstring)."""
    n = sum(r["n"] for r in rows)
    conf_gap = key.replace("gap", "conf_gap")
    conf_mean = mean.replace("mean", "conf_mean")

    def pooled(name):
        return sum(r[name] * r["n"] for r in rows) / n if n else None

    widest_conf = max((r[conf_gap] for r in rows), default=None)
    return [
        common.check(f"{prefix}served_logit_gap.widest",
                     max((r[key] for r in rows), default=None),
                     limits["served_logit_gap"], why),
        common.check(f"{prefix}served_logit_gap.mean", pooled(mean),
                     limits["served_logit_gap_mean"], why),
        common.check(f"{prefix}unmask_logconf_gap.mean", pooled(conf_mean),
                     limits["unmask_logconf_gap_mean"],
                     f"{why}; the widest {widest_conf} (recorded, not "
                     f"judged)"),
    ]


def run(ctx):
    """``closed_loop_moe.run`` with this kind's counters, comparison,
    warm-up wave and controls."""
    limits = ctx.model.get("limits") or {}
    if "unmask_logconf_gap_mean" not in limits:
        raise SystemExit(
            "closed_loop_diffusion: the configuration states no "
            "limits.unmask_logconf_gap_mean; calibrate it beside "
            "limits.served_logit_gap")
    moe_kind.STATS_DELTAS = STATS_DELTAS
    moe_kind.break_route = break_route
    moe_kind.reference_gaps = reference_gaps
    moe_kind.gap_checks = gap_checks
    moe_kind.drive = drive
    serving.warm_up = warm_up
    if ctx.inject("broken-commit"):
        break_commit()
    if ctx.inject("broken-blockmask"):
        break_blockmask()
    if ctx.inject("broken-unmask"):
        break_unmask()
    return moe_kind.run(ctx)
