"""Plain reference: dots3-note (``model_type: dots3_note``; ``config.json``
of dots-studio/dots3-note-prev), a decoder whose layers are pre-norm, ``a =
x + Attn(N1(x))``, ``y = a + F(N2(a))``, with latent attention of two kinds
(``layer_types``), a headwise output gate on both, a leading dense layer
and routed experts beside a shared one. All ``N`` are RMSNorm ``N(x) = x /
sqrt(mean x^2 + eps) * g``.

    Attn(h), both kinds, with the kind's own sizes:
              cq = s_q Nq(h Wqa);  q = cq Wqb -> H heads of [qn | qr], qr rotated
              [ckv | kr] = h Wkva;  ckv = s_kv Nkv(ckv);  kr rotated (ONE
              positional key a token, shared by all heads)
              [kn | v] = ckv Wkvb -> H heads of (dn + dv)
              p = softmax_{s in A_t}((qn_t . kn_s + qr_t . kr_s) / sqrt(dn + dr))
              g = sigmoid(h Wg)  (one a head)
              concat_heads(g_j sum_s p v_s) Wo
              s_q = sqrt(d / q_rank), s_kv = sqrt(d / kv_rank)
              (apply_mla_qkv_lora_rescale; 1 without it)
    A_t, a sliding layer:  t - window < s <= t
    A_t, a full layer:     the min(index_topk, t + 1) positions s <= t of
              largest I[t, s], the lower position at a tie, where
              qI = cq WqI -> J heads of D;  kI = LN(h WkI) (D; LayerNorm
              with scale and bias);  the first dr dimensions of both rotated
              w = (h Ww) / sqrt(J) / sqrt(D)
              I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    F(h), a leading dense layer:   (silu(h Wg) * (h Wu)) Wd
    F(h), an expert layer:
              sigma = sigmoid(h Wr) over ALL experts
              T = the k largest of sigma + b   (b chooses, it does not weigh)
              w_e = scale * sigma_e / sum_T sigma
              sum_{e in T, e held} w_e E_e(h) + the shared expert
              E(h) = (silu(h Wg) * (h Wu)) Wd
    logits = N_f(x) W_head^T

Positions rotate by halves: dimension i of the first half of a rotated
part turns with dimension i of the second by ``position * theta ** (-2i /
dr)``, ``theta`` the layer kind's. ``held`` is the list of routed experts
whose weights were handed over (a chip's share of a layer divided over
chips): the weights ``w_e`` are normalised over all ``k`` chosen experts,
held or not, and what an absent expert would add is left out. With every
expert held this is the published layer. The towers and the extra
prediction layer of the published model are not here.

float32 ``jax.numpy`` at matmul precision "highest"; attention in the
plain (expanded) form: per-head keys and values made from the latent, no
cache, no kernels, no batching, no grouping: one sequence at a time, every
held expert run on every token and weighted (zero where the token did not
choose it). The index scores and each query's set are computed densely, a
block of queries at a time (``jax.lax.top_k`` of a block's masked scores,
scattered into a (queries, keys) mask); the sliding layers read the band of
keys a block of queries can see. It imports nothing of the program and
reads only the canonical weights of ``chipbench/seeded_dots3_note.py``:
``embed`` (V, d), ``ln_f`` (d), ``lm_head`` (V, d) and ``layers``, a list
with one dict a layer (a dense layer holds ``w_gate``, an expert layer
``router``; a full layer holds ``wi_q``).

The canonical tree arrives in the configuration's parameter type
(bfloat16) and is widened here one matrix (one expert, one head's slice, a
block of the vocabulary) at a time; attention works a head and a block of
query rows at a time and the feed-forward parts a block of rows at a time,
so that a 33,024-token sequence fits beside 8.2 GB of weights on a 16 GB
chip.

``quant`` runs the same mathematics in a lower precision, for the control
that must come out as not correct: "bf16" rounds every matmul operand to
bfloat16; "fp8" rounds it to float8_e4m3 under a per-tensor scale. The
router's and the indexer's products are matmuls like the others.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
HEAD_BLOCKS = 32
QUERY_BLOCK = 256
ROW_BLOCK = 2048
FULL, SLIDING = "full_attention", "sliding_attention"


class Sizes(NamedTuple):
    """One kind of latent attention."""

    heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    theta: float


class Hyper(NamedTuple):
    """The published keys the mathematics reads, and the share (hashable:
    a static argument of the jitted entry points)."""

    d_model: int
    layer_types: tuple
    full: Sizes
    sliding: Sizes
    window: int
    index_heads: int
    index_dim: int
    index_topk: int
    rescale: bool
    top_k: int
    routed_scale: float
    eps: float
    held: tuple             # global ids of the experts handed over
    # Parts left out, for the tests that show each one matters: "gate"
    # (no output gate), "rescale" (the latents not rescaled), "index"
    # (a full layer attends every causal key), "bias" (the experts chosen
    # by their scores alone), "shared" (no shared expert).
    without: tuple = ()


def hyper_of(model: dict, held, without=()) -> Hyper:
    """From a configuration's published keys and the experts held."""
    n = int(model["num_hidden_layers"])
    return Hyper(
        d_model=int(model["hidden_size"]),
        layer_types=tuple(model["layer_types"][:n]),
        full=Sizes(
            int(model["num_attention_heads"]), int(model["q_lora_rank"]),
            int(model["kv_lora_rank"]), int(model["qk_nope_head_dim"]),
            int(model["qk_rope_head_dim"]), int(model["v_head_dim"]),
            float(model["rope_theta"])),
        sliding=Sizes(
            int(model["swa_num_attention_heads"]),
            int(model["swa_q_lora_rank"]), int(model["swa_kv_lora_rank"]),
            int(model["swa_qk_nope_head_dim"]),
            int(model["swa_qk_rope_head_dim"]), int(model["swa_v_head_dim"]),
            float(model["swa_rope_theta"])),
        window=int(model["sliding_window_size"]),
        index_heads=int(model["index_n_heads"]),
        index_dim=int(model["index_head_dim"]),
        index_topk=int(model["index_topk"]),
        rescale=bool(model.get("apply_mla_qkv_lora_rescale", False)),
        top_k=int(model["num_experts_per_tok"]),
        routed_scale=float(model.get("routed_scaling_factor", 1.0)),
        eps=float(model["rms_norm_eps"]),
        held=tuple(int(e) for e in held),
        without=tuple(without),
    )


def _round_operand(x, quant):
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if quant == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown quant {quant!r}")


def mm(a, b, quant=None):
    return jnp.matmul(_round_operand(a, quant),
                      _round_operand(b.astype(F32), quant), precision=HI)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def rope_halves(x, positions, theta):
    """x (S, D): dimension i turns with dimension i + D/2 by position *
    theta ** (-2i / D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _column_block(w, i, width):
    """Columns ``i * width .. (i + 1) * width - 1`` of a matrix, sliced
    where it lies (one head's part of a projection)."""
    return jax.lax.dynamic_slice_in_dim(w, i * width, width, axis=1)


def _query_block(s: int) -> int:
    return QUERY_BLOCK if s % QUERY_BLOCK == 0 else s


def index_inputs(h, cq, lay, positions, hp: Hyper, quant):
    """The indexer's queries (S, J, D), keys (S, D) and weights (S, J)."""
    s = h.shape[0]
    dr, theta = hp.full.d_rope, hp.full.theta
    qi = mm(cq, lay["wi_q"], quant).reshape(s, hp.index_heads, hp.index_dim)
    qi = jnp.concatenate(
        [jax.vmap(lambda x: rope_halves(x, positions, theta), 1, 1)(
            qi[..., :dr]), qi[..., dr:]], -1)
    ki = layer_norm(mm(h, lay["wi_k"], quant), lay["i_norm"], lay["i_bias"],
                    hp.eps)
    ki = jnp.concatenate(
        [rope_halves(ki[:, :dr], positions, theta), ki[:, dr:]], -1)
    w = mm(h, lay["wi_w"], quant) * (hp.index_heads * hp.index_dim) ** -0.5
    return qi, ki, w


def index_scores(qi, ki, w, quant=None):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for the queries
    handed over (Q, J, D) against every key (S, D), a head at a time."""
    ki = _round_operand(ki, quant)

    def head(acc, j):
        q = _round_operand(qi[:, j], quant)
        return acc + w[:, j, None] * jax.nn.relu(
            jnp.matmul(q, ki.T, precision=HI)), None

    acc, _ = jax.lax.scan(head, jnp.zeros((qi.shape[0], ki.shape[0]), F32),
                          jnp.arange(qi.shape[1]))
    return acc


def selected(scores, q_pos, k_pos, k: int):
    """(Q, S) bool: per query the ``min(k, causal keys)`` positions of
    largest score among ``k_pos <= q_pos`` (``jax.lax.top_k`` puts the
    lower position first among equal scores)."""
    causal = k_pos[None, :] <= q_pos[:, None]
    top, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(k, scores.shape[1]))
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(top > -jnp.inf)


def selection(h, cq, lay, positions, hp: Hyper, quant):
    """A full layer's sets, (blocks, queries a block, S) bool."""
    s = h.shape[0]
    qb = _query_block(s)
    qi, ki, w = index_inputs(h, cq, lay, positions, hp, quant)

    def block(args):
        qi_b, w_b, q_pos = args
        return selected(index_scores(qi_b, ki, w_b, quant), q_pos,
                        positions, hp.index_topk)

    return jax.lax.map(block, (
        qi.reshape(s // qb, qb, *qi.shape[1:]),
        w.reshape(s // qb, qb, -1), positions.reshape(s // qb, qb)))


def latent_scales(sz: Sizes, hp: Hyper) -> tuple:
    """(s_q, s_kv): what multiplies the two latents after their norms."""
    if not hp.rescale or "rescale" in hp.without:
        return 1.0, 1.0
    return (hp.d_model / sz.q_rank) ** 0.5, (hp.d_model / sz.kv_rank) ** 0.5


def query_latent(h, lay, sz: Sizes, hp: Hyper, quant):
    """``cq = s_q Nq(h Wqa)``."""
    return latent_scales(sz, hp)[0] * rms_norm(
        mm(h, lay["wq_a"], quant), lay["q_norm"], hp.eps)


def attention(h, lay, positions, kind: str, hp: Hyper, quant):
    """Latent attention of a normed ``h`` (S, d), in the plain form: a
    head at a time, its keys and values expanded from the latent; a block
    of queries against the keys it may see."""
    s = h.shape[0]
    sz = hp.full if kind == FULL else hp.sliding
    cq = query_latent(h, lay, sz, hp, quant)
    ckr = mm(h, lay["wkv_a"], quant)
    ckv = latent_scales(sz, hp)[1] * rms_norm(
        ckr[:, :sz.kv_rank], lay["kv_norm"], hp.eps)
    kr = rope_halves(ckr[:, sz.kv_rank:], positions, sz.theta)
    d_qk = sz.d_nope + sz.d_rope
    qb = _query_block(s)
    n_blocks = s // qb
    allowed = None
    # Keys a block of queries may see: all of them, or the band that ends
    # with the block and reaches a window back from its first query.
    band = s
    if kind == FULL:
        if "index" not in hp.without:
            allowed = selection(h, cq, lay, positions, hp, quant)
    else:
        band = min(s, qb * (1 + -(-(hp.window - 1) // qb)))

    def one_head(i):
        q = mm(cq, _column_block(lay["wq_b"], i, d_qk), quant)
        q = jnp.concatenate(
            [q[:, :sz.d_nope],
             rope_halves(q[:, sz.d_nope:], positions, sz.theta)], -1)
        kv = mm(ckv, _column_block(lay["wkv_b"], i, sz.d_nope + sz.d_v),
                quant)
        k = jnp.concatenate([kv[:, :sz.d_nope], kr], -1)
        v = _round_operand(kv[:, sz.d_nope:], quant)
        q, k = _round_operand(q, quant), _round_operand(k, quant)

        def rows(b):
            q_rows = jax.lax.dynamic_slice_in_dim(q, b * qb, qb)
            q_pos = jax.lax.dynamic_slice_in_dim(positions, b * qb, qb)
            start = jnp.clip((b + 1) * qb - band, 0, s - band)
            kb = jax.lax.dynamic_slice_in_dim(k, start, band)
            vb = jax.lax.dynamic_slice_in_dim(v, start, band)
            k_pos = start + jnp.arange(band)
            seen = k_pos[None, :] <= q_pos[:, None]
            if kind == SLIDING:
                seen &= k_pos[None, :] > q_pos[:, None] - hp.window
            elif allowed is not None:
                seen &= allowed[b]
            scores = jnp.matmul(q_rows, kb.T, precision=HI) * d_qk ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.matmul(_round_operand(probs, quant), vb, precision=HI)

        return jax.lax.map(rows, jnp.arange(n_blocks)).reshape(s, sz.d_v)

    o = jax.lax.map(one_head, jnp.arange(sz.heads))        # (H, S, dv)
    o = o.transpose(1, 0, 2)
    if "gate" not in hp.without:
        o = o * jax.nn.sigmoid(mm(h, lay["w_og"], quant))[:, :, None]
    return mm(o.reshape(s, sz.heads * sz.d_v), lay["wo"], quant)


def gated(h, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(h, wg, quant)) * mm(h, wu, quant), wd, quant)


def by_rows(fn, h):
    """``fn`` over ``h`` (S, d) a block of rows at a time (a row's result
    depends on no other row's)."""
    s = h.shape[0]
    if s <= ROW_BLOCK or s % ROW_BLOCK:
        return fn(h)
    out = jax.lax.map(fn, h.reshape(s // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(s, -1)


def routing(h, lay, hp: Hyper, quant):
    """Expert ids (S, k) and weights (S, k): sigmoid scores over all
    experts, the k largest of score + bias, weighed by the scores,
    normalised over the k, times the scale. Also the scores and what the
    choice was made by."""
    sigma = jax.nn.sigmoid(mm(h, lay["router"], quant))
    choose = sigma
    if "bias" not in hp.without:
        choose = sigma + lay["router_bias"].astype(F32)
    _, idx = jax.lax.top_k(choose, hp.top_k)
    top = jnp.take_along_axis(sigma, idx, axis=-1)
    return (idx, hp.routed_scale * top / top.sum(-1, keepdims=True), sigma,
            choose)


def _one(stack, j):
    """Matrix ``j`` of a stack (n, a, b), sliced where it lies."""
    return jax.lax.dynamic_index_in_dim(stack, j, 0, keepdims=False)


def routed(h, lay, hp: Hyper, quant):
    """What the held experts add: each one on every token, weighted by
    the token's normalised score for it (zero where it was not chosen)."""
    idx, w, _, _ = routing(h, lay, hp, quant)
    held = jnp.asarray(hp.held, jnp.int32)

    def add(y, j):
        w_e = jnp.sum(jnp.where(idx == held[j], w, 0.0), -1)
        out = gated(h, _one(lay["we_gate"], j), _one(lay["we_up"], j),
                    _one(lay["we_down"], j), quant)
        return y + w_e[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(len(hp.held)))
    return y


def shared(h, lay, quant):
    """The sum of the shared experts' outputs (the published model has
    one)."""
    def add(y, j):
        return y + gated(h, _one(lay["ws_gate"], j), _one(lay["ws_up"], j),
                         _one(lay["ws_down"], j), quant), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        jnp.arange(lay["ws_gate"].shape[0]))
    return y


def feed_forward(h, lay, hp: Hyper, quant):
    if "w_gate" in lay:
        return gated(h, lay["w_gate"], lay["w_up"], lay["w_down"], quant)
    if "shared" in hp.without:
        return routed(h, lay, hp, quant)
    return routed(h, lay, hp, quant) + shared(h, lay, quant)


def layer(x, lay, positions, kind: str, hp: Hyper, quant=None):
    a = x + attention(rms_norm(x, lay["ln1"], hp.eps), lay, positions, kind,
                      hp, quant)
    return a + by_rows(lambda rows: feed_forward(rows, lay, hp, quant),
                       rms_norm(a, lay["ln2"], hp.eps))


def hidden(w, tokens, hp: Hyper, quant=None):
    """tokens (S,) -> final normed hidden states (S, d)."""
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(F32)
    for kind, lay in zip(hp.layer_types, w["layers"]):
        x = layer(x, lay, positions, kind, hp, quant)
    return rms_norm(x, w["ln_f"], hp.eps)


def head(x, lm_head, quant=None):
    """Logits (n, V) of hidden states (n, d) against the head (V, d),
    widened a block of the vocabulary at a time."""
    v, d = lm_head.shape
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    if quant == "fp8":
        # One scale for the whole tensor, as everywhere else.
        s = jnp.maximum(jnp.max(jnp.abs(lm_head)).astype(F32), 1e-30) / 448.0
        xq = _round_operand(x, quant)
        block = lambda wb: jnp.matmul(  # noqa: E731
            xq, ((wb.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32)
                 * s).T, precision=HI)
    else:
        block = lambda wb: mm(x, wb.astype(F32).T, quant)  # noqa: E731
    out = jax.lax.map(block, lm_head.reshape(nb, v // nb, d))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


@functools.partial(jax.jit, static_argnums=(3, 4))
def logits_at(w, tokens, idx, hp: Hyper, quant=None):
    """Logits (len(idx), V) at positions ``idx`` of one sequence, from one
    full forward pass. ``tokens`` may be padded on the right: a causal
    model's earlier positions cannot see the padding."""
    x = hidden(w, tokens, hp, quant)
    return head(x[idx], w["lm_head"], quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def forward(w, tokens, hp: Hyper, quant=None):
    """Logits (S, V) at every position (the CPU tests' sizes)."""
    return head(hidden(w, tokens, hp, quant), w["lm_head"], quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def selected_sets(w, tokens, layer_index: int, hp: Hyper):
    """The (S, S) bool of full layer ``layer_index``'s sets over one
    sequence: row ``t`` marks the keys query ``t`` attends (the tests
    compare the program's selection with it, position for position)."""
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(F32)
    for i, (kind, lay) in enumerate(zip(hp.layer_types, w["layers"])):
        if i == layer_index:
            h = rms_norm(x, lay["ln1"], hp.eps)
            cq = query_latent(h, lay, hp.full, hp, None)
            return selection(h, cq, lay, positions, hp, None).reshape(
                tokens.shape[0], -1)
        x = layer(x, lay, positions, kind, hp)
    raise ValueError(f"no layer {layer_index}")
