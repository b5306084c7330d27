"""Plain reference: Pangu-Ultra-MoE (``model_type: pangu_ultra_moe``;
``config.json`` of FreedomIntelligence/openPangu-Ultra-MoE-718B), a decoder
with multi-head latent attention, sandwich norms, leading dense layers and
routed experts beside a shared one. All norms are RMSNorm ``N(x) = x /
sqrt(mean x^2 + eps) * g``:

    a = x + N2(Attn(N1(x)))              y = a + N4(F(N3(a)))

    Attn(h):  cq = Nq(h Wqa);  q = cq Wqb -> H heads of [qn | qr], qr rotated
              [ckv | kr] = h Wkva;  ckv = Nkv(ckv);  kr rotated (ONE
              positional key a token, shared by all heads)
              [kn | v] = ckv Wkvb -> H heads of (dn + dv)
              p = softmax_{s <= t}((qn_t . kn_s + qr_t . kr_s) / sqrt(dn + dr))
              concat_heads(sum_s p v_s) Wo
    F(h), a leading dense layer:   (silu(h Wg) * (h Wu)) Wd
    F(h), an expert layer:
              sigma = sigmoid(h Wr) over ALL experts; T = the k largest
              w_e = scale * sigma_e / sum_T sigma
              sum_{e in T, e held} w_e E_e(h) + sum of the shared experts
              E(h) = (silu(h Wg) * (h Wu)) Wd
    logits = N_f(x) W_head^T

Positions rotate by halves: dimension i of the first half of the rotated
part turns with dimension i of the second by ``position * theta ** (-2i
/ dr)``. ``held`` is the list of routed experts whose weights were handed
over (a chip's share of a layer divided over chips): the weights ``w_e``
are normalised over all ``k`` chosen experts, held or not, and what an
absent expert would add is left out. With every expert held this is the
published layer. The extra multi-token-prediction layer is not part of
the next token's logits and is not here.

float32 ``jax.numpy`` at matmul precision "highest"; attention in the
plain (expanded) form: per-head keys and values made from the latent, no
cache, no kernels, no batching, no grouping: one sequence at a time, every
held expert run on every token and weighted (zero where the token did not
choose it). It imports nothing of the program and reads only the canonical
weights of ``chipbench/seeded_pangu_ultra_moe.py``: ``embed`` (V, d),
``ln_f`` (d), ``lm_head`` (V, d) and ``layers``, a list with one dict a
layer (a dense layer holds ``w_gate``, an expert layer ``router``).

The canonical tree arrives in the configuration's parameter type
(bfloat16) and is widened here one matrix (one expert, one head's slice, a
block of the vocabulary) at a time; attention works a head and a block of
query rows at a time and the feed-forward parts a block of rows at a time,
so that an 11,264-token sequence fits beside 9.8 GB of weights on a 16 GB
chip.

``quant`` runs the same mathematics in a lower precision, for the control
that must come out as not correct: "bf16" rounds every matmul operand to
bfloat16; "fp8" rounds it to float8_e4m3 under a per-tensor scale. The
router's product is a matmul like the others.
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


class Hyper(NamedTuple):
    """The published keys the mathematics reads, and the share (hashable:
    a static argument of the jitted entry points)."""

    heads: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    top_k: int
    routed_scale: float
    theta: float
    eps: float
    held: tuple             # global ids of the experts handed over
    # Parts left out, for the tests that show each one matters: any of
    # "ln1" .. "ln4" (that norm becomes the identity), "shared" (no shared
    # expert), "scale" (the routed weights not scaled).
    without: tuple = ()


def hyper_of(model: dict, held, without=()) -> Hyper:
    """From a configuration's published keys and the experts held."""
    return Hyper(
        without=tuple(without),
        heads=int(model["num_attention_heads"]),
        kv_rank=int(model["kv_lora_rank"]),
        d_nope=int(model["qk_nope_head_dim"]),
        d_rope=int(model["qk_rope_head_dim"]),
        d_v=int(model["v_head_dim"]),
        top_k=int(model["num_experts_per_tok"]),
        routed_scale=float(model.get("routed_scaling_factor", 1.0)),
        theta=float(model["rope_theta"]),
        eps=float(model["rms_norm_eps"]),
        held=tuple(int(e) for e in held),
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


def attention(h, lay, positions, hp: Hyper, quant):
    """Latent attention of a normed ``h`` (S, d), in the plain form: a
    head at a time, its keys and values expanded from the latent."""
    s = h.shape[0]
    cq = rms_norm(mm(h, lay["wq_a"], quant), lay["q_norm"], hp.eps)
    ckr = mm(h, lay["wkv_a"], quant)
    ckv = rms_norm(ckr[:, :hp.kv_rank], lay["kv_norm"], hp.eps)
    kr = rope_halves(ckr[:, hp.kv_rank:], positions, hp.theta)
    d_qk = hp.d_nope + hp.d_rope
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def one_head(i):
        q = mm(cq, _column_block(lay["wq_b"], i, d_qk), quant)
        q = jnp.concatenate(
            [q[:, :hp.d_nope],
             rope_halves(q[:, hp.d_nope:], positions, hp.theta)], -1)
        kv = mm(ckv, _column_block(lay["wkv_b"], i, hp.d_nope + hp.d_v),
                quant)
        k = jnp.concatenate([kv[:, :hp.d_nope], kr], -1)
        v = _round_operand(kv[:, hp.d_nope:], quant)
        q, k = _round_operand(q, quant), _round_operand(k, quant)

        def rows(block):
            q_rows, q_pos = block
            scores = jnp.matmul(q_rows, k.T, precision=HI) * d_qk ** -0.5
            seen = positions[None, :] <= q_pos[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.matmul(_round_operand(probs, quant), v, precision=HI)

        out = jax.lax.map(rows, (q.reshape(s // qb, qb, d_qk),
                                 positions.reshape(s // qb, qb)))
        return out.reshape(s, hp.d_v)

    o = jax.lax.map(one_head, jnp.arange(hp.heads))        # (H, S, dv)
    o = o.transpose(1, 0, 2).reshape(s, hp.heads * hp.d_v)
    return mm(o, lay["wo"], quant)


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


def routing(h, router, hp: Hyper, quant):
    """Expert ids (S, k) and weights (S, k): sigmoid scores over all
    experts, the k largest, normalised over the k, times the scale."""
    sigma = jax.nn.sigmoid(mm(h, router, quant))
    top, idx = jax.lax.top_k(sigma, hp.top_k)
    scale = 1.0 if "scale" in hp.without else hp.routed_scale
    return idx, scale * top / top.sum(-1, keepdims=True), sigma


def _one(stack, j):
    """Matrix ``j`` of a stack (n, a, b), sliced where it lies."""
    return jax.lax.dynamic_index_in_dim(stack, j, 0, keepdims=False)


def routed(h, lay, hp: Hyper, quant):
    """What the held experts add: each one on every token, weighted by
    the token's scaled, normalised score for it (zero where it was not
    chosen)."""
    idx, w, _ = routing(h, lay["router"], hp, quant)
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


def layer(x, lay, positions, hp: Hyper, quant=None):
    def norm(name, t):
        if name in hp.without:
            return t
        return rms_norm(t, lay[name], hp.eps)

    a = x + norm("ln2", attention(norm("ln1", x), lay, positions, hp, quant))
    f = by_rows(lambda rows: feed_forward(rows, lay, hp, quant),
                norm("ln3", a))
    return a + norm("ln4", f)


def hidden(w, tokens, hp: Hyper, quant=None):
    """tokens (S,) -> final normed hidden states (S, d)."""
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(F32)
    for lay in w["layers"]:
        x = layer(x, lay, positions, hp, quant)
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
