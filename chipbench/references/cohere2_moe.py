"""Plain reference: Cohere2-MoE (``model_type: cohere2_moe``;
``config.json`` of CohereLabs/command-a-plus-05-2026), a decoder whose
every layer reads one bias-free LayerNorm into attention, routed experts
and shared experts side by side (the parallel block):

    h = (x - mean x) / sqrt(var x + eps) * g
    A = concat_heads(softmax_{s in S(t)}(q_t . k_s / sqrt Dh) v_s) Wo
        S(t) = {s <= t} on a full layer (nothing rotated: no positions),
        {t - window + 1 <= s <= t} on a sliding one (q, k rotated over the
        whole head, adjacent pairs, ``rope_gptj``)
    sigma = sigmoid(h Wr) over ALL experts; T = the k largest;
        w_e = sigma_e / sum_{T} sigma
    F_routed = sum_{e in T, e held} w_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    F_shared = mean over the shared experts of the same form
    y = x + A + F_routed + F_shared
    logits = logit_scale * LN_f(x) E^T   (the embedding, tied)

``held`` is the list of routed experts whose weights were handed over (a
chip's share of a layer divided over chips): the weights ``w_e`` are
normalised over all ``k`` chosen experts, held or not, and what an absent
expert would add is left out. With every expert held this is the
published layer.

float32 ``jax.numpy`` at matmul precision "highest"; no kernels, no cache,
no batching, no grouping: one sequence at a time, every held expert run on
every token and weighted (zero where the token did not choose it). It
imports nothing of the program and reads only the canonical weights of
``chipbench/seeded_cohere2_moe.py``: ``embed`` (V, d), ``ln_f`` (d) and
``layers``, a list with one dict a layer (no stack to slice).

The canonical tree arrives in the configuration's parameter type
(bfloat16) and is widened here one matrix (one expert) at a time, and
attention works a query head and a block of query rows at a time, so that
a 12,800-token sequence fits beside 9.5 GB of weights on a 16 GB chip.

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


class Hyper(NamedTuple):
    """The published keys the mathematics reads, and the share (hashable:
    a static argument of the jitted entry points)."""

    heads: int
    kv_heads: int
    head_dim: int
    top_k: int
    window: int
    theta: float
    eps: float
    logit_scale: float
    layer_types: tuple      # "sliding_attention" / "full_attention"
    held: tuple             # global ids of the experts handed over


def hyper_of(model: dict, held) -> Hyper:
    """From a configuration's published keys and the experts held."""
    n = int(model["num_hidden_layers"])
    return Hyper(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        top_k=int(model["num_experts_per_tok"]),
        window=int(model["sliding_window"]),
        theta=float(model["rope_theta"]),
        eps=float(model["layer_norm_eps"]),
        logit_scale=float(model.get("logit_scale", 1.0)),
        layer_types=tuple(model["layer_types"][:n]),
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


def layer_norm(x, scale, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale.astype(F32)


def rope_pairs(x, positions, theta):
    """x (S, H, Dh): dimensions (2i, 2i+1) turn by position * theta **
    (-2i / Dh) (GPT-J's interleaved form, the whole head)."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def attention(h, lay, positions, kind, hp: Hyper, quant):
    """The attention branch of a normed ``h`` (S, d)."""
    s = h.shape[0]
    q = mm(h, lay["wq"], quant).reshape(s, hp.heads, hp.head_dim)
    k = mm(h, lay["wk"], quant).reshape(s, hp.kv_heads, hp.head_dim)
    v = mm(h, lay["wv"], quant).reshape(s, hp.kv_heads, hp.head_dim)
    if kind == "sliding_attention":
        q = rope_pairs(q, positions, hp.theta)
        k = rope_pairs(k, positions, hp.theta)
    q, k, v = (_round_operand(t, quant) for t in (q, k, v))
    group = hp.heads // hp.kv_heads
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def one_head(i):
        # K/V head i // group serves query head i.
        qh = jax.lax.dynamic_index_in_dim(q, i, 1, keepdims=False)
        kh = jax.lax.dynamic_index_in_dim(k, i // group, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, i // group, 1, keepdims=False)

        def rows(block):
            q_rows, q_pos = block
            scores = jnp.matmul(q_rows, kh.T, precision=HI) \
                * hp.head_dim ** -0.5
            seen = positions[None, :] <= q_pos[:, None]
            if kind == "sliding_attention":
                seen &= positions[None, :] > q_pos[:, None] - hp.window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.matmul(_round_operand(probs, quant), vh, precision=HI)

        out = jax.lax.map(rows, (qh.reshape(s // qb, qb, hp.head_dim),
                                 positions.reshape(s // qb, qb)))
        return out.reshape(s, hp.head_dim)

    o = jax.lax.map(one_head, jnp.arange(hp.heads))       # (H, S, Dh)
    o = o.transpose(1, 0, 2).reshape(s, hp.heads * hp.head_dim)
    return mm(o, lay["wo"], quant)


def routing(h, router, hp: Hyper, quant):
    """Expert ids (S, k) and weights (S, k): sigmoid scores over all
    experts, the k largest, normalised over the k."""
    sigma = jax.nn.sigmoid(mm(h, router, quant))
    top, idx = jax.lax.top_k(sigma, hp.top_k)
    return idx, top / top.sum(-1, keepdims=True), sigma


def gated(h, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(h, wg, quant)) * mm(h, wu, quant), wd, quant)


def _one(stack, j):
    """Matrix ``j`` of a stack (n, a, b), sliced where it lies: the stack
    is never copied, and one matrix at a time is widened."""
    return jax.lax.dynamic_index_in_dim(stack, j, 0, keepdims=False)


def routed(h, lay, hp: Hyper, quant):
    """What the held experts add: each one on every token, weighted by
    the token's normalised score for it (zero where it was not chosen)."""
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
    """The mean of the shared experts' outputs."""
    n = lay["ws_gate"].shape[0]

    def add(y, j):
        return y + gated(h, _one(lay["ws_gate"], j), _one(lay["ws_up"], j),
                         _one(lay["ws_down"], j), quant), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(n))
    return y / n


def layer(x, lay, positions, kind, hp: Hyper, quant=None):
    h = layer_norm(x, lay["ln"], hp.eps)
    return (x + attention(h, lay, positions, kind, hp, quant)
            + routed(h, lay, hp, quant) + shared(h, lay, quant))


def hidden(w, tokens, hp: Hyper, quant=None):
    """tokens (S,) -> final normed hidden states (S, d)."""
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(F32)
    for lay, kind in zip(w["layers"], hp.layer_types, strict=True):
        x = layer(x, lay, positions, kind, hp, quant)
    return layer_norm(x, w["ln_f"], hp.eps)


def head(x, embed, hp: Hyper, quant=None):
    """Logits (n, V) of hidden states (n, d) against the tied embedding,
    widened a block of the vocabulary at a time."""
    v, d = embed.shape
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    if quant == "fp8":
        # One scale for the whole tensor, as everywhere else.
        s = jnp.maximum(jnp.max(jnp.abs(embed)).astype(F32), 1e-30) / 448.0
        xq = _round_operand(x, quant)
        block = lambda eb: jnp.matmul(  # noqa: E731
            xq, ((eb.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32)
                 * s).T, precision=HI)
    else:
        block = lambda eb: mm(x, eb.astype(F32).T, quant)  # noqa: E731
    out = jax.lax.map(block, embed.reshape(nb, v // nb, d))  # (nb, n, V/nb)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v) * hp.logit_scale


@functools.partial(jax.jit, static_argnums=(3, 4))
def logits_at(w, tokens, idx, hp: Hyper, quant=None):
    """Logits (len(idx), V) at positions ``idx`` of one sequence, from one
    full forward pass. ``tokens`` may be padded on the right: a causal
    model's earlier positions cannot see the padding."""
    x = hidden(w, tokens, hp, quant)
    return head(x[idx], w["embed"], hp, quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def forward(w, tokens, hp: Hyper, quant=None):
    """Logits (S, V) at every position (the CPU tests' sizes)."""
    return head(hidden(w, tokens, hp, quant), w["embed"], hp, quant)
