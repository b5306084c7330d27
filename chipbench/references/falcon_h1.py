"""Plain reference: Falcon-H1 (``model_type: falcon_h1``), a decoder whose
every block runs grouped-query attention and a Mamba-2 state-space mixer
side by side on one pre-norm input, then a SwiGLU MLP, with the published
scalar multipliers on every path (``config.json`` of
tiiuae/Falcon-H1-34B-Instruct and the modelling code it names).

float32 ``jax.numpy`` at matmul precision "highest"; no kernels, no cache,
no batching, no chunks: one sequence at a time, full causal attention, and
the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
C_t + D x_t`` as a plain ``lax.scan`` over time from a zero state. It
imports nothing of the program and reads only the canonical weights of
``chipbench/seeded_falcon_h1.py``.

The canonical tree arrives in the configuration's parameter type
(bfloat16) and is widened here a layer at a time (the layer scan's body)
and, for the head, a block of the vocabulary at a time: the depth-6 tree
in float32 is 21 GB, more than a chip holds.

``quant`` runs the same mathematics in a lower precision, for the control
that must come out as not correct: "bf16" rounds every matmul operand to
bfloat16; "fp8" rounds it to float8_e4m3 under a per-tensor scale. The
recurrence's operands (x, B, C after the convolution) are matmul operands
in any blocked form of it, so they are rounded too; dt, the decay and the
state stay float32, as the configuration states.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
HEAD_BLOCKS = 32


class Hyper(NamedTuple):
    """The published keys the mathematics reads (hashable: a static
    argument of the jitted entry points)."""

    heads: int
    kv_heads: int
    head_dim: int
    d_ssm: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    theta: float
    eps: float
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple
    mlp_multipliers: tuple


def hyper_of(model: dict) -> Hyper:
    """From a configuration's published keys."""
    return Hyper(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        d_ssm=int(model["mamba_d_ssm"]),
        ssm_heads=int(model["mamba_n_heads"]),
        ssm_head_dim=int(model["mamba_d_head"]),
        d_state=int(model["mamba_d_state"]),
        groups=int(model["mamba_n_groups"]),
        d_conv=int(model["mamba_d_conv"]),
        theta=float(model["rope_theta"]),
        eps=float(model["rms_norm_eps"]),
        embedding_multiplier=float(model["embedding_multiplier"]),
        lm_head_multiplier=float(model["lm_head_multiplier"]),
        attention_in_multiplier=float(model["attention_in_multiplier"]),
        attention_out_multiplier=float(model["attention_out_multiplier"]),
        key_multiplier=float(model["key_multiplier"]),
        ssm_in_multiplier=float(model["ssm_in_multiplier"]),
        ssm_out_multiplier=float(model["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in model["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in model["mlp_multipliers"]),
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
    return jnp.matmul(_round_operand(a, quant), _round_operand(b, quant),
                      precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (S, H, Dh); rotate-half form, frequencies theta**(-2i/Dh)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _head_attention(q, k, v, mask, quant):
    """One query head: q k v (S, Dh) -> (S, Dh)."""
    scores = jnp.matmul(q, k.T, precision=HI) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.matmul(_round_operand(probs, quant), v, precision=HI)


def attention(h, lay, positions, hp: Hyper, quant):
    """The attention branch of a pre-normed ``h`` (S, d)."""
    s = h.shape[0]
    a = h * hp.attention_in_multiplier
    q = mm(a, lay["wq"], quant).reshape(s, hp.heads, hp.head_dim)
    k = (mm(a, lay["wk"], quant) * hp.key_multiplier).reshape(
        s, hp.kv_heads, hp.head_dim)
    v = mm(a, lay["wv"], quant).reshape(s, hp.kv_heads, hp.head_dim)
    q, k = rope(q, positions, hp.theta), rope(k, positions, hp.theta)
    # K/V head i serves query heads i*G .. (i+1)*G - 1.
    group = hp.heads // hp.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    qh, kh, vh = (_round_operand(t, quant).transpose(1, 0, 2)
                  for t in (q, k, v))
    mask = positions[:, None] >= positions[None, :]
    o = jax.lax.map(
        jax.checkpoint(lambda t: _head_attention(*t, mask, quant)),
        (qh, kh, vh))
    o = o.transpose(1, 0, 2).reshape(s, hp.heads * hp.head_dim)
    return mm(o, lay["wo"], quant) * hp.attention_out_multiplier


def mixer(h, lay, hp: Hyper, quant):
    """The Mamba-2 branch of a pre-normed ``h`` (S, d), from a zero
    state, one position at a time."""
    s = h.shape[0]
    gn = hp.groups * hp.d_state
    conv_dim = hp.d_ssm + 2 * gn
    widths = (hp.d_ssm, hp.d_ssm, gn, gn, hp.ssm_heads)
    mup = jnp.concatenate([jnp.full((w,), m, F32)
                           for w, m in zip(widths, hp.ssm_multipliers)])
    p = mm(h * hp.ssm_in_multiplier, lay["in_proj"], quant) * mup
    z, xbc, dt = (p[:, :hp.d_ssm], p[:, hp.d_ssm:hp.d_ssm + conv_dim],
                  p[:, hp.d_ssm + conv_dim:])
    # Depthwise causal convolution, kernel d_conv: output t reads inputs
    # t-d_conv+1 .. t (zeros before the sequence); the last tap is the
    # current input.
    padded = jnp.concatenate(
        [jnp.zeros((hp.d_conv - 1, conv_dim), F32), xbc], 0)
    conv = lay["conv_b"] + sum(
        lay["conv_w"][j] * padded[j:j + s] for j in range(hp.d_conv))
    xbc = _round_operand(jax.nn.silu(conv), quant)
    x = xbc[:, :hp.d_ssm].reshape(s, hp.ssm_heads, hp.ssm_head_dim)
    b = xbc[:, hp.d_ssm:hp.d_ssm + gn].reshape(s, hp.groups, hp.d_state)
    c = xbc[:, hp.d_ssm + gn:].reshape(s, hp.groups, hp.d_state)
    # Heads 0..Hs/G-1 use group 0, the next Hs/G group 1, ...
    per = hp.ssm_heads // hp.groups
    b, c = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)
    dt = jax.nn.softplus(dt + lay["dt_bias"])            # (S, Hs)
    a = -jnp.exp(lay["A_log"])                           # (Hs,)

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.sum(state * c_t[:, None, :], -1) + lay["D"][:, None] * x_t
        return state, y_t

    zero = jnp.zeros((hp.ssm_heads, hp.ssm_head_dim, hp.d_state), F32)
    _, y = jax.lax.scan(step, zero, (x, b, c, dt))
    # Gate first (mamba_norm_before_gate false), then RMS norm within each
    # of the groups' channels, times the norm's weight.
    g = y.reshape(s, hp.d_ssm) * jax.nn.silu(z)
    g = g.reshape(s, hp.groups, hp.d_ssm // hp.groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + hp.eps)
    g = g.reshape(s, hp.d_ssm) * lay["ssm_norm"]
    return mm(g, lay["out_proj"], quant) * hp.ssm_out_multiplier


def _layer(x, lay, positions, hp: Hyper, quant):
    h = rms_norm(x, lay["ln1"], hp.eps)
    x = x + attention(h, lay, positions, hp, quant) + mixer(h, lay, hp, quant)
    m = rms_norm(x, lay["ln2"], hp.eps)
    gate = jax.nn.silu(mm(m, lay["w_gate"], quant) * hp.mlp_multipliers[0])
    return x + mm(gate * mm(m, lay["w_up"], quant), lay["w_down"],
                  quant) * hp.mlp_multipliers[1]


def hidden(w, tokens, hp: Hyper, quant=None):
    """tokens (S,) -> final normed hidden states (S, d). Each layer's
    weights are widened to float32 inside the scan's body: one layer in
    float32 at a time."""
    positions = jnp.arange(tokens.shape[0])

    def body(x, lay):
        lay = jax.tree_util.tree_map(lambda t: t.astype(F32), lay)
        return _layer(x, lay, positions, hp, quant), None

    x0 = w["embed"][tokens].astype(F32) * hp.embedding_multiplier
    x, _ = jax.lax.scan(jax.checkpoint(body), x0, w["layers"])
    return rms_norm(x, w["ln_f"].astype(F32), hp.eps)


def head(x, lm_head, hp: Hyper, quant=None):
    """Logits (n, V) of hidden states (n, d), the head widened a block of
    the vocabulary at a time."""
    d, v = lm_head.shape
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    if quant == "fp8":
        # One scale for the whole tensor, as everywhere else.
        s = jnp.maximum(jnp.max(jnp.abs(lm_head)).astype(F32), 1e-30) / 448.0
        xq = _round_operand(x, quant)
        block = lambda wb: jnp.matmul(  # noqa: E731
            xq, (wb.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32)
            * s, precision=HI)
    else:
        block = lambda wb: mm(x, wb.astype(F32), quant)  # noqa: E731
    blocks = jnp.moveaxis(lm_head.reshape(d, nb, v // nb), 1, 0)
    out = jax.lax.map(block, blocks)                     # (nb, n, V/nb)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v) \
        * hp.lm_head_multiplier


@functools.partial(jax.jit, static_argnums=(3, 4))
def logits_at(w, tokens, idx, hp: Hyper, quant=None):
    """Logits (len(idx), V) at positions ``idx`` of one sequence, from one
    full forward pass. ``tokens`` may be padded on the right: a causal
    model's earlier positions cannot see the padding, and the recurrence
    runs forward in time."""
    x = hidden(w, tokens, hp, quant)
    return head(x[idx], w["lm_head"], hp, quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def forward(w, tokens, hp: Hyper, quant=None):
    """Logits (S, V) at every position (the CPU tests' sizes)."""
    return head(hidden(w, tokens, hp, quant), w["lm_head"], hp, quant)
