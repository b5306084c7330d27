"""Plain reference: Olmo-Hybrid (``model_type: olmo_hybrid``), a decoder
whose layers are gated delta-rule linear attention with a full-attention
layer among every few (``layer_types``; three to one as published), each
followed by a SwiGLU MLP, in the block of the Olmo 2 / Olmo 3 family
(arXiv:2501.00656): the RMSNorm sits on the OUTPUT of each part, inside
the residual, ``h = x + N_a(Mixer(x))``, ``y = h + N_f(MLP(h))``.

float32 ``jax.numpy`` at matmul precision "highest"; no kernels, no cache,
no batching, no chunks: one sequence at a time.

* Full layer: ``q = N_q(x Wq)``, ``k = N_k(x Wk)`` (RMSNorms over the
  whole projections, before the heads are split), ``v = x Wv``; causal
  softmax of ``q . k / sqrt(Dh)`` a block of queries at a time; ``Wo``.
  Rotated by halves only where ``rope_parameters.rope_theta`` holds a
  number (the published one is null: no rotary positions).
* Linear layer (the gated delta rule, arXiv:2412.06464): ``[q | k | v] =
  silu(conv(x [Wq | Wk | Wv]))``, a depthwise causal convolution without
  a bias (zeros before the sequence); per head ``q^ = q / ||q|| /
  sqrt(dk)``, ``k^ = k / ||k||`` (``x * rsqrt(sum x^2 + 1e-6)``);
  ``beta_t = 2 sigmoid(x_t Wb)`` (the 2 is ``linear_allow_neg_eigval``),
  ``g_t = -exp(A_log) softplus(x_t Wa + dt_bias)``. The recurrence is a
  plain ``lax.scan`` over positions from ``S = 0`` (dv x dk a head)::

      S' = exp(g_t) S;  u = v_t - S' k^_t;  S = S' + beta_t u k^_t^T;
      o_t = S q^_t

  then per head an RMSNorm of ``o_t`` over ``dv`` with one learned (dv,)
  scale, times ``silu(x_t Wg)``; heads concatenated; ``Wo``.

It imports nothing of the program and reads only the canonical weights of
``chipbench/seeded_olmo_hybrid.py``. The canonical tree arrives in the
configuration's parameter type (bfloat16) and is widened here a layer at
a time and, for the head, a block of the vocabulary at a time. The layers
run one jitted program per KIND of layer, called in the stack's order.

``quant`` runs the same mathematics in a lower precision, for the control
that must come out as not correct: "bf16" rounds every matmul operand to
bfloat16, "fp8" to float8_e4m3 under a per-tensor scale; the recurrence's
operands (q^, k^, v) are matmul operands in any blocked form of it, so
they are rounded too, and under either the STATE is rounded to bfloat16
after every position. ``g``, ``beta`` and the norms stay float32. (Only
"fp8" comes out as not correct at this configuration's limit: the state's
rounding moves a served logit less than the program's own bfloat16
arithmetic does, so the logit comparison does not guard the state's
type; the configuration's ``limits.calibrated``.)
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
HEAD_BLOCKS = 32
QUERY_BLOCK = 256
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


class Hyper(NamedTuple):
    """The published keys the mathematics reads (hashable: a static
    argument of the jitted entry points)."""

    layer_types: tuple
    heads: int
    kv_heads: int
    head_dim: int
    lin_heads: int
    lin_key_dim: int
    lin_value_dim: int
    conv: int
    neg_eigval: bool
    theta: Optional[float]
    eps: float


def hyper_of(model: dict) -> Hyper:
    """From a configuration's published keys (``layer_types`` as far as
    ``num_hidden_layers``)."""
    if model["linear_num_value_heads"] != model["linear_num_key_heads"]:
        raise ValueError("the reference has as many value heads as key heads")
    theta = (model.get("rope_parameters") or {}).get("rope_theta")
    return Hyper(
        layer_types=tuple(
            model["layer_types"][:int(model["num_hidden_layers"])]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model.get("head_dim") or model["hidden_size"]
                     // model["num_attention_heads"]),
        lin_heads=int(model["linear_num_key_heads"]),
        lin_key_dim=int(model["linear_key_head_dim"]),
        lin_value_dim=int(model["linear_value_head_dim"]),
        conv=int(model["linear_conv_kernel_dim"]),
        neg_eigval=bool(model["linear_allow_neg_eigval"]),
        theta=None if theta is None else float(theta),
        eps=float(model["rms_norm_eps"]),
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


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rope(x, positions, theta):
    """x (S, H, Dh); rotate-half form, frequencies theta**(-2i/Dh)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def full_attention(x, lay, hp: Hyper, quant):
    """The full layers' mixer of ``x`` (S, d): a dense causal softmax, a
    block of queries at a time."""
    s = x.shape[0]
    positions = jnp.arange(s)
    q = rms_norm(mm(x, lay["wq"], quant), lay["q_norm"], hp.eps)
    k = rms_norm(mm(x, lay["wk"], quant), lay["k_norm"], hp.eps)
    v = mm(x, lay["wv"], quant)
    q = q.reshape(s, hp.heads, hp.head_dim)
    k = k.reshape(s, hp.kv_heads, hp.head_dim)
    v = v.reshape(s, hp.kv_heads, hp.head_dim)
    if hp.theta is not None:
        q, k = rope(q, positions, hp.theta), rope(k, positions, hp.theta)
    # K/V head i serves query heads i*G .. (i+1)*G - 1.
    group = hp.heads // hp.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    q, k, v = (_round_operand(t, quant) for t in (q, k, v))
    qb = min(QUERY_BLOCK, s)
    assert s % qb == 0, (s, qb)

    def block(i):
        q_i = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        q_pos = i * qb + jnp.arange(qb)
        scores = jnp.einsum("qhd,khd->hqk", q_i, k, precision=HI) \
            * hp.head_dim ** -0.5
        mask = q_pos[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", _round_operand(probs, quant), v,
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(s // qb))
    return mm(o.reshape(s, hp.heads * hp.head_dim), lay["wo"], quant)


def linear_attention(x, lay, hp: Hyper, quant):
    """The linear layers' mixer of ``x`` (S, d): the gated delta rule from
    a zero state, one position at a time."""
    s = x.shape[0]
    h, dk, dv = hp.lin_heads, hp.lin_key_dim, hp.lin_value_dim
    p = mm(x, lay["w_qkv"], quant)
    # Depthwise causal convolution, kernel K, no bias: output t reads
    # inputs t-K+1 .. t (zeros before the sequence); the last tap is the
    # current input.
    padded = jnp.concatenate([jnp.zeros((hp.conv - 1, p.shape[1]), F32), p], 0)
    c = jax.nn.silu(sum(lay["conv_w"][j] * padded[j:j + s]
                        for j in range(hp.conv)))
    q = l2_norm(c[:, :h * dk].reshape(s, h, dk)) * dk ** -0.5
    k = l2_norm(c[:, h * dk:2 * h * dk].reshape(s, h, dk))
    v = c[:, 2 * h * dk:].reshape(s, h, dv)
    q, k, v = (_round_operand(t, quant) for t in (q, k, v))
    ab = mm(x, lay["w_ab"], quant)
    g = -jnp.exp(lay["A_log"]) * jax.nn.softplus(ab[:, :h] + lay["dt_bias"])
    beta = jax.nn.sigmoid(ab[:, h:]) * (2.0 if hp.neg_eigval else 1.0)

    def step(state, t):
        q_t, k_t, v_t, g_t, beta_t = t
        decayed = jnp.exp(g_t)[:, None, None] * state           # (H, dv, dk)
        u = v_t - jnp.einsum("hvk,hk->hv", decayed, k_t, precision=HI)
        state = decayed + (beta_t[:, None] * u)[:, :, None] * k_t[:, None, :]
        if quant is not None:
            state = state.astype(jnp.bfloat16).astype(F32)
        return state, jnp.einsum("hvk,hk->hv", state, q_t, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((h, dv, dk), F32), (q, k, v, g, beta))
    o = rms_norm(o, lay["o_norm"], hp.eps).reshape(s, h * dv)
    return mm(o * jax.nn.silu(mm(x, lay["w_g"], quant)), lay["w_o"], quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def layer(x, lay, kind: str, hp: Hyper, quant=None):
    """One layer of ``kind`` on ``x`` (S, d); its weights are widened to
    float32 here, one layer at a time."""
    lay = jax.tree_util.tree_map(lambda t: t.astype(F32), lay)
    mixer = linear_attention if kind == LINEAR else full_attention
    h = x + rms_norm(mixer(x, lay, hp, quant), lay["norm_mixer"], hp.eps)
    m = mm(jax.nn.silu(mm(h, lay["w_gate"], quant))
           * mm(h, lay["w_up"], quant), lay["w_down"], quant)
    return h + rms_norm(m, lay["norm_mlp"], hp.eps)


def hidden(w, tokens, hp: Hyper, quant=None):
    """tokens (S,) -> final normed hidden states (S, d), the layers in
    the stack's order, each kind's leaves indexed by the layer's ordinal
    among the layers of its kind."""
    x = w["embed"][tokens].astype(F32)
    seen = {LINEAR: 0, FULL: 0}
    for kind in hp.layer_types:
        tree = w["linear" if kind == LINEAR else "full"]
        lay = jax.tree_util.tree_map(lambda t: t[seen[kind]], tree)
        x = layer(x, lay, kind, hp, quant)
        seen[kind] += 1
    return rms_norm(x, w["ln_f"].astype(F32), hp.eps)


@functools.partial(jax.jit, static_argnums=(2,))
def head(x, lm_head, quant=None):
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
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def logits_at(w, tokens, idx, hp: Hyper, quant=None):
    """Logits (len(idx), V) at positions ``idx`` of one sequence, from one
    full forward pass. ``tokens`` may be padded on the right: a causal
    model's earlier positions cannot see the padding, and the recurrence
    and the convolution run forward in time."""
    with jax.default_matmul_precision("highest"):
        return head(hidden(w, tokens, hp, quant)[idx], w["lm_head"], quant)


def forward(w, tokens, hp: Hyper, quant=None):
    """Logits (S, V) at every position (the CPU tests' sizes)."""
    with jax.default_matmul_precision("highest"):
        return head(hidden(w, tokens, hp, quant), w["lm_head"], quant)
