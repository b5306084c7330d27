"""Plain reference: SDAR-MoE (``model_type: sdar_moe``; ``config.json`` of
JetLM/SDAR-30B-A3B-Chat), a decoder whose every layer carries routed
experts, with per-head query/key norms, a block-causal mask, and
generation by diffusion over blocks of ``B`` positions:

    h = N1(x);  q = h Wq, k = h Wk, v = h Wv
    q = Nq(q), k = Nk(k)      RMSNorm over each head's Dh values, one
                              learned (Dh,) scale for all query heads and
                              one for all key heads, BEFORE the rotation
    q, k rotated by halves (rotate_half), theta
    a = x + concat_heads(softmax_{j in S(i)}(q_i . k_j / sqrt Dh) v_j) Wo
        S(i) = {j : j // B <= i // B}     every earlier block whole, the
                                          own block in both directions
    h = N2(a);  p = softmax(h Wr) over ALL experts; T = the k largest;
        w_e = p_e / sum_T p
    y = a + sum_{e in T} w_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = N_f(x) W_head^T      untied; the logits at position i are for
                                  the token AT position i

N1, N2, N_f: ``x / sqrt(mean x^2 + eps) * g``. Query head ``g`` reads K/V
head ``g // (H / Hkv)``. No shared expert, no scale, no routing bias.

Generation (``generate``; the family's published routine): a prompt's
whole blocks are context. Then block after block: the block starts as the
prompt's left-over tokens followed by the mask id ``M``. While it holds an
``M``: one forward of the clean earlier blocks and the block; at every
masked position ``x0 = argmax`` of the logits (``M`` itself left out) and
``c = softmax(logits)[x0]``; ``n_s`` positions at least take their ``x0``
(``B // T``, the remainder given to the first steps): those of largest
``c``, the first at a tie (``low_confidence_static``), or under
``low_confidence_dynamic`` all those with ``c`` over the threshold where
at least ``n_s`` pass it. A clean block joins the context.

float32 ``jax.numpy`` at matmul precision "highest"; no kernels, no cache,
no batching, no grouping: one sequence at a time, every expert run on
every token and weighted (zero where the token did not choose it). It
imports nothing of the program and reads only the canonical weights of
``chipbench/seeded_sdar_moe.py``: ``embed`` (V, d), ``ln_f`` (d),
``lm_head`` (V, d) and ``layers``, a list with one dict a layer. The tree
arrives in the configuration's parameter type (bfloat16) and is widened
here one matrix (one expert, one block of the vocabulary) at a time.

``quant`` runs the same mathematics in a lower precision, for the control
that must come out as not correct: "bf16" rounds every matmul operand to
bfloat16; "fp8" rounds it to float8_e4m3 under a per-tensor scale.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
HEAD_BLOCKS = 32


class Hyper(NamedTuple):
    """The published keys the mathematics reads and the routine's numbers
    (hashable: a static argument of the jitted entry points)."""

    heads: int
    kv_heads: int
    head_dim: int
    top_k: int
    theta: float
    eps: float
    block: int
    steps: int
    rule: str
    threshold: float
    mask_id: int


def hyper_of(model: dict) -> Hyper:
    """From a configuration's published keys; the routine's numbers from
    the keys the configuration's ``assumed`` adds beside them."""
    return Hyper(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        top_k=int(model["num_experts_per_tok"]),
        theta=float(model["rope_theta"]),
        eps=float(model["rms_norm_eps"]),
        block=int(model.get("block_length", 4)),
        steps=int(model.get("denoising_steps", 4)),
        rule=str(model.get("remasking", "low_confidence_dynamic")),
        threshold=float(model.get("confidence_threshold", 0.9)),
        mask_id=int(model.get("mask_token_id", 151669)),
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


def rotate_halves(x, positions, theta):
    """x (S, H, Dh): ``x * cos + rotate_half(x) * sin`` with the angles
    ``position * theta ** (-2i / Dh)`` repeated over the two halves."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions[:, None].astype(F32) * freqs
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def attention(h, lay, positions, visible, hp: Hyper, quant):
    """The attention branch of a normed ``h`` (S, d) under the mask
    ``visible`` (S, S) bool (query, key)."""
    s = h.shape[0]
    q = mm(h, lay["wq"], quant).reshape(s, hp.heads, hp.head_dim)
    k = mm(h, lay["wk"], quant).reshape(s, hp.kv_heads, hp.head_dim)
    v = mm(h, lay["wv"], quant).reshape(s, hp.kv_heads, hp.head_dim)
    q = rotate_halves(rms_norm(q, lay["q_norm"], hp.eps), positions, hp.theta)
    k = rotate_halves(rms_norm(k, lay["k_norm"], hp.eps), positions, hp.theta)
    q, k, v = (_round_operand(t, quant) for t in (q, k, v))
    group = hp.heads // hp.kv_heads

    def one_head(i):
        qh = jax.lax.dynamic_index_in_dim(q, i, 1, keepdims=False)
        kh = jax.lax.dynamic_index_in_dim(k, i // group, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, i // group, 1, keepdims=False)
        scores = jnp.matmul(qh, kh.T, precision=HI) * hp.head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.matmul(_round_operand(probs, quant), vh, precision=HI)

    o = jax.lax.map(one_head, jnp.arange(hp.heads))       # (H, S, Dh)
    o = o.transpose(1, 0, 2).reshape(s, hp.heads * hp.head_dim)
    return mm(o, lay["wo"], quant)


def routing(h, router, hp: Hyper, quant):
    """Expert ids (S, k) and weights (S, k): softmax over all experts,
    the k largest, normalised over the k; and the probabilities."""
    p = jax.nn.softmax(mm(h, router, quant), -1)
    top, idx = jax.lax.top_k(p, hp.top_k)
    return idx, top / top.sum(-1, keepdims=True), p


def _one(stack, j):
    """Matrix ``j`` of a stack (n, a, b), sliced where it lies."""
    return jax.lax.dynamic_index_in_dim(stack, j, 0, keepdims=False)


def routed(h, lay, hp: Hyper, quant):
    """The routed experts: each one on every token, weighted by the
    token's normalised score for it (zero where it was not chosen)."""
    idx, w, _ = routing(h, lay["router"], hp, quant)

    def add(y, j):
        w_e = jnp.sum(jnp.where(idx == j, w, 0.0), -1)
        gate = mm(h, _one(lay["we_gate"], j), quant)
        up = mm(h, _one(lay["we_up"], j), quant)
        out = mm(jax.nn.silu(gate) * up, _one(lay["we_down"], j), quant)
        return y + w_e[:, None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        jnp.arange(lay["we_gate"].shape[0]))
    return y


def layer(x, lay, positions, visible, hp: Hyper, quant=None):
    a = x + attention(rms_norm(x, lay["ln1"], hp.eps), lay, positions,
                      visible, hp, quant)
    return a + routed(rms_norm(a, lay["ln2"], hp.eps), lay, hp, quant)


def hidden(w, tokens, visible, hp: Hyper, quant=None):
    """tokens (S,) at positions 0..S-1 under ``visible`` -> final normed
    hidden states (S, d)."""
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(F32)
    for lay in w["layers"]:
        x = layer(x, lay, positions, visible, hp, quant)
    return rms_norm(x, w["ln_f"], hp.eps)


def head(x, lm_head, quant=None):
    """Logits (n, V) of hidden states (n, d) against the untied head,
    widened a block of the vocabulary at a time."""
    v, d = lm_head.shape
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    if quant == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(lm_head)).astype(F32), 1e-30) / 448.0
        xq = _round_operand(x, quant)
        block = lambda eb: jnp.matmul(  # noqa: E731
            xq, ((eb.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32)
                 * s).T, precision=HI)
    else:
        block = lambda eb: mm(x, eb.astype(F32).T, quant)  # noqa: E731
    out = jax.lax.map(block, lm_head.reshape(nb, v // nb, d))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def block_causal(n: int, block: int):
    """(n, n) bool: key ``j`` visible to query ``i`` iff ``j // block <=
    i // block``."""
    at = jnp.arange(n) // block
    return at[None, :] <= at[:, None]


@functools.partial(jax.jit, static_argnums=(2, 3))
def forward(w, tokens, hp: Hyper, quant=None):
    """Logits (S, V) at every position of one sequence under the
    block-causal mask (the CPU tests' sizes)."""
    visible = block_causal(tokens.shape[0], hp.block)
    return head(hidden(w, tokens, visible, hp, quant), w["lm_head"], quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block_logits(w, tokens, n_ctx, hp: Hyper, quant=None):
    """``tokens`` (S,): ``n_ctx`` clean context tokens (whole blocks),
    then one block, then padding. Logits (B, V) at the block's
    positions. The padding sees itself alone and nobody sees it."""
    s = tokens.shape[0]
    at = jnp.arange(s)
    real = at < n_ctx + hp.block
    visible = (block_causal(s, hp.block) & real[None, :]) \
        | (at[None, :] == at[:, None])
    x = hidden(w, tokens, visible, hp, quant)
    x = jax.lax.dynamic_slice_in_dim(x, n_ctx, hp.block, axis=0)
    return head(x, w["lm_head"], quant)


def block_logits(w, context, block, hp: Hyper, quant=None, grid: int = 1):
    """One forward of the clean ``context`` (a whole number of blocks) and
    a partly masked ``block`` (``hp.block`` ids, the mask id where
    masked): logits (B, V) float32 at the block's positions. ``grid``
    pads the sequence to a multiple of itself, so that contexts of many
    lengths share a compiled program."""
    n_ctx = len(context)
    assert n_ctx % hp.block == 0 and len(block) == hp.block
    n = n_ctx + hp.block
    tokens = np.zeros(-(-n // grid) * grid, np.int32)
    tokens[:n] = list(context) + list(block)
    return _block_logits(w, jnp.asarray(tokens), jnp.int32(n_ctx), hp, quant)


def unmask_step(logits, block, step: int, hp: Hyper):
    """One denoising step of the routine on the host: ``logits`` (B, V)
    float32 at the block's positions, ``block`` its ids. Returns (the
    block after the step, the candidates ``x0`` (B,), the confidences
    ``c`` (B,) float32, of every position)."""
    logits = np.array(logits, np.float32)
    logits[:, hp.mask_id] = -np.inf
    x0 = logits.argmax(-1)
    top = logits.max(-1)
    conf = 1.0 / np.exp(logits - top[:, None]).sum(-1, dtype=np.float32)
    masked = np.asarray(block) == hp.mask_id
    n_s = hp.block // hp.steps + (1 if step < hp.block % hp.steps else 0)
    c = np.where(masked, conf, -np.inf)
    high = masked & (c > hp.threshold)
    if hp.rule == "low_confidence_dynamic" and high.sum() >= n_s:
        chosen = high
    else:
        order = np.argsort(-c, kind="stable")[:n_s]
        chosen = np.zeros(len(c), bool)
        chosen[order] = True
        chosen &= masked
    out = np.where(chosen, x0, np.asarray(block))
    return [int(t) for t in out], x0, conf


def generate(w, prompt, n: int, hp: Hyper, quant=None):
    """The routine, greedy: ``n`` tokens after ``prompt`` (what the last
    block holds beyond them is dropped). Returns (tokens, per token the
    denoising step of its block that unmasked it)."""
    prompt = [int(t) for t in prompt]
    left = len(prompt) % hp.block
    context = prompt[:len(prompt) - left]
    block = prompt[len(context):] + [hp.mask_id] * (hp.block - left)
    steps_of = [-1] * left + [0] * (hp.block - left)
    out, steps_out = [], []
    while len(out) < n:
        step = 0
        while hp.mask_id in block:
            logits = block_logits(w, context, block, hp, quant)
            new, _, _ = unmask_step(logits, block, step, hp)
            for j, (a, b) in enumerate(zip(block, new)):
                if a != b:
                    steps_of[j] = step
            block, step = new, step + 1
        out += [t for t, s in zip(block, steps_of) if s >= 0]
        steps_out += [s for s in steps_of if s >= 0]
        context = context + block
        block = [hp.mask_id] * hp.block
        steps_of = [0] * hp.block
    return out[:n], steps_out[:n]
