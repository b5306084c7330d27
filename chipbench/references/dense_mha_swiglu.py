"""Plain reference: a dense decoder with multi-head attention, SwiGLU,
RMSNorm and rotary positions (DeepSeek-LLM / DeepSeek-Coder dense, as in
their published ``config.json`` and modelling code).

float32 ``jax.numpy`` at matmul precision "highest"; no kernels, no cache,
no batching: one sequence at a time, full causal attention. Forward, loss,
gradients and AdamW are written out here. It imports nothing of the
program and reads only the canonical weights of ``chipbench/seeded.py``.

Departure from the source: deepseek-coder's linear RoPE scaling (factor 4)
is not applied, because the program has none; both sides see the same
positions.

``quant`` runs the same mathematics in a lower precision, for the control
that must come out as not correct: "bf16" rounds every matmul operand to
bfloat16; "fp8" rounds it to float8_e4m3 under a per-tensor scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.compare import leaf_norms, to_host

HI = jax.lax.Precision.HIGHEST


def _round_operand(x, quant):
    """Round ``x`` to the lower precision's grid; the gradient passes
    straight through, as it does in a mixed-precision step."""
    if quant is None:
        return x
    if quant == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + jax.lax.stop_gradient(r - x)


def mm(a, b, quant=None):
    return jnp.matmul(_round_operand(a, quant), _round_operand(b, quant),
                      precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (S, H, Dh); rotate-half form, frequencies theta**(-2i/Dh)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _head_attention(q, k, v, mask, quant):
    """One head: q k v (S, Dh) -> (S, Dh). Checkpointed, so that the
    (S, S) scores of one head at a time are all that is ever held."""
    scores = jnp.matmul(q, k.T, precision=HI) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.matmul(_round_operand(probs, quant), v, precision=HI)


def _layer(x, lay, positions, heads, theta, eps, quant):
    s, d = x.shape
    dh = d // heads
    h = rms_norm(x, lay["ln1"], eps)
    q = rope(mm(h, lay["wq"], quant).reshape(s, heads, dh), positions, theta)
    k = rope(mm(h, lay["wk"], quant).reshape(s, heads, dh), positions, theta)
    v = mm(h, lay["wv"], quant).reshape(s, heads, dh)
    qh, kh, vh = (_round_operand(t, quant).transpose(1, 0, 2)
                  for t in (q, k, v))
    mask = positions[:, None] >= positions[None, :]
    o = jax.lax.map(
        jax.checkpoint(lambda t: _head_attention(*t, mask, quant)),
        (qh, kh, vh))
    x = x + mm(o.transpose(1, 0, 2).reshape(s, d), lay["wo"], quant)
    h2 = rms_norm(x, lay["ln2"], eps)
    gate = jax.nn.silu(mm(h2, lay["w_gate"], quant))
    return x + mm(gate * mm(h2, lay["w_up"], quant), lay["w_down"], quant)


def hidden(w, tokens, heads, theta, eps, quant=None):
    """tokens (S,) -> final normed hidden states (S, d)."""
    positions = jnp.arange(tokens.shape[0])
    body = jax.checkpoint(
        lambda x, lay: (_layer(x, lay, positions, heads, theta, eps, quant),
                        None))
    x, _ = jax.lax.scan(body, w["embed"][tokens], w["layers"])
    return rms_norm(x, w["ln_f"], eps)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def logits_at(w, tokens, idx, heads, theta, eps, quant=None):
    """Logits (len(idx), V) at positions ``idx`` of one sequence, from one
    full forward pass. ``tokens`` may be padded on the right: a causal
    model's earlier positions cannot see the padding."""
    x = hidden(w, tokens, heads, theta, eps, quant)
    return mm(x[idx], w["lm_head"], quant)


def _row_loss_sum(w, inputs, targets, heads, theta, eps, quant):
    x = hidden(w, inputs, heads, theta, eps, quant)
    logits = mm(x, w["lm_head"], quant)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.sum(logz - gold)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def loss_and_grads(w, inputs, targets, heads, theta, eps, quant=None):
    """Mean next-token cross entropy over (B, S) and its gradient. Rows
    go one at a time through a checkpointed scan, so the gradient is
    accumulated in place and one row's activations are all that is held."""
    def total(w):
        row = jax.checkpoint(lambda acc, r: (
            acc + _row_loss_sum(w, r[0], r[1], heads, theta, eps, quant),
            None))
        return jax.lax.scan(row, jnp.zeros((), jnp.float32),
                            (inputs, targets))[0] / inputs.size

    return jax.value_and_grad(total)(w)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   donate_argnums=(0, 1, 2, 3))
def adamw_apply(w, mu, nu, grads, lr, b1, b2, eps, wd, count=1.0):
    """AdamW as published (decoupled decay, bias-corrected moments)."""
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    w = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p),
        w, mu, nu)
    return w, mu, nu


def train_readings(make_w, change_norms, batches, heads, theta, eps, opt,
                   quant=None):
    """Follow the program's first ``len(batches)`` steps from
    ``make_w()``; ``change_norms(w)`` gives the leaf norms of ``w`` less
    the seeded start. Returns the losses, the first gradient's leaf norms and
    the leaf norms of the parameters' change over all the steps, as host
    numbers. Moments are made only after the first gradient, so the peak
    is four trees and one row's activations."""
    w = make_w()
    mu = nu = None
    losses, grad_norms = [], None
    for i, (inputs, targets) in enumerate(batches):
        loss, grads = loss_and_grads(w, inputs, targets, heads, theta, eps,
                                     quant)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = to_host(leaf_norms(grads))
            mu = jax.tree_util.tree_map(jnp.zeros_like, w)
            nu = jax.tree_util.tree_map(jnp.zeros_like, w)
        w, mu, nu = adamw_apply(
            w, mu, nu, grads, opt["lr"], opt["b1"], opt["b2"], opt["eps"],
            opt["weight_decay"], jnp.float32(i + 1))
        del grads
    del mu, nu
    change = to_host(change_norms(w))
    return losses, grad_norms, change
