# Copyright 2026 The rayfed-tpu Authors.
#
# Licensed under the Apache License, Version 2.0 (the "License");
# you may not use this file except in compliance with the License.
# You may obtain a copy of the License at
#
#     http://www.apache.org/licenses/LICENSE-2.0
#
# Unless required by applicable law or agreed to in writing, software
# distributed under the License is distributed on an "AS IS" BASIS,
# WITHOUT WARRANTIES OR CONDITIONS OF ANY KIND, either express or implied.
# See the License for the specific language governing permissions and
# limitations under the License.

"""Decoder-only transformer LM (functional JAX, TPU-first).

The reference ships no models (its engine moves opaque payloads); this is
the flagship model family for federated LM training on party meshes — the
driver's graft entry jits its forward, and ``parallel/`` shards its train
step over party/data/model/seq mesh axes.

TPU-first design choices:
 - layer parameters are **stacked** along a leading (n_layers, ...) axis and
   the forward is a single ``lax.scan`` over layers: one compiled layer body
   regardless of depth, XLA-friendly, and the stacked leaves shard cleanly;
 - matmul-heavy blocks (QKV/O projections, SwiGLU) are einsums that tile
   onto the MXU; compute dtype is configurable (bf16 by default) with
   params and softmax/logsumexp accumulation kept in f32;
 - RoPE + causal attention with an optional ring-attention path
   (:mod:`rayfed_tpu.parallel.ring`) for sequence-parallel long context.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 1408  # SwiGLU (or per-expert MoE) hidden width
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # 0 = dense SwiGLU FFN; >0 = top-1 MoE FFN with this many experts
    # (expert-parallel over an "expert" mesh axis; see models/moe.py).
    n_experts: int = 0

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def serving_model(cfg: TransformerConfig):
    """What the serving engine asks of this model (see
    :class:`rayfed_tpu.models.decode.TransformerServing`)."""
    from rayfed_tpu.models import decode

    return decode.TransformerServing(cfg)


def tiny_config(**overrides) -> TransformerConfig:
    """A config small enough to compile in seconds on one chip / CPU sim."""
    base = dict(vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=176)
    base.update(overrides)
    return TransformerConfig(**base)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(rng, cfg: TransformerConfig) -> Params:
    d, h, dh, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    k_embed, k_layers, k_out = jax.random.split(rng, 3)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape) * (fan_in**-0.5)).astype(
            cfg.param_dtype
        )

    def layer(key):
        ks = jax.random.split(key, 7)
        out = {
            "ln1": jnp.ones((d,), cfg.param_dtype),
            "wq": dense(ks[0], (d, h, dh), d),
            "wk": dense(ks[1], (d, h, dh), d),
            "wv": dense(ks[2], (d, h, dh), d),
            "wo": dense(ks[3], (h, dh, d), h * dh),
            "ln2": jnp.ones((d,), cfg.param_dtype),
        }
        if cfg.n_experts > 0:
            from rayfed_tpu.models.moe import init_moe_ffn

            out["moe"] = init_moe_ffn(
                ks[4], d, f, cfg.n_experts, dtype=cfg.param_dtype
            )
        else:
            out.update(
                w_gate=dense(ks[4], (d, f), d),
                w_up=dense(ks[5], (d, f), d),
                w_down=dense(ks[6], (f, d), f),
            )
        return out

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[layer(k) for k in layer_keys]
    )
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, d)) * 0.02).astype(
            cfg.param_dtype
        ),
        "layers": stacked,
        "ln_f": jnp.ones((d,), cfg.param_dtype),
        # Untied output head: keeps vocab-dim sharding independent.
        "lm_head": dense(k_out, (d, cfg.vocab), d),
    }


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(dtype) * scale.astype(dtype)


def rope(q, k, positions, theta: float):
    """Rotary position embedding on (B, S, H, Dh) q/k."""
    dh = q.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )
        return out.astype(x.dtype)

    return rot(q), rot(k)


def causal_attention(q, k, v, q_offset=None):
    """Standard causal attention on (B, S, H, Dh); softmax in f32.

    ``q_offset`` shifts query positions (used by sequence-parallel callers
    where this shard's queries start at a global offset).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    scale = dh**-0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    q_pos = jnp.arange(sq)[:, None] + (0 if q_offset is None else q_offset)
    mask = q_pos >= jnp.arange(sk)[None, :]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


AttnFn = Callable[..., jax.Array]


def qkv_proj(x, layer: Params, positions, cfg: TransformerConfig):
    """Pre-norm + Q/K/V projections + RoPE for one block; shared by the
    training forward and the KV-cache decode path (models/decode.py)."""
    cdt = cfg.compute_dtype
    h = rms_norm(x, layer["ln1"])
    q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(cdt))
    q, k = rope(q, k, positions, cfg.rope_theta)
    return q, k, v


def ffn_apply(hmlp, layer: Params, cfg: TransformerConfig):
    """The block's FFN half on a pre-normed input: SwiGLU, or the MoE FFN
    when ``cfg.n_experts > 0``."""
    cdt = cfg.compute_dtype
    if cfg.n_experts > 0:
        from rayfed_tpu.models.moe import moe_ffn_apply

        moe = jax.tree_util.tree_map(
            lambda p: p.astype(cdt), layer["moe"]
        )
        return moe_ffn_apply(moe, hmlp)
    gate = jax.nn.silu(hmlp @ layer["w_gate"].astype(cdt))
    up = hmlp @ layer["w_up"].astype(cdt)
    return (gate * up) @ layer["w_down"].astype(cdt)


def layer_fn(x, layer: Params, positions, cfg: TransformerConfig,
             attn_fn: Optional[AttnFn] = None):
    """One pre-norm decoder block; ``attn_fn(q, k, v)`` is pluggable so
    sequence-parallel callers can swap in ring attention."""
    attn_fn = attn_fn or causal_attention
    cdt = cfg.compute_dtype
    q, k, v = qkv_proj(x, layer, positions, cfg)
    # Named for selective rematerialization: saving each layer's attention
    # output (B*S*D, the cheapest-to-keep/most-expensive-to-recompute
    # tensor) lets the remat backward skip re-running the attention kernel.
    o = checkpoint_name(attn_fn(q, k, v), "attn_out")
    x = x + jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(cdt))
    hmlp = rms_norm(x, layer["ln2"])
    return x + ffn_apply(hmlp, layer, cfg)


def hidden_states(params: Params, tokens, cfg: TransformerConfig,
                  attn_fn: Optional[AttnFn] = None,
                  positions=None, remat: bool = False) -> jax.Array:
    """tokens (B, S) int32 -> final hidden states (B, S, d_model), post
    final-norm, in compute dtype.

    Layers run under one ``lax.scan`` over the stacked parameters.
    ``remat=True`` checkpoints each layer (recompute activations in the
    backward pass — HBM for FLOPs, the standard trade for deep/long
    configs).
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed"][tokens].astype(cfg.compute_dtype)

    def body(x, layer):
        return layer_fn(x, layer, positions, cfg, attn_fn), None

    if remat:
        # prevent_cse=False: scan's loop semantics already block the CSE
        # that checkpoint's default barriers guard against; leaving them on
        # just costs XLA fusion opportunities. remat="attn" additionally
        # saves each layer's attention output (B*S*d_model bf16) so the
        # backward skips re-running the attention kernel — opt-in: the
        # named-save policy costs dramatically longer XLA compiles around
        # the Pallas custom_vjp under scan.
        policy = (
            jax.checkpoint_policies.save_only_these_names("attn_out")
            if remat == "attn" else None
        )
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["ln_f"])


def forward(params: Params, tokens, cfg: TransformerConfig,
            attn_fn: Optional[AttnFn] = None,
            positions=None, remat: bool = False) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab) float32."""
    x = hidden_states(params, tokens, cfg, attn_fn, positions, remat)
    return (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)


def lm_loss_pair(params: Params, inputs, targets, cfg: TransformerConfig,
                 attn_fn: Optional[AttnFn] = None,
                 remat: bool = False,
                 loss_chunk: Optional[int] = None) -> jax.Array:
    """Next-token cross entropy over pre-shifted (inputs, targets) pairs,
    both (B, S) — the sharding-friendly form (S stays divisible by the seq
    axis; no in-jit slicing of sharded dims). f32 accumulation.

    ``loss_chunk`` evaluates the vocab head + CE in checkpointed chunks of
    that many sequence positions, so the full (B, S, vocab) f32 logits
    never materialize — at 32k vocab they dominate step memory. Leave None
    when the sequence dim is sharded (chunking reshapes S).
    """
    # Named scopes are metadata on the device operations (a profile groups
    # by them; the backward shows as transpose(jvp(train/forward))); they
    # change no computation.
    with jax.named_scope("train/forward"):
        x = hidden_states(params, inputs, cfg, attn_fn, remat=remat)
    with jax.named_scope("train/loss_head"):
        return _head_loss(x, params, targets, cfg, loss_chunk)


def _head_loss(x, params: Params, targets, cfg: TransformerConfig,
               loss_chunk: Optional[int]) -> jax.Array:
    w = params["lm_head"].astype(cfg.compute_dtype)
    if not loss_chunk or x.shape[1] % loss_chunk:
        logits = (x @ w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return (logz - gold).mean()

    b, s, d = x.shape
    n = s // loss_chunk

    def chunk_ce(carry, xt):
        xc, tc = xt  # (B, chunk, D), (B, chunk)
        logits = (xc @ w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return carry + (logz - gold).sum(), None

    xs = jnp.moveaxis(x.reshape(b, n, loss_chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, n, loss_chunk), 1, 0)
    total, _ = jax.lax.scan(
        jax.checkpoint(chunk_ce, prevent_cse=False), jnp.zeros((), jnp.float32),
        (xs, ts),
    )
    return total / (b * s)


def lm_loss(params: Params, tokens, cfg: TransformerConfig,
            attn_fn: Optional[AttnFn] = None) -> jax.Array:
    """Next-token cross entropy over a (B, S+1) token block."""
    return lm_loss_pair(params, tokens[:, :-1], tokens[:, 1:], cfg, attn_fn)
