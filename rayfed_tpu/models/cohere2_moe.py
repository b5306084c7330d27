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

"""Cohere2-MoE (``model_type: cohere2_moe``): routed and shared experts in
a parallel block, over layers of two kinds.

Every layer reads ONE bias-free LayerNorm ``h = LN(x)`` into three
branches that are summed into the residual side by side (the parallel
block): grouped-query attention, the routed experts (sigmoid scores over
all experts, the ``k`` largest, normalised over the ``k``; gated SiLU) and
the mean of the shared experts: ``y = x + A(h) + F_routed(h) +
F_shared(h)``. Layers come in two kinds in one stack (``layer_types``):

* ``sliding``: queries and keys rotated over the whole head in adjacent
  pairs (``rope_gptj``), and a token attends the last ``window`` keys,
  its own among them;
* ``full``: nothing is rotated (no positions) and a token attends every
  key before it.

A chip may hold a share of each layer (``held``: the routed experts whose
weights are here, of ``n_experts`` the router scores; a slice of the tied
vocabulary). It then computes what its own experts add
(:func:`rayfed_tpu.models.moe.routed_experts`); attention and the shared
experts are whole.

The serving engine (:mod:`rayfed_tpu.serving.server`) takes this module
through :func:`serving_model`, the protocol of
:class:`rayfed_tpu.models.decode.TransformerServing`. Its K/V are the
paged pool's own (no state beside them): the decode step and the prompt
chunk read them through the block tables and write theirs in place, each
building one ``decode.paged_attention`` / ``decode.paged_chunk_attention``
per kind of layer over the same pool. What it adds to the protocol is
optional and declared: ``layer_windows()`` (the engine counts the blocks
each layer must read) and ``step_counters`` (numbers only the device
knows, appended to the ids a decode step returns).

The layers are a LIST of per-layer trees and the programs walk it in
Python: one decode program and one program per chunk size, each layer's
kind static inside it. Not a ``lax.scan`` over stacked leaves: a scan (or
a slice of a stack) hands a layer its weights as a *copy* of its slice of
the stack, every execution (compiled for a v5e at the published widths
the chunk program held 6.5 GB of such copies, and an expert's matrices
were copied before every matmul that read them); a leaf that is an array
of its own is read where it lies.

LayerNorm, the router's scores, softmax and the sums over experts are
float32; matmuls take compute-dtype operands and accumulate in float32.

Parameter tree (``Eh`` held experts, ``S`` shared experts of width
``f``; leaves in ``param_dtype``)::

    embed (V, d)   ln_f (d)          the head is the embedding (tied)
    layers[i]: ln (d)
               wq (d, H*Dh)  wk wv (d, Hkv*Dh)  wo (H*Dh, d)
               router (d, E)
               we_gate we_up (Eh, d, f)   we_down (Eh, f, d)
               ws_gate ws_up (d, S*f)     ws_down (S*f, d)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from rayfed_tpu.models import decode
from rayfed_tpu.models import moe

Params = Dict[str, Any]
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    d_expert: int = 4096
    n_experts: int = 128
    top_k: int = 8
    n_shared: int = 4
    # Global ids of the routed experts whose weights are here; None: all.
    held: Optional[Tuple[int, ...]] = None
    # One of "sliding" / "full" per layer.
    layer_types: Tuple[str, ...] = ("sliding", "sliding", "sliding",
                                    "full") * 8
    window: int = 4096
    rope_theta: float = 50000.0
    ln_eps: float = 1e-5
    logit_scale: float = 1.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", tuple(range(self.n_experts)))
        held = tuple(int(e) for e in self.held)
        object.__setattr__(self, "held", held)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if (self.n_heads % self.n_kv_heads or self.head_dim % 2
                or len(self.layer_types) != self.n_layers
                or set(self.layer_types) - {"sliding", "full"}
                or not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_experts
                or not 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                "cohere2_moe: query heads must be a multiple of K/V heads, "
                "the head size even, one layer type ('sliding' or 'full') "
                "per layer, the held experts distinct ids under n_experts "
                f"and top_k at most n_experts: {self}"
            )

    @classmethod
    def from_published(cls, config: Dict[str, Any], **overrides):
        """The configuration from the keys of a published ``config.json``
        (``model_type: cohere2_moe``): every expert held, the whole
        vocabulary. A chip's share overrides ``held`` (and ``vocab``,
        ``n_layers`` with ``layer_types``)."""
        c = config
        for key, want in (
            ("attention_bias", False), ("use_qk_norm", False),
            ("use_parallel_block", True), ("use_gated_activation", True),
            ("hidden_act", "silu"), ("expert_selection_fn", "sigmoid"),
            ("norm_topk_prob", True), ("tie_word_embeddings", True),
            ("position_embedding_type", "rope_gptj"), ("rotary_pct", 1),
            ("shared_expert_combination_strategy", "average"),
            ("first_k_dense_replace", 0),
        ):
            if c.get(key, want) != want:
                raise ValueError(
                    f"cohere2_moe: {key}={c[key]!r} is not computed here "
                    f"(only {want!r})"
                )
        n = int(c["num_hidden_layers"])
        kinds = tuple(
            {"sliding_attention": "sliding", "full_attention": "full"}[t]
            for t in c["layer_types"][:n]
        )
        fields = dict(
            vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n,
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_expert=c["intermediate_size"], n_experts=c["num_experts"],
            top_k=c["num_experts_per_tok"], n_shared=c["num_shared_experts"],
            layer_types=kinds, window=c["sliding_window"],
            rope_theta=float(c["rope_theta"]),
            ln_eps=float(c["layer_norm_eps"]),
            logit_scale=float(c.get("logit_scale", 1.0)),
        )
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Pieces of a layer
# ---------------------------------------------------------------------------


def layer_norm(x, scale, eps: float):
    """``(x - mean) / sqrt(var + eps) * scale``, no bias; float32 inside,
    the input's dtype out."""
    x32 = x.astype(F32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32)
    return out.astype(x.dtype)


def rope_pairs(x, positions, theta: float):
    """Rotary positions over the whole head, adjacent pairs (GPT-J's
    form): dimensions ``(2i, 2i+1)`` turn by ``position * theta **
    (-2i / Dh)``. ``x`` (..., S, H, Dh), ``positions`` (..., S)."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions[..., None].astype(F32) * freqs       # (..., S, Dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    pairs = x.astype(F32).reshape(*x.shape[:-1], dh // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def qkv(h, layer, positions, kind: str, cfg: Cohere2MoeConfig):
    """Q (..., S, H, Dh) and K, V (..., S, Hkv, Dh) of a normed ``h``
    (..., S, d): rotated on a sliding layer, as they are on a full one."""
    cdt = cfg.compute_dtype

    def proj(w):
        # A plain matmul, its heads split out afterwards.
        out = jnp.einsum("...sd,df->...sf", h, w.astype(cdt),
                         preferred_element_type=F32).astype(cdt)
        return out.reshape(*out.shape[:-1], -1, cfg.head_dim)

    q, k, v = proj(layer["wq"]), proj(layer["wk"]), proj(layer["wv"])
    if kind == "sliding":
        q = rope_pairs(q, positions, cfg.rope_theta)
        k = rope_pairs(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(o, layer, cfg: Cohere2MoeConfig):
    """(..., S, H, Dh) -> (..., S, d) float32."""
    return jnp.einsum("...sf,fd->...sd", o.reshape(*o.shape[:-2], -1),
                      layer["wo"].astype(cfg.compute_dtype),
                      preferred_element_type=F32)


def _scope(kind: str):
    return jax.named_scope(
        "serve/attn_window" if kind == "sliding" else "serve/attn_full")


def seq_attention(q, k, v, q_pos, kind: str, cfg: Cohere2MoeConfig):
    """Causal attention of queries (S, H, Dh) at positions ``q_pos`` (S,)
    over keys (Sk, Hkv, Dh) at positions 0..Sk-1, the last ``window`` of
    them on a sliding layer; K/V head i serves query heads i*G..(i+1)*G-1.
    Softmax in float32."""
    with _scope(kind):
        s, h, dh = q.shape
        sk, hkv = k.shape[0], k.shape[1]
        qg = q.reshape(s, hkv, h // hkv, dh)
        scores = jnp.einsum("qhgd,khd->hgqk", qg, k,
                            preferred_element_type=F32) * dh**-0.5
        k_pos = jnp.arange(sk)
        mask = k_pos[None, :] <= q_pos[:, None]
        if kind == "sliding":
            mask &= k_pos[None, :] > q_pos[:, None] - cfg.window
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v)
        return o.reshape(s, h, dh)


def ffn(h, layer, cfg: Cohere2MoeConfig, live=None):
    """Routed (the held experts' part) plus shared experts of a normed
    ``h`` (T, d). Returns ((T, d) float32, experts hit, assignments on
    held experts)."""
    routed, hit, local = moe.routed_experts(
        h, layer, cfg.held, cfg.top_k, live)
    return routed + moe.shared_experts(h, layer, cfg.n_shared), hit, local


def _embed(params, tokens, cfg: Cohere2MoeConfig):
    return params["embed"][tokens].astype(cfg.compute_dtype)


def _head(x, params, cfg: Cohere2MoeConfig):
    """Logits (.., V) float32 of hidden states ``x`` (.., d): the final
    norm, then the (held slice of the) tied embedding."""
    x = layer_norm(x, params["ln_f"], cfg.ln_eps)
    return jnp.einsum(
        "...d,vd->...v", x, params["embed"].astype(cfg.compute_dtype),
        preferred_element_type=F32,
    ) * cfg.logit_scale


def _seq_layers(x, params, positions, live, cfg: Cohere2MoeConfig,
                attend):
    """The stack over one sequence ``x`` (S, d). ``attend(q, k, v, kind)``
    is the caller's attention. Returns (x, K (L, S, Hkv, Dh), V)."""
    ks, vs = [], []
    for layer, kind in zip(params["layers"], cfg.layer_types, strict=True):
        h = layer_norm(x, layer["ln"], cfg.ln_eps)
        q, k, v = qkv(h, layer, positions, kind, cfg)
        att = attn_out(attend(q, k, v, kind), layer, cfg)
        f, _, _ = ffn(h, layer, cfg, live)
        x = (x.astype(F32) + att + f).astype(cfg.compute_dtype)
        ks.append(k)
        vs.append(v)
    return x, jnp.stack(ks), jnp.stack(vs)


# ---------------------------------------------------------------------------
# Whole-model programs
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: Cohere2MoeConfig):
    """tokens (B, S) -> logits (B, S, V) float32: no cache, every
    position real, one sequence at a time."""
    s = tokens.shape[1]
    positions = jnp.arange(s)

    def one(row):
        x, _, _ = _seq_layers(
            _embed(params, row, cfg), params, positions, None, cfg,
            lambda q, k, v, kind: seq_attention(
                q, k, v, positions, kind, cfg))
        return _head(x, params, cfg)

    return jax.lax.map(one, tokens)


def prefill_rows(params, prompts, last_idx, cache_dtype,
                 cfg: Cohere2MoeConfig, landed=None):
    """Right-padded prompts (R, S), each real up to ``last_idx`` (R,),
    from an empty cache. Returns the logits (R, V) at ``last_idx`` and
    K/V rows (L, R, S, Hkv, Dh): as long as the bucket, not as the
    cache's rows (the pool lands rows of the length they come in).

    Only the rows ``landed`` (R,) bool names are computed (all, when it
    is None), one at a time (:func:`decode.landed_rows`); the others
    come back zero and land in the sacrificial block."""
    r, s = prompts.shape
    cache_dtype = cache_dtype or cfg.compute_dtype
    positions = jnp.arange(s)

    def one_row(i):
        prompt, n_real = prompts[i], last_idx[i] + 1
        x, k, v = _seq_layers(
            _embed(params, prompt, cfg), params, positions,
            positions < n_real, cfg,
            lambda q, k, v, kind: seq_attention(
                q, k, v, positions, kind, cfg))
        last = jax.lax.dynamic_index_in_dim(x, n_real - 1, 0, keepdims=False)
        return (_head(last, params, cfg), k.astype(cache_dtype),
                v.astype(cache_dtype))

    kv = jnp.zeros((cfg.n_layers, r, s, cfg.n_kv_heads, cfg.head_dim),
                   cache_dtype)
    logits, k, v = decode.landed_rows(
        one_row, landed, (jnp.zeros((r, cfg.vocab), F32), kv, kv))
    return logits, k, v, {}


def chunk(params, pk, pv, table, toks, offset, n_real,
          cfg: Cohere2MoeConfig):
    """One prompt chunk ``toks`` (C,), real up to ``n_real``, at positions
    ``offset .. offset + C - 1`` of the slot whose block table is
    ``table``: its context read from the pool through the table and no
    further than it reaches (:func:`decode.paged_chunk_attention`; on a
    sliding layer from the window's first keys), its own K/V written
    there in place, once, after the last layer. ``pk``/``pv`` are
    donated. Returns the logits (V,) at the last real position and the
    pool."""
    clen = toks.shape[0]
    n_phys = pk.shape[1]
    positions = offset + jnp.arange(clen)
    live = jnp.arange(clen) < n_real
    attend = {
        "full": decode.paged_chunk_attention(pk, pv, table, offset, n_real),
        "sliding": decode.paged_chunk_attention(
            pk, pv, table, offset, n_real, window=cfg.window),
    }
    x = _embed(params, toks, cfg)
    ks, vs = [], []
    for i, (layer, kind) in enumerate(
            zip(params["layers"], cfg.layer_types, strict=True)):
        h = layer_norm(x, layer["ln"], cfg.ln_eps)
        q, k, v = qkv(h, layer, positions, kind, cfg)
        k, v = k.astype(pk.dtype), v.astype(pv.dtype)
        with _scope(kind):
            o = attend[kind](q, k, v, i * n_phys)
        att = attn_out(o, layer, cfg)
        f, _, _ = ffn(h, layer, cfg, live)
        x = (x.astype(F32) + att + f).astype(cfg.compute_dtype)
        ks.append(k)
        vs.append(v)
    pk, pv = decode.paged_chunk_write(
        pk, pv, jnp.stack(ks), jnp.stack(vs), table, offset)
    last = jax.lax.dynamic_index_in_dim(x, n_real - 1, 0, keepdims=False)
    return _head(last, params, cfg), pk, pv


def paged_decode_step(params, pk, pv, tokens, positions, tables, live,
                      cfg: Cohere2MoeConfig):
    """One decode token for every row: K/V read through the block tables
    (:func:`decode.paged_attention`; on a sliding layer from the window's
    first block) and written in place. ``live`` (R,) bool names the rows
    that are requests: the others are routed to no expert. Returns
    (logits (R, V), pk, pv, counters (2,) int32: held experts chosen by
    at least one live row and (row, expert) pairs on held experts, both
    summed over the layers)."""
    n_phys = pk.shape[1]
    attend = {
        "full": decode.paged_attention(pk, pv, positions, tables),
        "sliding": decode.paged_attention(
            pk, pv, positions, tables, window=cfg.window),
    }
    x = _embed(params, tokens, cfg)
    hit = local = jnp.asarray(0, jnp.int32)
    ks, vs = [], []
    for i, (layer, kind) in enumerate(
            zip(params["layers"], cfg.layer_types, strict=True)):
        h = layer_norm(x, layer["ln"], cfg.ln_eps)
        q, k, v = qkv(h[:, None], layer, positions[:, None], kind, cfg)
        k1 = k[:, 0].astype(pk.dtype)
        v1 = v[:, 0].astype(pv.dtype)
        with _scope(kind):
            o = attend[kind](q[:, 0], k1, v1, i * n_phys)
        att = attn_out(o[:, None], layer, cfg)[:, 0]
        f, n_hit, n_local = ffn(h, layer, cfg, live)
        x = (x.astype(F32) + att + f).astype(cfg.compute_dtype)
        hit, local = hit + n_hit, local + n_local
        ks.append(k1)
        vs.append(v1)
    pk, pv = decode.paged_write(
        pk, pv, jnp.stack(ks), jnp.stack(vs), positions, tables)
    return _head(x, params, cfg), pk, pv, jnp.stack([hit, local])


class Cohere2MoeServing:
    """What the serving engine asks of this model (the protocol of
    :class:`rayfed_tpu.models.decode.TransformerServing`), and the two
    optional members this model declares."""

    # Appended, in this order, to the ids a decode step returns.
    step_counters = ("moe_experts_hit", "moe_assignments_local")

    def __init__(self, cfg: Cohere2MoeConfig):
        self.cfg = cfg

    def kv_spec(self):
        head = (self.cfg.n_kv_heads, self.cfg.head_dim)
        return (self.cfg.n_layers, head), (self.cfg.n_layers, head)

    def state_spec(self, cache_dtype=None):
        return {}

    def serving_dtype(self):
        """As published (bfloat16): every leaf is read through a cast to
        the compute dtype, a no-op then."""
        return None

    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """Per layer, the keys a token attends counting its own, or None
        for every key."""
        return tuple(self.cfg.window if kind == "sliding" else None
                     for kind in self.cfg.layer_types)

    def prefill_rows(self, params, prompts, last_idx, row_len, cache_dtype,
                     landed):
        last, k, v, state = prefill_rows(
            params, prompts, last_idx, cache_dtype, self.cfg, landed)
        return last, (k, v), state

    def chunk(self, params, kv, state, table, slot, toks, offset, n_real):
        last, pk, pv = chunk(
            params, *kv, table, toks, offset, n_real, self.cfg)
        return last, (pk, pv), state

    def decode_step(self, params, kv, state, tokens, positions, tables,
                    live):
        logits, pk, pv, counters = paged_decode_step(
            params, *kv, tokens, positions, tables, live, self.cfg)
        return logits, (pk, pv), state, counters


def serving_model(cfg: Cohere2MoeConfig) -> Cohere2MoeServing:
    return Cohere2MoeServing(cfg)
