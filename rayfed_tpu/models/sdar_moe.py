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

"""SDAR-MoE (``model_type: sdar_moe``): routed experts in every layer
under grouped-query attention with per-head query/key norms, generating
by diffusion over blocks.

A layer is sequential: ``a = x + A(N1(x))``, ``y = a + F(N2(a))``, both
norms RMSNorm with a learned scale. Attention projects ``h`` to ``H``
query heads over ``Hkv`` K/V heads of ``Dh``; each query and key head is
RMS-normed over its ``Dh`` values with one learned ``(Dh,)`` scale for all
query heads and one for all key heads, BEFORE the rotation (by halves,
``rotate_half``); K/V head ``g // (H / Hkv)`` serves query head ``g``. The
mask is **block-causal** with block length ``B``: key ``j`` is visible to
query ``i`` iff ``j // B <= i // B`` (every earlier block whole, the own
block in both directions). ``F`` is the routed experts alone (softmax
scores over all experts in float32, the ``k`` largest, normalised over the
``k``; gated SiLU; :func:`rayfed_tpu.models.moe.routed_experts` told the
scoring): no shared expert, no scale. The head is untied, and **the logits
at position ``i`` are for the token AT position ``i``**: a masked position
predicts itself.

Generation (the family's published routine; the numbers are fields of the
configuration): a prompt's whole blocks are prefilled under the
block-causal mask and their K/V kept. Then block after block: the block
starts as the prompt's left-over tokens followed by the mask id; a
forward over the clean earlier blocks chooses a candidate and a
confidence for every masked position and unmasks some
(:func:`rayfed_tpu.serving.sampling.unmask`), its K/V NOT kept; once the
block holds no mask id one more forward commits it (K/V kept) and the
next block opens. Under the block-causal mask that forward and the next
block's first denoising forward are ONE forward: nothing of a block
depends on the block behind it. So a row of the serving engine's decode
step (:mod:`rayfed_tpu.serving.server`; :func:`paged_decode_step`, told
which rows commit) forwards a PAIR of blocks, the carried one and a block
of mask ids behind it, and no forward is spent on a commit alone. The
engine learns of all this through the one optional member of the protocol
this model adds: ``block_spec()`` (a ``decode.BlockSpec``).

The layers are a LIST of per-layer trees and the programs walk it in
Python (a scan hands a layer its weights as a copy of its slice of the
stack: :mod:`rayfed_tpu.models.cohere2_moe`). Norms, the router's scores,
softmaxes and the sums over experts are float32; matmuls take
compute-dtype operands and accumulate in float32.

Parameter tree (``Eh`` held experts of width ``f``; leaves in
``param_dtype``)::

    embed (V, d)   ln_f (d)   lm_head (V, d)
    layers[i]: ln1 ln2 (d)   q_norm k_norm (Dh)
               wq (d, H*Dh)  wk wv (d, Hkv*Dh)  wo (H*Dh, d)
               router (d, E)
               we_gate we_up (Eh, d, f)   we_down (Eh, f, d)
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
RULES = ("low_confidence_dynamic", "low_confidence_static")


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    d_expert: int = 768
    n_experts: int = 128
    top_k: int = 8
    # Global ids of the routed experts whose weights are here; None: all.
    held: Optional[Tuple[int, ...]] = None
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    # Generation by blocks (the family's inference routine).
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_id: int = 151669
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", tuple(range(self.n_experts)))
        held = tuple(int(e) for e in self.held)
        object.__setattr__(self, "held", held)
        b = self.block_length
        if (self.n_heads % self.n_kv_heads or self.head_dim % 2
                or not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_experts
                or not 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                "sdar_moe: query heads must be a multiple of K/V heads, the "
                "head size even, the held experts distinct ids under "
                f"n_experts and top_k at most n_experts: {self}"
            )
        if b < 1 or b & (b - 1):
            raise ValueError(
                f"sdar_moe: block_length={b} is not a power of two (the "
                "block-causal masks are k_pos <= q_pos | (block_length - 1))"
            )
        if not 1 <= self.denoising_steps <= b:
            raise ValueError(
                f"sdar_moe: denoising_steps={self.denoising_steps} must lie "
                f"in 1..block_length ({b}): every step unmasks a position"
            )
        if self.remasking not in RULES:
            raise ValueError(
                f"sdar_moe: remasking={self.remasking!r} is not computed "
                f"here (only {RULES})"
            )
        if not 0 <= self.mask_id < self.vocab:
            raise ValueError(
                f"sdar_moe: mask_id={self.mask_id} is no id of a vocabulary "
                f"of {self.vocab}"
            )

    @classmethod
    def from_published(cls, config: Dict[str, Any], **overrides):
        """The configuration from the keys of a published ``config.json``
        (``model_type: sdar_moe``): every expert held, the whole
        vocabulary. What is not computed here is refused by name. The
        generation routine's numbers are not in ``config.json``; they are
        fields of this class and come in as ``overrides``."""
        c = config
        if c.get("rope_scaling") is not None:
            raise ValueError(
                f"sdar_moe: rope_scaling={c['rope_scaling']!r} is not "
                "computed here (plain rotation only)")
        for key, want in (
            ("use_sliding_window", False), ("mlp_only_layers", []),
            ("decoder_sparse_step", 1), ("norm_topk_prob", True),
            ("hidden_act", "silu"), ("attention_bias", False),
            ("tie_word_embeddings", False),
        ):
            if c.get(key, want) != want:
                raise ValueError(
                    f"sdar_moe: {key}={c[key]!r} is not computed here "
                    f"(only {want!r})"
                )
        fields = dict(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_expert=c["moe_intermediate_size"], n_experts=c["num_experts"],
            top_k=c["num_experts_per_tok"],
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
        )
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Pieces of a layer
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last dimension;
    float32 inside, the input's dtype out."""
    x32 = x.astype(F32)
    out = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (out * scale.astype(F32)).astype(x.dtype)


def rope_halves(x, positions, theta: float):
    """Rotary positions over the whole head, by halves (``rotate_half``):
    dimensions ``(i, i + Dh/2)`` turn by ``position * theta ** (-2i /
    Dh)``. ``x`` (..., S, H, Dh), ``positions`` (..., S)."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions[..., None].astype(F32) * freqs       # (..., S, Dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(F32)
    lo, hi = x32[..., :dh // 2], x32[..., dh // 2:]
    out = jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)
    return out.astype(x.dtype)


def qkv(h, layer, positions, cfg: SdarMoeConfig):
    """Q (..., S, H, Dh) and K, V (..., S, Hkv, Dh) of a normed ``h``
    (..., S, d): each query and key head normed over its own values, then
    rotated."""
    cdt = cfg.compute_dtype

    def proj(w):
        out = jnp.einsum("...sd,df->...sf", h, w.astype(cdt),
                         preferred_element_type=F32).astype(cdt)
        return out.reshape(*out.shape[:-1], -1, cfg.head_dim)

    q = rms_norm(proj(layer["wq"]), layer["q_norm"], cfg.rms_eps)
    k = rms_norm(proj(layer["wk"]), layer["k_norm"], cfg.rms_eps)
    return (rope_halves(q, positions, cfg.rope_theta),
            rope_halves(k, positions, cfg.rope_theta), proj(layer["wv"]))


def attn_out(o, layer, cfg: SdarMoeConfig):
    """(..., S, H, Dh) -> (..., S, d) float32."""
    return jnp.einsum("...sf,fd->...sd", o.reshape(*o.shape[:-2], -1),
                      layer["wo"].astype(cfg.compute_dtype),
                      preferred_element_type=F32)


def seq_attention(q, k, v, cfg: SdarMoeConfig):
    """Block-causal attention of one sequence's queries (S, H, Dh) over
    its keys (S, Hkv, Dh), both at positions 0..S-1. Softmax in
    float32."""
    with jax.named_scope("serve/attn_block"):
        s, h, dh = q.shape
        hkv = k.shape[1]
        qg = q.reshape(s, hkv, h // hkv, dh)
        scores = jnp.einsum("qhgd,khd->hgqk", qg, k,
                            preferred_element_type=F32) * dh**-0.5
        pos = jnp.arange(s)
        mask = pos[None, :] <= (pos | (cfg.block_length - 1))[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v)
        return o.reshape(s, h, dh)


def ffn(h, layer, cfg: SdarMoeConfig, live=None):
    """The routed experts of a normed ``h`` (T, d): ((T, d) float32,
    experts hit, assignments on held experts)."""
    return moe.routed_experts(h, layer, cfg.held, cfg.top_k, live,
                              scoring="softmax")


def _layer(x, layer, positions, live, cfg: SdarMoeConfig, attend):
    """One layer over ``x`` (..., S, d) with the caller's attention
    ``attend(q, k, v)``. Returns (x, k, v, experts hit, assignments)."""
    cdt = cfg.compute_dtype
    q, k, v = qkv(rms_norm(x, layer["ln1"], cfg.rms_eps), layer, positions,
                  cfg)
    a = (x.astype(F32) + attn_out(attend(q, k, v), layer, cfg)).astype(cdt)
    h = rms_norm(a, layer["ln2"], cfg.rms_eps)
    f, hit, local = ffn(h.reshape(-1, h.shape[-1]), layer, cfg, live)
    return ((a.astype(F32) + f.reshape(a.shape)).astype(cdt), k, v, hit,
            local)


def _embed(params, tokens, cfg: SdarMoeConfig):
    return params["embed"][tokens].astype(cfg.compute_dtype)


def _head(x, params, cfg: SdarMoeConfig):
    """Logits (.., V) float32 of hidden states ``x`` (.., d): the final
    norm, then the untied head."""
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    return jnp.einsum(
        "...d,vd->...v", x, params["lm_head"].astype(cfg.compute_dtype),
        preferred_element_type=F32)


def _seq_layers(x, params, live, cfg: SdarMoeConfig):
    """The stack over one sequence ``x`` (S, d) from position 0. Returns
    (x, K (L, S, Hkv, Dh), V)."""
    positions = jnp.arange(x.shape[0])
    ks, vs = [], []
    for layer in params["layers"]:
        x, k, v, _, _ = _layer(
            x, layer, positions, live, cfg,
            lambda q, k, v: seq_attention(q, k, v, cfg))
        ks.append(k)
        vs.append(v)
    return x, jnp.stack(ks), jnp.stack(vs)


# ---------------------------------------------------------------------------
# Whole-model programs
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: SdarMoeConfig):
    """tokens (B, S) -> logits (B, S, V) float32 under the block-causal
    mask: no cache, every position real, one sequence at a time."""
    def one(row):
        x, _, _ = _seq_layers(_embed(params, row, cfg), params, None, cfg)
        return _head(x, params, cfg)

    return jax.lax.map(one, tokens)


def prefill_rows(params, prompts, last_idx, cache_dtype, cfg: SdarMoeConfig,
                 landed=None):
    """Right-padded prompts (R, S), each real up to ``last_idx`` (R,) (a
    whole number of blocks: the engine stops a prompt's prefill at its
    last whole block), from an empty cache. Returns K/V rows (L, R, S,
    Hkv, Dh), as long as the bucket. A prompt's last position predicts
    itself, so no logits are computed: a (R, 1) zero stands where the
    protocol hands some back.

    Only the rows ``landed`` (R,) bool names are computed, one at a time
    (:func:`decode.landed_rows`)."""
    r, s = prompts.shape
    cache_dtype = cache_dtype or cfg.compute_dtype

    def one_row(i):
        _, k, v = _seq_layers(
            _embed(params, prompts[i], cfg), params,
            jnp.arange(s) <= last_idx[i], cfg)
        return (jnp.zeros((1,), F32), k.astype(cache_dtype),
                v.astype(cache_dtype))

    kv = jnp.zeros((cfg.n_layers, r, s, cfg.n_kv_heads, cfg.head_dim),
                   cache_dtype)
    return decode.landed_rows(
        one_row, landed, (jnp.zeros((r, 1), F32), kv, kv))


def _paged(attend, cache_dtype, base):
    """A layer's attention through the pool: ``attend`` (one of
    :mod:`decode`'s paged reads) at the layer's first physical block
    ``base``, the new keys and values in the cache's type."""
    def paged(q, k, v):
        with jax.named_scope("serve/attn_block"):
            return attend(q, k.astype(cache_dtype), v.astype(cache_dtype),
                          base)

    return paged


def chunk(params, pk, pv, table, toks, offset, n_real, cfg: SdarMoeConfig):
    """One prompt chunk ``toks`` (C,), real up to ``n_real``, at positions
    ``offset .. offset + C - 1`` (``offset``, ``C`` and ``n_real`` whole
    blocks) of the slot whose block table is ``table``: its context read
    from the pool through the table, the chunk's own keys under the
    block-causal mask (:func:`decode.paged_chunk_attention`), its own K/V
    written in place after the last layer. Returns (a zero where the
    protocol hands back logits, (1,): nothing is predicted from a prompt,
    the pool)."""
    clen = toks.shape[0]
    n_phys = pk.shape[1]
    positions = offset + jnp.arange(clen)
    live = jnp.arange(clen) < n_real
    attend = decode.paged_chunk_attention(
        pk, pv, table, offset, n_real, block=cfg.block_length)
    x = _embed(params, toks, cfg)
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        x, k, v, _, _ = _layer(x, layer, positions, live, cfg,
                               _paged(attend, pk.dtype, i * n_phys))
        ks.append(k.astype(pk.dtype))
        vs.append(v.astype(pv.dtype))
    pk, pv = decode.paged_chunk_write(
        pk, pv, jnp.stack(ks), jnp.stack(vs), table, offset)
    return jnp.zeros((1,), F32), pk, pv


def paged_decode_step(params, pk, pv, tokens, positions, tables, live,
                      commit, cfg: SdarMoeConfig):
    """One forward of every row's carried block AND the block behind it:
    ``tokens`` (R, B) the carried blocks (the mask id where a position is
    still masked), ``positions`` (R,) each carried block's first
    position. A row forwards ``2B`` positions: its carried block, then a
    block of mask ids. The clean earlier blocks are read through the
    block tables, once for all of a row's queries, and the pair's own
    keys beside the pool under the block-causal mask: the carried block's
    queries see the carried block, the second block's see both
    (:func:`decode.paged_block_attention`).

    The rows that ``commit`` (R,) bool names carry a clean block: its K/V
    are written in place (:func:`decode.paged_block_write`: the one place
    K/V of generated tokens are kept; the carried half only, every other
    row's land in the sacrificial block), and the second half is the
    first denoising forward of the block that follows. Every other row
    denoises its carried block, and its second half is junk. ``live``
    (R,) bool names the rows that are requests: only their carried
    blocks, and the second blocks of those that commit, are routed to
    experts. Returns (logits (R, B, V) at the positions of the half that
    denoises: the second where a row commits, else the carried one; pk,
    pv, counters (2,) int32 as
    :func:`rayfed_tpu.models.cohere2_moe.paged_decode_step` counts
    them)."""
    n_rows, n_pos = tokens.shape
    n_phys = pk.shape[1]
    attend = decode.paged_block_attention(
        pk, pv, jnp.stack([positions, positions + n_pos], axis=1), tables)
    pos = positions[:, None] + jnp.arange(2 * n_pos)
    live_pos = jnp.repeat(
        jnp.stack([live, commit], axis=1), n_pos, axis=1).reshape(-1)
    x = _embed(params, jnp.concatenate(
        [tokens, jnp.full_like(tokens, cfg.mask_id)], axis=1), cfg)
    hit = local = jnp.asarray(0, jnp.int32)
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        x, k, v, n_hit, n_local = _layer(
            x, layer, pos, live_pos, cfg,
            _paged(attend, pk.dtype, i * n_phys))
        hit, local = hit + n_hit, local + n_local
        ks.append(k[:, :n_pos].astype(pk.dtype))
        vs.append(v[:, :n_pos].astype(pv.dtype))
    with jax.named_scope("serve/commit"):
        pk, pv = decode.paged_block_write(
            pk, pv, jnp.stack(ks), jnp.stack(vs), positions, tables, commit)
    x = jnp.where(commit[:, None, None], x[:, n_pos:], x[:, :n_pos])
    return _head(x, params, cfg), pk, pv, jnp.stack([hit, local])


class SdarMoeServing:
    """What the serving engine asks of this model (the protocol of
    :class:`rayfed_tpu.models.decode.TransformerServing`), with ``step_
    counters`` as the other expert models declare them and the ONE member
    this model adds: ``block_spec()``. An engine that finds it carries a
    block a row and hands ``decode_step`` the rows that commit; the
    logits it gets back are those of the block each row denoises (the one
    behind the carried block where that one commits)."""

    step_counters = ("moe_experts_hit", "moe_assignments_local")

    def __init__(self, cfg: SdarMoeConfig):
        self.cfg = cfg

    def kv_spec(self):
        head = (self.cfg.n_kv_heads, self.cfg.head_dim)
        return (self.cfg.n_layers, head), (self.cfg.n_layers, head)

    def state_spec(self, cache_dtype=None):
        return {}

    def serving_dtype(self):
        return None

    def block_spec(self) -> decode.BlockSpec:
        c = self.cfg
        return decode.BlockSpec(
            length=c.block_length, mask_id=c.mask_id,
            steps=c.denoising_steps, rule=c.remasking,
            threshold=c.confidence_threshold)

    def prefill_rows(self, params, prompts, last_idx, row_len, cache_dtype,
                     landed):
        last, k, v = prefill_rows(
            params, prompts, last_idx, cache_dtype, self.cfg, landed)
        return last, (k, v), {}

    def chunk(self, params, kv, state, table, slot, toks, offset, n_real):
        last, pk, pv = chunk(
            params, *kv, table, toks, offset, n_real, self.cfg)
        return last, (pk, pv), state

    def decode_step(self, params, kv, state, tokens, positions, tables,
                    live, commit):
        logits, pk, pv, counters = paged_decode_step(
            params, *kv, tokens, positions, tables, live, commit, self.cfg)
        return logits, (pk, pv), state, counters


def serving_model(cfg: SdarMoeConfig) -> SdarMoeServing:
    return SdarMoeServing(cfg)
