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

"""Pangu-Ultra-MoE (``model_type: pangu_ultra_moe``): multi-head latent
attention over a paged latent cache, sandwich norms, leading dense layers
and routed experts beside a shared one.

Every layer is ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(F(N3(a)))``:
four RMSNorms, the second and the fourth on a sub-block's OUTPUT before
it joins the residual (``sandwich_norm``). ``F`` is a SwiGLU in the first
``n_dense`` layers and the expert layer in the others: sigmoid scores
over all experts, the ``top_k`` largest, normalised over those and
multiplied by ``routed_scale``, plus the shared expert
(:func:`rayfed_tpu.models.moe.routed_experts`, ``shared_experts``: the
grouped path that :mod:`rayfed_tpu.models.cohere2_moe` serves too; a chip
may hold a share of the experts, ``held``).

**Latent attention.** A token's query goes through a low rank:
``cq = Nq(h Wqa)``, ``q = cq Wqb`` -> H heads of ``[qn | qr]`` (``d_nope
+ d_rope``), ``qr`` rotated. Its key and value come from ONE latent row:
``[ckv | kr] = h Wkva``, ``ckv = Nkv(ckv)`` (``kv_rank`` wide), ``kr``
rotated (``d_rope`` wide: one positional key for all heads); per head
``kn = ckv Wk_h``, ``v = ckv Wv_h``; scores ``(qn . kn + qr . kr) /
sqrt(d_nope + d_rope)``, causal softmax in float32, the output ``sum p
v`` through ``wo``. **What a token keeps in the pool is that row, ``[ckv
| kr]`` after the norm and the rotation** (``kv_spec``: one array of
``(kv_rank + d_rope,)`` a token; the pool allocates (L, blocks, block,
640): a row padded with zeros to whole tiles of the device's memory, as
a row-major array of 576-wide rows is held there anyway; 576 values where
per-head keys and values would be 32,768), and the two programs that read it do so in two forms
of the same mathematics:

* the decode step *absorbs* ``Wk`` into the query and ``Wv`` into the
  output: ``qn' = qn Wk_h^T`` (per head ``d_nope -> kv_rank``), scores
  ``[qn' | qr] . [ckv | kr]``, ``o' = sum p ckv``, ``o = o' Wv_h``. Every
  head reads the same row, so a row's scores are one ``(H x width) x
  (width x keys)`` product and its values are the first ``kv_rank``
  columns of the keys it gathered: one gather a trip
  (:func:`decode.paged_attention` with no value array). Expanding
  instead would cost ``2 kv_rank H (d_nope + d_v)`` operations a cached
  key a step (33 M at the published sizes) where the absorbed form
  costs ``2 H (2 kv_rank + d_rope)`` (0.28 M);
* a prompt chunk (and the bucketed prefill) *expands* ``kn | v`` from
  the rows a trip gathered (:func:`decode.paged_chunk_attention`'s
  ``expand``): with hundreds of queries against each key the expansion
  is shared, and a (query, key) pair costs ``2 H (d_nope + d_rope +
  d_v)`` where the absorbed form costs ``2 H (2 kv_rank + d_rope)``.

The serving engine takes this module through :func:`serving_model` (the
protocol of :class:`rayfed_tpu.models.decode.TransformerServing`);
``step_counters`` are the expert layer's two. The layers are a LIST of
per-layer trees walked in Python (not a scan over stacked leaves, which
hands each layer a copy of its weights: ``PERF.md`` section 6, PR 31).
The extra multi-token-prediction layer some checkpoints carry is not
served (the next token's logits do not depend on it).

Norms, the router's scores, softmax and the sums over experts are
float32; matmuls take compute-dtype operands and accumulate in float32.

Parameter tree (``Eh`` held experts of width ``f``; ``H`` heads; leaves
in ``param_dtype``; ``wk_b | wv_b`` are the published ``kv_b_proj``
split by what it yields)::

    embed (V, d)   ln_f (d)   lm_head (V, d)
    layers[i]: ln1 ln2 ln3 ln4 (d)
               wq_a (d, rq)   q_norm (rq)    wq_b (rq, H*(dn+dr))
               wkv_a (d, rkv+dr)             kv_norm (rkv)
               wk_b (rkv, H*dn)  wv_b (rkv, H*dv)   wo (H*dv, d)
       dense:  w_gate w_up (d, fd)   w_down (fd, d)
       expert: router (d, E)
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
class PanguUltraMoeConfig:
    vocab: int = 153600
    d_model: int = 7680
    n_layers: int = 61
    # Leading layers whose FFN is a dense SwiGLU of width d_dense.
    n_dense: int = 3
    n_heads: int = 128
    q_rank: int = 1536
    kv_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    d_dense: int = 18432
    d_expert: int = 2048
    n_experts: int = 256
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 2.5
    # Global ids of the routed experts whose weights are here; None: all.
    held: Optional[Tuple[int, ...]] = None
    rope_theta: float = 25600000.0
    rms_eps: float = 1e-5
    # Extra multi-token-prediction layers SERVED: none can be.
    nextn: int = 0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", tuple(range(self.n_experts)))
        held = tuple(int(e) for e in self.held)
        object.__setattr__(self, "held", held)
        if self.nextn:
            raise ValueError(
                "pangu_ultra_moe: num_nextn_predict_layers is not served "
                "(multi-token prediction: self-drafting is not computed "
                "here; the next token's logits do not depend on it)")
        if (self.d_rope % 2 or not 0 <= self.n_dense <= self.n_layers
                or not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_experts
                or not 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                "pangu_ultra_moe: the rotated part of a head must be even, "
                "the dense layers no more than the layers, the held "
                "experts distinct ids under n_experts and top_k at most "
                f"n_experts: {self}")

    @property
    def cache_width(self) -> int:
        """Values a token keeps a layer: the latent and the rotated key."""
        return self.kv_rank + self.d_rope

    @classmethod
    def from_published(cls, config: Dict[str, Any], **overrides):
        """The configuration from the keys of a published ``config.json``
        (``model_type: pangu_ultra_moe``): every expert held, the whole
        vocabulary. A chip's share overrides ``held`` (and ``vocab``,
        ``n_layers``, ``n_dense``). What is not computed here is refused
        by name."""
        c = config
        if c.get("rope_scaling") is not None:
            raise ValueError(
                f"pangu_ultra_moe: rope_scaling={c['rope_scaling']!r} is "
                "not computed here (plain rotary positions only)")
        for key, want in (
            ("attention_bias", False), ("hidden_act", "silu"),
            ("sandwich_norm", True), ("norm_topk_prob", True),
            ("tie_word_embeddings", False), ("scoring_func", "sigmoid"),
            ("n_group", 1), ("topk_group", 1),
        ):
            if c.get(key, want) != want:
                raise ValueError(
                    f"pangu_ultra_moe: {key}={c[key]!r} is not computed "
                    f"here (only {want!r})")
        n = int(c["num_hidden_layers"])
        fields = dict(
            vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n,
            n_dense=min(int(c["first_k_dense_replace"]), n),
            n_heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
            kv_rank=c["kv_lora_rank"], d_nope=c["qk_nope_head_dim"],
            d_rope=c["qk_rope_head_dim"], d_v=c["v_head_dim"],
            d_dense=c["intermediate_size"],
            d_expert=c["moe_intermediate_size"],
            n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            n_shared=c["n_shared_experts"],
            routed_scale=float(c.get("routed_scaling_factor", 1.0)),
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
        )
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Pieces of a layer
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    """``x / sqrt(mean x^2 + eps) * scale``; float32 inside, the input's
    dtype out."""
    x32 = x.astype(F32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * inv * scale.astype(F32)).astype(x.dtype)


def rope_halves(x, positions, theta: float):
    """Rotary positions by halves: dimension ``i`` of the first half
    turns with dimension ``i`` of the second by ``position * theta **
    (-2i / D)``. ``x`` (..., S, H, D), ``positions`` (..., S)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = positions[..., None].astype(F32) * freqs        # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(F32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _mm(x, w, cfg):
    """(..., a) @ (a, b) -> (..., b) float32; compute-dtype operands."""
    return jnp.einsum("...a,ab->...b", x, w.astype(cfg.compute_dtype),
                      preferred_element_type=F32)


def project_low_rank(h, layer, positions, cfg, q_scale=1.0, kv_scale=1.0):
    """The two low ranks of a normed ``h`` (..., S, d) at ``positions``
    (..., S), and the queries: ``cq`` (..., S, rq) the query's latent
    after its norm, ``qn`` (..., S, H, dn), ``qr`` (..., S, H, dr)
    rotated, and the latent row ``c`` (..., S, 1, rkv + dr): the normed
    latent beside the rotated positional key, as it is cached. ``cfg``
    is any object with the latent attention's sizes (``n_heads``,
    ``kv_rank``, ``d_nope``, ``d_rope``, ``d_v``, ``cache_width``,
    ``rope_theta``, ``rms_eps``, ``compute_dtype``): a model with two
    kinds of latent layers hands one per kind
    (:mod:`rayfed_tpu.models.dots3_note`). ``q_scale`` / ``kv_scale``
    are constants a model multiplies the two latents by after their
    norms (1: none, and nothing is traced for them)."""
    with jax.named_scope("serve/mla_project"):
        cdt = cfg.compute_dtype
        cq = rms_norm(_mm(h, layer["wq_a"], cfg), layer["q_norm"],
                      cfg.rms_eps)
        if q_scale != 1.0:
            cq = cq * q_scale
        cq = cq.astype(cdt)
        q = _mm(cq, layer["wq_b"], cfg)
        q = q.reshape(*q.shape[:-1], cfg.n_heads, cfg.d_nope + cfg.d_rope)
        qn = q[..., :cfg.d_nope].astype(cdt)
        qr = rope_halves(q[..., cfg.d_nope:], positions, cfg.rope_theta)
        ckr = _mm(h, layer["wkv_a"], cfg)
        ckv = rms_norm(ckr[..., :cfg.kv_rank], layer["kv_norm"],
                       cfg.rms_eps)
        if kv_scale != 1.0:
            ckv = ckv * kv_scale
        kr = rope_halves(ckr[..., None, cfg.kv_rank:], positions,
                         cfg.rope_theta)
        c = jnp.concatenate([ckv[..., None, :], kr], -1).astype(cdt)
        return cq, qn, qr.astype(cdt), c


def project(h, layer, positions, cfg: PanguUltraMoeConfig):
    """:func:`project_low_rank` for this model: ``qn``, ``qr`` and the
    latent row ``c``, nothing rescaled."""
    return project_low_rank(h, layer, positions, cfg)[1:]


def expand(c, layer, cfg: PanguUltraMoeConfig):
    """Per-head keys and values of latent rows ``c`` (K, 1, rkv + dr, or
    wider: a pool's rows come zero-padded to whole tiles):
    ``k`` (K, H, dn + dr) = ``[ckv Wk_h | kr]``, ``v`` (K, H, dv)."""
    cdt = cfg.compute_dtype
    ckv, kr = c[:, 0, :cfg.kv_rank], c[:, :, cfg.kv_rank:cfg.cache_width]

    def per_head(w, width):
        # The heads as a dimension of the PRODUCT (the weight's reshape
        # is free): the compiler may then lay the result out heads first
        # for a reader that wants it so (the chunk's kernel), where a
        # (K, H * width) product reshaped afterwards is transposed by
        # two copies of itself (PERF.md section 6, PR 45).
        w = w.astype(cdt).reshape(cfg.kv_rank, cfg.n_heads, width)
        return jnp.einsum("ka,ahd->khd", ckv, w,
                          preferred_element_type=F32).astype(cdt)

    kr = jnp.broadcast_to(kr, (kr.shape[0], cfg.n_heads, cfg.d_rope))
    return (jnp.concatenate(
        [per_head(layer["wk_b"], cfg.d_nope), kr.astype(cdt)], -1),
        per_head(layer["wv_b"], cfg.d_v))


def absorb_query(qn, qr, layer, cfg: PanguUltraMoeConfig):
    """``[qn Wk_h^T | qr]`` (..., H, rkv + dr): the query in the space of
    the cached row."""
    wk = layer["wk_b"].astype(cfg.compute_dtype).reshape(
        cfg.kv_rank, cfg.n_heads, cfg.d_nope)
    qc = jnp.einsum("...hn,chn->...hc", qn, wk, preferred_element_type=F32)
    return jnp.concatenate([qc.astype(cfg.compute_dtype), qr], -1)


def absorb_output(oc, layer, cfg: PanguUltraMoeConfig):
    """``o' Wv_h`` (..., H, dv) of attention outputs in the latent space
    ``oc`` (..., H, rkv)."""
    wv = layer["wv_b"].astype(cfg.compute_dtype).reshape(
        cfg.kv_rank, cfg.n_heads, cfg.d_v)
    return jnp.einsum("...hc,chv->...hv", oc, wv,
                      preferred_element_type=F32).astype(cfg.compute_dtype)


def attn_out(o, layer, cfg: PanguUltraMoeConfig):
    """(..., H, dv) -> (..., d) float32."""
    return _mm(o.reshape(*o.shape[:-2], -1), layer["wo"], cfg)


def seq_attention(q, k, v, q_pos):
    """Causal attention of queries (S, H, D) at positions ``q_pos`` (S,)
    over keys (Sk, H, D) and values (Sk, H, Dv) at positions 0..Sk-1
    (the expanded form). Softmax in float32."""
    with jax.named_scope("serve/attn_expand"):
        scores = jnp.einsum("qhd,khd->hqk", q, k,
                            preferred_element_type=F32) * q.shape[-1]**-0.5
        seen = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)


def dense_ffn(h, layer, cfg: PanguUltraMoeConfig):
    """SwiGLU of a leading dense layer, (T, d) -> (T, d) float32."""
    with jax.named_scope("serve/dense_ffn"):
        act = (jax.nn.silu(_mm(h, layer["w_gate"], cfg))
               * _mm(h, layer["w_up"], cfg)).astype(cfg.compute_dtype)
        return _mm(act, layer["w_down"], cfg)


def ffn(h, layer, cfg: PanguUltraMoeConfig, live=None):
    """A layer's FFN of a normed ``h`` (T, d): the dense SwiGLU where the
    layer holds one, else the routed experts held here (weights scaled by
    ``routed_scale``) plus the shared expert. Returns ((T, d) float32,
    experts hit, assignments on held experts)."""
    if "w_gate" in layer:
        zero = jnp.asarray(0, jnp.int32)
        return dense_ffn(h, layer, cfg), zero, zero
    routed, hit, local = moe.routed_experts(
        h, layer, cfg.held, cfg.top_k, live, scale=cfg.routed_scale)
    return routed + moe.shared_experts(h, layer, cfg.n_shared), hit, local


def _tail(x, att, layer, cfg: PanguUltraMoeConfig, live=None):
    """The rest of a block after attention's output projection ``att``
    (T, d) float32: the two sandwich norms around the residual adds and the FFN.
    Returns (x, experts hit, assignments)."""
    a = x + rms_norm(att, layer["ln2"], cfg.rms_eps).astype(x.dtype)
    f, hit, local = ffn(rms_norm(a, layer["ln3"], cfg.rms_eps), layer, cfg,
                        live)
    return (a + rms_norm(f, layer["ln4"], cfg.rms_eps).astype(x.dtype), hit,
            local)


def _embed(params, tokens, cfg: PanguUltraMoeConfig):
    return params["embed"][tokens].astype(cfg.compute_dtype)


def _head(x, params, cfg: PanguUltraMoeConfig):
    """Logits (.., V) float32 of hidden states ``x`` (.., d): the final
    norm, then the (held slice of the) head."""
    return jnp.einsum(
        "...d,vd->...v", rms_norm(x, params["ln_f"], cfg.rms_eps),
        params["lm_head"].astype(cfg.compute_dtype),
        preferred_element_type=F32)


def _seq_layers(x, params, positions, live, cfg: PanguUltraMoeConfig):
    """The stack over one sequence ``x`` (S, d) from an empty cache, in
    the expanded form. Returns (x, latent rows (L, S, 1, width))."""
    rows = []
    for layer in params["layers"]:
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        qn, qr, c = project(h, layer, positions, cfg)
        k, v = expand(c, layer, cfg)
        o = seq_attention(jnp.concatenate([qn, qr], -1), k, v, positions)
        x, _, _ = _tail(x, attn_out(o, layer, cfg), layer, cfg, live)
        rows.append(c)
    return x, jnp.stack(rows)


# ---------------------------------------------------------------------------
# Whole-model programs
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: PanguUltraMoeConfig):
    """tokens (B, S) -> logits (B, S, V) float32: no cache, every
    position real, one sequence at a time."""
    positions = jnp.arange(tokens.shape[1])

    def one(row):
        x, _ = _seq_layers(_embed(params, row, cfg), params, positions,
                           None, cfg)
        return _head(x, params, cfg)

    return jax.lax.map(one, tokens)


def prefill_rows(params, prompts, last_idx, cache_dtype,
                 cfg: PanguUltraMoeConfig, landed=None):
    """Right-padded prompts (R, S), each real up to ``last_idx`` (R,),
    from an empty cache. Returns the logits (R, V) at ``last_idx`` and
    the latent rows (L, R, S, width): as long as the bucket, not as
    the cache's rows (the pool lands rows of the length they come in).

    Only the rows ``landed`` (R,) bool names are computed (all, when it
    is None), one at a time (:func:`decode.landed_rows`); the others
    come back zero and land in the sacrificial block."""
    r, s = prompts.shape
    cache_dtype = cache_dtype or cfg.compute_dtype
    positions = jnp.arange(s)

    def one_row(i):
        prompt, n_real = prompts[i], last_idx[i] + 1
        x, c = _seq_layers(_embed(params, prompt, cfg), params, positions,
                           positions < n_real, cfg)
        last = jax.lax.dynamic_index_in_dim(x, n_real - 1, 0, keepdims=False)
        return _head(last, params, cfg), c[:, :, 0].astype(cache_dtype)

    return decode.landed_rows(one_row, landed, (
        jnp.zeros((r, cfg.vocab), F32),
        jnp.zeros((cfg.n_layers, r, s, cfg.cache_width), cache_dtype)))


def chunk(params, pc, table, toks, offset, n_real,
          cfg: PanguUltraMoeConfig):
    """One prompt chunk ``toks`` (C,), real up to ``n_real``, at positions
    ``offset .. offset + C - 1`` of the slot whose block table is
    ``table``: its context's latent rows gathered from the pool ``pc``
    through the table a trip at a time and expanded to keys and values
    there (:func:`decode.paged_chunk_attention`), its own rows written in
    place, once, after the last layer. ``pc`` is donated. Returns the
    logits (V,) at the last real position and the pool."""
    clen = toks.shape[0]
    n_phys = pc.shape[1]
    positions = offset + jnp.arange(clen)
    live = jnp.arange(clen) < n_real
    attend = decode.paged_chunk_attention(pc, None, table, offset, n_real)
    x = _embed(params, toks, cfg)
    rows = []
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        qn, qr, c = project(h, layer, positions, cfg)
        c = c.astype(pc.dtype)
        k, v = expand(c, layer, cfg)
        with jax.named_scope("serve/attn_expand"):
            o = attend(jnp.concatenate([qn, qr], -1), k, v, i * n_phys,
                       lambda cached, layer=layer: expand(cached, layer, cfg))
        x, _, _ = _tail(x, attn_out(o, layer, cfg), layer, cfg, live)
        rows.append(c[:, 0])
    pc, _ = decode.paged_chunk_write(
        pc, None, jnp.stack(rows), None, table, offset)
    last = jax.lax.dynamic_index_in_dim(x, n_real - 1, 0, keepdims=False)
    return _head(last, params, cfg), pc


def paged_decode_step(params, pc, tokens, positions, tables, live,
                      cfg: PanguUltraMoeConfig):
    """One decode token for every row, in the absorbed form: ``Wk``
    multiplied into the query and ``Wv`` into the output, each cached row
    read once through the block tables as key and value
    (:func:`decode.paged_attention` with no value array), the new rows
    written in place. ``live`` (R,) bool names the rows that are
    requests: the others are routed to no expert. Returns (logits (R, V),
    pc, counters (2,) int32: held experts chosen by at least one live row
    and (row, expert) pairs on held experts, both summed over the
    layers)."""
    n_phys = pc.shape[1]
    attend = decode.paged_attention(
        pc, None, positions, tables, v_width=cfg.kv_rank,
        scale=(cfg.d_nope + cfg.d_rope) ** -0.5)
    x = _embed(params, tokens, cfg)
    hit = local = jnp.asarray(0, jnp.int32)
    rows = []
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        qn, qr, c = project(h[:, None], layer, positions[:, None], cfg)
        c1 = c[:, 0].astype(pc.dtype)                       # (R, 1, width)
        with jax.named_scope("serve/attn_latent"):
            oc = attend(absorb_query(qn[:, 0], qr[:, 0], layer, cfg), c1,
                        None, i * n_phys)
            o = absorb_output(oc, layer, cfg)
        x, n_hit, n_local = _tail(x, attn_out(o, layer, cfg), layer, cfg,
                                  live)
        hit, local = hit + n_hit, local + n_local
        rows.append(c1[:, 0])
    pc, _ = decode.paged_write(
        pc, None, jnp.stack(rows), None, positions, tables)
    return _head(x, params, cfg), pc, jnp.stack([hit, local])


class PanguUltraMoeServing:
    """What the serving engine asks of this model (the protocol of
    :class:`rayfed_tpu.models.decode.TransformerServing`): the pool holds
    ONE array of latent rows, and ``layer_windows`` is absent (every
    layer attends every key)."""

    # Appended, in this order, to the ids a decode step returns.
    step_counters = ("moe_experts_hit", "moe_assignments_local")

    def __init__(self, cfg: PanguUltraMoeConfig):
        self.cfg = cfg

    def kv_spec(self):
        """One array over every layer: a token's latent row, (kv_rank +
        d_rope,)."""
        return ((self.cfg.n_layers, (self.cfg.cache_width,)),)

    def state_spec(self, cache_dtype=None):
        return {}

    def serving_dtype(self):
        """As published (bfloat16): every leaf is read through a cast to
        the compute dtype, a no-op then."""
        return None

    def prefill_rows(self, params, prompts, last_idx, row_len, cache_dtype,
                     landed):
        last, rows = prefill_rows(
            params, prompts, last_idx, cache_dtype, self.cfg, landed)
        return last, (rows,), {}

    def chunk(self, params, kv, state, table, slot, toks, offset, n_real):
        last, pc = chunk(
            params, *kv, table, toks, offset, n_real, self.cfg)
        return last, (pc,), state

    def decode_step(self, params, kv, state, tokens, positions, tables,
                    live):
        logits, pc, counters = paged_decode_step(
            params, *kv, tokens, positions, tables, live, self.cfg)
        return logits, (pc,), state, counters


def serving_model(cfg: PanguUltraMoeConfig) -> PanguUltraMoeServing:
    return PanguUltraMoeServing(cfg)
