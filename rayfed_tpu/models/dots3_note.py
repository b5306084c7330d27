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

"""dots3-note (``model_type: dots3_note``): latent attention of two widths
in one stack (full layers whose queries attend the keys a learned indexer
picks, sliding layers over a wider latent row and a window), headwise
output gates, a leading dense layer and routed experts chosen under a
selection bias beside a shared one.

Every layer is pre-norm, ``a = x + Attn(N1(x))``, ``y = a + F(N2(a))``.
``layer_types[i]`` says which attention layer ``i`` has:

* **full** (``H`` heads over a latent of ``kv_rank + d_rope``): the
  latent attention of :mod:`rayfed_tpu.models.pangu_ultra_moe` (its
  ``project_low_rank``, ``expand``, ``absorb_query``, ``absorb_output``:
  the same mathematics, called with this kind's sizes), the two latents
  multiplied after their norms by ``sqrt(d / q_rank)`` and ``sqrt(d /
  kv_rank)`` (``apply_mla_qkv_lora_rescale``), and an **indexer**: ``qI =
  cq WqI`` (``J`` index heads of ``D``), ``kI = LN(h WkI)`` (ONE ``D``-wide
  key a token, a LayerNorm with scale and bias), the first ``d_rope``
  dimensions of both rotated, ``w = (h Ww) / sqrt(J) / sqrt(D)``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` over ``s <= t``, and
  the query at ``t`` attends the ``min(index_topk, t + 1)`` positions of
  largest ``I[t, .]`` ONLY (the lower position at a tie): a causal
  softmax over that set. The top-k is exact
  (:func:`rayfed_tpu.models.decode.select_mask`).
* **sliding** (its own heads, ranks and head sizes, ``swa_*``; both
  latents multiplied by their own constants): the same latent attention
  over the keys ``t - window < s <= t``, no indexer.

Both end in a **headwise gate**: ``g = sigmoid(h Wg)`` (one a head), head
``j``'s output multiplied by ``g_j`` before ``wo``.

**What a token keeps** (``kv_spec``: three arrays, two layer counts): on
a full layer its latent row ``[ckv | kr]`` and its index key ``kI``; on a
sliding layer its (wider) latent row. The decode step reads the index
keys through the block tables, takes the top-k, and reads the chosen
latent rows in the absorbed form (:func:`decode.paged_index_scores`,
:func:`decode.paged_selected_attention`); a prompt chunk scores its
queries against the cached index keys a trip at a time, turns the scores
into each query's set, and masks the expanded read's trips by it
(:func:`decode.paged_chunk_index_scores`, :func:`decode.select_mask`,
:func:`decode.paged_chunk_attention`'s ``seen``). The sliding layers are
:func:`decode.paged_attention` / ``paged_chunk_attention`` with
``window=``.

The expert layer is :func:`rayfed_tpu.models.moe.routed_experts` with a
``router_bias`` leaf (``noaux_tc``: the ``top_k`` are chosen by ``sigmoid
score + bias`` and weighed by the scores, normalised over the chosen) and
:func:`moe.shared_experts`; a chip may hold a share of the experts
(``held``). The layers are a LIST of per-layer trees (``PERF.md`` section
6, PR 31). The vision and audio towers and the extra prediction layer of
the published model are not here: prompts are ids, and the next token's
logits do not depend on the prediction layer.

Norms, the index scores, the router's scores, the gates, softmax and the
sums over experts are float32; matmuls take compute-dtype operands and
accumulate in float32.

Parameter tree (leaves in ``param_dtype``; sizes by the layer's kind)::

    embed (V, d)   ln_f (d)   lm_head (V, d)
    layers[i]: ln1 ln2 (d)
               wq_a (d, rq)   q_norm (rq)    wq_b (rq, H*(dn+dr))
               wkv_a (d, rkv+dr)             kv_norm (rkv)
               wk_b (rkv, H*dn)  wv_b (rkv, H*dv)   wo (H*dv, d)
               w_og (d, H)
       full:   wi_q (rq, J*D)   wi_k (d, D)   wi_w (d, J)
               i_norm (D)   i_bias (D)
       dense:  w_gate w_up (d, fd)   w_down (fd, d)
       expert: router (d, E)   router_bias (E)
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
from rayfed_tpu.models import pangu_ultra_moe as mla

Params = Dict[str, Any]
F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """The sizes of one kind of latent attention, as
    :mod:`rayfed_tpu.models.pangu_ultra_moe`'s helpers read them off a
    config."""

    n_heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    rope_theta: float
    rms_eps: float
    compute_dtype: Any
    # Constants on the two latents after their norms.
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def cache_width(self) -> int:
        return self.kv_rank + self.d_rope

    @property
    def scale(self) -> float:
        """The softmax's, of the head a query came from."""
        return (self.d_nope + self.d_rope) ** -0.5


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab: int = 152064
    d_model: int = 5120
    n_layers: int = 46
    # Per layer FULL or SLIDING; None: two full layers, then three
    # sliding and a full one in turn (the published pattern).
    layer_types: Optional[Tuple[str, ...]] = None
    # Leading layers whose FFN is a dense SwiGLU of width d_dense.
    n_dense: int = 1
    # Full layers.
    n_heads: int = 128
    q_rank: int = 1024
    kv_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    rope_theta: float = 80000000.0
    # Their indexer.
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    # Sliding layers.
    swa_heads: int = 64
    swa_q_rank: int = 1024
    swa_kv_rank: int = 1024
    swa_d_nope: int = 192
    swa_d_rope: int = 64
    swa_d_v: int = 128
    swa_rope_theta: float = 50000.0
    # Keys a sliding layer's query sees, its own among them.
    window: int = 513
    # apply_mla_qkv_lora_rescale: sqrt(d / rank) on each normed latent.
    rescale: bool = True
    d_dense: int = 13824
    d_expert: int = 1536
    n_experts: int = 256
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 1.0
    # Global ids of the routed experts whose weights are here; None: all.
    held: Optional[Tuple[int, ...]] = None
    rms_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", tuple(range(self.n_experts)))
        held = tuple(int(e) for e in self.held)
        object.__setattr__(self, "held", held)
        kinds = self.layer_types
        if kinds is None:
            kinds = tuple(FULL if i < 2 or i % 4 == 1 else SLIDING
                          for i in range(self.n_layers))
        kinds = tuple(kinds)
        object.__setattr__(self, "layer_types", kinds)
        unknown = sorted(set(kinds) - {FULL, SLIDING})
        if unknown:
            raise ValueError(
                f"dots3_note: layer_types entries {unknown} are not "
                f"computed here (only {FULL!r} and {SLIDING!r})")
        if (len(kinds) != self.n_layers or FULL not in kinds
                or SLIDING not in kinds):
            raise ValueError(
                "dots3_note: layer_types names every layer, and the stack "
                f"holds both kinds: {kinds} for {self.n_layers} layers")
        if (self.d_rope % 2 or self.swa_d_rope % 2
                or self.d_rope > self.index_dim
                or not 0 <= self.n_dense <= self.n_layers
                or self.window < 1 or self.index_topk < 1
                or not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_experts
                or not 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                "dots3_note: the rotated parts must be even and no wider "
                "than an index head, the dense layers no more than the "
                "layers, window and index_topk at least 1, the held experts "
                f"distinct ids under n_experts and top_k at most n_experts: "
                f"{self}")

    def dims(self, kind: str) -> LatentDims:
        """The latent attention's sizes on a layer of ``kind``."""
        if kind == FULL:
            sizes = (self.n_heads, self.q_rank, self.kv_rank, self.d_nope,
                     self.d_rope, self.d_v, self.rope_theta)
        else:
            sizes = (self.swa_heads, self.swa_q_rank, self.swa_kv_rank,
                     self.swa_d_nope, self.swa_d_rope, self.swa_d_v,
                     self.swa_rope_theta)
        scales = {}
        if self.rescale:
            scales = dict(q_scale=(self.d_model / sizes[1]) ** 0.5,
                          kv_scale=(self.d_model / sizes[2]) ** 0.5)
        return LatentDims(*sizes, self.rms_eps, self.compute_dtype, **scales)

    def ordinal(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its kind: its layer in
        the pool's arrays that its kind keeps."""
        return self.layer_types[:i].count(self.layer_types[i])

    @classmethod
    def from_published(cls, config: Dict[str, Any], **overrides):
        """The configuration from the keys of a published ``config.json``
        (``model_type: dots3_note``): every expert held, the whole
        vocabulary. A chip's share overrides ``held`` (and ``vocab``,
        ``n_layers``: the first layers' kinds are kept). What is not
        computed here is refused by name."""
        c = config
        if c.get("rope_scaling") is not None:
            raise ValueError(
                f"dots3_note: rope_scaling={c['rope_scaling']!r} is not "
                "computed here (plain rotary positions only)")
        for key in ("n_group", "topk_group"):
            if key in c:
                raise ValueError(
                    f"dots3_note: {key}={c[key]!r}: grouped routing is not "
                    "computed here (the published config has no groups)")
        for key, want in (
            ("attention_gate_type", "headwise"),
            ("swa_attention_gate_type", "headwise"),
            ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
            ("norm_topk_prob", True), ("attention_bias", False),
            ("hidden_act", "silu"), ("tie_word_embeddings", False),
            ("moe_layer_freq", 1),
        ):
            if c.get(key, want) != want:
                raise ValueError(
                    f"dots3_note: {key}={c[key]!r} is not computed here "
                    f"(only {want!r})")
        n = int(overrides.get("n_layers", c["num_hidden_layers"]))
        fields = dict(
            vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n,
            layer_types=tuple(c["layer_types"][:n]),
            n_dense=min(int(c["first_k_dense_replace"]), n),
            n_heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
            kv_rank=c["kv_lora_rank"], d_nope=c["qk_nope_head_dim"],
            d_rope=c["qk_rope_head_dim"], d_v=c["v_head_dim"],
            rope_theta=float(c["rope_theta"]),
            index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
            index_topk=c["index_topk"],
            swa_heads=c["swa_num_attention_heads"],
            swa_q_rank=c["swa_q_lora_rank"],
            swa_kv_rank=c["swa_kv_lora_rank"],
            swa_d_nope=c["swa_qk_nope_head_dim"],
            swa_d_rope=c["swa_qk_rope_head_dim"],
            swa_d_v=c["swa_v_head_dim"],
            swa_rope_theta=float(c["swa_rope_theta"]),
            window=c["sliding_window_size"],
            rescale=bool(c.get("apply_mla_qkv_lora_rescale", False)),
            d_dense=c["intermediate_size"],
            d_expert=c["moe_intermediate_size"],
            n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            n_shared=c["n_shared_experts"],
            routed_scale=float(c.get("routed_scaling_factor", 1.0)),
            rms_eps=float(c["rms_norm_eps"]),
        )
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Pieces of a layer
# ---------------------------------------------------------------------------

rms_norm = mla.rms_norm


def project(h, layer, positions, dims: LatentDims):
    """The low ranks of a normed ``h`` at ``positions``, rescaled:
    ``(cq, qn, qr, c)`` of :func:`pangu_ultra_moe.project_low_rank`."""
    return mla.project_low_rank(h, layer, positions, dims, dims.q_scale,
                                dims.kv_scale)


def _rotate_first(x, positions, width: int, theta: float):
    """``x`` (..., S, heads, D) with its first ``width`` dimensions
    rotated by halves."""
    return jnp.concatenate(
        [mla.rope_halves(x[..., :width], positions, theta),
         x[..., width:]], -1)


def index_project(h, cq, layer, positions, cfg: Dots3NoteConfig):
    """The indexer's inputs of a full layer: ``qi`` (..., S, J, D) from
    the query's latent, ``ki`` (..., S, D) a LayerNorm of ``h WkI``, both
    with their first ``d_rope`` dimensions rotated, in the compute dtype;
    ``w`` (..., S, J) float32, ``(h Ww) / sqrt(J) / sqrt(D)``."""
    with jax.named_scope("serve/attn_index"):
        cdt = cfg.compute_dtype
        dims = cfg.dims(FULL)
        qi = mla._mm(cq, layer["wi_q"], dims)
        qi = qi.reshape(*qi.shape[:-1], cfg.index_heads, cfg.index_dim)
        qi = _rotate_first(qi, positions, cfg.d_rope, cfg.rope_theta)
        k = mla._mm(h, layer["wi_k"], dims)
        mean = jnp.mean(k, -1, keepdims=True)
        var = jnp.mean((k - mean) ** 2, -1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(var + cfg.rms_eps) \
            * layer["i_norm"].astype(F32) + layer["i_bias"].astype(F32)
        ki = _rotate_first(k[..., None, :], positions, cfg.d_rope,
                           cfg.rope_theta)[..., 0, :]
        w = mla._mm(h, layer["wi_w"], dims) * (
            cfg.index_heads * cfg.index_dim) ** -0.5
        return qi.astype(cdt), ki.astype(cdt), w


def gated(o, h, layer, dims: LatentDims):
    """Heads' outputs ``o`` (..., H, dv) under the headwise gate of the
    normed input ``h`` (..., d): ``o_j sigmoid(h Wg)_j``."""
    g = jax.nn.sigmoid(mla._mm(h, layer["w_og"], dims))
    return (o.astype(F32) * g[..., None]).astype(dims.compute_dtype)


def ffn(h, layer, cfg: Dots3NoteConfig, live=None):
    """A layer's FFN of a normed ``h`` (T, d): the dense SwiGLU where the
    layer holds one, else the routed experts held here (chosen under the
    layer's selection bias) plus the shared expert. Returns ((T, d)
    float32, experts hit, assignments on held experts)."""
    if "w_gate" in layer:
        zero = jnp.asarray(0, jnp.int32)
        return mla.dense_ffn(h, layer, cfg), zero, zero
    routed, hit, local = moe.routed_experts(
        h, layer, cfg.held, cfg.top_k, live, scale=cfg.routed_scale)
    return routed + moe.shared_experts(h, layer, cfg.n_shared), hit, local


def _tail(x, att, layer, cfg: Dots3NoteConfig, live=None):
    """The rest of a block after attention's output projection ``att``
    (T, d) float32. Returns (x, experts hit, assignments)."""
    a = x + att.astype(x.dtype)
    f, hit, local = ffn(rms_norm(a, layer["ln2"], cfg.rms_eps), layer, cfg,
                        live)
    return a + f.astype(x.dtype), hit, local


def seq_attention(q, k, v, seen):
    """Attention of queries (S, H, D) over keys (Sk, H, D) and values
    (Sk, H, Dv) where ``seen`` (S, Sk) allows (the expanded form, whole).
    Softmax in float32."""
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=F32) * q.shape[-1]**-0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)


def cache_arrays(cfg: Dots3NoteConfig):
    """What a token keeps, as ``kv_spec`` declares it: (layers of the
    array, a token's row in it) for the latent row and the index key of
    every full layer and the latent row of every sliding one."""
    n_full = cfg.layer_types.count(FULL)
    return ((n_full, (cfg.dims(FULL).cache_width,)),
            (n_full, (cfg.index_dim,)),
            (cfg.n_layers - n_full, (cfg.dims(SLIDING).cache_width,)))


def _embed(params, tokens, cfg: Dots3NoteConfig):
    return params["embed"][tokens].astype(cfg.compute_dtype)


def _stacked(rows):
    """Per-layer rows ``{kind: [...]}`` and the index keys as the pool's
    three arrays: full latent, index keys, sliding latent."""
    return (jnp.stack(rows[FULL]), jnp.stack(rows["index"]),
            jnp.stack(rows[SLIDING]))


def _seq_layers(x, params, positions, live, cfg: Dots3NoteConfig):
    """The stack over one sequence ``x`` (S, d) from an empty cache, in
    the expanded form, whole. Returns (x, the three arrays' rows: (Lf, S,
    width), (Lf, S, D), (Ls, S, width))."""
    causal = positions[None, :] <= positions[:, None]
    in_window = causal & (positions[None, :] > positions[:, None]
                          - cfg.window)
    rows = {FULL: [], "index": [], SLIDING: []}
    for kind, layer in zip(cfg.layer_types, params["layers"]):
        dims = cfg.dims(kind)
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        cq, qn, qr, c = project(h, layer, positions, dims)
        k, v = mla.expand(c, layer, dims)
        q = jnp.concatenate([qn, qr], -1)
        if kind == FULL:
            qi, ki, w = index_project(h, cq, layer, positions, cfg)
            with jax.named_scope("serve/attn_index"):
                seen = decode.select_mask(
                    decode.index_scores(qi, w, ki), causal, cfg.index_topk)
            with jax.named_scope("serve/attn_sparse"):
                o = seq_attention(q, k, v, seen)
            rows["index"].append(ki)
        else:
            with jax.named_scope("serve/attn_window_latent"):
                o = seq_attention(q, k, v, in_window)
        o = gated(o, h, layer, dims)
        x, _, _ = _tail(x, mla.attn_out(o, layer, dims), layer, cfg, live)
        rows[kind].append(c[:, 0])
    return x, _stacked(rows)


# ---------------------------------------------------------------------------
# Whole-model programs
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: Dots3NoteConfig):
    """tokens (B, S) -> logits (B, S, V) float32: no cache, every
    position real, one sequence at a time."""
    positions = jnp.arange(tokens.shape[1])

    def one(row):
        x, _ = _seq_layers(_embed(params, row, cfg), params, positions,
                           None, cfg)
        return mla._head(x, params, cfg)

    return jax.lax.map(one, tokens)


def prefill_rows(params, prompts, last_idx, cache_dtype,
                 cfg: Dots3NoteConfig, landed=None):
    """Right-padded prompts (R, S), each real up to ``last_idx`` (R,),
    from an empty cache. Returns the logits (R, V) at ``last_idx`` and
    the three arrays' rows ((Lf, R, S, width), (Lf, R, S, D), (Ls, R, S,
    width)), as long as the bucket. Only the rows ``landed`` (R,) bool
    names are computed (all, when it is None), one at a time
    (:func:`decode.landed_rows`)."""
    r, s = prompts.shape
    cache_dtype = cache_dtype or cfg.compute_dtype
    positions = jnp.arange(s)

    def one_row(i):
        prompt, n_real = prompts[i], last_idx[i] + 1
        x, rows = _seq_layers(_embed(params, prompt, cfg), params,
                              positions, positions < n_real, cfg)
        last = jax.lax.dynamic_index_in_dim(x, n_real - 1, 0, keepdims=False)
        return (mla._head(last, params, cfg),
                *(a.astype(cache_dtype) for a in rows))

    logits, *rows = decode.landed_rows(one_row, landed, (
        jnp.zeros((r, cfg.vocab), F32),
        *(jnp.zeros((layers, r, s, *shape), cache_dtype)
          for layers, shape in cache_arrays(cfg))))
    return logits, tuple(rows)


def chunk(params, kv, table, toks, offset, n_real, cfg: Dots3NoteConfig):
    """One prompt chunk ``toks`` (C,), real up to ``n_real``, at positions
    ``offset .. offset + C - 1`` of the slot whose block table is
    ``table``. A full layer scores its queries against the slot's cached
    index keys through the table and against the chunk's own, keeps each
    query's ``index_topk`` best, and attends those in the expanded form
    (the trips that hold the context, masked by the selection); a sliding
    layer reads its window. The chunk's own rows of the three arrays are
    written in place, once, after the last layer. ``kv`` is donated.
    Returns the logits (V,) at the last real position and ``kv``."""
    full, index, sliding = kv
    clen = toks.shape[0]
    n_phys = full.shape[1]
    positions = offset + jnp.arange(clen)
    live = jnp.arange(clen) < n_real
    attend = {
        FULL: decode.paged_chunk_attention(full, None, table, offset, n_real),
        SLIDING: decode.paged_chunk_attention(
            sliding, None, table, offset, n_real, window=cfg.window),
    }
    score = decode.paged_chunk_index_scores(index, table, offset)
    scope = {FULL: "serve/attn_sparse", SLIDING: "serve/attn_window_latent"}
    x = _embed(params, toks, cfg)
    rows = {FULL: [], "index": [], SLIDING: []}
    for i, (kind, layer) in enumerate(zip(cfg.layer_types, params["layers"])):
        dims = cfg.dims(kind)
        base = cfg.ordinal(i) * n_phys
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        cq, qn, qr, c = project(h, layer, positions, dims)
        c = c.astype(full.dtype)
        k, v = mla.expand(c, layer, dims)
        seen = None
        if kind == FULL:
            qi, ki, w = index_project(h, cq, layer, positions, cfg)
            ki = ki.astype(index.dtype)
            with jax.named_scope("serve/attn_index"):
                seen = decode.select_mask(
                    *score(qi, w, ki, base), cfg.index_topk)
            rows["index"].append(ki)
        with jax.named_scope(scope[kind]):
            o = attend[kind](
                jnp.concatenate([qn, qr], -1), k, v, base,
                lambda cached, layer=layer, dims=dims: mla.expand(
                    cached, layer, dims), seen)
        o = gated(o, h, layer, dims)
        x, _, _ = _tail(x, mla.attn_out(o, layer, dims), layer, cfg, live)
        rows[kind].append(c[:, 0])
    kv = tuple(
        decode.paged_chunk_write(pool, None, new, None, table, offset)[0]
        for pool, new in zip(kv, _stacked(rows)))
    last = jax.lax.dynamic_index_in_dim(x, n_real - 1, 0, keepdims=False)
    return mla._head(last, params, cfg), kv


def paged_decode_step(params, kv, tokens, positions, tables, live,
                      cfg: Dots3NoteConfig):
    """One decode token for every row, the latent rows read in the
    absorbed form through the block tables. A full layer scores the row's
    index query against its own blocks of the index keys, a trip at a
    time, takes the ``index_topk`` best positions and reads those latent
    rows only; a sliding layer reads its window's blocks. The new rows of
    the three arrays are written in place. ``live`` (R,) bool names the
    rows that are requests: the others (position 0 under an all-zero
    table) score no cached key and are routed to no expert. Returns
    (logits (R, V), kv, counters (2,) int32: held experts chosen by at
    least one live row and (row, expert) pairs on held experts, both
    summed over the layers)."""
    full, index, sliding = kv
    n_phys = full.shape[1]
    fd, sd = cfg.dims(FULL), cfg.dims(SLIDING)
    score = decode.paged_index_scores(index, positions, tables)
    attend_selected = decode.paged_selected_attention(
        full, positions, tables, cfg.index_topk, scale=fd.scale,
        v_width=fd.kv_rank)
    attend_window = decode.paged_attention(
        sliding, None, positions, tables, window=cfg.window, scale=sd.scale,
        v_width=sd.kv_rank)
    x = _embed(params, tokens, cfg)
    hit = local = jnp.asarray(0, jnp.int32)
    rows = {FULL: [], "index": [], SLIDING: []}
    for i, (kind, layer) in enumerate(zip(cfg.layer_types, params["layers"])):
        dims = cfg.dims(kind)
        base = cfg.ordinal(i) * n_phys
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        cq, qn, qr, c = project(h[:, None], layer, positions[:, None], dims)
        c1 = c[:, 0].astype(full.dtype)                     # (R, 1, width)
        qa = mla.absorb_query(qn[:, 0], qr[:, 0], layer, dims)
        if kind == FULL:
            qi, ki, w = index_project(
                h[:, None], cq, layer, positions[:, None], cfg)
            ki = ki[:, 0].astype(index.dtype)
            with jax.named_scope("serve/attn_index"):
                scores, valid = score(qi[:, 0], w[:, 0], ki, base)
            with jax.named_scope("serve/attn_sparse"):
                oc = attend_selected(qa, c1, scores, valid, base)
            rows["index"].append(ki)
        else:
            with jax.named_scope("serve/attn_window_latent"):
                oc = attend_window(qa, c1, None, base)
        o = gated(mla.absorb_output(oc, layer, dims), h, layer, dims)
        x, n_hit, n_local = _tail(x, mla.attn_out(o, layer, dims), layer,
                                  cfg, live)
        hit, local = hit + n_hit, local + n_local
        rows[kind].append(c1[:, 0])
    kv = tuple(
        decode.paged_write(pool, None, new, None, positions, tables)[0]
        for pool, new in zip(kv, _stacked(rows)))
    return mla._head(x, params, cfg), kv, jnp.stack([hit, local])


class Dots3NoteServing:
    """What the serving engine asks of this model (the protocol of
    :class:`rayfed_tpu.models.decode.TransformerServing`). The pool holds
    THREE arrays of two depths: the full layers' latent rows and index
    keys, the sliding layers' latent rows. ``layer_windows`` and
    ``layer_index_topk`` tell the engine what each layer reads."""

    # Appended, in this order, to the ids a decode step returns.
    step_counters = ("moe_experts_hit", "moe_assignments_local")

    def __init__(self, cfg: Dots3NoteConfig):
        self.cfg = cfg

    def kv_spec(self):
        """Three arrays of two depths: :func:`cache_arrays`."""
        return cache_arrays(self.cfg)

    def layer_windows(self):
        """Per layer, the keys a token attends through a window; None on
        a full layer."""
        return tuple(self.cfg.window if kind == SLIDING else None
                     for kind in self.cfg.layer_types)

    def layer_index_topk(self):
        """Per layer, the keys a token attends of those its indexer
        scored; None on a layer without an indexer."""
        return tuple(self.cfg.index_topk if kind == FULL else None
                     for kind in self.cfg.layer_types)

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
        return last, rows, {}

    def chunk(self, params, kv, state, table, slot, toks, offset, n_real):
        last, kv = chunk(params, kv, table, toks, offset, n_real, self.cfg)
        return last, kv, state

    def decode_step(self, params, kv, state, tokens, positions, tables,
                    live):
        logits, kv, counters = paged_decode_step(
            params, kv, tokens, positions, tables, live, self.cfg)
        return logits, kv, state, counters


def serving_model(cfg: Dots3NoteConfig) -> Dots3NoteServing:
    return Dots3NoteServing(cfg)
