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

"""Falcon-H1: attention and a Mamba-2 mixer side by side in every block.

Each block reads one pre-norm ``h`` into two branches that are summed into
the residual: grouped-query attention (fewer K/V heads than query heads)
and a Mamba-2 state-space mixer, then a SwiGLU MLP. Every path carries a
published scalar (the ``*_multiplier`` fields; muP made explicit).

The serving engine (:mod:`rayfed_tpu.serving.server`) takes this module
through :func:`serving_model`, the same protocol
:class:`rayfed_tpu.models.decode.TransformerServing` implements. What is
new for the engine is a second kind of per-slot state beside the paged
K/V: per layer a convolution tail (the last ``ssm_conv - 1`` inputs of
the depthwise convolution) and the SSM state ``(heads, head_dim,
d_state)`` in float32. K/V that is stale or padded is harmless because
no query attends it; a recurrent state is *carried*, so here

* a padded position never advances it: its ``dt`` is 0 (``exp(0 * A) =
  1`` and ``0 * x B^T = 0``, the state passes through) and the tail kept
  is that of the last real inputs;
* a request always starts from zero: the bucketed prefill computes from
  a fresh zero state, the first chunk of a chunked prefill (``offset ==
  0``) zeroes what its slot held;
* a row that sits a decode step out (``live`` false) gets its state back
  bit for bit.

Prefill runs the chunked (SSD) form of the recurrence, decode the
one-step form; both are plain ``jnp``. ``dt``, ``exp(dt * A)``, the
state and the gated norm are float32; matmuls take compute-dtype operands
and accumulate in float32, and a path's multiplier is applied to that
float32 result before it is rounded.

Parameter tree (``L`` = layers, leaves in ``param_dtype``)::

    embed (V, d)   ln_f (d)   lm_head (d, V)
    layers: ln1 ln2 (L, d)
            wq (L, d, H, Dh)  wk wv (L, d, Hkv, Dh)  wo (L, H, Dh, d)
            in_proj (L, d, 2*d_ssm + 2*G*N + Hs)   zones z | x | B | C | dt
            conv_w (L, K, d_ssm + 2*G*N)  conv_b (L, d_ssm + 2*G*N)
            dt_bias A_log D (L, Hs)   ssm_norm (L, d_ssm)
            out_proj (L, d_ssm, d)
            w_gate w_up (L, d, f)   w_down (L, f, d)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rayfed_tpu.models import decode
from rayfed_tpu.models import transformer as tfm

Params = Dict[str, Any]
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab: int = 261120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    d_ssm: int = 4096
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256
    ssm_groups: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # One per zone of in_proj's output: z, x, B, C, dt.
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    # Gate pre-activation, down projection.
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.n_heads % self.n_kv_heads
                or self.ssm_heads * self.ssm_head_dim != self.d_ssm
                or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                "falcon_h1: query heads must be a multiple of K/V heads, "
                "mixer heads x head size must be d_ssm and a multiple of "
                f"the groups: {self}"
            )

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_proj_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.ssm_heads

    @classmethod
    def from_published(cls, config: Dict[str, Any], **overrides):
        """The configuration from the keys of a published ``config.json``
        (``model_type: falcon_h1``)."""
        c = config
        for key, want in (
            ("attention_bias", False), ("mamba_conv_bias", True),
            ("mamba_proj_bias", False), ("mlp_bias", False),
            ("mamba_norm_before_gate", False), ("mamba_rms_norm", True),
            ("rope_scaling", None), ("tie_word_embeddings", False),
            ("hidden_act", "silu"),
        ):
            if c.get(key, want) != want:
                raise ValueError(
                    f"falcon_h1: {key}={c[key]!r} is not computed here "
                    f"(only {want!r})"
                )
        fields = dict(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], d_ssm=c["mamba_d_ssm"],
            ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
            ssm_state=c["mamba_d_state"], ssm_groups=c["mamba_n_groups"],
            ssm_conv=c["mamba_d_conv"], ssm_chunk=c["mamba_chunk_size"],
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
            ssm_multipliers=tuple(float(m) for m in c["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in c["mlp_multipliers"]),
        )
        for name in (
            "embedding_multiplier", "lm_head_multiplier",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
        ):
            fields[name] = float(c[name])
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Pieces of a block
# ---------------------------------------------------------------------------


def _mm(x, w, cfg: FalconH1Config, scale=None, spec: str = "...d,df->...f"):
    """Compute-dtype operands, float32 accumulation, the path's multiplier
    on the float32 result, one rounding."""
    y = jnp.einsum(
        spec, x, w.astype(cfg.compute_dtype), preferred_element_type=F32
    )
    if scale is not None:
        y = y * scale
    return y.astype(cfg.compute_dtype)


def mup_vector(cfg: FalconH1Config) -> np.ndarray:
    """The constant that multiplies in_proj's five zones."""
    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.ssm_heads)
    return np.concatenate([
        np.full(w, m, np.float32)
        for w, m in zip(widths, cfg.ssm_multipliers)
    ])


def qkv(h, layer, positions, cfg: FalconH1Config):
    """Q (B, S, H, Dh) and K, V (B, S, Hkv, Dh) of a pre-normed ``h``."""
    # (h * m) W = m (h W): the input's multiplier rides the float32 result.
    m_in = cfg.attention_in_multiplier
    q = _mm(h, layer["wq"], cfg, m_in, "bsd,dhk->bshk")
    k = _mm(h, layer["wk"], cfg, m_in * cfg.key_multiplier, "bsd,dhk->bshk")
    v = _mm(h, layer["wv"], cfg, m_in, "bsd,dhk->bshk")
    q, k = tfm.rope(q, k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attention(q, k, v, q_pos):
    """Causal attention of (B, Sq, H, Dh) queries at positions ``q_pos``
    (B, Sq) over keys (B, Sk, Hkv, Dh) at positions 0..Sk-1; K/V head i
    serves query heads i*G..(i+1)*G-1. Softmax in float32."""
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    qg = q.reshape(b, sq, hkv, h // hkv, dh)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=F32
    ) * dh**-0.5
    mask = q_pos[:, None, None, :, None] >= jnp.arange(sk)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return o.reshape(b, sq, h, dh)


def attn_out(o, layer, cfg: FalconH1Config):
    return _mm(o, layer["wo"], cfg, cfg.attention_out_multiplier,
               "bshk,hkd->bsd")


def mlp(x, layer, cfg: FalconH1Config):
    m = tfm.rms_norm(x, layer["ln2"], cfg.rms_eps)
    gate = jax.nn.silu(_mm(m, layer["w_gate"], cfg, cfg.mlp_multipliers[0]))
    up = _mm(m, layer["w_up"], cfg)
    return _mm(gate * up, layer["w_down"], cfg, cfg.mlp_multipliers[1])


def _in_proj(h, layer, cfg: FalconH1Config):
    """z (.., d_ssm), xBC (.., conv_dim), raw dt (.., Hs) of a pre-normed
    ``h``."""
    p = _mm(h, layer["in_proj"], cfg,
            cfg.ssm_in_multiplier * mup_vector(cfg))
    return (p[..., :cfg.d_ssm],
            p[..., cfg.d_ssm:cfg.d_ssm + cfg.conv_dim],
            p[..., cfg.d_ssm + cfg.conv_dim:])


def _split_xbc(xbc, cfg: FalconH1Config):
    """x (.., Hs, P), B and C (.., G, N) of the convolved zone."""
    gn = cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :cfg.d_ssm].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    b = xbc[..., cfg.d_ssm:cfg.d_ssm + gn].reshape(
        *lead, cfg.ssm_groups, cfg.ssm_state)
    c = xbc[..., cfg.d_ssm + gn:].reshape(
        *lead, cfg.ssm_groups, cfg.ssm_state)
    return x, b, c


def _dt_of(dt_raw, layer):
    return jax.nn.softplus(dt_raw.astype(F32) + layer["dt_bias"].astype(F32))


def conv_seq(xbc, tail, layer, n_real):
    """Depthwise causal convolution of (B, S, C) inputs whose first
    ``n_real`` (B,) positions are real, continuing from ``tail`` (B, K-1,
    C), the inputs before position 0; ``layer`` holds ``conv_w`` (K, C)
    and, where the model has one, the bias ``conv_b`` (C,). Returns
    silu(conv + bias) and the new tail: the last K-1 *real* inputs.
    (Shared with :mod:`rayfed_tpu.models.olmo_hybrid`, whose
    linear-attention layers convolve the same way, without a bias.)"""
    with jax.named_scope("serve/conv"):
        w = layer["conv_w"].astype(F32)
        k, s = w.shape[0], xbc.shape[1]
        u = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        out = layer["conv_b"].astype(F32) if "conv_b" in layer else 0.0
        for j in range(k):
            out = out + w[j] * u[:, j:j + s].astype(F32)
        idx = n_real[:, None] + jnp.arange(k - 1)
        new_tail = jnp.take_along_axis(u, idx[:, :, None], axis=1)
        return jax.nn.silu(out).astype(xbc.dtype), new_tail


def conv_step(xbc, tail, layer):
    """One position: ``xbc`` (R, C), ``tail`` (R, K-1, C)."""
    with jax.named_scope("serve/conv"):
        u = jnp.concatenate(
            [tail.astype(xbc.dtype), xbc[:, None]], axis=1
        )
        w = layer["conv_w"].astype(F32)
        out = jnp.sum(w * u.astype(F32), axis=1)
        if "conv_b" in layer:
            out = layer["conv_b"].astype(F32) + out
        return jax.nn.silu(out).astype(xbc.dtype), u[:, 1:]


def ssd_scan(x, dt, b, c, layer, state, cfg: FalconH1Config):
    """The recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t`` over a sequence, in the chunked (SSD) form.

    ``x`` (B, S, Hs, P), ``dt`` (B, S, Hs) float32 and 0 at padded
    positions, ``b``/``c`` (B, S, G, N), ``state`` (B, Hs, P, N) float32.
    Returns ``y`` (B, S, Hs, P) float32 and the state after the last
    position. Within a chunk the outputs are one masked matmul; chunk to
    chunk the state is handed on by a short scan.
    """
    with jax.named_scope("serve/ssd_scan"):
        cdt = cfg.compute_dtype
        bt, s, hs, p = x.shape
        g, n = cfg.ssm_groups, cfg.ssm_state
        hg = hs // g
        q = min(cfg.ssm_chunk, s)
        pad = -s % q
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            x, b, c = (jnp.pad(t, widths) for t in (x, b, c))
            dt = jnp.pad(dt, widths[:3])
        nc = (s + pad) // q
        a_head = -jnp.exp(layer["A_log"].astype(F32)).reshape(g, hg)
        d_head = layer["D"].astype(F32).reshape(g, hg)
        xc = x.reshape(bt, nc, q, g, hg, p)
        dtc = dt.reshape(bt, nc, q, g, hg)
        bc = b.reshape(bt, nc, q, g, n)
        cc = c.reshape(bt, nc, q, g, n)
        cum = jnp.cumsum(dtc * a_head, axis=2)          # (bt,nc,q,g,hg) <= 0
        xdt = xc.astype(F32) * dtc[..., None]
        # Within a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dt_j x_j
        cb = jnp.einsum("zcign,zcjgn->zcijg", cc, bc,
                        preferred_element_type=F32)
        seg = cum[:, :, :, None] - cum[:, :, None, :]   # (bt,nc,i,j,g,hg)
        causal = jnp.tril(jnp.ones((q, q), bool))[:, :, None, None]
        m = jnp.exp(jnp.where(causal, seg, -jnp.inf)) * cb[..., None]
        y = jnp.einsum("zcijgh,zcjghp->zcighp", m.astype(cdt),
                       xdt.astype(cdt), preferred_element_type=F32)
        # What each chunk adds to the state by its end.
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        added = jnp.einsum(
            "zcjghp,zcjgn->zcghpn", (xdt * to_end[..., None]).astype(cdt),
            bc, preferred_element_type=F32)
        decay = jnp.exp(cum[:, :, -1])                  # (bt,nc,g,hg)

        def hand_on(st, chunk):
            add, dec = chunk
            return dec[..., None, None] * st + add, st

        state, at_start = jax.lax.scan(
            hand_on, state.reshape(bt, g, hg, p, n),
            (jnp.moveaxis(added, 1, 0), jnp.moveaxis(decay, 1, 0)))
        at_start = jnp.moveaxis(at_start, 0, 1)         # (bt,nc,g,hg,p,n)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "zcign,zcghpn->zcighp", cc, at_start.astype(cdt),
            preferred_element_type=F32)
        y = y + d_head[..., None] * xc.astype(F32)
        y = y.reshape(bt, s + pad, hs, p)[:, :s]
        return y, state.reshape(bt, hs, p, n)


def ssm_step(x, dt, b, c, layer, state, cfg: FalconH1Config):
    """One position of the same recurrence: ``x`` (R, Hs, P), ``dt`` (R,
    Hs) float32, ``b``/``c`` (R, G, N), ``state`` (R, Hs, P, N) float32.
    All float32: the state is read and written once."""
    with jax.named_scope("serve/ssm_step"):
        r, hs, p = x.shape
        g, n = cfg.ssm_groups, cfg.ssm_state
        hg = hs // g
        a_head = -jnp.exp(layer["A_log"].astype(F32)).reshape(g, hg)
        d_head = layer["D"].astype(F32).reshape(g, hg)
        xg = x.astype(F32).reshape(r, g, hg, p)
        dtg = dt.reshape(r, g, hg)
        bg = b.astype(F32)[:, :, None, None, :]
        cg = c.astype(F32)[:, :, None, None, :]
        st = state.reshape(r, g, hg, p, n)
        st = (jnp.exp(dtg * a_head)[..., None, None] * st
              + (dtg[..., None] * xg)[..., None] * bg)
        y = jnp.sum(st * cg, axis=-1) + d_head[..., None] * xg
        return y.reshape(r, hs, p), st.reshape(r, hs, p, n)


def gated_norm(y, z, layer, cfg: FalconH1Config):
    """``y * silu(z)`` (gate first), RMS-normalised within each group of
    ``d_ssm / G`` channels, times the norm's weight; float32 inside."""
    lead = z.shape[:-1]
    gated = y.reshape(*lead, cfg.d_ssm) * jax.nn.silu(z.astype(F32))
    grouped = gated.reshape(*lead, cfg.ssm_groups, -1)
    inv = jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.rms_eps
    )
    out = (grouped * inv).reshape(*lead, cfg.d_ssm)
    return (out * layer["ssm_norm"].astype(F32)).astype(cfg.compute_dtype)


def mixer_seq(h, layer, tail, state, real, n_real, cfg: FalconH1Config):
    """The Mamba-2 branch over a sequence ``h`` (B, S, d) whose positions
    ``real`` (B, S) count (a prefix of ``n_real`` (B,) of them). Returns
    the branch's output, the new tail and the new state."""
    z, xbc, dt_raw = _in_proj(h, layer, cfg)
    xbc, tail = conv_seq(xbc, tail, layer, n_real)
    x, b, c = _split_xbc(xbc, cfg)
    dt = jnp.where(real[..., None], _dt_of(dt_raw, layer), 0.0)
    y, state = ssd_scan(x, dt, b, c, layer, state, cfg)
    out = _mm(gated_norm(y, z, layer, cfg), layer["out_proj"], cfg,
              cfg.ssm_out_multiplier)
    return out, tail, state


def mixer_step(h, layer, tail, state, cfg: FalconH1Config):
    """The Mamba-2 branch for one position of every row: ``h`` (R, d)."""
    z, xbc, dt_raw = _in_proj(h, layer, cfg)
    xbc, tail = conv_step(xbc, tail, layer)
    x, b, c = _split_xbc(xbc, cfg)
    y, state = ssm_step(x, _dt_of(dt_raw, layer), b, c, layer, state, cfg)
    out = _mm(gated_norm(y, z, layer, cfg), layer["out_proj"], cfg,
              cfg.ssm_out_multiplier)
    return out, tail, state


def _embed(params, tokens, cfg: FalconH1Config):
    x = params["embed"][tokens].astype(F32) * cfg.embedding_multiplier
    return x.astype(cfg.compute_dtype)


def _head(x, params, cfg: FalconH1Config):
    """Logits (.., V) float32 of hidden states ``x`` (.., d)."""
    x = tfm.rms_norm(x, params["ln_f"], cfg.rms_eps)
    return jnp.einsum(
        "...d,dv->...v", x, params["lm_head"].astype(cfg.compute_dtype),
        preferred_element_type=F32,
    ) * cfg.lm_head_multiplier


def zero_state(cfg: FalconH1Config, rows: int, cache_dtype=None):
    """The state a request starts from, for ``rows`` rows of one layer."""
    return (
        jnp.zeros((rows, cfg.ssm_conv - 1, cfg.conv_dim),
                  cache_dtype or cfg.compute_dtype),
        jnp.zeros((rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                  F32),
    )


# ---------------------------------------------------------------------------
# Whole-model programs
# ---------------------------------------------------------------------------


def forward(params: Params, tokens, cfg: FalconH1Config):
    """tokens (B, S) -> logits (B, S, V) float32: no cache, zero initial
    state, every position real."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    real = jnp.ones((b, s), bool)
    n_real = jnp.full((b,), s, jnp.int32)
    tail0, state0 = zero_state(cfg, b)

    def body(x, layer):
        h = tfm.rms_norm(x, layer["ln1"], cfg.rms_eps)
        q, k, v = qkv(h, layer, positions, cfg)
        att = attn_out(gqa_attention(q, k, v, positions), layer, cfg)
        ssm, _, _ = mixer_seq(h, layer, tail0, state0, real, n_real, cfg)
        x = x + (ssm + att)
        return x + mlp(x, layer, cfg), None

    x, _ = jax.lax.scan(body, _embed(params, tokens, cfg), params["layers"])
    return _head(x, params, cfg)


def prefill_rows(params, prompts, last_idx, row_len: int, cache_dtype,
                 cfg: FalconH1Config, landed=None):
    """Right-padded prompts (R, S), each real up to ``last_idx`` (R,),
    from an empty cache and a zero state. Returns the logits (R, V) at
    ``last_idx``, K/V rows (L, R, row_len, Hkv, Dh) and each row's state
    after its last real token.

    Only the rows ``landed`` (R,) bool names are computed (all, when it
    is None), one at a time (:func:`decode.landed_rows`); the other rows
    come back zero."""
    r, s = prompts.shape
    cache_dtype = cache_dtype or cfg.compute_dtype
    positions = jnp.arange(s)[None]
    tail0, state0 = zero_state(cfg, 1, cache_dtype)

    def one_row(i):
        # A batch of one: prompt (1, S), n_real (1,).
        prompt = jax.lax.dynamic_slice_in_dim(prompts, i, 1, 0)
        n_real = jax.lax.dynamic_slice_in_dim(last_idx, i, 1, 0) + 1
        real = positions < n_real[:, None]

        def body(x, layer):
            h = tfm.rms_norm(x, layer["ln1"], cfg.rms_eps)
            q, k, v = qkv(h, layer, positions, cfg)
            att = attn_out(gqa_attention(q, k, v, positions), layer, cfg)
            ssm, tail, state = mixer_seq(
                h, layer, tail0, state0, real, n_real, cfg)
            x = x + (ssm + att)
            x = x + mlp(x, layer, cfg)
            return x, (k.astype(cache_dtype), v.astype(cache_dtype), tail,
                       state)

        x, (k, v, tails, states) = jax.lax.scan(
            body, _embed(params, prompt, cfg), params["layers"])
        last = jax.lax.dynamic_index_in_dim(
            x[0], n_real[0] - 1, 0, keepdims=False)
        # (L, 1, ...) each: the row without the batch's axis.
        return (_head(last, params, cfg), k[:, 0], v[:, 0], tails[:, 0],
                states[:, 0])

    n = cfg.n_layers
    kv = jnp.zeros((n, r, row_len, cfg.n_kv_heads, cfg.head_dim), cache_dtype)
    logits, k, v, tails, states = decode.landed_rows(one_row, landed, (
        jnp.zeros((r, cfg.vocab), F32), kv, kv,
        jnp.zeros((n, r) + tail0.shape[1:], tail0.dtype),
        jnp.zeros((n, r) + state0.shape[1:], state0.dtype),
    ))
    return logits, k, v, {"conv": tails, "ssm": states}


def chunk(params, pk, pv, state, table, slot, toks, offset, n_real,
          cfg: FalconH1Config):
    """One prompt chunk ``toks`` (C,), real up to ``n_real``, at positions
    ``offset .. offset + C - 1`` of the slot whose block table is
    ``table``: its context read from the pool through the table
    (:func:`decode.paged_chunk_attention`, each K/V head serving its
    group of query heads), its own K/V written there in place; the slot's
    rows of the recurrent state (L, slots, ...) read at ``slot``, handed
    from chunk to chunk (zeroed when ``offset == 0``: a request starts
    here) and written back at ``slot``, every other slot's bit for bit.
    ``pk``/``pv``/``state`` are donated. Returns the logits (V,) at the
    last real position, the pool and the state."""
    clen = toks.shape[0]
    n_phys = pk.shape[1]
    positions = (offset + jnp.arange(clen))[None]
    real = (jnp.arange(clen) < n_real)[None]
    carried = offset > 0
    attend = decode.paged_chunk_attention(pk, pv, table, offset, n_real)

    def body(carry, xs):
        x, i = carry
        layer, tail, st = xs
        tail = jnp.where(carried, tail, jnp.zeros_like(tail))
        st = jnp.where(carried, st, jnp.zeros_like(st))
        h = tfm.rms_norm(x, layer["ln1"], cfg.rms_eps)
        q, k, v = qkv(h, layer, positions, cfg)
        k, v = k[0].astype(pk.dtype), v[0].astype(pv.dtype)
        att = attn_out(attend(q[0], k, v, i * n_phys)[None], layer, cfg)
        ssm, tail, st = mixer_seq(
            h, layer, tail[None], st[None], real, n_real[None], cfg)
        x = x + (ssm + att)
        x = x + mlp(x, layer, cfg)
        return (x, i + 1), (k, v, tail[0], st[0])

    def rows(a):
        return jax.lax.dynamic_index_in_dim(a, slot, 1, keepdims=False)

    def put(a, new):
        return jax.lax.dynamic_update_index_in_dim(
            a, new.astype(a.dtype), slot, 1)

    (x, _), (k_new, v_new, tails, states) = jax.lax.scan(
        body, (_embed(params, toks[None], cfg), jnp.asarray(0, jnp.int32)),
        (params["layers"], rows(state["conv"]), rows(state["ssm"])))
    last = jax.lax.dynamic_index_in_dim(x[0], n_real - 1, 0, keepdims=False)
    pk, pv = decode.paged_chunk_write(pk, pv, k_new, v_new, table, offset)
    return _head(last, params, cfg), pk, pv, {
        "conv": put(state["conv"], tails), "ssm": put(state["ssm"], states)}


def paged_decode_step(params, pk, pv, state, tokens, positions, tables,
                      live, cfg: FalconH1Config):
    """One decode token for every row: K/V read through the block tables
    (:func:`decode.paged_attention`, each K/V head serving its group of
    query heads) and written in place, the recurrent state (L, R, ...)
    advanced one step for the rows that are ``live`` (R,) and returned
    bit for bit for the others. ``pk``/``pv``/``state`` are donated."""
    n_phys = pk.shape[1]
    attend = decode.paged_attention(pk, pv, positions, tables)
    keep = live[:, None, None]

    def body(carry, layer):
        x, conv, ssm, i = carry
        tail = jax.lax.dynamic_index_in_dim(conv, i, 0, keepdims=False)
        st = jax.lax.dynamic_index_in_dim(ssm, i, 0, keepdims=False)
        h = tfm.rms_norm(x, layer["ln1"], cfg.rms_eps)
        q, k, v = qkv(h, layer, positions[:, None], cfg)
        k1 = k[:, 0].astype(pk.dtype)
        v1 = v[:, 0].astype(pv.dtype)
        o = attend(q[:, 0], k1, v1, i * n_phys)
        att = attn_out(o[:, None], layer, cfg)
        out, tail_new, st_new = mixer_step(h[:, 0], layer, tail, st, cfg)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(keep, tail_new.astype(conv.dtype), tail), i, 0)
        ssm = jax.lax.dynamic_update_index_in_dim(
            ssm, jnp.where(keep[..., None], st_new, st), i, 0)
        x = x + (out[:, None] + att)
        x = x + mlp(x, layer, cfg)
        return (x, conv, ssm, i + 1), (k1, v1)

    x = _embed(params, tokens[:, None], cfg)
    (x, conv, ssm, _), (k_new, v_new) = jax.lax.scan(
        body, (x, state["conv"], state["ssm"], jnp.asarray(0, jnp.int32)),
        params["layers"])
    pk, pv = decode.paged_write(pk, pv, k_new, v_new, positions, tables)
    return (_head(x[:, 0], params, cfg), pk, pv,
            {"conv": conv, "ssm": ssm})


class FalconH1Serving:
    """What the serving engine asks of this model (the protocol of
    :class:`rayfed_tpu.models.decode.TransformerServing`)."""

    def __init__(self, cfg: FalconH1Config):
        self.cfg = cfg

    def kv_spec(self):
        head = (self.cfg.n_kv_heads, self.cfg.head_dim)
        return (self.cfg.n_layers, head), (self.cfg.n_layers, head)

    def state_spec(self, cache_dtype=None):
        """Per slot, beside the paged K/V: name -> (layers that keep it,
        shape, dtype); every layer keeps both here. The pool owns one
        (layers, slots, *shape) array of each."""
        cfg = self.cfg
        return {
            "conv": (cfg.n_layers, (cfg.ssm_conv - 1, cfg.conv_dim),
                     cache_dtype or cfg.compute_dtype),
            "ssm": (cfg.n_layers,
                    (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32),
        }

    def serving_dtype(self):
        """As published: the tree is bfloat16 already, and ``conv_w``,
        ``A_log``, ``dt_bias``, ``D`` and ``ssm_norm`` are widened to
        float32 where they are used, whatever they arrive in."""
        return None

    def prefill_rows(self, params, prompts, last_idx, row_len, cache_dtype,
                     landed):
        last, k, v, state = prefill_rows(
            params, prompts, last_idx, row_len, cache_dtype, self.cfg,
            landed)
        return last, (k, v), state

    def chunk(self, params, kv, state, table, slot, toks, offset, n_real):
        last, pk, pv, state = chunk(
            params, *kv, state, table, slot, toks, offset, n_real,
            self.cfg)
        return last, (pk, pv), state

    def decode_step(self, params, kv, state, tokens, positions, tables,
                    live):
        logits, pk, pv, state = paged_decode_step(
            params, *kv, state, tokens, positions, tables, live,
            self.cfg)
        return logits, (pk, pv), state


def serving_model(cfg: FalconH1Config) -> FalconH1Serving:
    return FalconH1Serving(cfg)
