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

"""Olmo-Hybrid: linear-attention layers with a full-attention layer among
every few (``model_type: olmo_hybrid``; three to one as published).

Two kinds of layer in one stack, each with its own kind of cache:

* a *full* layer is causal softmax attention (an RMSNorm over the whole
  query and the whole key projection before the heads are split, no
  rotary positions where ``rope_theta`` is null) and keeps a key and a
  value row a token in the paged pool;
* a *linear* layer is the gated delta rule (arXiv:2412.06464): it keeps
  NO row a token but, a slot, a state ``S`` (heads, dv, dk) in float32
  and the tail of a depthwise causal convolution (the last ``K - 1``
  inputs of its q | k | v channels). Per head, with ``S_{-1} = 0``::

      S'  = alpha_t S_{t-1}           alpha_t = exp(g_t) in (0, 1)
      u_t = v_t - S' k^_t
      S_t = S' + beta_t u_t k^_t^T    beta_t in (0, 2): the transition's
      o_t = S_t q^_t                  eigenvalue along k^ is 1 - beta

Both kinds share the block of the family (arXiv:2501.00656): the norm
sits on the OUTPUT of each part, inside the residual, ``h = x +
N_a(Mixer(x))``, ``y = h + N_f(MLP(h))``.

A full layer's cached rows hold the K/V heads padded with zero heads to
a multiple of 8 (30 -> 32): a TPU tiles a (30, 128) bfloat16 row as (32,
128) anyway, and the paged-read kernel (:mod:`rayfed_tpu.ops.
paged_attention`) copies whole tiles of heads. The queries are padded to
match and the padded heads' outputs (zero) dropped before ``Wo``.

The pool allocates each cache for the layers that keep it
(``kv_spec``: K/V as deep as the full layers; ``state_spec``: ``S`` and
the tail as deep as the linear ones), and the programs index an array by
a layer's ordinal among those that keep it. The stack is walked a period
of ``layer_types`` at a time (a ``lax.scan`` over the periods, the layers
of one period unrolled inside it).

The delta rule has two forms. A decode row takes the step above
(:func:`delta_step`; on a TPU backend, where :func:`delta_step_is_kernel`
says so by what the state's array shows, the same lines as one Pallas
kernel over the stacked state, in place:
:func:`rayfed_tpu.ops.delta_rule.delta_state_step`). A prompt
(``prefill_rows``, ``chunk``) takes the chunked form
(:func:`delta_chunked`): sub-chunks of ``DELTA_CHUNK`` positions, within
one the unit lower-triangular system of the rule is solved at once and
the state is handed on from sub-chunk to sub-chunk; a prompt's chunks
hand ``S`` and the tail on through the pool's state row.
The recurrence is the definition; the chunked form is held to it by
``tests/test_olmo_hybrid.py``, the kernel by
``tests/test_delta_step_kernel.py``. Both forms here are plain ``jnp``.

A carried state is not masked, so (as in :mod:`rayfed_tpu.models.
falcon_h1`) a padded position must leave everything as it was (``g = 0``
and ``beta = 0`` there: ``alpha = 1``, no update; the tail kept is that
of the last real inputs), a request starts from zero, and a row that
sits a decode step out (``live`` false) gets ``S`` and its tail back bit
for bit.

``S``, ``g``, ``beta``, the L2 norms, the gated norm and the whole
recurrence are float32 (its matmuls at the highest precision: a state
rounded every step drifts); everything else takes compute-dtype operands
and accumulates in float32.

Parameter tree (``Ll`` linear layers, ``Lf`` full ones, leaves in
``param_dtype``; ``H`` heads)::

    embed (V, d)   ln_f (d)   lm_head (d, V)
    linear: w_qkv (Ll, d, 2*H*dk + H*dv)       zones q | k | v
            conv_w (Ll, K, 2*H*dk + H*dv)
            w_ab (Ll, d, 2*H)                  zones a (decay) | b (beta)
            A_log dt_bias (Ll, H)
            w_g (Ll, d, H*dv)  o_norm (Ll, dv)  w_o (Ll, H*dv, d)
            norm_mixer norm_mlp (Ll, d)
            w_gate w_up (Ll, d, f)   w_down (Ll, f, d)
    full:   wq (Lf, d, Hq*Dh)  wk wv (Lf, d, Hkv*Dh)  wo (Lf, Hq*Dh, d)
            q_norm (Lf, Hq*Dh)  k_norm (Lf, Hkv*Dh)
            norm_mixer norm_mlp (Lf, d)
            w_gate w_up (Lf, d, f)   w_down (Lf, f, d)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from rayfed_tpu import utils
from rayfed_tpu.models import decode, falcon_h1
from rayfed_tpu.models import transformer as tfm

Params = Dict[str, Any]
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"
# Positions the chunked form of the delta rule solves at once.
DELTA_CHUNK = 64
# Under the root of the L2 norms of q and k (the published
# implementation's; the row has no key for it).
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab: int = 100352
    d_model: int = 3840
    layer_types: Tuple[str, ...] = ((LINEAR,) * 3 + (FULL,)) * 8
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    d_ff: int = 11008
    lin_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    lin_conv: int = 4
    allow_neg_eigval: bool = True
    # None: no rotary positions (the published ``rope_theta`` is null).
    rope_theta: Optional[float] = None
    rms_eps: float = 1e-6
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        other = sorted(set(self.layer_types) - {LINEAR, FULL})
        if other:
            raise ValueError(
                f"olmo_hybrid: layer_types {other} are not computed here "
                f"(only {LINEAR!r} and {FULL!r})")
        if not (self.n_linear and self.n_full):
            raise ValueError(
                "olmo_hybrid: the stack holds both kinds of layer "
                f"(layer_types {self.layer_types})")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                "olmo_hybrid: query heads must be a multiple of K/V heads: "
                f"{self.n_heads} over {self.n_kv_heads}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_linear(self) -> int:
        return self.layer_types.count(LINEAR)

    @property
    def n_full(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def period(self) -> int:
        """The shortest prefix of ``layer_types`` whose repetition is the
        whole stack."""
        n = self.n_layers
        return next(p for p in range(1, n + 1) if n % p == 0
                    and self.layer_types == self.layer_types[:p] * (n // p))

    @property
    def cache_kv_heads(self) -> int:
        """K/V heads of a cached row: whole tiles of 8, the last zero."""
        return -(-self.n_kv_heads // 8) * 8

    @property
    def conv_dim(self) -> int:
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @classmethod
    def from_published(cls, config: Dict[str, Any], **overrides):
        """The configuration from the keys of a published ``config.json``
        (``model_type: olmo_hybrid``); ``layer_types`` is read as far as
        ``num_hidden_layers``. What is not computed here is refused by
        name."""
        c = config
        for key, want in (
            ("hidden_act", "silu"), ("attention_bias", False),
            ("tie_word_embeddings", False),
        ):
            if c.get(key, want) != want:
                raise ValueError(
                    f"olmo_hybrid: {key}={c[key]!r} is not computed here "
                    f"(only {want!r})")
        if c["linear_num_value_heads"] != c["linear_num_key_heads"]:
            raise ValueError(
                "olmo_hybrid: linear_num_value_heads="
                f"{c['linear_num_value_heads']} != linear_num_key_heads="
                f"{c['linear_num_key_heads']} is not computed here (no "
                "repeat of key heads over value heads)")
        rope = dict(c.get("rope_parameters") or {})
        theta = rope.pop("rope_theta", None)
        scaling = sorted(k for k, v in rope.items()
                         if v is not None and not (
                             k == "rope_type" and v == "default"))
        if scaling:
            raise ValueError(
                f"olmo_hybrid: rope_parameters {scaling} (a scaling of the "
                "rotary positions) is not computed here")
        n = c["num_hidden_layers"]
        if len(c["layer_types"]) < n:
            raise ValueError(
                f"olmo_hybrid: layer_types names {len(c['layer_types'])} "
                f"layers, num_hidden_layers is {n}")
        fields = dict(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            layer_types=tuple(c["layer_types"][:n]),
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim")
            or c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"],
            lin_heads=c["linear_num_key_heads"],
            lin_key_dim=c["linear_key_head_dim"],
            lin_value_dim=c["linear_value_head_dim"],
            lin_conv=c["linear_conv_kernel_dim"],
            allow_neg_eigval=bool(c["linear_allow_neg_eigval"]),
            rope_theta=None if theta is None else float(theta),
            rms_eps=float(c["rms_norm_eps"]),
        )
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Pieces of a block
# ---------------------------------------------------------------------------


def _mm(x, w, cfg: OlmoHybridConfig, out_dtype=None):
    """Compute-dtype operands, float32 accumulation, one rounding (none
    for a float32 ``out_dtype``)."""
    y = jnp.einsum(
        "...d,df->...f", x, w.astype(cfg.compute_dtype),
        preferred_element_type=F32,
    )
    return y.astype(out_dtype or cfg.compute_dtype)


def mlp(h, layer, cfg: OlmoHybridConfig):
    gate = jax.nn.silu(_mm(h, layer["w_gate"], cfg))
    return _mm(gate * _mm(h, layer["w_up"], cfg), layer["w_down"], cfg)


def block(x, mixed, layer, cfg: OlmoHybridConfig):
    """Both kinds of layer: the norms on the parts' outputs, inside the
    residual."""
    h = x + tfm.rms_norm(mixed, layer["norm_mixer"], cfg.rms_eps)
    return h + tfm.rms_norm(mlp(h, layer, cfg), layer["norm_mlp"],
                            cfg.rms_eps)


def qkv(x, layer, positions, cfg: OlmoHybridConfig):
    """A full layer's Q (B, S, H, Dh) and K, V (B, S, Hkv, Dh): the norms
    over the whole projections, then the heads; rotated by halves only
    where the configuration holds a ``rope_theta``."""
    lead = x.shape[:-1]
    q = tfm.rms_norm(_mm(x, layer["wq"], cfg), layer["q_norm"], cfg.rms_eps)
    k = tfm.rms_norm(_mm(x, layer["wk"], cfg), layer["k_norm"], cfg.rms_eps)
    v = _mm(x, layer["wv"], cfg)
    q = q.reshape(*lead, cfg.n_heads, cfg.head_dim)
    k = k.reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    if cfg.rope_theta is not None:
        q, k = tfm.rope(q, k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(o, layer, cfg: OlmoHybridConfig):
    """``Wo`` of the heads' outputs ``o`` (.., H or more, Dh): heads
    beyond the model's (a padded cache's) are dropped."""
    o = o[..., :cfg.n_heads, :]
    return _mm(o.reshape(*o.shape[:-2], -1), layer["wo"], cfg)


def pad_heads(x, groups: int, cfg: OlmoHybridConfig):
    """``x`` (.., groups * Hkv, Dh) with zero heads behind it, up to the
    cache's ``groups * cache_kv_heads``: K/V head i still serves query
    heads i*G .. (i+1)*G - 1."""
    short = groups * (cfg.cache_kv_heads - cfg.n_kv_heads)
    if not short:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, short), (0, 0)])


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _split_qkv(qkv_c, cfg: OlmoHybridConfig):
    """q^ and k^ (.., H, dk), L2-normalised in float32 (q^ also scaled by
    ``dk ** -0.5``), and v (.., H, dv) float32, of the convolved zone."""
    h, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    lead = qkv_c.shape[:-1]
    x = qkv_c.astype(F32)
    q = x[..., :h * dk].reshape(*lead, h, dk)
    k = x[..., h * dk:2 * h * dk].reshape(*lead, h, dk)
    v = x[..., 2 * h * dk:].reshape(*lead, h, dv)
    return _l2(q) * dk**-0.5, _l2(k), v


def _decay_beta(x, layer, cfg: OlmoHybridConfig):
    """``g`` (.., H) <= 0, the log of the decay, and ``beta`` (.., H) in
    (0, 2) (in (0, 1) without ``allow_neg_eigval``), float32."""
    ab = _mm(x, layer["w_ab"], cfg, F32)
    a, b = ab[..., :cfg.lin_heads], ab[..., cfg.lin_heads:]
    g = -jnp.exp(layer["A_log"].astype(F32)) * jax.nn.softplus(
        a + layer["dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.allow_neg_eigval else 1.0)
    return g, beta


def delta_step(q, k, v, g, beta, state):
    """One position of the gated delta rule for every row: ``q``/``k``
    (R, H, dk), ``v`` (R, H, dv), ``g``/``beta`` (R, H), ``state`` (R, H,
    dv, dk), all float32. Elementwise products and sums (no matmul
    rounds anything). As written the state is read and written once; a
    TPU's compiler makes three reads and a write of it (each sum over
    ``dk`` is a fusion of its own, the update with its write-back into
    the stack a third: ledger, PR 48, the breakdown of
    ``olmohybrid-assist-closed48``). The one-pass form is the kernel
    :func:`rayfed_tpu.ops.delta_rule.delta_state_step`, whose definition
    and whose test's reference this function is."""
    with jax.named_scope("serve/delta_rule"):
        decayed = jnp.exp(g)[..., None, None] * state
        u = v - jnp.sum(decayed * k[..., None, :], axis=-1)
        state = decayed + (beta[..., None] * u)[..., None] * k[..., None, :]
        return jnp.sum(state * q[..., None, :], axis=-1), state


def delta_step_is_kernel(delta) -> bool:
    """Whether a decode step advances the stacked state ``delta`` (layers,
    R, H, dv, dk) through the Pallas kernel: on a TPU backend, a float32
    state whose ``dv`` is whole sublanes (a head's tile is copied and
    computed on whole)."""
    return (utils.is_tpu_backend() and delta.dtype == F32
            and delta.shape[-2] % 8 == 0)


def _unit_lower_inverse(a):
    """``(I + tril(a, -1))^-1`` of (.., C, C) by forward substitution,
    a row a trip (row ``i`` of the inverse is ``e_i - a[i] @ rows < i``):
    as stable as the solve itself, where the series ``sum (-a)^n`` is not
    (``beta`` near 2 over alike keys makes its terms huge)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    a = jnp.tril(a, -1)

    def row(i, inv):
        a_i = jax.lax.dynamic_index_in_dim(a, i, -2, keepdims=False)
        new = eye[i] - jnp.sum(a_i[..., :, None] * inv, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, -2)

    return jax.lax.fori_loop(1, c, row, jnp.broadcast_to(eye, a.shape))


def delta_chunked(q, k, v, g, beta, state, chunk: int):
    """The same recurrence over a sequence, in the chunked form. ``q``/
    ``k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``g``/``beta`` (B, T, H)
    (both 0 at padded positions), ``state`` (B, H, dv, dk), all float32.
    Returns ``o`` (B, T, H, dv) and the state after the last position.

    Within a sub-chunk of ``chunk`` positions, with ``gamma`` the running
    sum of ``g`` and ``Gamma_ij = exp(gamma_i - gamma_j)`` formed for ``i
    >= j`` only (no exponent is positive)::

        (I + tril(diag(beta) (K K^T * Gamma), -1)) [W | U]
            = diag(beta) [K * exp(gamma) | V]
        U' = U - W S^T
        O  = (Q * exp(gamma)) S^T + tril(Q K^T * Gamma) U'
        S <- exp(gamma_C) S + (U' * exp(gamma_C - gamma))^T K

    What does not read ``S`` is computed for every sub-chunk at once; the
    rest is a scan over the sub-chunks. Matmuls at the highest
    precision."""
    with jax.named_scope("serve/delta_rule"):
        b, t, h, dk = q.shape
        c = min(chunk, t)
        pad = -t % c
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                for a in (q, k, v, g, beta))
        n = (t + pad) // c

        def heads_first(a):
            # (B, T, H, ..) -> (N, B, H, C, ..): the sub-chunks lead.
            a = a.reshape(b, n, c, h, *a.shape[3:])
            return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

        q, k, v, g, beta = (heads_first(a) for a in (q, k, v, g, beta))
        mm = lambda spec, x, y: jnp.einsum(  # noqa: E731
            spec, x, y, precision=HI, preferred_element_type=F32)
        gamma = jnp.cumsum(g, axis=-1)                     # (N,B,H,C) <= 0
        seg = gamma[..., :, None] - gamma[..., None, :]
        causal = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # Gamma, i >= j
        into = jnp.exp(gamma)[..., None]                   # since the start
        a = beta[..., None] * mm("...ik,...jk->...ij", k, k) * decay
        rhs = beta[..., None] * jnp.concatenate([k * into, v], axis=-1)
        wu = mm("...ij,...jd->...id", _unit_lower_inverse(a), rhs)
        w, u = wu[..., :dk], wu[..., dk:]
        qk = mm("...ik,...jk->...ij", q, k) * decay
        to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]
        whole = jnp.exp(gamma[..., -1])[..., None, None]

        def sub_chunk(s, xs):
            w_, u_, qk_, qin, k_, to_end_, whole_ = xs
            u_ = u_ - mm("...ck,...vk->...cv", w_, s)
            o = mm("...ck,...vk->...cv", qin, s) + mm(
                "...ij,...jv->...iv", qk_, u_)
            s = whole_ * s + mm("...cv,...ck->...vk", u_ * to_end_, k_)
            return s, o

        state, o = jax.lax.scan(
            sub_chunk, state, (w, u, qk, q * into, k, to_end, whole))
        # (N, B, H, C, dv) -> (B, T, H, dv)
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
        return o.reshape(b, t + pad, h, -1)[:, :t], state


def gated_norm(o, gate, layer, cfg: OlmoHybridConfig):
    """Per head: an RMSNorm of ``o`` (.., H, dv) over ``dv`` with one
    learned (dv,) scale, times ``silu(gate)``; float32 inside. Returns
    the heads concatenated, (.., H*dv) in the compute dtype."""
    inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
    out = (o * inv * layer["o_norm"].astype(F32)).reshape(gate.shape)
    return (out * jax.nn.silu(gate.astype(F32))).astype(cfg.compute_dtype)


def linear_seq(x, layer, tail, state, real, n_real, cfg: OlmoHybridConfig):
    """A linear layer's mixer over a sequence ``x`` (B, S, d) whose
    positions ``real`` (B, S) count (a prefix of ``n_real`` (B,) of
    them), continuing from ``tail`` and ``state``. Returns the mixer's
    output, the new tail and the new state."""
    with jax.named_scope("serve/linear_attn"):
        qkv_c, tail = falcon_h1.conv_seq(
            _mm(x, layer["w_qkv"], cfg), tail, layer, n_real)
        q, k, v = _split_qkv(qkv_c, cfg)
        g, beta = _decay_beta(x, layer, cfg)
        keep = real[..., None]
        o, state = delta_chunked(
            q, k, v, jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0),
            state, DELTA_CHUNK)
        o = gated_norm(o, _mm(x, layer["w_g"], cfg), layer, cfg)
        return _mm(o, layer["w_o"], cfg), tail, state


def linear_step(x, layer, tail, step, cfg: OlmoHybridConfig):
    """A linear layer's mixer for one position of every row: ``x`` (R,
    d). ``step(q, k, v, g, beta)`` advances the rows' state by the
    position and returns ``o`` and the state as its caller keeps it.
    Returns the mixer's output, the new tail and that state."""
    with jax.named_scope("serve/linear_attn"):
        qkv_c, tail = falcon_h1.conv_step(
            _mm(x, layer["w_qkv"], cfg), tail, layer)
        q, k, v = _split_qkv(qkv_c, cfg)
        g, beta = _decay_beta(x, layer, cfg)
        o, state = step(q, k, v, g, beta)
        o = gated_norm(o, _mm(x, layer["w_g"], cfg), layer, cfg)
        return _mm(o, layer["w_o"], cfg), tail, state


def _embed(params, tokens, cfg: OlmoHybridConfig):
    return params["embed"][tokens].astype(cfg.compute_dtype)


def _head(x, params, cfg: OlmoHybridConfig):
    """Logits (.., V) float32 of hidden states ``x`` (.., d)."""
    x = tfm.rms_norm(x, params["ln_f"], cfg.rms_eps)
    return _mm(x, params["lm_head"], cfg, F32)


def zero_state(cfg: OlmoHybridConfig, rows: int, cache_dtype=None):
    """What a request starts from, for ``rows`` rows of one linear
    layer: the tail and ``S``."""
    return (
        jnp.zeros((rows, cfg.lin_conv - 1, cfg.conv_dim),
                  cache_dtype or cfg.compute_dtype),
        jnp.zeros((rows, cfg.lin_heads, cfg.lin_value_dim, cfg.lin_key_dim),
                  F32),
    )


# ---------------------------------------------------------------------------
# The stack, a period at a time
# ---------------------------------------------------------------------------


def _layer_at(tree, ordinal):
    """Layer ``ordinal`` (a traced int32) of leaves stacked over the
    layers of a kind: a dynamic slice of each leaf where it is used (the
    form a ``lax.scan`` gives its ``xs``: a matmul reads its layer of the
    stacked weights in place, nothing is copied)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, ordinal, 0, keepdims=False),
        tree)


def scan_periods(cfg: OlmoHybridConfig, params, carry, linear, full):
    """Walk the stack: a ``lax.scan`` over the periods of ``layer_types``
    with the layers of one period unrolled. ``linear(carry, layer,
    ordinal)`` and ``full(carry, layer, ordinal)`` run one layer of their
    kind (``ordinal``: the layer's place among the layers of its kind in
    the whole stack, a traced int32; ``layer``: its leaves) and return
    the new carry and the layer's outputs. Returns the carry and the
    outputs of each kind, stacked over its layers."""
    kinds = cfg.layer_types[:cfg.period]
    n_periods = cfg.n_layers // cfg.period
    per_lin, per_full = kinds.count(LINEAR), kinds.count(FULL)

    def body(carry, p):
        lin_out, full_out, jl, jf = [], [], 0, 0
        for kind in kinds:
            if kind == LINEAR:
                ordinal = p * per_lin + jl
                carry, out = linear(
                    carry, _layer_at(params["linear"], ordinal), ordinal)
                lin_out.append(out)
                jl += 1
            else:
                ordinal = p * per_full + jf
                carry, out = full(
                    carry, _layer_at(params["full"], ordinal), ordinal)
                full_out.append(out)
                jf += 1
        stack = lambda outs: jax.tree_util.tree_map(  # noqa: E731
            lambda *a: jnp.stack(a), *outs)
        return carry, (stack(lin_out), stack(full_out))

    carry, outs = jax.lax.scan(
        body, carry, jnp.arange(n_periods, dtype=jnp.int32))
    # (periods, of the kind a period, ..) -> (layers of the kind, ..)
    lin_out, full_out = jax.tree_util.tree_map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), outs)
    return carry, lin_out, full_out


# ---------------------------------------------------------------------------
# Whole-model programs
# ---------------------------------------------------------------------------


def _dense_rows(params, tokens, n_real, cache_dtype, cfg: OlmoHybridConfig):
    """tokens (B, S), real up to ``n_real`` (B,), from an empty cache and
    a zero state: the final hidden states (B, S, d), the full layers' K/V
    (Lf, B, S, cache heads, Dh) and the linear layers' tails and states (Ll, B,
    ..) after each row's last real token."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    real = positions < n_real[:, None]
    tail0, state0 = zero_state(cfg, b, cache_dtype)

    def linear(x, layer, _):
        mixed, tail, state = linear_seq(
            x, layer, tail0, state0, real, n_real, cfg)
        return block(x, mixed, layer, cfg), (tail, state)

    def full(x, layer, _):
        with jax.named_scope("serve/attn_full"):
            q, k, v = qkv(x, layer, positions, cfg)
            mixed = attn_out(
                falcon_h1.gqa_attention(q, k, v, positions), layer, cfg)
        return block(x, mixed, layer, cfg), (
            pad_heads(k, 1, cfg).astype(cache_dtype),
            pad_heads(v, 1, cfg).astype(cache_dtype))

    x, (tails, states), (k, v) = scan_periods(
        cfg, params, _embed(params, tokens, cfg), linear, full)
    return x, k, v, tails, states


def forward(params: Params, tokens, cfg: OlmoHybridConfig):
    """tokens (B, S) -> logits (B, S, V) float32: no cache, zero initial
    state, every position real."""
    n_real = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, *_ = _dense_rows(params, tokens, n_real, cfg.compute_dtype, cfg)
    return _head(x, params, cfg)


def prefill_rows(params, prompts, last_idx, row_len: int, cache_dtype,
                 cfg: OlmoHybridConfig, landed=None):
    """Right-padded prompts (R, S), each real up to ``last_idx`` (R,),
    from an empty cache and a zero state. Returns the logits (R, V) at
    ``last_idx``, the full layers' K/V rows (Lf, R, S, cache heads, Dh) (as
    long
    as the bucket: the pool lands rows of the length they come in) and
    each row's state after its last real token (Ll, R, ..). Only the
    rows ``landed`` (R,) bool names are computed (all, when it is None),
    one at a time (:func:`decode.landed_rows`)."""
    del row_len
    r, s = prompts.shape
    cache_dtype = cache_dtype or cfg.compute_dtype
    tail0, state0 = zero_state(cfg, 1, cache_dtype)

    def one_row(i):
        prompt = jax.lax.dynamic_slice_in_dim(prompts, i, 1, 0)
        n_real = jax.lax.dynamic_slice_in_dim(last_idx, i, 1, 0) + 1
        x, k, v, tails, states = _dense_rows(
            params, prompt, n_real, cache_dtype, cfg)
        last = jax.lax.dynamic_index_in_dim(
            x[0], n_real[0] - 1, 0, keepdims=False)
        return (_head(last, params, cfg), k[:, 0], v[:, 0], tails[:, 0],
                states[:, 0])

    kv = jnp.zeros((cfg.n_full, r, s, cfg.cache_kv_heads, cfg.head_dim),
                   cache_dtype)
    logits, k, v, tails, states = decode.landed_rows(one_row, landed, (
        jnp.zeros((r, cfg.vocab), F32), kv, kv,
        jnp.zeros((cfg.n_linear, r) + tail0.shape[1:], tail0.dtype),
        jnp.zeros((cfg.n_linear, r) + state0.shape[1:], F32),
    ))
    return logits, k, v, {"conv": tails, "delta": states}


def chunk(params, pk, pv, state, table, slot, toks, offset, n_real,
          cfg: OlmoHybridConfig):
    """One prompt chunk ``toks`` (C,), real up to ``n_real``, at positions
    ``offset .. offset + C - 1`` of the slot whose block table is
    ``table``. A full layer reads its context from the pool through the
    table (:func:`decode.paged_chunk_attention`) and its own K/V are
    written there in place; a linear layer reads the slot's rows of the
    state (Ll, slots, ..) at ``slot`` (what the chunk before handed on;
    zero when ``offset == 0``: a request starts here) and writes them
    back, every other slot's bit for bit. ``pk``/``pv``/``state`` are
    donated. Returns the logits (V,) at the last real position, the pool
    and the state."""
    clen = toks.shape[0]
    n_phys = pk.shape[1]
    positions = (offset + jnp.arange(clen))[None]
    real = (jnp.arange(clen) < n_real)[None]
    carried = offset > 0
    attend = decode.paged_chunk_attention(pk, pv, table, offset, n_real)
    group = cfg.n_heads // cfg.n_kv_heads

    def rows(a, ordinal):
        # The slot's rows of layer ``ordinal`` of a state array.
        return jax.lax.dynamic_slice(
            a, (ordinal, slot) + (0,) * (a.ndim - 2),
            (1, 1) + a.shape[2:])[0, 0]

    def put(a, new, ordinal):
        return jax.lax.dynamic_update_slice(
            a, new.astype(a.dtype)[None, None],
            (ordinal, slot) + (0,) * (a.ndim - 2))

    # The tails are read and written through their (layers, slots, row)
    # view: under their own shape a v5e's compiler lays them out with the
    # K - 1 inputs as the minor dimension (3 padded to 128 lanes) and
    # copies all of them in and out of every chunk, 1.1 GB each way
    # (``tests/test_tpu_compile.py`` holds the program's temporaries).
    def flat(a):
        return a.reshape(*a.shape[:2], -1)

    def linear(carry, layer, ordinal):
        x, conv, delta = carry
        # (The state's read and write-back are the mixer's too.)
        with jax.named_scope("serve/linear_attn"):
            tail = rows(flat(conv), ordinal).reshape(conv.shape[2:])
            st = rows(delta, ordinal)
            tail = jnp.where(carried, tail, jnp.zeros_like(tail))
            st = jnp.where(carried, st, jnp.zeros_like(st))
            mixed, tail, st = linear_seq(
                x, layer, tail[None], st[None], real, n_real[None], cfg)
            conv = put(flat(conv), tail.reshape(-1), ordinal).reshape(
                conv.shape)
            delta = put(delta, st[0], ordinal)
        return (block(x, mixed, layer, cfg), conv, delta), ()

    def full(carry, layer, ordinal):
        x, conv, delta = carry
        with jax.named_scope("serve/attn_full"):
            q, k, v = qkv(x, layer, positions, cfg)
            k = pad_heads(k[0], 1, cfg).astype(pk.dtype)
            v = pad_heads(v[0], 1, cfg).astype(pv.dtype)
            q = pad_heads(q[0], group, cfg)
            mixed = attn_out(
                attend(q, k, v, ordinal * n_phys)[None], layer, cfg)
        return (block(x, mixed, layer, cfg), conv, delta), (k, v)

    (x, conv, delta), _, (k_new, v_new) = scan_periods(
        cfg, params,
        (_embed(params, toks[None], cfg), state["conv"], state["delta"]),
        linear, full)
    last = jax.lax.dynamic_index_in_dim(x[0], n_real - 1, 0, keepdims=False)
    pk, pv = decode.paged_chunk_write(pk, pv, k_new, v_new, table, offset)
    return _head(last, params, cfg), pk, pv, {"conv": conv, "delta": delta}


def paged_decode_step(params, pk, pv, state, tokens, positions, tables,
                      live, cfg: OlmoHybridConfig):
    """One decode token for every row: a full layer reads K/V through the
    block tables (:func:`decode.paged_attention`) and its new K/V are
    written in place; a linear layer advances its rows of the state (Ll,
    R, ..) one step for the rows that are ``live`` (R,) and hands the
    others' back bit for bit. ``pk``/``pv``/``state`` are donated."""
    n_phys = pk.shape[1]
    attend = decode.paged_attention(pk, pv, positions, tables)
    keep = live[:, None, None]
    if delta_step_is_kernel(state["delta"]):
        # (Pallas is the engine's import, as ``decode.paged_attention``'s.)
        from rayfed_tpu.ops import delta_rule

        def step(delta, ordinal, q, k, v, g, beta):
            with jax.named_scope("serve/delta_rule"):
                return delta_rule.delta_state_step(
                    delta, ordinal, live, q, k, v, g, beta)
    else:
        def step(delta, ordinal, q, k, v, g, beta):
            st = jax.lax.dynamic_index_in_dim(
                delta, ordinal, 0, keepdims=False)
            o, st_new = delta_step(q, k, v, g, beta, st)
            return o, jax.lax.dynamic_update_index_in_dim(
                delta, jnp.where(keep[..., None], st_new, st), ordinal, 0)

    def linear(carry, layer, ordinal):
        x, conv, delta = carry
        # (The state's read and write-back are the mixer's too.)
        with jax.named_scope("serve/linear_attn"):
            tail = jax.lax.dynamic_index_in_dim(
                conv, ordinal, 0, keepdims=False)
            mixed, tail_new, delta = linear_step(
                x[:, 0], layer, tail,
                functools.partial(step, delta, ordinal), cfg)
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, jnp.where(keep, tail_new.astype(conv.dtype), tail),
                ordinal, 0)
        return (block(x, mixed[:, None], layer, cfg), conv, delta), ()

    def full(carry, layer, ordinal):
        x, conv, delta = carry
        with jax.named_scope("serve/attn_full"):
            q, k, v = qkv(x, layer, positions[:, None], cfg)
            k1 = pad_heads(k[:, 0], 1, cfg).astype(pk.dtype)
            v1 = pad_heads(v[:, 0], 1, cfg).astype(pv.dtype)
            q1 = pad_heads(q[:, 0], cfg.n_heads // cfg.n_kv_heads, cfg)
            mixed = attn_out(
                attend(q1, k1, v1, ordinal * n_phys)[:, None], layer, cfg)
        return (block(x, mixed, layer, cfg), conv, delta), (k1, v1)

    (x, conv, delta), _, (k_new, v_new) = scan_periods(
        cfg, params,
        (_embed(params, tokens[:, None], cfg), state["conv"],
         state["delta"]), linear, full)
    pk, pv = decode.paged_write(pk, pv, k_new, v_new, positions, tables)
    return (_head(x[:, 0], params, cfg), pk, pv,
            {"conv": conv, "delta": delta})


class OlmoHybridServing:
    """What the serving engine asks of this model (the protocol of
    :class:`rayfed_tpu.models.decode.TransformerServing`)."""

    def __init__(self, cfg: OlmoHybridConfig):
        self.cfg = cfg

    def kv_spec(self):
        """A key and a value row a token, in the FULL layers only (the
        heads padded to whole tiles: ``cache_kv_heads``)."""
        head = (self.cfg.cache_kv_heads, self.cfg.head_dim)
        return (self.cfg.n_full, head), (self.cfg.n_full, head)

    def state_spec(self, cache_dtype=None):
        """Per slot, in the LINEAR layers only: name -> (layers that keep
        it, shape, dtype)."""
        cfg = self.cfg
        return {
            "conv": (cfg.n_linear, (cfg.lin_conv - 1, cfg.conv_dim),
                     cache_dtype or cfg.compute_dtype),
            "delta": (cfg.n_linear, (cfg.lin_heads, cfg.lin_value_dim,
                                     cfg.lin_key_dim), F32),
        }

    def layer_windows(self):
        """Per layer the keys a token attends: every key (None) in a full
        layer, none (0) in a linear one, which reads no key and walks no
        block."""
        return tuple(None if kind == FULL else 0
                     for kind in self.cfg.layer_types)

    def serving_dtype(self):
        """As published: ``A_log``, ``dt_bias``, ``conv_w`` and ``o_norm``
        are widened to float32 where they are used, whatever they arrive
        in."""
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


def serving_model(cfg: OlmoHybridConfig) -> OlmoHybridServing:
    return OlmoHybridServing(cfg)
