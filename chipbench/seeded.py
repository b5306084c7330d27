"""Everything a run draws from ``--seed``: weights and token batches.

The benchmark makes the weights; the program and the plain reference are
each handed them (the reference takes nothing the program has made). One
jitted call builds the whole tree on the device, in float32, the type the
trainers hold and ``fed_aggregate`` publishes.

Canonical layout (what the reference reads), all float32:

    embed (V, d)   ln_f (d)   lm_head (d, V)
    layers: ln1 (L, d)  wq wk wv (L, d, H*Dh)  wo (L, H*Dh, d)
            ln2 (L, d)  w_gate w_up (L, d, f)   w_down (L, f, d)

``to_program_tree`` only renames and reshapes (free inside the jit) into
the tree ``rayfed_tpu.models.transformer`` computes on; a model with
another tree gets another adapter file, not an edit here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims_of(model: dict) -> tuple:
    """(V, d, H, Dh, f, L) from a configuration's published keys."""
    d = int(model["hidden_size"])
    h = int(model["num_attention_heads"])
    assert int(model.get("num_key_value_heads", h)) == h, "MHA only"
    return (int(model["vocab_size"]), d, h, d // h,
            int(model["intermediate_size"]), int(model["num_hidden_layers"]))


def key_of(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def canonical_weights(key, dims: tuple) -> dict:
    v, d, h, dh, f, n = dims
    ks = jax.random.split(key, 12)

    def normal(k, shape, std):
        return jax.random.normal(k, shape, jnp.float32) * std

    def scale(k, shape):
        # Norm scales off 1, so that a reference that forgot one shows.
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

    return {
        "embed": normal(ks[0], (v, d), 0.02),
        "ln_f": scale(ks[1], (d,)),
        "lm_head": normal(ks[2], (d, v), d ** -0.5),
        "layers": {
            "ln1": scale(ks[3], (n, d)),
            "wq": normal(ks[4], (n, d, h * dh), d ** -0.5),
            "wk": normal(ks[5], (n, d, h * dh), d ** -0.5),
            "wv": normal(ks[6], (n, d, h * dh), d ** -0.5),
            "wo": normal(ks[7], (n, h * dh, d), (h * dh) ** -0.5),
            "ln2": scale(ks[8], (n, d)),
            "w_gate": normal(ks[9], (n, d, f), d ** -0.5),
            "w_up": normal(ks[10], (n, d, f), d ** -0.5),
            "w_down": normal(ks[11], (n, f, d), f ** -0.5),
        },
    }


def to_program_tree(w: dict, dims: tuple) -> dict:
    """Canonical -> the tree of ``rayfed_tpu.models.transformer``."""
    _, d, h, dh, _, n = dims
    lay = dict(w["layers"])
    for name in ("wq", "wk", "wv"):
        lay[name] = lay[name].reshape(n, d, h, dh)
    lay["wo"] = lay["wo"].reshape(n, h, dh, d)
    return {"embed": w["embed"], "layers": lay, "ln_f": w["ln_f"],
            "lm_head": w["lm_head"]}


def from_program_tree(p: dict, dims: tuple) -> dict:
    """The inverse renaming, for comparing leaf by leaf."""
    _, d, h, dh, _, n = dims
    lay = dict(p["layers"])
    for name in ("wq", "wk", "wv"):
        lay[name] = lay[name].reshape(n, d, h * dh)
    lay["wo"] = lay["wo"].reshape(n, h * dh, d)
    return {"embed": p["embed"], "layers": lay, "ln_f": p["ln_f"],
            "lm_head": p["lm_head"]}


@functools.partial(jax.jit, static_argnums=(1,))
def make_canonical(key, dims):
    return canonical_weights(key, dims)


@functools.partial(jax.jit, static_argnums=(1,))
def make_program_tree(key, dims):
    return to_program_tree(canonical_weights(key, dims), dims)


def batch_key(seed: int, party_index: int, step: int):
    """The key of one party's ``step``-th batch (steps count on across
    rounds, so no batch is seen twice)."""
    return jax.random.fold_in(
        jax.random.fold_in(key_of(seed), 1000 + party_index), step)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def make_batch(key, batch: int, seq: int, vocab: int):
    """(inputs, targets), both (batch, seq): one block of token ids
    shifted by one; every row differs."""
    tok = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
    return tok[:, :-1], tok[:, 1:]
