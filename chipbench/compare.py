"""The comparisons that decide ``correct``: the benchmark's own, shared by
the harness and the plain references (never by the program)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def leaf_norms(tree):
    """L2 norm of every leaf of a canonical tree (``seeded.py``), as
    ``{name: (n,) array}``: a stacked (L, ...) leaf gives one per layer."""
    out = {}
    for name, x in tree.items():
        if name == "layers":
            for sub, y in x.items():
                y = y.astype(jnp.float32)
                out[f"layers.{sub}"] = jnp.sqrt(
                    jnp.sum(y * y, axis=tuple(range(1, y.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))[None]
    return out


def change_norms_fn(make_old):
    """``f(new_tree, key)``: leaf norms of ``new_tree - make_old(key)`` in
    ONE jitted call, so that the old tree (remade from the seed) is never
    held beside the new one: XLA makes, subtracts and reduces leaf by leaf."""
    return jax.jit(lambda new, key: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, new, make_old(key))))


def to_host(norms):
    return {k: np.asarray(v, np.float64).tolist() for k, v in norms.items()}


def worst_leaf_gap(got, ref):
    """The worst leaf's gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger: some gradients are all but zero. Returns (gap, leaf name)."""
    flat_ref = np.concatenate([np.asarray(ref[k], np.float64) for k in ref])
    floor = float(np.median(flat_ref))
    worst, where = 0.0, None
    for k in ref:
        r = np.asarray(ref[k], np.float64)
        g = np.asarray(got[k], np.float64)
        gap = np.abs(g - r) / np.maximum(r, floor)
        i = int(np.argmax(gap))
        if where is None or gap[i] > worst:
            worst, where = float(gap[i]), f"{k}[{i}]"
    return worst, where
