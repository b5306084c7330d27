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

"""The serving engine's one sampler: the next token is chosen on the
device, inside the program that computed the logits.

The engine traces :func:`choose_tokens` at the end of its own wrappers
around a model's ``decode_step``, ``prefill_rows`` and ``chunk`` (the
model protocol knows nothing of it), so a program returns ``(R,)`` int32
and the ``(R, vocab)`` logits never leave the device.

A greedy row (``temperature <= 0``) takes ``argmax`` of its float32
logits, the first index at a tie: what ``np.argmax`` of the same values
gives on the host. A sampled row draws from ``softmax(logits /
temperature)`` by Gumbel-max under a counter-based key: the Threefry key
whose two words are ``(seed, index)``, with ``index`` the token's
position in the request's output. So a request's tokens are a function
of (version, prompt, seed) alone, whatever shares its batch, whichever
slot it sits in, and the same when a preemption runs it again — there
is no generator state to rewind. The noise is skipped, inside the one
program, when no row of the batch asks for it.

The key is built from its words (``wrap_key_data``), not by
``fold_in(key(seed), index)``: the sampler is traced into every program
of the engine, a process lowers each of them at start-up even when the
compile cache is warm, and on the chip's host ``key`` + ``fold_in``
under ``vmap`` took 0.3 s of lowering a program (11-13 programs: 3.7 s
of every cell's set-up) where the words themselves take 0.06
(``PERF.md`` §6, PR 30). Distinct (seed, index) pairs are distinct keys
of the cipher either way.

The three per-row scalars ride to the device as ONE ``(3, R)`` int32
array (:func:`pack`) beside the step's other small inputs.

A model that generates by blocks (``decode.BlockSpec``) has the sampler
return each chosen id WITH its probability, and the unmasking rule is
traced behind it (:func:`unmask`): which of a row's masked positions take
their chosen id in this step is decided here, on the device, so the host
still samples nothing. A sampled row's key is then ``(seed, index * steps
+ step)``: ``index`` the position's place in the request's output,
``step`` the denoising step of its block (a fourth row of :func:`pack`),
so that a position redrawn in a later step of its block draws anew and a
request's tokens stay a function of (version, prompt, seed) alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def choose_tokens(logits, temperature, seed, index):
    """``(logits (R, V), temperature (R,) float32, seed (R,) uint32,
    index (R,) int32) -> (R,) int32``: each row's next token (module
    docstring). A junk row carries temperature 0."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = temperature > 0

    def draw_row(row, t, s, i):
        key = jax.random.wrap_key_data(
            jnp.stack([s, i.astype(jnp.uint32)]), impl="threefry2x32"
        )
        noise = jax.random.gumbel(key, row.shape, jnp.float32)
        return jnp.argmax(row / t + noise).astype(jnp.int32)

    def draw():
        drawn = jax.vmap(draw_row)(
            logits, jnp.where(sampled, temperature, 1.0), seed, index
        )
        return jnp.where(sampled, drawn, greedy)

    return jax.lax.cond(jnp.any(sampled), draw, lambda: greedy)


def choose_with_confidence(logits, temperature, seed, index):
    """:func:`choose_tokens`, and each chosen id's probability under
    ``softmax(logits)`` (R,) float32 beside it."""
    logits = logits.astype(jnp.float32)
    ids = choose_tokens(logits, temperature, seed, index)
    picked = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return ids, jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))


def pack(temperature, seed, index, step=None) -> np.ndarray:
    """The sampler's per-row scalars as one ``(3, R)`` int32 host array
    (one upload): the float32 bits of the temperatures, the seeds (any
    Python ints) modulo 2**32, the output positions; ``(4, R)`` with the
    denoising steps of the rows' blocks where the model generates by
    blocks."""
    rows = [
        np.asarray(temperature, np.float32).view(np.int32),
        np.array([int(s) & 0xFFFFFFFF for s in seed], np.uint32).view(
            np.int32),
        np.asarray(index, np.int32),
    ]
    if step is not None:
        rows.append(np.asarray(step, np.int32))
    return np.stack(rows)


def choose_packed(logits, draw):
    """:func:`choose_tokens` on a :func:`pack`-ed ``(3, R)`` array."""
    return choose_tokens(
        logits,
        jax.lax.bitcast_convert_type(draw[0], jnp.float32),
        jax.lax.bitcast_convert_type(draw[1], jnp.uint32),
        draw[2],
    )


def unmask(logits, block, draw, spec, active):
    """One denoising step's choice for every row that carries a block:
    ``logits`` (R, B, V) at the block's positions (unshifted: position
    ``j``'s logits are for the token AT ``j``), ``block`` (R, B) int32 the
    carried ids (``spec.mask_id`` where still masked), ``draw`` a
    :func:`pack`-ed ``(4, R)`` array (index: the place of the block's
    FIRST position in the request's output), ``spec`` a
    ``decode.BlockSpec``, ``active`` (R,) bool the rows that denoise in
    this step (live, and not committing). Returns (the block after the
    step (R, B) int32, positions newly unmasked, an int32 scalar).

    Every masked position gets a candidate ``x0`` (argmax, or a draw from
    ``softmax(logits / t)`` on a sampled row) and its confidence
    ``softmax(logits)[x0]`` in float32; the mask id itself is never a
    candidate (its logit is left out). ``spec.quota(step)`` positions at
    least are unmasked: those of largest confidence, the first at a tie
    (``low_confidence_static``); under ``low_confidence_dynamic`` all
    those whose confidence passes ``spec.threshold`` instead, where at
    least that many do. Only masked positions are ever written."""
    n_rows, n_pos, vocab = logits.shape
    temperature = jax.lax.bitcast_convert_type(draw[0], jnp.float32)
    seed = jax.lax.bitcast_convert_type(draw[1], jnp.uint32)
    step = draw[3]
    index = (draw[2][:, None] + jnp.arange(n_pos)) * spec.steps + step[:, None]
    logits = jnp.where(jnp.arange(vocab) == spec.mask_id, -jnp.inf,
                       logits.astype(jnp.float32))
    x0, conf = choose_with_confidence(
        logits.reshape(n_rows * n_pos, vocab),
        jnp.repeat(temperature, n_pos), jnp.repeat(seed, n_pos),
        index.reshape(-1))
    x0, conf = x0.reshape(n_rows, n_pos), conf.reshape(n_rows, n_pos)
    masked = block == spec.mask_id
    conf = jnp.where(masked, conf, -jnp.inf)
    rest = spec.length % spec.steps
    quota = spec.length // spec.steps + (step < rest).astype(jnp.int32)
    # Place of each position in the order of falling confidence, the
    # earlier position first at a tie.
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(n_pos)[None, :] < jnp.arange(n_pos)[:, None]))
    chosen = masked & (ahead.sum(-1) < quota[:, None])
    if spec.rule == "low_confidence_dynamic":
        high = masked & (conf > spec.threshold)
        chosen = jnp.where(
            (high.sum(-1) >= quota)[:, None], high, chosen)
    elif spec.rule != "low_confidence_static":
        raise ValueError(f"unknown remasking rule {spec.rule!r}")
    chosen &= active[:, None]
    return (jnp.where(chosen, x0, block).astype(jnp.int32),
            jnp.sum(chosen, dtype=jnp.int32))
