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


def pack(temperature, seed, index) -> np.ndarray:
    """The sampler's per-row scalars as one ``(3, R)`` int32 host array
    (one upload): the float32 bits of the temperatures, the seeds (any
    Python ints) modulo 2**32, the output positions."""
    return np.stack([
        np.asarray(temperature, np.float32).view(np.int32),
        np.array([int(s) & 0xFFFFFFFF for s in seed], np.uint32).view(
            np.int32),
        np.asarray(index, np.int32),
    ])


def choose_packed(logits, draw):
    """:func:`choose_tokens` on a :func:`pack`-ed ``(3, R)`` array."""
    return choose_tokens(
        logits,
        jax.lax.bitcast_convert_type(draw[0], jnp.float32),
        jax.lax.bitcast_convert_type(draw[1], jnp.uint32),
        draw[2],
    )
