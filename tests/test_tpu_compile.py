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

"""Programs of the serving path compiled for a TPU v5e that is described,
not attached (the TPU's compiler is installed here): what interpret mode
and the CPU branch cannot show. Only the TPU branch of
``moe.grouped_matmul`` (jax's megablox kernel) lives behind
``is_tpu_backend()``, so these compiles are the one place tier-1 sees it:
at the published widths of ``command-a-plus-05-2026`` and the row counts
the engine's programs hand it, and inside the whole serving programs of
``openpangu-ultra-moe-718b`` at its cell's shapes (the latent pool, both
forms of the latent read, a 7680-wide contraction in the grouped
kernel). Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture, after a test of this file has
started, and every such test is in this one file: the process that
describes it keeps the TPU's library until it exits.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip (the next
    # run warns and compiles again): keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "rows", [16, 512, 8], ids=["decode-16-rows", "chunk-512", "chunk-8"])
def test_the_routed_experts_compile_for_v5e_with_the_grouped_kernel(
        rows, one_chip, monkeypatch):
    """16 held experts of 4096 x 4096 under a 128-wide router, 8 a token:
    the decode step's 16 rows, a full chunk and the smallest ragged one.
    The program holds the Mosaic kernel three times and no copy of an
    expert's matrices."""
    from rayfed_tpu import utils
    from rayfed_tpu.models import moe

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    d, f, held, scored, k = 4096, 4096, 16, 128, 8

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = {"router": sds((d, scored)), "we_gate": sds((held, d, f)),
             "we_up": sds((held, d, f)), "we_down": sds((held, f, d))}
    compiled = jax.jit(
        lambda h, layer, live: moe.routed_experts(
            h, layer, tuple(range(held)), k, live)
    ).lower(sds((rows, d)), layer, sds((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
    # Beside its arguments (1.6 GB of experts) it needs room for the
    # sorted rows and three (rows * k, 4096) float32 results, not for
    # copies of weights.
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


@pytest.fixture(scope="module")
def dense_chunk_program(one_chip):
    """``compiled(as_a_tpu)``: the dense chunk program at
    ``coder1b-complete-closed16``'s shapes (24 layers, 16 heads of 128, 6
    slots of 2,049 positions in blocks of 16, a chunk of 256) for the
    described chip, traced as a TPU backend traces it or as every other
    backend does; each compiled once a process, so a test that compares
    the two compiles both itself wherever it runs."""
    from rayfed_tpu import utils
    from rayfed_tpu.models import decode
    from rayfed_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab=32256, d_model=2048, n_heads=16,
                                n_layers=24, d_ff=5504, rope_theta=1e5)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
    blocks_per_row = -(-2049 // 16)
    pool = sds((24, 1 + 6 * blocks_per_row, 16, 16, 128))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    programs = {}

    def compiled(as_a_tpu: bool):
        if as_a_tpu not in programs:
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(utils, "is_tpu_backend", lambda: as_a_tpu)
                programs[as_a_tpu] = jax.jit(
                    decode.serving_model(cfg).chunk, donate_argnums=(1,)
                ).lower(params, (pool, pool), {}, i32(blocks_per_row), i32(),
                        i32(256), i32(), i32()).compile()
        return programs[as_a_tpu]

    compiled.pool_bytes = 2 * 24 * (1 + 6 * blocks_per_row) * 16 * 16 * 128 * 2
    return compiled


@pytest.mark.parametrize("backend", ["as-every-backend", "as-a-tpu"])
def test_the_chunk_program_updates_the_donated_pool_in_place(
        backend, dense_chunk_program):
    """The dense chunk program at ``coder1b-complete-closed16``'s shapes:
    both halves of the donated pool come back aliased to their arguments,
    and beside them the program needs room for a chunk's activations, not
    for a copy of a pool or of a row. Traced as a TPU backend traces it,
    it is the same program: a slot reaches 2,064 keys, so its chunks keep
    the loop and the program holds no custom call
    (``decode.paged_chunk_is_kernel``)."""
    compiled = dense_chunk_program(backend == "as-a-tpu")
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= dense_chunk_program.pool_bytes
    assert memory.temp_size_in_bytes < 400e6
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # Instruction for instruction the program every other backend traces
    # (compiled here too, whichever process runs this case).
    assert text == dense_chunk_program(False).as_text()


def _cell_decode_step(workload, one_chip, chunk=None):
    """``jit_decode_step`` of a cell's model as the cell runs it (the
    configuration's file cut as the benchmark cuts it, the mix's slots and
    lengths), or its ``jit_chunk_step`` of ``chunk`` tokens, lowered for
    the described chip from shapes: (lowered, bytes of weights, bytes of
    pool)."""
    import importlib
    import inspect

    from chipbench import run
    from rayfed_tpu.models import decode

    plan = run.resolve(workload, False)
    adapter = importlib.import_module("chipbench.seeded_" + plan["reference"])
    cfg = adapter.program_cfg(plan["model"], plan["precision"])
    model = decode.serving_model(cfg)
    serving = plan["mix"]["serving"]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def tree(w):
        # (Falcon-H1's lays its tree out by the model's keys.)
        wants = inspect.signature(adapter.to_program_tree).parameters
        return adapter.to_program_tree(w, *[plan["model"]][:len(wants) - 1])

    def canonical():
        return adapter.make_canonical(jax.random.PRNGKey(0), plan["model"])

    try:
        shapes = jax.eval_shape(lambda: tree(canonical()))
    except jax.errors.TracerArrayConversionError:
        # A program tree laid out on the host: from leaves that take no
        # room (one zero, broadcast), not from the weights.
        shapes = tree(jax.tree_util.tree_map(
            lambda a: np.broadcast_to(np.zeros((), a.dtype), a.shape),
            jax.eval_shape(canonical)))
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), shapes)
    slots, block = serving["max_slots"], serving["kv_block_size"]
    blocks_per_row = -(-(serving["max_len"] + 1) // block)
    from rayfed_tpu.serving import kv_pool

    kv = tuple(sds((layers, 1 + slots * blocks_per_row, block,
                    *kv_pool._allocated(row)))
               for layers, row in model.kv_spec())
    state = {name: sds((layers, slots, *shape), dtype) for name, (
        layers, shape, dtype) in model.state_spec(jnp.bfloat16).items()}
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if chunk is None:
        lowered = jax.jit(model.decode_step, donate_argnums=(1, 2)).lower(
            params, kv, state, i32(slots), i32(slots),
            i32(slots, blocks_per_row), sds((slots,), jnp.bool_))
    else:
        lowered = jax.jit(model.chunk, donate_argnums=(1, 2)).lower(
            params, kv, state, i32(blocks_per_row), i32(), i32(chunk),
            i32(), i32())
    nbytes = lambda t: sum(  # noqa: E731
        a.dtype.itemsize * int(jnp.prod(jnp.asarray(a.shape)))
        for a in jax.tree_util.tree_leaves(t))
    return lowered, nbytes(params), nbytes(kv)


@pytest.mark.parametrize("workload, reads", [
    ("falconh1-chat-closed48", 1),      # grouped heads; layers a scan
    ("commandaplus-docs-closed24", 2),  # a full and a windowed form
])
def test_the_paged_read_is_one_kernel_a_form_in_the_decode_step(
        workload, reads, one_chip, monkeypatch):
    """The decode step of the hybrid and of the windowed model at their
    cells' shapes, compiled for v5e with the paged read as the Pallas
    kernel: lowered once a form however many layers call it (an inner
    ``jit``: the lowering is part of every start-up), present in the
    program under its name, the pool aliased and never copied."""
    from rayfed_tpu import utils

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    lowered, weights, pool_bytes = _cell_decode_step(workload, one_chip)
    # One function a form in the lowered module, called from every layer.
    assert lowered.as_text().count("func.func private @paged_read") == reads
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "paged_read" in text
    assert memory.alias_size_in_bytes >= pool_bytes
    assert weights + pool_bytes + memory.temp_size_in_bytes < 15.5e9


@pytest.mark.parametrize("workload, chunk, trips", [
    # The chunk's own keys, and the cached keys' trips: 1,024 keys of
    # the full layer and 896 of the sliding ones' 4,096-key window.
    ("commandaplus-docs-closed24", 512, 3),
    ("pangu718b-reason-closed72", 512, 2),
    # Two latent widths: 128 heads over 1,024 selected-or-not keys a
    # trip, 64 heads over one 640-key trip of the 513-key window.
    ("dots3-longdocs-closed16", 512, 4),
    # A prompt's ragged start: fewer queries than a tile holds.
    ("dots3-longdocs-closed16", 8, 4),
])
def test_the_chunk_read_is_one_kernel_a_form_in_the_long_cells(
        workload, chunk, trips, one_chip, monkeypatch):
    """The chunk programs of the three cells whose slots reach thousands
    of keys, compiled for v5e with a chunk's trips as the Pallas kernel
    (``decode.paged_chunk_is_kernel``): lowered once a shape of trip
    however many layers and trips call it, present in the program under
    its name, the pool aliased and never copied, and what the program
    needs beside weights and pool (a trip's expanded keys and values, the
    softmax's state) leaves the chip's 16.9 GB room."""
    from rayfed_tpu import utils

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    lowered, weights, pool_bytes = _cell_decode_step(
        workload, one_chip, chunk=chunk)
    assert lowered.as_text().count("func.func private @chunk_trip") == trips
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "%paged_chunk_read" in text and "%paged_read" not in text
    assert memory.alias_size_in_bytes >= pool_bytes
    print(workload, chunk, weights, pool_bytes, memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < 1.2e9
    assert weights + pool_bytes + memory.temp_size_in_bytes < 15.5e9


def test_the_dense_decode_step_compiles_for_v5e_with_the_paged_read(
        one_chip, monkeypatch):
    """``coder1b-complete-closed16``'s decode step (24 layers in a scan,
    16 heads of 128, 6 rows of 2,049 positions in blocks of 16): the
    kernel is in the program, both halves of the donated pool come back
    aliased, and beside them the step needs room for six rows'
    activations and the kernel's buffers, not for a gathered chunk."""
    from rayfed_tpu import utils
    from rayfed_tpu.models import decode
    from rayfed_tpu.models import transformer as tfm

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    cfg = tfm.TransformerConfig(vocab=32256, d_model=2048, n_heads=16,
                                n_layers=24, d_ff=5504, rope_theta=1e5)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
    blocks_per_row = -(-2049 // 16)
    pool = sds((24, 1 + 6 * blocks_per_row, 16, 16, 128))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    compiled = jax.jit(
        decode.serving_model(cfg).decode_step, donate_argnums=(1,)
    ).lower(params, (pool, pool), {}, i32(6), i32(6), i32(6, blocks_per_row),
            None).compile()
    pool_bytes = 2 * 24 * (1 + 6 * blocks_per_row) * 16 * 16 * 128 * 2
    memory = compiled.memory_analysis()
    assert "paged_read" in compiled.as_text()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 100e6


@pytest.mark.parametrize("rows, compiles", [(128, True), (256, False)])
def test_the_paged_read_holds_its_tables_in_scalar_memory(
        rows, compiles, one_chip):
    """The block tables are a scalar-prefetch operand: 1,024 blocks a row
    compile at 128 rows (``decode.PAGED_KERNEL_TABLE_ENTRIES``, up to
    which ``decode.paged_attention`` asks for the kernel) and are refused
    at 256 (1 MiB of scalar memory a core), where it keeps the loop."""
    from rayfed_tpu.models import decode
    from rayfed_tpu.ops import paged_attention

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = 1024
    assert (rows * blocks <= decode.PAGED_KERNEL_TABLE_ENTRIES) == compiles
    row, pool = sds((rows, 8, 128)), sds((2 * 4097, 16, 8, 128))
    lowered = jax.jit(lambda *a: paged_attention.paged_read(
        *a, window=None, scale=0.088)).lower(
        row, row, row, pool, pool, sds((rows,), jnp.int32),
        sds((rows, blocks), jnp.int32), sds((), jnp.int32))
    if compiles:
        assert "%paged_read" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="smem|RESOURCE_EXHAUSTED"):
            lowered.compile()


PANGU = {
    "hidden_size": 7680, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_attention_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 256,
    "n_shared_experts": 1, "num_experts_per_tok": 8,
    "routed_scaling_factor": 2.5, "rope_theta": 25600000,
    "rms_norm_eps": 1e-5,
    # The cell's cut: one dense and four expert layers, an eighth of the
    # vocabulary; experts 0-15 held below.
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "vocab_size": 19200,
}
PANGU_SLOTS, PANGU_MAX_LEN, PANGU_BLOCK = 48, 11264, 16


@pytest.mark.parametrize("program", ["decode_step", "chunk_512"])
def test_the_latent_programs_compile_for_v5e_and_fit_beside_their_pool(
        program, one_chip, monkeypatch):
    """``openpangu-ultra-moe-718b`` as ``pangu718b-reason-closed72`` runs
    it: 9.84 GB of weights and a pool of 1,152 B a token a layer (3.47
    GB as allocated, its rows padded to 640 values) go in; the pool comes back aliased to its argument, and what the
    program needs beside them leaves the chip's 16.9 GB room."""
    from rayfed_tpu import utils
    from rayfed_tpu.models import decode, pangu_ultra_moe as pm

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    cfg = pm.PanguUltraMoeConfig.from_published(PANGU, held=tuple(range(16)))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    d, h, f = cfg.d_model, cfg.n_heads, cfg.d_expert
    attn = {
        "ln1": (d,), "ln2": (d,), "ln3": (d,), "ln4": (d,),
        "wq_a": (d, cfg.q_rank), "q_norm": (cfg.q_rank,),
        "wq_b": (cfg.q_rank, h * (cfg.d_nope + cfg.d_rope)),
        "wkv_a": (d, cfg.cache_width), "kv_norm": (cfg.kv_rank,),
        "wk_b": (cfg.kv_rank, h * cfg.d_nope),
        "wv_b": (cfg.kv_rank, h * cfg.d_v), "wo": (h * cfg.d_v, d),
    }
    dense = {"w_gate": (d, cfg.d_dense), "w_up": (d, cfg.d_dense),
             "w_down": (cfg.d_dense, d)}
    expert = {"router": (d, 256), "we_gate": (16, d, f), "we_up": (16, d, f),
              "we_down": (16, f, d), "ws_gate": (d, f), "ws_up": (d, f),
              "ws_down": (f, d)}
    params = {
        "embed": sds((cfg.vocab, d)), "ln_f": sds((d,)),
        "lm_head": sds((cfg.vocab, d)),
        "layers": [{k: sds(v) for k, v in {**attn, **(
            dense if i < cfg.n_dense else expert)}.items()}
            for i in range(cfg.n_layers)],
    }
    weights = sum(2 * int(jnp.prod(jnp.asarray(a.shape)))
                  for a in jax.tree_util.tree_leaves(params))
    assert round(weights / 1e9, 2) == 9.84
    model = decode.serving_model(cfg)
    ((layers, row),) = model.kv_spec()
    assert row == (576,)
    blocks_per_row = -(-(PANGU_MAX_LEN + 1) // PANGU_BLOCK)
    # Rows padded to whole tiles, as ``PagedKVPool`` allocates them: a
    # 576-wide array left to the device's default layout gets its blocks
    # as the minor dimension, and both programs copy the pool, twice.
    from rayfed_tpu.serving import kv_pool

    assert kv_pool._allocated(row) == (640,)
    pool = sds((layers, 1 + PANGU_SLOTS * blocks_per_row, PANGU_BLOCK, 640))
    pool_bytes = 2 * int(jnp.prod(jnp.asarray(pool.shape)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if program == "decode_step":
        r = PANGU_SLOTS
        lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            params, (pool,), {}, i32(r), i32(r), i32(r, blocks_per_row),
            sds((r,), jnp.bool_))
    else:
        lowered = jax.jit(model.chunk, donate_argnums=(1,)).lower(
            params, (pool,), {}, i32(blocks_per_row), i32(), i32(512),
            i32(), i32())
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # The decode step reads the latent pool through the paged kernel (one
    # array, every head the same rows), a chunk through its own (PR 45).
    assert ("%paged_read" in compiled.as_text()) == (
        program == "decode_step")
    assert ("%paged_chunk_read" in compiled.as_text()) == (
        program == "chunk_512")
    # The grouped kernel three times an expert layer, never a copy of a
    # weight or of the pool: room for activations and score tiles only.
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= 12
    assert memory.temp_size_in_bytes < 1.5e9
    assert weights + pool_bytes + memory.temp_size_in_bytes < 15.5e9


SDAR = {
    "vocab_size": 151936, "hidden_size": 2048, "num_hidden_layers": 6,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "moe_intermediate_size": 768, "num_experts": 128,
    "num_experts_per_tok": 8, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rope_scaling": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "model_type": "sdar_moe",
}
SDAR_SLOTS, SDAR_MAX_LEN, SDAR_BLOCK = 96, 1024, 16


@pytest.mark.parametrize("program", ["decode_step", "chunk_256",
                                     "prefill_rows_256"])
def test_the_block_programs_compile_for_v5e_and_fit_beside_their_pool(
        program, one_chip, monkeypatch):
    """``sdar-30b-a3b-chat`` as ``sdar30b-gen-closed128`` runs it: 8.72 GB
    of weights (all 128 experts of 6 layers, the whole vocabulary twice)
    and a pool of 12,288 B a token go in; the decode step is the POOL's
    program (a block a row in and out, a PAIR of blocks a row forwarded
    inside it since PR 46, the unmasking rule traced behind the head: 96
    x 4 rows of 151,936 float32 logits), and what each program needs
    beside weights and pool leaves the chip's 16.9 GB room."""
    from rayfed_tpu import utils
    from rayfed_tpu.models import decode, sdar_moe
    from rayfed_tpu.serving import kv_pool

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    cfg = sdar_moe.SdarMoeConfig.from_published(SDAR)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    layer = {"ln1": (d,), "ln2": (d,), "q_norm": (cfg.head_dim,),
             "k_norm": (cfg.head_dim,), "wq": (d, q), "wk": (d, kv),
             "wv": (d, kv), "wo": (q, d), "router": (d, e),
             "we_gate": (e, d, f), "we_up": (e, d, f), "we_down": (e, f, d)}
    params = {
        "embed": sds((cfg.vocab, d)), "ln_f": sds((d,)),
        "lm_head": sds((cfg.vocab, d)),
        "layers": [{k: sds(v) for k, v in layer.items()}
                   for _ in range(cfg.n_layers)],
    }
    weights = sum(2 * int(jnp.prod(jnp.asarray(a.shape)))
                  for a in jax.tree_util.tree_leaves(params))
    assert round(weights / 1e9, 2) == 8.72
    model = decode.serving_model(cfg)
    blocks_per_row = -(-(SDAR_MAX_LEN + 1) // SDAR_BLOCK)
    pool = sds((cfg.n_layers, 1 + SDAR_SLOTS * blocks_per_row, SDAR_BLOCK,
                cfg.n_kv_heads, cfg.head_dim))
    pool_bytes = 2 * 2 * int(jnp.prod(jnp.asarray(pool.shape)))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    r = SDAR_SLOTS
    if program == "decode_step":
        # The pool's own wrapper, built without allocating a pool.
        shell = object.__new__(kv_pool.PagedKVPool)
        shell.max_slots, shell.model = r, model
        shell.block = model.block_spec()
        shell.ids_len = r * shell.block.length
        lowered = jax.jit(shell._block_step(), donate_argnums=(1,)).lower(
            params, (pool, pool), i32(r, 4), i32(r), i32(r, blocks_per_row),
            i32(4, r), i32(r * 4 + 5), sds((r,), jnp.bool_), {},
            sds((r,), jnp.bool_))
        # The pair of blocks a row forwards is made inside: the step takes
        # a block a row and returns a block a row, as it always did.
        assert jax.tree.map(lambda a: a.shape, lowered.out_info) == (
            (r * 4 + 5,), (pool.shape, pool.shape), {})
    elif program == "chunk_256":
        lowered = jax.jit(model.chunk, donate_argnums=(1,)).lower(
            params, (pool, pool), {}, i32(blocks_per_row), i32(), i32(256),
            i32(), i32())
    else:
        lowered = jax.jit(model.prefill_rows, static_argnums=(3, 4)).lower(
            params, i32(r, 256), i32(r), SDAR_MAX_LEN + 1, None,
            sds((r,), jnp.bool_))
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    if program != "prefill_rows_256":
        assert memory.alias_size_in_bytes >= pool_bytes
    # The grouped kernel three times a layer, never a copy of a weight or
    # of the pool. A prefill hands back no logits (a prompt's position
    # predicts itself), so nothing reads the last layer's experts and the
    # compiler drops them.
    expert_layers = cfg.n_layers - (program != "decode_step")
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= 3 * expert_layers
    print(program, memory.temp_size_in_bytes, memory.output_size_in_bytes)
    assert memory.temp_size_in_bytes < 2.5e9
    assert weights + pool_bytes + memory.temp_size_in_bytes < 15.5e9


@pytest.mark.parametrize("program", ["decode_step", "chunk_512"])
def test_the_selected_reads_compile_for_v5e_and_fit_beside_their_pool(
        program, one_chip, monkeypatch):
    """``dots3-note-prev`` as ``dots3-longdocs-closed16`` runs it: 8.17 GB
    of weights and a pool of THREE arrays of two depths (9,984 B a token
    as allocated: 12 slots of 33,024 positions, 4.13 GB) go in; the pool
    comes back aliased to its arguments, the sliding layers' read is the
    paged kernel (one form, lowered once), and what the indexer's scores,
    the top-k and the selected read need beside them leaves the chip's
    16.9 GB room."""
    import importlib

    from chipbench import run
    from rayfed_tpu import utils
    from rayfed_tpu.models import decode
    from rayfed_tpu.serving import kv_pool

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    plan = run.resolve("dots3-longdocs-closed16", False)
    adapter = importlib.import_module("chipbench.seeded_" + plan["reference"])
    cfg = adapter.program_cfg(plan["model"], plan["precision"])
    model = decode.serving_model(cfg)
    serving = plan["mix"]["serving"]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    shapes = adapter.to_program_tree(jax.tree_util.tree_map(
        lambda a: np.broadcast_to(np.zeros((), a.dtype), a.shape),
        jax.eval_shape(lambda: adapter.make_canonical(
            jax.random.PRNGKey(0), plan["model"]))), plan["model"])
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), shapes)
    nbytes = lambda t: sum(  # noqa: E731
        a.dtype.itemsize * int(np.prod(a.shape))
        for a in jax.tree_util.tree_leaves(t))
    weights = nbytes(params)
    assert round(weights / 1e9, 2) == 8.17
    slots, block = serving["max_slots"], serving["kv_block_size"]
    blocks_per_row = -(-(serving["max_len"] + 1) // block)
    kv = tuple(sds((layers, 1 + slots * blocks_per_row, block,
                    *kv_pool._allocated(row)))
               for layers, row in model.kv_spec())
    assert [a.shape[0] for a in kv] == [2, 2, 3]
    assert [a.shape[-1] for a in kv] == [640, 128, 1152]
    pool_bytes = nbytes(kv)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if program == "decode_step":
        lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            params, kv, {}, i32(slots), i32(slots),
            i32(slots, blocks_per_row), sds((slots,), jnp.bool_))
        # One windowed latent form, called from three layers.
        assert lowered.as_text().count("func.func private @paged_read") == 1
    else:
        lowered = jax.jit(model.chunk, donate_argnums=(1,)).lower(
            params, kv, {}, i32(blocks_per_row), i32(), i32(512), i32(),
            i32())
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    print(program, weights, pool_bytes, memory.temp_size_in_bytes)
    assert memory.alias_size_in_bytes >= pool_bytes
    assert ("%paged_read" in compiled.as_text()) == (program == "decode_step")
    assert ("%paged_chunk_read" in compiled.as_text()) == (
        program == "chunk_512")
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= 12
    assert weights + pool_bytes + memory.temp_size_in_bytes < 15.5e9


@pytest.mark.parametrize("program", ["decode_step", "chunk_256", "chunk_128",
                                     "prefill_256"])
def test_the_hybrid_programs_compile_for_v5e_and_fit_beside_their_pool(
        program, one_chip, monkeypatch):
    """``olmohybrid-assist-closed48``'s programs (depth 16 of the
    published widths: 12 linear-attention layers whose state is a
    float32 ``S`` and a convolution tail a slot, 4 full-attention layers
    whose K/V is paged, a row's 30 heads padded to 32; 32 slots of 1,793
    positions), compiled for v5e: the decode step, a whole and a ragged
    prompt chunk, and the bucketed prefill of a round. The pool's arrays
    and the state come back aliased to their arguments, the decode step's
    read is the paged kernel (one form, lowered once, called from the
    four full layers) and its delta rule the kernel over the stacked
    ``S`` (``ops/delta_rule.py``: lowered once, called from a period's
    three unrolled linear layers, the state aliased through every call:
    no copy of its 0.88 GB among the temporaries), a chunk's read keeps
    the loop (a slot reaches 1,808 keys, under
    ``decode.CHUNK_KERNEL_REACH``) and a prompt's delta rule the chunked
    form (no such kernel in the chunk programs or the prefill), and what
    a program needs beside its arguments and results is activations and a
    sub-chunk's products, never a copy of a layer's weights, of the tails
    or of ``S`` (a period's leaves sliced out of the stack, or the tails
    under their own shape, made the compiler copy 1.2 to 1.7 GB a call:
    PERF.md section 6, PR 48)."""
    from chipbench import run
    from rayfed_tpu import utils
    from rayfed_tpu.models import decode

    monkeypatch.setattr(utils, "is_tpu_backend", lambda: True)
    cell, kind = "olmohybrid-assist-closed48", program.split("_")
    plan = run.resolve(cell, False)
    serving = plan["mix"]["serving"]
    slots = serving["max_slots"]
    assert (slots, serving["max_len"]) == (32, 1792)
    # The state as counted: 27.37 MB a slot (its 96 columns are tiled as
    # 128 on the device: a third more).
    state_bytes = slots * 27371520
    if kind[0] == "prefill":
        import importlib

        adapter = importlib.import_module(
            "chipbench.seeded_" + plan["reference"])
        model = decode.serving_model(
            adapter.program_cfg(plan["model"], plan["precision"]))
        sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
            tuple(shape), dtype, sharding=one_chip)
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: adapter.make_canonical(
                jax.random.PRNGKey(0), plan["model"])))
        lowered = jax.jit(lambda p, t, i, w: model.prefill_rows(
            p, t, i, serving["max_len"] + 1, jnp.bfloat16, w)).lower(
                params, sds((slots, int(kind[1])), jnp.int32),
                sds((slots,), jnp.int32), sds((slots,), jnp.bool_))
    else:
        lowered, weights, kv_bytes = _cell_decode_step(
            cell, one_chip, chunk=int(kind[1]) if kind[0] == "chunk" else None)
        assert round(weights / 1e9, 2) == 8.2
        assert round(kv_bytes / 1e9, 2) == 3.79       # 4 layers deep
    if program == "decode_step":
        for kernel in ("paged_read", "delta_state_step"):
            assert lowered.as_text().count(
                f"func.func private @{kernel}") == 1
    compiled = lowered.compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    print(program, memory.temp_size_in_bytes, memory.output_size_in_bytes)
    assert ("%paged_read" in text) == (program == "decode_step")
    # One call a linear layer of the unrolled period.
    assert len(re.findall(r"%delta_state_step\.\d+ = ", text)) == (
        3 if program == "decode_step" else 0)
    assert "%paged_chunk_read" not in text
    assert memory.temp_size_in_bytes < 0.25e9
    if kind[0] == "prefill":
        # Its rows and their state are new arrays (the pool lands them):
        # the state rows and 0.54 GB of K/V rows a round of 32.
        assert memory.output_size_in_bytes < 1.8e9
        assert 8.2e9 + 3.79e9 + state_bytes + 1.8e9 + 0.25e9 < 15.5e9
    else:
        assert memory.alias_size_in_bytes >= kv_bytes + state_bytes
        assert weights + kv_bytes + state_bytes + 0.25e9 < 15.5e9
