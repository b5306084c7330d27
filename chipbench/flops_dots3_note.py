"""Parameters, operations and bytes of dots3-note (``model_type:
dots3_note``), computed from the published keys and the chip's share
(``chipbench/seeded_dots3_note.py``: ``n_routed_experts`` held of
``router_experts`` scored, ``layer_types`` of the layers held, a slice of
the vocabulary). The yardstick's arithmetic for the ``*.dsa`` readers: no
PR that claims a gain may change it. Everything here is the LEAST a
program must do for the work the engine counted, whatever implements it
(a program that attends every key and masks does more, and reads a lower
share): no share can read over 100 %.

What the engine counts (``stats()``, host, from positions): the (query,
key) pairs the full layers' indexers scored and kept, decode steps and
prefill together (``index_keys_scored``, ``index_keys_selected``) and the
decode steps' part of each (``*_decode``); the pairs attended over all
layers (``decode_keys_attended``, ``prefill_keys_attended``: a full
layer's are those its indexer kept, a sliding layer's at most its
window); on the device, the held experts hit and the (row, expert) pairs
on them.

**A decode step's least bytes**: every weight held whole once (both
kinds of attention with their gates, the indexers, the dense SwiGLU, the
shared expert, the router and its bias, the norms; the final norm and the
head), the three matrices of each held expert hit, ONE index key
(``index_head_dim`` values) for every pair scored, ONE latent row for
every pair kept on a full layer (``kv_lora_rank + qk_rope_head_dim``
values: 1,152 B) and for every key a sliding layer's window holds (the
``swa_`` widths: 2,176 B). **Its operations**: two per parameter of what
every row passes, two per parameter of an expert for each (row, expert)
pair held, ``2 J D`` for each pair scored, and for each pair attended the
cheaper of the two forms of the latent read: absorbed, ``2 H (2 rkv +
dr)``, or expanded, ``2 H (dn + dr + dv)`` plus the row's expansion ``2
rkv H (dn + dv)``, which one query a row never repays. Live rows a step
are not counted by the engine: taken as the pairs kept over ``full
layers x index_topk``, which is exact where every row stands past
``index_topk`` (every request of ``dots3-longdocs-closed16``) and never
more than the rows there were.

**The prefill programs' least seconds** (``chunk_step`` and
``prefill_rows`` together): two per parameter of what every token passes,
the head left out (one position a prompt); two per parameter of an expert
for each (token, expert) pair held, at the share the window's decode
steps measured; ``2 J D`` for each pair scored; for each pair attended
the cheaper form's ``2 H (dn + dr + dv)`` (the expansion of a row is
shared by a chunk's queries and is left out: it can only lower the
share). Bytes: the weights held whole once a call and the experts a call
touches, at their expectation under even routing; the cached rows and
index keys a chunk reads are left out.
"""

from __future__ import annotations

from chipbench import flops

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
STEP_PROGRAM = "jit_decode_step"
PREFILL_PROGRAMS = ("jit_chunk_step", "jit_prefill_rows")
FULL, SLIDING = "full_attention", "sliding_attention"


def sizes_of(model: dict, kind: str) -> tuple:
    """(H, rq, rkv, dn, dr, dv) of a layer of ``kind``."""
    pre = "" if kind == FULL else "swa_"
    return tuple(int(model[pre + key]) for key in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))


def layer_kinds(model: dict) -> tuple:
    """(full layers, sliding layers) held."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    n_full = sum(1 for kind in kinds if kind == FULL)
    return n_full, len(kinds) - n_full


def attention_params(model: dict, kind: str) -> tuple:
    """(matrices, norm scales and biases) of one layer's attention: the
    two low ranks, the expansions, the output projection and the gate; on
    a full layer the indexer's three matrices and its LayerNorm."""
    d = model["hidden_size"]
    h, rq, rkv, dn, dr, dv = sizes_of(model, kind)
    matrices = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
                + rkv * h * (dn + dv) + h * dv * d + d * h)
    small = rq + rkv
    if kind == FULL:
        j, di = model["index_n_heads"], model["index_head_dim"]
        matrices += rq * j * di + d * di + d * j
        small += 2 * di
    return matrices, small


def param_counts(model: dict) -> dict:
    """Parameters of the layers' parts and of the model as held here."""
    d = model["hidden_size"]
    n_full, n_sliding = layer_kinds(model)
    full, full_small = attention_params(model, FULL)
    sliding, sliding_small = attention_params(model, SLIDING)
    dense = 3 * d * model["intermediate_size"]
    expert = 3 * d * model["moe_intermediate_size"]
    shared = model["n_shared_experts"] * expert
    scored = model.get("router_experts", model["n_routed_experts"])
    router = d * scored
    n_layers = n_full + n_sliding
    n_dense = min(model["first_k_dense_replace"], n_layers)
    n_expert = n_layers - n_dense
    head = model["vocab_size"] * d
    # What every token passes, all layers: matrices only, then with the
    # norms' scales and the selection biases.
    whole_matmul = (n_full * full + n_sliding * sliding + n_dense * dense
                    + n_expert * (shared + router))
    whole = (whole_matmul + n_full * full_small + n_sliding * sliding_small
             + n_layers * 2 * d + n_expert * scored)
    return {
        "full_attention": full, "sliding_attention": sliding,
        "dense": dense, "expert": expert, "shared": shared,
        "router": router, "head": head, "embed": head,
        "expert_layers": n_expert, "whole_matmul": whole_matmul,
        "whole": whole,
        "total": whole + n_expert * model["n_routed_experts"] * expert
        + 2 * head + d,
    }


def _param_bytes(precision: dict) -> int:
    return DTYPE_BYTES[precision.get("parameters", "bfloat16")]


def _cache_bytes(precision: dict) -> int:
    return DTYPE_BYTES[precision.get("kv_cache", "bfloat16")]


def whole_bytes_per_call(model: dict, precision: dict) -> int:
    """What every execution of a serving program reads whatever it
    routes: the layers' whole parts, the final norm, the head."""
    c = param_counts(model)
    return (c["whole"] + c["head"] + model["hidden_size"]) \
        * _param_bytes(precision)


def expert_bytes(model: dict, precision: dict) -> int:
    return param_counts(model)["expert"] * _param_bytes(precision)


def row_bytes(model: dict, precision: dict, kind: str) -> int:
    """What a token keeps in ONE layer of ``kind``: the latent and the
    rotated key."""
    _, _, rkv, _, dr, _ = sizes_of(model, kind)
    return (rkv + dr) * _cache_bytes(precision)


def index_key_bytes(model: dict, precision: dict) -> int:
    return model["index_head_dim"] * _cache_bytes(precision)


def pair_ops(model: dict, kind: str, queries_a_row: float) -> float:
    """Operations of ONE attended (query, key) pair on a layer of
    ``kind``, in the cheaper of the two forms of the latent read when
    ``queries_a_row`` queries share each row's expansion."""
    h, _, rkv, dn, dr, dv = sizes_of(model, kind)
    absorbed = 2.0 * h * (2 * rkv + dr)
    expanded = 2.0 * h * (dn + dr + dv)
    if queries_a_row != float("inf"):
        expanded += 2.0 * rkv * h * (dn + dv) / queries_a_row
    return min(absorbed, expanded)


def index_pair_ops(model: dict) -> float:
    return 2.0 * model["index_n_heads"] * model["index_head_dim"]


def decode_pairs(counted: dict) -> dict:
    """The decode steps' (query, key) pairs by what they cost: scored by
    an indexer, kept by it (attended on a full layer), attended on a
    sliding layer."""
    kept = counted["index_keys_selected_decode"]
    return {"scored": counted["index_keys_scored_decode"], "kept": kept,
            "window": counted["decode_keys_attended"] - kept}


def prefill_pairs(counted: dict) -> dict:
    kept = (counted["index_keys_selected"]
            - counted["index_keys_selected_decode"])
    return {"scored": counted["index_keys_scored"]
            - counted["index_keys_scored_decode"], "kept": kept,
            "window": counted["prefill_keys_attended"] - kept}


def window_least_bytes(facts: dict) -> dict:
    """Least bytes of the decode steps that ``facts["stats"]`` counts
    (deltas of the engine's ``stats()``), by part."""
    model, precision, stats = (facts["model"], facts["precision"],
                               facts["stats"])
    pairs = decode_pairs(stats)
    weights = stats["steps"] * whole_bytes_per_call(model, precision)
    experts = stats["moe_experts_hit"] * expert_bytes(model, precision)
    index = pairs["scored"] * index_key_bytes(model, precision)
    selected = pairs["kept"] * row_bytes(model, precision, FULL)
    window = pairs["window"] * row_bytes(model, precision,
                                         SLIDING)
    return {"weights": weights, "experts": experts, "index_keys": index,
            "selected_rows": selected, "window_rows": window,
            "total": weights + experts + index + selected + window}


def decode_rows(counted: dict, model: dict) -> float:
    """Live rows summed over the steps ``counted`` holds: the pairs kept
    over ``full layers x index_topk`` (never more than there were)."""
    n_full, _ = layer_kinds(model)
    return counted["index_keys_selected_decode"] / (
        n_full * model["index_topk"])


def decode_least_seconds(facts: dict) -> dict:
    """Least seconds of the decode steps in the traced part of the
    window (``facts["traced_stats"]``): operations and bytes by part, and
    the larger of the two times."""
    counted = facts["traced_stats"]
    model = facts["model"]
    c = param_counts(model)
    pairs = decode_pairs(counted)
    ops = {
        "whole": 2.0 * decode_rows(counted, model)
        * (c["whole_matmul"] + c["head"]),
        "experts": 2.0 * counted["moe_assignments_local"] * c["expert"],
        "index": index_pair_ops(model) * pairs["scored"],
        "selected": pair_ops(model, FULL, 1.0) * pairs["kept"],
        "window": pair_ops(model, SLIDING, 1.0)
        * pairs["window"],
    }
    nbytes = window_least_bytes(dict(facts, stats=counted))
    seconds, bound = flops.least_time(
        sum(ops.values()), nbytes["total"], flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}


def local_share(facts: dict) -> float:
    """(token, expert) pairs on held experts per token and expert layer,
    as a share of the ``k`` pairs a token has: what the window's decode
    steps measured, or the share of the experts held."""
    model, stats = facts["model"], facts["stats"]
    rows = decode_rows(stats, model) if stats.get(
        "index_keys_selected_decode") else 0
    layers = param_counts(model)["expert_layers"]
    if rows > 0 and layers and stats.get("moe_assignments_local"):
        return min(1.0, stats["moe_assignments_local"] / (
            rows * model["num_experts_per_tok"] * layers))
    return model["n_routed_experts"] / model.get(
        "router_experts", model["n_routed_experts"])


def chunk_least_seconds(facts: dict) -> dict:
    """Least seconds of the prefill programs' executions in the traced
    part of the window: ``facts["programs"]`` has how often each ran
    there, ``facts["traced_stats"]`` what the engine counted between the
    profile's start and its stop. Operations and bytes by part, and the
    larger of the two times."""
    counted = facts["traced_stats"]
    calls = sum(facts["programs"].get(name, {}).get("calls", 0)
                for name in PREFILL_PROGRAMS)
    model, precision = facts["model"], facts["precision"]
    c = param_counts(model)
    k = model["num_experts_per_tok"]
    held = model["n_routed_experts"]
    scored = model.get("router_experts", held)
    tokens = counted["prefill_tokens"]
    pairs = prefill_pairs(counted)
    ops = {
        "whole": 2.0 * tokens * c["whole_matmul"],
        "experts": 2.0 * tokens * k * local_share(facts)
        * c["expert_layers"] * c["expert"],
        "index": index_pair_ops(model) * pairs["scored"],
        "selected": pair_ops(model, FULL, float("inf")) * pairs["kept"],
        "window": pair_ops(model, SLIDING, float("inf"))
        * pairs["window"],
    }
    touched = held * (1.0 - (1.0 - k / scored) ** (tokens / max(calls, 1)))
    nbytes = {
        "whole": calls * whole_bytes_per_call(model, precision),
        "experts": calls * c["expert_layers"] * touched
        * expert_bytes(model, precision),
    }
    seconds, bound = flops.least_time(
        sum(ops.values()), sum(nbytes.values()),
        flops.peaks(facts["device_kind"]))
    return {"ops": ops, "bytes": nbytes, "seconds": seconds, "bound": bound}
