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

"""The config-key schema FED009 checks literal dicts against.

Most ``*Config.from_dict`` methods silently DROP unknown keys
(``config.py``'s reference-parity contract), so a typo'd knob never
takes effect and never errors — the worst failure mode a linter can
close. (``ServingConfig.from_dict`` is the exception: it raises on
unknown keys at ``fed.init``; FED009 still catches the same typo before
the job ever launches.) The tables
here are a static mirror of the dataclasses in ``rayfed_tpu/config.py``
(+ membership/privacy/serving): fedlint must import nothing heavier than
the stdlib, so the mirror is hand-maintained and pinned by
``tests/test_fedlint.py::test_schema_matches_config_dataclasses``, which
diffs every ``*_FIELDS`` set against ``dataclasses.fields()`` of the
real class. Editing a config dataclass without updating this file is a
test failure, not a silent lint gap.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Tuple

#: dataclass-mirrored field sets (pinned by the runtime test).
CROSS_SILO_BASE_FIELDS = frozenset({
    "adaptive_timeouts", "allow_pickle_payloads", "compression_level",
    "continue_waiting_for_data_sending_on_error", "device_dma",
    "dma_listen_addr", "exit_on_sending_failure", "expose_error_trace",
    "frame_crc", "lane_tiers", "messages_max_size_in_bytes",
    "min_timeout_in_ms", "payload_compression", "payload_wire_dtype",
    "recv_timeout_in_ms", "rtt_timeout_multiple", "same_mesh_push",
    "send_deadline_in_ms", "serializing_allowed_list", "shm_enabled",
    "shm_min_bytes", "shm_push_timeout_ms", "shm_repromote_after_ms",
    "shm_ring_mb", "small_message_threshold", "timeout_in_ms",
})

TCP_CROSS_SILO_FIELDS = CROSS_SILO_BASE_FIELDS | frozenset({
    "connect_timeout_in_ms", "num_reactors", "num_streams",
    "per_party_config", "proxy_max_restarts", "retry_policy",
    "send_window", "use_reactor", "verify_peer_identity",
})

RETRY_POLICY_FIELDS = frozenset({
    "backoff_multiplier", "initial_backoff_ms", "jitter", "max_attempts",
    "max_backoff_ms",
})

PARTY_MESH_FIELDS = frozenset({
    "axis_names", "device_ids", "mesh_shape", "platform",
})

SERVING_FIELDS = frozenset({
    "eos_id", "kv_block_size", "kv_blocks", "kv_layout", "max_len",
    "max_new_tokens", "max_pending", "max_slots", "prefill_chunk",
    "prefill_token_budget", "prefix_reuse", "prompt_buckets",
    "stream_window", "temperature",
})

MEMBERSHIP_FIELDS = frozenset({
    "auth_token", "bootstrap_dir", "coordinator", "evict_dead",
    "failover", "join_timeout_s", "sync_timeout_s",
})

PRIVACY_FIELDS = frozenset({
    "clip_norm", "delta", "error_feedback", "fixedpoint_bits",
    "handshake_timeout_s", "mask_seed", "noise_multiplier", "noise_seed",
    "quantize", "secure_aggregation",
})

TELEMETRY_FIELDS = frozenset({
    "collector", "enable_tracing", "http_host", "http_port",
    "push_interval_ms", "span_batch", "stale_after_ms",
})

CHECKPOINT_FIELDS = frozenset({"base_dir", "keep"})

TENANCY_FIELDS = frozenset({
    "executor_quota", "fair_window_mb", "kv_block_quota", "max_wait_ms",
    "shm_ring_quota_mb", "weight",
})

LIVENESS_FIELDS = frozenset({
    "dead_after", "interval_ms", "suspect_after", "timeout_ms",
})

FAILOVER_FIELDS = frozenset({
    "enabled", "resync_window", "takeover_timeout_s",
})

#: AsyncAggregationConfig fields; the ``aggregation`` section spells them
#: with an ``async_`` prefix (``from_aggregation_dict``, config.py).
ASYNC_AGGREGATION_FIELDS = frozenset({
    "buffer_k", "max_staleness", "server_lr", "staleness",
    "staleness_exp", "suspect_factor",
})

AGGREGATION_SECTION_KEYS = frozenset({"topology", "group_size"}) | frozenset(
    f"async_{name}" for name in ASYNC_AGGREGATION_FIELDS
)

#: sections read directly by ``fed.init`` (api.py) rather than a config
#: dataclass — key sets mirror the ``dict.get`` calls there.
COLLECTIVE_SECTION_KEYS = frozenset({
    "coordinator", "inner_axes", "inner_shape", "init_timeout_s",
})
JAX_DISTRIBUTED_SECTION_KEYS = frozenset({
    "coordinator_address", "num_processes", "process_id",
})
KV_STORE_SECTION_KEYS = frozenset({"backend", "path"})
RESILIENCE_SECTION_KEYS = frozenset({"fault_schedule", "liveness"})

#: keys accepted at the top level of ``fed.init(config=...)``.
TOP_LEVEL_KEYS = frozenset({
    "aggregation", "barrier_on_initializing", "checkpoint", "collective",
    "cross_silo_comm", "jax_distributed", "kv_store", "membership",
    "party_mesh", "privacy", "resilience", "serving", "telemetry",
    "tenancy", "transport",
})

#: section name -> allowed keys in a literal dict value.
#: ``use_global_proxy`` is read straight off the cross_silo_comm dict by
#: api.py before from_dict sees it, so it is schema-legal there without
#: being a dataclass field.
SECTION_KEYS: Dict[str, FrozenSet[str]] = {
    "aggregation": AGGREGATION_SECTION_KEYS,
    "checkpoint": CHECKPOINT_FIELDS,
    "collective": COLLECTIVE_SECTION_KEYS,
    "cross_silo_comm": TCP_CROSS_SILO_FIELDS | {"use_global_proxy"},
    "jax_distributed": JAX_DISTRIBUTED_SECTION_KEYS,
    "kv_store": KV_STORE_SECTION_KEYS,
    "membership": MEMBERSHIP_FIELDS,
    "party_mesh": PARTY_MESH_FIELDS,
    "privacy": PRIVACY_FIELDS,
    "resilience": RESILIENCE_SECTION_KEYS,
    "serving": SERVING_FIELDS,
    "telemetry": TELEMETRY_FIELDS,
    "tenancy": TENANCY_FIELDS,
}

#: (section, key) -> schema for a nested literal dict value.
NESTED_SECTION_KEYS: Dict[Tuple[str, str], FrozenSet[str]] = {
    ("cross_silo_comm", "retry_policy"): RETRY_POLICY_FIELDS,
    ("membership", "failover"): FAILOVER_FIELDS,
    ("resilience", "liveness"): LIVENESS_FIELDS,
}

#: (section, key) whose values are free-form (per-party overlays, fault
#: schedules) — never descended into.
OPAQUE_SECTION_VALUES = frozenset({
    ("cross_silo_comm", "per_party_config"),
    ("resilience", "fault_schedule"),
})

#: config class name -> (module tail under rayfed_tpu, from_dict field
#: set). Drives the ``<Class>.from_dict({...})`` check.
CONFIG_CLASS_FIELDS: Dict[str, FrozenSet[str]] = {
    "CrossSiloMessageConfig": CROSS_SILO_BASE_FIELDS,
    "TcpCrossSiloMessageConfig": TCP_CROSS_SILO_FIELDS,
    "RetryPolicy": RETRY_POLICY_FIELDS,
    "PartyMeshConfig": PARTY_MESH_FIELDS,
    "ServingConfig": SERVING_FIELDS,
    "MembershipConfig": MEMBERSHIP_FIELDS,
    "PrivacyConfig": PRIVACY_FIELDS,
    "TelemetryConfig": TELEMETRY_FIELDS,
    "CheckpointConfig": CHECKPOINT_FIELDS,
    "LivenessConfig": LIVENESS_FIELDS,
    "FailoverConfig": FAILOVER_FIELDS,
    "TenancyConfig": TENANCY_FIELDS,
}


def section_schema(section: str) -> Optional[FrozenSet[str]]:
    return SECTION_KEYS.get(section)


def nested_schema(section: str, key: str) -> Optional[FrozenSet[str]]:
    return NESTED_SECTION_KEYS.get((section, key))
