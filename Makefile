# Build the native fastwire extension in place (optional: the transport
# falls back to pure-Python socket IO when the extension is absent).
.PHONY: native test lint sanitize chaos latency scale dma shm async churn obs privacy ha wan tenant clean

native:
	python setup.py build_ext --inplace

test:
	./test.sh

# Static checks: license headers, fedlint over the shipped drivers AND
# the framework itself (both must be clean — every self-lint finding is
# fixed or suppressed in place with a justification), and the fedlint
# contract tests (fixture corpus + seq-id validation). Mirrors
# .github/workflows/fedlint.yml.
lint:
	python tools/check_license_headers.py
	python -m rayfed_tpu.lint examples
	python -m rayfed_tpu.lint rayfed_tpu
	JAX_PLATFORMS=cpu python -m pytest tests/test_fedlint.py tests/test_seq_id_validation.py -q

# FedSanitizer lane (docs/sanitizer.md): the probe unit tests (each
# probe forced to trip), the chaos FedAvg spawn test under
# FEDTPU_SANITIZE=1 (zero trips, bitwise-identical results vs the
# unsanitized run), and the overhead gate — sanitized round time must
# stay within FEDTPU_SANITIZE_BUDGET_PCT (default 10%) of baseline.
# Mirrors the `sanitize` job in .github/workflows/tests.yml.
sanitize:
	JAX_PLATFORMS=cpu FEDTPU_SANITIZE=1 python -m pytest \
	  tests/test_sanitizer.py -q
	JAX_PLATFORMS=cpu python tools/sanitize_check.py

# Chaos/failure lane (docs/resilience.md): the seeded fault-schedule
# FedAvg run plus the multi-process failure-path tests. Slow by design
# (real timeouts, spawned parties) — mirrors the `chaos` job in
# .github/workflows/tests.yml.
chaos:
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/test_resilience.py tests/test_failure_paths.py -q

# Latency gate: the many-tiny-tasks micro-bench must stay under
# FEDTPU_TINY_BUDGET_MS per task (default 1.0) or this exits 1 — a change
# that re-adds a thread hop or pickle round to the small-message fast
# path fails loudly here. Mirrors the `latency` job in
# .github/workflows/tests.yml.
latency:
	JAX_PLATFORMS=cpu python tools/latency_check.py

# Scale gate: 8- and 16-party simulated hierarchical rounds (real TCP
# proxies over shared epoll reactors, in-process parties) must keep
# their MEDIAN round under budget, with a hard wall-clock cap on the
# whole check — a serialized reactor loop or re-added per-peer thread
# hop fails loudly here. Mirrors the `scale` job in
# .github/workflows/tests.yml.
scale:
	JAX_PLATFORMS=cpu python tools/scale_check.py

# Data-plane gate: the striped multi-stream lane (num_streams reactor
# lanes carrying stripe frames) must out-run the device-DMA lane's
# CPU-sim throughput (FEDTPU_DMA_RATIO, default 1.0x) — a change that
# serializes the stripe lanes or re-adds full-payload staging fails
# loudly here.
dma:
	JAX_PLATFORMS=cpu python tools/dma_check.py

# Shared-memory lane gate: same-host pushes over the /dev/shm ring must
# beat loopback TCP by FEDTPU_SHM_RATIO (default 4.0x), with an
# absolute FEDTPU_SHM_FLOOR_GBPS anti-gaming floor — a change that
# re-adds a staging copy, breaks ring adoption (silent per-push socket
# fallback), or serializes pushes behind the ring lock fails loudly
# here. Mirrors the `shm` job in .github/workflows/tests.yml.
shm: native
	JAX_PLATFORMS=cpu python tools/shm_check.py

# Async gate (docs/async_rounds.md): 3 spawned parties with carol's
# every send delayed by a seeded fault schedule; buffered-async rounds
# (fed.async_round, K-publish without the straggler) must sustain
# FEDTPU_ASYNC_BUDGET_RATIO x (default 3.0) the lock-step baseline's
# rounds/s AND an absolute FEDTPU_ASYNC_BUDGET_FLOOR — a change that
# re-serializes the fold path or makes publish wait for the straggler
# fails loudly here. Mirrors the `async` job in
# .github/workflows/tests.yml.
async:
	JAX_PLATFORMS=cpu python tools/async_check.py

# Churn gate (docs/membership.md): elastic membership under fire — one
# party crash-killed mid-round and liveness-evicted, a replacement
# joining mid-training via fed.join. churn_rounds_lost must stay 0,
# the replacement must take over, and churn_join_ms must stay under
# budget, plus the spawn-based membership lifecycle tests. Mirrors the
# `churn` job in .github/workflows/tests.yml.
churn:
	JAX_PLATFORMS=cpu python tools/churn_check.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_membership.py -q

# Observability gate (docs/observability.md): a 3-party round with the
# telemetry plane on, paired against telemetry-off windows —
# metrics_overhead_pct must stay under FEDTPU_OBS_BUDGET_PCT (default
# 3%), every core series must appear in the collector's /metrics
# scrape, all parties must report in /fleet, and at least one seq-id
# edge in /trace must stitch spans from two parties. Mirrors the `obs`
# job in .github/workflows/tests.yml.
obs:
	JAX_PLATFORMS=cpu python tools/obs_check.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q

# Privacy gate (docs/privacy.md): 3 spawned parties with the privacy
# plane on — paired plaintext/secure FedAvg windows, every secure round
# bitwise-checked against the plaintext fold (mask cancellation is
# EXACT or broken, never "close"), secure_agg_overhead_pct under
# FEDTPU_SECAGG_BUDGET_PCT, the int8 quantized push over its floor,
# plus the privacy unit/chaos tests. Mirrors the `privacy` job in
# .github/workflows/tests.yml.
privacy:
	JAX_PLATFORMS=cpu python tools/privacy_check.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_privacy.py -q

# HA gate (docs/ha.md): control-plane failover under fire — the
# configured coordinator crash-killed mid-sync-broadcast, the
# deterministic successor taking over the sync point under term 1.
# ha_rounds_lost must stay 0, the successor must actually hold the
# role, and coordinator_failover_ms must stay under
# FEDTPU_HA_BUDGET_MS, plus the failover/handoff/checkpoint chaos
# tests. Mirrors the `ha` job in .github/workflows/tests.yml.
ha:
	JAX_PLATFORMS=cpu python tools/ha_check.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_ha.py -q

# WAN gate (docs/resilience.md): 3 spawned parties over an in-proxy
# emulated 50ms/100Mbit link (LinkProfile shaper) with frame crc and
# adaptive deadlines on — wan_round_ms must stay latency-bound under
# FEDTPU_WAN_ROUND_BUDGET_MS (and ABOVE the shaper-is-alive floor),
# link_rtt_ms must show the LinkHealth estimator converging on the
# emulated RTT, plus the WAN unit + chaos tests (link shaping, crc
# NACK/retransmit, lane re-promotion, bounded duplicates). Mirrors the
# `wan` job in .github/workflows/tests.yml.
wan:
	JAX_PLATFORMS=cpu python tools/wan_check.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_wan.py -q

# Tenancy gate (docs/multitenancy.md): the full tenancy unit suite +
# the multitenant_isolation chaos test, then tools/tenant_check.py —
# byte-identical isolation between co-resident jobs (non-negotiable)
# and the weighted-fair QoS keys from bench.py's tenant stage:
# tenant_fairness_ratio >= FEDTPU_TENANT_FAIRNESS (default 0.25 at the
# 1:4 weight split) and multitenant_victim_p99_ms under
# FEDTPU_TENANT_P99_MS. Mirrors the `tenant` job in
# .github/workflows/tests.yml.
tenant:
	JAX_PLATFORMS=cpu python -m pytest tests/test_tenancy.py \
	  tests/test_multitenant_chaos.py -q
	JAX_PLATFORMS=cpu python tools/tenant_check.py

clean:
	rm -rf build rayfed_tpu/_fastwire*.so
