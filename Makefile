# Developer/CI entry points (role of the reference's root Makefile, whose
# DEVICE matrix builds container images; ours gates the source tree).

PY ?= python

.PHONY: check check-quick test dryrun lint manifests chaos structured slo device-obs kvplane decisions durable util moe pd

# full gate: lint + manifests + check tools + suite + 8-device dryrun
check:
	$(PY) tools/ci_gate.py

# PR-sized gate (fail-fast tests, 2-device dryrun)
check-quick:
	$(PY) tools/ci_gate.py --quick

test:
	$(PY) -m pytest tests/ -q

# full static-analysis suite: lock discipline, deadlock order, hot-path
# purity, env/metrics/events contracts (docs/static-analysis.md)
lint:
	$(PY) tools/lint_envvars.py
	$(PY) tools/lint_events.py
	JAX_PLATFORMS=cpu $(PY) tools/lint_metrics.py
	JAX_PLATFORMS=cpu $(PY) -m tools.llmd_lint

manifests:
	$(PY) tools/validate_manifests.py deploy

# router resilience vs fault-injected endpoints (goodput >= 99%, no 5xx)
chaos:
	JAX_PLATFORMS=cpu $(PY) tools/chaos_check.py

# grammar-constrained decoding: 100% conformance, malformed schemas -> 400
structured:
	JAX_PLATFORMS=cpu $(PY) tools/structured_check.py

# autoscaling SLO gate: 10x burst + replica chaos, zero 5xx, warm 0->1
slo:
	JAX_PLATFORMS=cpu $(PY) tools/slo_check.py

# P/D disaggregation: role-labeled pools, predictor-gated splits, kv_pull
# ledgers, mid-burst prefill-pool kill degrades to aggregated, zero 5xx
pd:
	JAX_PLATFORMS=cpu $(PY) tools/pd_check.py

# device plane: watchdog, fabric probe, HBM gauges, profiler capture
device-obs:
	JAX_PLATFORMS=cpu $(PY) tools/device_obs_check.py

# global KV plane: precise routing + cross-engine pulls under churn, zero 5xx
kvplane:
	JAX_PLATFORMS=cpu $(PY) tools/kv_plane_check.py

# decision plane: per-request routing ledgers, predictor calibration,
# regret — 100% coverage over a replayed trace, zero 5xx
decisions:
	JAX_PLATFORMS=cpu $(PY) tools/decision_check.py

# durable prefix tier: write-back + store rung survive scale-to-zero and a
# mid-run store kill — five-rung token identity, zero 5xx
durable:
	JAX_PLATFORMS=cpu $(PY) tools/kv_durability_check.py

# utilization plane: per-program goodput sums to 1, MFU/MBU families on the
# null-peak path, recompile counter flat in steady state, ledger == /metrics
util:
	JAX_PLATFORMS=cpu $(PY) tools/util_check.py

# MoE dispatch plane: tiny-moe engine A/B — sorted path selected under auto,
# greedy parity vs the einsum reference, zero drops on sorted, provable drops
# on capacity-starved einsum, counter == engine ledger
moe:
	JAX_PLATFORMS=cpu $(PY) tools/moe_check.py

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) __graft_entry__.py
