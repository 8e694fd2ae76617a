"""One-command CI gate (VERDICT r4 missing #3): lint + manifest validation +
check tools + test suite + multi-chip dryrun, composed the way the reference
layers its CI (.github/workflows/ci-kustomize-dry-run.yaml PR dry-runs,
nightly hardware e2e). Every stage already existed as its own tool; this gates
them behind a single exit code for `make check` and the workflow YAMLs.

Usage: python tools/ci_gate.py [--quick] [--skip-tests] [--skip-dryrun]
  --quick: -x on pytest and a 2-device dryrun (PR-sized; nightly runs full)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# CPU-only, simulated accelerators — the gate must pass with zero TPU chips
# (the reference's `simulated-accelerators` CI filter / tpu_chips: 0 mode)
CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                  + " --xla_force_host_platform_device_count=8").strip(),
}


def run_stage(name: str, cmd: list[str], env=None) -> dict:
    t0 = time.monotonic()
    print(f"=== {name}: {' '.join(cmd)}", flush=True)
    p = subprocess.run(cmd, cwd=ROOT, env=env or os.environ)
    dt = time.monotonic() - t0
    ok = p.returncode == 0
    print(f"=== {name}: {'OK' if ok else f'FAILED rc={p.returncode}'} "
          f"({dt:.1f}s)", flush=True)
    return {"stage": name, "ok": ok, "rc": p.returncode, "seconds": round(dt, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="PR-sized: pytest -x, 2-device dryrun")
    ap.add_argument("--skip-tests", action="store_true")
    ap.add_argument("--skip-dryrun", action="store_true")
    args = ap.parse_args()

    py = sys.executable
    stages = [
        ("lint-envvars", [py, "tools/lint_envvars.py"], None),
        ("lint-metrics", [py, "tools/lint_metrics.py"], CPU_ENV),
        ("lint-events", [py, "tools/lint_events.py"], CPU_ENV),
        # unified static analysis: lock discipline, deadlock order, hot-path
        # purity, env/metrics/events contracts (docs/static-analysis.md)
        ("llmd-lint", [py, "-m", "tools.llmd_lint"], CPU_ENV),
        ("validate-manifests", [py, "tools/validate_manifests.py", "deploy"], None),
        ("chaos-check", [py, "tools/chaos_check.py"], CPU_ENV),
        # structured outputs: constrained generations must conform 100% and
        # malformed schemas must 400 before admission
        ("structured-check", [py, "tools/structured_check.py"], CPU_ENV),
        # closed autoscaling loop: 10x swing + replica kill/flap mid-burst,
        # SLO attainment >= 95%, zero 5xx, back to floor, warm 0->1 < cold
        ("slo-check", [py, "tools/slo_check.py"], CPU_ENV),
        # device plane: watchdog trips on synthetic stall, fabric probe
        # timeout path, HBM gauges scrape, profiler capture on CPU
        ("device-obs", [py, "tools/device_obs_check.py"], CPU_ENV),
        # global KV plane: precise routing >= 90% prefix-served, cross-engine
        # pull exercised, engine killed mid-run with zero 5xx, index bounded
        ("kv-plane-check", [py, "tools/kv_plane_check.py"], CPU_ENV),
        # decision plane: 100% of retired requests carry a routing/calibration
        # ledger, regret + calibration families exported, zero 5xx, and the
        # ledger stays inside the router-overhead bound
        ("decision-check", [py, "tools/decision_check.py"], CPU_ENV),
        # durable prefix tier: five-rung token identity, scale-to-zero ->
        # scale-up restores the working set from the store (>= 90% of repeat
        # prefixes skip recompute), store killed mid-run with zero 5xx
        ("kv-durability-check", [py, "tools/kv_durability_check.py"], CPU_ENV),
        # P/D disaggregation: predictor-gated splitting over role-labeled
        # pools, independent P (queue/hpa) and D (KV/wva) scaling, kv_pull
        # phase ledgers, and a mid-burst prefill-pool kill absorbed with
        # zero 5xx (aggregated fallback)
        ("pd-check", [py, "tools/pd_check.py"], CPU_ENV),
        # utilization plane: goodput fractions sum to 1 per program, MFU/MBU
        # families on the null-peak path, recompile counter flat in steady
        # state, ledger == /metrics token for token
        ("util-check", [py, "tools/util_check.py"], CPU_ENV),
        # MoE dispatch: tiny-moe engine A/B on CPU — sorted path selected,
        # greedy parity vs the einsum reference, zero drops on sorted and
        # provable drops on capacity-starved einsum
        ("moe-check", [py, "tools/moe_check.py"], CPU_ENV),
    ]
    if not args.skip_tests:
        pytest_cmd = [py, "-m", "pytest", "tests/", "-q"]
        if args.quick:
            pytest_cmd.append("-x")
        stages.append(("pytest", pytest_cmd, None))
    if not args.skip_dryrun:
        n = 2 if args.quick else 8
        stages.append((f"dryrun-multichip-{n}",
                       [py, "-c",
                        f"from __graft_entry__ import dryrun_multichip; "
                        f"dryrun_multichip({n})"],
                       {**CPU_ENV, "XLA_FLAGS":
                        f"--xla_force_host_platform_device_count={n}"}))

    results = [run_stage(name, cmd, env) for name, cmd, env in stages]
    ok = all(r["ok"] for r in results)
    print(json.dumps({"gate": "ok" if ok else "failed", "stages": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
