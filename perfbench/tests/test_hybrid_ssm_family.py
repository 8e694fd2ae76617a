"""The Mamba-and-attention family (``reference/hybrid_ssm_gqa.py``) in the
benchmark's own parts: the rehearsal cell through the whole harness on the
CPU, and the roofline of what a Mamba layer's recurrence demands of a decode
step (``kernels/selective_scan.py``) on a synthetic trace. The program against
the reference on logits is a tier-1 test (``tests/test_hybrid_ssm.py``)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

from kernels import selective_scan as ss


def test_rehearsal_through_the_whole_harness():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest-jamba.json"), "--workload",
         "rehearsal-jamba", "--seed", "3000000019", "--seconds", "6",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    r = lines[-1]
    assert r["attempted"] > 0 and r["failed"] == 0 and r["correct"] is True
    check = next(l for l in lines if l.get("note") == "check")
    # served cold and again give the same tokens, and nothing came from the
    # prefix cache either time: reuse is off for this family
    assert check["cold_equals_cached"] is True
    assert check["prefix_cached_tokens"] == {"cold": 0.0, "cached": 0.0}
    m = r["metrics"]
    assert 0 < m["ssm_decode_token_share"]["value"] < 100
    assert m["compiles_in_window"]["value"] == 0
    assert not any(l.get("note") == "metric_not_read" and l["name"] in (
        "ssm_decode_token_share", "batch_running_mean", "kv_pool_fill")
        for l in lines)
    # no device on the CPU: nothing read from a trace
    assert "selective_scan_roofline" not in m
    assert "selective_scan_dev_share" not in m
    assert "ssm_decode_step_dev_ms" not in m


with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
    CONF = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]
SRC = {"kind": "kernel_roofline", "kernel": "selective_scan",
       "pattern": "selective_scan", "module": "decode"}
B = 60.0  # sequences decoding, of 64 seats
# one call, one sequence: state 16 x 5120 x 4 B and window 3 x 5120 x 2 B,
# each read and written; x, delta, z and B, C in, y out, float32
PER_SEQ = 2 * 16 * 5120 * 4 + 2 * 3 * 5120 * 2 + (3 * 5120 + 32) * 4 + 5120 * 4


def _ctx(seconds: float, calls: int = 32 * 26, conf=CONF) -> dict:
    """A trace whose fused decode program made ``calls`` selective-scan
    calls (32 steps of 26 Mamba layers, as two kernel names: the period's two
    runs) that took ``seconds`` in all."""
    return {"gen": {"decoding_mean": B}, "device": {"kind": "TPU v5 lite"},
            "config": conf,
            "trace": {"modules": {
                "jit__decode_multi": {"ops": {
                    "selective_scan.3": {"count": calls // 2,
                                         "seconds": seconds / 2},
                    "selective_scan.4": {"count": calls - calls // 2,
                                         "seconds": seconds / 2},
                    "fusion.7": {"count": 999, "seconds": 9.0}}},
                "jit__unified": {"ops": {
                    "selective_scan.3": {"count": 50, "seconds": 1.0}}}}}}


def test_the_demand_is_the_models_bytes():
    ops, byts = ss.cost(B, 5120, 16, 4)
    assert byts == B * PER_SEQ == B * 798848
    assert ops == 7 * B * 16 * 5120
    # bound by bytes: 0.8 MB a sequence against 0.57 MFLOP
    assert byts / PEAKS["hbm_bytes_per_s"] > ops / PEAKS["bf16_flops"]
    # a bfloat16 state would halve the state's share of the demand
    assert ss.cost(B, 5120, 16, 4, state_bytes=2)[1] == byts - B * 16 * 5120 * 4


def test_a_call_at_the_byte_bound_reads_100_percent():
    least = 32 * 26 * B * PER_SEQ / PEAKS["hbm_bytes_per_s"]
    assert ss.roofline(SRC, _ctx(least)) == pytest.approx(1.0)
    assert ss.roofline(SRC, _ctx(2 * least)) == pytest.approx(0.5)


def test_nothing_to_read_is_none_not_an_error():
    assert ss.roofline(SRC, dict(_ctx(1.0), trace=None)) is None
    no_calls = _ctx(1.0)
    no_calls["trace"]["modules"].pop("jit__decode_multi")
    assert ss.roofline(SRC, no_calls) is None  # the parent: no such kernel
    dense = {k: v for k, v in CONF.items() if k != "mamba_d_state"}
    assert ss.roofline(SRC, _ctx(1.0, conf=dense)) is None
    assert ss.roofline(SRC, dict(_ctx(1.0), device={"kind": "cpu"})) is None
