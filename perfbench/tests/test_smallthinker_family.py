"""The window-and-full attention, early-router ReGLU family
(``reference/moe_swa_gqa.py``) in the benchmark's own parts: the rehearsal
cell through the whole harness on the CPU, and the roofline of what the model
demands (``kernels/mixed_window_attention.py``) on a synthetic trace. The
program against the reference on logits is a tier-1 test
(``tests/test_smallthinker.py``)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

from kernels import mixed_window_attention as mwa
from kernels.ragged_paged_attention import cost, least_seconds


def test_rehearsal_through_the_whole_harness():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest-smallthinker.json"), "--workload",
         "rehearsal-smallthinker", "--seed", "3000000019", "--seconds", "6",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["attempted"] > 0 and r["failed"] == 0 and r["correct"] is True
    m = r["metrics"]
    # the two counters this family feeds, read through their metric files
    assert 0 < m["window_kv_read_share"]["value"] < 100
    assert 1.0 <= m["moe_expert_imbalance"]["value"] <= 8.0
    assert m["compiles_in_window.tpot"]["value"] == 0
    # no device on the CPU: nothing read from a trace
    assert "mixed_window_attention_roofline" not in m
    assert "grouped_gemm_dev_share" not in m


with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
    CONF = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]
SRC = {"kind": "kernel_roofline", "kernel": "mixed_window_attention",
       "pattern": "ragged_paged_attention", "module": "decode"}
S, B = 64 * 8000.0, 64.0  # 64 rows at a mean context of 8,000 tokens


def _ctx(seconds: float, calls: int = 128, conf=CONF) -> dict:
    """A trace whose fused decode program made ``calls`` attention calls
    (32 steps of 4 layers) that took ``seconds`` in all."""
    return {"gen": {"decode_ctx_tokens_mean": S, "decoding_mean": B},
            "device": {"kind": "TPU v5 lite"}, "config": conf,
            "trace": {"modules": {
                "jit__decode_multi": {"ops": {
                    "ragged_paged_attention_kernel.3":
                        {"count": calls // 4, "seconds": seconds / 4},
                    "ragged_paged_attention_kernel.4":
                        {"count": calls - calls // 4,
                         "seconds": seconds * 3 / 4},
                    "fusion.7": {"count": 999, "seconds": 9.0}}},
                "jit__unified": {"ops": {
                    "ragged_paged_attention_kernel.3":
                        {"count": 50, "seconds": 1.0}}}}}}


def _demand(calls: int = 128) -> float:
    shape = (CONF["num_attention_heads"], CONF["num_key_value_heads"],
             CONF["head_dim"])
    full = least_seconds(*cost(S, B, *shape), PEAKS)
    window = least_seconds(*cost(B * 4096, B, *shape), PEAKS)
    return calls * (full / 4 + window * 3 / 4)


def test_a_call_that_reads_exactly_the_demand_reads_100_percent():
    assert mwa.roofline(SRC, _ctx(_demand())) == pytest.approx(1.0)


def test_masking_alone_reads_low_and_the_demand_is_the_models():
    # every layer reading every page at the byte bound: S tokens in all four
    shape = (28, 4, 128)
    masked = 128 * least_seconds(*cost(S, B, *shape), PEAKS)
    got = mwa.roofline(SRC, _ctx(masked))
    # (S + 3 * 64 * 4096) / 4S = 0.634 at a mean context of 8,000
    assert got == pytest.approx((S + 3 * B * 4096) / (4 * S), rel=0.01)
    # per call of a full layer every key and value once, 2,048 B a token
    full, window = mwa.demand_seconds(_ctx(1.0), CONF, PEAKS)
    assert full == pytest.approx(
        (S * 2048 + 2 * B * 28 * 128 * 2) / PEAKS["hbm_bytes_per_s"])
    assert window == pytest.approx(
        (B * 4096 * 2048 + 2 * B * 28 * 128 * 2) / PEAKS["hbm_bytes_per_s"])


def test_contexts_inside_the_window_demand_what_they_hold():
    short = dict(_ctx(1.0))
    short["gen"] = {"decode_ctx_tokens_mean": 64 * 1000.0, "decoding_mean": B}
    full, window = mwa.demand_seconds(short, CONF, PEAKS)
    assert full == window  # min(S, B * window) = S
    # the rows themselves: each row its own min(context, window), exactly
    rows = [(1000, None, 0)] * 32 + [(9000, None, 0)] * 32
    full, window = mwa.demand_seconds({"decode_rows": [rows]}, CONF, PEAKS)
    shape = (28, 4, 128)
    assert full == least_seconds(*cost(32 * 10000.0, B, *shape), PEAKS)
    assert window == least_seconds(*cost(32 * 5096.0, B, *shape), PEAKS)


def test_nothing_to_read_is_none_not_an_error():
    assert mwa.roofline(SRC, dict(_ctx(1.0), trace=None)) is None
    no_calls = _ctx(1.0)
    no_calls["trace"]["modules"].pop("jit__decode_multi")
    assert mwa.roofline(SRC, no_calls) is None
    dense = {k: v for k, v in CONF.items() if k != "sliding_window_layout"}
    assert mwa.roofline(SRC, _ctx(1.0, conf=dense)) is None
    assert mwa.roofline(SRC, dict(_ctx(1.0), device={"kind": "cpu"})) is None
