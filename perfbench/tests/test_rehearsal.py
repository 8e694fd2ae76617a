"""The whole harness on the CPU at a tiny size: parent, engine child, router
child, check, ramp, window, drain, result line. Not a cell: the result names
the CPU and carries no device metric. About a minute a case."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

DEVICE_ONLY = {"prefill_step_dev_ms", "decode_step_dev_ms", "attn_dev_share",
               "ragged_paged_attention_roofline", "device_idle_share",
               "hbm_in_use_gib"}


def _run(cell: str, trace: int, seconds: int = 6) -> dict:
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest.json"), "--workload", cell, "--seed",
         "3000000019", "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [("rehearsal-closed", 0),
                                        ("rehearsal-sessions-closed", 0),
                                        ("rehearsal-sessions", 1)])
def test_rehearsal(cell, trace):
    r = _run(cell, trace)
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert r["attempted"] > 0 and r["failed"] == 0 and r["correct"] is True
    if trace:
        # rehearsal-sessions reads the metrics of the Mistral cell, whose
        # shared quantities carry the .tpot names
        assert r["metrics"]["compiles_in_window.tpot"]["value"] == 0
        assert r["metrics"]["session_out_tok_s"]["value"] > 0
        assert not DEVICE_ONLY & {n.removesuffix(".tpot")
                                  for n in r["metrics"]}
        assert "busy_s" not in r["device"]
    else:
        # the end-to-end metric of the cell whose metrics the rehearsal reads
        rate = "tpot_p95_ms" if "sessions" in cell else "out_tok_s"
        assert set(r["metrics"]) == {rate, "setup_s"}
        assert r["metrics"][rate]["value"] > 0
        assert r["metrics"]["setup_s"]["value"] > 0


def test_no_result_without_the_tpu():
    """The command as the driver runs it, where JAX finds no TPU: no result
    line, and a code that is not 0."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen1.5b-offline", "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    last = (p.stdout.strip().splitlines() or [""])[-1]
    assert '"metrics"' not in last
