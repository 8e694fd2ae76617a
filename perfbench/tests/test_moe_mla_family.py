"""The latent-attention, leading-dense, sigmoid-routed mixture family
(``reference/moe_mla.py``) in the benchmark's own parts: the rehearsal cell
through the whole harness on the CPU, the controls' script at the rehearsal's
size, and the manifest's files. The program against the reference on logits,
the latent kernel and the rooflines' arithmetic are tier-1 tests
(``tests/test_glm.py``); ``tests/test_glm_bench.py`` runs this file there."""

import json
import os
import subprocess
import sys

from conftest import BENCH, HERE, ROOT


def test_rehearsal_through_the_whole_harness():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest-glm.json"), "--workload",
         "rehearsal-glm", "--seed", "3000000019", "--seconds", "6",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    check = next(l for l in lines if l.get("note") == "check")
    assert check["attn_backend"] == "xla_mla_absorbed"
    assert check["prefix_cached_tokens"]["cached"] > \
        check["prefix_cached_tokens"]["cold"] > 0
    r = lines[-1]
    assert r["attempted"] > 0 and r["failed"] == 0
    # `cold_equals_cached` is the chip's to hold, and the engine's own test's
    # (tests/test_glm.py) with the batches fixed. Here the two servings are
    # batched as the requests happen to arrive, and the CPU's bf16 products
    # depend on the number of rows: under six test workers 2 runs of 11 served
    # another token at a near tie. Every other part of `correct` is judged.
    assert check["lengths_ok"] and check["served_dtype_ok"]
    assert check["reference_worst_deficit"] <= check["margin"]
    probe = check["gap_probe"]
    assert probe["read"] <= probe["limit"]
    assert r["correct"] is check["cold_equals_cached"]
    m = r["metrics"]
    # the counters this family feeds, read through their metric files
    assert 2.0 < m["moe_bias_moved_share"]["value"] < 60.0
    assert 1.0 <= m["glm_moe_expert_imbalance"]["value"] <= 8.0
    assert 0.0 <= m["glm_moe_bank_reuse_share"]["value"] <= 100.0
    assert m["compiles_in_window.tpot"]["value"] == 0
    # no device on the CPU: nothing read from a trace
    for name in ("mla_decode_attention_roofline", "mla_attention_dev_share",
                 "mla_mixed_attention_roofline", "glm_grouped_gemm_dev_share"):
        assert name not in m
    said = {l["name"] for l in lines if l.get("note") == "metric_not_read"}
    assert "moe_bias_moved_share" not in said


def test_every_control_is_read_and_parts_from_the_sound_reference():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_mla_moe.py"), "--config",
         os.path.join(HERE, "tiny-glm.json"), "--seeds", "11", "--cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    faults = ("softmax", "bias_dropped", "bias_in_weights", "no_scaling",
              "no_renorm", "no_q_norm", "no_kv_norm", "rope_on_nope",
              "no_shared", "dense_as_expert")
    assert out["positions"] == 64
    for name in ("int8", "top_k-1") + faults:
        assert out[name]["gap_error"]["max"] > 0.05, name
    for name in faults:
        assert out[name]["argmax_agree"] < out["positions"], name


def test_the_cells_files_say_what_the_issue_asks():
    with open(os.path.join(BENCH, "traffic", "docs-sessions-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["pool_requests"], mix["block"],
            mix["drain_s"]) == ("closed", 64, 2048, 16, 45)
    assert mix["sessions"] == {"lanes": 64, "turns": 4, "tenants": 16,
                               "system_prompt": 16384}
    assert mix["prompt"] == {"kind": "lognormal", "median": 128, "sigma": 0.7,
                             "min": 64, "max": 512}
    assert mix["output"] == {"kind": "lognormal", "median": 128, "sigma": 0.4,
                             "min": 64, "max": 256}
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        conf = json.load(f)
    e = conf["engine"]
    assert (e["max_batch_size"], e["page_size"], e["max_model_len"],
            e["kv_cache_dtype"]) == (64, 16, 20480, "bfloat16")
    # the longest conversation fits the model length, and the pool the cell
    longest = 16384 + 4 * (mix["prompt"]["max"] + mix["output"]["max"])
    assert longest == 19456 <= e["max_model_len"]
    assert conf["num_hidden_layers"] * e["num_pages"] * 16 * 640 < 2 ** 31
    assert "num_nextn_predict_layers" in conf["assumed"]
    assert list(conf["reduced"]) == ["num_hidden_layers"]
