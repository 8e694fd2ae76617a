"""The Mamba-2, attention and non-gated-experts family
(``reference/hybrid_mamba2_moe.py``) in the benchmark's own parts: the
rehearsal cell through the whole harness on the CPU, the controls' script at
the rehearsal's size, and the cell's files. The program against the reference
on logits, the kernel and the roofline's arithmetic are tier-1 tests
(``tests/test_nemotron.py``); ``tests/test_nemotron_bench.py`` runs this file
there."""

import json
import os
import subprocess
import sys

from conftest import BENCH, HERE, ROOT


def test_rehearsal_through_the_whole_harness():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest-nemotron.json"), "--workload",
         "rehearsal-nemotron", "--seed", "3000000019", "--seconds", "6",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    check = next(l for l in lines if l.get("note") == "check")
    # nothing came from the prefix cache either time: reuse is off for a
    # model with recurrent layers
    assert check["prefix_cached_tokens"] == {"cold": 0.0, "cached": 0.0}
    r = lines[-1]
    assert r["attempted"] > 0 and r["failed"] == 0
    # `cold_equals_cached` is the chip's to hold, and the engine's own test's
    # (tests/test_nemotron.py) with the batches fixed: here the two servings
    # are batched as the requests happen to arrive, and the CPU's bf16
    # products depend on the number of rows. Every other part is judged.
    assert check["lengths_ok"] and check["served_dtype_ok"]
    assert check["reference_worst_deficit"] <= check["margin"]
    probe = check["gap_probe"]
    # judged as the cell's file is: the cleaner half of the prompts
    assert probe["judged"] == "clean_half"
    assert probe["read"] == probe["gap_error"]["clean_half"] <= probe["limit"]
    assert probe["read"] <= probe["gap_error"]["median"]
    assert r["correct"] is check["cold_equals_cached"]
    # every number compared, beside its limit, is the result's last key
    assert list(r)[-1] == "check"
    assert r["check"]["gap_clean_half"] == [probe["read"], probe["limit"]]
    assert r["check"]["worst_deficit"] == [check["reference_worst_deficit"],
                                           check["margin"]]
    m = r["metrics"]
    # the counters this family feeds, read through their metric files
    assert 30.0 < m["moe_held_copy_share"]["value"] < 70.0
    assert 0 < m["mamba2_decode_token_share"]["value"] < 100
    assert 1.0 <= m["nemotron_moe_expert_imbalance"]["value"] <= 4.0
    assert m["compiles_in_window"]["value"] == 0
    # no device on the CPU: nothing read from a trace
    for name in ("mamba2_ssd_dev_share", "mamba2_ssd_mixed_roofline",
                 "nemotron_grouped_gemm_dev_share"):
        assert name not in m
    said = {l["name"] for l in lines if l.get("note") == "metric_not_read"}
    assert not said & {"moe_held_copy_share", "mamba2_decode_token_share",
                       "nemotron_moe_expert_imbalance"}


def test_every_control_is_read_and_parts_from_the_sound_reference():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_mamba2_moe.py"),
         "--config", os.path.join(HERE, "tiny-nemotron.json"), "--seeds", "11",
         "--cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    faults = ("no_D", "no_conv_bias", "gate_after_norm", "group0_for_all",
              "relu", "no_shared", "no_scaling", "bias_in_weights",
              "absent_computed", "rope_on")
    assert out["positions"] == 24
    for name in ("int8", "top_k-1", "bf16_state") + faults:
        assert out[name]["gap_error"]["max"] > 0.0, name
    for name in faults:
        assert out[name]["gap_error"]["max"] > 0.05, name
    # read as the file's probe judges, the probe's width the most it finds
    assert 0 < out["int8"]["gap_error"]["clean_half"] <= 0.064


def test_the_cells_files_say_what_the_issue_asks():
    with open(os.path.join(BENCH, "traffic", "agentic-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["pool_requests"], mix["block"],
            mix["ramp_s"], mix["drain_s"]) == ("closed", 96, 2048, 16, 15, 60)
    assert "sessions" not in mix
    assert mix["prompt"] == {"kind": "lognormal", "median": 1536, "sigma": 0.7,
                             "min": 256, "max": 8192}
    assert mix["output"] == {"kind": "lognormal", "median": 512, "sigma": 0.5,
                             "min": 128, "max": 1536}
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        conf = json.load(f)
    e = conf["engine"]
    assert (e["max_batch_size"], e["page_size"], e["prefill_chunk"],
            e["max_model_len"], e["kv_cache_dtype"]) == (
                64, 16, 256, 10240, "bfloat16")
    # the longest request fits the model length, and the pool the 64 seats
    assert mix["prompt"]["max"] + mix["output"]["max"] <= e["max_model_len"]
    assert e["num_pages"] * e["page_size"] >= 64 * e["max_model_len"]
    assert sorted(conf["reduced"]) == ["n_routed_experts", "num_hidden_layers"]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["experts"]["published"], conf["experts"]["held_first"],
            conf["experts"]["chips_sharing_a_layer"]) == (14, 64, 128, 0, 2)
    assert conf["state"] == {"ssm_dtype": "float32", "conv_dtype": "bfloat16"}
    assert conf["weights"]["dtype"] == "bfloat16" and \
        conf["weights"]["quantize"] is None
    # every published key of the catalog row but the two that are cut
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(l) for l in f
                   if "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" in l)
    assert conf["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert differ == {"num_hidden_layers", "n_routed_experts"}
    for key in ("positional_encoding", "state.ssm_dtype", "chunk_size",
                "router_bias_scale"):
        assert key in conf["assumed"]
