"""The KDA, latent-attention and group-limited mixture family
(``reference/hybrid_kda_mla_moe.py``) in the benchmark's own parts: the
rehearsal cell through the whole harness on the CPU, the controls' script at
the rehearsal's size, and the cell's files. The program against the reference
on logits, the kernel and the roofline's arithmetic are tier-1 tests
(``tests/test_ling.py``); ``tests/test_ling_bench.py`` runs this file there."""

import json
import os
import subprocess
import sys

from conftest import BENCH, HERE, ROOT


def test_rehearsal_through_the_whole_harness():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest-ling.json"), "--workload",
         "rehearsal-ling", "--seed", "3000000019", "--seconds", "6",
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
    # (tests/test_ling.py) with the batches fixed: here the two servings are
    # batched as the requests happen to arrive, and the CPU's bf16 products
    # depend on the number of rows. Every other part is judged.
    assert check["lengths_ok"] and check["served_dtype_ok"]
    assert check["reference_worst_deficit"] <= check["margin"]
    probe = check["gap_probe"]
    # the rehearsal judges the cleaner half of the prompts (run.py's other
    # rule; the cell's file judges the median, PERF.md section 2)
    assert probe["judged"] == "clean_half"
    assert probe["read"] == probe["gap_error"]["clean_half"] <= probe["limit"]
    assert r["correct"] is check["cold_equals_cached"]
    # every number compared, beside its limit, is the result's last key
    assert list(r)[-1] == "check"
    assert r["check"]["gap_clean_half"] == [probe["read"], probe["limit"]]
    m = r["metrics"]
    # the counters this family feeds, read through their metric files
    assert 30.0 < m["ling_moe_held_copy_share"]["value"] < 70.0
    assert 0 < m["kda_decode_token_share"]["value"] < 100
    assert 0 < m["ling_moe_group_moved_share"]["value"] < 60.0
    assert 1.0 <= m["ling_moe_expert_imbalance"]["value"] <= 4.0
    assert m["ling_decode_call_steps"]["value"] >= 1
    assert 0 <= m["ling_decode_seat_waste_share"]["value"] < 100
    assert m["compiles_in_window"]["value"] == 0
    # no device on the CPU: nothing read from a trace
    for name in ("kda_attention_dev_share", "kda_mixed_roofline",
                 "kda_decode_roofline", "ling_grouped_gemm_dev_share"):
        assert name not in m
    said = {l["name"] for l in lines if l.get("note") == "metric_not_read"}
    assert not said & {"ling_moe_held_copy_share", "kda_decode_token_share",
                       "ling_moe_group_moved_share",
                       "ling_moe_expert_imbalance", "ling_decode_call_steps"}


def test_every_control_is_read_and_parts_from_the_sound_reference():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_kda_mla_moe.py"),
         "--config", os.path.join(HERE, "tiny-ling.json"), "--seeds", "11",
         "--cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    faults = ("bf16_state", "no_group_limit", "no_delta", "softplus_gate",
              "no_mla_rope", "no_head_gate", "no_qk_l2", "no_out_gate",
              "no_shared", "no_scaling", "bias_dropped", "absent_computed")
    assert out["positions"] == 24
    for name in ("int8", "top_k-1") + faults:
        assert out[name]["gap_error"]["max"] > 0.0, name
    for name in faults[1:]:
        assert out[name]["gap_error"]["max"] > 0.05, name


def test_the_cells_files_say_what_the_issue_asks():
    with open(os.path.join(BENCH, "traffic", "longanswer-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["pool_requests"], mix["block"],
            mix["ramp_s"], mix["drain_s"]) == ("closed", 96, 2048, 16, 15, 60)
    assert "sessions" not in mix
    assert mix["prompt"] == {"kind": "lognormal", "median": 1024, "sigma": 0.9,
                             "min": 128, "max": 8192}
    assert mix["output"]["kind"] == "lognormal"
    assert (mix["output"]["median"], mix["output"]["sigma"],
            mix["output"]["min"]) == (1024, 0.5, 256)
    # (2,048 as the issue gives it, or the 1,536 it allows where a decode
    # step reads over 20 ms on the chip: the file's `why` says which)
    assert mix["output"]["max"] in (2048, 1536)
    with open(os.path.join(BENCH, "configs", "ling-3.0-flash-vl.json")) as f:
        conf = json.load(f)
    e = conf["engine"]
    assert (e["max_batch_size"], e["page_size"], e["prefill_chunk"],
            e["max_model_len"], e["kv_cache_dtype"]) == (
                64, 16, 256, 10240, "bfloat16")
    # the longest request fits the model length, and the pool the 64 seats
    assert mix["prompt"]["max"] + mix["output"]["max"] <= e["max_model_len"]
    assert e["num_pages"] * e["page_size"] >= 64 * e["max_model_len"]
    assert sorted(conf["reduced"]) == [
        "first_k_dense_replace", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["experts"]["published"], conf["experts"]["held_first"],
            conf["first_k_dense_replace"], conf["vocab_size"],
            conf["layers"]["published_first"]) == (
                7, conf["experts"]["published"]
                // conf["experts"]["chips_sharing_a_layer"], 512, 0, 1,
                157184 // 4, 1)
    assert conf["state"] == {"kda_dtype": "float32", "conv_dtype": "bfloat16"}
    assert conf["weights"]["dtype"] == "bfloat16" and \
        conf["weights"]["quantize"] is None
    # every published key of the catalog row but the four that are cut
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(l) for l in f
                   if '"name": "Ling-3.0-flash-VL"' in l)
    assert conf["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert differ == set(conf["reduced"])
    for key in ("mla_layer_in_a_group", "qk_norm", "kda_gate", "kda_beta",
                "gates", "group_score", "state.kda_dtype",
                "router_bias_scale", "all_published_keys"):
        assert key in conf["assumed"]
