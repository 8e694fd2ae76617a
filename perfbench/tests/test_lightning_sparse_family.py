"""The lightning-and-sparse-attention family
(``reference/hybrid_lightning_sparse.py``) in the benchmark's own parts: the
rehearsal cell through the whole harness on the CPU, the controls' script,
the cell's files, and the two kernels' demand (``kernels/lightning_attention
.py``, ``kernels/sparse_paged_attention.py``) on synthetic traces. The program
against the reference on logits is a tier-1 test
(``tests/test_minicpm_sala.py``)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

from kernels import lightning_attention as la
from kernels import sparse_paged_attention as spa


def test_rehearsal_through_the_whole_harness():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--manifest",
         os.path.join(HERE, "manifest-sala.json"), "--workload",
         "rehearsal-sala", "--seed", "3000000019", "--seconds", "20",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    r = lines[-1]
    # a window of 20 s and not the manifest's 6: the CPU serves about one
    # request a second alone and a fifth of that beside five other test
    # processes, and a window in which no caller sent anything attempts none
    assert r["attempted"] > 0 and r["failed"] == 0
    check = next(l for l in lines if l.get("note") == "check")
    # `cold_equals_cached` is the chip's to hold, and the engine's own test's
    # (tests/test_minicpm_sala.py, float32, batches fixed). Here the two
    # servings are batched as the requests happen to arrive and the CPU's
    # bf16 products depend on the number of rows (test_moe_mla_family.py has
    # the story): every other part of `correct` is judged.
    assert check["lengths_ok"] and check["served_dtype_ok"]
    assert check["reference_worst_deficit"] <= check["margin"]
    assert r["correct"] is check["cold_equals_cached"]
    assert check["prefix_cached_tokens"] == {"cold": 0.0, "cached": 0.0}
    # every prompt of the check runs past the tiny file's dense_len
    assert check["prompt_tokens"][0] >= 128
    m = r["metrics"]
    assert 0 < m["linear_decode_token_share"]["value"] < 100
    assert m["compiles_in_window"]["value"] == 0
    # no device on the CPU: nothing read from a trace
    for name in ("lightning_attention_dev_share", "sparse_attention_dev_share",
                 "lightning_mixed_attention_roofline"):
        assert name not in m


def test_every_control_is_read_and_parts_from_the_sound_reference():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_lightning_sparse.py"),
         "--config", os.path.join(HERE, "tiny-sala.json"), "--seeds", "11",
         "--cpu"], cwd=ROOT, capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["past_dense_len"] == out["positions"] == 64
    for fault in ("int8", "bf16_state", "dense", "top_half", "rope_on_sparse",
                  "no_decay"):
        assert out[fault]["gap_error"]["max"] > 0, fault
    assert out["no_decay"]["worst_deficit"] > out["int8"]["worst_deficit"]


with open(os.path.join(BENCH, "configs", "minicpm-sala-9b.json")) as f:
    CONF = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]


def test_the_cells_files_say_what_the_issue_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "minicpmsala-longdoc")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala-9b", "longdoc-closed", 1)
    cfg = next(c for c in bench["configs"] if c["name"] == "minicpm-sala-9b")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == CONF["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    with open(os.path.join(BENCH, "traffic", "longdoc-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["ramp_s"], mix["drain_s"]) == (
        "closed", 48, 20, 60)
    assert mix["prompt"] == {"kind": "lognormal", "median": 10240,
                             "sigma": 0.35, "min": 6144, "max": 16384}
    assert mix["output"] == {"kind": "lognormal", "median": 512, "sigma": 0.6,
                             "min": 128, "max": 2048}
    assert mix["pool_requests"] <= 512
    assert mix["prompt"]["max"] + mix["output"]["max"] == 18432 \
        <= CONF["engine"]["max_model_len"]
    # the cut and what it keeps
    assert CONF["num_hidden_layers"] == 6 and len(CONF["mixer_types"]) == 32
    assert CONF["layers_served"]["first"] == 13
    assert set(CONF["reduced"]) == {"num_hidden_layers"}
    for key in ("sparse", "lightning_decay", "output_norm", "mup_denominator"):
        assert any(key in k for k in CONF["assumed"]), key
    assert CONF["state"] == {"linear_dtype": "float32"}
    # over half of the positions the check judges lie past dense_len
    chk = CONF["check"]
    n, lo, hi = chk["tails_per_prefix"], chk["tail_tokens"]["min"], \
        chk["tail_tokens"]["max"]
    prompts = [chk["prefix_tokens"] + hi - round(j * (hi - lo) / (n - 1))
               for j in range(n)]
    assert sum(p >= CONF["sparse"]["dense_len"] for p in prompts) * 2 > n
    # each pool array under 2^31 elements (PERF.md section 7's hang)
    e = CONF["engine"]
    assert 4 * e["num_pages"] * e["page_size"] * 2 * 128 < 2 ** 31
    assert 4 * (e["max_batch_size"] + 1) * 32 * 128 * 128 < 2 ** 31
    new = [m for m in bench["per_layer"]
           if m.get("workloads") == ["minicpmsala-longdoc"]]
    assert new and all(m["moves"] == "out_tok_s" for m in new)


LIN = {"kind": "kernel_roofline", "kernel": "lightning_attention",
       "pattern": "lightning_attention", "module": "unified"}
SPA = {"kind": "kernel_roofline", "kernel": "sparse_paged_attention",
       "pattern": "ragged_paged_attention", "module": "unified"}
STEPS = 100.0  # unified dispatches between the two scrapes
B = 14.0  # decode rows a step
CHUNK = 240.0  # prefill tokens a step
STATE = 2 * 32 * 128 * 128 * 4  # a row's state, read and written
PAIRS = 1.5e6  # (query, key) pairs the rule asks for, a step
FULL, HELD, SEEN = 90000.0, 6200.0 * B, 11000.0 * B


def _samples(steps: float) -> list:
    def s(name, value, **labels):
        return (name, labels, value * steps)

    return [
        s("llmd_tpu:engine_program_dispatches_total", 1, program="unified"),
        s("llmd_tpu:unified_decode_rows_total", B, token="host"),
        s("llmd_tpu:linear_attn_tokens_total", CHUNK, rows="prefill"),
        s("llmd_tpu:sparse_attn_qk_pairs_total", PAIRS, program="unified"),
        s("llmd_tpu:attn_kv_tokens_total", FULL, program="unified",
          layers="full"),
        s("llmd_tpu:attn_query_tokens_total", B + CHUNK, program="unified"),
        s("llmd_tpu:sparse_decode_kv_tokens_total", HELD, tokens="held"),
        s("llmd_tpu:sparse_decode_kv_tokens_total", SEEN, tokens="context")]


def _ctx(seconds: float, calls: int, op: str, conf=CONF,
         counters: bool = True) -> dict:
    return {"gen": {}, "device": {"kind": "TPU v5 lite"}, "config": conf,
            "before": {"engine": _samples(1.0) if counters else []},
            "after": {"engine": _samples(1.0 + STEPS) if counters else []},
            "trace": {"modules": {"jit__unified": {"ops": {
                op: {"count": calls, "seconds": seconds},
                "fusion.7": {"count": 999, "seconds": 9.0}}}}}}


def _lin_ctx(seconds: float, **kw) -> dict:
    return _ctx(seconds, 8 * 4, "lightning_attention.3_f32_48_4096_..", **kw)


def _spa_ctx(seconds: float, **kw) -> dict:
    return _ctx(seconds, 8 * 2 * 4, "ragged_paged_attention.5_bf16_256_..",
                **kw)


def test_the_lightning_demand_is_the_models_bytes():
    ops, byts = la.cost([(B, 1)], 32, 128)
    assert byts == B * (STATE + 32 * 128 * (3 * 2 + 4))
    assert ops == B * 32 * (4 * 128 + 4 * 128 * 128)
    assert byts / PEAKS["hbm_bytes_per_s"] > ops / PEAKS["bf16_flops"]
    # a chunk of 240 tokens is bound by its products, not its state
    ops, byts = la.cost([(1, 240)], 32, 128)
    assert ops == 32 * (4 * 240 * 240 * 128 + 4 * 240 * 128 * 128)
    # a unified step: 14 decode rows and one chunk of 240 tokens, 8 steps of
    # 4 layers in the capture
    ops, byts = la.cost([(B, 1), (1, CHUNK)], 32, 128)
    least = 8 * 4 * max(byts / PEAKS["hbm_bytes_per_s"],
                        ops / PEAKS["bf16_flops"])
    assert la.roofline(LIN, _lin_ctx(least)) == pytest.approx(1.0)
    assert la.roofline(LIN, _lin_ctx(4 * least)) == pytest.approx(0.25)
    # the sparse layers' calls: 8 steps of 2 layers, 2 calls a KV head
    ops, byts = spa.cost(PAIRS, FULL - SEEN + HELD, B + CHUNK, 32, 2, 128)
    assert ops == 4 * 32 * 128 * PAIRS
    assert byts == ((FULL - SEEN + HELD) * 2 * 2 * 128
                    + (B + CHUNK) * 2 * 32 * 128) * 2
    least = 8 * 2 * max(byts / PEAKS["hbm_bytes_per_s"],
                        ops / PEAKS["bf16_flops"])
    assert spa.roofline(SPA, _spa_ctx(least)) == pytest.approx(1.0)
    assert spa.roofline(SPA, _spa_ctx(5 * least)) == pytest.approx(0.2)


def test_nothing_to_read_is_none_not_an_error():
    for src, ctx in ((LIN, _lin_ctx), (SPA, _spa_ctx)):
        mod = la if src is LIN else spa
        assert mod.roofline(src, dict(ctx(1.0), trace=None)) is None
        empty = ctx(1.0)
        empty["trace"]["modules"].pop("jit__unified")
        assert mod.roofline(src, empty) is None  # no such call in the capture
        # a program without the counters: the parent
        assert mod.roofline(src, ctx(1.0, counters=False)) is None
    dense = {k: v for k, v in CONF.items()
             if k not in ("lightning_nh", "sparse")}
    assert la.roofline(LIN, _lin_ctx(1.0, conf=dense)) is None
    assert spa.roofline(SPA, _spa_ctx(1.0, conf=dense)) is None
