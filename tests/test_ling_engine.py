"""The engine's side of ISSUE 51's family ('kda' mixers beside latent
attention over a group-limited mixture; ``tests/test_ling.py`` holds the
program against the reference and the kernel): greedy tokens served alone, in
batches and through the fused decode call are equal, the served tokens are
the reference's, the counters move, a prompt's chunks start on the kernel's
blocks, and what cannot stand beside recurrent layers is refused by name. A
file of its own so that the two run on two workers."""

from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest
from test_ling import CONF, SIZES, family

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from llmd_tpu.ops.kda_attention import BLOCK, kda_attention_pallas
from llmd_tpu.parallel.mesh import MeshConfig


def _engine(cfg=None, **kw):
    fields = dict(page_size=4, num_pages=512, max_model_len=512,
                  max_batch_size=8, prefill_chunk=64, decode_steps=4)
    return LLMEngine(cfg or get_model_config("tiny-ling"),
                     EngineConfig(**dict(fields, **kw)), seed=3)


GREEDY = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)


def _run(eng, prompts, tag):
    ids = [f"{tag}{i}" for i in range(len(prompts))]
    for i, p in zip(ids, prompts):
        eng.add_request(i, p, GREEDY)
    out = {}
    while eng.has_work():
        for o in eng.step():
            out.setdefault(o.request_id, []).extend(o.new_token_ids)
    return [out[i] for i in ids]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [[int(t) for t in rng.integers(0, 288, size=n)]
            for n in (70, 130, 65, 200, 12, 1)]


@pytest.fixture(scope="module")
def served(prompts):
    eng = _engine()
    return eng, _run(eng, prompts, "a")


def _series(eng, name):
    return {l.split(" ")[0]: float(l.split(" ")[-1])
            for l in eng.metrics.registry.expose().splitlines()
            if l.startswith(name)}


def test_tokens_served_alone_in_two_batches_and_all_at_once_are_equal(
        served, prompts):
    _, all6 = served
    eng = _engine()
    assert _run(eng, prompts[:2], "b") + _run(eng, prompts[2:], "c") == all6
    # (the same engine, emptied: every seat's state starts from zeros)
    assert [_run(eng, [p], f"d{i}")[0] for i, p in enumerate(prompts)] == all6


def test_engine_tokens_are_the_references(served, prompts):
    eng, out = served
    conf = dict(CONF, weights={"dtype": "bfloat16", "quantize": None})
    assert family.model_config(conf) == replace(
        eng.model_cfg, name=conf["name"], max_position=1024)
    p, o = prompts[3], out[3]
    ref = np.asarray(family.logits(SIZES, eng.params, p + o[:-1]))[-len(o):]
    # bf16 weights and activations against the float32 reference: a served
    # token lies within 1.0 of the reference's maximum (logits of magnitude
    # 4; read 0.0 to 0.60 over the 8 tokens: a bf16 rounding flips an expert
    # at a near tie and the delta-rule state carries it on; a named fault
    # reads 2 or more at its worst position, above)
    assert float((ref.max(-1) - ref[np.arange(len(o)), o]).max()) < 1.0


def test_the_engine_counts_the_state_the_share_and_the_groups(served):
    eng, _ = served
    lin = _series(eng, "llmd_tpu:linear_attn_tokens_total")
    assert lin['llmd_tpu:linear_attn_tokens_total{rows="prefill"}'] == 478
    assert lin['llmd_tpu:linear_attn_tokens_total{rows="decode"}'] > 0
    assert _series(eng, "llmd_tpu:linear_state_resets_total")[
        "llmd_tpu:linear_state_resets_total"] == 6
    assert not _series(eng, "llmd_tpu:ssm_scan_tokens_total")
    info = _series(eng, "llmd_tpu:engine_ssm_backend")
    assert list(info) == [
        'llmd_tpu:engine_ssm_backend{impl="xla_kda_attention_block%d",'
        'state_dtype="float32",prefix_reuse="off"}' % BLOCK]
    routed = list(_series(eng, "llmd_tpu:moe_routed_copies_total").values())[0]
    held = list(_series(eng, "llmd_tpu:moe_held_copies_total").values())[0]
    kept = list(_series(
        eng, "llmd_tpu:moe_group_kept_copies_total").values())[0]
    assert 0.3 < held / routed < 0.7 and 0.5 < kept / routed < 1.0
    assert list(_series(eng, "llmd_tpu:moe_bias_moved_choices_total")
                .values())[0] > 0
    assert 'gemm="none held=0-3/8"' in eng.metrics.registry.expose()
    assert eng.backends.compiler_options == {
        "xla_allow_excess_precision": False}
    assert eng.backends.attn_backend == "xla_mla_absorbed"
    assert eng.prefix_reuse is False
    # prompts through the unified step, answers through the fused decode call
    counters = eng.programs.counters()
    assert counters["unified"][0] > 0 and counters["decode"][0] > 0


def test_a_prompts_chunks_start_on_the_kernels_blocks(prompts):
    eng = _engine(prefill_chunk=100)
    eng.add_request("x", prompts[3], replace(GREEDY, max_tokens=1))  # 200
    seq, starts = eng.waiting[0], []
    while eng.has_work():
        if seq.num_computed < 200 and seq.num_computed not in starts:
            starts.append(seq.num_computed)
        eng.step()
    # chunks of 100 are cut to 96; the last, 8 tokens, is the prompt's rest
    assert starts == [0, 6 * BLOCK, 12 * BLOCK]


@pytest.mark.parametrize("kw,name", [
    (dict(spec_mode="ngram"), "spec_mode"),
    (dict(mesh=MeshConfig(tp=2)), "mesh.tp"),
    (dict(mesh=MeshConfig(ep=2)), "mesh.ep"),
    (dict(cpu_offload_pages=8), "cpu_offload_pages"),
    (dict(role="prefill"), "role"),
    (dict(kv_connector="x"), "kv_connector")])
def test_what_cannot_stand_beside_recurrent_layers_is_refused_by_name(kw, name):
    with pytest.raises(ValueError, match=name):
        _engine(**kw)


def test_the_pallas_backends_are_bound_by_resolve():
    eng = _engine(attn_impl="pallas", moe_matmul="pallas")
    b = eng.backends
    assert b.ssm_backend == "pallas_kda_attention_block%d" % BLOCK
    assert b.attn_backend == "pallas_mla_ragged_paged_attention"
    assert b.ssm_state_dtype == "float32" and "kda_impl" in b.core_kwargs
    # (the programs these bind: tests/test_step_programs.py, tiny-ling+pallas)
    assert b.core_kwargs["kda_impl"].func is kda_attention_pallas
    assert b.core_kwargs["kda_impl"].keywords == {"interpret": True}


