"""Device time by part of the model (ISSUE 55): the step programs' compiled
text names a part of ``MODEL_PARTS`` for its instructions, the engine
publishes the map as ``llmd_tpu:program_part_ops``, and the benchmark's
reader (``perfbench/kernels/step_parts.py``) joins a scrape of it with a
trace's seconds by operation.

On the CPU, one tiny configuration of each family the benchmark's cells
serve. The CPU compiler fuses otherwise than the chip's, so which
instruction lands in which part is the chip's to say (PERF.md section 5);
what holds here is the mechanism: every instruction gets a name of the
vocabulary, the parts a family's layers have are there, the series parse back
to the map, and the reader gives nothing where the program gives no map.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import conftest  # noqa: F401
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
# the benchmark's modules, by path and for the import alone (perfbench/ has a
# tests/ of its own, which must not shadow this package for the other files)
sys.path.append(BENCH)
try:
    import prom  # noqa: E402
    import readers  # noqa: E402
    import xplane  # noqa: E402
    from kernels import step_parts  # noqa: E402
    from reference import moe_swa_gqa  # noqa: E402
finally:
    sys.path.remove(BENCH)

from llmd_tpu.core.request import SamplingParams  # noqa: E402
from llmd_tpu.engine import EngineConfig, LLMEngine  # noqa: E402
from llmd_tpu.engine.programs import MODEL_PARTS  # noqa: E402
from llmd_tpu.models import get_model_config  # noqa: E402
from llmd_tpu.obs.program_parts import (  # noqa: E402
    AMBIGUOUS, UNSCOPED, ProgramParts, part_of_path, parts_of_text,
    weight_copies)

SERIES = "llmd_tpu:program_part_ops"
BASE = dict(page_size=8, num_pages=128, max_model_len=128, max_batch_size=4,
            prefill_chunk=32, decode_steps=4)
EVERY = {"attn_qkv", "kv_write", "attn", "attn_out", "unembed", "sample"}
MOE = {"moe_router", "moe_dispatch", "moe_experts"}
MIXER = {"mixer_in", "mixer", "mixer_out"}
# family -> (preset or None, EngineConfig fields, parts its programs hold)
FAMILIES = {
    "dense": ("tiny", {}, EVERY | {"ffn"}),
    "windowed-moe": (None, dict(page_size=4), EVERY | MOE),
    "mamba1-hybrid": ("tiny-jamba", dict(max_model_len=96, prefill_chunk=16),
                      EVERY | MIXER | {"ffn"}),
    "lightning-sparse": ("tiny-sala", dict(page_size=2, num_pages=512,
                                           max_model_len=256),
                         EVERY | MIXER | {"ffn", "sparse_select"}),
    "mamba2-moe": ("tiny-nemotron-h", {}, EVERY | MIXER | MOE),
    "kda-latent-moe": ("tiny-ling", {}, EVERY | MIXER | MOE | {"ffn"}),
}
PROGRAMS = ("jit__unified", "jit__decode_multi")


@functools.lru_cache(maxsize=None)
def _served(family: str) -> LLMEngine:
    """The family's tiny engine after both step programs compiled and their
    text was read, as a served engine's loop reads it."""
    preset, fields, _ = FAMILIES[family]
    if preset is None:
        with open(os.path.join(BENCH, "tests", "tiny-smallthinker.json")) as f:
            model = moe_swa_gqa.model_config(json.load(f))
    else:
        model = get_model_config(preset)
    eng = LLMEngine(model, EngineConfig(**dict(BASE, **fields)), seed=3)
    eng.generate([list(range(5, 22)), [3, 4, 5]],
                 SamplingParams(max_tokens=10, temperature=0.0))
    assert len(eng.programs.unread) == 2  # one signature a program
    assert eng.read_compiled_programs() == 2 and not eng.programs.unread
    return eng


CASES = [(f, p) for f in FAMILIES for p in PROGRAMS]


@pytest.mark.parametrize("family,program", CASES,
                         ids=[f"{f}-{p}" for f, p in CASES])
def test_compiled_program_names_a_part_for_every_instruction(family, program):
    eng = _served(family)
    held = eng.programs.parts.maps[program]
    assert held and set(held.values()) <= set(MODEL_PARTS) | {UNSCOPED}
    missing = FAMILIES[family][2] - set(held.values())
    assert not missing, f"{family}/{program} holds no instruction of {missing}"
    assert program not in eng.programs.parts.stale
    # the published series, through the text exposition and the benchmark's
    # own parser, give the same map back
    samples = prom.parse(eng.metrics.registry.expose())
    got = step_parts.program_maps(samples)
    assert got is not None and got[program] == held
    assert all(ls["stale"] == "0" and v == len(ls["ops"].split())
               for n, ls, v in samples if n == SERIES)


def test_reading_the_text_compiles_nothing_and_costs_no_step():
    """A signature is read once: a second step of the same shapes leaves
    nothing to read, and the step programs' jit caches hold one entry."""
    eng = _served("dense")
    before = dict(eng.programs.compile_counts())
    eng.generate([list(range(7, 20)), [9, 8, 7]],
                 SamplingParams(max_tokens=6, temperature=0.0))
    assert not eng.programs.unread
    assert eng.read_compiled_programs() == 0
    assert eng.programs.compile_counts() == before
    assert eng.programs.parts.signatures == {p: 1 for p in PROGRAMS}


@pytest.mark.parametrize("path,part", [
    ("jit(_unified)/while/body/closed_call/ffn/...d,df->...f/dot_general",
     "ffn"),
    ("jit(_unified)/while/body/attn/sparse_select/top_k", "sparse_select"),
    ("jit(_unified)/mixer_out/mixer_in/logistic", "mixer_in"),
    ("jit(_unified)/sample/jit(sample_tokens)/sample/cond/argmax", "sample"),
    ("jit(_unified)/while/body/add", UNSCOPED),
    ("jit(sample_tokens)/jit(unembed_like)/dot_general", UNSCOPED),
    ("", UNSCOPED),
])
def test_the_innermost_part_on_a_path_is_the_instructions(path, part):
    assert part_of_path(path) == part


TEXT = """HloModule jit__unified, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(_unified)/ffn/add"}
}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%t), index=1
  %fusion.20 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_unified)/while/body/PART/mul"}
  %copy.3 = f32[4]{0:T(128)} copy(%fusion.20)
  ROOT %tuple.9 = (s32[], f32[4]{0}) tuple(%gte.1, %copy.3)
}

%cond.3 (t: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.7 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.12 = (s32[], f32[4]{0}) while(%a), condition=%cond.3, body=%body.2, metadata={op_name="jit(_unified)/while"}
  ROOT %fusion.208 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_unified)/unembed/dot_general"}
}
"""


def test_text_is_read_by_computation_and_by_the_instructions_own_line():
    module, parts = parts_of_text(TEXT.replace("PART", "attn_qkv"))
    assert module == "jit__unified"
    # the entry and the loop's body; a fused computation's instructions and
    # what never runs as an operation (parameters, tuples) are left out
    assert parts == {"while.12": UNSCOPED, "fusion.208": "unembed",
                     "fusion.20": "attn_qkv", "copy.3": UNSCOPED}


def test_two_signatures_that_disagree_are_ambiguous_and_no_part_is_stale():
    held = ProgramParts()
    held.add(TEXT.replace("PART", "attn_qkv"))
    held.add(TEXT.replace("PART", "attn_out"))
    assert held.maps["jit__unified"]["fusion.20"] == AMBIGUOUS
    assert held.maps["jit__unified"]["fusion.208"] == "unembed"
    assert held.signatures == {"jit__unified": 2} and not held.stale
    assert {ls["stale"] for ls, _ in held.series()} == {"0"}
    bare = ProgramParts()  # an executable whose metadata names no part
    bare.add(TEXT.replace("PART", "x").replace("unembed/", ""))
    assert bare.stale == {"jit__unified"}
    assert {ls["stale"] for ls, _ in bare.series()} == {"1"}


# ------------------------------------------- copies of a layer of a leaf

# cut from the compiled unified program of minicpm-sala-9b as PR 55 traced it
# (chiprun_out/pr54/hlo/minicpm-sala-9b.unified.0.hlo.txt; the cut keeps the
# entry's parameters, the loops, the copies with their consumers and fused
# computations, and three products whose own fusion holds the slice)
CUT = os.path.join(ROOT, "tests", "data",
                   "minicpm-sala-9b.unified.pr54.cut.hlo.txt")
# ISSUE 56's table: instruction -> (the product that reads the copy, or the
# copy behind it). The issue counts thirteen rows; its table names twelve
# instructions, all here, and the text holds two more, the sparse layers'
# ``wk`` and ``wv`` (2 MB each).
TABLE = {
    "constant_dynamic-slice_fusion.26": "mixer_in/nd,ed->ne/dot_general",
    "constant_dynamic-slice_fusion.27": "mixer_in/nd,ed->ne/dot_general",
    "constant_dynamic-slice_fusion.28": "mixer_in/nd,ed->ne/dot_general",
    "constant_dynamic-slice_fusion.29": "mixer_out/nd,ed->ne/dot_general",
    "slice.105": "mixer_in/nd,ed->ne/dot_general",
    "slice.99": "mixer_in/nd,ed->ne/dot_general",
    "slice.103": "mixer_in/nd,ed->ne/dot_general",
    "slice.107": "mixer_out/nd,ed->ne/dot_general",
    "constant_dynamic-slice_fusion.34": "attn_qkv/nd,dhk->nhk/dot_general",
    "constant_dynamic-slice_fusion.31": "copy.499",
    "copy.499": "attn_out/nd,dhk->nhk/dot_general",
    "constant_dynamic-slice_fusion.30": "attn_out/nk,kd->nd/dot_general",
    "constant_dynamic-slice_fusion.32": "attn_qkv/nd,dhk->nhk/dot_general",
    "constant_dynamic-slice_fusion.33": "attn_qkv/nd,dhk->nhk/dot_general",
}


@functools.lru_cache(maxsize=None)
def _cut_rows() -> dict:
    with open(CUT) as f:
        return {r["instruction"]: r for r in weight_copies(f.read())}


@pytest.mark.parametrize("instruction", sorted(TABLE))
def test_a_copy_of_a_layers_matrix_is_found_with_its_consumer(instruction):
    row = _cut_rows()[instruction]
    small = instruction[-3:] in (".32", ".33")
    assert row["bytes"] == (2 if small else 32) * 2**20 and not row["async"]
    assert ("wk" if small else "lin_wq") in row["leaves"]
    (consumer,) = row["consumers"]
    assert TABLE[instruction] in (consumer["instruction"],
                                  "/".join(consumer["op_name"].split("/")[-3:]))


def test_a_slice_inside_its_products_fusion_is_no_row():
    with open(CUT) as f:
        text = f.read()
    assert set(_cut_rows()) == set(TABLE)
    # the cut holds products that read the stack in place: the lightning
    # layers' ``lin_wo`` and the MLP's two, a dynamic-slice inside each
    folded = [ln for ln in text.splitlines() if " dynamic-slice(" in ln
              and not any(f"%{name} = " in ln for name in TABLE)]
    assert len([ln for ln in folded if "bf16[1,4096,4096]" in ln]) >= 1
    assert len([ln for ln in folded if "bf16[1,16384,4096]" in ln]) >= 1


LEAF_TEXT = """HloModule jit__unified, is_scheduled=true

%fused_slice.1 (p: bf16[4,8,8], i: s32[]) -> bf16[1,8,8] {
  %p = bf16[4,8,8]{2,1,0} parameter(0)
  %i = s32[] parameter(1)
  %z = s32[] constant(0)
  ROOT %ds.1 = bf16[1,8,8]{2,1,0} dynamic-slice(%p, %i, %z, %z), dynamic_slice_sizes={1,8,8}
}

%fused_product.2 (p: bf16[4,8,8], i: s32[], x: bf16[2,8]) -> bf16[2,8] {
  %p.1 = bf16[4,8,8]{2,1,0} parameter(0)
  %i.1 = s32[] parameter(1)
  %x.1 = bf16[2,8]{1,0} parameter(2)
  %z.1 = s32[] constant(0)
  %ds.2 = bf16[1,8,8]{2,1,0} dynamic-slice(%p.1, %i.1, %z.1, %z.1), dynamic_slice_sizes={1,8,8}
  %b.2 = bf16[8,8]{1,0} bitcast(%ds.2)
  ROOT %conv.2 = bf16[2,8]{1,0} convolution(%x.1, %b.2), dim_labels=bf_io->bf
}

%fused_scale.3 (a: bf16[2,2,2,8]) -> bf16[2,2,2,8] {
  %a = bf16[2,2,2,8]{3,2,1,0} parameter(0)
  ROOT %m.3 = bf16[2,2,2,8]{3,2,1,0} multiply(%a, %a)
}

ENTRY %main.9 (w: bf16[4,8,8], v: bf16[4,8], i: s32[], x: bf16[2,8], pool: bf16[16,4]) -> bf16[2,8] {
  %w = bf16[4,8,8]{2,1,0} parameter(0), metadata={op_name="params[\'lin_wq\']"}
  %v = bf16[4,8]{1,0} parameter(1), metadata={op_name="params[\'attn_norm\']"}
  %i.9 = s32[] parameter(2), metadata={op_name="i"}
  %x = bf16[2,8]{1,0} parameter(3), metadata={op_name="x"}
  %pool = bf16[16,4]{1,0} parameter(4), metadata={op_name="cache[\'kv\']"}
  %fusion.1 = bf16[1,8,8]{2,1,0:S(1)} fusion(%w, %i.9), kind=kLoop, calls=%fused_slice.1, metadata={op_name="jit(_unified)/dynamic_slice"}
  %bitcast.1 = bf16[2,4,8]{2,1,0} bitcast(%fusion.1)
  %copy.1 = bf16[2,4,8]{0,2,1} copy(%bitcast.1)
  %fusion.2 = bf16[2,8]{1,0} fusion(%w, %i.9, %x), kind=kOutput, calls=%fused_product.2, metadata={op_name="jit(_unified)/mixer_out/ne,ed->nd/dot_general"}
  %scaled.3 = bf16[2,2,2,8]{3,2,1,0} fusion(%copy.1), kind=kLoop, calls=%fused_scale.3, metadata={op_name="jit(_unified)/mixer_in/mul"}
  %copy.2 = bf16[16,4]{0,1} copy(%pool)
  %slice.4 = bf16[1,8]{1,0} slice(%v), slice={[1:2], [0:8]}
  %start.5 = (bf16[4,8,8], bf16[1,8,8]) slice-start(%w), slice={[2:3], [0:8], [0:8]}
  %done.5 = bf16[1,8,8]{2,1,0:S(1)} slice-done(%start.5)
  ROOT %add.6 = bf16[2,8]{1,0} add(%fusion.2, %fusion.2)
}
"""


def test_what_counts_as_a_copy_of_a_layer_and_what_does_not():
    rows = {r["instruction"]: r for r in weight_copies(LEAF_TEXT)}
    # the slice as a fusion of its own, the relayout of its view behind it
    # (consumers are read through bitcasts), and a slice the compiler runs
    # beside other work; not the product that holds its slice, not
    # arithmetic on a value of a layer's size, not a vector's slice, not a
    # pool of as many elements
    assert set(rows) == {"fusion.1", "copy.1", "done.5"}
    assert [c["instruction"] for c in rows["fusion.1"]["consumers"]] == [
        "copy.1"]
    assert rows["copy.1"]["consumers"] == [{
        "instruction": "scaled.3", "opcode": "fusion",
        "op_name": "jit(_unified)/mixer_in/mul"}]
    assert rows["done.5"]["async"] and not rows["fusion.1"]["async"]
    assert all(r["leaves"] == ["lin_wq"] and r["bytes"] == 128
               for r in rows.values())


# ------------------------------------------------------------- the reader

@pytest.mark.parametrize("trace_name,instruction", [
    ("fusion.20_bf16_64_128_", "fusion.20"),
    ("fusion.208_bf16_64_128_", "fusion.208"),
    ("fusion.1_f32_64_64_..", "fusion.1"),          # a tuple's result
    ("while.12_s32__..", "while.12"),               # a scalar's: no dims
    ("multiply_add_fusion.2_bf16_256_1536_", "multiply_add_fusion.2"),
    ("copy.91_s8_1_4096_32_128_", "copy.91"),
    ("%x.1 = opaque thing", "x.1"),                 # a name kept whole
])
def test_the_instruction_of_a_trace_name(trace_name, instruction):
    assert step_parts.instruction(trace_name) == instruction


def _series(program, by_part, stale="0"):
    return [(SERIES, {"program": program, "part": part, "stale": stale,
                      "ops": " ".join(ops)}, float(len(ops)))
            for part, ops in by_part.items()]


def _ctx(samples, ops, modules):
    def sec(d):
        return {n: {"count": 1, "seconds": s} for n, s in d.items()}
    return {"after": {"engine": samples},
            "trace": {"ops": sec(ops), "busy_s": sum(ops.values()),
                      "modules": {m: {"count": 1, "seconds": sum(d.values()),
                                      "ops": sec(d)}
                                  for m, d in modules.items()}}}


def test_a_name_two_programs_share():
    """Booked whole where they agree; where they do not, each program's
    seconds from its own list and the rest unscoped; ``fusion.20`` does not
    take ``fusion.208``'s seconds; a module with no map is unscoped."""
    samples = (_series("jit__unified", {"ffn": ["fusion.20", "fusion.7"],
                                        "norm": ["fusion.9"]})
               + _series("jit__decode_multi", {"ffn": ["fusion.7"],
                                               "unembed": ["fusion.9"],
                                               "attn": ["fusion.208"]}))
    ops = {"fusion.20_bf16_64_128_": 1.0, "fusion.208_bf16_64_128_": 2.0,
           "fusion.7_bf16_8_": 4.0, "fusion.9_f32_8_": 8.0,
           "fusion.33_f32_8_": 16.0}
    modules = {"jit__unified": {"fusion.20_bf16_64_128_": 1.0,
                                "fusion.7_bf16_8_": 3.0,
                                "fusion.9_f32_8_": 5.0},
               "jit__decode_multi": {"fusion.208_bf16_64_128_": 2.0,
                                     "fusion.7_bf16_8_": 1.0,
                                     "fusion.9_f32_8_": 2.0},
               "jit_helper": {"fusion.33_f32_8_": 16.0}}
    unscoped: dict = {}
    by = step_parts.seconds_by_part(_ctx(samples, ops, modules), unscoped)
    assert by == {"ffn": 5.0, "attn": 2.0, "norm": 5.0, "unembed": 2.0,
                  "unscoped": 17.0}
    assert unscoped == {"fusion.9_f32_8_": 1.0, "fusion.33_f32_8_": 16.0}
    assert sum(by.values()) == sum(ops.values())


@pytest.mark.parametrize("samples", [
    [], [("llmd_tpu:program_compiles_total", {"program": "unified"}, 2.0)],
    _series("jit__unified", {"ffn": ["fusion.20"]}, stale="1"),
    _series("jit__unified", {"ffn": ["fusion.20"]})
    + _series("jit__decode_multi", {"unscoped": ["fusion.3"]}, stale="1"),
], ids=["no-scrape", "no-series", "stale", "one-program-stale"])
def test_no_map_or_a_stale_one_gives_none(samples, capsys):
    ctx = _ctx(samples, {"fusion.20_bf16_64_128_": 1.0}, {})
    assert step_parts.seconds_by_part(ctx) is None
    assert step_parts.roofline({"parts": ["ffn"]}, ctx) is None
    assert "device_time_by_part" not in capsys.readouterr().out


def test_a_share_is_told_once_and_is_zero_where_no_part_ran(capsys):
    ctx = _ctx(_series("jit__unified", {"ffn": ["fusion.20"]}),
               {"fusion.20_bf16_64_128_": 1.0, "copy.1_f32_8_": 3.0},
               {"jit__unified": {"fusion.20_bf16_64_128_": 1.0,
                                 "copy.1_f32_8_": 3.0}})
    assert step_parts.roofline({"parts": ["ffn"]}, ctx) == 0.25
    assert step_parts.roofline({"parts": ["unscoped", "ambiguous"]},
                               ctx) == 0.75
    assert step_parts.roofline({"parts": ["sparse_select"]}, ctx) == 0.0
    told = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [t["note"] for t in told] == ["device_time_by_part"]
    assert told[0]["seconds"] == {"ffn": 1.0, "unscoped": 3.0}
    assert told[0]["unscoped_top"] == [["copy.1_f32_8_", 3.0]]


RECORDED = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
NEW_METRICS = sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
    if "step_parts" in open(os.path.join(BENCH, "metrics", f)).read()
    or f[:-5].removesuffix(".tpot") in (
        "ffn_dev_share", "attn_proj_dev_share", "unembed_dev_share",
        "unscoped_dev_share", "moe_route_dev_share"))


@functools.lru_cache(maxsize=None)
def _recorded():
    return xplane.reduce(xplane.read(RECORDED))


def test_parts_and_unscoped_sum_to_the_recorded_traces_seconds():
    """On the recorded trace (the unified step and the sampler's helpers of
    an engine older than the map): a map of the unified step's operations by
    an arbitrary rule, nothing for the helpers' modules."""
    tr = _recorded()
    names = sorted(tr["modules"]["jit__unified"]["ops"])
    by_part: dict = {}
    for i, n in enumerate(names):
        by_part.setdefault(MODEL_PARTS[i % 5], []).append(
            step_parts.instruction(n))
    ctx = {"after": {"engine": _series("jit__unified", by_part)},
           "trace": tr}
    by = step_parts.seconds_by_part(ctx)
    total = sum(o["seconds"] for o in tr["ops"].values())
    assert sum(by.values()) == pytest.approx(total, rel=1e-9)
    assert total == pytest.approx(tr["busy_s"], rel=0.01)
    assert by["unscoped"] > 0 and len(by) == 6


def test_the_metric_files_are_the_issues_twelve():
    assert len(NEW_METRICS) == 12
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_METRICS:
        m = readers.load(name)
        entry = listed[name]
        assert entry["workloads"] and entry["source"] == "device_trace"
        assert {k: m[k] for k in ("unit", "better", "layer", "moves")} == \
            {k: entry[k] for k in ("unit", "better", "layer", "moves")}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_read_against_the_parents_scrape_is_none(name, capsys):
    """The parent commit's program publishes no map: with a trace to read
    and a scrape of the parent's kind, every new metric is None, nothing is
    raised and nothing is told."""
    parent = [("llmd_tpu:program_compiles_total", {"program": "unified"}, 2.0),
              ("llmd_tpu:engine_steps_total", {}, 100.0)]
    ctx = {"before": {"engine": parent}, "after": {"engine": parent},
           "polls": {"engine": []}, "trace": _recorded(), "gen": {},
           "device": {}, "config": {}}
    assert readers.read(readers.load(name)["reads"], ctx) is None
    assert readers.read(readers.load(name)["reads"],
                        dict(ctx, trace=None)) is None
    assert capsys.readouterr().out == ""


def test_scopes_are_metadata_the_vocabulary_is_one():
    """``MODEL_PARTS`` is defined once (models/parts.py) and is what the
    step programs' module re-exports; the two scopes that named no part are
    gone from the model's source."""
    from llmd_tpu.models import parts, transformer

    assert MODEL_PARTS is parts.MODEL_PARTS and len(set(MODEL_PARTS)) == 17
    src = open(transformer.__file__).read()
    assert "leading_dense_layers\")" not in src
    assert "named_scope(\"expert_layers\")" not in src
    with pytest.raises(AssertionError):
        parts.part("expert_layers")
