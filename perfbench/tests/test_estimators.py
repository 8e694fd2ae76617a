"""The end-to-end arithmetic: a rate over all the tokens and all the time of
the window, and percentiles that failed requests cannot improve. And two
pieces of the per-layer arithmetic that every attention roofline and both
fused-step times stand on: the cached tokens a call has to read, each
distinct one once, and the steps a fused call ran."""

import json
import math
import os
import random

import pytest

import estimators as est
import readers
from conftest import BENCH
from kernels import cached_tokens as ct
from kernels import mixed_window_attention as mwa
from kernels import mla_attention as mla
from kernels import ragged_paged_attention as rpa


@pytest.mark.parametrize("phase", [0.0, 0.07, 0.21, 0.33, 0.5, 0.58])
@pytest.mark.parametrize("window", [40.0, 50.0])
def test_rate_on_lumps_is_within_a_lump_whatever_the_phase(phase, window):
    """One fused call of 32 steps at 64 seats reports 2,048 tokens at once:
    all tokens over all the window is the true rate to within one lump over
    the window's tokens, wherever the window's edges fall."""
    period, lump = 0.584, 2048
    events = [(phase + i * period, lump) for i in range(200)]
    rate, n = est.window_rate(events, 10.0, 10.0 + window)
    true = lump / period
    assert abs(rate - true) / true <= period / window
    assert n == round(rate * window / lump)


def test_rate_is_all_tokens_over_all_the_window():
    events = [(0.01 * i, 3) for i in range(1000)]
    rate, n = est.window_rate(events, 1.0, 9.0)
    assert rate == pytest.approx(300.0) and n == 800
    # a stall at either edge, or in the middle, costs what it cost
    stalled = [e for e in events if not 1.0 <= e[0] < 3.0]
    assert est.window_rate(stalled, 1.0, 9.0)[0] == pytest.approx(225.0)
    late = [e for e in events if not 4.0 <= e[0] < 6.0]
    assert est.window_rate(late, 1.0, 9.0)[0] == pytest.approx(225.0)
    assert est.window_rate([], 0.0, 1.0) == (0.0, 0)
    # the end is outside the window, the start inside
    assert est.window_rate([(0.0, 5), (1.0, 7)], 0.0, 1.0) == (5.0, 1)


def test_percentiles_keep_failed_requests_at_infinity():
    ok = [float(i) for i in range(1, 96)]
    assert est.percentile(ok, 95) == 91.0
    with_failed = ok + [est.INF] * 5
    assert est.percentile(with_failed, 95) == 95.0
    assert math.isinf(est.percentile(ok + [est.INF] * 6, 95))
    assert est.percentile(with_failed, 50) == 50.0
    assert est.percentile([], 95) is None
    assert est.finite(est.INF) == 1e9 and est.finite(3.5) == 3.5


def test_tpot_is_a_mean_over_the_request():
    # 128 tokens in four lumps of 32: the gaps are zeros and stalls, the
    # mean is what the user saw
    assert est.tpot_ms(1.0, 1.0 + 3 * 0.584, 128) == \
        pytest.approx(3 * 584 / 127)
    assert est.tpot_ms(1.0, 1.0, 1) is None


# ------------------------------------------ the cached tokens a call reads

DOC = [(16384 + 1000, 7, 16384)] * 4  # four rows behind one tenant's document
LONE = [(c, None, 0) for c in (17384, 900, 5000, 4096)]  # no sessions
SPLIT = [(17384, t, 16384) for t in range(4)]  # a tenant each: none shared


@pytest.mark.parametrize("rows, window, per_row, unique", [
    # (a) the document once and four tails; operations keep 4 x 17,384 pairs
    (DOC, 0, 4 * 17384, 16384 + 4000),
    # a window layer: each row reads (13288, 17384], of it (13288, 16384]
    # is the document's and comes once
    (DOC, 4096, 4 * 4096, 3096 + 4000),
    # rows at unequal progress: the document's part of the earliest window on
    (DOC[:2] + [(16384 + 3000, 7, 16384), (16384 + 5000, 7, 16384)], 4096,
     4 * 4096, 3096 + 2 * 1000 + 3000 + 4096),
    # (b) nothing shared: every token is distinct, once a row is the count
    (LONE, 0, 27380, 27380),
    (LONE, 4096, 4096 + 900 + 4096 + 4096, 4096 + 900 + 4096 + 4096),
    (SPLIT, 0, 4 * 17384, 4 * 17384),
    (SPLIT, 4096, 4 * 4096, 4 * 4096),
    ([], 0, 0, 0),
])
def test_distinct_cached_tokens_once(rows, window, per_row, unique):
    assert ct.row_tokens(rows, window) == per_row
    assert ct.unique_tokens(rows, window) == unique
    n = max(1, len(rows))
    means = ct.decode_means({"decode_rows": [rows, rows]}, window)
    assert means == ((per_row, unique, n) if rows else None)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("window", [0, 512, 4096])
def test_the_distinct_count_never_passes_the_count_a_row(seed, window):
    """(c) whatever the rows: and it is no less than the longest row's."""
    rng = random.Random(seed)
    rows = [(rng.randrange(1, 20000), rng.choice([None, 0, 1, 2]),
             rng.choice([0, 2048, 6144, 16384])) for _ in range(40)]
    u, s = ct.unique_tokens(rows, window), ct.row_tokens(rows, window)
    assert ct.row_tokens(rows[:1], window) <= \
        max(ct.row_tokens([r], window) for r in rows) <= u <= s
    # a tenant a row shares nothing, whatever the lengths
    apart = [(c, i, sh) for i, (c, _, sh) in enumerate(rows)]
    assert ct.unique_tokens(apart, window) == s


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


with open(os.path.join(BENCH, "peaks.json")) as _f:
    PEAKS = json.load(_f)["TPU v5 lite"]
CASES = {
    "gqa": (rpa, _conf("qwen2.5-1.5b"), "ragged_paged_attention",
            "ragged_paged_attention_kernel.6"),
    "window": (mwa, _conf("smallthinker-21b-a3b"), "mixed_window_attention",
               "ragged_paged_attention_kernel.6"),
    "latent": (mla, _conf("glm-4.7-flash"), "mla_attention",
               "mla_ragged_paged_attention.23"),
}


def _decode_ctx(conf, op, rows=None, gen=None, seconds=1.0, calls=8):
    ctx = {"gen": gen or {}, "config": conf,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"modules": {"jit__decode_multi": {"ops": {
               op: {"count": calls, "seconds": seconds}}}}}}
    if rows is not None:
        ctx["decode_rows"] = [rows]
    return ctx


@pytest.mark.parametrize("case", sorted(CASES))
def test_unshared_rows_read_what_the_means_alone_read(case):
    """(b) for every estimator that takes the fact: rows that share nothing
    give exactly the number that the client's means gave before there were
    rows (contexts past the window, as the window layers' old count asks)."""
    mod, conf, kernel, op = CASES[case]
    src = {"kind": "kernel_roofline", "kernel": kernel, "pattern": op[:10],
           "module": "decode"}
    rows = [(6000 + 37 * i, None, 0) for i in range(64)]
    S = float(sum(c for c, _, _ in rows))
    old = mod.roofline(src, _decode_ctx(conf, op, gen={
        "decode_ctx_tokens_mean": S, "decoding_mean": 64.0}))
    assert old and mod.roofline(src, _decode_ctx(conf, op, rows)) == old
    # a tenant each: still nothing shared
    apart = [(c, i, 2048) for i, (c, _, _) in enumerate(rows)]
    assert mod.roofline(src, _decode_ctx(conf, op, apart)) == old


def test_four_rows_behind_one_document_bytes_once_operations_a_row():
    """(a) through the latent estimator and the GQA one: 16,384 + 4,000
    tokens of bytes, 4 x 17,384 pairs of operations."""
    _, conf, kernel, op = CASES["latent"]
    ops, byts = mla.cost(4 * 17384, 4, 4 * 17384, 20, 512, 64,
                         unique_ctx=16384 + 4000)
    assert ops == 2 * 20 * 1088 * 4 * 17384
    assert byts == ((16384 + 4000) * 576 + 4 * 20 * 1088) * 2
    src = {"kind": "kernel_roofline", "kernel": kernel, "module": "decode"}
    least = mla.least_seconds(ops, byts, PEAKS)
    got = mla.roofline(src, _decode_ctx(conf, op, DOC, seconds=8 * least))
    assert got == pytest.approx(1.0)
    # a kernel that fetches the document once a row, at the memory's rate
    a_row = mla.least_seconds(*mla.cost(4 * 17384, 4, 4 * 17384, 20, 512, 64),
                              PEAKS)
    got = mla.roofline(src, _decode_ctx(conf, op, DOC, seconds=8 * a_row))
    assert got == pytest.approx(least / a_row) and 0.29 < got < 0.30
    ops, byts = rpa.cost(4 * 17384, 4, 12, 2, 128, unique_ctx=16384 + 4000)
    assert ops == 4 * 12 * 128 * 4 * 17384
    assert byts == (16384 + 4000) * 2 * 2 * 128 * 2 + 2 * 4 * 12 * 128 * 2


def test_the_unified_steps_bytes_follow_the_clients_share():
    """The program's context tokens are once a row; the bytes take the
    client's share of distinct tokens among the decoding rows, the operations
    every pair."""
    _, conf, kernel, op = CASES["latent"]
    src = {"kind": "kernel_roofline", "kernel": kernel, "module": "unified"}
    q, kv = 2.0, 17384.0  # few queries: bound by bytes either way
    pairs = 3 * kv + q * kv - q * (q - 1) / 2
    counters = {"engine_program_dispatches_total": 10.0,
                "program_kv_read_tokens_total": 10 * 4 * kv,
                "attn_query_tokens_total": 10 * (3 + q),
                "attn_query_key_pairs_total": 10 * pairs}

    def ctx(rows, seconds):
        c = _decode_ctx(conf, op, rows, seconds=seconds, calls=6)
        c["trace"]["modules"]["jit__unified"] = \
            c["trace"]["modules"].pop("jit__decode_multi")
        for when, k in (("before", 1.0), ("after", 3.0)):
            c[when] = {"engine": [("llmd_tpu:" + n, {"program": "unified"},
                                   v * k) for n, v in counters.items()]}
        return c

    share = (16384 + 4000) / (4 * 17384)
    ops, byts = mla.cost(4 * kv, 3 + q, pairs, 20, 512, 64,
                         unique_ctx=4 * kv * share)
    least = mla.least_seconds(ops, byts, PEAKS)
    assert mla.roofline(src, ctx(DOC, 6 * least)) == pytest.approx(1.0)
    unshared = mla.least_seconds(
        *mla.cost(4 * kv, 3 + q, pairs, 20, 512, 64), PEAKS)
    assert mla.roofline(src, ctx(SPLIT, 6 * unshared)) == pytest.approx(1.0)
    assert least < unshared


def test_a_window_layer_shares_only_what_its_windows_hold():
    conf = CASES["window"][1]
    rows = [(6144 + 200 + 400 * i, i % 2, 6144) for i in range(8)]
    ctx = {"decode_rows": [rows]}
    full, window = mwa.demand_seconds(ctx, conf, PEAKS)
    shape = (28, 4, 128)
    S = sum(c for c, _, _ in rows)
    own = S - 8 * 6144
    assert full == rpa.least_seconds(*rpa.cost(
        S, 8, *shape, unique_ctx=2 * 6144 + own), PEAKS)
    # tenant 0's shortest row is at 6,344 (window from 2,248), tenant 1's at
    # 6,744 (from 2,648): the prompt from there on once, the tails a row
    in_window = (6144 - 2248) + (6144 - 2648) + own
    assert window == rpa.least_seconds(*rpa.cost(
        8 * 4096, 8, *shape, unique_ctx=in_window), PEAKS)
    assert mwa.demand_seconds({"decode_rows": [[]], "gen": {}}, conf,
                              PEAKS) is None


# ------------------------------------------------- the steps a call ran

def test_a_fused_step_is_the_calls_time_over_the_steps_they_ran():
    """(d) three executions of 6, 4 and 14 steps: the head's logits ran 24
    times inside them, and a step is the module's time over 24, whatever the
    cap (``engine.decode_steps`` 32) and whatever else shares the shape."""
    src = readers.load("decode_step_dev_ms")["reads"]
    conf = _conf("qwen2.5-1.5b")
    assert conf["engine"]["decode_steps"] == 32
    step = 0.009
    mod = {"count": 3, "seconds": 24 * step, "whole": 3,
           "whole_seconds": 24 * step, "ops": {
               "ragged_paged_attention_kernel.6_bf16_64_12_128_":
                   {"count": 24 * 28, "seconds": 0.1},
               "fusion.187_f32_64_151936_": {"count": 24, "seconds": 0.01},
               "fusion.190_f32_64_151936_": {"count": 10, "seconds": 0.001},
               "fusion.185_s32_64_": {"count": 24, "seconds": 0.0001},
               "copy.24_bf16_28_1536_2_128_": {"count": 3, "seconds": 0.0}}}
    ctx = {"config": conf, "gen": {}, "trace": {
        "ops": {}, "modules": {"jit__decode_multi": mod,
                               "jit__unified": {"count": 9, "seconds": 0.1,
                                                "ops": {}}}}}
    assert readers.read(src, ctx) == pytest.approx(1000 * step)
    assert readers.read(src, ctx) != pytest.approx(1000 * 24 * step / 3 / 32)
    # the same reader under the recurrent cell's name
    ssm = readers.load("ssm_decode_step_dev_ms")["reads"]
    assert readers.read(ssm, ctx) == pytest.approx(1000 * step)
    # no head operation in the capture (another vocabulary): nothing to read
    other = dict(ctx, config=dict(conf, vocab_size=32768))
    assert readers.read(src, other) is None
    del ctx["trace"]["modules"]["jit__decode_multi"]
    assert readers.read(src, ctx) is None
