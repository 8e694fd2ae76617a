"""Token-sorted drop-free MoE dispatch (ops/moe_dispatch) vs the legacy
capacity einsum in models.transformer.moe_block.

Routing (softmax, top-k, renorm, EPLB replica choice) lives in moe_block for
BOTH paths, so at a capacity factor generous enough that the einsum keeps
every routed token the two paths compute the same function — parity is exact
up to summation order. The suite pins that parity across the feature matrix
(EPLB, int8 banks, token_mask padding, DBO), the drop-free property where the
legacy path provably drops, recompile-free EPLB rebalance on the engine, and
the ep-axis all_to_all exchange on the 8-device virtual mesh."""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _moe_inputs(seed=0, T=16, dtype=jnp.float32):
    from llmd_tpu.models import get_model_config

    cfg = get_model_config("tiny-moe")
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    D, E, Fe = cfg.hidden_size, cfg.moe_num_experts, cfg.moe_intermediate_size
    x = jax.random.normal(k1, (T, D), dtype)
    router = jax.random.normal(k2, (D, E), jnp.float32) * 0.1
    wi = jax.random.normal(k3, (E, D, 2 * Fe), dtype) * 0.05
    wo = jax.random.normal(k4, (E, Fe, D), dtype) * 0.05
    return cfg, x, router, wi, wo


def _both_paths(cfg, x, router, wi, wo, **kw):
    """(y_einsum, y_sorted) at identical routing decisions."""
    from llmd_tpu.models.transformer import moe_block
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

    y0, _ = moe_block(cfg, x, router, wi, wo, **kw)
    y1, _ = moe_block(cfg, x, router, wi, wo,
                      dispatch_impl=make_sorted_dispatch(), **kw)
    return np.asarray(y0), np.asarray(y1)


# ------------------------------------------------------------------- parity


def test_sorted_matches_einsum_fp32():
    cfg, x, router, wi, wo = _moe_inputs()
    cfg = replace(cfg, moe_capacity_factor=8.0)  # einsum keeps every token
    y0, y1 = _both_paths(cfg, x, router, wi, wo)
    np.testing.assert_allclose(y0, y1, rtol=0, atol=2e-6)


def test_sorted_matches_einsum_bf16():
    cfg, x, router, wi, wo = _moe_inputs(dtype=jnp.bfloat16)
    cfg = replace(cfg, moe_capacity_factor=8.0, dtype="bfloat16")
    y0, y1 = _both_paths(cfg, x, router, wi, wo)
    np.testing.assert_allclose(y0.astype(np.float32), y1.astype(np.float32),
                               rtol=0, atol=3e-2)


def test_sorted_matches_einsum_with_eplb():
    """EPLB replica choice feeds the sort key: both paths see the same
    physical slot ids, so redundant-expert placement preserves parity."""
    from llmd_tpu.parallel.eplb import rebalance

    cfg, x, router, wi, wo = _moe_inputs(T=32)
    cfg = replace(cfg, moe_capacity_factor=8.0)
    E = cfg.moe_num_experts
    loads = np.ones((1, E), np.int64)
    loads[0, 0] = 100  # hot expert gets the redundant slots
    s2e, slots, counts = rebalance(loads, E + 4, ep_size=4)
    eplb = (jnp.asarray(slots[0]), jnp.asarray(counts[0]))
    y0, y1 = _both_paths(cfg, x, router, wi[s2e[0]], wo[s2e[0]], eplb=eplb)
    np.testing.assert_allclose(y0, y1, rtol=0, atol=2e-6)


def test_sorted_matches_einsum_int8_banks():
    """Per-slot per-out-channel int8 scales gather with the bank on the
    sorted path exactly as they broadcast on the einsum path."""
    cfg, x, router, wi, wo = _moe_inputs()
    cfg = replace(cfg, moe_capacity_factor=8.0)
    E, Fe, D = cfg.moe_num_experts, cfg.moe_intermediate_size, cfg.hidden_size
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    wi_q = jax.random.randint(k1, wi.shape, -127, 128, jnp.int8)
    wo_q = jax.random.randint(k2, wo.shape, -127, 128, jnp.int8)
    # realistic per-channel scales (amax/127 at weight std 0.05) keep the
    # activations O(1); the paths differ only in summation order, so the
    # residual is relative
    wi_s = jnp.full((E, 2 * Fe), 4e-4, jnp.float32)
    wo_s = jnp.full((E, D), 4e-4, jnp.float32)
    y0, y1 = _both_paths(cfg, x, router, wi_q, wo_q,
                         wi_scale=wi_s, wo_scale=wo_s)
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)


def test_sorted_matches_einsum_with_token_mask():
    """Masked (padding) tokens consume no capacity on either path and the
    outputs agree row for row — including the masked rows."""
    cfg, x, router, wi, wo = _moe_inputs(T=16)
    cfg = replace(cfg, moe_capacity_factor=8.0)
    mask = jnp.asarray(np.arange(16) % 3 != 0, jnp.bool_)
    y0, y1 = _both_paths(cfg, x, router, wi, wo, token_mask=mask)
    np.testing.assert_allclose(y0, y1, rtol=0, atol=2e-6)


def test_sorted_matches_einsum_with_dbo():
    """moe_dbo halves the batch upstream of dispatch_impl: both halves run
    the sorted path independently and concatenate to the full-batch answer."""
    cfg, x, router, wi, wo = _moe_inputs(T=32)
    cfg = replace(cfg, moe_capacity_factor=8.0, moe_dbo=True)
    y0, y1 = _both_paths(cfg, x, router, wi, wo)
    np.testing.assert_allclose(y0, y1, rtol=0, atol=2e-6)
    cfg_off = replace(cfg, moe_dbo=False)
    _, y1_off = _both_paths(cfg_off, x, router, wi, wo)
    np.testing.assert_allclose(y1, y1_off, rtol=0, atol=2e-6)


def test_sorted_pallas_interpret_matches_xla_backend():
    from llmd_tpu.models.transformer import moe_block
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

    cfg, x, router, wi, wo = _moe_inputs(T=32)
    y0, _ = moe_block(cfg, x, router, wi, wo,
                      dispatch_impl=make_sorted_dispatch())
    y1, _ = moe_block(cfg, x, router, wi, wo,
                      dispatch_impl=make_sorted_dispatch(use_pallas=True,
                                                         interpret=True))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------- the ragged grouped GEMM's fetch plan

# (bc, copies by slot) of one layer's plan: a decode-like one (few copies a
# slot, blocks of 8 rows) and a chunk-like one (blocks of 32). Both hold a
# slot no copy reached, a slot of more than bc copies (two adjacent blocks)
# and, the worst-case padding being what it is, a run of padding blocks at
# the end; in the third the last slots are the unrouted ones, so the padding
# run follows a real block that is not the last slot's.
RAGGED_PLANS = {
    "decode-like": (8, [3, 0, 19, 8, 1, 5]),
    "chunk-like": (32, [24, 40, 0, 31, 70, 7]),
    "last-slots-unrouted": (8, [9, 2, 0, 11, 0, 0]),
}


@pytest.mark.parametrize("bf", [None, 128])  # the rule's tile (whole F); two tiles
@pytest.mark.parametrize("slot_offset", [0, 12])  # into a stack of 3 layers' banks
@pytest.mark.parametrize("plan", sorted(RAGGED_PLANS))
def test_ragged_grouped_gemm_matches_the_gathered_einsum(plan, slot_offset, bf):
    """The Pallas kernel (interpret mode) against `_experts_xla` on the same
    blocks, both banks and the activation between them; and the fetch plan the
    host books from the counts against the plan the kernel is handed."""
    from llmd_tpu.ops import moe_dispatch as md
    from llmd_tpu.ops.grouped_gemm import bank_fetch_plan, ragged_grouped_gemm

    bc, counts = RAGGED_PLANS[plan]
    S, D, Fe = len(counts), 32, 128
    rng = np.random.default_rng(sum(counts))
    slot = np.repeat(np.arange(S + 1), counts + [5]).astype(np.int32)  # 5 sentinels
    rng.shuffle(slot)
    row, block_slot, block_rows, Tp = md._row_plan(jnp.asarray(slot), S, bc)
    nb = Tp // bc
    assert nb == md.plan_blocks(len(slot), S, bc)
    xr = rng.standard_normal((len(slot), D)).astype(np.float32)
    xs = jnp.zeros((Tp, D), jnp.float32).at[row].set(jnp.asarray(xr),
                                                     mode="drop")
    wi = jnp.asarray(rng.standard_normal((3 * S, D, 2 * Fe)), jnp.float32) * 0.1
    wo = jnp.asarray(rng.standard_normal((3 * S, Fe, D)), jnp.float32) * 0.1
    xb, slots = xs.reshape(nb, bc, D), block_slot + slot_offset

    want = md._experts_xla(xb, slots, block_rows, wi, wo, None, None,
                           act=jax.nn.relu)
    gate_up = ragged_grouped_gemm(xb, wi, slots, block_rows, interpret=True,
                                  bf=bf)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    got = ragged_grouped_gemm(jax.nn.relu(gate) * up, wo, slots, block_rows,
                              interpret=True, bf=None if bf is None else 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    rows = np.asarray(block_rows)
    assert not np.asarray(got)[rows == 0].any()  # padding blocks: zeros

    # the fetch plan: for a sorted block_slot the fetches are its runs over
    # the blocks that hold rows, padding adds none, the three sum to nb
    fetch, reuse, padding = bank_fetch_plan(counts, bc, nb)
    real = np.asarray(block_slot)[rows > 0]
    assert (np.diff(real) >= 0).all()
    assert fetch == 1 + int((np.diff(real) != 0).sum()) == sum(c > 0 for c in counts)
    assert reuse == len(real) - fetch and reuse >= 1
    assert padding == int((rows == 0).sum()) and padding >= 2
    assert fetch + reuse + padding == nb
    assert (rows[len(real):] == 0).all()  # the padding blocks are one run at the end


def test_bank_fetch_plan_sums_over_layers_and_tile_rule_reads_shapes_only():
    from llmd_tpu.ops.grouped_gemm import (RGG_TILE_BUDGET, bank_fetch_plan,
                                           pick_bank_tile, rgg_vmem_bytes)

    a, b = RAGGED_PLANS["decode-like"][1], RAGGED_PLANS["last-slots-unrouted"][1]
    one = [bank_fetch_plan(c, 8, 12) for c in (a, b)]
    assert bank_fetch_plan(np.asarray([a, b]), 8, 12) == tuple(
        map(sum, zip(*one)))
    assert bank_fetch_plan(np.zeros((2, 6), np.int32), 8, 12) == (0, 0, 24)
    # the cell's four calls take the whole F: one DMA an expert
    for bc in (8, 32):
        assert pick_bank_tile(2560, 1536, bc) == 1536
        assert pick_bank_tile(768, 2560, bc) == 2560
    # a bank too wide for the budget: the widest lane-aligned divisor that fits
    bf = pick_bank_tile(8192, 8192, 128)
    assert bf % 128 == 0 and 8192 % bf == 0 and bf < 8192
    assert rgg_vmem_bytes(128, 8192, bf, 2) <= RGG_TILE_BUDGET
    assert rgg_vmem_bytes(128, 8192, 2 * bf, 2) > RGG_TILE_BUDGET
    assert pick_bank_tile(32, 200, 8) == 200  # no multiple of 128 divides it


# ------------------------------------------------------------- drop-free


def test_sorted_drop_free_where_einsum_drops():
    """At a starved capacity factor the legacy path provably drops routed
    copies; the sorted path keeps every one and still matches the
    generous-capacity ground truth."""
    from llmd_tpu.models.transformer import moe_block
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

    cfg, x, router, wi, wo = _moe_inputs(T=32)
    starved = replace(cfg, moe_capacity_factor=0.5)
    y_e, _, drop_e = moe_block(starved, x, router, wi, wo,
                               return_dropped=True)
    assert int(drop_e) > 0, "capacity factor 0.5 dropped nothing on T=32"
    y_s, _, drop_s = moe_block(starved, x, router, wi, wo,
                               dispatch_impl=make_sorted_dispatch(),
                               return_dropped=True)
    assert int(drop_s) == 0
    truth, _ = moe_block(replace(cfg, moe_capacity_factor=8.0),
                         x, router, wi, wo)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(truth),
                               rtol=0, atol=2e-6)
    # and the starved einsum really lost those tokens' contributions
    assert not np.allclose(np.asarray(y_e), np.asarray(truth), atol=1e-4)


def test_einsum_drop_count_is_exact():
    """routed - kept accounting: dropped == sum over slots of
    max(0, routed_to_slot - C), computed from the routing decisions."""
    from llmd_tpu.models.transformer import moe_block

    cfg, x, router, wi, wo = _moe_inputs(T=32)
    cfg = replace(cfg, moe_capacity_factor=0.5)
    k, S = cfg.moe_top_k, cfg.moe_num_experts
    logits = np.asarray(x, np.float32) @ np.asarray(router, np.float32)
    order = np.argsort(-logits, axis=-1)[:, :k]
    C = max(1, int(32 * k / S * cfg.moe_capacity_factor))
    per_slot = np.bincount(order.reshape(-1), minlength=S)
    want = int(np.maximum(0, per_slot - C).sum())
    _, _, dropped = moe_block(cfg, x, router, wi, wo, return_dropped=True)
    assert int(dropped) == want


# ------------------------------------------------------------- block plan


def test_pick_block_size_regimes():
    from llmd_tpu.ops.moe_dispatch import pick_block_size

    # decode: Tk ~ S -> bc == 1 keeps the padded buffer near-dense
    assert pick_block_size(8, 8, pallas=False) == 1
    # prefill: Tk >> S -> MXU-sized blocks, capped at 128
    assert pick_block_size(4096, 8, pallas=False) == 128
    assert pick_block_size(100_000, 8, pallas=False) == 128
    # Pallas tiles need >= 8 sublanes
    assert pick_block_size(8, 8, pallas=True) == 8
    for tk in (1, 7, 64, 513):
        bc = pick_block_size(tk, 16, pallas=False)
        assert bc & (bc - 1) == 0  # power of two


def test_dispatch_stage_places_every_valid_copy():
    """Every valid (token, k) copy lands in a row of its slot's segment;
    sentinels land nowhere; combine inverts the permutation exactly."""
    from llmd_tpu.ops.moe_dispatch import combine_stage, dispatch_stage

    T, D, S, k, bc = 12, 4, 5, 2, 2
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, S, size=(T, k)).astype(np.int32))
    valid = jnp.asarray((rng.random((T, 1)) < 0.8).astype(np.int32))
    topw = jnp.full((T, k), 0.5, jnp.float32)
    xs, row, tok, wf, block_slot, block_rows = dispatch_stage(
        x, idx, topw, valid, S, bc)
    rown, xsn = np.asarray(row), np.asarray(xs)
    Tp = xsn.shape[0]
    slot = np.where(np.asarray(valid) > 0, np.asarray(idx), S).reshape(-1)
    live = slot < S
    # every valid copy has a distinct in-buffer row carrying its token's x
    assert len(set(rown[live].tolist())) == int(live.sum())
    for i in np.nonzero(live)[0]:
        np.testing.assert_array_equal(xsn[rown[i]], np.asarray(x)[i // k])
        # and that row's block belongs to the copy's slot
        assert int(np.asarray(block_slot)[rown[i] // bc]) == slot[i]
    assert np.all(rown[~live] == Tp)  # sentinels scatter off the end
    assert int(np.asarray(block_rows).sum()) == int(live.sum())
    # identity experts -> combine is sum of topw-weighted copies
    y = combine_stage(xs, row, tok, wf, T)
    want = np.zeros((T, D), np.float32)
    for i in np.nonzero(live)[0]:
        want[i // k] += 0.5 * np.asarray(x)[i // k]
    np.testing.assert_allclose(np.asarray(y), want, rtol=0, atol=1e-6)


# ----------------------------------------------------------------- ep axis


def test_ep_all_to_all_matches_local():
    """The bounded-bucket all_to_all exchange over a real (dp=2, ep=4) mesh
    computes the same function as the single-shard sorted path."""
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch
    from llmd_tpu.parallel.mesh import MeshConfig, build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    mesh = build_mesh(MeshConfig(dp=2, ep=4))
    T, D, S, k = 24, 16, 8, 2
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, S, size=(T, k)).astype(np.int32))
    topw = jnp.asarray(rng.random((T, k)).astype(np.float32))
    valid = jnp.asarray((rng.random((T, 1)) < 0.9).astype(np.int32))
    wi = jnp.asarray(rng.normal(size=(S, D, 2 * 8)).astype(np.float32) * 0.1)
    wo = jnp.asarray(rng.normal(size=(S, 8, D)).astype(np.float32) * 0.1)
    y_local = make_sorted_dispatch()(x, idx, topw, valid, wi, wo)
    y_ep = make_sorted_dispatch(mesh)(x, idx, topw, valid, wi, wo)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                               rtol=1e-5, atol=1e-5)


def test_ep_all_to_all_matches_local_int8():
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch
    from llmd_tpu.parallel.mesh import MeshConfig, build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    mesh = build_mesh(MeshConfig(ep=8))
    T, D, S, k = 16, 8, 8, 2
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, S, size=(T, k)).astype(np.int32))
    topw = jnp.full((T, k), 0.5, jnp.float32)
    valid = jnp.ones((T, 1), jnp.int32)
    wi = jnp.asarray(rng.integers(-127, 128, size=(S, D, 8)).astype(np.int8))
    wo = jnp.asarray(rng.integers(-127, 128, size=(S, 4, D)).astype(np.int8))
    wi_s = jnp.full((S, 8), 0.01, jnp.float32)
    wo_s = jnp.full((S, D), 0.02, jnp.float32)
    y_local = make_sorted_dispatch()(x, idx, topw, valid, wi, wo, wi_s, wo_s)
    y_ep = make_sorted_dispatch(mesh)(x, idx, topw, valid, wi, wo, wi_s, wo_s)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ engine


def _tiny_engine(**over):
    from llmd_tpu.engine import EngineConfig, LLMEngine
    from llmd_tpu.models import get_model_config

    base = dict(page_size=8, num_pages=64, max_model_len=128,
                max_batch_size=4, prefill_chunk=16)
    base.update(over)
    return LLMEngine(get_model_config("tiny-moe"), EngineConfig(**base),
                     seed=7)


def test_engine_auto_selects_sorted_and_env_overrides(monkeypatch):
    eng = _tiny_engine()
    assert eng.moe_dispatch == "sorted"
    monkeypatch.setenv("LLMD_MOE_DISPATCH", "einsum")
    assert _tiny_engine().moe_dispatch == "einsum"
    monkeypatch.delenv("LLMD_MOE_DISPATCH")
    assert _tiny_engine(moe_dispatch="einsum").moe_dispatch == "einsum"
    with pytest.raises(ValueError):
        _tiny_engine(moe_dispatch="bogus")


def test_engine_sorted_vs_einsum_greedy_parity_and_drops():
    from llmd_tpu.core.request import SamplingParams

    prompts = [list(range(3, 30)), list(range(40, 55))]
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    eng_s = _tiny_engine(moe_dispatch="sorted")
    out_s = eng_s.generate(prompts, sp)
    assert eng_s.stats.moe_dropped_tokens == 0
    eng_e = _tiny_engine(moe_dispatch="einsum")
    out_e = eng_e.generate(prompts, sp)
    if eng_e.stats.moe_dropped_tokens == 0:
        # nothing dropped -> identical math -> identical greedy outputs
        assert out_s == out_e


# greedy tokens of the tiny MoE model through the Pallas kernel (interpret
# mode) at 52c1282, the tree before the kernel's grid was turned
TOKENS_BEFORE_ISSUE_35 = {
    "req-0": [120, 54, 211, 243, 219, 103, 104, 148],
    "req-1": [268, 114, 271, 53, 137, 187, 9, 83],
    "req-2": [28, 152, 201, 270, 269, 175, 12, 131],
}


@pytest.mark.parametrize("moe_matmul", ["pallas", "einsum"])
def test_engine_tokens_are_what_they_were_and_blocks_are_booked(moe_matmul):
    """The same greedy tokens as before the change through `sorted_moe_local`,
    by either block backend; and every unified step books nb blocks a layer on
    ``moe_gemm_blocks_total``, by the plan its program was built with."""
    from llmd_tpu.core.request import SamplingParams
    from llmd_tpu.ops.moe_dispatch import pick_block_size, plan_blocks
    from tests.test_unified_ahead import _count

    eng = _tiny_engine(moe_matmul=moe_matmul)
    cfg = eng.model_cfg
    pallas = moe_matmul == "pallas"
    assert eng.moe_backend == ("pallas_grouped_gemm" if pallas else "xla_einsum")
    out = eng.generate([list(range(3, 30)), list(range(40, 55)), [5, 9, 2]],
                       SamplingParams(max_tokens=8, temperature=0.0))
    assert out == TOKENS_BEFORE_ISSUE_35

    copies = eng.cfg.batched_tokens * cfg.moe_top_k
    bc = pick_block_size(copies, cfg.moe_num_experts, pallas)
    nb = plan_blocks(copies, cfg.moe_num_experts, bc)
    assert eng.backends.moe_gemm_plan.keywords == {"bc": bc, "nb": nb}
    booked = {o: _count(eng, "moe_gemm_blocks_total", f'outcome="{o}"')
              for o in ("fetch", "reuse", "padding")}
    steps = _count(eng, "engine_program_dispatches_total",
                   'program="unified"')
    assert steps > 0 and booked["fetch"] > 0 and booked["padding"] > 0
    assert sum(booked.values()) == steps * cfg.num_layers * nb
    gemm = "fbx{}x{}".format(2 * cfg.moe_intermediate_size,
                             cfg.hidden_size) if pallas else "none"
    assert _count(eng, "engine_moe_backend",
                  f'backend="{eng.moe_backend}",dispatch="sorted",'
                  f'gemm="{gemm}"') == 1


def test_engine_eplb_rebalance_no_recompile_on_sorted():
    """Skewed load forces real placement changes; the sorted path's bucket
    shapes are static, so rebalances must regather weights WITHOUT growing
    any program cache (the zero-recompile acceptance criterion)."""
    from llmd_tpu.core.request import SamplingParams
    from llmd_tpu.parallel.eplb import EPLBConfig

    eng = _tiny_engine(eplb=EPLBConfig(window_size=8, step_interval=2,
                                       num_redundant_experts=4))
    assert eng.moe_dispatch == "sorted"
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    # warmup: compiles every program this workload uses, crosses >= 1 rebalance
    eng.generate([list(range(3, 30)), list(range(50, 70))], sp)
    reb0 = eng.stats.eplb_rebalances
    sizes0 = {name: fn._cache_size()
              for name, fn in [("decode", eng._decode_multi_fn)]
              if hasattr(fn, "_cache_size")}
    assert sizes0, "decode program exposes no _cache_size"
    # steady state at the same shapes: rebalances continue, compiles don't
    eng.generate([list(range(7, 34)), list(range(90, 110))], sp)
    assert eng.stats.eplb_rebalances > reb0
    for name, fn in [("decode", eng._decode_multi_fn)]:
        if hasattr(fn, "_cache_size"):
            assert fn._cache_size() == sizes0[name], (
                f"{name} recompiled across EPLB rebalance")


def test_engine_ep_imbalance_gauge_stamped():
    from llmd_tpu.core.request import SamplingParams
    from llmd_tpu.parallel.eplb import EPLBConfig

    eng = _tiny_engine(eplb=EPLBConfig(window_size=8, step_interval=2,
                                       num_redundant_experts=4))
    eng.generate([list(range(3, 30))],
                 SamplingParams(max_tokens=8, temperature=0.0))
    vals = {}
    for name, labels, value in eng.metrics.registry.collect():
        if name == "llmd_tpu:moe_ep_load_imbalance":
            vals[labels] = value
    whens = {lbl.strip("{}").split("=")[1].strip('"') for lbl in vals}
    assert whens == {"before", "after"}, vals
    assert all(v >= 1.0 - 1e-9 for v in vals.values()), vals
