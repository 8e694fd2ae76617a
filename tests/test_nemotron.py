"""A stack of single sublayers (Mamba-2, attention, experts), non-gated relu^2
experts of which a device holds a share, and the Mamba-2 recurrence for mixed
steps and the fused decode call (ISSUE 47), held against the plain float32
reference of the family (``perfbench/reference/hybrid_mamba2_moe.py``) at a
tiny size on the CPU: the period MEMEM*E once, 8 heads of 32 channels in 2
groups of state 16, 8 experts top-2 of which 4 are held, pages of 4 tokens.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import replace

import conftest  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the family modules, by path and for the import alone: perfbench/ has a
# tests/ of its own, which must not shadow this package for the other files
sys.path.append(os.path.join(ROOT, "perfbench"))
try:
    from kernels import mamba2_ssd as ssd_roofline  # noqa: E402
    from reference import hybrid_mamba2_moe as family  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

from llmd_tpu.core.request import SamplingParams  # noqa: E402
from llmd_tpu.engine import EngineConfig, LLMEngine  # noqa: E402
from llmd_tpu.models import get_model_config  # noqa: E402
from llmd_tpu.models.config import ModelConfig  # noqa: E402
from llmd_tpu.models.transformer import (  # noqa: E402
    forward, forward_core, init_cache, init_params, init_state, moe_block,
    unembed)
from llmd_tpu.ops.mamba2_ssd import (  # noqa: E402
    BLOCK, mamba2_ssd_pallas, mamba2_ssd_xla)
from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch  # noqa: E402
from llmd_tpu.parallel.mesh import MeshConfig  # noqa: E402

with open(os.path.join(ROOT, "perfbench", "tests", "tiny-nemotron.json")) as f:
    CONF = dict(json.load(f), weights={"dtype": "float32", "quantize": None})
CFG = family.model_config(CONF)
SIZES = family.sizes(CONF)
PS, T = 4, 150  # page size; a sequence of two whole blocks and a part
SEATS, MAXP = 4, 48
SORTED = make_sorted_dispatch()  # drop-free, as the engine serves
# float32 on both sides: what is left is the order of the sums. Read on the
# CPU over three seeds of weights (0, 1, 2): 3.8e-6 to 6.2e-6 on logits of
# magnitude 4. The controls read, at their worst position: the state held in
# bfloat16 2.6e-3 to 4.4e-3, each named fault 0.12 to 2.9. The limit stands
# 8 times above the sound readings and 50 times below the nearest control.
TOLERANCE = 5e-5

FAULTS = [("skip_d", False), ("conv_bias", False), ("gate_first", False),
          ("own_group", False), ("act", "relu"), ("shared", False),
          ("scaling", 1.0), ("bias_in_weights", True),
          ("bias_in_choice", False), ("absent_left_out", False),
          ("attn_rope", True)]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in np.random.default_rng(0).integers(0, 288, size=T)]


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(family.logits(SIZES, params, tokens))


def _pools(cfg=CFG, poison: float = 0.0):
    state = {k: v + jnp.asarray(poison, v.dtype)
             for k, v in init_state(cfg, SEATS).items()}
    return {"kv": init_cache(cfg, 64, PS), **state}


def _serve(params, tokens, chunks, cfg=CFG, ssd_impl=None, nt=None,
           slot=1, poison=0.0):
    """``tokens`` through ``forward_core`` in ``chunks`` (a chunk of 1 after
    the first is a decode step through the unified packing), one sequence in
    seat ``slot`` beside an idle padding row; the logits of every token."""
    nt = nt or max(chunks) + 8  # (no token in the array's last rows)
    pools = _pools(cfg, poison)
    pt = np.full((2, MAXP), -1, np.int32)
    pt[0, :] = np.arange(MAXP)
    out, at = [], 0
    step = jax.jit(lambda pools, toks, pos, sid, lens, cu: forward_core(
        cfg, params, pools, toks, pos, sid, jnp.asarray(pt), lens,
        cu_q_lens=cu, num_seqs=jnp.asarray([1], jnp.int32),
        state_slots=jnp.asarray([slot, SEATS], jnp.int32),
        moe_dispatch_impl=SORTED, ssd_impl=ssd_impl)[:2])
    for n in chunks:
        toks = np.zeros(nt, np.int32)
        pos = np.full(nt, -1, np.int32)
        toks[:n], pos[:n] = tokens[at:at + n], np.arange(at, at + n)
        hidden, pools = step(
            pools, jnp.asarray(toks), jnp.asarray(pos),
            jnp.zeros(nt, jnp.int32), jnp.asarray([at + n, 0], jnp.int32),
            jnp.asarray([0, n, n], jnp.int32))
        out.append(np.asarray(unembed(cfg, params, hidden[:n])))
        at += n
    return np.concatenate(out), pools


def _worst(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# ------------------------------------------------------------ configuration
def test_the_family_maps_the_published_keys():
    assert CFG.layer_kinds == ("mamba2", "experts", "mamba2", "experts",
                               "mamba2", "attention", "experts")
    assert CFG.single_sublayer and not CFG.moe_gated
    assert (CFG.num_mamba2_layers, CFG.num_moe_layers, CFG.num_attn_layers,
            CFG.kv_pool_folds) == (3, 3, 1, 1)
    assert (CFG.mamba2_d_inner, CFG.mamba2_conv_dim) == (256, 256 + 2 * 2 * 16)
    assert (CFG.moe_num_experts, CFG.moe_held_first, CFG.moe_held_count,
            CFG.moe_bank_slots, CFG.moe_shared_width) == (8, 0, 4, 4, 160)
    assert CFG.moe_activation == "relu2" and CFG.moe_scoring == "sigmoid"
    assert CFG.rope_pattern == (False,) and CFG.has_recurrent
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        big = family.model_config(json.load(f))
    assert big.layer_kinds == CFG.layer_kinds and big.num_layers == 14
    assert (big.mamba2_heads, big.mamba2_head_dim, big.mamba2_groups,
            big.mamba2_d_state, big.mamba2_conv_dim) == (64, 64, 8, 128, 6144)
    assert (big.moe_num_experts, big.moe_held_count, big.moe_top_k,
            big.moe_intermediate_size, big.moe_shared_width,
            big.moe_routed_scaling) == (128, 64, 6, 1856, 3712, 2.5)


def test_the_registry_names_a_preset_of_the_family():
    cfg = get_model_config("tiny-nemotron-h")
    assert cfg.single_sublayer and cfg.num_layers == 14
    assert cfg.layer_kinds == CFG.layer_kinds and cfg.moe_held_count == 0


@pytest.mark.parametrize("key,value", [
    ("mamba_proj_bias", True), ("n_group", 2), ("norm_topk_prob", False),
    ("mlp_hidden_act", "silu"), ("tie_word_embeddings", True),
    ("hybrid_override_pattern", "MEMEMEE")])
def test_model_config_refuses_by_the_keys_name(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        family.model_config(dict(CONF, **{key: value}))


@pytest.mark.parametrize("kw,match", [
    (dict(moe_router_input="attn_norm"), "single sublayers"),
    (dict(layer_kinds=("mamba2", "attention")), "'experts'"),
    (dict(moe_activation="silu"), "moe_activation"),
    (dict(moe_held_first=6, moe_held_count=4), "moe_held_first"),
    (dict(mamba2_groups=3), "mamba2_heads"),
    (dict(attn_bias=True), "attention bias"),
    (dict(mla_kv_lora_rank=64, mla_rope_dim=16, mla_qk_nope_dim=32,
          mla_v_head_dim=32), "MLA")])
def test_the_config_refuses_what_is_still_not_served(kw, match):
    with pytest.raises(ValueError, match=match):
        replace(CFG, **kw)


def test_a_recurrent_mixer_over_a_mixture_in_one_layer_is_refused_by_name():
    with pytest.raises(ValueError, match="single sublayers"):
        replace(get_model_config("tiny-jamba"), moe_num_experts=4,
                moe_top_k=2)


def test_the_leaves_are_stacked_by_kind_and_the_banks_by_held_slot(params):
    assert "mlp_norm" not in params and "wi" not in params
    assert params["attn_norm"].shape == (7, 128)
    assert params["wq"].shape[0] == 1 and params["m2_in"].shape == (
        3, 128, 256 + 320 + 128)  # dt's 8 columns, then zeros to a lane tile
    assert not np.asarray(params["m2_in"][:, :, 256 + 320 + 8:]).any()
    assert params["router"].shape == (3, 128, 8)
    assert params["router_bias"].shape == (3, 8)
    # no gate half; the width 96 stored as a whole lane tile, zeros past it
    assert params["moe_wi"].shape == (3, 4, 128, 128)
    assert params["moe_wo"].shape == (3, 4, 128, 128)
    assert not np.asarray(params["moe_wi"][..., 96:]).any()
    assert not np.asarray(params["moe_wo"][:, :, 96:]).any()
    assert params["shared_wi"].shape == (3, 128, 160)
    state = init_state(CFG, SEATS)
    assert state["ssm"].shape == (3, SEATS + 1, 2, 16, 128)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (3, 3, SEATS + 1, 320)
    assert float(jnp.abs(params["router_bias"]).max()) > 0


def test_forward_refuses_a_model_with_recurrent_layers(params):
    with pytest.raises(ValueError, match="forward_core"):
        forward(CFG, params, init_cache(CFG, 8, PS), jnp.zeros((1, 4), jnp.int32),
                jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 2), jnp.int32),
                jnp.asarray([4]))


# ------------------------------------------------- program against reference
def test_forward_core_agrees_with_the_reference(params, tokens, want):
    got, _ = _serve(params, tokens, (T,))
    assert _worst(got, want) < TOLERANCE


def test_a_bfloat16_state_fails_the_tolerance(params, tokens, want):
    low = family.logits(dict(SIZES, state_dtype="bfloat16"), params, tokens)
    assert _worst(low, want) > 10 * TOLERANCE


@pytest.mark.parametrize("key,value", FAULTS, ids=[k for k, _ in FAULTS])
def test_each_named_fault_fails_and_the_reference_has_the_mechanism(
        params, tokens, want, key, value):
    bad = family.logits(dict(SIZES, **{key: value}), params, tokens)
    assert _worst(bad, want) > 1000 * TOLERANCE


@pytest.mark.parametrize("chunks", [
    (64, 64, 22), (128, 22), (64, 85) + (1,) * 1, (149, 1),
    (128,) + (1,) * 22], ids=lambda c: "+".join(map(str, c[:3])))
def test_chunks_and_decode_through_the_pools_equal_the_reference(
        params, tokens, want, chunks):
    got, _ = _serve(params, tokens, chunks, nt=160, poison=7.0)
    assert _worst(got, want[:sum(chunks)]) < TOLERANCE


def test_the_fused_decode_packing_continues_a_prefill(params, tokens, want):
    """Prefill through the unified packing, then steps as the fused decode
    call packs them (row b is seat b, one token, ``state_slots`` None)."""
    n0 = 130
    _, pools = _serve(params, tokens, (n0,), slot=2)
    pt = np.full((SEATS, MAXP), -1, np.int32)
    pt[2, :] = np.arange(MAXP)
    step = jax.jit(lambda pools, toks, pos, lens: forward_core(
        CFG, params, pools, toks, pos, jnp.arange(SEATS, dtype=jnp.int32),
        jnp.asarray(pt), lens, cu_q_lens=jnp.arange(SEATS + 1, dtype=jnp.int32),
        num_seqs=jnp.asarray([SEATS], jnp.int32),
        moe_dispatch_impl=SORTED)[:2])
    before = np.asarray(pools["ssm"][:, 0])
    for at in range(n0, n0 + 6):
        toks = np.zeros(SEATS, np.int32)
        pos = np.full(SEATS, -1, np.int32)
        lens = np.zeros(SEATS, np.int32)
        toks[2], pos[2], lens[2] = tokens[at], at, at + 1
        hidden, pools = step(pools, jnp.asarray(toks), jnp.asarray(pos),
                             jnp.asarray(lens))
        got = np.asarray(unembed(CFG, params, hidden[2]))
        assert _worst(got, want[at]) < TOLERANCE
    # an idle seat's slot is left bit for bit
    assert (np.asarray(pools["ssm"][:, 0]) == before).all()


def test_the_expert_layers_report_counts_by_held_slot(params, tokens):
    pools = _pools()
    pt = np.full((2, MAXP), -1, np.int32)
    pt[0] = np.arange(MAXP)
    n = 64
    _, _, cnt, drop = forward_core(
        CFG, params, pools, jnp.asarray(tokens[:n]), jnp.arange(n),
        jnp.zeros(n, jnp.int32), jnp.asarray(pt), jnp.asarray([n, 0]),
        cu_q_lens=jnp.asarray([0, n, n]), num_seqs=jnp.asarray([1]),
        state_slots=jnp.asarray([0, SEATS]), moe_dispatch_impl=SORTED)
    dropped, moved, routed, held = (int(v) for v in drop)
    assert cnt.shape == (3, 4) and int(cnt.sum()) == held
    assert routed == 3 * n * 2 and 0 < held < routed and dropped == 0
    assert 0 < moved < routed


# ----------------------------------------------------------- the experts
def _layer_inputs(seed=0, t=40):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    E, D, F = 8, 128, 96
    x = jax.random.normal(k[0], (t, D), jnp.float32)
    return (x, jax.random.normal(k[1], (D, E)) * D ** -0.5,
            jax.random.normal(k[2], (E, D, F)) * D ** -0.5,
            jax.random.normal(k[3], (E, F, D)) * F ** -0.5,
            jax.random.normal(k[4], (E,)) * 0.1)


def test_the_shares_add_up_to_the_uncut_layer():
    """Held 0-3 plus held 4-7 of 8 experts equal the whole layer's routed
    part; the shared expert is every device's alike and counted once, which
    the reference's uncut layer does."""
    x, router, wi, wo, bias = _layer_inputs()
    whole = replace(CFG, moe_held_first=0, moe_held_count=0)
    y_all, cnt_all = moe_block(whole, x, router, wi, wo, router_bias=bias,
                               dispatch_impl=SORTED)
    parts, counts = [], []
    for first in (0, 4):
        cfg = replace(CFG, moe_held_first=first, moe_held_count=4)
        y, cnt, drop = moe_block(cfg, x, router, wi[first:first + 4],
                                 wo[first:first + 4], router_bias=bias,
                                 dispatch_impl=SORTED, return_dropped=True)
        parts.append(y)
        counts.append(cnt)
        assert int(drop[3]) == int(cnt.sum()) and int(drop[2]) == 80
    assert _worst(parts[0] + parts[1], y_all) < 2e-6
    assert (np.concatenate(counts) == np.asarray(cnt_all)).all()
    # and against the reference's uncut layer, shared expert once
    k = jax.random.split(jax.random.PRNGKey(9), 2)
    swi = jax.random.normal(k[0], (128, 160)) * 128 ** -0.5
    swo = jax.random.normal(k[1], (160, 128)) * 160 ** -0.5
    w = {"attn_norm": jnp.ones((128,)), "router": router, "router_bias": bias,
         "shared_wi": swi, "shared_wo": swo}
    banks = {"moe_wi": wi[None], "moe_wo": wo[None]}
    with jax.default_matmul_precision("highest"):
        ref = family.experts(x, w, banks, 0, eps=1e-5, top_k=2, scaling=2.5,
                             held_first=0, held=8) - x
        g = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        summed = sum(moe_block(
            replace(CFG, moe_held_first=f, moe_held_count=4), g, router,
            wi[f:f + 4], wo[f:f + 4], router_bias=bias,
            dispatch_impl=SORTED)[0] for f in (0, 4))
        shared = jnp.square(jax.nn.relu(g @ swi)) @ swo
    assert _worst(summed + shared, ref) < 1e-4


def test_an_absent_copy_costs_no_row():
    """The held range masks routed copies before dispatch: the sorted
    buffer's real rows are the held copies only."""
    from llmd_tpu.ops.moe_dispatch import dispatch_stage

    x, router, wi, wo, bias = _layer_inputs()
    seen = {}

    def spy(x, idx, topw, valid, wi, wo, wi_scale, wo_scale, **kw):
        seen.update(kw, idx=idx, valid=valid, slots=wi.shape[0])
        rows = dispatch_stage(x, idx, topw, valid, wi.shape[0], 8)[-1]
        seen["rows"] = int(rows.sum())
        return SORTED(x, idx, topw, valid, wi, wo, wi_scale, wo_scale, **kw)

    spy.stacked_banks = spy.ordered_combine = True
    cfg = replace(CFG, moe_held_first=4, moe_held_count=4)
    _, cnt = moe_block(cfg, x, router, wi[4:], wo[4:], router_bias=bias,
                       dispatch_impl=spy)
    assert seen["gated"] is False and seen["slots"] == 4
    assert seen["rows"] == int(cnt.sum()) == int(seen["valid"].sum()) < 80
    assert int(seen["idx"].max()) <= 3


@pytest.mark.parametrize("held", [0, 4], ids=["all", "share"])
def test_non_gated_experts_through_both_dispatch_paths(held):
    """The sorted dispatch and the capacity einsum (capacity above any
    expert's load) give the non-gated experts' sum alike."""
    x, router, wi, wo, bias = _layer_inputs(seed=3)
    cfg = replace(CFG, moe_held_first=0, moe_held_count=held,
                  moe_capacity_factor=8.0)
    wi, wo = (wi[:held], wo[:held]) if held else (wi, wo)
    a, ca = moe_block(cfg, x, router, wi, wo, router_bias=bias,
                      dispatch_impl=SORTED)
    b, cb, drop = moe_block(cfg, x, router, wi, wo, router_bias=bias,
                            return_dropped=True)
    assert int(drop[0]) == 0 and (np.asarray(ca) == np.asarray(cb)).all()
    # (not bit for bit: the sorted path adds a token's copies in the order of
    # its choice, the einsum in the experts' order; float32 sums of values
    # up to 5 part by a unit or two in the last place)
    assert _worst(a, b) < 2e-5


@pytest.mark.parametrize("rows", ["live-first", "idle-first", "none", "all"])
def test_the_gathered_buffer_is_the_scattered_one_bit_for_bit(rows):
    """`dispatch_stage(gather_rows=True)`, which the hybrid stack's expert
    layers take, builds the sorted buffer the scatter builds: every result
    equal, for the row patterns of a fused decode call (the scatter did not
    return from the chip with idle seats before live ones)."""
    from llmd_tpu.models.transformer import relu2
    from llmd_tpu.ops.moe_dispatch import dispatch_stage, sorted_moe_local

    T, k, S, D, bc = 64, 6, 16, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (T, D)).astype(jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, S)
    topw = jax.random.uniform(ks[2], (T, k))
    live = {"live-first": np.arange(T) < 4, "none": np.zeros(T, bool),
            "idle-first": (np.arange(T) >= 4) & (np.arange(T) < 8),
            "all": np.ones(T, bool)}[rows]
    valid = jnp.asarray(live[:, None] & np.asarray(
        jax.random.uniform(ks[3], (T, k)) < 0.5), jnp.int32)
    want = dispatch_stage(x, idx, topw, valid, S, bc)
    got = dispatch_stage(x, idx, topw, valid, S, bc, gather_rows=True)
    for a, b in zip(want, got):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()
    wi = jax.random.normal(ks[4], (S, D, 24)).astype(jnp.bfloat16)
    wo = jax.random.normal(ks[5], (S, 24, D)).astype(jnp.bfloat16)
    y = [sorted_moe_local(x, idx, topw, valid, wi, wo, gated=False,
                          act=relu2, ordered_combine=True, gather_rows=g)
         for g in (False, True)]
    assert (np.asarray(y[0]) == np.asarray(y[1])).all()


def test_gated_experts_keep_their_body_bit_for_bit():
    """``moe_gated`` true is what every other model has: the same two halves
    and product as before the non-gated body was added."""
    from llmd_tpu.ops.moe_dispatch import _experts_xla

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    xb = jax.random.normal(k[0], (6, 8, 32))
    wi = jax.random.normal(k[1], (3, 32, 2 * 24))
    wo = jax.random.normal(k[2], (3, 24, 32))
    slot = jnp.asarray([0, 0, 1, 2, 2, 2])
    got = _experts_xla(xb, slot, None, wi, wo, None, None)
    gate, up = jnp.split(jnp.einsum("bcd,bdf->bcf", xb, wi[slot]), 2, -1)
    want = jnp.einsum("bcf,bfd->bcd", jax.nn.silu(gate) * up, wo[slot])
    assert (np.asarray(got) == np.asarray(want)).all()


def test_the_bias_scale_moves_a_tenth_and_loads_no_expert_twice_the_mean():
    """The configuration's ``router_bias_scale`` from the router's arithmetic
    alone: 128 experts, top-6, scores of logits of unit variance."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        scale = json.load(f)["router_bias_scale"]
    rng = np.random.default_rng(0)
    E, k, n = 128, 6, 20000
    for _ in range(3):
        s = 1 / (1 + np.exp(-rng.standard_normal((n, E), np.float32)))
        b = rng.standard_normal(E).astype(np.float32) * scale
        plain = np.argpartition(-s, k, axis=1)[:, :k]
        chosen = np.argpartition(-(s + b), k, axis=1)[:, :k]
        hit = np.zeros((n, E), bool)
        np.put_along_axis(hit, plain, True, axis=1)
        moved = 1 - np.take_along_axis(hit, chosen, axis=1).mean()
        load = np.bincount(chosen.ravel(), minlength=E)
        assert 0.09 < moved < 0.16 and load.max() / load.mean() < 2.0
    assert ModelConfig.moe_router_bias_scale == 0.1  # GLM's draw stands


# --------------------------------------------------------------- the kernel
def _ragged(lens, live, fresh, seed=0, H=8, P=32, G=2, N=16, S=9):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    nt, nb = sum(lens) + 5, len(lens)
    return dict(
        x=jax.random.normal(k[0], (nt, H, P)).astype(jnp.bfloat16),
        dt=jax.nn.softplus(jax.random.normal(k[1], (nt, H)) - 3.0),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7)),
        Bm=jax.random.normal(k[3], (nt, G, N)).astype(jnp.bfloat16),
        Cm=jax.random.normal(k[4], (nt, G, N)).astype(jnp.bfloat16),
        pool=jax.random.normal(k[5], (S, G, N, H // G * P)),
        slots=jnp.asarray([3, 1, 4, 0, 7, 8, 2][:nb], jnp.int32),
        cu_q_lens=jnp.asarray(np.concatenate([[0], np.cumsum(lens)]),
                              jnp.int32),
        live=jnp.asarray(live), fresh=jnp.asarray(fresh))


RAGGED = {
    "mixed": ([1, 37, 1, 70, 5, 0], [1, 1, 0, 1, 1, 0], [0, 1, 0, 0, 1, 0]),
    "decode": ([1] * 6, [1, 1, 1, 0, 1, 1], [0, 0, 1, 0, 0, 0]),
    "one-chunk": ([130], [1], [1]),
    "block-and-one": ([BLOCK + 1, 1], [1, 1], [0, 0]),
}


@pytest.mark.parametrize("block", [16, BLOCK])
@pytest.mark.parametrize("case", sorted(RAGGED))
def test_the_kernel_equals_the_xla_form(case, block):
    lens, live, fresh = RAGGED[case]
    a = _ragged(lens, np.asarray(live, bool), np.asarray(fresh, bool))
    y0, p0 = mamba2_ssd_xla(**a)
    y1, p1 = mamba2_ssd_pallas(**a, interpret=True, block=block)
    # x, B and C are exact in bfloat16; float32 operands go to the matrix
    # unit as two pieces (16 bits): 2e-4 of |y| <= 20 at its worst
    assert _worst(y0, y1) < 1e-3 and _worst(p0, p1) < 1e-4
    dead = np.asarray([s for s, l in zip(np.asarray(a["slots"]), live)
                       if not l] + [5, 6], np.int32)
    assert (np.asarray(p1[dead]) == np.asarray(a["pool"][dead])).all()
    rows = np.repeat(np.arange(len(lens)), lens)
    off = ~np.asarray(live, bool)[rows]
    assert not np.asarray(y1[:len(rows)])[off].any()


def test_a_fresh_row_starts_from_zeros_whatever_its_slot_held():
    lens, live, fresh = [40], np.asarray([True]), np.asarray([True])
    a = _ragged(lens, live, fresh)
    y0, p0 = mamba2_ssd_pallas(**a, interpret=True)
    y1, p1 = mamba2_ssd_pallas(**dict(a, pool=a["pool"] * 0 + 9.0),
                               interpret=True)
    assert (np.asarray(y0) == np.asarray(y1)).all()
    assert (np.asarray(p0[3]) == np.asarray(p1[3])).all()


def test_a_token_does_not_depend_on_its_chunk_or_its_neighbours():
    """A prompt's blocks are its own: one call, chunks that start on
    multiples of the block, and other rows beside it give the same bits."""
    a = _ragged([2 * BLOCK + 9], np.asarray([True]), np.asarray([True]))
    n = 2 * BLOCK + 9
    whole_y, whole_p = mamba2_ssd_pallas(**a, interpret=True)
    ys, pool = [], a["pool"]
    for at, m, fr in ((0, BLOCK, True), (BLOCK, BLOCK + 9, False)):
        part = {k: a[k][at:at + m] for k in ("x", "dt", "Bm", "Cm")}
        # the same rows beside a decode row of another slot, placed first
        other = {k: a[k][n:n + 1] for k in part}
        y, pool = mamba2_ssd_pallas(
            **{k: jnp.concatenate([other[k], part[k]]) for k in part},
            A=a["A"], pool=pool, slots=jnp.asarray([6, 3], jnp.int32),
            cu_q_lens=jnp.asarray([0, 1, 1 + m], jnp.int32),
            live=jnp.asarray([True, True]), fresh=jnp.asarray([False, fr]),
            interpret=True)
        ys.append(y[1:])
    assert (np.asarray(jnp.concatenate(ys)) == np.asarray(whole_y[:n])).all()
    assert (np.asarray(pool[3]) == np.asarray(whole_p[3])).all()


def test_the_pallas_kernel_serves_the_stack_as_the_xla_form_does(
        params, tokens):
    import functools

    got, pools = _serve(params, tokens, (128, 22), nt=160)
    via, pools_k = _serve(
        params, tokens, (128, 22), nt=160,
        ssd_impl=functools.partial(mamba2_ssd_pallas, interpret=True))
    # float32 weights here: the kernel rounds B and C to bfloat16, which the
    # served bf16 stack's are already
    assert _worst(got, via) < 0.2 and _worst(
        pools["ssm"], pools_k["ssm"]) < 0.05


# --------------------------------------------------------------- the engine
def _engine(cfg=None, **kw):
    cfg = cfg or replace(get_model_config("tiny-nemotron-h"),
                         moe_held_first=0, moe_held_count=4)
    fields = dict(page_size=4, num_pages=512, max_model_len=512,
                  max_batch_size=8, prefill_chunk=64, decode_steps=4)
    return LLMEngine(cfg, EngineConfig(**dict(fields, **kw)), seed=3)


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
    _, all8 = served
    eng = _engine()
    assert _run(eng, prompts[:2], "b") + _run(eng, prompts[2:], "c") == all8
    eng = _engine()
    assert [_run(eng, [p], f"d{i}")[0] for i, p in enumerate(prompts)] == all8


def test_engine_tokens_are_the_references(served, prompts):
    eng, out = served
    cfg = eng.model_cfg
    conf = dict(CONF, hybrid_override_pattern="MEMEM*E" * 2,
                num_hidden_layers=14, mamba_num_heads=4, mamba_head_dim=16,
                expand=0.5, weights={"dtype": "bfloat16", "quantize": None})
    sizes = family.sizes(conf)
    assert family.model_config(conf).layer_kinds == cfg.layer_kinds
    p, o = prompts[3], out[3]
    ref = np.asarray(family.logits(sizes, eng.params, p + o[:-1]))[-len(o):]
    # bf16 weights and activations against the float32 reference: a served
    # token lies within 0.5 of the reference's maximum (logits of
    # magnitude 4; read 0.0 to 0.21)
    assert float((ref.max(-1) - ref[np.arange(len(o)), o]).max()) < 0.5


def test_the_engine_counts_the_state_the_share_and_the_blocks(served):
    eng, _ = served
    scan = _series(eng, "llmd_tpu:ssm_scan_tokens_total")
    assert scan['llmd_tpu:ssm_scan_tokens_total{program="unified",'
                'rows="chunk"}'] == 478
    assert _series(eng, "llmd_tpu:ssm_state_resets_total")[
        'llmd_tpu:ssm_state_resets_total{cause="admit"}'] == 6
    info = _series(eng, "llmd_tpu:engine_ssm_backend")
    assert list(info) == [
        'llmd_tpu:engine_ssm_backend{impl="xla_mamba2_ssd_block%d",'
        'state_dtype="float32",prefix_reuse="off"}' % BLOCK]
    routed = _series(eng, "llmd_tpu:moe_routed_copies_total")
    held = _series(eng, "llmd_tpu:moe_held_copies_total")
    share = list(held.values())[0] / list(routed.values())[0]
    assert 0.3 < share < 0.7
    assert list(_series(eng, "llmd_tpu:moe_bias_moved_choices_total")
                .values())[0] > 0
    blocks = _series(eng, "llmd_tpu:moe_gemm_blocks_total")
    assert blocks['llmd_tpu:moe_gemm_blocks_total{outcome="fetch"}'] > 0
    assert 'gemm="none held=0-3/8"' in eng.metrics.registry.expose()
    assert eng.backends.compiler_options == {
        "xla_allow_excess_precision": False}
    assert eng.prefix_reuse is False
    # prompts through the unified step, answers through the fused decode call
    counters = eng.programs.counters()
    assert counters["unified"][0] > 0 and counters["decode"][0] > 0
    assert scan['llmd_tpu:ssm_scan_tokens_total{program="decode",'
                'rows="decode"}'] > 0


def test_a_prompts_chunks_start_on_the_kernels_blocks(prompts):
    eng = _engine(prefill_chunk=100)
    eng.add_request("x", prompts[3], GREEDY)  # 200 tokens
    seq, starts = eng.waiting[0], []
    while eng.has_work():
        if seq.num_computed < 200 and seq.num_computed not in starts:
            starts.append(seq.num_computed)
        eng.step()
    # chunks of 100 are cut to 64; the last, 72 tokens, is the prompt's rest
    assert starts == [0, BLOCK, 2 * BLOCK]


@pytest.mark.parametrize("kw,name", [
    (dict(spec_mode="ngram"), "spec_mode"),
    (dict(mesh=MeshConfig(tp=2)), "mesh.tp"),
    (dict(mesh=MeshConfig(ep=2)), "mesh.ep"),
    (dict(cpu_offload_pages=8), "cpu_offload_pages")])
def test_what_cannot_stand_beside_recurrent_layers_is_refused_by_name(kw, name):
    with pytest.raises(ValueError, match=name):
        _engine(**kw)


def test_eplb_and_dbo_are_refused_by_name():
    from llmd_tpu.parallel.eplb import EPLBConfig

    with pytest.raises(ValueError, match="eplb"):
        _engine(eplb=EPLBConfig())
    with pytest.raises(ValueError, match="moe_dbo"):
        _engine(cfg=replace(get_model_config("tiny-nemotron-h"), moe_dbo=True))


def test_the_loader_refuses_the_familys_checkpoints_by_name(tmp_path):
    from llmd_tpu.models.hf_loader import config_from_hf

    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "nemotron_h", "architectures": ["NemotronHForCausalLM"]}))
    with pytest.raises(ValueError, match="nemotron_h"):
        config_from_hf(str(tmp_path))


# ------------------------------------------------------------- the roofline
def test_the_roofline_reads_the_demand_and_cannot_pass_it():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    ops, byts = ssd_roofline.cost(60, 60, 64, 64, 8, 128)
    state = 60 * 2 * 64 * 64 * 128 * 4
    assert byts == state + 60 * ((2 * 4096 + 2 * 1024) * 2 + 64 * 4 + 4096 * 4)
    assert ops == 6.0 * 60 * 4096 * 128
    least = ssd_roofline.least_seconds(ops, byts, peaks)
    assert abs(least - byts / peaks["hbm_bytes_per_s"]) < 1e-9  # byte-bound

    def ctx(module, seconds, calls):
        samples = lambda n, d, c: [  # noqa: E731
            ("llmd_tpu:engine_program_dispatches_total",
             {"program": "unified"}, n),
            ("llmd_tpu:unified_decode_rows_total", {}, d),
            ("llmd_tpu:ssm_scan_tokens_total",
             {"program": "unified", "rows": "chunk"}, c)]
        return {"config": conf, "gen": {"decoding_mean": 60.0},
                "device": {"kind": "TPU v5 lite"},
                "before": {"engine": samples(0, 0, 0)},
                "after": {"engine": samples(10, 600, 2560)},
                "trace": {"modules": {"jit__" + module: {"ops": {
                    "mamba2_ssd.1": {"count": calls, "seconds": seconds}}}}}}

    src = {"kind": "kernel_roofline", "kernel": "mamba2_ssd",
           "pattern": "mamba2_ssd", "module": "unified"}
    o2, b2 = ssd_roofline.cost(61, 60 + 256, 64, 64, 8, 128)
    least = ssd_roofline.least_seconds(o2, b2, peaks)
    got = ssd_roofline.roofline(src, ctx("unified", 6 * least * 2, 6))
    assert abs(got - 0.5) < 1e-6
    # a program without the kernel, or a file without the sizes: nothing
    assert ssd_roofline.roofline(src, dict(ctx("unified", 1.0, 6),
                                           trace={"modules": {}})) is None
    assert ssd_roofline.roofline(src, dict(
        ctx("unified", 1.0, 6), config={"weights": conf["weights"]})) is None
