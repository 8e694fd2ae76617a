"""'kda' delta-rule mixers beside latent attention, each over a group-limited
mixture of which a device holds a share, behind a leading dense layer (ISSUE
51), held against the plain float32 reference of the family
(``perfbench/reference/hybrid_kda_mla_moe.py``) at a tiny size on the CPU:
one leading layer and the period KDA KDA KDA MLA KDA KDA once, 4 heads of 32
lanes, 8 experts in 4 groups of which 2 are kept, top-2, 4 held, pages of 4.
"""

from __future__ import annotations

import functools
import hashlib
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
    from kernels import kda_attention as kda_roofline  # noqa: E402
    from reference import hybrid_kda_mla_moe as family  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

from llmd_tpu.models import get_model_config  # noqa: E402
from llmd_tpu.models.config import ModelConfig  # noqa: E402
from llmd_tpu.models.transformer import (  # noqa: E402
    forward, forward_core, init_cache, init_params, init_state, moe_block,
    unembed)
from llmd_tpu.ops.kda_attention import (  # noqa: E402
    BLOCK, kda_attention_pallas, kda_attention_xla)
from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch  # noqa: E402

with open(os.path.join(ROOT, "perfbench", "tests", "tiny-ling.json")) as f:
    CONF = dict(json.load(f), weights={"dtype": "float32", "quantize": None})
CFG = family.model_config(CONF)
SIZES = family.sizes(CONF)
PS, T = 4, 150  # page size; a sequence of nine whole blocks and a part
SEATS, MAXP = 4, 48
SORTED = make_sorted_dispatch()  # drop-free, as the engine serves
# float32 on both sides: what is left is the order of the sums. Read on the
# CPU over three seeds of weights (0, 1, 2), whole and in chunks: 1.6e-5 to
# 2.1e-5 on logits of magnitude 4.4. The controls read, at their worst
# position: the state rounded to bfloat16 a token 2.4 to 3.4 (a rounding
# flips an expert's choice, and the state carries it on), each named fault
# 2.2 to 6.8. The limit stands 5 times above the sound readings and four
# orders below the nearest control.
TOLERANCE = 1e-4

FAULTS = [("delta", False), ("safe_gate", False), ("qk_l2", False),
          ("out_gate", False), ("out_norm", False), ("mla_rope", False),
          ("head_gate", False), ("group_limit", False), ("shared", False),
          ("bias_in_choice", False), ("absent_left_out", False),
          ("scaling", 1.0)]


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


def _serve(params, tokens, chunks, cfg=CFG, kda_impl=None, nt=None,
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
        moe_dispatch_impl=SORTED, kda_impl=kda_impl)[:2])
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
    assert CFG.layer_kinds == ("kda", "kda", "kda", "attention", "kda", "kda")
    assert (CFG.num_layers, CFG.moe_leading_dense_layers, CFG.num_kda_layers,
            CFG.num_attn_layers, CFG.num_moe_layers) == (7, 1, 6, 1, 6)
    assert CFG.recurrent_over_mixture and not CFG.single_sublayer
    assert (CFG.moe_num_experts, CFG.moe_held_count, CFG.moe_n_group,
            CFG.moe_topk_group, CFG.moe_top_k) == (8, 4, 4, 2, 2)
    assert CFG.attn_output_gate and CFG.attn_gate_per_head and CFG.is_mla
    assert CFG.kda_gate_lower_bound == -5.0 and CFG.kda_d_conv == 4
    assert CFG.kv_pool_folds == 1 and CFG.kv_cache_heads == 1
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        cell = family.model_config(json.load(f))
    assert cell.layer_kinds == CFG.layer_kinds and cell.num_layers == 7
    assert (cell.kda_heads, cell.kda_head_dim, cell.hidden_size) == (
        32, 128, 2560)
    assert (cell.moe_num_experts, cell.moe_held_count, cell.moe_n_group,
            cell.moe_topk_group, cell.moe_top_k) == (512, 128, 8, 4, 8)
    assert cell.kv_cache_head_dim == 576 and cell.vocab_size == 39296


def test_the_registry_names_a_preset_of_the_family():
    cfg = get_model_config("tiny-ling")
    assert cfg.recurrent_over_mixture and cfg.num_layers == 7
    assert cfg.layer_kinds == CFG.layer_kinds and cfg.moe_held_count == 4


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 64), ("kda_safe_gate", False), ("use_mla_nope", True),
    ("norm_topk_prob", False), ("score_function", "softmax"),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("rotary_dim", 8), ("use_kda_lora", True),
    ("expert_swiglu_limit_list", [0, 0, 4] + [0] * 39)])
def test_model_config_refuses_by_the_keys_name(key, value):
    with pytest.raises(ValueError, match=key):
        family.model_config(dict(CONF, **{key: value}))


@pytest.mark.parametrize("kw,match", [
    (dict(layer_kinds=("kda", "lightning", "attention"), lightning_heads=4,
          lightning_head_dim=32), "attention layers only"),
    (dict(attn_bias=True), "attention bias"),
    (dict(qk_norm=True), "beside kda layers"),
    (dict(sparse_topk=4), "beside kda layers"),
    (dict(moe_n_group=3), "moe_n_group"),
    (dict(moe_topk_group=1, moe_top_k=4), "moe_n_group"),
    (dict(moe_scoring="softmax", moe_router_bias=False,
          moe_routed_scaling=1.0, moe_n_group=1, moe_topk_group=1,
          moe_held_count=0), "single sublayers"),
    (dict(kda_gate_lower_bound=1.0), "kda_gate_lower_bound"),
    (dict(num_layers=8), "one period"),
    (dict(moe_held_first=6), "moe_held_first")])
def test_the_config_refuses_what_is_still_not_served(kw, match):
    with pytest.raises(ValueError, match=match):
        replace(CFG, **kw)


def test_a_mamba_or_lightning_mixer_over_a_mixture_stays_refused():
    for name in ("tiny-jamba", "tiny-sala"):
        with pytest.raises(ValueError, match="single sublayers"):
            replace(get_model_config(name), moe_num_experts=4, moe_top_k=2)
    with pytest.raises(ValueError, match="MLA"):
        replace(get_model_config("tiny-jamba"), mla_kv_lora_rank=64,
                mla_rope_dim=16, mla_qk_nope_dim=32, mla_v_head_dim=32)
    with pytest.raises(ValueError, match="attn_gate_per_head"):
        replace(get_model_config("moe-wide-mla"), attn_output_gate=True)


def test_the_leaves_are_stacked_by_kind_and_the_banks_by_held_slot(params):
    assert params["attn_norm"].shape == params["mlp_norm"].shape == (7, 128)
    assert params["kda_wqkv"].shape == (6, 3 * 128, 128)
    assert params["kda_wf"].shape == params["kda_wg"].shape == (6, 128, 128)
    assert params["kda_wb"].shape == (6, 4, 128)
    assert params["kda_conv_w"].shape == (6, 4, 384)
    assert params["mla_wq"].shape == (1, 128, 4, 48)
    assert params["wg"].shape == (1, 128, 4)  # one scalar a head
    assert params["router"].shape == (6, 128, 8)
    assert params["router_bias"].shape == (6, 8)
    assert params["moe_wi"].shape == (6, 4, 128, 128)  # 4 held of 8
    assert params["wi"].shape == (1, 128, 384)  # the one leading dense layer
    state = init_state(CFG, SEATS)
    assert state["lin"].shape == (6, SEATS + 1, 4, 32, 32)
    assert state["lin"].dtype == jnp.float32
    assert state["conv"].shape == (6, 3, SEATS + 1, 384)
    assert init_cache(CFG, 8, PS).shape == (8, PS, 1, 128)  # one plane
    g = -5 * jax.nn.sigmoid(jnp.exp(params["kda_a_log"])[:, :, None] * (
        params["kda_dt_bias"].reshape(6, 4, 32)))
    assert float(g.min()) < -1.0 and float(g.max()) > -0.01


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
    assert _worst(low, want) > 100 * TOLERANCE
    # and the program's own bfloat16 pool, under the float32 name
    got, pools = _serve(params, tokens, (T,),
                        cfg=replace(CFG, lightning_state_dtype="bfloat16"))
    assert pools["lin"].dtype == jnp.bfloat16
    assert _worst(got, want[:T]) < 0.5  # (rounded once a call, not a token)


@pytest.mark.parametrize("key,value", FAULTS, ids=[k for k, _ in FAULTS])
def test_each_named_fault_fails_and_the_reference_has_the_mechanism(
        params, tokens, want, key, value):
    bad = family.logits(dict(SIZES, **{key: value}), params, tokens)
    # (without the q/k norm the delta rule's state can run away: nan)
    assert not _worst(bad, want) <= 1000 * TOLERANCE


def test_the_probed_pair_holds_what_the_latent_layers_positions_give(
        params, tokens, want):
    """``probed_pair``: the token the positions raise most against the one
    they lower most, with the reference's gap. RoPE left off then reads
    several times what it reads in the gap between the two best tokens, and a
    fault with no direction among the tokens (the scaling factor) reads alike
    in both."""
    sound = jnp.asarray(want[-32:])
    flat = family.logits(dict(SIZES, mla_rope=False), params, tokens)[-32:]
    pair = family.probed_pair(sound, flat)
    d = np.asarray(sound - flat)
    i = np.arange(32)
    a, b, gap = (np.asarray(c) for c in zip(*pair))
    assert (a == d.argmax(-1)).all() and (b == d.argmin(-1)).all()
    np.testing.assert_allclose(gap, want[-32:][i, a] - want[-32:][i, b],
                               rtol=1e-6)
    two = np.argsort(-want[-32:], axis=-1)[:, :2]

    def moved(rows, x, y):  # the median error of the gap between x and y
        rows = np.asarray(rows)
        return np.median(np.abs((rows - want[-32:])[i, x]
                                - (rows - want[-32:])[i, y]))

    assert moved(flat, a, b) > 3 * moved(flat, two[:, 0], two[:, 1])
    other = family.logits(dict(SIZES, scaling=1.0), params, tokens)[-32:]
    assert 0.25 < moved(other, a, b) / moved(other, two[:, 0], two[:, 1]) < 4


def test_the_programs_int8_stack_under_the_bf16_name_fails_the_dtype_check():
    """What stands in for the served int8 control, which cannot start at the
    cell's size (PERF.md section 7): the launcher's ``served_dtype_ok`` over
    this family's leaves refuses the program's own int8 stack under the
    file's bfloat16 name, exactly."""
    sys.path.append(os.path.join(ROOT, "perfbench"))
    try:
        import engine_child
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))
    from llmd_tpu.models.quant import quantize_params

    conf = dict(CONF, weights={"dtype": "bfloat16", "quantize": None})
    cfg = family.model_config(conf)
    stack = init_params(cfg, jax.random.PRNGKey(1))
    leaves = family.weight_leaves(conf)
    assert engine_child.served_dtype_ok(conf, leaves, stack)
    low, _ = quantize_params(cfg, stack)
    assert "moe_wi_q" in low and "moe_wi" not in low
    assert not engine_child.served_dtype_ok(conf, leaves, low)


def test_readings_name_the_probed_pair_and_the_served_tokens_deficits(
        params, tokens, want):
    got = family.readings(SIZES, params, [tokens[:140]], [tokens[140:]])
    flat = family.logits(dict(SIZES, mla_rope=False), params, tokens[:-1])
    pair = family.probed_pair(jnp.asarray(want[139:149]), flat[139:149])
    assert [r[:2] for r in got["top2"][0]] == [r[:2] for r in pair]
    np.testing.assert_allclose([r[2] for r in got["top2"][0]],
                               [r[2] for r in pair], atol=1e-5)
    served = np.asarray(tokens[140:])
    np.testing.assert_allclose(
        got["deficits"][0],
        want[139:149].max(-1) - want[139:149][np.arange(10), served],
        atol=1e-5)


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
    before = {k: np.asarray(pools[k][:, 0] if k == "lin" else pools[k][:, :, 0])
              for k in ("lin", "conv")}
    for at in range(n0, n0 + 6):
        toks = np.zeros(SEATS, np.int32)
        pos = np.full(SEATS, -1, np.int32)
        lens = np.zeros(SEATS, np.int32)
        toks[2], pos[2], lens[2] = tokens[at], at, at + 1
        hidden, pools = step(pools, jnp.asarray(toks), jnp.asarray(pos),
                             jnp.asarray(lens))
        got = np.asarray(unembed(CFG, params, hidden[2]))
        assert _worst(got, want[at]) < TOLERANCE
    # an idle seat's slots are left bit for bit
    assert (np.asarray(pools["lin"][:, 0]) == before["lin"]).all()
    assert (np.asarray(pools["conv"][:, :, 0]) == before["conv"]).all()


def test_the_mixture_layers_report_counts_by_held_slot(params, tokens):
    pools = _pools()
    pt = np.full((2, MAXP), -1, np.int32)
    pt[0] = np.arange(MAXP)
    n = 64
    _, _, cnt, drop = forward_core(
        CFG, params, pools, jnp.asarray(tokens[:n]), jnp.arange(n),
        jnp.zeros(n, jnp.int32), jnp.asarray(pt), jnp.asarray([n, 0]),
        cu_q_lens=jnp.asarray([0, n, n]), num_seqs=jnp.asarray([1]),
        state_slots=jnp.asarray([0, SEATS]), moe_dispatch_impl=SORTED)
    dropped, moved, routed, held, kept = (int(v) for v in drop)
    assert cnt.shape == (6, 4) and int(cnt.sum()) == held
    assert routed == 6 * n * 2 and 0 < held < routed and dropped == 0
    assert 0 < moved < routed and 0 < kept < routed


# ----------------------------------------------------------- the experts
def _layer_inputs(seed=0, t=40, E=8, D=128, F=64):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (t, D), jnp.float32)
    return (x, jax.random.normal(k[1], (D, E)) * D ** -0.5,
            jax.random.normal(k[2], (E, D, 2 * F)) * D ** -0.5,
            jax.random.normal(k[3], (E, F, D)) * F ** -0.5,
            jax.random.normal(k[4], (E,)) * 0.1)


WHOLE = replace(CFG, moe_held_first=0, moe_held_count=0)


def _choice(cfg, x, router, bias):
    """[T, E] 0/1: the experts ``moe_block`` routed each token to."""
    E = cfg.moe_num_experts
    seen = {}  # what the dispatch is handed: the choice and its weights

    def spy(x, idx, topw, valid, wi, wo, wi_scale, wo_scale, **kw):
        seen.update(idx=idx, topw=topw)
        return jnp.zeros_like(x)

    spy.stacked_banks = spy.ordered_combine = True
    moe_block(cfg, x, router, jnp.zeros((E, 128, 128)),
              jnp.zeros((E, 64, 128)), router_bias=bias, dispatch_impl=spy)
    hot = jnp.zeros((x.shape[0], E)).at[
        jnp.arange(x.shape[0])[:, None], seen["idx"]].set(seen["topw"])
    return np.asarray(hot)


@pytest.mark.parametrize("groups,kept", [(1, 1), (4, 4), (4, 2), (2, 1)])
def test_grouped_top_k_is_the_plain_one_at_one_group_and_the_references(
        groups, kept):
    x, router, _, _, bias = _layer_inputs(t=200)
    cfg = replace(WHOLE, moe_n_group=groups, moe_topk_group=kept)
    got = _choice(cfg, x, router, bias)
    ref = np.asarray(family.route(
        x @ router, bias, top_k=2, scaling=2.5, n_group=groups,
        topk_group=kept))
    assert np.abs(got - ref).max() < 1e-6 and ((got > 0) == (ref > 0)).all()
    plain = _choice(replace(WHOLE, moe_n_group=1, moe_topk_group=1), x,
                    router, bias)
    # every group kept is no limit; fewer moves some choice
    assert ((got > 0) == (plain > 0)).all() == (groups == kept)
    if groups > 1:
        # a token's experts lie in `kept` groups at most
        per = 8 // groups
        used = (got.reshape(200, groups, per) > 0).any(-1).sum(-1)
        assert used.max() <= kept


def test_the_shares_add_up_to_the_uncut_layer():
    """Held 0-1, 2-3, 4-5 and 6-7 of 8 experts (four chips share the layer)
    equal the whole layer's routed part; the shared expert is every device's
    alike and counted once, which the reference's uncut layer does."""
    x, router, wi, wo, bias = _layer_inputs()
    k = jax.random.split(jax.random.PRNGKey(9), 2)
    swi = jax.random.normal(k[0], (128, 128)) * 128 ** -0.5
    swo = jax.random.normal(k[1], (64, 128)) * 64 ** -0.5
    w = {"mlp_norm": jnp.ones((128,)), "router": router, "router_bias": bias,
         "shared_wi": swi, "shared_wo": swo}
    banks = {"moe_wi": wi[None], "moe_wo": wo[None]}
    with jax.default_matmul_precision("highest"):
        g = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        y_all, cnt_all = moe_block(WHOLE, g, router, wi, wo, router_bias=bias,
                                   dispatch_impl=SORTED)
        parts, counts = [], []
        for first in (0, 2, 4, 6):
            cfg = replace(CFG, moe_held_first=first, moe_held_count=2)
            y, cnt, drop = moe_block(cfg, g, router, wi[first:first + 2],
                                     wo[first:first + 2], router_bias=bias,
                                     dispatch_impl=SORTED, return_dropped=True)
            parts.append(y)
            counts.append(cnt)
            assert int(drop[3]) == int(cnt.sum()) and int(drop[2]) == 80
        # the uncut layer of the program, and the reference's with the
        # shared expert once
        ref = family.mixture(x, w, banks, 0, eps=1e-6, top_k=2, scaling=2.5,
                             n_group=4, topk_group=2, held_first=0,
                             held=8) - x
        gate, up = jnp.split(g @ swi, 2, axis=-1)
        shared = (jax.nn.silu(gate) * up) @ swo
    assert _worst(sum(parts), y_all) < 2e-6
    assert (np.concatenate(counts) == np.asarray(cnt_all)).all()
    assert _worst(sum(parts) + shared, ref) < 1e-4


def test_the_bias_scale_moves_a_tenth_and_loads_no_expert_twice_the_mean():
    """The configuration's ``router_bias_scale`` from the router's arithmetic
    alone: 512 experts in 8 groups of which 4 are kept, top-8, scores of
    logits of unit variance."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        scale = json.load(f)["router_bias_scale"]
    key = jax.random.PRNGKey(0)
    kw = dict(top_k=8, scaling=1.0, n_group=8, topk_group=4)
    for _ in range(2):
        key, a, b = jax.random.split(key, 3)
        g = jax.random.normal(a, (8192, 512))
        bias = jax.random.normal(b, (512,)) * scale
        chosen = np.asarray(family.route(g, bias, **kw)) > 0
        plain = np.asarray(family.route(g, 0 * bias, **kw)) > 0
        moved = (chosen & ~plain).sum() / chosen.sum()
        load = chosen.sum(0)
        assert 0.07 < moved < 0.16 and load.max() / load.mean() < 2.0


# --------------------------------------------------------------- the kernel
def _ragged(lens, live, fresh, seed=0, H=4, D=32, S=9):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    nt, nb = sum(lens) + 5, len(lens)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return dict(
        q=unit(jax.random.normal(k[0], (nt, H, D))) * D ** -0.5,
        k=unit(jax.random.normal(k[1], (nt, H, D))),
        v=jax.random.normal(k[2], (nt, H, D)),
        # log-decays from near the bound to near nothing
        g=-5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(k[3], (nt, H, D))),
        b=jax.nn.sigmoid(jax.random.normal(k[4], (nt, H))),
        pool=jax.random.normal(k[5], (S, H, D, D)),
        slots=jnp.asarray([3, 1, 4, 0, 7, 8, 2][:nb], jnp.int32),
        cu_q_lens=jnp.asarray(np.concatenate([[0], np.cumsum(lens)]),
                              jnp.int32),
        live=jnp.asarray(live), fresh=jnp.asarray(fresh))


def _naive(a):
    """The recurrence of the module's first lines, a row and a head at a
    time in numpy float64, the state [key, value] as published."""
    q, k, v, g, b = (np.asarray(a[n], np.float64) for n in "qkvgb")
    pool = np.asarray(a["pool"], np.float64).copy()
    o = np.zeros_like(q)
    cu = np.asarray(a["cu_q_lens"])
    for r, slot in enumerate(np.asarray(a["slots"])):
        if not bool(a["live"][r]):
            continue
        for h in range(q.shape[1]):
            s = np.zeros_like(pool[slot, h]) if bool(a["fresh"][r]) \
                else pool[slot, h].T.copy()
            for t in range(cu[r], cu[r + 1]):
                s = np.exp(g[t, h])[:, None] * s
                s = s - b[t, h] * np.outer(k[t, h], k[t, h] @ s) \
                    + b[t, h] * np.outer(k[t, h], v[t, h])
                o[t, h] = s.T @ q[t, h]
            pool[slot, h] = s.T
    return o, pool


RAGGED = {
    "mixed": ([1, 37, 1, 70, 5, 0], [1, 1, 0, 1, 1, 0], [0, 1, 0, 0, 1, 0]),
    "decode": ([1] * 6, [1, 1, 1, 0, 1, 1], [0, 0, 1, 0, 0, 0]),
    "one-chunk": ([130], [1], [1]),
    "block-and-one": ([BLOCK + 1, 1], [1, 1], [0, 0]),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_the_kernel_the_xla_form_and_the_naive_scan_agree(case):
    lens, live, fresh = RAGGED[case]
    a = _ragged(lens, np.asarray(live, bool), np.asarray(fresh, bool))
    y0, p0 = kda_attention_xla(**a)
    y1, p1 = kda_attention_pallas(**a, interpret=True)
    y2, p2 = _naive(a)
    # float32 against float64 of the same sums in another order: |y| <= 3
    assert _worst(y0, y2) < 2e-5 and _worst(p0, p2) < 2e-5
    assert _worst(y1, y2) < 2e-5 and _worst(p1, p2) < 2e-5
    dead = np.asarray([s for s, l in zip(np.asarray(a["slots"]), live)
                       if not l] + [5, 6], np.int32)
    for p in (p0, p1):  # idle and frozen rows leave their slots bit for bit
        assert (np.asarray(p[dead]) == np.asarray(a["pool"][dead])).all()
    rows = np.repeat(np.arange(len(lens)), lens)
    off = ~np.asarray(live, bool)[rows]
    assert not np.asarray(y1[:len(rows)])[off].any()
    assert not np.asarray(y0[:len(rows)])[off].any()


def test_a_fresh_row_starts_from_zeros_whatever_its_slot_held():
    lens, live, fresh = [40], np.asarray([True]), np.asarray([True])
    a = _ragged(lens, live, fresh)
    y0, p0 = kda_attention_pallas(**a, interpret=True)
    y1, p1 = kda_attention_pallas(**dict(a, pool=a["pool"] * 0 + 9.0),
                                  interpret=True)
    assert (np.asarray(y0) == np.asarray(y1)).all()
    assert (np.asarray(p0[3]) == np.asarray(p1[3])).all()


def test_a_token_does_not_depend_on_its_chunk_or_its_neighbours():
    """A prompt's blocks are its own: one call, chunks that start on
    multiples of the block, and other rows beside it give the same bits."""
    n = 2 * BLOCK + 9
    a = _ragged([n], np.asarray([True]), np.asarray([True]))
    whole_y, whole_p = kda_attention_pallas(**a, interpret=True)
    ys, pool = [], a["pool"]
    for at, m, fr in ((0, BLOCK, True), (BLOCK, BLOCK + 9, False)):
        part = {k: a[k][at:at + m] for k in "qkvgb"}
        # the same rows beside a decode row of another slot, placed first
        other = {k: a[k][n:n + 1] for k in part}
        y, pool = kda_attention_pallas(
            **{k: jnp.concatenate([other[k], part[k]]) for k in part},
            pool=pool, slots=jnp.asarray([6, 3], jnp.int32),
            cu_q_lens=jnp.asarray([0, 1, 1 + m], jnp.int32),
            live=jnp.asarray([True, True]), fresh=jnp.asarray([False, fr]),
            interpret=True)
        ys.append(y[1:])
    assert (np.asarray(jnp.concatenate(ys)) == np.asarray(whole_y[:n])).all()
    assert (np.asarray(pool[3]) == np.asarray(whole_p[3])).all()


# ------------------------------------------- the block of one token (ISSUE 53)
def _decode_rows(gate, nb=64, seed=1):
    """``nb`` rows of one token in slots of their own, live, idle and fresh
    mixed; ``gate``: every channel's log-decay at the bound, near nothing,
    or lane by lane one and the other."""
    live = np.arange(nb) % 5 != 2
    fresh = np.arange(nb) % 7 == 3
    a = _ragged([1] * nb, live, fresh, seed=seed, S=nb + 6)
    g = {"bound": jnp.full_like(a["g"], -4.999),
         "near-nothing": jnp.full_like(a["g"], -1e-4),
         "both": jnp.full_like(a["g"], -4.999).at[:, :, ::2].set(-1e-4),
         "drawn": a["g"]}[gate]
    slots = np.random.default_rng(seed).permutation(nb + 6)[:nb]
    return dict(a, g=g, slots=jnp.asarray(slots, jnp.int32)), live, fresh


@pytest.mark.parametrize("gate", ["bound", "near-nothing", "both", "drawn"])
def test_one_token_rows_agree_with_the_xla_form_and_the_naive_scan(gate):
    """The fused decode call's shape: 64 blocks of one token, in float32
    lanes where the parent ran two matrix products a head."""
    a, live, _ = _decode_rows(gate)
    y0, p0 = kda_attention_xla(**a)
    y1, p1 = kda_attention_pallas(**a, interpret=True)
    y2, p2 = _naive(a)
    # the tolerance of the ragged cases above: |y| <= 3, float32 against
    # float64 of the same sums in another order
    assert _worst(y0, y2) < 2e-5 and _worst(p0, p2) < 2e-5
    assert _worst(y1, y2) < 2e-5 and _worst(p1, p2) < 2e-5
    idle = np.asarray(a["slots"])[~live]
    assert (np.asarray(p1[idle]) == np.asarray(a["pool"][idle])).all()
    assert not np.asarray(y1[:64])[~live].any()
    assert np.asarray(y1[:64])[live].any(axis=(1, 2)).all()


def test_a_one_token_row_is_the_same_bits_in_both_step_programs_calls():
    """A decode row of the fused call and the same row beside a 256-token
    chunk of a unified step: one arithmetic, the same o and the same slot
    (what ``cold_equals_cached`` rests on in the served cell)."""
    a, live, fresh = _decode_rows("drawn", nb=8)
    y0, p0 = kda_attention_pallas(**a, interpret=True)
    chunk = _ragged([256], np.asarray([True]), np.asarray([True]), seed=2)
    both = {k: jnp.concatenate([a[k][:8], chunk[k][:256]]) for k in "qkvgb"}
    spare = sorted(set(range(14)) - set(np.asarray(a["slots"]).tolist()))[0]
    y1, p1 = kda_attention_pallas(
        **both, pool=a["pool"],
        slots=jnp.concatenate([a["slots"], jnp.asarray([spare], jnp.int32)]),
        cu_q_lens=jnp.asarray(list(range(9)) + [8 + 256], jnp.int32),
        live=jnp.asarray(list(live) + [True]),
        fresh=jnp.asarray(list(fresh) + [True]), interpret=True)
    assert (np.asarray(y0[:8]) == np.asarray(y1[:8])).all()
    rows = np.asarray(a["slots"])
    assert (np.asarray(p0[rows]) == np.asarray(p1[rows])).all()
    assert np.asarray(y1[8:264]).any() and live.any() and not live.all()


def test_a_chunks_last_token_alone_is_the_one_token_call():
    """33 tokens are two blocks and a token that stands alone: the same
    bits as the 32 tokens in one call and the last in a call of its own,
    whose state comes from the slot where the chunk's came from the
    scratch."""
    n = 2 * BLOCK + 1
    a = _ragged([n], np.asarray([True]), np.asarray([False]), seed=3)
    whole_y, whole_p = kda_attention_pallas(**a, interpret=True)
    ys, pool = [], a["pool"]
    for at, m in ((0, n - 1), (n - 1, 1)):
        y, pool = kda_attention_pallas(
            **{k: a[k][at:at + m] for k in "qkvgb"}, pool=pool,
            slots=a["slots"], cu_q_lens=jnp.asarray([0, m], jnp.int32),
            live=a["live"], fresh=a["fresh"], interpret=True)
        ys.append(y)
    assert (np.asarray(jnp.concatenate(ys)) == np.asarray(whole_y[:n])).all()
    assert (np.asarray(pool[3]) == np.asarray(whole_p[3])).all()


# sha256 of o and of the pool as the parent's kernel (bbe8e08) gave them in
# interpret mode on this CPU for RAGGED["one-chunk"] at seed 0: eight blocks
# of sixteen tokens and one of two, none of one token
PARENT_BLOCKS = (
    "2ed6d6f0d8765c7f3ece0ccac33df98689a7544df4a947641745826c79d7d0ac",
    "e045b0d2df2d9baf64613ebba8ebebd60ec449fd8db753e84e3630a4a37ed763")


def test_blocks_of_more_than_one_token_are_the_parents_bits():
    lens, live, fresh = RAGGED["one-chunk"]
    assert all(n % BLOCK != 1 for n in lens)
    a = _ragged(lens, np.asarray(live, bool), np.asarray(fresh, bool))
    y, pool = kda_attention_pallas(**a, interpret=True)
    assert tuple(hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
                 for x in (y, pool)) == PARENT_BLOCKS


def test_a_blocks_log_decay_stays_a_float32_at_the_gates_bound():
    """Sixteen tokens at the bound itself: exp(80) is finite, and the kernel
    gives what the scan does."""
    a = _ragged([BLOCK], np.asarray([True]), np.asarray([False]))
    a = dict(a, g=jnp.full_like(a["g"], -4.999).at[:, :, ::2].set(-1e-4))
    y0, p0 = kda_attention_xla(**a)
    y1, p1 = kda_attention_pallas(**a, interpret=True)
    assert np.isfinite(np.asarray(y1)).all() and _worst(y0, y1) < 2e-5
    assert _worst(p0, p1) < 2e-5


def test_the_pallas_kernel_serves_the_stack_as_the_xla_form_does(
        params, tokens, want):
    via, pools_k = _serve(
        params, tokens, (128, 22), nt=160,
        kda_impl=functools.partial(kda_attention_pallas, interpret=True))
    assert _worst(via, want[:150]) < TOLERANCE


# ------------------------------------------------------------- the roofline
def test_the_roofline_reads_the_demand_and_cannot_pass_it():
    from kernels.lightning_attention import prom

    with open(os.path.join(ROOT, "perfbench", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        conf = json.load(f)
    H, d = 32, 128
    ops, byts = kda_roofline.cost(64.0, 64.0, H, d)
    # a decode row: its state read and written, 64 KB a head each way, is
    # all but 2% of the bytes, and tens of times the operations' time
    assert byts == 64 * (2 * H * d * d * 4 + H * (d * 20 + 4))
    assert ops == 64 * H * 8 * d * d
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert kda_roofline.least_seconds(ops, byts, peaks) == byts / 819e9 \
        > 50 * ops / 197e12
    scrape = prom.parse("""
llmd_tpu:decode_call_steps_total{bound="cap"} 1000
llmd_tpu:linear_attn_tokens_total{rows="decode"} 70300
llmd_tpu:linear_attn_tokens_total{rows="prefill"} 25600
llmd_tpu:unified_decode_rows_total{token="ahead"} 6300
llmd_tpu:engine_program_dispatches_total{program="unified"} 100
""")
    ctx = {"config": conf, "device": {"kind": "TPU v5 lite"},
           "before": {"engine": []}, "after": {"engine": scrape}}
    for module, rows, tokens in (("decode", 64.0, 64.0),
                                 ("unified", 64.0, 319.0)):
        src = {"kernel": "kda_attention", "pattern": "kda_attention",
               "module": module}
        assert kda_roofline.demand(src, ctx) == (rows, tokens)
        t_min = kda_roofline.least_seconds(
            *kda_roofline.cost(rows, tokens, H, d), peaks)
        # 6 calls that took twice the least time read 50%; no call, nothing
        ctx["trace"] = {"modules": {f"jit__{module}(1)": {"ops": {
            "kda_attention.3": {"count": 6, "seconds": 12 * t_min}}}}}
        assert abs(kda_roofline.roofline(src, ctx) - 0.5) < 1e-9
        ctx["trace"] = {"modules": {f"jit__{module}(1)": {"ops": {}}}}
        assert kda_roofline.roofline(src, ctx) is None
    # a program without the counters (the parent) reads nothing
    ctx["after"] = {"engine": []}
    ctx["trace"] = {"modules": {"jit__decode(1)": {"ops": {
        "kda_attention.3": {"count": 6, "seconds": 1.0}}}}}
    assert kda_roofline.roofline(
        {"pattern": "kda_attention", "module": "decode"}, ctx) is None
    assert ModelConfig.kda_gate_lower_bound == -5.0
