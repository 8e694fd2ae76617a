"""The mixture-of-experts family (``reference/moe_gqa.py``): its block against
the definition written as a loop over tokens, the program's ``forward`` on
``tiny-moe.json`` against its full forward pass (prefill, then decode through
the paged cache), and what its ``model_config`` refuses."""

import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE

from llmd_tpu.models.quant import quantize_params
from llmd_tpu.models.transformer import forward, init_cache, init_params
from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

from reference import moe_gqa

with open(os.path.join(HERE, "tiny-moe.json")) as f:
    CONF = json.load(f)
# float32 on both sides: what is left is the order of the sums. Read on the
# CPU over the eight cases below: at most 1.8e-6, on logits of standard
# deviation 0.23. The control, the same stack with only the expert banks
# rounded to bfloat16 (bf16 expert arithmetic under a float32 name), reads
# 2.2e-3 and 3.7e-3: the limit stands 28 times above the one and 44 times
# below the other.
TOLERANCE = 5e-5
T, DECODED = 48, 6


def _silu(a):
    return a / (1.0 + np.exp(-a))


@pytest.mark.parametrize("norm_topk", [True, False])
def test_mixture_is_each_tokens_k_experts_summed(norm_topk):
    """``mixture`` against the published definition, token by token in
    float64 numpy: softmax over all experts, the k largest, renormalised or
    not, each as a SwiGLU with separate gate and up halves, summed."""
    rng = np.random.default_rng(3)
    t, d, f, e, k, fs = 12, 16, 24, 8, 3, 40
    x = rng.normal(size=(t, d))
    w = {"mlp_norm": 1 + 0.1 * rng.normal(size=d),
         "router": rng.normal(size=(d, e)),
         "shared_wi": rng.normal(size=(d, 2 * fs)) * d ** -0.5,
         "shared_wo": rng.normal(size=(fs, d)) * fs ** -0.5}
    wi = rng.normal(size=(2, e, d, 2 * f)) * d ** -0.5
    wo = rng.normal(size=(2, e, f, d)) * f ** -0.5
    want = np.zeros((t, d))
    for i in range(t):
        h = x[i] / np.sqrt(np.mean(x[i] ** 2) + 1e-6) * w["mlp_norm"]
        z = h @ w["router"]
        p = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        top = np.argsort(-p)[:k]
        pw = p[top] / p[top].sum() if norm_topk else p[top]
        y = np.zeros(d)
        for j, a in zip(top, pw):
            gate, up = h @ wi[1, j, :, :f], h @ wi[1, j, :, f:]
            y += a * ((_silu(gate) * up) @ wo[1, j])
        gate, up = h @ w["shared_wi"][:, :fs], h @ w["shared_wi"][:, fs:]
        want[i] = x[i] + y + (_silu(gate) * up) @ w["shared_wo"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = moe_gqa.mixture(
            f32(x), {n: f32(v) for n, v in w.items()},
            {"moe_wi": f32(wi), "moe_wo": f32(wo)}, 1, top_k=k,
            norm_topk=norm_topk, eps=1e-6)
    assert np.max(np.abs(np.asarray(got) - want)) < 2e-5
    if not norm_topk:  # and the two settings are not the same function
        other = moe_gqa.mixture(
            f32(x), {n: f32(v) for n, v in w.items()},
            {"moe_wi": f32(wi), "moe_wo": f32(wo)}, 1, top_k=k,
            norm_topk=True, eps=1e-6)
        assert np.max(np.abs(np.asarray(other) - want)) > 1e-2


def _served_logits(cfg, params, tokens, dispatch):
    """The program's logits of every position: a prefill of all but the last
    ``DECODED`` tokens, then those one at a time over the paged cache."""
    cache = init_cache(cfg, 8, 16, dtype=cfg.jax_dtype)
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    kw = {"moe_dispatch_impl": make_sorted_dispatch()} if dispatch == "sorted" \
        else {}
    n = len(tokens) - DECODED
    out, cache, _ = forward(cfg, params, cache, jnp.asarray(tokens[:n])[None],
                            jnp.arange(n, dtype=jnp.int32)[None], pt,
                            jnp.asarray([n], jnp.int32), **kw)
    rows = [np.asarray(out[0], np.float32)]
    for p in range(n, len(tokens)):
        out, cache, _ = forward(cfg, params, cache,
                                jnp.asarray([[tokens[p]]]),
                                jnp.asarray([[p]], jnp.int32), pt,
                                jnp.asarray([p + 1], jnp.int32), **kw)
        rows.append(np.asarray(out[0], np.float32))
    return np.concatenate(rows)


def _case(shared: int, int8: bool):
    conf = dict(CONF, n_shared_experts=shared,
                weights={"dtype": "float32",
                         "quantize": "int8" if int8 else None})
    # a capacity at which the einsum dispatch drops nothing: every token of a
    # step may choose the same expert
    cfg = replace(moe_gqa.model_config(conf), moe_capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(11 + shared))
    if int8:
        params, _ = quantize_params(cfg, params)
    tokens = [int(t) for t in
              np.random.default_rng(shared).integers(2, 288, size=T)]
    return conf, cfg, params, tokens


@pytest.mark.parametrize("shared", [0, 1])
@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("dispatch", ["sorted", "einsum"])
def test_forward_through_the_cache_agrees_with_the_reference(dispatch, int8,
                                                             shared):
    conf, cfg, params, tokens = _case(shared, int8)
    assert cfg.moe_num_shared_experts == shared
    assert ("shared_wi_q" if int8 else "shared_wi" in params) or not shared
    want = np.asarray(moe_gqa.logits(moe_gqa.sizes(conf), params, tokens))
    with jax.default_matmul_precision("highest"):
        got = _served_logits(cfg, params, tokens, dispatch)
    assert got.shape == want.shape == (T, 288)
    assert np.max(np.abs(got - want)) < TOLERANCE, np.max(np.abs(got - want))


def test_the_tolerance_sees_bfloat16_expert_arithmetic():
    """The control: the same float32 stack served with its expert banks
    rounded to bfloat16 lies far outside the tolerance."""
    conf, cfg, params, tokens = _case(1, False)
    want = np.asarray(moe_gqa.logits(moe_gqa.sizes(conf), params, tokens))
    low = dict(params, **{k: params[k].astype(jnp.bfloat16).astype(jnp.float32)
                          for k in ("moe_wi", "moe_wo")})
    with jax.default_matmul_precision("highest"):
        got = _served_logits(cfg, low, tokens, "sorted")
    assert np.max(np.abs(got - want)) > 10 * TOLERANCE


def test_deficits_of_the_programs_own_tokens():
    conf, cfg, params, tokens = _case(1, True)
    sizes = moe_gqa.sizes(conf)
    want = np.asarray(moe_gqa.logits(sizes, params, tokens))
    served, wrong = [int(np.argmax(want[-1]))], [int(np.argmin(want[-1]))]
    d = moe_gqa.deficits(sizes, params, [tokens, tokens], [served, wrong])
    assert d[0] == [0.0] and d[1][0] > 0.25


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("qk_norm", True), ("clip_qkv", 8.0),
    ("scoring_func", "sigmoid"), ("first_k_dense_replace", 1),
    ("shared_expert_gate", True)])
def test_model_config_refuses_by_name_what_the_program_cannot_express(key,
                                                                      value):
    with pytest.raises(ValueError, match=key):
        moe_gqa.model_config(dict(CONF, **{key: value}))


def test_model_config_reads_the_published_mixture_keys():
    cfg = moe_gqa.model_config(CONF)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_intermediate_size,
            cfg.moe_num_shared_experts, cfg.intermediate_size) == (
        8, 2, 128, 1, 256)
    # OLMoE's and Mixtral's names: the expert width is intermediate_size
    olmoe = {k: v for k, v in CONF.items() if k not in (
        "num_experts", "moe_intermediate_size", "n_shared_experts",
        "shared_expert_intermediate_size")}
    cfg = moe_gqa.model_config(dict(olmoe, num_local_experts=64,
                                    num_experts_per_tok=8,
                                    intermediate_size=1024))
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_intermediate_size,
            cfg.moe_num_shared_experts) == (64, 8, 1024, 0)
    assert moe_gqa.weight_leaves(olmoe) == ("wq", "wk", "wv", "wo", "moe_wi",
                                            "moe_wo")
    with pytest.raises(KeyError, match="norm_topk_prob"):
        moe_gqa.model_config({k: v for k, v in CONF.items()
                              if k != "norm_topk_prob"})


def test_rehearsal_of_the_whole_harness_on_a_mixture_cell():
    """``rehearsal-moe`` through parent, engine child, router, check, window
    and result line, as ``test_rehearsal.py`` runs the dense cells."""
    from test_rehearsal import _run

    r = _run("rehearsal-moe", 1)
    assert r["device"]["platform"] == "cpu" and r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["compiles_in_window"]["value"] == 0
