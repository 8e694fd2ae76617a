"""The family module is where the harness learns a model: for every
configuration file, ``reference/<family>.py::model_config`` agrees field by
field with the file's published keys, and ``engine_child.served_dtype_ok`` is
one rule over the family's ``weight_leaves``."""

import glob
import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

import engine_child
from conftest import BENCH, HERE

from llmd_tpu.models.quant import quantize_params
from llmd_tpu.models.transformer import init_cache, init_params

CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))
                 + glob.glob(os.path.join(HERE, "tiny*.json")))
# the chip rehearsal's shape: too wide to initialise in a test
WIDE = [os.path.join(HERE, "moe-shape.json")]
# ModelConfig field -> the key of the file it must equal
FIELDS = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
          "num_layers": "num_hidden_layers",
          "num_heads": "num_attention_heads",
          "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
          "rope_theta": "rope_theta", "rms_eps": "rms_norm_eps",
          "max_position": "max_position_embeddings",
          "tie_embeddings": "tie_word_embeddings", "attn_bias": "attention_bias"}


def load(path: str):
    with open(path) as f:
        conf = json.load(f)
    return conf, importlib.import_module("reference." + conf["reference"])


def test_a_rehearsal_manifests_overrides_make_the_int8_variant():
    import run as bench

    with open(os.path.join(HERE, "manifest-chip.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "moe-shape-2048-int8"]
    conf, family = load(os.path.join(HERE, os.path.basename(entry["file"])))
    low = bench.with_overrides(conf, entry["overrides"])
    assert (low["name"], low["num_hidden_layers"], low["weights"]) == (
        "moe-shape-2048-int8", 4, {"dtype": "bfloat16", "quantize": "int8"})
    assert low["engine"] == dict(conf["engine"], num_pages=6144)
    assert conf["weights"]["quantize"] is None  # the file's own is untouched
    a, b = family.model_config(conf), family.model_config(low)
    assert (a.num_layers, b.num_layers) == (7, 4)
    assert a.hidden_size == b.hidden_size and a.moe_top_k == b.moe_top_k


def small(conf: dict) -> dict:
    """The file's family and keys at a depth and width a test can hold."""
    return dict(conf, num_hidden_layers=2, vocab_size=288)


@pytest.mark.parametrize("path", CONFIGS + WIDE, ids=os.path.basename)
def test_model_config_agrees_with_the_published_keys(path):
    conf, family = load(path)
    cfg = family.model_config(conf)
    assert cfg.name == conf["name"] and cfg.dtype == conf["weights"]["dtype"]
    for field, key in FIELDS.items():
        assert getattr(cfg, field) == conf[key], (field, key)
    assert not cfg.qk_norm and not cfg.is_mla and cfg.mm_tokens == 0
    s = family.sizes(conf)
    assert (s["layers"], s["heads"], s["kv_heads"], s["head_dim"]) == (
        cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    assert (s["eps"], s["theta"], s["tied"]) == (
        cfg.rms_eps, cfg.rope_theta, cfg.tie_embeddings)
    if conf["reference"] == "dense_gqa":
        assert cfg.intermediate_size == conf["intermediate_size"]
        assert not cfg.is_moe


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
@pytest.mark.parametrize("quantize", [None, "int8"])
def test_served_dtype_ok_is_one_rule_over_the_familys_leaves(path, quantize):
    conf, family = load(path)
    conf = small(dict(conf, weights={"dtype": "bfloat16",
                                     "quantize": quantize}))
    cfg = family.model_config(conf)
    leaves = family.weight_leaves(conf)
    params = init_params(cfg, jax.random.PRNGKey(1))
    other, _ = quantize_params(cfg, params)
    if quantize:
        params, other = other, params
    cache = init_cache(cfg, 4, 16, dtype=jnp.bfloat16)
    ok = engine_child.served_dtype_ok
    assert ok(conf, leaves, params) and ok(conf, leaves, params, cache)
    assert not ok(conf, leaves, other)
    # the pool in another type than the file states
    assert not ok(conf, leaves, params, cache.astype(jnp.float8_e4m3fn))
    for leaf in leaves:
        # one leaf left in the other stack's form
        mixed = {k: v for k, v in params.items()
                 if k not in (leaf, leaf + "_q", leaf + "_scale")}
        mixed.update({k: other[k] for k in (leaf, leaf + "_q", leaf + "_scale")
                      if k in other})
        assert not ok(conf, leaves, mixed), leaf
        # a float leaf beside its int8 form
        if quantize:
            assert not ok(conf, leaves, dict(params, **{leaf: other[leaf]}))
        # the stated type under another name: float32 for bfloat16
        else:
            assert not ok(conf, leaves, dict(
                params, **{leaf: params[leaf].astype(jnp.float32)}))
