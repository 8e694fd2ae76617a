"""The float32 reference against the program's ``forward_core`` at a tiny
width on the CPU, bf16 and int8 stacks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_tpu.models.config import ModelConfig
from llmd_tpu.models.quant import quantize_params
from llmd_tpu.models.transformer import forward, init_cache, init_params

from reference import dense_gqa

SIZES = dict(layers=3, heads=4, kv_heads=2, head_dim=32, eps=1e-6,
             theta=1e6, tied=True)


def _cfg(dtype, tied=True, bias=True):
    return ModelConfig(name="t", vocab_size=320, hidden_size=128,
                       intermediate_size=256, num_layers=3, num_heads=4,
                       num_kv_heads=2, head_dim=32, rope_theta=1e6,
                       rms_eps=1e-6, tie_embeddings=tied, dtype=dtype,
                       attn_bias=bias)


def _program_logits(cfg, params, tokens):
    t = len(tokens)
    cache = init_cache(cfg, 8, 16, dtype=cfg.jax_dtype)
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    out = forward(cfg, params, cache, jnp.asarray(tokens)[None, :],
                  jnp.arange(t, dtype=jnp.int32)[None, :], pt,
                  jnp.asarray([t], jnp.int32))
    return np.asarray(out[0][0], np.float32)


@pytest.mark.parametrize("tied,bias,quant", [
    (True, True, False), (False, False, False), (False, False, True)])
def test_reference_agrees_with_forward_core_in_float32(tied, bias, quant):
    cfg = _cfg("float32", tied, bias)
    params = init_params(cfg, jax.random.PRNGKey(5))
    if bias:  # the program initialises biases to zero: make them count
        for i, k in enumerate(("bq", "bk", "bv", "bo")):
            params[k] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(10 + i), params[k].shape, jnp.float32)
    if quant:
        params, _ = quantize_params(cfg, params)
    tokens = list(np.random.default_rng(0).integers(2, 320, size=40))
    sizes = dict(SIZES, tied=tied)
    with jax.default_matmul_precision("highest"):
        want = _program_logits(cfg, params, tokens)
    got = np.asarray(dense_gqa.logits(sizes, params, tokens))
    assert got.shape == want.shape == (40, 320)
    assert np.max(np.abs(got - want)) < 2e-4
    # teacher forcing: the program's own greedy tokens have no deficit
    served = [int(np.argmax(want[-1]))]
    wrong = [int(np.argmin(want[-1]))]
    d = dense_gqa.deficits(sizes, params, [tokens, tokens], [served, wrong])
    assert d[0] == [0.0] and d[1][0] > 0.25
    # several served tokens, sequences of different lengths, more of them
    # than one group holds
    seq = [int(t) for t in tokens]
    many = dense_gqa.deficits(
        sizes, params, [seq[:n] for n in range(20, 30)],
        [seq[n:n + 2] for n in range(20, 30)])
    assert len(many) == 10
    for n, m in zip(range(20, 30), many):
        for i in (0, 1):
            row = want[n - 1 + i]
            assert m[i] == pytest.approx(float(row.max() - row[seq[n + i]]),
                                         abs=2e-4)


def test_reference_sees_a_lower_precision_than_stated():
    """bf16 activations stay inside the margin; a stack quantised to int8
    against the bf16 one it claims to be does not pass the dtype check the
    launcher makes (engine_child.served_dtype_ok over the family's leaves)."""
    import engine_child

    cfg = _cfg("bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(2))
    q, _ = quantize_params(cfg, params)
    leaves = dense_gqa.weight_leaves({})
    assert leaves == ("wq", "wk", "wv", "wo", "wi", "wo_mlp")
    conf = {"weights": {"dtype": "bfloat16", "quantize": None}}
    assert engine_child.served_dtype_ok(conf, leaves, params)
    assert not engine_child.served_dtype_ok(conf, leaves, q)
    conf8 = {"weights": {"dtype": "bfloat16", "quantize": "int8"}}
    assert engine_child.served_dtype_ok(conf8, leaves, q)
    assert not engine_child.served_dtype_ok(conf8, leaves, params)
