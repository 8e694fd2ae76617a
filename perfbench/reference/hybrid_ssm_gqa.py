"""Plain float32 reference of a decoder that mixes Mamba layers and
grouped-query attention layers over one dense SwiGLU MLP a layer
(JambaForCausalLM with ``num_experts: 1``: AI21-Jamba2-3B / Jamba Reasoning
3B). Layer ``l`` on ``x`` [T, D], positions 0..T-1:

    x = x + mixer_l(RMSNorm(x; attn_norm_l))
    x = x + (silu(u @ gate_l) * (u @ up_l)) @ down_l,  u = RMSNorm(x; mlp_norm_l)

then the final RMSNorm and the output head (the embedding transposed, tied).
Layer ``l`` is an attention layer iff ``l % attn_layer_period ==
attn_layer_offset`` (7 and 21 of 28), else a Mamba layer.

Attention mixer: ``q, k, v = h @ wq, h @ wk, h @ wv`` (no bias), NO positional
encoding of any kind, causal softmax attention over all earlier positions,
``@ wo``.

Mamba mixer (Mamba-1 with Jamba's three inner RMSNorms), ``u_t`` the normed
row at position t, ``Di = mamba_expand * D``, state ``h`` [N, Di] zero before
position 0, rows before the sequence zero:

    [xr_t ; z_t] = u_t @ W_in                                   (D -> 2 Di)
    x_t   = silu(b_c + sum_{k=0..K-1} w_c[k] * xr_{t-(K-1)+k})  (depthwise, causal)
    [dt_t ; B_t ; C_t] = x_t @ W_x                              (Di -> R + 2N)
    dt_t, B_t, C_t = RMSNorm(dt_t; g_dt), RMSNorm(B_t; g_B), RMSNorm(C_t; g_C)
    delta_t = softplus(dt_t @ W_dt + b_dt)                      (R -> Di)
    A     = -exp(A_log)                                         [N, Di]
    h_t   = exp(delta_t[None, :] * A) * h_{t-1} + (delta_t * x_t)[None, :] * B_t[:, None]
    y_t   = sum_n h_t[n, :] * C_t[n] + D * x_t
    out_t = (y_t * silu(z_t)) @ W_out                           (Di -> D)

No cache, no kernel, no batching: one sequence, every position at once, the
recurrence as a ``lax.scan`` over time, one head of attention at a time, one
layer's weights cast to float32 at a time so that the stack never sits on the
device in float32 beside the engine.

Departures from the published block, all forced by the weights the program
makes (``llmd_tpu/models/transformer.py::_init_hybrid_params``; the loader
``models/hf_loader.py`` maps the published tensors onto them): the MLP's gate
and up projections are one fused matrix ``wi`` [D, 2F] split in halves; the
state is held [N, Di] and ``A_log`` stored so (the published tensor is
[Di, N]); the conv's weight is stored [K, Di] (published [Di, 1, K]).

Assumed, because the catalog row's ``config`` lacks the key (the
configuration file lists them under ``assumed``): ``head_dim`` =
hidden_size / num_attention_heads; the recurrent state is float32 and the conv
window the weights' type between steps (a statement about the served path:
this reference has no cache and computes everything in float32).

What the program cannot express is refused by ``model_config`` with the key's
name, never approximated: ``num_experts`` > 1 (a mixture in place of the
MLP), a ``sliding_window``, ``mamba_proj_bias`` or ``attention_bias`` true, a ``hidden_act``
other than silu, untied embeddings with no head, and a depth that is not a whole
number of ``attn_layer_period``.

``sizes(conf)`` carries one switch a mechanism (``inner_norms``,
``conv_bias``, ``skip_d``, ``gate``, ``attn_rope``, ``reset_every``): a test
or a control turns one to read what leaving that mechanism out would give;
``state_dtype`` rounds the state to that type after every token, which is
what a served path that held its state so would do at every decode step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense_gqa, moe_swa_gqa
from reference.dense_gqa import F32

# a published key the program cannot express -> the one value it can
_ONLY = {"num_experts": 1, "sliding_window": None, "mamba_proj_bias": False,
         "hidden_act": "silu", "attention_bias": False}

MAMBA_LEAVES = ("mamba_in", "mamba_conv_w", "mamba_conv_b", "mamba_x",
                "mamba_dt_norm", "mamba_b_norm", "mamba_c_norm", "mamba_dt",
                "mamba_dt_bias", "mamba_a_log", "mamba_d", "mamba_out")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
SHARED_LEAVES = ("attn_norm", "mlp_norm", "wi", "wo_mlp")


def layer_kinds(conf: dict) -> list:
    """'attention' or 'mamba' for each of the file's layers."""
    per, off = conf["attn_layer_period"], conf["attn_layer_offset"]
    depth = conf["num_hidden_layers"]
    # a depth under one period (a test's cut of a file) is the prefix it is,
    # where that prefix holds an attention layer (the KV pool folds one)
    if depth % per and not off < depth < per:
        raise ValueError(f"num_hidden_layers={depth} is not a whole number of "
                         f"periods of attn_layer_period={per}")
    return ["attention" if l % per == off else "mamba" for l in range(depth)]


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or (conf["hidden_size"]
                                    // conf["num_attention_heads"])


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys;
    raises, naming the key, on one the program cannot express."""
    from llmd_tpu.models.config import ModelConfig

    for key, only in _ONLY.items():
        if key in conf and conf[key] != only:
            raise ValueError(f"{key}={conf[key]!r}: the program has only "
                             f"{key}={only!r} for this family")
    kinds = layer_kinds(conf)
    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim(conf),
        rms_eps=conf["rms_norm_eps"],
        max_position=conf["max_position_embeddings"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["weights"]["dtype"],
        rope_pattern=(False,),
        rope_theta=conf.get("rope_theta", 10000.0),
        layer_kinds=tuple(kinds[:conf["attn_layer_period"]]),
        mamba_d_inner=conf["mamba_expand"] * conf["hidden_size"],
        mamba_d_state=conf["mamba_d_state"],
        mamba_d_conv=conf["mamba_d_conv"],
        mamba_dt_rank=conf["mamba_dt_rank"],
        mamba_conv_bias=conf["mamba_conv_bias"],
        mamba_state_dtype=conf.get("state", {}).get("ssm_dtype", "float32"),
    )


def sizes(conf: dict) -> dict:
    """What ``readings`` needs of the configuration, and the mechanisms'
    switches (all as published here)."""
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": head_dim(conf), "eps": conf["rms_norm_eps"],
            # read only where a control turns attn_rope on
            "theta": conf.get("rope_theta", 10000.0),
            "tied": conf["tie_word_embeddings"],
            "kinds": layer_kinds(conf), "dt_rank": conf["mamba_dt_rank"],
            "d_state": conf["mamba_d_state"],
            "inner_norms": True, "conv_bias": conf["mamba_conv_bias"],
            "skip_d": True, "gate": True, "attn_rope": False,
            "reset_every": 0, "state_dtype": "float32"}


def weight_leaves(conf: dict) -> tuple:
    """The leaves stored as ``conf["weights"]`` says (the mixer's small
    leaves, like the norms, stay in the model's type)."""
    return ("wq", "wk", "wv", "wo", "wi", "wo_mlp", "mamba_in", "mamba_out")


def mamba(x, w, *, eps, dt_rank, d_state, inner_norms, conv_bias, skip_d,
          gate, reset_every, state_dtype="float32"):
    """The Mamba half of a block on ``x`` [T, D], residual included."""
    t = x.shape[0]
    u = dense_gqa._rms(x, w["attn_norm"], eps)
    xz = u @ w["mamba_in"]
    di = xz.shape[1] // 2
    xr, z = xz[:, :di], xz[:, di:]
    k = w["mamba_conv_w"].shape[0]
    pos = jnp.arange(t)
    acc = w["mamba_conv_b"] if conv_bias else jnp.zeros((di,), F32)
    for j in range(k):  # tap j multiplies the row k-1-j tokens back
        back = k - 1 - j
        row = jnp.pad(xr, ((back, 0), (0, 0)))[:t]
        if reset_every:  # a control: nothing is carried over a chunk's start
            row = jnp.where((pos % reset_every >= back)[:, None], row, 0.0)
        acc = acc + w["mamba_conv_w"][j] * row
    xc = jax.nn.silu(acc)
    dbc = xc @ w["mamba_x"]
    dt, bm, cm = (dbc[:, :dt_rank], dbc[:, dt_rank:dt_rank + d_state],
                  dbc[:, dt_rank + d_state:])
    if inner_norms:
        dt = dense_gqa._rms(dt, w["mamba_dt_norm"], eps)
        bm = dense_gqa._rms(bm, w["mamba_b_norm"], eps)
        cm = dense_gqa._rms(cm, w["mamba_c_norm"], eps)
    delta = jax.nn.softplus(dt @ w["mamba_dt"] + w["mamba_dt_bias"])
    a = -jnp.exp(w["mamba_a_log"])  # [N, Di]

    def step(h, inp):
        x_t, d_t, b_t, c_t, p = inp
        if reset_every:
            h = jnp.where(p % reset_every == 0, 0.0, h)
        h = jnp.exp(d_t[None, :] * a) * h + (d_t * x_t)[None, :] * b_t[:, None]
        y = jnp.sum(h * c_t[:, None], axis=0)
        if state_dtype == "bfloat16":
            # not a pair of casts: the chip's compiler keeps the excess
            # precision of float32 -> bfloat16 -> float32 (read on the chip,
            # PR 34: the control came out equal to the sound run to the bit)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32),
                        (xc, delta, bm, cm, pos))
    if skip_d:
        y = y + w["mamba_d"] * xc
    if gate:
        y = y * jax.nn.silu(z)
    return x + y @ w["mamba_out"]


def make_block(sizes: dict):
    """``block(params, l)``: layer ``l`` as a function of ``x`` [T, D], its
    leaves cast to float32 now (the norms and the MLP by ``l``, the mixer's by
    its ordinal among the layers of its kind)."""
    kw = dense_gqa.attention_sizes(sizes)
    attend = jax.jit(lambda x, w, rope: moe_swa_gqa.attention(
        x, w, window=0, rope=rope, **kw)[0], static_argnums=2)
    keys = ("dt_rank", "d_state", "inner_norms", "conv_bias", "skip_d",
            "gate", "reset_every", "state_dtype")
    ssm = jax.jit(lambda x, w: mamba(x, w, eps=sizes["eps"],
                                     **{k: sizes[k] for k in keys}))
    mlp = jax.jit(lambda x, w: x + dense_gqa.swiglu(
        dense_gqa._rms(x, w["mlp_norm"], sizes["eps"]), w["wi"], w["wo_mlp"]))
    kinds = sizes["kinds"]

    def block(params, l):
        kind = kinds[l]
        o = kinds[:l].count(kind)
        own = ATTN_LEAVES if kind == "attention" else tuple(
            k for k in MAMBA_LEAVES if k in params or k + "_q" in params)
        w = {k: dense_gqa.weight(params, k, l) for k in SHARED_LEAVES}
        w.update({k: dense_gqa.weight(params, k, o) for k in own})
        if kind == "attention":
            return lambda x: mlp(attend(x, w, sizes["attn_rope"]), w)
        return lambda x: mlp(ssm(x, w), w)

    return block


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return dense_gqa.logits(sizes, params, tokens, make_block)


def readings(sizes: dict, params: dict, prompts: list, served: list) -> dict:
    """As ``dense_gqa.readings``, through this family's block."""
    return dense_gqa.readings(sizes, params, prompts, served, make_block)
