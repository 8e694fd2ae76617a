"""Plain float32 reference of a decoder whose feed-forward is a mixture of
experts over grouped-query attention (OlmoeForCausalLM, MixtralForCausalLM,
the routed block of DeepseekV2/Qwen2Moe): pre-norm blocks of RMSNorm, rotary
attention over all earlier positions (``dense_gqa.attention``, unchanged), and
then, per token: router logits in float32, a softmax over ALL experts, the
``num_experts_per_tok`` largest probabilities as weights, renormalised to sum
to one where ``norm_topk_prob`` says so, each chosen expert applied as a plain
SwiGLU MLP to that token and the results summed by weight; plus the shared
experts, applied to every token, where the file has them.

No sorting, no capacity, no dropping, no kernel: every expert is run densely
over every position of the one sequence and its result is weighted by the
token's weight for it, which is zero unless the token chose it. That is
``num_experts / num_experts_per_tok`` times the arithmetic a dispatch needs
and the same sum. An expert is made float32 alone (``_expert``: a slice of the
stacked bank, dequantised when the stack holds it as int8 with
per-expert per-output-channel scales), so a 64-expert layer never sits on the
device in float32 beside the engine.

Departures from the published blocks, all forced by the weights the program
makes (``llmd_tpu/models/transformer.py::init_params``):
- an expert's gate and up projections are one fused matrix, ``moe_wi``
  [experts, D, 2F], split in halves (published: ``gate_proj`` and ``up_proj``);
- the shared experts are one fused SwiGLU of their summed width, ``shared_wi``
  / ``shared_wo``, added to the routed sum ungated (DeepSeek's form, and equal
  to that many separate experts; Qwen2Moe multiplies its shared expert by
  ``sigmoid(shared_expert_gate(x))``, a leaf the program does not have);
- the router has no bias and every layer is a mixture layer.

What the program cannot express is refused by ``model_config`` with the key's
name, never approximated: ``norm_topk_prob: false`` (``moe_block`` always
renormalises the top-k weights), a q/k norm (OLMoE's is over the full
projection width, ``ModelConfig.qk_norm`` is per head, and this reference has
neither), ``clip_qkv``, a rope scaling, sigmoid or grouped routing, leading
dense layers, a gated shared expert. The reference itself computes both
settings of ``norm_topk_prob``.

Keys read (published names; the first present wins): experts ``num_experts`` |
``num_local_experts``; experts per token
``num_experts_per_tok``; expert width ``moe_intermediate_size`` |
``intermediate_size``; ``norm_topk_prob`` (must be stated); shared experts
``n_shared_experts`` (default 0), each of width
``shared_expert_intermediate_size`` | the expert width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense_gqa
from reference.dense_gqa import F32

# a published key the program cannot express -> the one value it can
_ONLY = {"norm_topk_prob": True, "qk_norm": False, "clip_qkv": None,
         "rope_scaling": None, "scoring_func": "softmax",
         "routed_scaling_factor": 1.0, "n_group": 1, "topk_group": 1,
         "first_k_dense_replace": 0, "moe_layer_freq": 1,
         "decoder_sparse_step": 1, "shared_expert_gate": False,
         "sliding_window": None, "use_sliding_window": False}


def _first(conf: dict, *keys):
    for k in keys:
        if k in conf:
            return conf[k]
    raise KeyError(" | ".join(keys))


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys;
    raises, naming the key, on one the program cannot express."""
    from llmd_tpu.models.config import ModelConfig

    if "norm_topk_prob" not in conf:
        raise KeyError("norm_topk_prob")
    for key, only in _ONLY.items():
        if key in conf and conf[key] != only:
            raise ValueError(
                f"{key}={conf[key]!r}: the program's MoE block has only "
                f"{key}={only!r}")
    width = _first(conf, "moe_intermediate_size", "intermediate_size")
    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        # in a mixture model the program reads this as one shared expert's
        # width and nothing else
        intermediate_size=conf.get("shared_expert_intermediate_size", width),
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        rope_theta=conf["rope_theta"],
        rms_eps=conf["rms_norm_eps"],
        max_position=conf["max_position_embeddings"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["weights"]["dtype"],
        attn_bias=conf["attention_bias"],
        moe_num_experts=_first(conf, "num_experts", "num_local_experts"),
        moe_top_k=conf["num_experts_per_tok"],
        moe_intermediate_size=width,
        moe_num_shared_experts=conf.get("n_shared_experts", 0),
    )


def sizes(conf: dict) -> dict:
    """What ``deficits`` needs: the dense sizes, and of the mixture the
    experts per token, whether their weights are renormalised, and whether
    shared experts are there."""
    return dict(dense_gqa.sizes(conf), top_k=conf["num_experts_per_tok"],
                norm_topk=conf["norm_topk_prob"],
                shared=conf.get("n_shared_experts", 0) > 0)


def weight_leaves(conf: dict) -> tuple:
    """The leaves stored as ``conf["weights"]`` says; the router, like the
    norms, stays in the model's type under int8 too."""
    shared = ("shared_wi", "shared_wo") if conf.get("n_shared_experts", 0) \
        else ()
    return ("wq", "wk", "wv", "wo", "moe_wi", "moe_wo") + shared


def _expert(banks: dict, key: str, l, e):
    """Expert ``e`` of layer ``l`` of the stacked bank ``key`` in float32."""
    if key in banks:
        return banks[key][l, e].astype(F32)
    return banks[key + "_q"][l, e].astype(F32) * \
        banks[key + "_scale"][l, e].astype(F32)[None, :]


def mixture(x, w, banks, l, *, top_k, norm_topk, eps):
    """The mixture half of block ``l`` on ``x`` [T, D], residual included.
    ``w``: this layer's small leaves in float32; ``banks``: the stacked
    expert leaves as served."""
    h = dense_gqa._rms(x, w["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # [T, E], all experts
    topw, topi = jax.lax.top_k(probs, top_k)
    if norm_topk:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    # a token's weight for each expert: zero for those it did not choose
    share = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topw)

    def add(e, y):
        out = dense_gqa.swiglu(h, _expert(banks, "moe_wi", l, e),
                               _expert(banks, "moe_wo", l, e))
        return y + share[:, e][:, None] * out

    y = jax.lax.fori_loop(0, probs.shape[-1], add, jnp.zeros_like(x))
    if "shared_wi" in w:
        y = y + dense_gqa.swiglu(h, w["shared_wi"], w["shared_wo"])
    return x + y


_BANKS = ("moe_wi", "moe_wo")


def make_block(sizes: dict):
    kw = dense_gqa.attention_sizes(sizes)
    attend = jax.jit(lambda x, w: dense_gqa.attention(x, w, **kw))
    mix = jax.jit(lambda x, w, banks, l: mixture(
        x, w, banks, l, top_k=sizes["top_k"], norm_topk=sizes["norm_topk"],
        eps=sizes["eps"]))
    small = dense_gqa.ATTN_LEAVES + ("router",) + (
        ("shared_wi", "shared_wo") if sizes["shared"] else ())

    def block(params, l):
        w = dense_gqa.layer_weights(params, small, l)
        banks = {k: v for k, v in params.items()
                 if k.removesuffix("_q").removesuffix("_scale") in _BANKS}
        return lambda x: mix(attend(x, w), w, banks, l)

    return block


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return dense_gqa.logits(sizes, params, tokens, make_block)


def readings(sizes: dict, params: dict, prompts: list, served: list) -> dict:
    """As ``dense_gqa.readings``, through this family's block."""
    return dense_gqa.readings(sizes, params, prompts, served, make_block)


def deficits(sizes: dict, params: dict, prompts: list, served: list) -> list:
    return readings(sizes, params, prompts, served)["deficits"]
