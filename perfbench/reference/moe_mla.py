"""Plain float32 reference of a decoder with multi-head latent attention over a
mixture of experts with leading dense layers and sigmoid routing
(Glm4MoeLiteForCausalLM: GLM-4.7-Flash; the block of DeepseekV3ForCausalLM
with one routing group). Layer ``l`` on ``x`` [T, D], positions 0..T-1, in the
UNABSORBED form (the program computes the absorbed one):

    h   = RMSNorm(x; attn_norm_l)
    c_q = RMSNorm(h W_qa; q_norm)                  # [T, q_lora_rank]
    q   = c_q W_qb  as [T, H, dn + dr];  q_rope = RoPE(q[..., dn:])
    c   = RMSNorm(h W_dkv; kv_norm)                # [T, kv_lora_rank] latent
    k_rope = RoPE(h W_kr)                          # [T, dr], one for all heads
    k_nope = c W_UK  as [T, H, dn];   v = c W_UV  as [T, H, dv]
    score  = (q_nope . k_nope + q_rope . k_rope) / sqrt(dn + dr), causal softmax
    x += concat_h(sum_j p_j v_j) W_o
    g = RMSNorm(x; mlp_norm_l)
    l <  first_k_dense_replace:  x += SwiGLU(g; wi_l, wo_mlp_l)
    l >= first_k_dense_replace:  s = sigmoid(g W_g) in float32 over all experts
        choice = top_k(s + b), b = e_score_correction_bias (the choice only)
        w = s[choice];  w = w / (sum w + 1e-20) * routed_scaling_factor
        x += sum_k w_k E_k(g) + S(g)               # E_k, S: SwiGLU, silu

then the final RMSNorm and the output head. ``W_UK`` / ``W_UV`` are the two
halves of the published ``kv_b_proj`` and ``[W_dkv ; W_kr]`` the published
``kv_a_proj_with_mqa`` (models/hf_loader.py maps the names).

No cache, no kernel, no batching, no sorting, no absorption: one sequence, one
layer at a time, one head of attention at a time and its queries in blocks of
``QUERY_BLOCK`` (every key of a block's rows at once, so each row's softmax is
the plain one; a head's [T, T] scores at 19k tokens would be 1.4 GB), every
expert run densely over every position and weighted by the token's weight for
it (zero unless chosen), one expert in float32 at a time.

RoPE pairs lanes in halves ("rotate_half": lanes ``i`` and ``i + dr/2``, angle
``p * theta ** (-2i/dr)``) over all ``dr`` lanes, in program and reference
alike. A checkpoint that pairs adjacent lanes is the same model under a fixed
permutation of ``W_qb``'s and ``W_kr``'s rope columns, which the loader
applies.

Departures from the published block, forced by the weights the program makes
(``llmd_tpu/models/transformer.py::_init_layered_params``): gate and up
projections are one fused matrix split in halves (``wi``, ``moe_wi``,
``shared_wi``); the shared experts are one SwiGLU of their summed width.

Not served and not here: the multi-token prediction layer
(``num_nextn_predict_layers``); the model's logits do not depend on it.

What the program cannot express is refused by ``model_config`` with the key's
name, never approximated: a ``rope_scaling``, ``n_group`` or ``topk_group``
over 1, ``norm_topk_prob`` false, an attention bias, a ``topk_method`` other
than ``noaux_tc``, a ``partial_rotary_factor`` under 1, an activation other
than silu.

``sizes`` carries one switch a mechanism, all sound as the family has them; a
test or ``tests/control_mla_moe.py`` flips one to read what a fault would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense_gqa
from reference.dense_gqa import F32
from reference.moe_gqa import _expert

QUERY_BLOCK = 1024

# a published key the program cannot express -> the one value it can
_ONLY = {"rope_scaling": None, "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "attention_bias": False,
         "topk_method": "noaux_tc", "partial_rotary_factor": 1,
         "hidden_act": "silu"}


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys;
    raises, naming the key, on one the program cannot express."""
    from llmd_tpu.models.config import ModelConfig

    for key, only in _ONLY.items():
        if conf[key] != only:
            raise ValueError(f"{key}={conf[key]!r}: the program has only "
                             f"{key}={only!r} for this family")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("num_key_value_heads: latent attention has one "
                         "latent a token and as many key heads as query heads")
    width = conf["moe_intermediate_size"]
    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        # in a mixture model the program reads this as one shared expert's
        # width; the leading dense layers' is moe_dense_intermediate_size
        intermediate_size=width,
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        rope_theta=conf["rope_theta"],
        rms_eps=conf["rms_norm_eps"],
        max_position=conf["max_position_embeddings"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["weights"]["dtype"],
        mla_kv_lora_rank=conf["kv_lora_rank"],
        mla_rope_dim=conf["qk_rope_head_dim"],
        mla_qk_nope_dim=conf["qk_nope_head_dim"],
        mla_v_head_dim=conf["v_head_dim"],
        mla_q_lora_rank=conf["q_lora_rank"] or 0,
        moe_num_experts=conf["n_routed_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_intermediate_size=width,
        moe_num_shared_experts=conf["n_shared_experts"],
        moe_leading_dense_layers=conf["first_k_dense_replace"],
        moe_dense_intermediate_size=conf["intermediate_size"],
        moe_scoring="sigmoid",
        moe_router_bias=True,
        moe_routed_scaling=conf["routed_scaling_factor"],
    )


def sizes(conf: dict) -> dict:
    """What ``readings`` needs of the file, and the switches of the family's
    mechanisms (sound: as listed here)."""
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "dn": conf["qk_nope_head_dim"], "dr": conf["qk_rope_head_dim"],
            "eps": conf["rms_norm_eps"], "theta": conf["rope_theta"],
            "tied": conf["tie_word_embeddings"],
            "q_rank": conf["q_lora_rank"] or 0,
            "top_k": conf["num_experts_per_tok"],
            "first_dense": conf["first_k_dense_replace"],
            "scaling": conf["routed_scaling_factor"],
            "shared": conf["n_shared_experts"] > 0,
            # the switches: one a mechanism
            "scoring": "sigmoid", "bias_in_choice": True,
            "bias_in_weights": False, "norm_topk": True, "q_norm": True,
            "kv_norm": True, "rope_on_nope": False,
            "dense_as_expert": False}


def weight_leaves(conf: dict) -> tuple:
    """The leaves stored as ``conf["weights"]`` says; the router and its bias,
    like the norms, stay as they are made."""
    q = ("mla_wqa", "mla_wqb") if conf["q_lora_rank"] else ("mla_wq",)
    shared = ("shared_wi", "shared_wo") if conf["n_shared_experts"] else ()
    dense = ("wi", "wo_mlp") if conf["first_k_dense_replace"] else ()
    return q + ("mla_wdkv", "mla_wkr", "mla_wuk", "mla_wuv", "wo",
                "moe_wi", "moe_wo") + shared + dense


def attention(x, w, *, heads, dn, dr, eps, theta, q_norm=True, kv_norm=True,
              rope_on_nope=False):
    """The attention half of a block on ``x`` [T, D], residual included, in
    the unabsorbed form; ``w`` maps leaf names to float32 arrays."""
    t = x.shape[0]
    h = dense_gqa._rms(x, w["attn_norm"], eps)
    if "mla_wqa" in w:
        c_q = h @ w["mla_wqa"]
        if q_norm:
            c_q = dense_gqa._rms(c_q, w["mla_q_norm"], eps)
        q = jnp.einsum("tr,rhk->thk", c_q, w["mla_wqb"])
    else:
        q = jnp.einsum("td,dhk->thk", h, w["mla_wq"])
    c = h @ w["mla_wdkv"]
    if kv_norm:
        c = dense_gqa._rms(c, w["mla_kv_norm"], eps)
    k_rope = dense_gqa._rotary((h @ w["mla_wkr"])[:, None, :], theta)[:, 0]
    q_nope, q_rope = q[..., :dn], dense_gqa._rotary(q[..., dn:], theta)
    k_nope = jnp.einsum("tr,hkr->thk", c, w["mla_wuk"])  # [T, H, dn]
    v = jnp.einsum("tr,hrv->thv", c, w["mla_wuv"])  # [T, H, dv]
    if rope_on_nope:  # a fault: the rotation taken over the content lanes too
        q_nope = dense_gqa._rotary(q_nope, theta)
        k_nope = dense_gqa._rotary(k_nope, theta)
    scale = (dn + dr) ** -0.5
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    key_at = jnp.arange(t)[None, :]

    def one_head(i):
        qn = jnp.pad(q_nope[:, i], ((0, pad), (0, 0)))
        qr = jnp.pad(q_rope[:, i], ((0, pad), (0, 0)))

        def one_block(b):
            at = b * QUERY_BLOCK
            rows = at + jnp.arange(QUERY_BLOCK)[:, None]
            s = (jax.lax.dynamic_slice_in_dim(qn, at, QUERY_BLOCK) @ k_nope[:, i].T
                 + jax.lax.dynamic_slice_in_dim(qr, at, QUERY_BLOCK) @ k_rope.T
                 ) * scale
            s = jnp.where(key_at <= rows, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, i]

        out = jax.lax.map(one_block, jnp.arange(blocks))
        return out.reshape(blocks * QUERY_BLOCK, -1)[:t]

    a = jnp.swapaxes(jax.lax.map(one_head, jnp.arange(heads)), 0, 1)
    return x + jnp.einsum("thv,hvd->td", a, w["wo"])


def route(g, bias, *, top_k, scaling, scoring="sigmoid", bias_in_choice=True,
          bias_in_weights=False, norm_topk=True):
    """A token's weight for each expert, [T, E], from router logits ``g``
    [T, E] and the selection bias ``bias`` [E]."""
    s = jax.nn.sigmoid(g) if scoring == "sigmoid" else jax.nn.softmax(g, -1)
    _, topi = jax.lax.top_k(s + bias if bias_in_choice else s, top_k)
    w = jnp.take_along_axis(s + bias if bias_in_weights else s, topi, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(g).at[
        jnp.arange(g.shape[0])[:, None], topi].set(w * scaling)


def mixture(x, w, banks, e, *, eps, shared=True, **routing):
    """The expert half of a block on ``x`` [T, D], residual included, with
    the leaves of mixture layer ``e`` (its ordinal among the mixture layers)."""
    g = dense_gqa._rms(x, w["mlp_norm"], eps)
    share = route(g @ w["router"], w["router_bias"], **routing)

    def add(i, y):
        out = dense_gqa.swiglu(g, _expert(banks, "moe_wi", e, i),
                               _expert(banks, "moe_wo", e, i))
        return y + share[:, i][:, None] * out

    y = jax.lax.fori_loop(0, share.shape[-1], add, jnp.zeros_like(x))
    if shared and "shared_wi" in w:
        y = y + dense_gqa.swiglu(g, w["shared_wi"], w["shared_wo"])
    return x + y


def dense(x, w, *, eps):
    g = dense_gqa._rms(x, w["mlp_norm"], eps)
    return x + dense_gqa.swiglu(g, w["wi"], w["wo_mlp"])


_BANKS = ("moe_wi", "moe_wo")
_ATTN = ("mla_wdkv", "mla_wkr", "mla_kv_norm", "mla_wuk", "mla_wuv", "wo")
_ROUTING = ("top_k", "scaling", "scoring", "bias_in_choice",
            "bias_in_weights", "norm_topk")


def make_block(sizes: dict):
    """``block(params, l)``: layer ``l`` as a function of ``x`` [T, D]: a
    leading dense layer or a mixture layer, by ``first_dense``."""
    akw = {k: sizes[k] for k in ("heads", "dn", "dr", "eps", "theta",
                                 "q_norm", "kv_norm", "rope_on_nope")}
    attend = jax.jit(lambda x, w: attention(x, w, **akw))
    mix = jax.jit(lambda x, w, banks, e: mixture(
        x, w, banks, e, eps=sizes["eps"], shared=sizes["shared"],
        **{k: sizes[k] for k in _ROUTING}))
    mlp = jax.jit(lambda x, w: dense(x, w, eps=sizes["eps"]))
    q_leaves = (("mla_wqa", "mla_q_norm", "mla_wqb") if sizes["q_rank"]
                else ("mla_wq",))
    k = sizes["first_dense"]

    def block(params, l):
        w = {key: dense_gqa.weight(params, key, l)
             for key in ("attn_norm", "mlp_norm") + q_leaves + _ATTN}
        as_expert = l >= k or sizes["dense_as_expert"]
        if as_expert:
            e = max(l - k, 0)  # the fault: layer 0 with the first mixture's leaves
            w.update({key: dense_gqa.weight(params, key, e)
                      for key in ("router", "router_bias")
                      + (("shared_wi", "shared_wo") if "shared_wi" in params
                         or "shared_wi_q" in params else ())})
            banks = {key: v for key, v in params.items()
                     if key.removesuffix("_q").removesuffix("_scale") in _BANKS}
            return lambda x: mix(attend(x, w), w, banks, e)
        w.update({key: dense_gqa.weight(params, key, l)
                  for key in ("wi", "wo_mlp")})
        return lambda x: mlp(attend(x, w), w)

    return block


HEAD_BLOCK = 16384  # vocabulary rows of the head made float32 at a time


def _head(x, params: dict):
    """``x`` [n, D] through the output head, a block of the vocabulary at a
    time: the whole head in float32 (1.27 GB at 154,880 x 2048) would not fit
    beside an engine that holds 13 GB."""
    def block(i):  # dequantised where the stack holds the head as int8
        if "unembed" in params:
            return params["unembed"][:, i:i + HEAD_BLOCK].astype(F32)
        return params["unembed_q"][:, i:i + HEAD_BLOCK].astype(F32) * \
            params["unembed_scale"][i:i + HEAD_BLOCK].astype(F32)

    width = params["unembed" if "unembed" in params else "unembed_q"].shape[1]
    return jnp.concatenate([x @ block(i) for i in range(0, width, HEAD_BLOCK)],
                           axis=-1)


def logits_many(sizes: dict, params: dict, seqs: list, last: list,
                make_block=make_block) -> list:
    """As ``dense_gqa.logits_many`` (float32 logits of the last ``last[i]``
    positions of each token list), through this family's block and with the
    head applied in blocks; the head is untied."""
    assert not sizes["tied"], "this family's head is untied"
    block = make_block(sizes)
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"][jnp.asarray(t)].astype(F32) for t in seqs]
        for l in range(sizes["layers"]):
            f = block(params, l)
            xs = [f(x) for x in xs]
        norm = params["final_norm"].astype(F32)
        return [_head(dense_gqa._rms(x[-n:], norm, sizes["eps"]), params)
                for x, n in zip(xs, last)]


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return logits_many(sizes, params, [tokens], [len(tokens)])[0]


def readings(sizes: dict, params: dict, prompts: list, served: list) -> dict:
    """As ``dense_gqa.readings``: teacher-force each ``prompts[i] +
    served[i]`` and read, at every position that served a token, ``deficits``
    (how far the served token's reference logit lies below the reference
    maximum) and ``top2`` (the reference's two largest logits there as
    ``[token, runner-up, gap]``)."""
    out = {"deficits": [], "top2": []}
    for g in range(0, len(prompts), dense_gqa.GROUP):
        ps, ss = prompts[g:g + dense_gqa.GROUP], served[g:g + dense_gqa.GROUP]
        rows = logits_many(sizes, params,
                           [list(p) + list(s[:-1]) for p, s in zip(ps, ss)],
                           [len(s) for s in ss])
        for r, s in zip(rows, ss):
            got = r[jnp.arange(len(s)), jnp.asarray(s)]
            top, at = jax.lax.top_k(r, 2)
            gaps = jax.device_get(top[:, 0] - top[:, 1])
            out["deficits"].append([float(d) for d in (top[:, 0] - got)])
            out["top2"].append([[int(a), int(b), float(x)] for (a, b), x
                                in zip(jax.device_get(at), gaps)])
    return out
