"""Plain float32 reference of a decoder whose layers are a KDA delta-rule
mixer or a latent-attention mixer, each over a feed-forward that is a dense
SwiGLU (the leading layers) or a group-limited sigmoid-routed mixture with a
shared expert (the language model of Ling-3.0-flash-VL). Published layer ``l``
on ``x`` [T, D], positions 0..T-1, pre-norm, ``rms_norm_eps``:

    x = x + Mixer_l(RMSNorm(x; attn_norm_l))
    x = x + FFN_l(RMSNorm(x; mlp_norm_l))

The mixer is latent attention where ``(l + 1) % layer_group_size == 0`` and
KDA elsewhere; the feed-forward is dense for ``l < first_k_dense_replace``.
Then the final RMSNorm and an untied output head.

KDA (Kimi Linear, arXiv:2510.26692), ``u`` the normed rows, H heads of d
lanes (``num_attention_heads`` x ``head_dim``; ``num_kv_heads_for_linear_attn``
0: k and v have the q heads' count), state ``S_h`` [d, d] zero before
position 0:

    q^, k^, v^ = u W_q, u W_k, u W_v                       (rows of kda_wqkv)
    q, k, v    = silu(conv4(q^)), silu(conv4(k^)), silu(conv4(v^))
                 (causal, depthwise, ``short_conv_kernel_size`` taps, no bias)
    q_h = q_h / |q_h|_2 * d^-1/2;   k_h = k_h / |k_h|_2     (``use_qk_norm``)
    log a = kda_lower_bound * sigmoid(exp(A_log_h) * (u W_f + dt_bias))
                                               [H, d] in (bound, 0) a channel
    b     = sigmoid(u W_b)                                           [H]
    S_h   = (I - b k k^T) Diag(a) S_h + b k v^T
    o_h   = S_h^T q_h
    out   = (RMSNorm_h(o; kda_o_norm) * sigmoid(u W_g)) W_o

Latent attention: ``reference/moe_mla.py``'s (DeepSeek-V3's; ``q_lora_rank``
null, RoPE over the ``qk_rope_head_dim`` lanes, ``use_mla_nope`` false), with
each head's output times ``sigmoid((u W_g)_h)``, one scalar a head
(``gated_attention_proj_granularity_type`` ``head_wise``), before ``W_o``.

Mixture: ``s = sigmoid(g W_r)`` in float32 over ALL the published experts;
the choice is taken over ``s + bias``: the experts stand in ``n_group`` groups
of consecutive experts, a group's score is the sum of its two best, the best
``topk_group`` groups are kept, the best ``num_experts_per_tok`` experts among
them are chosen; ``w = s[choice] / (sum + 1e-20) * routed_scaling_factor``; an
expert and the shared expert are SwiGLUs. **This device's share:** the sum
runs over those of a token's chosen experts that are HELD here
(``experts.held_first`` .. ``+ num_experts - 1`` of ``experts.published``);
what the absent experts would have added is left out, here as in the program,
and the partial result goes on. With every expert held it is the whole layer.

No cache, no kernel, no batching, no sorting, no blocks: one sequence, every
position at once, the recurrence token by token as a ``lax.scan``, one head
of attention at a time, one expert at a time, one layer's weights cast to
float32 at a time.

Departures from the published block, forced by the weights the program makes
(``llmd_tpu/models/transformer.py::_init_kda_params``): q, k and v's
projections are the rows of one matrix stored out by in (``kda_wqkv`` [3 H d,
D]) and the conv's weight [K, 3 H d] (published: a conv a projection, [C, 1,
K]); ``W_f``, ``W_g`` [H d, D] and ``W_b`` [H, D] are stored out by in;
``A_log`` is [H], ``dt_bias`` [H d]; the program holds a head's state
transposed; gate and up projections are one fused matrix split in halves.

Assumed, because the catalog row's ``config`` does not settle it (the
configuration file lists them under ``assumed``): the latent layer is a
group's last; the L2 form of the q/k norm and ``d^-1/2``; the gate's closed
form; ``b`` a sigmoid a head; the head-wise gate on the latent layers and the
lane-wise one on the KDA layers; a group's score the sum of its two best;
experts outside the kept groups can never be chosen (masked with -inf).

Not served and not here: the vision tower and the multi-token-prediction
layer (the catalog's ``config`` holds neither's sizes).

What the program cannot express is refused by ``model_config`` with the key's
name, never approximated: a ``q_lora_rank``, ``use_qk_norm`` false, a score
function other than sigmoid, no expert bias, ``num_kv_heads_for_linear_attn``
or ``group_norm_size`` other than 0 and 1, ``linear_silu`` false,
``use_mla_nope``, ``use_nGPT``, ``scale_router_input``, ``value_norm``,
``up_proj_norm``, a gate granularity other than ``head_wise``, a KDA LoRA,
``kda_safe_gate`` false, ``norm_topk_prob`` false, a ``rotary_dim`` other than
``qk_rope_head_dim``, a non-zero entry of either ``*_swiglu_limit_list`` on a
layer that is kept.

``sizes(conf)`` carries one switch a mechanism; a test or
``tests/control_kda_mla_moe.py`` turns one to read what a fault would give.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense_gqa, moe_mla
from reference.dense_gqa import F32
from reference.moe_gqa import _expert

# a published key the program cannot express -> the one value it can
_ONLY = {"q_lora_rank": None, "use_qk_norm": True, "score_function": "sigmoid",
         "moe_router_enable_expert_bias": True,
         "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
         "linear_silu": True, "use_mla_nope": False, "use_nGPT": False,
         "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False,
         "gated_attention_proj_granularity_type": "head_wise",
         "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True,
         "norm_topk_prob": True}

KDA_LEAVES = ("kda_wqkv", "kda_wf", "kda_wg", "kda_wb", "kda_wo",
              "kda_conv_w", "kda_o_norm", "kda_a_log", "kda_dt_bias")
MLA_LEAVES = ("mla_wq", "mla_wdkv", "mla_wkr", "mla_kv_norm", "mla_wuk",
              "mla_wuv", "wo", "wg")
EXPERT_LEAVES = ("router", "router_bias", "shared_wi", "shared_wo")
_BANKS = ("moe_wi", "moe_wo")


def first_layer(conf: dict) -> int:
    """The published layer the file's first layer is."""
    return conf.get("layers", {}).get("published_first", 0)


def layer_kinds(conf: dict) -> list:
    """'kda' or 'attention' for each of the file's layers."""
    group, at = conf["layer_group_size"], first_layer(conf)
    return ["attention" if (at + j + 1) % group == 0 else "kda"
            for j in range(conf["num_hidden_layers"])]


def _period(kinds: list) -> int:
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p) \
                and "attention" in kinds[:p]:
            return p
    raise ValueError("layer_group_size: the layers kept after the leading "
                     "dense ones are not whole periods with a latent-"
                     "attention layer in each")


def held(conf: dict) -> tuple:
    """(published experts, first held, held) of a mixture layer."""
    e = conf.get("experts", {})
    count = conf["num_experts"]
    return e.get("published", count), e.get("held_first", 0), count


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys;
    raises, naming the key, on one the program cannot express."""
    from llmd_tpu.models.config import ModelConfig

    for key, only in _ONLY.items():
        if key in conf and conf[key] != only:
            raise ValueError(f"{key}={conf[key]!r}: the program has only "
                             f"{key}={only!r} for this family")
    if conf["rotary_dim"] != conf["qk_rope_head_dim"]:
        raise ValueError("rotary_dim: the latent layers rotate their "
                         "qk_rope_head_dim lanes, all of them")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("num_key_value_heads: latent attention has one "
                         "latent a token and as many key heads as query heads")
    at, depth = first_layer(conf), conf["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(conf.get(key, [])[at:at + depth]):
            raise ValueError(f"{key}: a clamped SwiGLU is not served (a "
                             "non-zero limit on a layer that is kept)")
    kinds, k = layer_kinds(conf), conf["first_k_dense_replace"]
    if "attention" in kinds[:k]:
        raise ValueError("first_k_dense_replace: the leading dense layers "
                         "are KDA layers")
    published, first, count = held(conf)
    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        intermediate_size=conf["moe_intermediate_size"],
        num_layers=depth,
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        rope_theta=float(conf["rope_theta"]),
        rms_eps=conf["rms_norm_eps"],
        max_position=conf["max_position_embeddings"],
        tie_embeddings=False,
        dtype=conf["weights"]["dtype"],
        layer_kinds=tuple(kinds[k:k + _period(kinds[k:])]),
        kda_heads=conf["num_attention_heads"],
        kda_head_dim=conf["head_dim"],
        kda_d_conv=conf["short_conv_kernel_size"],
        kda_gate_lower_bound=float(conf["kda_lower_bound"]),
        lightning_state_dtype=conf.get("state", {}).get("kda_dtype",
                                                        "float32"),
        attn_output_gate=True,
        mla_kv_lora_rank=conf["kv_lora_rank"],
        mla_rope_dim=conf["qk_rope_head_dim"],
        mla_qk_nope_dim=conf["qk_nope_head_dim"],
        mla_v_head_dim=conf["v_head_dim"],
        moe_num_experts=published,
        moe_top_k=conf["num_experts_per_tok"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        moe_num_shared_experts=1,
        moe_shared_intermediate_size=conf[
            "moe_shared_expert_intermediate_size"],
        moe_leading_dense_layers=k,
        moe_dense_intermediate_size=conf["intermediate_size"],
        moe_scoring="sigmoid",
        moe_router_bias=True,
        moe_router_bias_scale=conf.get("router_bias_scale", 0.1),
        moe_routed_scaling=conf["routed_scaling_factor"],
        moe_n_group=conf["n_group"],
        moe_topk_group=conf["topk_group"],
        moe_held_first=first,
        moe_held_count=count if count < published else 0,
    )


def sizes(conf: dict) -> dict:
    """What ``readings`` needs of the file, and the switches of the family's
    mechanisms (sound: as listed here)."""
    published, first, count = held(conf)
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "dn": conf["qk_nope_head_dim"], "dr": conf["qk_rope_head_dim"],
            "eps": conf["rms_norm_eps"], "theta": float(conf["rope_theta"]),
            "tied": False, "kinds": layer_kinds(conf),
            "first_dense": conf["first_k_dense_replace"],
            "kda_heads": conf["num_attention_heads"],
            "bound": float(conf["kda_lower_bound"]),
            "top_k": conf["num_experts_per_tok"],
            "scaling": conf["routed_scaling_factor"],
            "n_group": conf["n_group"], "topk_group": conf["topk_group"],
            "held_first": first, "held": count,
            # the switches: one a mechanism
            "state_dtype": "float32", "delta": True, "safe_gate": True,
            "qk_l2": True, "out_gate": True, "out_norm": True,
            "mla_rope": True, "head_gate": True, "group_limit": True,
            "shared": True, "bias_in_choice": True,
            "absent_left_out": True}


def weight_leaves(conf: dict) -> tuple:
    """The leaves stored as ``conf["weights"]`` says; the router and its bias
    and the mixer's vectors, like the norms, stay as they are made."""
    return ("mla_wq", "mla_wdkv", "mla_wkr", "mla_wuk", "mla_wuv", "wo",
            "kda_wqkv", "kda_wf", "kda_wg", "kda_wo", "moe_wi", "moe_wo",
            "shared_wi", "shared_wo", "wi", "wo_mlp")


def kda(x, w, *, eps, heads, bound, state_dtype="float32", delta=True,
        safe_gate=True, qk_l2=True, out_gate=True, out_norm=True):
    """The KDA mixer on ``x`` [T, D], residual included."""
    t = x.shape[0]
    u = dense_gqa._rms(x, w["attn_norm"], eps)
    di = w["kda_wo"].shape[0]
    d = di // heads
    xr = u @ w["kda_wqkv"].T  # [T, 3 Di]
    taps = w["kda_conv_w"].shape[0]
    acc = jnp.zeros_like(xr)
    for j in range(taps):  # tap j multiplies the row taps-1-j tokens back
        acc = acc + w["kda_conv_w"][j] * jnp.pad(
            xr, ((taps - 1 - j, 0), (0, 0)))[:t]
    qkv = jax.nn.silu(acc).reshape(t, 3, heads, d)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    if qk_l2:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * d ** -0.5
    arg = (u @ w["kda_wf"].T + w["kda_dt_bias"]).reshape(t, heads, d)
    rate = jnp.exp(w["kda_a_log"])[None, :, None]
    # the fault: Kimi Linear's unbounded gate in the safe gate's place
    g = bound * jax.nn.sigmoid(rate * arg) if safe_gate \
        else -rate * jax.nn.softplus(arg)
    b = jax.nn.sigmoid(u @ w["kda_wb"].T)  # [T, H]

    def step(s, inp):  # s: [H, d(key), d(value)]
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        if delta:
            seen = jnp.einsum("hde,hd->he", s, k_t)
            s = s + (b_t[:, None] * (v_t - seen))[:, None, :] * k_t[:, :, None]
        else:  # the fault: gated linear attention, nothing erased
            s = s + (b_t[:, None] * v_t)[:, None, :] * k_t[:, :, None]
        o = jnp.einsum("hde,hd->he", s, q_t)
        if state_dtype == "bfloat16":
            # not a pair of casts: the chip's compiler keeps the excess
            # precision of float32 -> bfloat16 -> float32 (PR 34)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, o

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), F32), (q, k, v, g, b))
    if out_norm:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * w["kda_o_norm"]
    o = o.reshape(t, di)
    if out_gate:
        o = o * jax.nn.sigmoid(u @ w["kda_wg"].T)
    return x + o @ w["kda_wo"]


def latent(x, w, *, heads, dn, dr, eps, theta, mla_rope=True, head_gate=True):
    """The latent-attention mixer on ``x`` [T, D], residual included, in the
    unabsorbed form: ``moe_mla.attention`` without the q-side rank, and with
    the head-wise gate on each head's output before ``W_o``."""
    t = x.shape[0]
    h = dense_gqa._rms(x, w["attn_norm"], eps)
    q = jnp.einsum("td,dhk->thk", h, w["mla_wq"])
    c = dense_gqa._rms(h @ w["mla_wdkv"], w["mla_kv_norm"], eps)
    k_rope = (h @ w["mla_wkr"])[:, None, :]
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    if mla_rope:
        k_rope = dense_gqa._rotary(k_rope, theta)
        q_rope = dense_gqa._rotary(q_rope, theta)
    k_rope = k_rope[:, 0]
    k_nope = jnp.einsum("tr,hkr->thk", c, w["mla_wuk"])
    v = jnp.einsum("tr,hrv->thv", c, w["mla_wuv"])
    scale = (dn + dr) ** -0.5
    qb = moe_mla.QUERY_BLOCK
    blocks = -(-t // qb)
    pad = blocks * qb - t
    key_at = jnp.arange(t)[None, :]

    def one_head(i):
        qn = jnp.pad(q_nope[:, i], ((0, pad), (0, 0)))
        qr = jnp.pad(q_rope[:, i], ((0, pad), (0, 0)))

        def one_block(j):
            at = j * qb
            rows = at + jnp.arange(qb)[:, None]
            s = (jax.lax.dynamic_slice_in_dim(qn, at, qb) @ k_nope[:, i].T
                 + jax.lax.dynamic_slice_in_dim(qr, at, qb) @ k_rope.T) * scale
            s = jnp.where(key_at <= rows, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, i]

        return jax.lax.map(one_block, jnp.arange(blocks)).reshape(
            blocks * qb, -1)[:t]

    a = jnp.swapaxes(jax.lax.map(one_head, jnp.arange(heads)), 0, 1)
    if head_gate:
        a = a * jax.nn.sigmoid(h @ w["wg"])[:, :, None]
    return x + jnp.einsum("thv,hvd->td", a, w["wo"])


def route(g, bias, *, top_k, scaling, n_group, topk_group, group_limit=True,
          bias_in_choice=True):
    """A token's weight for each expert, [T, E], from router logits ``g``
    [T, E] and the selection bias ``bias`` [E]: the group-limited choice."""
    s = jax.nn.sigmoid(g)
    pick = s + bias if bias_in_choice else s
    if group_limit and n_group > 1:
        t, e = pick.shape
        by_group = pick.reshape(t, n_group, e // n_group)
        score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, best = jax.lax.top_k(score, topk_group)
        kept = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        pick = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(t, e)
    _, topi = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, topi, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(g).at[
        jnp.arange(g.shape[0])[:, None], topi].set(w * scaling)


def mixture(x, w, banks, e, *, eps, held_first, held, shared=True,
            absent_left_out=True, **routing):
    """The mixture feed-forward on ``x`` [T, D], residual included, with the
    leaves of mixture layer ``e``: the part of the layer's result that the
    experts held here give, and the shared expert."""
    g = dense_gqa._rms(x, w["mlp_norm"], eps)
    share = route(g @ w["router"], w["router_bias"], **routing)
    if not absent_left_out:
        # the fault: an absent expert's copies are not masked and take the
        # bank slot their clipped index names
        slot = jnp.clip(jnp.arange(share.shape[1]) - held_first, 0, held - 1)
        share = jnp.zeros((x.shape[0], held), F32).at[:, slot].add(share)
        held_first = 0

    def add(i, y):
        out = dense_gqa.swiglu(g, _expert(banks, "moe_wi", e, i),
                               _expert(banks, "moe_wo", e, i))
        return y + share[:, held_first + i][:, None] * out

    y = jax.lax.fori_loop(0, held, add, jnp.zeros_like(x))
    if shared:
        y = y + dense_gqa.swiglu(g, w["shared_wi"], w["shared_wo"])
    return x + y


_KDA_SWITCHES = ("state_dtype", "delta", "safe_gate", "qk_l2", "out_gate",
                 "out_norm")
_ROUTING = ("top_k", "scaling", "n_group", "topk_group", "group_limit",
            "bias_in_choice", "held_first", "held", "shared",
            "absent_left_out")


def make_block(sizes: dict):
    """``block(params, l)``: layer ``l`` as a function of ``x`` [T, D], its
    leaves cast to float32 now (the norms by ``l``, the mixer's by its
    ordinal among the layers of its kind, the feed-forward's by its own)."""
    mix_kda = jax.jit(lambda x, w: kda(
        x, w, eps=sizes["eps"], heads=sizes["kda_heads"],
        bound=sizes["bound"], **{k: sizes[k] for k in _KDA_SWITCHES}))
    mix_mla = jax.jit(lambda x, w: latent(
        x, w, **{k: sizes[k] for k in ("heads", "dn", "dr", "eps", "theta",
                                       "mla_rope", "head_gate")}))
    ffn = jax.jit(lambda x, w, banks, e: mixture(
        x, w, banks, e, eps=sizes["eps"], **{k: sizes[k] for k in _ROUTING}))
    mlp = jax.jit(lambda x, w: moe_mla.dense(x, w, eps=sizes["eps"]))
    kinds, k = sizes["kinds"], sizes["first_dense"]
    own = {"kda": KDA_LEAVES, "attention": MLA_LEAVES}

    def block(params, l):
        kind = kinds[l]
        o = kinds[:l].count(kind)
        w = {key: dense_gqa.weight(params, key, l)
             for key in ("attn_norm", "mlp_norm")}
        w.update({key: dense_gqa.weight(params, key, o) for key in own[kind]
                  if key in params or key + "_q" in params})
        mixer = mix_kda if kind == "kda" else mix_mla
        if l < k:
            w.update({key: dense_gqa.weight(params, key, l)
                      for key in ("wi", "wo_mlp")})
            return lambda x: mlp(mixer(x, w), w)
        w.update({key: dense_gqa.weight(params, key, l - k)
                  for key in EXPERT_LEAVES})
        banks = {key: v for key, v in params.items()
                 if key.removesuffix("_q").removesuffix("_scale") in _BANKS}
        return lambda x: ffn(mixer(x, w), w, banks, l - k)

    return block


def logits_many(sizes: dict, params: dict, seqs: list, last: list) -> list:
    """As ``moe_mla.logits_many``, through this family's block (the untied
    head applied a block of the vocabulary at a time)."""
    return moe_mla.logits_many(sizes, params, seqs, last, make_block)


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return logits_many(sizes, params, [tokens], [len(tokens)])[0]


def probed_pair(sound, flat):
    """The two tokens the gap probe asks the served path about at each
    position, and the reference's gap between them: ``[a, b, sound[a] -
    sound[b]]`` for ``sound`` [n, vocab], the reference's logits, and ``flat``
    [n, vocab], the reference's logits with the latent layers' positions
    taken away (``mla_rope`` off: every key at every distance scores as at
    distance 0). ``a`` is the token whose logit the positions raise most and
    ``b`` the one they lower most, so the gap between them holds the whole
    of what the latent layers' positions give this row of logits, 8 standard
    deviations of it over a vocabulary of 39,296 where the reference's two
    best tokens (``dense_gqa.readings``'s pair) hold 0.5 at their lower
    quartile; a served path that loses that term, or puts another in its
    place, reads off by it at every position. A fault with no direction
    among the tokens reads in this pair about what it reads in any other
    (1.2 to 1.8 times more on the chip: PERF.md section 2)."""
    d = sound - flat
    a, b = d.argmax(-1), d.argmin(-1)
    i = jnp.arange(sound.shape[0])
    return [[int(x), int(y), float(g)] for x, y, g in zip(
        *jax.device_get((a, b, sound[i, a] - sound[i, b])))]


def readings(sizes: dict, params: dict, prompts: list, served: list) -> dict:
    """As ``dense_gqa.readings`` (``deficits`` and ``top2`` at every position
    that served a token), the head applied in blocks, with ``probed_pair``'s
    tokens and gap as a position's ``top2``: one latent layer in seven moves
    the gap between the reference's two best tokens by less than the served
    path's bf16 arithmetic does (PERF.md section 2), so the probe is pointed
    at the pair that the latent layer's positions move most."""
    out = {"deficits": [], "top2": []}
    for g in range(0, len(prompts), dense_gqa.GROUP):
        ps, ss = prompts[g:g + dense_gqa.GROUP], served[g:g + dense_gqa.GROUP]
        seqs = [list(p) + list(s[:-1]) for p, s in zip(ps, ss)]
        last = [len(s) for s in ss]
        rows = logits_many(sizes, params, seqs, last)
        flat = logits_many({**sizes, "mla_rope": False}, params, seqs, last)
        for r, f, s in zip(rows, flat, ss):
            got = r[jnp.arange(len(s)), jnp.asarray(s)]
            out["deficits"].append([float(d) for d in (r.max(-1) - got)])
            out["top2"].append(probed_pair(r, f))
    return out
