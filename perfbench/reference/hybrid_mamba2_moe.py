"""Plain float32 reference of a decoder whose layers are ONE sublayer each: a
Mamba-2 mixer, a grouped-query attention mixer, or a mixture of non-gated
experts (NemotronHForCausalLM: NVIDIA-Nemotron-3-Nano-30B-A3B). Layer ``l`` on
``x`` [T, D], positions 0..T-1, of the kind ``hybrid_override_pattern[l]``
names (``M`` Mamba-2, ``*`` attention, ``E`` experts):

    x = x + Sub_l(RMSNorm(x; attn_norm_l))          # eps layer_norm_epsilon

then the final RMSNorm and an untied output head.

``M``, with ``u_t`` the normed row at position t, H heads of P channels (Di =
H P: ``mamba_num_heads`` x ``mamba_head_dim``, 4,096 at hidden 2,688, whatever
``expand`` says), G groups of B and C of N entries (head h reads group h // (H / G)), C =
Di + 2 G N conv channels, state ``S_h`` [P, N] zero before position 0:

    [z_t | xBC_t | dt_t] = u_t W_in                          (Di | C | H)
    xBC_t = silu(b_c + sum_{k=0..K-1} w_c[k] * xBC_{t-(K-1)+k})   (depthwise, causal,
                                                                    all C channels)
    x_t, B_t, C_t = xBC_t split (Di | G N | G N)
    dt_h  = softplus(dt_h + dt_bias_h);  a_h = exp(dt_h A_h),  A_h = -exp(A_log_h)
    S_h   = a_h S_h + dt_h x_h B_g^T
    y_h   = S_h C_g + D_h x_h
    y     = RMSNorm_g(y * silu(z); m2_norm)     # the gate first; over each
                                                # group's Di / G lanes
    out   = y W_out

(no clamp of dt: ``time_step_min/max/floor`` are the initialiser's;
``chunk_size`` is the published kernel's tiling and enters no equation).

``*``: ``q, k, v = u wq, u wk, u wv`` (no bias, no q/k norm), NO positional
encoding, causal softmax attention at scale 1 / sqrt(head_dim), ``@ wo``.

``E``: ``s = sigmoid(u W_r)`` in float32 over ALL the published experts;
choice = top-k of ``s + b`` (``e_score_correction_bias``, the choice only; one
group); ``w = s[choice] / (sum + 1e-20) * routed_scaling_factor``; an expert is
``relu(u W_up)^2 W_down``, no gate, no bias; the shared expert the same form
at its own width. **This device's share:** the sum runs over those of a
token's chosen experts that are HELD here (``experts.held_first`` ..
``+ n_routed_experts - 1`` of ``experts.published``), one expert at a time;
what the absent experts would have added is left out, here as in the program,
and the partial result goes on. With every expert held it is the whole layer.

No cache, no kernel, no batching, no sorting, no blocks: one sequence, every
position at once, the recurrence token by token as a ``lax.scan``, one head of
attention at a time, one expert at a time, one layer's weights cast to float32
at a time.

Departures from the published block, forced by the weights the program makes
(``llmd_tpu/models/transformer.py::_init_sublayer_params``): the state is
held ``[N, H P]`` (a head's matrix transposed, heads side by side) and here
``[N, H, P]``; the conv's weight is stored [K, C] (published [C, 1, K]);
``A_log``, ``D`` and ``dt_bias`` are [H]; the gated norm's weight [Di] lies a
group after the other; the in-projection's columns and the experts' width are
stored rounded up to whole lane tiles of 128 with zeros (10,304 -> 10,368 and
1,856 -> 1,920: zero columns of ``W_up`` give relu(0)^2 = 0, which meets zero
rows of ``W_down``; dt's zero columns are read by nothing), so the products
here run over the zeros too and add exact zeros.

Assumed, because the catalog row's ``config`` does not settle it (the
configuration file lists them under ``assumed``): no positional encoding on
the attention layers; the recurrent state float32 and the conv window the
weights' type between steps (statements about the served path).

What the program cannot express is refused by ``model_config`` with the key's
name, never approximated: any bias (``attention_bias``, ``mamba_proj_bias``,
``mlp_bias``, ``use_bias``), ``n_group`` or ``topk_group`` over 1,
``norm_topk_prob`` false, an activation other than relu2 in the experts or
silu in the mixer, a ``sliding_window``, tied embeddings, ``use_conv_bias``
false, a pattern that is not whole periods with an attention layer in each.

``sizes(conf)`` carries one switch a mechanism; a test or
``tests/control_mamba2_moe.py`` turns one to read what a fault would give.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import dense_gqa, moe_mla, moe_swa_gqa
from reference.dense_gqa import F32
from reference.moe_gqa import _expert

# a published key the program cannot express -> the one value it can
_ONLY = {"attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
         "use_bias": False, "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "mlp_hidden_act": "relu2",
         "mamba_hidden_act": "silu", "sliding_window": None,
         "tie_word_embeddings": False, "use_conv_bias": True}
_KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}

M2_LEAVES = ("m2_in", "m2_conv_w", "m2_conv_b", "m2_dt_bias", "m2_a_log",
             "m2_d", "m2_norm", "m2_out")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("router", "router_bias", "shared_wi", "shared_wo")
_BANKS = ("moe_wi", "moe_wo")


def layer_kinds(conf: dict) -> list:
    """'mamba2', 'attention' or 'experts' for each of the file's layers: the
    first ``num_hidden_layers`` letters of the published pattern."""
    depth, pattern = conf["num_hidden_layers"], conf["hybrid_override_pattern"]
    if len(pattern) < depth or set(pattern) - set(_KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: "
                         f"{depth} letters of M, E and * are needed")
    return [_KINDS[c] for c in pattern[:depth]]


def _period(kinds: list) -> int:
    depth = len(kinds)
    for p in range(1, depth + 1):
        if depth % p == 0 and kinds == kinds[:p] * (depth // p) \
                and "attention" in kinds[:p]:
            return p
    raise ValueError("hybrid_override_pattern: the layers kept are not whole "
                     "periods with an attention layer in each")


def held(conf: dict) -> tuple:
    """(published experts, first held, held) of a mixture layer."""
    e = conf.get("experts", {})
    count = conf["n_routed_experts"]
    return e.get("published", count), e.get("held_first", 0), count


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys;
    raises, naming the key, on one the program cannot express."""
    from llmd_tpu.models.config import ModelConfig

    for key, only in _ONLY.items():
        if key in conf and conf[key] != only:
            raise ValueError(f"{key}={conf[key]!r}: the program has only "
                             f"{key}={only!r} for this family")
    kinds = layer_kinds(conf)
    published, first, count = held(conf)
    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        rms_eps=conf["layer_norm_epsilon"],
        max_position=conf["max_position_embeddings"],
        tie_embeddings=False,
        dtype=conf["weights"]["dtype"],
        rope_pattern=(False,),
        rope_theta=float(conf.get("rope_theta", 10000.0)),
        layer_kinds=tuple(kinds[:_period(kinds)]),
        mamba2_heads=conf["mamba_num_heads"],
        mamba2_head_dim=conf["mamba_head_dim"],
        mamba2_groups=conf["n_groups"],
        mamba2_d_state=conf["ssm_state_size"],
        mamba2_d_conv=conf["conv_kernel"],
        mamba_state_dtype=conf.get("state", {}).get("ssm_dtype", "float32"),
        moe_num_experts=published,
        moe_top_k=conf["num_experts_per_tok"],
        moe_intermediate_size=conf["moe_intermediate_size"],
        moe_num_shared_experts=conf["n_shared_experts"],
        moe_shared_intermediate_size=conf[
            "moe_shared_expert_intermediate_size"],
        moe_gated=False,
        moe_activation="relu2",
        moe_scoring="sigmoid",
        moe_router_bias=True,
        moe_router_bias_scale=conf.get("router_bias_scale", 0.1),
        moe_routed_scaling=conf["routed_scaling_factor"],
        moe_held_first=first,
        moe_held_count=count if count < published else 0,
    )


def sizes(conf: dict) -> dict:
    """What ``readings`` needs of the file, and the switches of the family's
    mechanisms (sound: as listed here)."""
    published, first, count = held(conf)
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"], "eps": conf["layer_norm_epsilon"],
            # read only where a control turns attn_rope on
            "theta": float(conf.get("rope_theta", 10000.0)), "tied": False,
            "kinds": layer_kinds(conf),
            "m2_heads": conf["mamba_num_heads"],
            "m2_groups": conf["n_groups"], "d_state": conf["ssm_state_size"],
            "top_k": conf["num_experts_per_tok"],
            "scaling": conf["routed_scaling_factor"],
            "held_first": first, "held": count,
            # the switches: one a mechanism
            "skip_d": True, "conv_bias": True, "gate_first": True,
            "own_group": True, "state_dtype": "float32", "act": "relu2",
            "shared": conf["n_shared_experts"] > 0, "bias_in_choice": True,
            "bias_in_weights": False, "absent_left_out": True,
            "attn_rope": False}


def weight_leaves(conf: dict) -> tuple:
    """The leaves stored as ``conf["weights"]`` says; the router and its bias
    and the mixer's vectors, like the norms, stay as they are made."""
    shared = ("shared_wi", "shared_wo") if conf["n_shared_experts"] else ()
    return ATTN_LEAVES + ("m2_in", "m2_out", "moe_wi", "moe_wo") + shared


def mamba2(x, w, *, eps, heads, groups, d_state, skip_d=True, conv_bias=True,
           gate_first=True, own_group=True, state_dtype="float32"):
    """The Mamba-2 layer on ``x`` [T, D], residual included."""
    t = x.shape[0]
    u = dense_gqa._rms(x, w["attn_norm"], eps)
    di = w["m2_out"].shape[0]
    c = w["m2_conv_w"].shape[1]
    p, gn = di // heads, groups * d_state
    zxd = u @ w["m2_in"]
    z, xr, dt = zxd[:, :di], zxd[:, di:di + c], zxd[:, di + c:di + c + heads]
    k = w["m2_conv_w"].shape[0]
    acc = w["m2_conv_b"] if conv_bias else jnp.zeros((c,), F32)
    for j in range(k):  # tap j multiplies the row k-1-j tokens back
        acc = acc + w["m2_conv_w"][j] * jnp.pad(
            xr, ((k - 1 - j, 0), (0, 0)))[:t]
    xbc = jax.nn.silu(acc)
    xs = xbc[:, :di].reshape(t, heads, p)
    bm = xbc[:, di:di + gn].reshape(t, groups, d_state)
    cm = xbc[:, di + gn:].reshape(t, groups, d_state)
    # head h reads group h // (heads / groups); the fault: group 0 for all
    of = (jnp.arange(heads) // (heads // groups)) if own_group \
        else jnp.zeros((heads,), jnp.int32)
    dt = jax.nn.softplus(dt + w["m2_dt_bias"])  # [T, H]
    a = -jnp.exp(w["m2_a_log"])  # [H]

    def step(s, inp):  # s: [H, P, N]
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[of][:, None, :]
        y = jnp.einsum("hpn,hn->hp", s, c_t[of])
        if state_dtype == "bfloat16":
            # not a pair of casts: the chip's compiler keeps the excess
            # precision of float32 -> bfloat16 -> float32 (PR 34)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, d_state), F32),
                        (xs, dt, bm, cm))
    if skip_d:
        y = y + w["m2_d"][:, None] * xs
    y, gate = y.reshape(t, di), jax.nn.silu(z)

    def group_norm(v):
        v = v.reshape(t, groups, di // groups)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        return v.reshape(t, di) * w["m2_norm"]

    y = group_norm(y * gate) if gate_first else group_norm(y) * gate
    return x + y @ w["m2_out"]


def _act(name):
    return {"relu2": lambda v: jnp.square(jax.nn.relu(v)),
            "relu": jax.nn.relu}[name]


def experts(x, w, banks, e, *, eps, top_k, scaling, held_first, held,
            act="relu2", shared=True, bias_in_choice=True,
            bias_in_weights=False, absent_left_out=True):
    """The expert layer on ``x`` [T, D], residual included, with the leaves
    of expert layer ``e`` (its ordinal among the expert layers): the part of
    the layer's result that the experts held here give, and the shared
    expert."""
    g = dense_gqa._rms(x, w["attn_norm"], eps)
    share = moe_mla.route(g @ w["router"], w["router_bias"], top_k=top_k,
                          scaling=scaling, bias_in_choice=bias_in_choice,
                          bias_in_weights=bias_in_weights)  # [T, published]
    f = _act(act)
    if not absent_left_out:
        # the fault: an absent expert's copies are not masked and take the
        # bank slot their clipped index names
        slot = jnp.clip(jnp.arange(share.shape[1]) - held_first, 0, held - 1)
        share = jnp.zeros((x.shape[0], held), F32).at[:, slot].add(share)
        held_first = 0

    def add(i, y):
        out = f(g @ _expert(banks, "moe_wi", e, i)) @ _expert(
            banks, "moe_wo", e, i)
        return y + share[:, held_first + i][:, None] * out

    y = jax.lax.fori_loop(0, held, add, jnp.zeros_like(x))
    if shared and "shared_wi" in w:
        y = y + f(g @ w["shared_wi"]) @ w["shared_wo"]
    return x + y


def make_block(sizes: dict):
    """``block(params, l)``: layer ``l`` as a function of ``x`` [T, D], its
    leaves cast to float32 now (the norm by ``l``, the sublayer's by its
    ordinal among the layers of its kind)."""
    kw = dense_gqa.attention_sizes(sizes)
    attend = jax.jit(lambda x, w, rope: moe_swa_gqa.attention(
        x, w, window=0, rope=rope, **kw)[0], static_argnums=2)
    ssm = jax.jit(lambda x, w: mamba2(
        x, w, eps=sizes["eps"], heads=sizes["m2_heads"],
        groups=sizes["m2_groups"], d_state=sizes["d_state"],
        **{k: sizes[k] for k in ("skip_d", "conv_bias", "gate_first",
                                 "own_group", "state_dtype")}))
    mix = jax.jit(lambda x, w, banks, e: experts(
        x, w, banks, e, eps=sizes["eps"],
        **{k: sizes[k] for k in ("top_k", "scaling", "held_first", "held",
                                 "act", "shared", "bias_in_choice",
                                 "bias_in_weights", "absent_left_out")}))
    kinds = sizes["kinds"]
    own = {"mamba2": M2_LEAVES, "attention": ATTN_LEAVES,
           "experts": EXPERT_LEAVES}

    def block(params, l):
        kind = kinds[l]
        o = kinds[:l].count(kind)
        w = {"attn_norm": dense_gqa.weight(params, "attn_norm", l)}
        w.update({k: dense_gqa.weight(params, k, o) for k in own[kind]
                  if k in params or k + "_q" in params})
        if kind == "attention":
            return lambda x: attend(x, w, sizes["attn_rope"])
        if kind == "mamba2":
            return lambda x: ssm(x, w)
        banks = {key: v for key, v in params.items()
                 if key.removesuffix("_q").removesuffix("_scale") in _BANKS}
        return lambda x: mix(x, w, banks, o)

    return block


def logits_many(sizes: dict, params: dict, seqs: list, last: list) -> list:
    """As ``moe_mla.logits_many``, through this family's block (the untied
    head applied a block of the vocabulary at a time)."""
    return moe_mla.logits_many(sizes, params, seqs, last, make_block)


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return logits_many(sizes, params, [tokens], [len(tokens)])[0]


def readings(sizes: dict, params: dict, prompts: list, served: list) -> dict:
    """As ``dense_gqa.readings`` (``deficits`` and ``top2`` at every position
    that served a token), the head applied in blocks."""
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
