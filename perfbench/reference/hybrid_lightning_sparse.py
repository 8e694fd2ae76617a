"""Plain float32 reference of a decoder that mixes lightning linear-attention
layers and block-sparse grouped-query attention layers over one dense SwiGLU
MLP a layer (``minicpm_sala``: MiniCPM-SALA). On ``x`` [T, D], positions
0..T-1:

    x0 = scale_emb * E[token]
    x  = x + a * mixer_l(RMSNorm(x; attn_norm_l))
    x  = x + a * (silu(u @ gate_l) * (u @ up_l)) @ down_l,  u = RMSNorm(x; mlp_norm_l)
    logits = W_head (RMSNorm(x; final_norm) / (hidden_size / dim_model_base))

with ``a = scale_depth / sqrt(len(mixer_types))``: the PUBLISHED depth, also
where the file serves fewer layers (``layers_served``: the file's
``num_hidden_layers`` layers from ``first`` on, kinds as ``mixer_types``
lists them). ``mup_denominator`` enters no equation.

``minicpm4`` layer (sparse attention): ``q = h W_q`` (H heads of d), ``k, v =
h W_k, h W_v`` (Hk heads), no bias; q and k take an RMSNorm over each head's
lanes with a learned weight; NO positional encoding; output ``(A *
sigmoid(h W_g)) W_o``. ``A`` for the query at position t, which sees n = t + 1
keys, scale 1/sqrt(d): with n < ``dense_len`` causal softmax over all n keys;
otherwise, per KV head g (its H / Hk query heads share one selection),
softmax over the visible tokens of the blocks in

    S = {0 .. init_blocks - 1} U {b : b_t - window / block < b <= b_t} U top-k of the rest

a block ``block_size`` tokens, ``b_t = t // block_size``, the rest ranked by
``s_b = max over kernels j that overlap block b of sum_h p_(h, j)``, ``p_(h,
.) = softmax_j(q_h . c_j / sqrt(d))`` over the whole kernels visible to t,
``c_j = mean(k[j * stride : j * stride + kernel_size])`` (after the k-norm).
Fewer candidates than top-k: all are taken; ties: the lower block first. The
rule is per query (the published prefill switches on the prompt's length,
which would make a token's output depend on tokens after it). The selection's
scores are float32 sums over q, k and c as the served pool would hold them:
rounded to ``weights.dtype`` (``stored``), since which block wins a near tie
is the stored values' to decide; the attention itself is float32 throughout.

``lightning-attn`` layer: ``q, k, v = h W_q, h W_k, h W_v`` (Hl heads of dl);
q, k: RMSNorm a head (learned weight), then RoPE over all lanes; per head h a
state ``S`` [dl, dl], zero before position 0:

    S_t = lam_h S_(t-1) + k_t^T v_t,   o_t = (q_t / sqrt(dl)) S_t,   lam_h = exp(-2^(-8 (h + 1) / Hl))

``o_t`` takes an RMSNorm over each head's lanes (learned weight), then ``(o *
sigmoid(h W_g)) W_o``.

No cache, no paging, no compressed-key cache, no kernel, no batching: one
sequence, the recurrence as a ``lax.scan`` over time, attention a block of
queries and a head at a time so that 18k-token rows fit, one layer's weights
cast to float32 at a time. int8 is not offered.

``sizes(conf)`` carries one switch a mechanism (``state_dtype``, ``sparse``,
``topk``, ``attn_rope``, ``decay``): a test or a control turns one to read
what a program with that fault would give.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import dense_gqa
from reference.dense_gqa import F32

_ONLY = {"attention_bias": False, "attn_use_rope": False, "hidden_act": "silu",
         "lightning_use_rope": True, "qk_norm": True, "use_output_gate": True,
         "use_output_norm": True, "attn_use_output_gate": True,
         "tie_word_embeddings": False, "lightning_scale": "1/sqrt(d)"}
KINDS = {"minicpm4": "attention", "lightning-attn": "lightning"}

ATTN_LEAVES = ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm")
LIN_LEAVES = ("lin_wq", "lin_wk", "lin_wv", "lin_wg", "lin_wo", "lin_q_norm",
              "lin_k_norm", "lin_o_norm")
SHARED_LEAVES = ("attn_norm", "mlp_norm", "wi", "wo_mlp")
# queries a block of attention, tokens a block of the MLP: bounds the
# float32 temporaries beside the engine ([QB, T] scores a head, [MB, 2F])
QB, MB = 512, 2048
GROUP = 2  # sequences whose activations are held at once


def layer_kinds(conf: dict) -> list:
    """'attention' or 'lightning' for each of the file's layers."""
    first = conf.get("layers_served", {}).get("first", 0)
    types = conf["mixer_types"][first:first + conf["num_hidden_layers"]]
    if len(types) != conf["num_hidden_layers"]:
        raise ValueError("layers_served runs past mixer_types")
    return [KINDS[t] for t in types]


def residual_scale(conf: dict) -> float:
    return conf["scale_depth"] / len(conf["mixer_types"]) ** 0.5


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys;
    raises, naming the key, on one the program cannot express."""
    from llmd_tpu.models.config import ModelConfig

    for key, only in _ONLY.items():
        if key in conf and conf[key] != only:
            raise ValueError(f"{key}={conf[key]!r}: the program has only "
                             f"{key}={only!r} for this family")
    if conf["weights"]["quantize"]:
        raise ValueError("weights.quantize: int8 is not offered for this "
                         "family")
    if conf["lightning_nkv"] != conf["lightning_nh"]:
        raise ValueError("lightning_nkv != lightning_nh")
    sp = conf["sparse"]
    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        rms_eps=conf["rms_norm_eps"],
        rope_theta=float(conf["rope_theta"]),
        max_position=conf["max_position_embeddings"],
        tie_embeddings=False,
        dtype=conf["weights"]["dtype"],
        qk_norm=True,
        rope_pattern=(False,),
        layer_kinds=tuple(layer_kinds(conf)),
        lightning_heads=conf["lightning_nh"],
        lightning_head_dim=conf["lightning_head_dim"],
        lightning_state_dtype=conf.get("state", {}).get("linear_dtype",
                                                        "float32"),
        attn_output_gate=True,
        sparse_topk=sp["topk"], sparse_block_size=sp["block_size"],
        sparse_kernel_size=sp["kernel_size"],
        sparse_kernel_stride=sp["kernel_stride"],
        sparse_window=sp["window_size"], sparse_init_blocks=sp["init_blocks"],
        sparse_dense_len=sp["dense_len"],
        embed_scale=float(conf["scale_emb"]),
        residual_scale=residual_scale(conf),
        logit_scale=conf["dim_model_base"] / conf["hidden_size"],
    )


def sizes(conf: dict) -> dict:
    """What ``readings`` needs of the configuration, and the mechanisms'
    switches (all as published here)."""
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"], "eps": conf["rms_norm_eps"],
            "theta": float(conf["rope_theta"]), "tied": False,
            "kinds": layer_kinds(conf),
            "embed_scale": float(conf["scale_emb"]),
            "residual_scale": residual_scale(conf),
            "logit_scale": conf["dim_model_base"] / conf["hidden_size"],
            "stored": conf["weights"]["dtype"],
            **{k: conf["sparse"][k] for k in (
                "kernel_size", "kernel_stride", "block_size", "window_size",
                "topk", "init_blocks", "dense_len")},
            "sparse": True, "attn_rope": False, "decay": True,
            "state_dtype": "float32"}


def weight_leaves(conf: dict) -> tuple:
    """The leaves stored as ``conf["weights"]`` says."""
    return ("wq", "wk", "wv", "wo", "wg", "wi", "wo_mlp", "lin_wq", "lin_wk",
            "lin_wv", "lin_wg", "lin_wo")


def _stored(x, dtype: str):
    """``x`` as a pool of ``dtype`` would hold it (float32: as it is)."""
    return x if dtype == "float32" else jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7)


def _selected(qs, c, t, sz, n_tokens: int):
    """Which blocks each query of a block attends to: ``qs`` [Q, G, d] the
    stored queries of one KV head, ``c`` [K, d] its compressed keys, ``t``
    [Q] the queries' positions in a sequence of ``n_tokens``; returns bool
    [Q, blocks]."""
    st, ks, bs = sz["kernel_stride"], sz["kernel_size"], sz["block_size"]
    n_kern = c.shape[0]
    nb = -(-n_tokens // bs)
    visible = (jnp.arange(n_kern) * st + ks - 1)[None, :] <= t[:, None]
    s = jnp.einsum("qgd,kd->qgk", qs, c) * sz["head_dim"] ** -0.5
    s = jnp.where(visible[:, None, :], s, -jnp.inf)
    p = jnp.where(visible[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    p = jnp.where(visible, p.sum(axis=1), -1.0)  # [Q, K]
    # kernel j covers tokens [j st, j st + ks): the blocks it overlaps
    lo = jnp.arange(n_kern) * st // bs
    hi = (jnp.arange(n_kern) * st + ks - 1) // bs
    blk = jnp.arange(nb)
    over = (lo[:, None] <= blk[None, :]) & (blk[None, :] <= hi[:, None])
    score = jnp.max(jnp.where(over[None], p[:, :, None], -1.0), axis=1)
    b_t = (t // bs)[:, None]
    wb = sz["window_size"] // bs
    init = blk[None, :] < sz["init_blocks"]
    window = (blk[None, :] > b_t - wb) & (blk[None, :] <= b_t)
    cand = ~init & (blk[None, :] <= b_t - wb)
    k = min(sz["topk"], nb)
    top, idx = jax.lax.top_k(jnp.where(cand, score, -jnp.inf), k)
    picked = jnp.any((idx[:, :, None] == blk[None, None, :])
                     & jnp.isfinite(top)[:, :, None], axis=1)
    return picked | init | window


def sparse_attention(x, w, *, sz):
    """The sparse-attention half of a block on ``x`` [T, D], the residual's
    scale included."""
    T = x.shape[0]
    H, Hk, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    G = H // Hk
    h = dense_gqa._rms(x, w["attn_norm"], sz["eps"])
    q = dense_gqa._rms(jnp.einsum("td,dhk->thk", h, w["wq"]), w["q_norm"],
                       sz["eps"])
    k = dense_gqa._rms(jnp.einsum("td,dhk->thk", h, w["wk"]), w["k_norm"],
                       sz["eps"])
    v = jnp.einsum("td,dhk->thk", h, w["wv"])
    if sz["attn_rope"]:  # a control: the model has none
        q, k = dense_gqa._rotary(q, sz["theta"]), dense_gqa._rotary(k, sz["theta"])
    st, ks, bs = sz["kernel_stride"], sz["kernel_size"], sz["block_size"]
    n_kern = max(0, (T - ks) // st + 1)
    k_st = _stored(k, sz["stored"])
    if n_kern:
        at = jnp.arange(n_kern)[:, None] * st + jnp.arange(ks)[None, :]
        comp = _stored(jnp.mean(k_st[at], axis=1), sz["stored"])  # [K, Hk, d]
    pos = jnp.arange(T)

    def block_of_queries(t0):
        t = t0 + jnp.arange(QB)
        tc = jnp.minimum(t, T - 1)
        qb = q[tc]  # [QB, H, d]
        mask = pos[None, :] <= tc[:, None]  # [QB, T]
        outs = []
        for g in range(Hk):
            m = mask
            if sz["sparse"] and n_kern:
                qs = _stored(qb[:, g * G:(g + 1) * G], sz["stored"])
                sel = _selected(qs, comp[:, g], tc, sz, T)  # [QB, blocks]
                m = mask & jnp.where((tc + 1 >= sz["dense_len"])[:, None],
                                     sel[:, pos // bs], True)

            def one_head(i, g=g, m=m):
                s = (jax.lax.dynamic_index_in_dim(qb, i, 1, False)
                     @ k[:, g].T) * d ** -0.5
                s = jnp.where(m, s, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ v[:, g]

            outs.append(jax.lax.map(one_head, g * G + jnp.arange(G)))
        return jnp.swapaxes(jnp.concatenate(outs), 0, 1)  # [QB, H, d]

    nq = -(-T // QB)
    a = jax.lax.map(block_of_queries, jnp.arange(nq) * QB)
    a = a.reshape(nq * QB, H, d)[:T]
    a = a * jax.nn.sigmoid(jnp.einsum("td,dhk->thk", h, w["wg"]))
    return x + sz["residual_scale"] * jnp.einsum("thk,hkd->td", a, w["wo"])


def lightning(x, w, *, sz):
    """The lightning half of a block on ``x`` [T, D], the residual's scale
    included."""
    h = dense_gqa._rms(x, w["attn_norm"], sz["eps"])
    d = w["lin_q_norm"].shape[0]

    def heads(key):  # stored [Hl * dl, D]: out by in, a head's lanes together
        return (h @ w[key].T).reshape(h.shape[0], -1, d)

    q = dense_gqa._rms(heads("lin_wq"), w["lin_q_norm"], sz["eps"])
    k = dense_gqa._rms(heads("lin_wk"), w["lin_k_norm"], sz["eps"])
    v = heads("lin_wv")
    q, k = dense_gqa._rotary(q, sz["theta"]), dense_gqa._rotary(k, sz["theta"])
    nh = q.shape[1]
    slopes = jnp.exp2(-8.0 * jnp.arange(1, nh + 1, dtype=F32) / nh)
    lam = jnp.exp(-slopes)[:, None, None] if sz["decay"] else 1.0

    def step(s, inp):
        q_t, k_t, v_t = inp
        s = lam * s + k_t[:, :, None] * v_t[:, None, :]
        if sz["state_dtype"] == "bfloat16":  # a control (see hybrid_ssm_gqa)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hd,hde->he", q_t * d ** -0.5, s)

    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), F32), (q, k, v))
    o = dense_gqa._rms(o, w["lin_o_norm"], sz["eps"])
    o = (o * jax.nn.sigmoid(heads("lin_wg"))).reshape(h.shape[0], -1)
    return x + sz["residual_scale"] * (o @ w["lin_wo"])


def mlp(x, w, *, sz):
    """The feed-forward half of a block, ``MB`` tokens at a time."""
    def some(xb):
        u = dense_gqa._rms(xb, w["mlp_norm"], sz["eps"])
        return xb + sz["residual_scale"] * dense_gqa.swiglu(
            u, w["wi"], w["wo_mlp"])

    T = x.shape[0]
    n = -(-T // MB)
    xp = jnp.pad(x, ((0, n * MB - T), (0, 0))).reshape(n, MB, -1)
    return jax.lax.map(some, xp).reshape(n * MB, -1)[:T]


def make_block(sizes: dict):
    """``block(params, l)``: layer ``l`` as a function of ``x`` [T, D], its
    leaves cast to float32 now (the norms and the MLP by ``l``, the mixer's by
    its ordinal among the layers of its kind)."""
    frozen = {k: tuple(v) if isinstance(v, list) else v
              for k, v in sizes.items()}
    attend = jax.jit(functools.partial(sparse_attention, sz=frozen))
    linear = jax.jit(functools.partial(lightning, sz=frozen))
    ffn = jax.jit(functools.partial(mlp, sz=frozen))
    kinds = sizes["kinds"]

    def block(params, l):
        kind = kinds[l]
        o = list(kinds[:l]).count(kind)
        w = {k: dense_gqa.weight(params, k, l) for k in SHARED_LEAVES}
        w.update({k: dense_gqa.weight(params, k, o) for k in (
            ATTN_LEAVES if kind == "attention" else LIN_LEAVES)})
        mixer = attend if kind == "attention" else linear
        return lambda x: ffn(mixer(x, w), w)

    return block


def logits_many(sizes: dict, params: dict, seqs: list, last: list) -> list:
    """Float32 logits of the last ``last[i]`` positions of each token list
    ``seqs[i]`` under ``params`` (the program's stacked layout), ``GROUP``
    sequences at a time, a layer's weights made float32 once a group and let
    go before the next layer's are made (1.14 GB a layer at the published
    widths, beside the engine), the head's only when the layers are done."""
    block = make_block(sizes)
    hidden = []
    with jax.default_matmul_precision("highest"):
        norm = params["final_norm"].astype(F32)
        for g in range(0, len(seqs), GROUP):
            xs = [params["embed"][jnp.asarray(t)].astype(F32)
                  * sizes["embed_scale"] for t in seqs[g:g + GROUP]]
            for l in range(sizes["layers"]):
                f = None  # the last layer's float32 weights go first
                f = block(params, l)
                xs = [f(x) for x in xs]
            f = None
            hidden += [dense_gqa._rms(x[-n:], norm, sizes["eps"])
                       * sizes["logit_scale"]
                       for x, n in zip(xs, last[g:g + GROUP])]
            del xs
        head = dense_gqa.weight(params, "unembed")
        return [h @ head for h in hidden]


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return logits_many(sizes, params, [tokens], [len(tokens)])[0]


def readings(sizes: dict, params: dict, prompts: list, served: list) -> dict:
    """As ``dense_gqa.readings``: teacher-force each ``prompts[i] +
    served[i]`` and read, at every position that served a token, the served
    token's ``deficits`` under the reference maximum and the reference's
    ``top2`` (``[token, runner-up, gap]``)."""
    out = {"deficits": [], "top2": []}
    rows = logits_many(sizes, params,
                       [list(p) + list(s[:-1]) for p, s in zip(prompts, served)],
                       [len(s) for s in served])
    for r, s in zip(rows, served):
        got = r[jnp.arange(len(s)), jnp.asarray(s)]
        top, at = jax.lax.top_k(r, 2)
        gaps = jax.device_get(top[:, 0] - top[:, 1])
        out["deficits"].append([float(d) for d in (top[:, 0] - got)])
        out["top2"].append([[int(a), int(b), float(x)] for (a, b), x
                            in zip(jax.device_get(at), gaps)])
    return out
