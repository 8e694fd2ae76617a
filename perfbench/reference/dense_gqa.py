"""Plain float32 reference of a dense decoder with grouped-query attention
(Qwen2ForCausalLM, MistralForCausalLM): pre-norm blocks of RMSNorm, rotary
attention over all earlier positions, SwiGLU, and a linear output head that is
the embedding transposed where the configuration ties them.

No cache, no kernel, no batching: one sequence, every position at once, one
head of attention at a time. It runs one layer at a time, so that a 7B stack
never sits on the device in float32 beside the engine, and it takes the stack
the engine serves: a leaf
stored as int8 with per-output-channel scales is dequantised here, so the
reference and the engine see the same weights and differ only in arithmetic.

Departures from the published models, all inherited from the weights the
program makes (``llmd_tpu/models/transformer.py::init_params``): the gate and
up projections are one fused matrix ``wi`` split in halves, and Qwen2's q/k/v
biases are joined by an output bias ``bo`` (zero at initialisation).

Besides the forward pass this module is what the harness knows of the family
(``engine_child.py`` calls these and holds no model key itself):
``model_config(conf)`` makes the program's ``ModelConfig`` from a configuration
file's published keys, ``sizes(conf)`` what ``deficits`` needs of them, and
``weight_leaves(conf)`` names the leaves whose stored type the file states
under ``weights``.

Rotary embedding is the half-split ("rotate_half") form both published models
use: with ``d = head_dim``, pairs are ``(x[i], x[i + d/2])`` and the angle of
pair ``i`` at position ``p`` is ``p * theta ** (-2i/d)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def model_config(conf: dict):
    """The program's ModelConfig from a configuration file's published keys."""
    from llmd_tpu.models.config import ModelConfig

    return ModelConfig(
        name=conf["name"],
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        intermediate_size=conf["intermediate_size"],
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
    )


def sizes(conf: dict) -> dict:
    """What ``deficits`` and ``logits_many`` need of the configuration."""
    return {"layers": conf["num_hidden_layers"],
            "heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"], "eps": conf["rms_norm_eps"],
            "theta": conf["rope_theta"], "tied": conf["tie_word_embeddings"]}


def weight_leaves(conf: dict) -> tuple:
    """The leaves that are stored as ``conf["weights"]`` says (norms, biases
    and the embedding stay in the model's type under int8 too)."""
    return ("wq", "wk", "wv", "wo", "wi", "wo_mlp")


@jax.jit
def _dequantise(q, scale):
    return q.astype(F32) * scale.astype(F32)


def weight(params: dict, key: str, layer: int | None = None) -> jax.Array:
    """Leaf ``key`` in float32 (layer ``layer`` of a stacked leaf),
    dequantised when the stack holds it as ``<key>_q`` int8 and
    ``<key>_scale``."""
    at = (lambda a: a) if layer is None else (lambda a: a[layer])
    if key in params:
        return at(params[key]).astype(F32)
    return _dequantise(at(params[key + "_q"]), at(params[key + "_scale"]))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(x, theta):
    # x: [T, heads, d]
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, w, *, heads, kv_heads, head_dim, eps, theta):
    """The attention half of a block on ``x`` [T, D], residual included;
    ``w`` maps leaf names to float32 arrays."""
    t = x.shape[0]
    h = _rms(x, w["attn_norm"], eps)
    q = jnp.einsum("td,dhk->thk", h, w["wq"])
    k = jnp.einsum("td,dhk->thk", h, w["wk"])
    v = jnp.einsum("td,dhk->thk", h, w["wv"])
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = _rotary(q, theta), _rotary(k, theta)
    group = heads // kv_heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(i):
        # a head at a time: the [T, T] scores of every head at once would not
        # fit beside the engine at the lengths the check uses
        s = (q[:, i] @ k[:, i // group].T) * (head_dim ** -0.5)
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, i // group]

    a = jnp.swapaxes(jax.lax.map(one_head, jnp.arange(heads)), 0, 1)
    o = jnp.einsum("thk,hkd->td", a, w["wo"])
    if "bo" in w:
        o = o + w["bo"]
    return x + o


def swiglu(h, wi, wo):
    """The gated MLP with gate and up fused in ``wi`` [D, 2F]."""
    gate, up = jnp.split(h @ wi, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo


def layer(x, w, **kw):
    """One block on ``x`` [T, D]; ``w`` maps leaf names to float32 arrays."""
    x = attention(x, w, **kw)
    return x + swiglu(_rms(x, w["mlp_norm"], kw["eps"]), w["wi"], w["wo_mlp"])


ATTN_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo")
ATTN_BIASES = ("bq", "bk", "bv", "bo")


def attention_sizes(sizes: dict) -> dict:
    return {k: sizes[k] for k in ("heads", "kv_heads", "head_dim", "eps",
                                  "theta")}


def layer_weights(params: dict, keys, l: int) -> dict:
    """Layer ``l`` of the leaves ``keys`` in float32, with the attention
    biases where the stack has them."""
    w = {k: weight(params, k, l) for k in keys}
    w.update({k: weight(params, k, l) for k in ATTN_BIASES if k in params})
    return w


def make_block(sizes: dict):
    """``block(params, l)`` gives layer ``l`` as a function of ``x`` [T, D].
    A family with another block passes its own maker to ``logits_many``."""
    kw = attention_sizes(sizes)
    step = jax.jit(lambda x, w: layer(x, w, **kw))

    def block(params, l):
        w = layer_weights(params, ATTN_LEAVES + ("wi", "wo_mlp"), l)
        return lambda x: step(x, w)

    return block


GROUP = 8  # sequences whose activations are held at once beside the engine


def logits_many(sizes: dict, params: dict, seqs: list, last: list,
                make_block=make_block) -> list:
    """Float32 logits of the last ``last[i]`` positions of each token list
    ``seqs[i]`` under ``params`` (the program's stacked layout). ``sizes``:
    layers, heads, kv_heads, head_dim, eps, theta, tied. Each sequence goes
    through each layer on its own; the loop over layers is the outer one only
    so that a layer's weights are made float32 once for all of them."""
    block = make_block(sizes)
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"][jnp.asarray(t)].astype(F32) for t in seqs]
        for l in range(sizes["layers"]):
            f = block(params, l)
            xs = [f(x) for x in xs]
        norm = params["final_norm"].astype(F32)
        xs = [_rms(x[-n:], norm, sizes["eps"]) for x, n in zip(xs, last)]
        if sizes["tied"] and "unembed_q" not in params:
            head = params["embed"].astype(F32)
            return [jnp.einsum("td,vd->tv", x, head) for x in xs]
        head = weight(params, "unembed")
        return [x @ head for x in xs]


def logits(sizes: dict, params: dict, tokens,
           make_block=make_block) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return logits_many(sizes, params, [tokens], [len(tokens)], make_block)[0]


def readings(sizes: dict, params: dict, prompts: list, served: list,
             make_block=make_block) -> dict:
    """Teacher-force each ``prompts[i] + served[i]`` and read, at every
    position that served a token: ``deficits``, how far the served token's
    reference logit lies below the reference maximum (0 where the reference
    would have chosen it too); and ``top2``, the reference's two largest
    logits there as ``[token, runner-up, gap between them]``, which is what
    the harness's gap probe asks the served path for."""
    out = {"deficits": [], "top2": []}
    for g in range(0, len(prompts), GROUP):
        ps, ss = prompts[g:g + GROUP], served[g:g + GROUP]
        rows = logits_many(sizes, params,
                           [list(p) + list(s[:-1]) for p, s in zip(ps, ss)],
                           [len(s) for s in ss], make_block)
        for r, s in zip(rows, ss):
            got = r[jnp.arange(len(s)), jnp.asarray(s)]
            top, at = jax.lax.top_k(r, 2)
            gaps = jax.device_get(top[:, 0] - top[:, 1])
            out["deficits"].append([float(d) for d in (top[:, 0] - got)])
            out["top2"].append([[int(a), int(b), float(x)] for (a, b), x
                                in zip(jax.device_get(at), gaps)])
    return out


def deficits(sizes: dict, params: dict, prompts: list, served: list,
             make_block=make_block) -> list:
    """The ``deficits`` of ``readings``."""
    return readings(sizes, params, prompts, served, make_block)["deficits"]
