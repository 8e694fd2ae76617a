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

Rotary embedding is the half-split ("rotate_half") form both published models
use: with ``d = head_dim``, pairs are ``(x[i], x[i + d/2])`` and the angle of
pair ``i`` at position ``p`` is ``p * theta ** (-2i/d)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def layer(x, w, *, heads, kv_heads, head_dim, eps, theta):
    """One block on ``x`` [T, D]; ``w`` maps leaf names to float32 arrays."""
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
    x = x + o
    h = _rms(x, w["mlp_norm"], eps)
    gate, up = jnp.split(h @ w["wi"], 2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ w["wo_mlp"]


_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "wi", "wo_mlp")
_BIASES = ("bq", "bk", "bv", "bo")


GROUP = 8  # sequences whose activations are held at once beside the engine


def logits_many(sizes: dict, params: dict, seqs: list, last: list) -> list:
    """Float32 logits of the last ``last[i]`` positions of each token list
    ``seqs[i]`` under ``params`` (the program's stacked layout). ``sizes``:
    layers, heads, kv_heads, head_dim, eps, theta, tied. Each sequence goes
    through each layer on its own; the loop over layers is the outer one only
    so that a layer's weights are made float32 once for all of them."""
    kw = dict(heads=sizes["heads"], kv_heads=sizes["kv_heads"],
              head_dim=sizes["head_dim"], eps=sizes["eps"],
              theta=sizes["theta"])
    step = jax.jit(lambda x, w: layer(x, w, **kw))
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"][jnp.asarray(t)].astype(F32) for t in seqs]
        for l in range(sizes["layers"]):
            w = {k: weight(params, k, l) for k in _LEAVES}
            w.update({k: weight(params, k, l) for k in _BIASES if k in params})
            xs = [step(x, w) for x in xs]
        norm = params["final_norm"].astype(F32)
        xs = [_rms(x[-n:], norm, sizes["eps"]) for x, n in zip(xs, last)]
        if sizes["tied"] and "unembed_q" not in params:
            head = params["embed"].astype(F32)
            return [jnp.einsum("td,vd->tv", x, head) for x in xs]
        head = weight(params, "unembed")
        return [x @ head for x in xs]


def logits(sizes: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [T, vocab] of ``tokens`` [T]."""
    return logits_many(sizes, params, [tokens], [len(tokens)])[0]


def deficits(sizes: dict, params: dict, prompts: list, served: list) -> list:
    """Teacher-force each ``prompts[i] + served[i]``; for each served token,
    how far its reference logit lies below the reference maximum at its
    position (0 where the reference would have chosen it too)."""
    out = []
    for g in range(0, len(prompts), GROUP):
        ps, ss = prompts[g:g + GROUP], served[g:g + GROUP]
        rows = logits_many(sizes, params,
                           [list(p) + list(s[:-1]) for p, s in zip(ps, ss)],
                           [len(s) for s in ss])
        for r, s in zip(rows, ss):
            got = r[jnp.arange(len(s)), jnp.asarray(s)]
            out.append([float(d) for d in (r.max(axis=-1) - got)])
    return out
