"""Jitted batched sampler: greedy / temperature / top-k / top-p, static shapes.

One program for the whole decode batch; per-slot parameters arrive as arrays so a mixed
batch (greedy + sampled + different temperatures) is a single XLA launch. The step
programs inline it (jit-in-jit): the unified step and each step of the fused
decode call pick their own tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def greedy_tokens(logits: jax.Array) -> jax.Array:
    """Greedy token per row, matching `sample_tokens`' temperature<=0 branch
    bitwise: argmax over float32 logits. The speculative verify program
    (programs.py `_verify`) uses this on every packed position, so accepted
    draft tokens are exactly what sequential greedy decoding would emit."""
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def _sample_core(
    logits: jax.Array,  # [B, V] float32
    key: jax.Array,
    temperature: jax.Array,  # [B] (0 = greedy)
    top_k: jax.Array,  # [B] int32 (0 = disabled)
    top_p: jax.Array,  # [B] (1.0 = disabled)
    top_k_max: int,
) -> jax.Array:
    """One program with a branch on what it was given: a batch in which some
    row samples takes the whole sampler (a greedy row of it still gets its
    argmax, from the final ``where``); a batch in which none does is argmax
    over the same float32 logits and nothing else. The top-k_max over the
    vocabulary is the sampler's cost, so it is not computed to be thrown
    away, and the first sampling row to arrive compiles nothing."""

    def _argmax(logits):
        return jnp.argmax(logits, axis=-1)

    def _sampled(logits):
        V = logits.shape[1]
        greedy = jnp.argmax(logits, axis=-1)

        temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = logits / temp

        # top-k_max candidates once; per-slot k masking inside.
        topv, topi = jax.lax.top_k(scaled, min(top_k_max, V))  # [B, K]
        K = topv.shape[1]
        ranks = jnp.arange(K)[None, :]
        k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, K), K)[:, None]
        topv = jnp.where(ranks < k_eff, topv, -jnp.inf)

        # top-p on the (sorted) candidates
        probs = jax.nn.softmax(topv, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p[:, None]  # keep tokens until mass reached (incl. first)
        topv = jnp.where(keep, topv, -jnp.inf)

        choice = jax.random.categorical(key, topv, axis=-1)  # [B] index into candidates
        sampled = jnp.take_along_axis(topi, choice[:, None], axis=1)[:, 0]
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(temperature > 0.0), _sampled, _argmax, logits)


@partial(jax.jit, static_argnames=("top_k_max",))
def sample_tokens(
    logits: jax.Array,  # [B, V] float32
    key: jax.Array,
    temperature: jax.Array,  # [B] (0 = greedy)
    top_k: jax.Array,  # [B] int32 (0 = disabled)
    top_p: jax.Array,  # [B] (1.0 = disabled)
    top_k_max: int = 64,
) -> jax.Array:
    """Return sampled token ids [B].

    top-k is bounded by static `top_k_max` (per-slot k masks within the top-k_max
    candidates) to keep shapes static.
    """
    return _sample_core(logits, key, temperature, top_k, top_p, top_k_max)


@partial(jax.jit, static_argnames=("top_k_max",))
def sample_tokens_biased(
    logits: jax.Array,  # [B, V] float32
    bias: jax.Array,  # [B, V] float32 additive (0 allow / -1e9 ban / logit_bias)
    key: jax.Array,
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    top_k_max: int = 64,
) -> jax.Array:
    """`sample_tokens` with an additive logit bias applied ON DEVICE before
    argmax/sample — the grammar-mask / logit_bias path (llmd_tpu/structured).
    Also inlined (jit-in-jit) by the fused masked decode program
    (programs.py `_decode_multi_masked`), which gathers each row's bias from
    the staged dense tables per step — same sampler, bitwise-identical
    tokens whether the bias rides a unified step or a device chain.
    After a unified step it is a program of its own, over the step's logits
    and the host-built bias: engines that never see a structured request
    never compile it, and the unified program keeps its exact HLO."""
    return _sample_core(logits + bias, key, temperature, top_k, top_p,
                        top_k_max)
