"""Functional decoder-only transformer over a paged KV cache.

TPU-first design choices:
- Weights stacked ``[num_layers, ...]`` and the layer stack runs under ``lax.scan`` —
  one trace/compile regardless of depth, XLA pipelines the layers.
- All shapes static: the engine packs work into a fixed flat token budget; page
  tables are fixed-width. No data-dependent control flow.
- **Flat token batch** (vLLM-TPU style): the core takes ``tokens [N]`` holding a
  *mixed* batch — several sequences' prefill chunks plus decode tokens — described by
  ``cu_q_lens``/``num_seqs``. One compiled program serves chunked prefill, batched
  prefill across sequences, and decode; this is what lets the engine pack a full
  ``max-num-batched-tokens`` budget per step instead of one sequence's chunk.
- KV cache layout ``[L*P, page_size, 2*Hk, Dhp]`` — ONE flat page pool with the
  layer folded into the page dimension (layer ``l``'s page ``p`` lives at row
  ``l*P + p``), K/V interleaved per head (K at combined index 2h, V at 2h+1), and
  head_dim padded to the 128-lane tile. This is the layout the TPU
  ragged-paged-attention kernel consumes directly (lane padding is free — XLA's
  HBM tiling would pad the minor dim anyway), and the layer folding is what keeps
  the layer stack scannable: the cache threads through ``lax.scan`` as a *carry*
  updated by in-place scatters, and each layer's attention passes the kernel
  layer-offset page indices into the shared pool. Stacking the cache
  ``[L, P, ...]`` as scan xs/ys instead materializes the full 134 MB layer slice
  twice per layer per step (measured 25-90 ms/step on v5e — the silent dominant
  cost of the round-1 engine).
- bfloat16 everywhere on the matmul path (MXU); fp32 for softmax/rmsnorm accumulation.
- Sharding via logical axis names bound by ``llmd_tpu.parallel.mesh.ShardingRules``:
  heads/mlp → tp, experts → ep, batch → dp (GSPMD inserts the collectives).

Engine-parity note: this plays the role of vLLM's model runner on the reference's TPU
path (vllm `tpu_inference` plugin, docker/common-versions:5-6); attention is the
XLA-reference ragged paged attention below; the Pallas fused kernel lives in
``llmd_tpu.ops.paged_attention`` and is swapped in by the runner on TPU.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from llmd_tpu.models.config import ModelConfig
from llmd_tpu.models.parts import part
from llmd_tpu.ops.moe_dispatch import expert_hidden

LANE = 128


def padded_head_dim(head_dim: int) -> int:
    """Head dim as stored in the KV cache: padded up to the 128-lane tile."""
    return max(LANE, ((head_dim + LANE - 1) // LANE) * LANE)


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------


def param_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    """Logical axis names per parameter leaf (None entry = replicated axis)."""
    axes: dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        # stacked per-layer leaves carry a leading 'layers' axis
        "attn_norm": ("layers", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.is_mla:
        # TP shards over heads for W_Q/W_UK/W_UV/W_O; the latent path
        # (W_DKV/W_KR, the per-token shared c_kv) is replicated — it is tiny
        # and every head's shard needs the full latent (DeepSeek TP layout).
        axes |= ({
            "mla_wqa": ("layers", "embed", None),
            "mla_q_norm": ("layers", None),
            "mla_wqb": ("layers", None, "heads", "head_dim"),
        } if cfg.mla_q_lora_rank else {
            "mla_wq": ("layers", "embed", "heads", "head_dim"),
        }) | {
            "mla_wdkv": ("layers", "embed", None),
            "mla_wkr": ("layers", "embed", None),
            "mla_kv_norm": ("layers", None),
            "mla_wuk": ("layers", "heads", "head_dim", None),
            "mla_wuv": ("layers", "heads", None, "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
        }
    else:
        axes |= {
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
        }
    if cfg.qk_norm:
        axes |= {"q_norm": ("layers", "head_dim"), "k_norm": ("layers", "head_dim")}
    if cfg.attn_output_gate:
        axes["wg"] = ("layers", "embed", "heads", "head_dim")
    if cfg.attn_bias:
        axes |= {
            "bq": ("layers", "heads", "head_dim"),
            "bk": ("layers", "kv_heads", "head_dim"),
            "bv": ("layers", "kv_heads", "head_dim"),
            "bo": ("layers", "embed"),
        }
    if cfg.is_moe:
        axes |= {
            "router": ("layers", "embed", "experts"),
            "moe_wi": ("layers", "experts", "embed", "expert_mlp"),
            "moe_wo": ("layers", "experts", "expert_mlp", "embed"),
        }
        if cfg.moe_num_shared_experts:
            axes |= {
                "shared_wi": ("layers", "embed", "mlp"),
                "shared_wo": ("layers", "mlp", "embed"),
            }
        if cfg.moe_router_bias:
            axes["router_bias"] = ("layers", "experts")
    if not cfg.is_moe or cfg.moe_leading_dense_layers:
        # (a mixture model's leading dense layers: [k, ...], where the expert
        # leaves above are [L - k, ...])
        axes |= {"wi": ("layers", "embed", "mlp"), "wo_mlp": ("layers", "mlp", "embed")}
    if not cfg.tie_embeddings:
        axes["unembed"] = ("embed", "vocab")
    if cfg.has_lightning:
        axes |= {
            "lin_wq": ("layers", None, "embed"),
            "lin_wk": ("layers", None, "embed"),
            "lin_wv": ("layers", None, "embed"),
            "lin_wg": ("layers", None, "embed"),
            "lin_wo": ("layers", None, "embed"),
            "lin_q_norm": ("layers", "head_dim"),
            "lin_k_norm": ("layers", "head_dim"),
            "lin_o_norm": ("layers", "head_dim"),
        }
    if cfg.single_sublayer:
        # a layer is one sublayer: no ``mlp_norm``; the expert leaves are
        # stacked by 'experts' layer, the banks over the experts held here,
        # non-gated ones [.., D, F] where gated ones are [.., D, 2F]
        for gone in ("mlp_norm", "wi", "wo_mlp"):
            axes.pop(gone, None)
    if cfg.has_mamba2:
        axes |= {
            "m2_in": ("layers", "embed", "mamba_inner"),
            "m2_conv_w": ("layers", None, "mamba_inner"),
            "m2_conv_b": ("layers", "mamba_inner"),
            "m2_dt_bias": ("layers", None),
            "m2_a_log": ("layers", None),
            "m2_d": ("layers", None),
            "m2_norm": ("layers", "mamba_inner"),
            "m2_out": ("layers", "mamba_inner", "embed"),
        }
    if cfg.has_mamba:
        # each kind's leaves are stacked over the layers of that kind (their
        # leading axis is still 'layers': attention leaves [num_attn_layers,
        # ...] as above, mamba leaves [num_mamba_layers, ...]); the mixer's
        # channels are replicated (a tp mesh is refused for such a model)
        axes |= {
            "mamba_in": ("layers", "embed", "mamba_inner"),
            "mamba_conv_w": ("layers", None, "mamba_inner"),
            "mamba_x": ("layers", "mamba_inner", None),
            "mamba_dt_norm": ("layers", None),
            "mamba_b_norm": ("layers", None),
            "mamba_c_norm": ("layers", None),
            "mamba_dt": ("layers", None, "mamba_inner"),
            "mamba_dt_bias": ("layers", "mamba_inner"),
            "mamba_a_log": ("layers", None, "mamba_inner"),
            "mamba_d": ("layers", "mamba_inner"),
            "mamba_out": ("layers", "mamba_inner", "embed"),
        }
        if cfg.mamba_conv_bias:
            axes["mamba_conv_b"] = ("layers", "mamba_inner")
    return axes


def _stack_drawer(key: jax.Array, dt, splits: int = 24):
    """``draw(n, shape, scale)``: a stacked leaf [n, *shape] of scaled
    normals, drawn a layer at a time inside one jitted ``lax.map`` (float32
    draw, cast, next layer), each call from the next of ``splits`` keys."""
    keys = iter(jax.random.split(key, splits))

    def draw(n, shape, scale, pad=None):
        # ``pad``: zeros appended along each axis of a layer's leaf
        @jax.jit
        def stack(ks):
            def one(k):
                x = (jax.random.normal(k, shape, jnp.float32)
                     * scale).astype(dt)
                return x if pad is None else jnp.pad(x, [(0, p) for p in pad])
            return lax.map(one, ks)
        return stack(jax.random.split(next(keys), n))

    return draw, keys


# What ``router_bias`` is drawn at: the checkpoint's is a trained buffer of
# the order of the spread of a token's sigmoid scores; at zero, a program that
# dropped it, or added it into the weights, could not be told from a sound
# one. 0.1 moves the choice of a fifth of the routed copies at 8 experts and
# of nearly half at 64 (random weights; ``moe_bias_moved_choices_total``
# counts them: 47-48% in GLM-4.7-Flash's cell on the chip, PR 39).
ROUTER_BIAS_SCALE = 0.1


def _init_layered_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params of a mixture model with a q-side low-rank
    projection, leading dense layers or sigmoid routing (``cfg.layered_init``;
    MLA or GQA attention), each stacked leaf drawn a layer at a time as
    ``_init_hybrid_params`` does: ``init_params``'s eager draw holds two
    float32 copies of a whole stacked leaf, 2 x 9.7 GB for six expert banks
    of [64, 2048, 3072].

    Both norms are [L, ...] and the attention leaves [La, ...] (La the
    attention layers: every layer, but beside 'kda' mixers, whose leaves
    ``_init_kda_params`` adds); the expert leaves (``router``,
    ``router_bias`` over ALL the model's experts, ``moe_wi``, ``moe_wo`` over
    the ``cfg.moe_bank_slots`` held here, ``shared_*``) are [L - k, ...] and
    the leading dense layers' ``wi`` / ``wo_mlp`` [k, ...], k =
    ``cfg.moe_leading_dense_layers``. A head-wise output gate's ``wg`` is
    [La, D, H]."""
    dt = cfg.jax_dtype
    L, D, H = cfg.num_layers, cfg.hidden_size, cfg.num_heads
    La = cfg.num_attn_layers
    draw, keys = _stack_drawer(key, dt)
    s = D ** -0.5
    p: dict[str, jax.Array] = {
        "embed": draw(1, (cfg.vocab_size, D), 0.02)[0],
        "final_norm": jnp.ones((D,), dt),
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if cfg.is_mla:
        r, dr = cfg.mla_kv_lora_rank, cfg.mla_rope_dim
        dn, dv, rq = cfg.mla_qk_nope_dim, cfg.mla_v_head_dim, cfg.mla_q_lora_rank
        if rq:
            p["mla_wqa"] = draw(La, (D, rq), s)
            p["mla_q_norm"] = jnp.ones((La, rq), dt)
            p["mla_wqb"] = draw(La, (rq, H, dn + dr), rq ** -0.5)
        else:
            p["mla_wq"] = draw(La, (D, H, dn + dr), s)
        p["mla_wdkv"] = draw(La, (D, r), s)
        p["mla_wkr"] = draw(La, (D, dr), s)
        p["mla_kv_norm"] = jnp.ones((La, r), dt)
        p["mla_wuk"] = draw(La, (H, dn, r), dn ** -0.5)
        p["mla_wuv"] = draw(La, (H, r, dv), r ** -0.5)
        p["wo"] = draw(La, (H, dv, D), (H * dv) ** -0.5)
        if cfg.attn_output_gate:  # head-wise (config.attn_gate_per_head)
            p["wg"] = draw(La, (D, H), s)
    else:
        Hk, Dh = cfg.num_kv_heads, cfg.head_dim
        p["wq"] = draw(La, (D, H, Dh), s)
        p["wk"] = draw(La, (D, Hk, Dh), s)
        p["wv"] = draw(La, (D, Hk, Dh), s)
        p["wo"] = draw(La, (H, Dh, D), (H * Dh) ** -0.5)
    assert cfg.is_moe and cfg.moe_gated \
        and not (cfg.qk_norm or cfg.attn_bias), cfg
    k, F = cfg.moe_leading_dense_layers, cfg.intermediate_size
    Le, E, Eh = L - k, cfg.moe_num_experts, cfg.moe_bank_slots
    Fe = cfg.moe_intermediate_size or F
    p["router"] = draw(Le, (D, E), s)
    if cfg.moe_router_bias:
        p["router_bias"] = (jax.random.normal(next(keys), (Le, E), jnp.float32)
                            * cfg.moe_router_bias_scale)
    p["moe_wi"] = draw(Le, (Eh, D, 2 * Fe), s)
    p["moe_wo"] = draw(Le, (Eh, Fe, D), Fe ** -0.5)
    if cfg.moe_num_shared_experts:
        Fs = cfg.moe_shared_width
        p["shared_wi"] = draw(Le, (D, 2 * Fs), s)
        p["shared_wo"] = draw(Le, (Fs, D), Fs ** -0.5)
    if k:
        Fd = cfg.moe_dense_intermediate_size
        p["wi"] = draw(k, (D, 2 * Fd), s)
        p["wo_mlp"] = draw(k, (Fd, D), Fd ** -0.5)
    if not cfg.tie_embeddings:
        p["unembed"] = draw(1, (D, cfg.vocab_size), s)[0]
    return p


def _init_hybrid_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params of a model with mamba layers. Each stacked leaf is
    drawn a layer at a time inside one jitted ``lax.map`` (float32 draw, cast,
    next layer): ``init_params``'s eager draw holds two float32 copies of a
    whole stacked leaf, and ``wi`` at [28, 2560, 16384] would be 4.7 GB a copy
    beside 6 GB of finished leaves on a 16 GB chip.

    The mixer's leaves, for layer m of the mamba layers (published names in
    models/hf_loader.py): ``mamba_in`` [D, 2*Di] (x half, then gate z),
    ``mamba_conv_w`` [K, Di] (tap k multiplies the row K-1-k tokens back),
    ``mamba_conv_b`` [Di], ``mamba_x`` [Di, R + 2N] (dt, B, C),
    ``mamba_dt_norm`` / ``mamba_b_norm`` / ``mamba_c_norm`` (Jamba's inner
    RMSNorms), ``mamba_dt`` [R, Di] + ``mamba_dt_bias`` [Di], ``mamba_a_log``
    [N, Di] (A = -exp(.)), ``mamba_d`` [Di], ``mamba_out`` [Di, D]. The draw
    follows Mamba's own initialisation where it matters for the recurrence:
    A = -(1..N), dt's bias the inverse softplus of a step log-uniform in
    [1e-3, 1e-1], D = 1; so a state remembers hundreds of tokens."""
    dt = cfg.jax_dtype
    L, La, Lm = cfg.num_layers, cfg.num_attn_layers, cfg.num_mamba_layers
    D, H, Hk, Dh, F = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, cfg.intermediate_size)
    Di, N, K, R = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    norm, keys = _stack_drawer(key, dt, 16)
    s = D ** -0.5
    p: dict[str, jax.Array] = {
        "embed": norm(1, (cfg.vocab_size, D), 0.02)[0],
        "final_norm": jnp.ones((D,), dt),
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
        "wq": norm(La, (D, H, Dh), s),
        "wk": norm(La, (D, Hk, Dh), s),
        "wv": norm(La, (D, Hk, Dh), s),
        "wo": norm(La, (H, Dh, D), (H * Dh) ** -0.5),
        "wi": norm(L, (D, 2 * F), s),
        "wo_mlp": norm(L, (F, D), F ** -0.5),
        "mamba_in": norm(Lm, (D, 2 * Di), s),
        "mamba_conv_w": norm(Lm, (K, Di), K ** -0.5),
        "mamba_x": norm(Lm, (Di, R + 2 * N), Di ** -0.5),
        "mamba_dt_norm": jnp.ones((Lm, R), dt),
        "mamba_b_norm": jnp.ones((Lm, N), dt),
        "mamba_c_norm": jnp.ones((Lm, N), dt),
        "mamba_dt": norm(Lm, (R, Di), R ** -0.5),
        "mamba_d": jnp.ones((Lm, Di), dt),
        "mamba_out": norm(Lm, (Di, D), Di ** -0.5),
    }
    if cfg.mamba_conv_bias:
        p["mamba_conv_b"] = norm(Lm, (Di,), 0.2)
    step = jnp.exp(jax.random.uniform(
        next(keys), (Lm, Di), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    p["mamba_dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    p["mamba_a_log"] = jnp.broadcast_to(
        jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
        (Lm, N, Di)).astype(dt)
    if not cfg.tie_embeddings:
        p["unembed"] = norm(1, (D, cfg.vocab_size), s)[0]
    return p


# what the sparse layers' q/k norm weights are drawn around
SPARSE_QK_GAIN = 1.8


def _init_lightning_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params of a model with lightning layers, each stacked
    leaf drawn a layer at a time as ``_init_hybrid_params`` does (12 layers
    at MiniCPM-SALA's widths are 7.9 GB of finished leaves; the largest
    float32 draw in flight is one layer's ``wi``, 0.54 GB).

    Attention leaves are [La, ...] (``wg`` the output gate's, ``q_norm`` /
    ``k_norm`` a head's RMSNorm weights), lightning leaves [Ll, ...]:
    ``lin_wq`` / ``lin_wk`` / ``lin_wv`` / ``lin_wg`` [Hl * Dl, D] (out by
    in, a head's lanes side by side) and ``lin_wo`` [Hl * Dl, D] (in by out):
    plain matrices with the hidden size minor. Kept [D, Hl, Dl] or [D, Hl *
    Dl], the TPU compiler transposed the whole stacks of the four input
    leaves at every call's entry (the compiled text for a described v5e: four
    copies of 302 MB a step). Out by in, a layer's matrix is read where it
    lies in its stack once the heads are split on the stack and not on the
    product's rows (``lightning_views``; split on the rows, each of the four
    was copied out of its stack before its product, 33.5 MB a leaf and
    layer: PR 56). The sparse layers' ``wq`` / ``wg`` [D, H, Dh] are still
    copied out, a layer's 33.5 MB each: 16 heads of one input row share a
    tile, which no form of the product reads in place (``_hybrid_stack``).
    The three norms a head are ``lin_q_norm``
    / ``lin_k_norm`` / ``lin_o_norm`` [Dl]; norms and the MLP [L, ...]. The norms' weights are
    drawn around 1 (a program that left one out, or put it on the wrong
    side of RoPE, must not read as a sound one), but for the sparse layers'
    ``q_norm`` / ``k_norm``, drawn around ``SPARSE_QK_GAIN``: with gains of 1
    a query's softmax over thousands of random keys is near uniform, its
    output the mean of all values (near nothing), and a program that never
    selected would read as a sound one; with these a score has a standard
    deviation near 3.2, some tens of keys hold a query's weight as in a
    trained model, and which blocks were selected decides the output (at
    2.2 the check's sound readings spread too widely: PERF.md section 2)."""
    dt = cfg.jax_dtype
    L, La, Ll = cfg.num_layers, cfg.num_attn_layers, cfg.num_lightning_layers
    D, H, Hk, Dh, F = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, cfg.intermediate_size)
    Hl, Dl = cfg.lightning_heads, cfg.lightning_head_dim
    norm, keys = _stack_drawer(key, dt, 24)
    s = D ** -0.5

    def around_one(n, width, gain=1.0):
        return (gain * (1.0 + 0.1 * jax.random.normal(
            next(keys), (n, width), jnp.float32))).astype(dt)

    p: dict[str, jax.Array] = {
        "embed": norm(1, (cfg.vocab_size, D), 0.02)[0],
        "final_norm": jnp.ones((D,), dt),
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
        "wq": norm(La, (D, H, Dh), s),
        "wk": norm(La, (D, Hk, Dh), s),
        "wv": norm(La, (D, Hk, Dh), s),
        "wo": norm(La, (H, Dh, D), (H * Dh) ** -0.5),
        "wi": norm(L, (D, 2 * F), s),
        "wo_mlp": norm(L, (F, D), F ** -0.5),
        "lin_wq": norm(Ll, (Hl * Dl, D), s),
        "lin_wk": norm(Ll, (Hl * Dl, D), s),
        "lin_wv": norm(Ll, (Hl * Dl, D), s),
        "lin_wg": norm(Ll, (Hl * Dl, D), s),
        "lin_wo": norm(Ll, (Hl * Dl, D), (Hl * Dl) ** -0.5),
        "lin_q_norm": around_one(Ll, Dl),
        "lin_k_norm": around_one(Ll, Dl),
        "lin_o_norm": around_one(Ll, Dl),
    }
    if cfg.attn_output_gate:
        p["wg"] = norm(La, (D, H, Dh), s)
    if cfg.qk_norm:
        p["q_norm"] = around_one(La, Dh, SPARSE_QK_GAIN)
        p["k_norm"] = around_one(La, Dh, SPARSE_QK_GAIN)
    if not cfg.tie_embeddings:
        p["unembed"] = norm(1, (D, cfg.vocab_size), s)[0]
    return p


def _init_sublayer_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params of a stack of single sublayers (``cfg.
    single_sublayer``: Mamba-2, attention and expert layers), each stacked
    leaf drawn a layer at a time as ``_init_hybrid_params`` does: the largest
    float32 draw in flight is one layer's bank of held experts, [64, 2688,
    1856] = 1.28 GB at Nemotron-3-Nano's widths beside 9.9 GB of finished
    leaves.

    ``attn_norm`` [L, D] is every layer's one pre-norm; there is no
    ``mlp_norm``. Attention leaves [La, ...]. Expert leaves [Le, ...]:
    ``router`` [D, E] and ``router_bias`` [E] over ALL the model's experts,
    the banks ``moe_wi`` [Eh, D, F] / ``moe_wo`` [Eh, F, D] over the ``Eh =
    cfg.moe_bank_slots`` experts held here (slot s is expert
    ``cfg.moe_held_first + s``; non-gated: one up-projection, no gate half),
    their F rounded up to whole lane tiles with zero columns and rows
    (``cfg.moe_bank_width``), the shared expert ``shared_wi`` [D, Fs] /
    ``shared_wo`` [Fs, D]. Mamba-2 leaves [Lm, ...]: ``m2_in`` [D, Di + C +
    H, zero columns up to ``cfg.mamba2_in_width``] (gate z, then the conv's
    channels x | B | C, then dt a head: the published in-projection's
    order), ``m2_conv_w`` [K, C] (tap k multiplies the row K-1-k tokens
    back), ``m2_conv_b`` [C], ``m2_dt_bias`` / ``m2_a_log`` / ``m2_d`` [H],
    ``m2_norm`` [Di] (the gated norm's weight, a group's lanes side by side),
    ``m2_out`` [Di, D]. The draw follows Mamba-2's own initialisation where
    the recurrence feels it: A = -(uniform in 1..16) a head, dt's bias the
    inverse softplus of a step log-uniform in [1e-3, 1e-1]; D and the norm's
    weight are drawn around 1 so that a program that dropped one, or put the
    gate on the wrong side of the norm, does not read as a sound one."""
    dt = cfg.jax_dtype
    L, La, Lm, Le = (cfg.num_layers, cfg.num_attn_layers,
                     cfg.num_mamba2_layers, cfg.num_moe_layers)
    D, H, Hk, Dh = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Di, C, Hm, K = (cfg.mamba2_d_inner, cfg.mamba2_conv_dim, cfg.mamba2_heads,
                    cfg.mamba2_d_conv)
    norm, keys = _stack_drawer(key, dt, 24)
    s = D ** -0.5
    assert not (cfg.qk_norm or cfg.attn_bias or cfg.attn_output_gate), cfg

    def around_one(n, width):
        return (1.0 + 0.1 * jax.random.normal(
            next(keys), (n, width), jnp.float32)).astype(dt)

    p: dict[str, jax.Array] = {
        "embed": norm(1, (cfg.vocab_size, D), 0.02)[0],
        "final_norm": jnp.ones((D,), dt),
        "attn_norm": jnp.ones((L, D), dt),
        "wq": norm(La, (D, H, Dh), s),
        "wk": norm(La, (D, Hk, Dh), s),
        "wv": norm(La, (D, Hk, Dh), s),
        "wo": norm(La, (H, Dh, D), (H * Dh) ** -0.5),
        "m2_in": norm(Lm, (D, Di + C + Hm), s,
                      pad=(0, cfg.mamba2_in_width - (Di + C + Hm))),
        "m2_conv_w": norm(Lm, (K, C), K ** -0.5),
        "m2_conv_b": norm(Lm, (C,), 0.2),
        "m2_d": around_one(Lm, Hm),
        "m2_norm": around_one(Lm, Di),
        "m2_out": norm(Lm, (Di, D), Di ** -0.5),
    }
    step = jnp.exp(jax.random.uniform(
        next(keys), (Lm, Hm), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    p["m2_dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    p["m2_a_log"] = jnp.log(jax.random.uniform(
        next(keys), (Lm, Hm), jnp.float32, 1.0, 16.0)).astype(dt)
    if cfg.is_moe:
        E, Eh = cfg.moe_num_experts, cfg.moe_bank_slots
        Fe = cfg.moe_intermediate_size or cfg.intermediate_size
        up = (2 if cfg.moe_gated else 1)
        p["router"] = norm(Le, (D, E), s)
        if cfg.moe_router_bias:
            p["router_bias"] = (jax.random.normal(
                next(keys), (Le, E), jnp.float32) * cfg.moe_router_bias_scale)
        more = cfg.moe_bank_width - Fe  # zero columns / rows (non-gated)
        p["moe_wi"] = norm(Le, (Eh, D, up * Fe), s, pad=(0, 0, more))
        p["moe_wo"] = norm(Le, (Eh, Fe, D), Fe ** -0.5, pad=(0, more, 0))
        if cfg.moe_num_shared_experts:
            Fs = cfg.moe_shared_width
            p["shared_wi"] = norm(Le, (D, up * Fs), s)
            p["shared_wo"] = norm(Le, (Fs, D), Fs ** -0.5)
    if not cfg.tie_embeddings:
        p["unembed"] = norm(1, (D, cfg.vocab_size), s)[0]
    return p


def _init_kda_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params of a model whose layers are 'kda' mixers and latent
    attention, each over a mixture feed-forward, behind leading 'kda' layers
    over a dense SwiGLU (``cfg.recurrent_over_mixture``): the norms, the
    latent layers', the mixture's, the leading layers' and the table's leaves
    as ``_init_layered_params`` draws them, and the KDA leaves [Lk, ...] (Lk
    counts the leading layers too), each drawn a layer at a time:
    ``kda_wqkv`` [3 Hk Dk, D] (out by in: q's rows, then k's, then v's; plain
    matrices with the hidden size minor, as the lightning leaves are),
    ``kda_wf`` (the decay gate's) and ``kda_wg`` (the output gate's)
    [Hk Dk, D], ``kda_wb`` [Hk, D] (the write strength's), ``kda_wo``
    [Hk Dk, D] (in by out), ``kda_conv_w`` [K, 3 Hk Dk] (tap k multiplies the
    row K-1-k tokens back; no bias), ``kda_a_log`` [Hk], ``kda_dt_bias``
    [Hk Dk], ``kda_o_norm`` [Dk]. ``A_log`` is the log of a uniform draw in
    [0.5, 2] and ``dt_bias`` uniform in [-5, 0], so that a channel's
    log-decay ``bound * sigmoid(exp(A_log) (W_f x + dt_bias))`` spreads from
    near the bound (a channel that forgets in a token) to 1e-4 of it (one
    that keeps thousands): a state that was rounded, or a gate of another
    form, then shows; the output norm's weight is drawn around 1."""
    dt = cfg.jax_dtype
    D, Lk = cfg.hidden_size, cfg.num_kda_layers
    Di, Hk, K = cfg.kda_d_inner, cfg.kda_heads, cfg.kda_d_conv
    assert cfg.is_mla and not cfg.mla_q_lora_rank, cfg
    rest, own = jax.random.split(key)
    p = _init_layered_params(cfg, rest)
    norm, keys = _stack_drawer(own, dt, 12)
    s = D ** -0.5
    p.update({
        "kda_wqkv": norm(Lk, (3 * Di, D), s),
        "kda_wf": norm(Lk, (Di, D), s),
        "kda_wg": norm(Lk, (Di, D), s),
        "kda_wb": norm(Lk, (Hk, D), s),
        "kda_wo": norm(Lk, (Di, D), Di ** -0.5),
        "kda_conv_w": norm(Lk, (K, 3 * Di), K ** -0.5),
        "kda_o_norm": (1.0 + 0.1 * jax.random.normal(
            next(keys), (Lk, cfg.kda_head_dim), jnp.float32)).astype(dt),
        "kda_a_log": jnp.log(jax.random.uniform(
            next(keys), (Lk, Hk), jnp.float32, 0.5, 2.0)),
        "kda_dt_bias": jax.random.uniform(
            next(keys), (Lk, Di), jnp.float32, -5.0, 0.0),
    })
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params (scaled normal); shapes match param_logical_axes."""
    if cfg.has_kda:
        assert cfg.recurrent_over_mixture, "kda layers: over a mixture"
        return _init_kda_params(cfg, key)
    if cfg.single_sublayer:
        return _init_sublayer_params(cfg, key)
    if cfg.has_lightning:
        assert not cfg.has_mamba, "lightning and mamba layers in one stack"
        return _init_lightning_params(cfg, key)
    if cfg.has_recurrent:
        return _init_hybrid_params(cfg, key)
    if cfg.layered_init:
        return _init_layered_params(cfg, key)
    dt = cfg.jax_dtype
    L, D, H, Hk, Dh = cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    F = cfg.intermediate_size
    keys = iter(jax.random.split(key, 20))

    def norm(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dt)

    s = D ** -0.5
    p: dict[str, jax.Array] = {
        "embed": norm((cfg.vocab_size, D), 0.02),
        "final_norm": jnp.ones((D,), dt),
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if cfg.is_mla:
        # DeepSeek-V2/V3 latent attention (deepseek-ai modeling: kv_a_proj
        # W_DKV + decoupled-RoPE key W_KR, up-projections W_UK/W_UV absorbed
        # at inference). No wk/wv — the pool stores [c_kv ; k_rope] once per
        # token, shared by every head.
        r, dr = cfg.mla_kv_lora_rank, cfg.mla_rope_dim
        dn, dv = cfg.mla_qk_nope_dim, cfg.mla_v_head_dim
        p["mla_wq"] = norm((L, D, H, dn + dr), s)
        p["mla_wdkv"] = norm((L, D, r), s)
        p["mla_wkr"] = norm((L, D, dr), s)
        p["mla_kv_norm"] = jnp.ones((L, r), dt)
        p["mla_wuk"] = norm((L, H, dn, r), dn ** -0.5)
        p["mla_wuv"] = norm((L, H, r, dv), r ** -0.5)
        p["wo"] = norm((L, H, dv, D), (H * dv) ** -0.5)
    else:
        p["wq"] = norm((L, D, H, Dh), s)
        p["wk"] = norm((L, D, Hk, Dh), s)
        p["wv"] = norm((L, D, Hk, Dh), s)
        p["wo"] = norm((L, H, Dh, D), (H * Dh) ** -0.5)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, Dh), dt)
        p["k_norm"] = jnp.ones((L, Dh), dt)
    if cfg.attn_output_gate:
        p["wg"] = norm((L, D, H, Dh), s)
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((L, H, Dh), dt)
        p["bk"] = jnp.zeros((L, Hk, Dh), dt)
        p["bv"] = jnp.zeros((L, Hk, Dh), dt)
        p["bo"] = jnp.zeros((L, D), dt)
    if cfg.is_moe:
        E, Fe = cfg.moe_num_experts, cfg.moe_intermediate_size or F
        p["router"] = norm((L, D, E), s)
        p["moe_wi"] = norm((L, E, D, 2 * Fe), s)
        p["moe_wo"] = norm((L, E, Fe, D), Fe ** -0.5)
        if cfg.moe_num_shared_experts:
            Fs = F * cfg.moe_num_shared_experts
            p["shared_wi"] = norm((L, D, 2 * Fs), s)
            p["shared_wo"] = norm((L, Fs, D), Fs ** -0.5)
    else:
        p["wi"] = norm((L, D, 2 * F), s)  # fused gate+up (SwiGLU)
        p["wo_mlp"] = norm((L, F, D), F ** -0.5)
    if not cfg.tie_embeddings:
        p["unembed"] = norm((D, cfg.vocab_size), s)
    return p


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * w


@part("norm")
def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """``rms_norm`` as a layer's own norm (before the mixer, before the
    feed-forward, the final one): the part ``norm``. The norms inside a mixer
    or an attention's projections call ``rms_norm`` and stay in their part."""
    return rms_norm(x, w, eps)


@part("ffn")
def dense_ffn(h: jax.Array, lp: dict, mm) -> jax.Array:
    """A layer's dense SwiGLU on the normed rows ``h`` with the layer's
    leaves ``lp`` (``mm``: the layer's int8-aware weight product)."""
    return swiglu(h, None, None, mm=mm) if "wi_q" in lp else swiglu(
        h, lp["wi"], lp["wo_mlp"])


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., T, H, Dh]; positions: [..., T]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, half]
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, wi: jax.Array, wo: jax.Array, mm=None) -> jax.Array:
    """Fused gate/up MLP. ``mm(key, pattern, x)`` overrides the two matmuls
    (the int8 weight-only path injects its scaled-dot here — ONE body for
    both precisions, no drift hazard); wi/wo may be None when mm supplies
    the weights itself."""
    if mm is None:
        def mm(key, pattern, xin, _w={"wi": wi, "wo_mlp": wo}):
            return jnp.einsum(pattern, xin, _w[key])
    gate_up = mm("wi", "...d,df->...f", x)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return mm("wo_mlp", "...f,fd->...d", jax.nn.silu(gate) * up)


def relu2(x: jax.Array) -> jax.Array:
    """The square of relu: the one activation of non-gated experts."""
    r = jax.nn.relu(x)
    return r * r


MOE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu, "relu2": relu2}


def router_logits(h: jax.Array, router: jax.Array) -> jax.Array:
    """Float32 router logits [T, E] of the normed stream ``h`` [T, D]."""
    return jnp.einsum("td,de->te", h.astype(jnp.float32), router.astype(jnp.float32))


def moe_block(
    cfg: ModelConfig,
    x: jax.Array,
    router,
    wi,
    wo,
    eplb: Optional[tuple[jax.Array, jax.Array]] = None,
    matmul_impl=None,
    token_mask: Optional[jax.Array] = None,
    wi_scale: Optional[jax.Array] = None,
    wo_scale: Optional[jax.Array] = None,
    dispatch_impl=None,
    return_dropped: bool = False,
    logits: Optional[jax.Array] = None,
    slot_offset: Optional[jax.Array] = None,
    router_bias: Optional[jax.Array] = None,
    gather_rows: bool = False,
):
    """Top-k routed MoE with capacity-based dispatch (XLA-friendly static shapes).

    ``dispatch_impl(x, idx, topw, valid, wi, wo, wi_scale, wo_scale) -> y``
    replaces the capacity einsums below with the token-sorted drop-free path
    (ops/moe_dispatch; ``EngineConfig.moe_dispatch``). Routing — softmax,
    top-k, renorm, EPLB replica choice — stays HERE either way, so both
    paths see identical routing decisions and the einsum path remains a
    bit-for-bit parity reference. ``return_dropped`` appends a scalar int32
    count of routed-but-dropped copies (always 0 on the sorted path; the
    legacy path drops past capacity C) for the
    ``llmd_tpu:moe_dropped_tokens_total`` surface.

    x: [T, D]. Expert dim is sharded over the `ep` mesh axis; the dispatch/combine
    einsums lower to all-to-all when tokens are dp/sp-sharded — the XLA-native stand-in
    for DeepEP's NVSHMEM all-to-all (reference wide-ep decode.yaml:87-121).

    ``eplb = (replica_slots [E, R], replica_counts [E])`` switches to redundant-expert
    dispatch: ``wi``/``wo`` then hold *physical slot* weights [S, ...] (S >= E, slot
    order = EP-rank placement, see parallel.eplb) and each token spreads across its
    expert's replicas round-robin. ``matmul_impl(xe, w, slot_counts)`` overrides the
    expert GEMMs (Pallas grouped GEMM on TPU — reference DeepGEMM's role, SURVEY §2.5
    N7). Returns (y [T, D], logical expert counts [E] int32).

    ``cfg.moe_dbo`` splits tokens into two independent half-batches so XLA can overlap
    one half's all-to-all with the other's GEMMs (reference --enable-dbo,
    wide-ep decode.yaml:87-121).

    ``logits`` [T, E] float32: router logits the caller computed from another
    stream (``cfg.moe_router_input == "attn_norm"``: the layer's pre-attention
    normed stream); None computes them here from ``x``. The expert's gate
    activation is ``cfg.moe_activation``.

    ``slot_offset`` (traced scalar): ``wi``/``wo`` and their scales are every
    layer's bank along the slot axis, ``[L*E, ...]``, and this layer's experts
    begin at ``slot_offset``; only a ``dispatch_impl`` that says
    ``stacked_banks`` takes them so (no EPLB: its slots are per layer).

    ``cfg.moe_scoring == "sigmoid"`` (DeepSeek-V3's ``noaux_tc`` with one
    group): scores are sigmoids of the logits, the choice is the top-k of
    score + ``router_bias`` [E] (float32; the bias enters the choice and
    nothing else), the weights are the scores at the choice, renormalised
    and times ``cfg.moe_routed_scaling``. With ``return_dropped`` the third
    result is then ``[dropped, bias_moved, routed]`` int32: the routed copies
    whose expert the bias changed (``top_k(s + b)`` against ``top_k(s)``) and
    all routed copies, of live tokens, for
    ``llmd_tpu:moe_bias_moved_choices_total`` / ``moe_routed_copies_total``.

    ``cfg.moe_gated`` false: an expert is two products with the activation
    between (``wi`` [S, D, F]), and the dispatch is told so. ``gather_rows``:
    the sorted dispatch gathers its buffer and scatters no row
    (ops/moe_dispatch.dispatch_stage: the same buffer, and what the scatter
    did on the chip); the hybrid stack's expert layers ask for it.
    ``cfg.moe_held_count``: the banks hold experts ``moe_held_first ..
    moe_held_first + moe_held_count - 1`` only (a device's share of the
    layer). The router scores and chooses over all E; a routed copy whose
    expert is not held is marked invalid before dispatch (no GEMM row, no
    bank fetch) and the slot index is the expert's less ``moe_held_first``.
    The counts returned are then by held slot [Eh] and the third result
    gains a fourth entry, the held copies of live tokens
    (``llmd_tpu:moe_held_copies_total``). ``cfg.moe_n_group`` over 1 (the
    group-limited choice, ``ModelConfig.moe_n_group``): the third result is
    ``[dropped, bias_moved, routed, held, group_kept]``, the last the routed
    copies that the plain top-k of the same biased scores would have chosen
    too (``llmd_tpu:moe_group_kept_copies_total``).
    """
    T, D = x.shape
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    act = MOE_ACTIVATIONS[cfg.moe_activation]
    held = cfg.moe_held_count > 0

    with part("moe_router"):
        if logits is None:
            logits = router_logits(x, router)
        sigmoid = cfg.moe_scoring == "sigmoid"
        if sigmoid:
            scores = jax.nn.sigmoid(logits)
            _, plain = lax.top_k(scores, k)  # the choice the bias did not move
            biased = scores if router_bias is None else (
                scores + router_bias.astype(jnp.float32)[None, :])
            if cfg.moe_n_group > 1:
                # the group limit: a group's score is the sum of its two best,
                # the best ``moe_topk_group`` groups are kept, the top-k is
                # taken among their experts (``ungrouped``: what the plain top-k
                # of the same scores would have taken, for the counter)
                _, ungrouped = lax.top_k(biased, k)
                G = cfg.moe_n_group
                by_group = biased.reshape(T, G, E // G)
                group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
                _, best = lax.top_k(group_score, cfg.moe_topk_group)
                kept = jnp.sum(jax.nn.one_hot(best, G, dtype=jnp.int32), axis=1)
                topi = lax.top_k(jnp.where(
                    kept[:, :, None] > 0, by_group, -jnp.inf).reshape(T, E), k)[1]
            else:
                topi = plain if router_bias is None else lax.top_k(biased, k)[1]
            topw = jnp.take_along_axis(scores, topi, axis=-1)
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-20) \
                * cfg.moe_routed_scaling
        else:
            weights = jax.nn.softmax(logits, axis=-1)
            topw, topi = lax.top_k(weights, k)  # [T, k]
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
        # Padding tokens (prefill chunk tail, idle decode slots) must not consume
        # expert capacity nor pollute the EPLB load stats.
        valid = (
            token_mask.astype(jnp.int32)[:, None]
            if token_mask is not None
            else jnp.ones((T, 1), jnp.int32)
        )  # [T, 1]
        counts = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.int32) * valid[..., None], axis=(0, 1))
        if sigmoid:
            chosen = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.int32), axis=1)
            unmoved = jnp.sum(jax.nn.one_hot(plain, E, dtype=jnp.int32), axis=1)
            bias_moved = jnp.sum(chosen * (1 - unmoved) * valid)
            if cfg.moe_n_group > 1:
                group_kept = jnp.sum(chosen * jnp.sum(jax.nn.one_hot(
                    ungrouped, E, dtype=jnp.int32), axis=1) * valid)
        if held:
            assert eplb is None and sigmoid, "a share of the experts: no EPLB"
            routed = jnp.sum(counts)
            first, E = cfg.moe_held_first, cfg.moe_held_count
            valid = valid * ((topi >= first) & (topi < first + E)).astype(jnp.int32)
            topi = jnp.clip(topi - first, 0, E - 1)  # [T, k] held slot
            counts = counts[first:first + E]

        if eplb is not None:
            replica_slots, replica_counts = eplb  # [E, R], [E]
            S = wi.shape[0]
            rc = replica_counts[topi]  # [T, k]
            choice = (jnp.arange(T, dtype=jnp.int32)[:, None]
                      + jnp.arange(k, dtype=jnp.int32)[None, :]) % rc
            idx = replica_slots[topi, choice]  # [T, k] physical slot ids
        else:
            S, idx = E, topi

    stacked = {} if slot_offset is None else {
        "slot_offset": slot_offset, "num_slots": E}
    assert not stacked or (eplb is None and getattr(
        dispatch_impl, "stacked_banks", False)), "stacked banks: sorted local dispatch only"
    if sigmoid and getattr(dispatch_impl, "ordered_combine", False):
        # a token's copies are summed in the order of its choice, so that its
        # result does not depend on the rows beside it (ops/moe_dispatch.
        # combine_in_order). With this routing only: the softmax models'
        # step programs stay the StableHLO they were (ROADMAP: move them over
        # in a change of their own, with their cells measured)
        stacked["ordered_combine"] = True
    if not cfg.moe_gated:
        stacked["gated"] = False
    if gather_rows:
        stacked["gather_rows"] = True
    if dispatch_impl is not None:
        def half(x, idx, topw, valid):
            y = dispatch_impl(x, idx, topw, valid, wi, wo, wi_scale, wo_scale,
                              act=act, **stacked)
            return y, jnp.zeros((), jnp.int32)  # drop-free by construction
    else:
        half = None

    def half_einsum(x, idx, topw, valid):
        t = x.shape[0]
        # moe_capacity_factor is a legacy-path-only knob: the sorted path
        # has no capacity C to overflow
        C = max(1, int(t * k / S * cfg.moe_capacity_factor))
        with part("moe_dispatch"):
            onehot = jax.nn.one_hot(idx, S, dtype=jnp.int32) * valid[..., None]  # [t, k, S]
            flat = onehot.reshape(t * k, S)
            pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, S)
            keep_i = (pos_in_expert < C).astype(jnp.int32) * onehot  # exact count
            keep = keep_i.astype(x.dtype)
            disp = keep[..., None] * jax.nn.one_hot(pos_in_expert, C, dtype=x.dtype)
            comb = disp * topw[..., None, None].astype(x.dtype)
            disp2 = disp.sum(1)  # [t, S, C]
            comb2 = comb.sum(1)

            xe = jnp.einsum("tec,td->ecd", disp2, x)  # all-to-all in, [S, C, D]
        with part("moe_experts"):
            if matmul_impl is not None and wi_scale is None:
                slot_counts = jnp.sum(disp2, axis=(0, 2)).astype(jnp.int32)  # [S]
                gate_up = matmul_impl(xe, wi, slot_counts)
                ye = matmul_impl(expert_hidden(gate_up, act, cfg.moe_gated),
                                 wo, slot_counts)
            else:
                # int8 expert banks: per-expert per-output-channel scales
                # commute out of the dot (see models/quant.py): [S, 2F] / [S, D]
                gate_up = jnp.einsum("ecd,edf->ecf", xe, wi.astype(x.dtype))
                if wi_scale is not None:
                    gate_up = gate_up * wi_scale[:, None, :].astype(x.dtype)
                ye = jnp.einsum("ecf,efd->ecd",
                                expert_hidden(gate_up, act, cfg.moe_gated),
                                wo.astype(x.dtype))
                if wo_scale is not None:
                    ye = ye * wo_scale[:, None, :].astype(x.dtype)
        with part("moe_combine"):
            y = jnp.einsum("tec,ecd->td", comb2, ye)  # all-to-all back
            kept = jnp.sum(keep_i)  # routed copies that got a capacity slot
        return y, kept

    if half is None:
        half = half_einsum

    if cfg.moe_dbo and T % 2 == 0 and T >= 2:
        h = T // 2
        ya, ka = half(x[:h], idx[:h], topw[:h], valid[:h])
        yb, kb = half(x[h:], idx[h:], topw[h:], valid[h:])
        y, kept = jnp.concatenate([ya, yb]), ka + kb
    else:
        y, kept = half(x, idx, topw, valid)
    if not return_dropped:
        return y, counts
    if dispatch_impl is not None:
        dropped = jnp.zeros((), jnp.int32)
    else:
        dropped = jnp.sum(counts) - kept  # routed minus kept == capacity drops
    if sigmoid and cfg.moe_n_group > 1:
        dropped = jnp.stack([dropped, bias_moved,
                             routed if held else jnp.sum(counts),
                             jnp.sum(counts), group_kept])
    elif sigmoid:
        dropped = jnp.stack([dropped, bias_moved, routed, jnp.sum(counts)]
                            if held else
                            [dropped, bias_moved, jnp.sum(counts)])
    return y, counts, dropped


# ---------------------------------------------------------------------------
# Paged KV cache (kernel-native combined layout)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, num_pages: int, page_size: int,
               dtype=None, pack: int = 1) -> jax.Array:
    """[L*P, page_size, 2*(Hk/pack), Dhp] flat pool: layer l's page p at row
    l*P + p; K at combined head 2h, V at 2h+1.

    MLA allocates a SINGLE plane — one shared [c_kv ; k_rope] row per token
    (keys and values are the same latent in absorbed attention, so a second
    plane would double KV bytes for nothing; write_kv and the XLA impl detect
    the one-row layout by HkC == 1).

    ``dtype`` overrides the model dtype for the pool — float8_e4m3fn halves
    decode's KV read stream (EngineConfig.kv_cache_dtype="fp8"); the Pallas
    kernel dequantizes pages in VMEM and the XLA fallback upcasts at use.
    ``pack`` > 1 stores that many real KV heads per lane row (ops/packed_kv:
    reclaims the head_dim lane padding; requires Dhp == pack * head_dim).
    """
    if pack > 1:
        assert padded_head_dim(cfg.kv_cache_head_dim) == pack * cfg.kv_cache_head_dim
        assert cfg.kv_cache_heads % pack == 0
    rows = 1 if cfg.is_mla else 2 * (cfg.kv_cache_heads // pack)
    return jnp.zeros(
        (cfg.kv_pool_folds * num_pages, page_size, rows,
         padded_head_dim(cfg.kv_cache_head_dim)),
        dtype if dtype is not None else cfg.jax_dtype,
    )


def init_state(cfg: ModelConfig, seats: int) -> dict[str, jax.Array]:
    """The recurrent-state pools of a model with recurrent layers, a slot a
    seat. Mamba layers: ``ssm`` [Lm, seats + 1, N, Di] in
    ``cfg.mamba_state_dtype`` (d_state on the sublanes, d_inner on the lanes:
    what ops/selective_scan reads) and ``conv`` [Lm, K - 1, seats + 1, Di] in
    the model's dtype: the last K - 1 pre-conv rows of every slot, kept as
    planes whose two minor dimensions are (slots, d_inner), so that both step
    programs move whole tiles (``conv_window`` says what they moved slot
    major). Lightning layers: ``lin`` [Ll, seats + 1, Hl, Dl, Dl] in
    ``cfg.lightning_state_dtype``, a matrix a head (ops/lightning_attention).
    Slot ``seats`` is scratch: a unified step's padding rows are mapped to
    it, so that every row of a call has a slot to name and none of them a
    seat's. ``forward_core`` takes the pools in the ``cache`` argument, as
    ``{"kv": pool, **state}``."""
    S = seats + 1
    state: dict[str, jax.Array] = {}
    if cfg.has_mamba:
        Lm = cfg.num_mamba_layers
        state["ssm"] = jnp.zeros(
            (Lm, S, cfg.mamba_d_state, cfg.mamba_d_inner),
            jnp.dtype(cfg.mamba_state_dtype))
        state["conv"] = jnp.zeros(
            (Lm, cfg.mamba_d_conv - 1, S, cfg.mamba_d_inner), cfg.jax_dtype)
    if cfg.has_mamba2:
        # the pools of a Mamba-1 model under their names, a head's matrix
        # state [head_dim, d_state] held transposed and a group's heads side
        # by side: ``ssm`` [Lm2, seats + 1, groups, d_state, heads / groups
        # * head_dim] (ops/mamba2_ssd has the reason), ``conv`` over x, B
        # and C alike
        Lm, G = cfg.num_mamba2_layers, cfg.mamba2_groups
        state["ssm"] = jnp.zeros(
            (Lm, S, G, cfg.mamba2_d_state, cfg.mamba2_d_inner // G),
            jnp.dtype(cfg.mamba_state_dtype))
        state["conv"] = jnp.zeros(
            (Lm, cfg.mamba2_d_conv - 1, S, cfg.mamba2_conv_dim), cfg.jax_dtype)
    if cfg.has_lightning:
        state["lin"] = jnp.zeros(
            (cfg.num_lightning_layers, S, cfg.lightning_heads,
             cfg.lightning_head_dim, cfg.lightning_head_dim),
            jnp.dtype(cfg.lightning_state_dtype))
    if cfg.has_kda:
        # the lightning layers' pool under its name, a head's delta-rule
        # state held transposed (ops/kda_attention), and a conv window over
        # the q, k and v channels side by side
        state["lin"] = jnp.zeros(
            (cfg.num_kda_layers, S, cfg.kda_heads, cfg.kda_head_dim,
             cfg.kda_head_dim), jnp.dtype(cfg.lightning_state_dtype))
        state["conv"] = jnp.zeros(
            (cfg.num_kda_layers, cfg.kda_d_conv - 1, S, 3 * cfg.kda_d_inner),
            cfg.jax_dtype)
    return state


def init_compressed_keys(cfg: ModelConfig, num_pages: int,
                         dtype=None) -> jax.Array:
    """The compressed-key plane of a model with sparse selection: one key a
    page of the folded KV pool, ``[folds * P, Dhp]`` in the pool's type (a
    sixteenth of K at pages of 16). Rides with the recurrent-state pools
    (``{"kv": pool, "ck": plane, ...}``); ops/sparse_select writes and reads
    it. An entry belongs to its page: nothing allocates or frees it."""
    return jnp.zeros((cfg.kv_pool_folds * num_pages,
                      padded_head_dim(cfg.kv_cache_head_dim)),
                     dtype if dtype is not None else cfg.jax_dtype)


# float8_e4m3fn has no inf: values past ±448 convert to nan, so fp8 cache
# writes clamp first. K/V activations live at O(1)–O(10); the clamp is a
# no-op in practice and fuses into the write's convert.
_FP8_MAX = 448.0


@part("kv_write")
def write_kv(flat_cache: jax.Array, k: jax.Array, v: jax.Array, slots: jax.Array) -> jax.Array:
    """Write new tokens' K/V into flat cache slots (in place under donation).

    flat_cache: [S, 2*Hk, Dhp] (the pool viewed as token slots); k/v:
    [N, Hk, Dhp] (already lane-padded); slots: [N] global slot ids
    (layer_offset + page_id * page_size + offset). Slot -1 marks padding
    (routed out of bounds and dropped by the scatter).
    """
    S, HkC, Dhp = flat_cache.shape
    N, Hk, _ = k.shape
    idx = jnp.where(slots >= 0, slots, S)
    if HkC == 1:
        # single-plane MLA pool: k IS the shared latent; v is ignored
        row = k.astype(jnp.float32) if flat_cache.dtype == jnp.float8_e4m3fn else k
        if flat_cache.dtype == jnp.float8_e4m3fn:
            row = jnp.clip(row, -_FP8_MAX, _FP8_MAX)
        return flat_cache.at[idx].set(row.astype(flat_cache.dtype), mode="drop")
    if HkC < 2 * Hk:
        # packed layout (ops/packed_kv): f real heads per lane row — strip the
        # lane padding and concatenate adjacent heads in slot order
        f = 2 * Hk // HkC
        Dh = Dhp // f
        k = k[:, :, :Dh].reshape(N, Hk // f, Dhp)
        v = v[:, :, :Dh].reshape(N, Hk // f, Dhp)
    # interleave K/V per (packed) head: K even / V odd combined index
    kv = jnp.stack([k, v], axis=2).reshape(N, HkC, Dhp)
    if flat_cache.dtype == jnp.float8_e4m3fn:
        kv = jnp.clip(kv.astype(jnp.float32), -_FP8_MAX, _FP8_MAX)
    kv = kv.astype(flat_cache.dtype)
    return flat_cache.at[idx].set(kv, mode="drop")


def ragged_paged_attention_xla(
    q: jax.Array,  # [N, H, Dhp] flat query tokens (lane-padded)
    layer_cache: jax.Array,  # [P, ps, 2*Hk, Dhp]
    page_tables: jax.Array,  # [B, max_pages] (-1 = unmapped)
    positions: jax.Array,  # [N] global positions (-1 = padding row)
    seq_slots: jax.Array,  # [N] owning batch row per token
    kv_lens: jax.Array,  # [B] tokens resident incl. this step's
    *,
    scale: float,
    cu_q_lens: Optional[jax.Array] = None,  # unused (uniform impl signature)
    num_seqs: Optional[jax.Array] = None,  # unused (uniform impl signature)
    chunk_k: Optional[jax.Array] = None,  # unused (ring-attn impls only)
    chunk_v: Optional[jax.Array] = None,  # unused (ring-attn impls only)
    sliding_window: Optional[int] = None,  # key j visible iff pos - window < j
) -> jax.Array:
    """Reference-semantics ragged paged attention (gather + mask), jittable anywhere.

    With ``sliding_window`` and ``cu_q_lens`` the pool is read through
    ``window_view`` (page tables shifted past the pages before the window), as
    the Pallas impl reads it; with ``sliding_window`` alone (the [B, T]
    wrapper, which has no ``cu_q_lens``) the window is a mask over the whole
    table. The two are equal exactly: a masked key's weight is an exact zero.

    Each query gathers ONLY its owning sequence's pages via the page table, and
    the token axis runs in fixed-size chunks under ``lax.map`` — peak memory is
    O(chunk * max_pages_per_seq * ps) regardless of pool size OR batch size, so
    the fallback degrades gracefully at serving scale (the pool-wide variant
    allocated multi-TB score tensors at bench shapes; a per-token gather would
    duplicate a prefill's KV once per query token). On TPU the Pallas kernel
    (llmd_tpu.ops.paged_attention) replaces this with per-sequence KV streaming.
    """
    N, H, Dhp = q.shape
    Pn, ps, HkC, _ = layer_cache.shape
    # HkC == 1: single-plane MLA pool — the stored latent serves as BOTH key
    # and value (absorbed attention), i.e. MQA with shared k==v
    single_plane = HkC == 1
    Hk = 1 if single_plane else HkC // 2
    B, maxp = page_tables.shape
    qpk = H // Hk

    b_all = jnp.clip(seq_slots, 0, B - 1)
    if sliding_window is not None and cu_q_lens is not None:
        page_tables, kv_lens, off = window_view(
            page_tables, kv_lens, cu_q_lens, sliding_window, ps)
        positions = jnp.where(positions >= 0, positions - off[b_all], -1)
    C = min(32, N)  # token chunk: bounds the per-step KV gather
    Np = (N + C - 1) // C * C
    qp = jnp.pad(q, ((0, Np - N), (0, 0), (0, 0))).reshape(Np // C, C, H, Dhp)
    posp = jnp.pad(positions, (0, Np - N), constant_values=-1).reshape(Np // C, C)
    bp = jnp.pad(b_all, (0, Np - N)).reshape(Np // C, C)
    key_pos = jnp.arange(maxp * ps, dtype=jnp.int32)[None, :]  # [1, S]

    def one_chunk(args):
        qc, posc, bc = args  # [C, H, Dhp], [C], [C]
        pt = page_tables[bc]  # [C, maxp] owning sequence's pages, in order
        kv = layer_cache[jnp.where(pt >= 0, pt, 0)]  # [C, maxp, ps, 2Hk, Dhp]
        kv = kv.reshape(C, maxp * ps, HkC, Dhp)
        if kv.dtype == jnp.float8_e4m3fn:
            # mirror the Pallas kernel's VMEM dequant: fp8 pages upcast at
            # use; scores already run f32 and p@v must not run in fp8
            kv = kv.astype(qc.dtype)
        if single_plane:
            kc = vc = kv  # [C, S, 1, Dhp] shared latent
        else:
            kc, vc = kv[:, :, 0::2], kv[:, :, 1::2]  # [C, S, Hk, Dhp]
        qg = qc.reshape(C, Hk, qpk, Dhp)
        s = jnp.einsum("nkqd,nskd->nkqs", qg.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
        # key j sits at sequence position j (page tables list pages in order)
        mask = (
            (pt[:, key_pos[0] // ps] >= 0)
            & (key_pos <= posc[:, None])
            & (key_pos < kv_lens[bc][:, None])
            & (posc[:, None] >= 0)
        )  # [C, S]
        if sliding_window is not None:
            mask = mask & (key_pos > posc[:, None] - sliding_window)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        # fully masked (padding) rows: softmax is uniform garbage; caller ignores
        return jnp.einsum("nkqs,nskd->nkqd", p.astype(vc.dtype), vc)

    out = lax.map(one_chunk, (qp, posp, bp))  # [Np//C, C, Hk, qpk, Dhp]
    return out.reshape(Np, H, Dhp)[:N]


def window_first_page(kv_lens, q_lens, window: int, page_size: int,
                      align_pages: int = 1):
    """The first page a window layer's call is handed, per sequence: the
    whole pages before the window of the sequence's earliest query row,
    rounded down to ``align_pages``. Plain arithmetic, so that the host's
    counter (numpy) and the program (jax) compute the same number."""
    first = (kv_lens - q_lens - window + 1).clip(0) // page_size
    return first // align_pages * align_pages


def window_view(page_tables: jax.Array, kv_lens: jax.Array,
                cu_q_lens: jax.Array, window: int, page_size: int,
                align_pages: int = 1):
    """What a layer with a sliding window reads its pool through: each
    sequence's page table and length without the whole pages that lie before
    the window of its earliest query row.

    A call's query rows of a sequence are its last ``q_len`` tokens, so the
    earliest sits at position ``kv_len - q_len`` and sees keys from
    ``kv_len - q_len - window + 1`` on. ``first`` [B] counts the whole pages
    before that key; the table is shifted left by it (entries shifted in from
    past its end are -1) and the length shortened by ``first * page_size``.
    Causality and the window are both differences of positions, so the shift
    changes no score: it removes pages no query of the call can see, which a
    kernel that masks by window would still have read.

    ``align_pages`` rounds ``first`` down to a multiple of the KV block of a
    kernel that streams its keys block by block: a block of the shifted call
    is then a block of the unshifted one, the blocks left out are those whose
    every key is masked (their contribution is multiplied by an exact zero
    when the first visible block arrives), and the result is the unshifted
    call's bit for bit, whatever chunks the sequence was computed in. Without
    it a row's sums would be blocked differently from call to call, and greedy
    tokens served cold and from the prefix cache would part at near ties.

    Returns ``(page_tables, kv_lens, first * page_size)``; the last is what a
    caller that masks by absolute position (the XLA impl) subtracts from its
    positions."""
    B, maxp = page_tables.shape
    q_lens = cu_q_lens[1:B + 1] - cu_q_lens[:B]
    first = window_first_page(kv_lens, q_lens, window, page_size, align_pages)
    # a shift per row as a barrel shifter: one static shift and one select a
    # bit of ``first``, all elementwise (a gather of the table took as long
    # as the window layer's attention call on the chip)
    shifted = page_tables
    for bit in range(maxp.bit_length()):
        step = min(1 << bit, maxp)
        moved = jnp.pad(shifted[:, step:], ((0, 0), (0, step)),
                        constant_values=-1)
        shifted = jnp.where(((first >> bit) & 1)[:, None] == 1, moved, shifted)
    return shifted, kv_lens - first * page_size, first * page_size


# ---------------------------------------------------------------------------
# Mamba mixer over the recurrent-state pool
# ---------------------------------------------------------------------------


def _inner_norms(cfg: ModelConfig, dbc: jax.Array, w: jax.Array) -> tuple:
    """Jamba's three inner RMSNorms at once on ``dbc`` [N, R + 2 Nst]
    float32 (dt, B, C side by side; ``w`` their weights side by side):
    returns (dt [N, R], B [N, Nst], C [N, Nst]).

    The three sums of squares are one matrix product with a 0/1 matrix of
    three columns (padded to a lane tile), at the highest precision: the
    matrix unit adds a row's terms in one order whatever the number of rows.
    A ``reduce`` is lowered as the array's shape suggests, and on the chip
    the sums over a [64, 160] and a [256, 160] array parted in the last bit:
    a decode row's norm then depended on whether the unified step or the
    fused decode call brought it, the cast to the next product's type turned
    the bit into a step of that type now and then, and the recurrent state
    carried it on (greedy tokens served alone and in a batch parted). Written
    as explicit adds in a fixed order the sums were the same too, as some
    twenty device operations a layer where this is three."""
    R, Nst = cfg.mamba_dt_rank, cfg.mamba_d_state
    sizes = (R, Nst, Nst)
    seg = np.zeros((R + 2 * Nst, 128), np.float32)
    at = 0
    for c, n in enumerate(sizes):
        seg[at:at + n, c] = 1.0 / n
        at += n
    mean_sq = jnp.dot(dbc * dbc, jnp.asarray(seg),
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)  # [N, 128]
    r = lax.rsqrt(mean_sq[:, :3] + cfg.rms_eps)
    scale = jnp.concatenate(
        [jnp.broadcast_to(r[:, c:c + 1], (dbc.shape[0], n))
         for c, n in enumerate(sizes)], axis=1)
    out = dbc * scale * w
    return out[:, :R], out[:, R:R + Nst], out[:, R + Nst:]


def mamba_vectors(cfg: ModelConfig, params: dict) -> dict:
    """The mamba layers' vectors, float32 and side by side, as ``mamba_mixer``
    takes them: ``mamba_vec`` [Lm, K + 3, Di] (conv taps, conv bias, dt bias,
    D) and ``mamba_norms`` [Lm, R + 2 Nst]. A layer then slices two arrays
    where it would slice and convert eight (each a device operation of its
    own a layer a step, which a trace pays for by the event)."""
    f32 = lambda k: params[k].astype(jnp.float32)  # noqa: E731
    bias = (f32("mamba_conv_b") if cfg.mamba_conv_bias
            else jnp.zeros_like(f32("mamba_d")))
    return {"mamba_vec": jnp.concatenate(
                [f32("mamba_conv_w")] + [v[:, None] for v in (
                    bias, f32("mamba_dt_bias"), f32("mamba_d"))], axis=1),
            "mamba_norms": jnp.concatenate(
                [f32("mamba_dt_norm"), f32("mamba_b_norm"),
                 f32("mamba_c_norm")], axis=-1)}


def window_plan(conv_shape: tuple, n_tokens: int, row_slots, seq_slots,
                cu_q_lens: jax.Array, live: jax.Array, fresh: jax.Array) -> dict:
    """What ``conv_window`` needs to know of a call's packing, which is the
    same for every layer: worked out once a call, outside the scans over the
    layers (inside them the compiler leaves every small index operation in
    the loop, a device operation of a microsecond or more a layer each).

    The fused decode call (``row_slots`` None) needs its rows' flags only. A
    unified step is seen from two sides. By slot: ``held`` [S] whether a live
    row holds the slot, ``zeroed`` [S] whether that row is fresh, ``n`` [S]
    its chunk's length, and for the window's plane k the chunk row that
    becomes the slot's row k (``last`` [K-1, S], where ``from_chunk``). By
    token: its slot, its offset into its chunk (held to 0 .. K-2) and, for
    tap t, whether it reads the window (``in_window`` [K-1, N])."""
    if row_slots is None:
        return {"live": live, "fresh": fresh}
    _, k1, S, _ = conv_shape
    B, N = live.shape[0], n_tokens
    hit = (row_slots[None, :] == jnp.arange(S, dtype=jnp.int32)[:, None]) \
        & live[None, :]  # [S, B]: at most one live row a slot
    held, row = hit.any(axis=1), jnp.argmax(hit, axis=1)
    n = jnp.where(held, (cu_q_lens[1:] - cu_q_lens[:-1])[row], 0)
    e = n - k1 + jnp.arange(k1, dtype=jnp.int32)[:, None]  # [K-1, S]
    b = jnp.clip(seq_slots, 0, B - 1)
    off = jnp.arange(N, dtype=jnp.int32) - cu_q_lens[b]
    return {"held": held, "zeroed": held & fresh[row], "n": n,
            "last": jnp.clip(cu_q_lens[row] + e, 0, N - 1),
            "from_chunk": e >= 0,
            "slot": row_slots[b], "off": jnp.clip(off, 0, k1 - 1),
            "in_window": (off >= 0) & (
                off < k1 - jnp.arange(k1, dtype=jnp.int32)[:, None])}


def conv_window(conv: jax.Array, o, xr: jax.Array, plan: dict):
    """The causal conv's inputs for the pre-conv rows ``xr`` [N, Di] of mamba
    layer ``o``, and the window pool after them: returns (taps, conv), taps[k]
    [N, Di] being the row K - 1 - k tokens before each token of its sequence.

    ``conv`` [Lm, K - 1, S, Di] holds every slot's window (its last K - 1
    pre-conv rows, oldest first) as planes: window row k of all the slots of
    a layer is one dense [S, Di] array, so whatever is read, chosen or written
    here has (rows, d_inner) as its two minor dimensions and fills its tiles.
    Kept slot major, [S, K - 1, Di], the K - 1 = 3 rows would sit alone on a
    bf16 sublane tile; the TPU compiler therefore kept that pool as planes of
    its own accord and paid for the difference: a transposition of the whole
    pool at every forward, where the program folded the layer into the slot
    axis, and a layer's update at an offset off the tile's boundary (PERF.md
    section 5, "since PR 40"). The layer is the pool's leading axis (``o``
    traced), so a layer's planes start on a tile boundary whatever S is.
    ``plan`` is the call's ``window_plan``.

    A token fewer than j tokens into its row's chunk finds the row j tokens
    back in the window, any other in ``xr``; a fresh row's window reads as
    zeros. Afterwards a live row's window is the last K - 1 rows of
    [window ; chunk]; a row that is not live leaves its slot as it is. In the
    fused decode call (row b is seat b and brings one token) the taps are the
    planes' first B rows, and the new window is plane k <- plane k + 1, last
    plane <- ``xr``."""
    K, S = conv.shape[1] + 1, conv.shape[2]
    N, Di = xr.shape
    zero = jnp.zeros((), conv.dtype)
    if "live" in plan:  # the fused decode call: row b is seat b
        assert N == plan["live"].shape[0], "one token a seat"
        win = lax.dynamic_slice(conv, (o, 0, 0, 0), (1, K - 1, N, Di))[0]
        use = jnp.where(plan["fresh"][None, :, None], zero, win)
        new_win = jnp.concatenate([use[1:], xr[None]], axis=0)  # [K-1, B, Di]
        conv = lax.dynamic_update_slice(
            conv, jnp.where(plan["live"][None, :, None], new_win, win)[None],
            (o, 0, 0, 0))
        return [use[k] for k in range(K - 1)] + [xr], conv
    # A unified step. On the chip a gather moves some 190 MB a millisecond
    # and a scatter some 45, where a dense select runs at the memory's rate
    # (PERF.md section 6, PR 40), so the window is worked on where it lies,
    # slot by slot and dense, and rows are gathered twice only: the window
    # rows of a chunk's first K - 1 tokens, one row of (K - 1) x Di a token,
    # and the chunks' last K - 1 rows, one a slot and plane. The layer's
    # planes are taken out of the pool and put back whole: given the pool
    # itself (64 MB at jamba2-3b's sizes) as a gather's operand, the TPU
    # compiler moves all of it to VMEM and back around every layer.
    planes = lax.dynamic_index_in_dim(conv, o, 0, keepdims=False)
    use = jnp.where(plan["zeroed"][None, :, None], zero, planes)
    # wall[off, s]: the window rows a token `off` tokens into slot s's chunk
    # still reads, side by side in its taps' order: tap t reads row t + off
    none = jnp.zeros((S, Di), conv.dtype)
    wall = jnp.stack([jnp.concatenate(
        [use[t + off] if t + off < K - 1 else none for t in range(K - 1)],
        axis=1) for off in range(K - 1)])  # [K-1, S, (K-1) Di]
    before = wall.at[plan["off"], plan["slot"]].get(
        mode="promise_in_bounds")  # [N, (K-1) Di]
    # xr[i - j] where the row j tokens back lies in the chunk. The rows before
    # the array's start, which no token of a chunk is given, take the array's
    # first element and not a literal: around a literal XLA's CPU backend
    # compiles those rows apart and rounds their conv otherwise (no fused
    # multiply-add), and the tests' float32 runs then show a prompt's split.
    def back(j):
        return lax.pad(xr, xr[0, 0], ((j, -j, 0), (0, 0, 0)))

    taps = [jnp.where(plan["in_window"][t][:, None],
                      before[:, t * Di:(t + 1) * Di], back(K - 1 - t))
            for t in range(K - 1)] + [xr]
    # the last K-1 rows of [window ; chunk]: plane k takes a row of the chunk,
    # or window row k + n
    new = []
    for k in range(K - 1):
        kept = use[k]
        for m in range(1, K - 1 - k):
            kept = jnp.where((plan["n"] == m)[:, None], use[k + m], kept)
        last = xr.at[plan["last"][k]].get(mode="promise_in_bounds")
        row = jnp.where(plan["from_chunk"][k][:, None], last, kept)
        new.append(jnp.where(plan["held"][:, None], row, planes[k]))
    return taps, lax.dynamic_update_index_in_dim(conv, jnp.stack(new), o, 0)


def mamba_mixer(cfg: ModelConfig, lp: dict, h: jax.Array, conv: jax.Array,
                ssm: jax.Array, o, plan: dict, row_slots,
                cu_q_lens: jax.Array, live: jax.Array, fresh: jax.Array,
                scan_impl, mm):
    """Mamba layer ``o``'s mixer (``o`` traced: the layer's ordinal among the
    mamba layers) on the normed rows ``h`` [N, D] of a flat mixed batch;
    returns (out [N, D], conv pool, ssm pool).

    ``conv`` [Lm, K - 1, S, Di] is the conv window's pool, a layer's slots
    the rows of K - 1 planes (``conv_window``, with the call's ``plan``);
    ``ssm`` [Lm * S, Nst, Di] the state pool with the layer folded into the
    slot axis, as the scan kernel indexes it. ``row_slots`` [B] names each
    batch row's slot, or is None where row b is seat b and brings one token
    (the fused decode call: no gather). Rows that are not ``live`` leave both
    pools as they are; a ``fresh`` row (first position 0) starts from a zero
    window and a zero state.

    Matrix products run in the model's dtype with float32 results; the conv,
    the three inner norms, softplus, the recurrence (``scan_impl``,
    ops/selective_scan) and the gate are float32. ``mm(key, pattern, x)`` is
    the layer's int8-aware weight product. ``lp`` holds the layer's matrices
    and ``mamba_a_log`` as stored, and its vectors as ``mamba_vectors`` packs
    them: ``mamba_vec`` and ``mamba_norms``."""
    B = live.shape[0]
    Di, K = cfg.mamba_d_inner, cfg.mamba_d_conv
    dt_ = cfg.jax_dtype
    with part("mixer_in"):
        xz = mm("mamba_in", "nd,de->ne", h)
        xr, z = xz[:, :Di], xz[:, Di:]  # pre-conv rows, gate (model dtype)
        vec = lp["mamba_vec"]  # [K + 3, Di] float32: conv taps, conv bias, dt bias, D
        taps, conv = conv_window(conv, o, xr, plan)
        acc = vec[K]
        for k in range(K):
            acc = acc + vec[k] * taps[k].astype(jnp.float32)
        x = jax.nn.silu(acc)  # [N, Di] float32
        dbc = mm("mamba_x", "ne,er->nr", x.astype(dt_), jnp.float32)
        dlow, Bm, Cm = _inner_norms(cfg, dbc, lp["mamba_norms"])
        delta = jax.nn.softplus(
            mm("mamba_dt", "nr,re->ne", dlow.astype(dt_), jnp.float32)
            + vec[K + 1])
        A = -jnp.exp(lp["mamba_a_log"].astype(jnp.float32))  # [Nst, Di]
    with part("mixer"):
        slots = o * conv.shape[2] + (
            jnp.arange(B, dtype=jnp.int32) if row_slots is None else row_slots)
        y, ssm = scan_impl(x, delta, Bm, Cm, A, ssm, slots, cu_q_lens, live,
                           fresh)
    with part("mixer_out"):
        y = y + vec[K + 2] * x
        y = y * jax.nn.silu(z.astype(jnp.float32))
        return mm("mamba_out", "ne,ed->nd", y.astype(dt_)), conv, ssm


def _head_mean_sq(xf: jax.Array) -> jax.Array:
    """Mean of squares over the lanes of each head of float32 ``xf`` [N, H,
    D], as [N, H, 1]: one matrix product at the highest precision, since the
    matrix unit adds a row's terms in one order whatever the number of rows.
    A ``reduce`` is lowered as the array's shape suggests (``_inner_norms``
    has the story), and here too a recurrent state stands behind the norm: on
    the chip a decode row's q through the 32-row and the 256-row program
    parted by a bf16 step now and then (PR 42)."""
    N, H, D = xf.shape
    ones = jnp.full((D, 128), 1.0 / D, jnp.float32)
    return jnp.dot((xf * xf).reshape(N * H, D), ones,
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)[:, :1].reshape(N, H, 1)


def head_rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """``rms_norm`` over the lanes of each head of ``x`` [N, H, D], its sum
    of squares by ``_head_mean_sq``."""
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(_head_mean_sq(xf) + eps)).astype(x.dtype) * w


def _head_norm(y: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the lanes of each head of float32 ``y`` [N, H, D], the
    result float32 too (the lightning layers' output norm)."""
    return y * lax.rsqrt(_head_mean_sq(y) + eps) * w.astype(jnp.float32)


def mamba2_vectors(cfg: ModelConfig, params: dict) -> dict:
    """The Mamba-2 layers' vectors, float32 and side by side, as
    ``mamba2_mixer`` takes them: ``m2_vec`` [Lm, K + 1, C] (conv taps, conv
    bias) and ``m2_heads`` [Lm, 3, H] (dt bias, A = -exp(A_log), D)."""
    f32 = lambda k: params[k].astype(jnp.float32)  # noqa: E731
    return {"m2_vec": jnp.concatenate(
                [f32("m2_conv_w"), f32("m2_conv_b")[:, None]], axis=1),
            "m2_heads": jnp.stack(
                [f32("m2_dt_bias"), -jnp.exp(f32("m2_a_log")), f32("m2_d")],
                axis=1)}


def mamba2_mixer(cfg: ModelConfig, lp: dict, h: jax.Array, conv: jax.Array,
                 ssm: jax.Array, o, plan: dict, row_slots,
                 cu_q_lens: jax.Array, live: jax.Array, fresh: jax.Array,
                 ssd_impl, mm):
    """Mamba-2 layer ``o``'s mixer (``o`` traced: the layer's ordinal among
    the Mamba-2 layers) on the normed rows ``h`` [N, D] of a flat mixed
    batch; returns (out [N, D], conv pool, ssm pool).

    ``conv`` [Lm, K - 1, S, C] is the conv window's pool over the C = Di + 2
    G Nst channels of x, B and C (``conv_window``, with the call's ``plan``);
    ``ssm`` [Lm * S, G, Nst, Di / G] the state pool with the layer folded into
    the slot axis, as the kernel indexes it. ``row_slots``, ``live`` and
    ``fresh`` as for ``mamba_mixer``.

    One in-projection gives the gate z, the conv's rows and dt a head. The
    conv and silu are float32 and their result is rounded to the model's
    type (x, B and C go to the matrix unit as they are); softplus, the
    recurrence (``ssd_impl``, ops/mamba2_ssd), the skip, the gate and the
    norm are float32. The gate comes before the norm, and the norm runs over
    each group's Di / G lanes, its sum of squares a matrix product
    (``_head_mean_sq``: a recurrent state stands behind it). ``lp`` holds
    the layer's matrices and norm weight as stored and its vectors as
    ``mamba2_vectors`` packs them."""
    B, N = live.shape[0], h.shape[0]
    Di, C, K = cfg.mamba2_d_inner, cfg.mamba2_conv_dim, cfg.mamba2_d_conv
    H, G, Nst = cfg.mamba2_heads, cfg.mamba2_groups, cfg.mamba2_d_state
    dt_ = cfg.jax_dtype
    with part("mixer_in"):
        zxd = mm("m2_in", "nd,de->ne", h)
        z, xr, dtr = zxd[:, :Di], zxd[:, Di:Di + C], zxd[:, Di + C:Di + C + H]
        vec = lp["m2_vec"]  # [K + 1, C] float32: conv taps, conv bias
        taps, conv = conv_window(conv, o, xr, plan)
        acc = vec[K]
        for k in range(K):
            acc = acc + vec[k] * taps[k].astype(jnp.float32)
        xbc = jax.nn.silu(acc).astype(dt_)  # [N, C]
        x = xbc[:, :Di].reshape(N, H, Di // H)
        Bm = xbc[:, Di:Di + G * Nst].reshape(N, G, Nst)
        Cm = xbc[:, Di + G * Nst:].reshape(N, G, Nst)
        hv = lp["m2_heads"]  # [3, H] float32: dt bias, A, D
        delta = jax.nn.softplus(dtr.astype(jnp.float32) + hv[0])
    with part("mixer"):
        slots = o * conv.shape[2] + (
            jnp.arange(B, dtype=jnp.int32) if row_slots is None else row_slots)
        y, ssm = ssd_impl(x, delta, hv[1], Bm, Cm, ssm, slots, cu_q_lens,
                          live, fresh)
    with part("mixer_out"):
        y = y + hv[2][None, :, None] * x.astype(jnp.float32)
        y = y.reshape(N, Di) * jax.nn.silu(z.astype(jnp.float32))
        y = _head_norm(y.reshape(N, G, Di // G),
                       lp["m2_norm"].reshape(G, Di // G), cfg.rms_eps)
        return (mm("m2_out", "ne,ed->nd", y.reshape(N, Di).astype(dt_)),
                conv, ssm)


def lightning_mixer(cfg: ModelConfig, lp: dict, h: jax.Array, lin: jax.Array,
                    o, positions: jax.Array, row_slots, cu_q_lens: jax.Array,
                    live: jax.Array, fresh: jax.Array, lin_impl, mm):
    """Lightning layer ``o``'s mixer (``o`` traced: the layer's ordinal among
    the lightning layers) on the normed rows ``h`` [N, D] of a flat mixed
    batch; returns (out [N, D], state pool).

    ``lin`` [Ll * S, Hl, Dl, Dl] is the matrix-state pool with the layer
    folded into the slot axis, as the kernel indexes it; ``row_slots`` [B]
    names each batch row's slot, or is None where row b is seat b (the fused
    decode call). q and k take an RMSNorm a head and RoPE over all lanes; the
    recurrence (``lin_impl``, ops/lightning_attention) gives float32 rows,
    which take an RMSNorm a head and the sigmoid gate before the output
    projection. ``mm(key, pattern, x)`` is the layer's int8-aware weight
    product. The four input leaves come a head apart, [Hl, Dl, D]
    (``lightning_views``): the product gives [N, Hl, Dl] and no reshape
    stands between the layer's slice of the stack and the product."""
    from llmd_tpu.ops.lightning_attention import head_slopes

    B, N = live.shape[0], h.shape[0]
    Hl, Dl = cfg.lightning_heads, cfg.lightning_head_dim

    def heads(key):
        return mm(key, "nd,hkd->nhk", h)

    with part("mixer_in"):
        q = head_rms_norm(heads("lin_wq"), lp["lin_q_norm"], cfg.rms_eps)
        k = head_rms_norm(heads("lin_wk"), lp["lin_k_norm"], cfg.rms_eps)
        v = heads("lin_wv")
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    with part("mixer"):
        slots = o * (lin.shape[0] // cfg.num_lightning_layers) + (
            jnp.arange(B, dtype=jnp.int32) if row_slots is None else row_slots)
        y, lin = lin_impl(q, k, v, head_slopes(cfg.lightning_heads), lin,
                          slots, cu_q_lens, live, fresh,
                          scale=cfg.lightning_head_dim ** -0.5)
    with part("mixer_out"):
        y = _head_norm(y, lp["lin_o_norm"], cfg.rms_eps)
        with part("mixer_in"):  # a gate: the innermost scope is its part
            gate = jax.nn.sigmoid(heads("lin_wg").astype(jnp.float32))
        return mm("lin_wo", "ne,ed->nd",
                  (y * gate).astype(cfg.jax_dtype).reshape(N, Hl * Dl)), lin


def lightning_views(cfg: ModelConfig, params: dict) -> dict:
    """The lightning layers' four input stacks as ``lightning_mixer`` reads
    them, [Ll, Hl, Dl, D] of the stored [Ll, Hl * Dl, D]: a view of the
    whole stack taken before the loops (the hidden size stays minor and a
    head's 128 rows are whole tiles, so nothing moves). Split after the
    product instead (``"nd,ed->ne"`` and a reshape of the rows), the TPU
    compiler moved the split onto the layer's matrix, between its slice of
    the stack and the product, and the slice then ran as an operation of its
    own: 33.5 MB copied into on-chip memory a leaf and layer before the
    product read the copy (PERF.md section 5, PR 56)."""
    Hl, Dl = cfg.lightning_heads, cfg.lightning_head_dim
    return {k: params[k].reshape(params[k].shape[0], Hl, Dl, -1)
            for k in ("lin_wq", "lin_wk", "lin_wv", "lin_wg")}


def kda_mixer(cfg: ModelConfig, lp: dict, h: jax.Array, conv: jax.Array,
              lin: jax.Array, o, plan: dict, row_slots, cu_q_lens: jax.Array,
              live: jax.Array, fresh: jax.Array, kda_impl, mm):
    """KDA layer ``o``'s mixer (``o`` traced: the layer's ordinal among the
    kda layers) on the normed rows ``h`` [N, D] of a flat mixed batch;
    returns (out [N, D], conv pool, state pool).

    ``conv`` [Lk, K - 1, S, 3 Di] is the conv window's pool over the q, k
    and v channels (``conv_window``, with the call's ``plan``); ``lin`` [Lk *
    S, Hk, Dk, Dk] the matrix-state pool with the layer folded into the slot
    axis, as the kernel indexes it. ``row_slots``, ``live`` and ``fresh`` as
    for ``mamba_mixer``. One product gives the pre-conv rows of q, k and v;
    the conv, SiLU, the L2 norms of q and k a head (their sums of squares a
    matrix product, ``_head_mean_sq``: a recurrent state stands behind
    them), the decay gate, the write strength, the recurrence (``kda_impl``,
    ops/kda_attention), the output norm a head and the gate a lane are
    float32."""
    B, N = live.shape[0], h.shape[0]
    Hk, Dk, Di, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_d_inner, cfg.kda_d_conv
    f32 = jnp.float32

    def unit(x):  # x / |x|_2 a head
        return x * lax.rsqrt(_head_mean_sq(x) * Dk + 1e-6)

    with part("mixer_in"):
        xr = mm("kda_wqkv", "nd,ed->ne", h)  # pre-conv rows (model dtype)
        taps, conv = conv_window(conv, o, xr, plan)
        vec = lp["kda_conv_w"].astype(f32)
        acc = vec[0] * taps[0].astype(f32)
        for t in range(1, K):
            acc = acc + vec[t] * taps[t].astype(f32)
        qkv = jax.nn.silu(acc).reshape(N, 3, Hk, Dk)
        q, k, v = unit(qkv[:, 0]) * Dk ** -0.5, unit(qkv[:, 1]), qkv[:, 2]
        gate_in = (mm("kda_wf", "nd,ed->ne", h, f32)
                   + lp["kda_dt_bias"].astype(f32))
        g = cfg.kda_gate_lower_bound * jax.nn.sigmoid(
            jnp.exp(lp["kda_a_log"].astype(f32))[None, :, None]
            * gate_in.reshape(N, Hk, Dk))
        b = jax.nn.sigmoid(mm("kda_wb", "nd,hd->nh", h, f32))
    with part("mixer"):
        slots = o * conv.shape[2] + (
            jnp.arange(B, dtype=jnp.int32) if row_slots is None else row_slots)
        y, lin = kda_impl(q, k, v, g, b, lin, slots, cu_q_lens, live, fresh)
    with part("mixer_out"):
        y = _head_norm(y, lp["kda_o_norm"], cfg.rms_eps)
        with part("mixer_in"):  # a gate: the innermost scope is its part
            gate = jax.nn.sigmoid(mm("kda_wg", "nd,ed->ne", h, f32))
        return mm("kda_wo", "ne,ed->nd",
                  (y.reshape(N, Di) * gate).astype(cfg.jax_dtype)), conv, lin


# ---------------------------------------------------------------------------
# Full forward over the scanned layer stack
# ---------------------------------------------------------------------------


def _joined(cfg: ModelConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    """The stream and a block's output: ``x + cfg.residual_scale * y`` (1.0
    leaves the program the plain sum it was)."""
    return x + y if cfg.residual_scale == 1.0 else x + y * cfg.residual_scale


def _weight_mm(lp: dict, key: str, pattern: str, xin: jax.Array, out=None):
    """``forward_core``'s int8-aware weight product for a layer's leaves
    ``lp``, with the result's type (``preferred_element_type``)."""
    if key in lp:
        return jnp.einsum(pattern, xin, lp[key], preferred_element_type=out)
    y = jnp.einsum(pattern, xin, lp[key + "_q"].astype(xin.dtype),
                   preferred_element_type=out)
    return y * lp[key + "_scale"].astype(y.dtype)


def _hybrid_stack(cfg, params, attention_layer, x, flat_cache, state,
                  positions, seq_slots, cu_q_lens, state_slots, scan_impl,
                  lin_impl=None, ssd_impl=None, expert_layer=None,
                  expert_keys=(), kda_impl=None):
    """The layer stack of a model whose layers differ in kind and in
    parameter shapes: a scan over the periods of ``cfg.layer_kinds`` whose
    body runs the period's runs of one kind (7 mamba, 1 attention, 6 mamba),
    a run of several layers as an inner scan, so that no layer is traced more
    than once a run. A layer takes its leaves from the whole stacks by index
    (layer ``l`` of the norms and the MLP, ordinal ``m`` or ``a`` of its
    kind's own leaves): the stacks stay loop invariants, where a period's
    slice handed to an inner loop would be copied out. ``attention_layer`` is
    ``forward_core``'s own layer, given the attention ordinal as its pool
    offset.

    Whether the index folds into the product that reads the layer's matrix
    is the TPU compiler's to say, and it was read from the compiled text of
    minicpm-sala-9b's programs (``tools/program_parts.py --weight-copies``;
    PERF.md section 5, PR 56). It folds (the ``dynamic-slice`` sits inside
    the product's fusion, which streams the matrix out of the stack) for a
    plain matrix whose slice goes to the product as it is: the MLP's ``wi``
    / ``wo_mlp``, ``lin_wo``, and since PR 56 the lightning layers'
    ``lin_wq`` / ``wk`` / ``wv`` / ``wg`` (``lightning_views``: the heads
    split on the stack before the loops, not on the product's rows) and the
    sparse layers' ``wo`` (viewed flat before the loops by ``forward_core``,
    not between slice and product). It did not fold where a reshape of the
    weight stood between slice and product, or was moved there from the
    rows by the compiler (``bitcast.600`` behind
    ``constant_dynamic-slice_fusion.26``-``.29`` and ``slice.99``-``.107``,
    ``constant_dynamic-slice_fusion.30``): the slice then ran as a copy of
    33.5 MB into on-chip memory, 46 us before a product of as long. It does
    not fold for a leaf stored [D, H, Dh] with 32 heads (the sparse layers'
    ``wq`` and ``wg``; ``wk`` / ``wv``, 2 MB): a tile of the stored matrix
    holds 16 heads of ONE input row, the product wants a head's [D, Dh], and
    its fusion relays the matrix out of on-chip memory
    (``copy_bitcast_fusion`` inside the product), so the slice stays a copy
    whatever the einsum (weights as the left operand, the gate on flat rows,
    ``lax.dynamic_slice`` for the clamped index: all compiled to the same
    copy; a leaf stored [H, D, Dh], as the compiler lays out Jamba's 20
    heads by itself, folds). Written out, the sparse pair's slices have
    fixed offsets and the compiler fetches them beside other work: 0.18 ms
    less a unified step and 0.43 ms MORE a decode-shaped call on the chip,
    so the pair stays a scan.

    The state pools ride the scans as carries, updated in place. The SSM pool
    is folded to [Lm * S, Nst, Di] on the way in and unfolded on the way out
    (its minor dimensions are whole tiles, so neither moves a byte) because
    the scan kernel names a state block by one index, and so is a lightning
    layer's matrix-state pool ([Ll * S, Hl, Dl, Dl]); the conv window's pool
    keeps its layer as a leading axis of its own, which a mamba layer indexes
    by its ordinal: folded into the slot axis, every layer but the first
    would start off a sublane tile's boundary (S = seats + 1), and the fold
    itself was a relayout of the pool at every call's entry and exit. The
    compressed-key plane of a model with sparse selection (``ck``) is handed
    to the attention layers and back.

    A stack of single sublayers (``cfg.single_sublayer``) has layers of kind
    'mamba2' (``mamba2_mixer`` on the ``ssm`` and ``conv`` pools), 'attention'
    (``attention_layer`` stops after the mixer) and 'experts':
    ``expert_layer(h, lp, ordinal) -> (y, counts, drops)``, forward_core's
    own mixture feed-forward, on the layer's leaves ``expert_keys`` (by its
    ordinal among the expert layers). Their counts [Le, slots] and drops
    ride the scans' carry and are returned.

    'kda' mixers over a mixture (``cfg.recurrent_over_mixture``): a layer is
    its mixer ('kda': ``kda_mixer`` on the ``lin`` and ``conv`` pools;
    'attention': ``attention_layer``, latent, which runs the feed-forward
    itself) and then ``expert_layer`` on the leaves of its ordinal among the
    mixture layers; the ``cfg.moe_leading_dense_layers`` leading layers,
    'kda' mixers over the dense SwiGLU, are written out before the scan over
    the periods. Counts and drops ride the carry as above.

    Returns (x, flat KV pool, state, expert counts, drops)."""
    from llmd_tpu.ops.selective_scan import row_flags, selective_scan_xla

    assert cu_q_lens is not None, "a model with recurrent layers needs cu_q_lens"
    pools = {k: state[k] for k in ("ck",) if k in state}
    if cfg.has_mamba:
        scan_impl = scan_impl or selective_scan_xla
        Lm, S1 = state["ssm"].shape[:2]
        pools["ssm"] = state["ssm"].reshape((Lm * S1,) + state["ssm"].shape[2:])
        pools["conv"] = state["conv"]
    if cfg.has_mamba2:
        from llmd_tpu.ops.mamba2_ssd import mamba2_ssd_xla

        ssd_impl = ssd_impl or mamba2_ssd_xla
        pools["ssm"] = state["ssm"].reshape((-1,) + state["ssm"].shape[2:])
        pools["conv"] = state["conv"]
    if cfg.has_kda:
        from llmd_tpu.ops.kda_attention import kda_attention_xla

        kda_impl = kda_impl or kda_attention_xla
        pools["lin"] = state["lin"].reshape((-1,) + state["lin"].shape[2:])
        pools["conv"] = state["conv"]
    live, fresh = row_flags(positions, cu_q_lens)
    if "conv" in pools:
        plan = window_plan(pools["conv"].shape, x.shape[0], state_slots,
                           seq_slots, cu_q_lens, live, fresh)
    if cfg.has_lightning:
        from llmd_tpu.ops.lightning_attention import lightning_attention_xla

        lin_impl = lin_impl or lightning_attention_xla
        pools["lin"] = state["lin"].reshape((-1,) + state["lin"].shape[2:])
        params = dict(params, **lightning_views(cfg, params))

    def present(*keys):
        return tuple(v for k in keys for v in (k, k + "_q", k + "_scale")
                     if v in params)

    if cfg.has_mamba:
        params = dict(params, **mamba_vectors(cfg, params))
    if cfg.has_mamba2:
        params = dict(params, **mamba2_vectors(cfg, params))
    counted = ()
    lead = cfg.moe_leading_dense_layers  # (over 0 beside 'kda' layers only)
    if (cfg.single_sublayer or cfg.has_kda) and cfg.is_moe:
        # what the expert layers report, by their ordinal, on the carry
        counted = ("moe_cnt", "moe_drop")
        _, cnt0, drop0 = jax.eval_shape(
            lambda: expert_layer(x, {k: params[k][0] for k in expert_keys}, 0))
        pools["moe_cnt"] = jnp.zeros((cfg.num_moe_layers,) + cnt0.shape,
                                     cnt0.dtype)
        pools["moe_drop"] = jnp.zeros(drop0.shape, drop0.dtype)
    shared = present("attn_norm", "mlp_norm", "wi", "wo_mlp")
    own = {"mamba": present("mamba_in", "mamba_x", "mamba_dt", "mamba_a_log",
                            "mamba_out", "mamba_vec", "mamba_norms"),
           "attention": present("wq", "wk", "wv", "wo", "wg", "q_norm",
                                "k_norm", "mla_wq", "mla_wdkv", "mla_wkr",
                                "mla_kv_norm", "mla_wuk", "mla_wuv"),
           "kda": present("kda_wqkv", "kda_wf", "kda_wg", "kda_wb", "kda_wo",
                          "kda_conv_w", "kda_o_norm", "kda_a_log",
                          "kda_dt_bias"),
           "lightning": present("lin_wq", "lin_wk", "lin_wv", "lin_wg",
                                "lin_wo", "lin_q_norm", "lin_k_norm",
                                "lin_o_norm"),
           "mamba2": present("m2_in", "m2_norm", "m2_out", "m2_vec",
                             "m2_heads"),
           "experts": tuple(expert_keys)}

    def leaves(keys, i):
        return {k: lax.dynamic_index_in_dim(params[k], i, 0, keepdims=False)
                for k in keys}

    if cfg.has_kda:
        # the feed-forward's leaves are taken by the layer that runs it
        shared = present("attn_norm", "mlp_norm")
        dense = present("wi", "wo_mlp")

    def counts_kept(pools, cnt, drop, e):
        return {**pools,
                "moe_cnt": lax.dynamic_update_index_in_dim(
                    pools["moe_cnt"], cnt, e, 0),
                "moe_drop": pools["moe_drop"] + drop}

    def kda_layer(kind, carry, l, o, leading=False):
        """Layer ``l``, the ``o``-th of its kind ('kda' or 'attention'), of
        a stack of mixers over a mixture; a ``leading`` layer's feed-forward
        is the dense one."""
        x, flat_cache, pools = carry
        e = l - lead  # the layer's ordinal among the mixture layers
        lp = {**leaves(shared, l), **leaves(own[kind], o),
              **(leaves(dense, l) if leading else leaves(expert_keys, e))}
        if kind == "attention":
            (x, flat_cache), (cnt, drop) = attention_layer(
                (x, flat_cache), lp, o, cfg.attn_window_pattern[0],
                cfg.rope_pattern[0], moe_ordinal=e)
            return x, flat_cache, counts_kept(pools, cnt, drop, e)

        def mm(key, pattern, xin, out=None):
            return _weight_mm(lp, key, pattern, xin, out)

        h = layer_norm(x, lp["attn_norm"], cfg.rms_eps)
        o_mix, conv, lin = kda_mixer(
            cfg, lp, h, pools["conv"], pools["lin"], o, plan, state_slots,
            cu_q_lens, live, fresh, kda_impl, mm)
        pools = {**pools, "conv": conv, "lin": lin}
        x = _joined(cfg, x, o_mix)
        h = layer_norm(x, lp["mlp_norm"], cfg.rms_eps)
        if leading:
            return _joined(cfg, x, dense_ffn(h, lp, mm)), flat_cache, pools
        y, cnt, drop = expert_layer(h, lp, e)
        return _joined(cfg, x, y), flat_cache, counts_kept(pools, cnt, drop, e)

    def one_layer(kind, carry, l, o):
        """Layer ``l``, the ``o``-th of its kind."""
        if cfg.has_kda:
            return kda_layer(kind, carry, l, o)
        x, flat_cache, pools = carry
        lp = {**leaves(shared, l), **leaves(own[kind], o)}
        if kind == "attention":
            ck = (pools["ck"],) if "ck" in pools else ()
            (x, flat_cache, *ck), _ = attention_layer(
                (x, flat_cache, *ck), lp, o, cfg.attn_window_pattern[0],
                cfg.rope_pattern[0])
            return x, flat_cache, ({**pools, "ck": ck[0]} if ck else pools)

        def mm(key, pattern, xin, out=None):
            return _weight_mm(lp, key, pattern, xin, out)

        h = layer_norm(x, lp["attn_norm"], cfg.rms_eps)
        if kind == "experts":
            y, cnt, drop = expert_layer(h, lp, o)
            return _joined(cfg, x, y), flat_cache, counts_kept(
                pools, cnt, drop, o)
        if kind == "mamba2":
            o_mix, conv, ssm = mamba2_mixer(
                cfg, lp, h, pools["conv"], pools["ssm"], o, plan, state_slots,
                cu_q_lens, live, fresh, ssd_impl, mm)
            return _joined(cfg, x, o_mix), flat_cache, {
                **pools, "conv": conv, "ssm": ssm}
        if kind == "mamba":
            o_mix, conv, ssm = mamba_mixer(
                cfg, lp, h, pools["conv"], pools["ssm"], o, plan, state_slots,
                cu_q_lens, live, fresh, scan_impl, mm)
            pools = {**pools, "conv": conv, "ssm": ssm}
        else:
            o_mix, lin = lightning_mixer(
                cfg, lp, h, pools["lin"], o, positions, state_slots,
                cu_q_lens, live, fresh, lin_impl, mm)
            pools = {**pools, "lin": lin}
        x = _joined(cfg, x, o_mix)
        h = layer_norm(x, lp["mlp_norm"], cfg.rms_eps)
        return _joined(cfg, x, dense_ffn(h, lp, mm)), flat_cache, pools

    period = len(cfg.layer_kinds)
    count = {k: cfg.layer_kinds.count(k) for k in own}

    def one_period(carry, i):
        seen = dict.fromkeys(own, 0)
        for kind, j0, n in cfg.layer_runs:
            l0, o0 = i * period + j0, i * count[kind] + seen[kind]
            if lead:  # the leading layers stand before the periods
                l0, o0 = l0 + lead, o0 + (lead if kind == "kda" else 0)
            if n == 1:
                carry = one_layer(kind, carry, l0, o0)
            else:
                carry, _ = lax.scan(
                    lambda c, j, kind=kind, l0=l0, o0=o0: (
                        one_layer(kind, c, l0 + j, o0 + j), None),
                    carry, jnp.arange(n, dtype=jnp.int32))
            seen[kind] += n
        return carry, None

    carry = (x, flat_cache, pools)
    for l in range(lead):
        carry = kda_layer("kda", carry, l, jnp.int32(l), leading=True)
    (x, flat_cache, pools), _ = lax.scan(
        one_period, carry,
        jnp.arange((cfg.num_layers - lead) // period, dtype=jnp.int32))
    return (x, flat_cache,
            {k: v.reshape(state[k].shape) for k, v in pools.items()
             if k not in counted}, *(pools[k] for k in counted))


def forward_core(
    cfg: ModelConfig,
    params: dict[str, jax.Array],
    cache: jax.Array,  # [L*P, ps, 2*Hk, Dhp] flat layer-folded pool
    tokens: jax.Array,  # [N] flat mixed batch
    positions: jax.Array,  # [N] (-1 pad)
    seq_slots: jax.Array,  # [N] owning batch row (for page lookup / masks)
    page_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] cache length AFTER this step's tokens
    cu_q_lens: Optional[jax.Array] = None,  # [B+1] (Pallas kernel path)
    num_seqs: Optional[jax.Array] = None,  # [1] (Pallas kernel path)
    attn_impl=None,
    moe_matmul_impl=None,
    lora_indices: Optional[jax.Array] = None,  # [N] adapter slot per token (0 = none)
    lora_scale: float = 1.0,
    mm_embeds: Optional[jax.Array] = None,  # [N, D] encode-stage rows, row-aligned
    mm_mask: Optional[jax.Array] = None,  # [N] True where tokens[i] is a placeholder
    moe_dispatch_impl=None,
    state_slots: Optional[jax.Array] = None,  # [B] row -> state slot (recurrent models)
    scan_impl=None,  # ops/selective_scan impl (mamba layers)
    lin_impl=None,  # ops/lightning_attention impl (lightning layers)
    ssd_impl=None,  # ops/mamba2_ssd impl (mamba2 layers)
    kda_impl=None,  # ops/kda_attention impl (kda layers)
    query_attn_impl=None,  # attention impl for one-query rows (sparse selection)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run a flat mixed batch through the model, writing K/V into the paged cache.

    Serves batched/chunked prefill and decode in ONE program: the engine packs
    whatever fits its token budget. Returns (hidden [N, D] final-normed, updated
    cache, expert_counts [mixture layers, E], moe_dropped scalar int32 — routed
    copies the legacy capacity path dropped this step, 0 on the sorted path and
    for dense models; ``[dropped, bias_moved, routed]`` under sigmoid routing,
    see ``moe_block``). Callers unembed whichever rows they need (the
    engine only unembeds each sequence's last row — prefill never pays the full
    [N, vocab] logits matmul).

    ``moe_dispatch_impl`` selects the token-sorted drop-free dispatch
    (ops/moe_dispatch.make_sorted_dispatch); None keeps the capacity-einsum
    legacy path.

    EPLB mode: when ``params`` carries ``eplb_replica_slots``/``eplb_replica_counts``
    (engine-injected, see engine's rebalance path), ``moe_wi``/``moe_wo`` are physical
    slot weights and dispatch spreads tokens over replicas.

    A model with recurrent layers (``cfg.has_recurrent``) takes its
    recurrent-state pool in the same argument, ``cache = {"kv": pool,
    **init_state(...)}``, and returns it so (both donated by the engine's
    programs); the KV pool then folds the attention layers only, by their
    ordinal. ``state_slots`` [B] names each batch row's state slot (rows in
    plan order; padding rows the scratch slot); None means row b is seat b
    with one token, the fused decode call. A row whose first position is -1
    (padding, idle, frozen) leaves its slot untouched, one whose first
    position is 0 starts from zero state. ``cu_q_lens`` is required.

    A model with sparse selection (``cfg.sparse_topk``) keeps its KV heads
    as pages of their own in the pool and a compressed-key plane ``ck``
    beside the state pools; its attention layers hand ``attn_impl`` the
    call's rows a KV head at a time and ``query_attn_impl`` (default: the
    same) the one-query rows of the selected page tables (ops/sparse_select).
    """
    state = None
    if cfg.has_recurrent:
        state = {k: v for k, v in cache.items() if k != "kv"}
        cache = cache["kv"]
    N = tokens.shape[0]
    Ptot, ps, HkC, Dhp = cache.shape
    Dh = cfg.head_dim
    P = Ptot // cfg.kv_pool_folds  # pages per layer (and KV head) with pages
    B = page_tables.shape[0]
    if attn_impl is None:
        attn_impl = ragged_paged_attention_xla
    # what an impl derives from the batch's layout alone it derives here,
    # once a program and not once a layer (``plan``: keyword arguments of
    # every layer's call; the latent kernel's groups of rows)
    attn_plan = getattr(attn_impl, "plan", None)
    planned = attn_plan(page_tables, kv_lens, cu_q_lens, num_seqs,
                        ps) if attn_plan and cu_q_lens is not None else {}
    with part("embed"):
        x = params["embed"][tokens].astype(cfg.jax_dtype)  # [N, D]
        if cfg.embed_scale != 1.0:
            x = x * cfg.embed_scale
        if mm_embeds is not None:
            # inject the encode stage's embedding rows at media placeholder
            # positions (E/PD contract: encode workers produce, prefill
            # consumes)
            x = jnp.where(mm_mask[:, None], mm_embeds.astype(x.dtype), x)

    # global slot ids for the new tokens: page_table[seq, pos // ps] * ps + pos % ps
    b = jnp.clip(seq_slots, 0, B - 1)
    pidx = jnp.where(positions >= 0, positions, 0) // ps
    safe_page = jnp.where(page_tables >= 0, page_tables, 0)[b, pidx]
    slots = jnp.where(positions >= 0, safe_page * ps + positions % ps, -1)  # [N]

    def _variants(*keys):
        # a weight-only-quantized model carries <key>_q + <key>_scale instead
        # of <key> (models/quant.py); the scan consumes whichever is present
        out: tuple[str, ...] = ()
        for k in keys:
            out += (k,) if k in params else (k + "_q", k + "_scale")
        return out

    if cfg.is_mla:
        # bias/qk-norm/LoRA-on-attn are GQA-family features; none of the MLA
        # checkpoints combine them (registry enforces the shapes)
        assert not (cfg.qk_norm or cfg.attn_bias), "MLA excludes qk_norm/attn_bias"
        attn_keys = (("mla_wqa", "mla_q_norm", "mla_wqb")
                     if cfg.mla_q_lora_rank else ("mla_wq",)) + (
            "mla_wdkv", "mla_wkr", "mla_kv_norm",
            "mla_wuk", "mla_wuv") + _variants("wo")
    else:
        attn_keys = _variants("wq", "wk", "wv", "wo")
    # leaves every layer has, then the feed-forward's: a mixture layer's
    # (stacked by mixture layer) or a dense layer's
    every_keys = ("attn_norm",) + (
        () if cfg.single_sublayer else ("mlp_norm",)) + attn_keys + (
        ("q_norm", "k_norm") if cfg.qk_norm else ()
    ) + (("bq", "bk", "bv", "bo") if cfg.attn_bias else ()) + (
        _variants("wg") if cfg.attn_output_gate else ())
    dense_keys = _variants("wi", "wo_mlp") if (
        not cfg.is_moe or cfg.moe_leading_dense_layers) else ()
    expert_keys = (
        ("router",) + (("router_bias",) if cfg.moe_router_bias else ())
        + _variants("moe_wi", "moe_wo")
        + (_variants("shared_wi", "shared_wo") if cfg.moe_num_shared_experts else ())
    ) if cfg.is_moe else ()
    if "eplb_replica_slots" in params:
        expert_keys += ("eplb_replica_slots", "eplb_replica_counts")
    stacked_keys = every_keys + (expert_keys if cfg.is_moe else dense_keys)
    # A dispatch that indexes the expert banks by slot takes every layer's
    # bank as one stack and the layer's offset into it: a bank scanned as a
    # layer's slice is copied out of the stack each step, which took as long
    # as the expert GEMMs on the chip (PERF.md section 5, PR 32).
    bank_keys = _variants("moe_wi", "moe_wo") if (
        cfg.is_moe and getattr(moe_dispatch_impl, "stacked_banks", False)
        and "eplb_replica_slots" not in params) else ()
    stacked_keys = tuple(k for k in stacked_keys if k not in bank_keys)
    expert_keys = tuple(k for k in expert_keys if k not in bank_keys)
    banks = {k: params[k].reshape((-1,) + params[k].shape[2:])
             for k in bank_keys}
    has_lora = "lora_A_wq" in params
    assert not (cfg.is_mla and has_lora), \
        "LoRA adapters are unsupported on MLA models (no adapter hook in the absorbed path)"
    if has_lora:
        from llmd_tpu.models.lora import LORA_TARGETS

        stacked_keys += tuple(f"lora_{ab}_{t}" for t in LORA_TARGETS for ab in "AB")
        if lora_indices is None:
            lora_indices = jnp.zeros((N,), jnp.int32)
    layer_params = ({k: params[k] for k in stacked_keys}
                    if state is None else {})  # (a hybrid stack indexes)

    def pad_heads(t):  # [N, h, Dh] → [N, h, Dhp]
        if Dhp == Dh:
            return t
        return jnp.pad(t, ((0, 0), (0, 0), (0, Dhp - Dh)))

    assert not (cfg.has_window and cfg.is_mla), \
        "MLA has no sliding-window layers"

    def expert_layer(h, lp, ordinal, early_logits=None, mm=None):
        """The mixture feed-forward of the ``ordinal``-th mixture layer on
        the normed rows ``h`` with the layer's leaves ``lp``: routed experts
        (``moe_block``, the banks a layer's or the whole stack's) and the
        shared expert. Returns (y, counts by expert slot, drops). Both
        stacks call it: ``layer`` below and ``_hybrid_stack``."""
        if mm is None:
            def mm(key, pattern, xin):
                return _weight_mm(lp, key, pattern, xin)
        eplb = (
            (lp["eplb_replica_slots"], lp["eplb_replica_counts"])
            if "eplb_replica_slots" in lp
            else None
        )
        mw = {**lp, **banks}  # a layer's bank, or the whole stack
        quant_moe = "moe_wi_q" in mw  # int8 expert banks: einsum path only
        y, cnt, drop = moe_block(
            cfg, h, lp["router"],
            mw["moe_wi_q" if quant_moe else "moe_wi"],
            mw["moe_wo_q" if quant_moe else "moe_wo"],
            eplb=eplb,
            matmul_impl=None if quant_moe else moe_matmul_impl,
            token_mask=(positions >= 0),
            wi_scale=mw["moe_wi_scale"] if quant_moe else None,
            wo_scale=mw["moe_wo_scale"] if quant_moe else None,
            dispatch_impl=moe_dispatch_impl,
            return_dropped=True,
            logits=early_logits,
            slot_offset=ordinal * cfg.moe_bank_slots if banks else None,
            # (the expert layers of a stack beside recurrent layers: new
            # programs all. The accepted mixtures' step programs stay the
            # StableHLO they were; ROADMAP: move them over in a change of
            # their own, with their cells measured)
            gather_rows=state is not None,
            **({"router_bias": lp["router_bias"]}
               if cfg.moe_router_bias else {}),
        )
        if cfg.moe_num_shared_experts:
            with part("ffn"):  # the shared expert is a dense feed-forward
                if not cfg.moe_gated:
                    y = y + mm("shared_wo", "nf,fd->nd",
                               MOE_ACTIVATIONS[cfg.moe_activation](
                                   mm("shared_wi", "nd,df->nf", h)))
                elif "shared_wi_q" in lp:
                    def _shared_mm(key, pattern, xin):
                        return mm({"wi": "shared_wi",
                                   "wo_mlp": "shared_wo"}[key], pattern, xin)

                    y = y + swiglu(h, None, None, mm=_shared_mm)
                else:
                    y = y + swiglu(h, lp["shared_wi"], lp["shared_wo"])
        return y, cnt, drop

    def layer(carry, lp, l, window, use_rope, moe_ordinal=None):
        """Layer ``l`` (traced index) with parameters ``lp``; ``window`` (0 =
        full attention) and ``use_rope`` are static: the kind of the layer.
        ``moe_ordinal``: which of the mixture layers it is where leading
        dense layers precede them (None: every layer is one, ``l``); the
        feed-forward is the dense MLP where ``lp`` holds no router."""
        # flat_cache: [L*P*ps, 2Hk, Dhp] slot view (in-place carry); with
        # sparse selection the compressed-key plane rides behind it
        x, flat_cache, *planes = carry

        def _mm(key, pattern, xin):
            """Weight matmul, int8-aware: per-OUTPUT-channel scales commute
            out of the dot (x @ (w*s) == (x @ w) * s), so the dot streams the
            int8 tensor from HBM (XLA fuses the convert into the operand) and
            the scale is one fused elementwise on the output."""
            if key in lp:
                return jnp.einsum(pattern, xin, lp[key])
            y = jnp.einsum(pattern, xin, lp[key + "_q"].astype(xin.dtype))
            return y * lp[key + "_scale"].astype(xin.dtype)

        @part("attn_out")
        def gated(attn, h):  # cfg.attn_output_gate
            return (attn.astype(jnp.float32) * jax.nn.sigmoid(
                _mm("wg", "nd,dhk->nhk", h).astype(jnp.float32))
                    ).astype(attn.dtype)

        h = layer_norm(x, lp["attn_norm"], cfg.rms_eps)
        with part("moe_router"):
            early_logits = router_logits(h, lp["router"]) if (
                "router" in lp and cfg.moe_router_input == "attn_norm"
            ) else None
        with part("attn_qkv"):
            if cfg.is_mla:
                # Absorbed MLA (DeepSeek-V2 §2.1.2 inference form): the pool holds
                # one shared [c_kv ; k_rope] vector per token, queries project into
                # latent space through W_UK, and the whole thing runs as MQA with
                # head_dim = rank + rope_dim over the unmodified paged-attention
                # impl. Scores: q_nope·(W_UK c) + q_rope·k_rope == (W_UK^T q_nope)·c
                # + q_rope·k_rope; values ARE the latents, re-expanded per head
                # through W_UV after the softmax-weighted sum.
                r, dr, dn = cfg.mla_kv_lora_rank, cfg.mla_rope_dim, cfg.mla_qk_nope_dim
                Dkv = r + dr

                def pad_kv(t):  # [N, h, Dkv] → [N, h, Dhp]
                    return t if Dhp == Dkv else jnp.pad(
                        t, ((0, 0), (0, 0), (0, Dhp - Dkv)))

                if cfg.mla_q_lora_rank:
                    c_q = rms_norm(jnp.einsum("nd,dr->nr", h, lp["mla_wqa"]),
                                   lp["mla_q_norm"], cfg.rms_eps)
                    q = jnp.einsum("nr,rhk->nhk", c_q, lp["mla_wqb"])
                else:
                    q = jnp.einsum("nd,dhk->nhk", h, lp["mla_wq"])  # [N, H, dn+dr]
                q_rope = rope(q[..., dn:], positions, cfg.rope_theta)
                c = jnp.einsum("nd,dr->nr", h, lp["mla_wdkv"])  # [N, r] latent
                c = rms_norm(c, lp["mla_kv_norm"], cfg.rms_eps)
                kr = rope(jnp.einsum("nd,dk->nk", h, lp["mla_wkr"])[:, None, :],
                          positions, cfg.rope_theta)[:, 0]  # [N, dr] shared key
                q_lat = jnp.einsum("nhk,hkr->nhr", q[..., :dn], lp["mla_wuk"])
                q_attn = pad_kv(jnp.concatenate([q_lat, q_rope], axis=-1))
                k_w = v_w = pad_kv(jnp.concatenate([c, kr], axis=-1)[:, None, :])
                scale = (dn + dr) ** -0.5
            else:
                q = _mm("wq", "nd,dhk->nhk", h)
                k = _mm("wk", "nd,dhk->nhk", h)
                v = _mm("wv", "nd,dhk->nhk", h)
                if cfg.attn_bias:
                    q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
                if has_lora:
                    from llmd_tpu.models.lora import apply_lora

                    Hq, Hkn = cfg.num_heads, cfg.num_kv_heads
                    q = q + apply_lora(h, lp["lora_A_wq"], lp["lora_B_wq"], lora_indices,
                                       lora_scale).reshape(N, Hq, Dh)
                    k = k + apply_lora(h, lp["lora_A_wk"], lp["lora_B_wk"], lora_indices,
                                       lora_scale).reshape(N, Hkn, Dh)
                    v = v + apply_lora(h, lp["lora_A_wv"], lp["lora_B_wv"], lora_indices,
                                       lora_scale).reshape(N, Hkn, Dh)
                if cfg.qk_norm:
                    # Per-head RMSNorm over head_dim before RoPE (Qwen3 semantics) — on
                    # the FULL projection output incl. bias and LoRA delta, matching the
                    # HF/PEFT order (adapters are trained against normalised q/k).
                    # (beside recurrent layers the sums of squares are matrix
                    # products: a row's result must not depend on the program)
                    qk = head_rms_norm if cfg.has_recurrent else rms_norm
                    q = qk(q, lp["q_norm"], cfg.rms_eps)
                    k = qk(k, lp["k_norm"], cfg.rms_eps)
                if use_rope:
                    q = rope(q, positions, cfg.rope_theta)
                    k = rope(k, positions, cfg.rope_theta)
                q_attn, k_w, v_w = pad_heads(q), pad_heads(k), pad_heads(v)
                scale = Dh ** -0.5
        if cfg.sparse_topk:
            # a KV head's pages are pages of their own: head g of attention
            # layer l at fold l * Hk + g (ops/sparse_select)
            from llmd_tpu.ops import sparse_select

            Hkn = cfg.num_kv_heads
            base = (l * Hkn + jnp.arange(Hkn, dtype=jnp.int32)) * P
            slots_h = jnp.where(slots[:, None] >= 0,
                                slots[:, None] + base[None, :] * ps, -1)
            flat_cache = write_kv(flat_cache, k_w.reshape(N * Hkn, 1, Dhp),
                                  v_w.reshape(N * Hkn, 1, Dhp),
                                  slots_h.reshape(-1))
            with part("kv_write"):
                planes = [sparse_select.write_compressed_keys(
                    planes[0], flat_cache, page_tables, positions, seq_slots,
                    base, ps)]
            with part("attn"):  # (the selection inside is its own part)
                attn = sparse_select.sparse_paged_attention(
                    cfg, q_attn, flat_cache, planes[0], page_tables,
                    positions, seq_slots, kv_lens, cu_q_lens, num_seqs, l, P,
                    ps, scale, attn_impl, query_attn_impl or attn_impl)
                attn = attn[..., :Dh]
            # one product over the H * Dh lanes of a row, as the latent
            # layers' is (below): contracted over (h, k) as two axes, XLA
            # splits the sum by the number of rows, and on the chip a decode
            # row's projection through the 32-row and the 256-row program
            # parted by a bf16 step now and then (PR 42: greedy tokens served
            # alone and beside a prefilling neighbour parted). ``wo`` comes
            # flat, [H * Dh, D] (``forward_core`` views the stack so before
            # the loops): flattened here, between the layer's slice and the
            # product, the slice ran as a copy of its own. The gate is
            # applied to the flat rows too: multiplied a head ([N, H, Dh]),
            # its product asked for the layer's ``wg`` relaid ({1,3,2,0}),
            # a second copy of 33.5 MB behind the slice (PERF.md section 5,
            # PR 56).
            with part("attn_out"):
                rows = attn.reshape(N, -1)
                if cfg.attn_output_gate:
                    rows = (rows.astype(jnp.float32) * jax.nn.sigmoid(
                        _mm("wg", "nd,dhk->nhk", h).reshape(N, -1).astype(
                            jnp.float32))).astype(rows.dtype)
                o = _weight_mm(lp, "wo", "nk,kd->nd", rows)
            x = _joined(cfg, x, o)
            h = layer_norm(x, lp["mlp_norm"], cfg.rms_eps)
            return (_joined(cfg, x, dense_ffn(h, lp, _mm)), flat_cache,
                    *planes), (
                jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32))
        # shared paged plumbing — this layer's slice of the pool: slots/pages
        # shifted by the layer offset, KV written, attention over the pool
        slots_l = jnp.where(slots >= 0, slots + l * (P * ps), -1)
        pt_l = jnp.where(page_tables >= 0, page_tables + l * P, -1)
        flat_cache = write_kv(flat_cache, k_w, v_w, slots_l)
        # a window layer's impl is told the window, and bounds its own reads
        # by it (window_view); only impls that serve such models take the
        # argument
        with part("attn"):
            attn = attn_impl(
                q_attn, flat_cache.reshape(Ptot, ps, HkC, Dhp), pt_l,
                positions, seq_slots, kv_lens,
                cu_q_lens=cu_q_lens, num_seqs=num_seqs, scale=scale,
                chunk_k=k_w, chunk_v=v_w,
                **({"sliding_window": window} if window else {}), **planned,
            )
        with part("attn_out"):
            if cfg.is_mla:
                # latent-weighted sum [..., :rank] re-expands per head via W_UV
                o_heads = jnp.einsum("nhr,hrv->nhv",
                                     attn[..., :cfg.mla_kv_lora_rank], lp["mla_wuv"])
                if cfg.attn_gate_per_head:  # one scalar a head
                    o_heads = (o_heads.astype(jnp.float32) * jax.nn.sigmoid(
                        _mm("wg", "nd,dh->nh", h).astype(jnp.float32)
                    )[:, :, None]).astype(o_heads.dtype)
                # one product over the H * dv lanes of a row: contracted over
                # (h, v) as two axes, XLA picks how to split the sum by the
                # number of rows, and on the chip a decode row's projection
                # through a 64-row and a 256-row program parted by a bf16 step
                # (PR 39: greedy tokens served cold and from the prefix cache
                # parted). A plain [N, H*dv] x [H*dv, D] product adds a row's
                # terms in one order whatever N.
                flat = {k: v.reshape((-1,) + v.shape[2:])
                        for k, v in lp.items() if k in ("wo", "wo_q")}
                o = _weight_mm({**lp, **flat}, "wo", "nk,kd->nd",
                               o_heads.reshape(N, -1))
            else:
                attn = attn[..., :Dh]
                if cfg.attn_output_gate:
                    attn = gated(attn, h)
                o = _mm("wo", "nhk,hkd->nd", attn)
                if cfg.attn_bias:
                    o = o + lp["bo"]
                if has_lora:
                    attn_flat = attn.reshape(N, cfg.num_heads * Dh)
                    o = o + apply_lora(attn_flat, lp["lora_A_wo"], lp["lora_B_wo"],
                                       lora_indices, lora_scale)
        x = _joined(cfg, x, o)

        if cfg.single_sublayer:  # the layer is its mixer alone
            return (x, flat_cache, *planes), (
                jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32))
        h = layer_norm(x, lp["mlp_norm"], cfg.rms_eps)
        if cfg.is_moe and "router" in lp:
            y, cnt, drop = expert_layer(
                h, lp, l if moe_ordinal is None else moe_ordinal,
                early_logits, _mm)
        else:
            cnt = jnp.zeros((0,), jnp.int32)
            drop = jnp.zeros((), jnp.int32)
            y = dense_ffn(h, lp, _mm)
        x = _joined(cfg, x, y)
        return (x, flat_cache, *planes), (cnt, drop)

    if state is not None:
        if cfg.sparse_topk:
            # the sparse layers' output projection reads its rows flat
            # (``layer``'s sparse branch): [La, H * Dh, D] of the stored
            # [La, H, Dh, D], a view of the whole stack (D stays minor)
            params = dict(params, **{
                k: params[k].reshape(params[k].shape[0], -1,
                                     params[k].shape[-1])
                for k in ("wo", "wo_q") if k in params})
        x, flat_cache, state, *counted = _hybrid_stack(
            cfg, params, layer, x, cache.reshape(Ptot * ps, HkC, Dhp), state,
            positions, seq_slots, cu_q_lens, state_slots, scan_impl, lin_impl,
            ssd_impl, expert_layer, expert_keys, kda_impl)
        x = layer_norm(x, params["final_norm"], cfg.rms_eps)
        return (x, {"kv": flat_cache.reshape(Ptot, ps, HkC, Dhp), **state},
                *(counted or (jnp.zeros((cfg.num_layers, 0), jnp.int32),
                              jnp.zeros((), jnp.int32))))

    if cfg.moe_leading_dense_layers:
        # Leading dense layers, then a scan over the mixture layers. A layer
        # takes its leaves from the whole stacks by index (layer ``l`` of the
        # attention leaves and norms, ordinal ``j`` of the expert leaves), as
        # ``_hybrid_stack`` does: the stacks stay loop invariants.
        k = cfg.moe_leading_dense_layers
        kind = (cfg.attn_window_pattern[0], cfg.rope_pattern[0])

        def take(keys, i):
            return {key: lax.dynamic_index_in_dim(params[key], i, 0,
                                                  keepdims=False)
                    for key in keys}

        carry = (x, cache.reshape(Ptot * ps, HkC, Dhp))
        for l in range(k):
            carry, _ = layer(
                carry, {**take(every_keys, l), **take(dense_keys, l)},
                jnp.int32(l), *kind)
        (x, flat_cache), (expert_counts, dropped) = lax.scan(
            lambda c, j: layer(
                c, {**take(every_keys, k + j), **take(expert_keys, j)},
                k + j, *kind, moe_ordinal=j),
            carry, jnp.arange(cfg.num_moe_layers, dtype=jnp.int32))
        x = layer_norm(x, params["final_norm"], cfg.rms_eps)
        return (x, flat_cache.reshape(Ptot, ps, HkC, Dhp), expert_counts,
                dropped.sum(0))

    # One trace for any depth: the scan runs over periods of the attention
    # pattern, and a period's layers are written out in the body so that each
    # one's window and RoPE flag are static. Layer l = i * period + j keeps its
    # offset l * P into the folded pool. A period of one layer (every model
    # with one kind of layer) scans the stacked leaves as they are.
    period = cfg.layer_period
    kinds = tuple(zip(cfg.attn_window_pattern, cfg.rope_pattern))
    if period == 1:
        def body(carry, scanned):
            lp, l = scanned  # per-layer params + layer index
            return layer(carry, lp, l, *kinds[0])
    else:
        layer_params = {k: v.reshape((cfg.num_layers // period, period)
                                     + v.shape[1:])
                        for k, v in layer_params.items()}

        def body(carry, scanned):
            lp, i = scanned  # one period's params [period, ...] + its index
            outs = []
            for j, kind in enumerate(kinds):
                carry, out = layer(carry, {k: v[j] for k, v in lp.items()},
                                   i * period + j, *kind)
                outs.append(out)
            return carry, jax.tree.map(lambda *o: jnp.stack(o), *outs)

    (x, flat_cache), (expert_counts, dropped) = lax.scan(
        body,
        (x, cache.reshape(Ptot * ps, HkC, Dhp)),
        (layer_params, jnp.arange(cfg.num_layers // period, dtype=jnp.int32)),
    )
    if period > 1:  # [L/period, period, ...] -> [L, ...]
        expert_counts = expert_counts.reshape((cfg.num_layers,)
                                              + expert_counts.shape[2:])
    x = layer_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.moe_scoring == "sigmoid":  # [dropped, bias_moved, routed]
        return (x, flat_cache.reshape(Ptot, ps, HkC, Dhp), expert_counts,
                dropped.sum(0))
    return x, flat_cache.reshape(Ptot, ps, HkC, Dhp), expert_counts, dropped.sum()


@part("unembed")
def unembed(cfg: ModelConfig, params: dict[str, jax.Array], hidden: jax.Array) -> jax.Array:
    """hidden [..., D] → logits [..., vocab] (fp32)."""
    if cfg.logit_scale != 1.0:
        hidden = hidden.astype(jnp.float32) * cfg.logit_scale
    if "unembed_q" in params:  # weight-only int8 (models/quant.py)
        logits = jnp.einsum("...d,dv->...v", hidden.astype(jnp.float32),
                            params["unembed_q"].astype(jnp.float32))
        return logits * params["unembed_scale"].astype(jnp.float32)
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("...d,dv->...v", hidden.astype(jnp.float32), w.astype(jnp.float32))


def forward(
    cfg: ModelConfig,
    params: dict[str, jax.Array],
    cache: jax.Array,  # [L, P, ps, 2*Hk, Dhp]
    tokens: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T] (-1 pad)
    page_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] cache length AFTER this step's tokens
    moe_matmul_impl=None,
    lora_indices: Optional[jax.Array] = None,  # [B] adapter slot per row (0 = none)
    lora_scale: float = 1.0,
    with_hidden: bool = False,
    moe_dispatch_impl=None,
) -> tuple[jax.Array, ...]:
    """[B, T]-shaped convenience wrapper over ``forward_core`` (tests, entrypoints).

    Flattens row-major and ALWAYS uses the XLA-reference attention — the [B, T]
    padded layout is incompatible with the Pallas kernel's cu_q_lens contract, so
    no attn_impl override is accepted (engine callers use forward_core directly).
    Returns full logits [B, T, vocab] like the classic contract.
    """
    if cfg.has_recurrent:
        raise ValueError(
            "forward(): a model with recurrent layers runs through "
            "forward_core, which takes the rows' lengths (cu_q_lens) and "
            "their state slots")
    B, T = tokens.shape
    seq_slots = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    lora_tok = jnp.repeat(lora_indices, T) if lora_indices is not None else None
    hidden, new_cache, counts, _dropped = forward_core(
        cfg, params, cache, tokens.reshape(-1), positions.reshape(-1), seq_slots,
        page_tables, kv_lens, attn_impl=None, moe_matmul_impl=moe_matmul_impl,
        lora_indices=lora_tok, lora_scale=lora_scale,
        moe_dispatch_impl=moe_dispatch_impl,
    )
    logits = unembed(cfg, params, hidden).reshape(B, T, -1)
    if with_hidden:
        return logits, new_cache, counts, hidden.reshape(B, T, -1)
    return logits, new_cache, counts
