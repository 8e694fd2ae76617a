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
from jax import lax

from llmd_tpu.models.config import ModelConfig

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
        axes |= {
            "mla_wq": ("layers", "embed", "heads", "head_dim"),
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
    else:
        axes |= {"wi": ("layers", "embed", "mlp"), "wo_mlp": ("layers", "mlp", "embed")}
    if not cfg.tie_embeddings:
        axes["unembed"] = ("embed", "vocab")
    return axes


def init_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    """Random-init params (scaled normal); shapes match param_logical_axes."""
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
    """
    T, D = x.shape
    E, k = cfg.moe_num_experts, cfg.moe_top_k

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router.astype(jnp.float32))
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

    if eplb is not None:
        replica_slots, replica_counts = eplb  # [E, R], [E]
        S = wi.shape[0]
        rc = replica_counts[topi]  # [T, k]
        choice = (jnp.arange(T, dtype=jnp.int32)[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]) % rc
        idx = replica_slots[topi, choice]  # [T, k] physical slot ids
    else:
        S, idx = E, topi

    if dispatch_impl is not None:
        def half(x, idx, topw, valid):
            y = dispatch_impl(x, idx, topw, valid, wi, wo, wi_scale, wo_scale)
            return y, jnp.zeros((), jnp.int32)  # drop-free by construction
    else:
        half = None

    def half_einsum(x, idx, topw, valid):
        t = x.shape[0]
        # moe_capacity_factor is a legacy-path-only knob: the sorted path
        # has no capacity C to overflow
        C = max(1, int(t * k / S * cfg.moe_capacity_factor))
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
        if matmul_impl is not None and wi_scale is None:
            slot_counts = jnp.sum(disp2, axis=(0, 2)).astype(jnp.int32)  # [S]
            gate_up = matmul_impl(xe, wi, slot_counts)
            gate, up = jnp.split(gate_up, 2, axis=-1)
            ye = matmul_impl(jax.nn.silu(gate) * up, wo, slot_counts)
        else:
            # int8 expert banks: per-expert per-output-channel scales commute
            # out of the dot (see models/quant.py) — [S, 2F] / [S, D]
            gate_up = jnp.einsum("ecd,edf->ecf", xe, wi.astype(x.dtype))
            if wi_scale is not None:
                gate_up = gate_up * wi_scale[:, None, :].astype(x.dtype)
            gate, up = jnp.split(gate_up, 2, axis=-1)
            ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                            wo.astype(x.dtype))
            if wo_scale is not None:
                ye = ye * wo_scale[:, None, :].astype(x.dtype)
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
        (cfg.num_layers * num_pages, page_size, rows,
         padded_head_dim(cfg.kv_cache_head_dim)),
        dtype if dtype is not None else cfg.jax_dtype,
    )


# float8_e4m3fn has no inf: values past ±448 convert to nan, so fp8 cache
# writes clamp first. K/V activations live at O(1)–O(10); the clamp is a
# no-op in practice and fuses into the write's convert.
_FP8_MAX = 448.0


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
) -> jax.Array:
    """Reference-semantics ragged paged attention (gather + mask), jittable anywhere.

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
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        # fully masked (padding) rows: softmax is uniform garbage; caller ignores
        return jnp.einsum("nkqs,nskd->nkqd", p.astype(vc.dtype), vc)

    out = lax.map(one_chunk, (qp, posp, bp))  # [Np//C, C, Hk, qpk, Dhp]
    return out.reshape(Np, H, Dhp)[:N]


# ---------------------------------------------------------------------------
# Full forward over the scanned layer stack
# ---------------------------------------------------------------------------


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
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run a flat mixed batch through the model, writing K/V into the paged cache.

    Serves batched/chunked prefill and decode in ONE program: the engine packs
    whatever fits its token budget. Returns (hidden [N, D] final-normed, updated
    cache, expert_counts [L, E], moe_dropped scalar int32 — routed copies the
    legacy capacity path dropped this step, 0 on the sorted path and for dense
    models). Callers unembed whichever rows they need (the
    engine only unembeds each sequence's last row — prefill never pays the full
    [N, vocab] logits matmul).

    ``moe_dispatch_impl`` selects the token-sorted drop-free dispatch
    (ops/moe_dispatch.make_sorted_dispatch); None keeps the capacity-einsum
    legacy path.

    EPLB mode: when ``params`` carries ``eplb_replica_slots``/``eplb_replica_counts``
    (engine-injected, see engine's rebalance path), ``moe_wi``/``moe_wo`` are physical
    slot weights and dispatch spreads tokens over replicas.
    """
    N = tokens.shape[0]
    Ptot, ps, HkC, Dhp = cache.shape
    Dh = cfg.head_dim
    P = Ptot // cfg.num_layers  # pages per layer
    B = page_tables.shape[0]
    if attn_impl is None:
        attn_impl = ragged_paged_attention_xla
    x = params["embed"][tokens].astype(cfg.jax_dtype)  # [N, D]
    if mm_embeds is not None:
        # inject the encode stage's embedding rows at media placeholder
        # positions (E/PD contract: encode workers produce, prefill consumes)
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
        attn_keys = ("mla_wq", "mla_wdkv", "mla_wkr", "mla_kv_norm",
                     "mla_wuk", "mla_wuv") + _variants("wo")
    else:
        attn_keys = _variants("wq", "wk", "wv", "wo")
    stacked_keys = ("attn_norm", "mlp_norm") + attn_keys + (
        ("q_norm", "k_norm") if cfg.qk_norm else ()
    ) + (("bq", "bk", "bv", "bo") if cfg.attn_bias else ()) + (
        ("router",) + _variants("moe_wi", "moe_wo")
        + (_variants("shared_wi", "shared_wo") if cfg.moe_num_shared_experts else ())
        if cfg.is_moe
        else _variants("wi", "wo_mlp")
    )
    if "eplb_replica_slots" in params:
        stacked_keys += ("eplb_replica_slots", "eplb_replica_counts")
    has_lora = "lora_A_wq" in params
    assert not (cfg.is_mla and has_lora), \
        "LoRA adapters are unsupported on MLA models (no adapter hook in the absorbed path)"
    if has_lora:
        from llmd_tpu.models.lora import LORA_TARGETS

        stacked_keys += tuple(f"lora_{ab}_{t}" for t in LORA_TARGETS for ab in "AB")
        if lora_indices is None:
            lora_indices = jnp.zeros((N,), jnp.int32)
    layer_params = {k: params[k] for k in stacked_keys}

    def pad_heads(t):  # [N, h, Dh] → [N, h, Dhp]
        if Dhp == Dh:
            return t
        return jnp.pad(t, ((0, 0), (0, 0), (0, Dhp - Dh)))

    def body(carry, scanned):
        x, flat_cache = carry  # flat_cache: [L*P*ps, 2Hk, Dhp] slot view (in-place carry)
        lp, l = scanned  # per-layer params + layer index

        def _mm(key, pattern, xin):
            """Weight matmul, int8-aware: per-OUTPUT-channel scales commute
            out of the dot (x @ (w*s) == (x @ w) * s), so the dot streams the
            int8 tensor from HBM (XLA fuses the convert into the operand) and
            the scale is one fused elementwise on the output."""
            if key in lp:
                return jnp.einsum(pattern, xin, lp[key])
            y = jnp.einsum(pattern, xin, lp[key + "_q"].astype(xin.dtype))
            return y * lp[key + "_scale"].astype(xin.dtype)

        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
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
                q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
                k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
            q_attn, k_w, v_w = pad_heads(q), pad_heads(k), pad_heads(v)
            scale = Dh ** -0.5
        # shared paged plumbing — this layer's slice of the pool: slots/pages
        # shifted by the layer offset, KV written, attention over the pool
        slots_l = jnp.where(slots >= 0, slots + l * (P * ps), -1)
        pt_l = jnp.where(page_tables >= 0, page_tables + l * P, -1)
        flat_cache = write_kv(flat_cache, k_w, v_w, slots_l)
        attn = attn_impl(
            q_attn, flat_cache.reshape(Ptot, ps, HkC, Dhp), pt_l,
            positions, seq_slots, kv_lens,
            cu_q_lens=cu_q_lens, num_seqs=num_seqs, scale=scale,
            chunk_k=k_w, chunk_v=v_w,
        )
        if cfg.is_mla:
            # latent-weighted sum [..., :rank] re-expands per head via W_UV
            o_heads = jnp.einsum("nhr,hrv->nhv",
                                 attn[..., :cfg.mla_kv_lora_rank], lp["mla_wuv"])
            o = _mm("wo", "nhv,hvd->nd", o_heads)
        else:
            attn = attn[..., :Dh]
            o = _mm("wo", "nhk,hkd->nd", attn)
            if cfg.attn_bias:
                o = o + lp["bo"]
            if has_lora:
                attn_flat = attn.reshape(N, cfg.num_heads * Dh)
                o = o + apply_lora(attn_flat, lp["lora_A_wo"], lp["lora_B_wo"],
                                   lora_indices, lora_scale)
        x = x + o

        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        if cfg.is_moe:
            eplb = (
                (lp["eplb_replica_slots"], lp["eplb_replica_counts"])
                if "eplb_replica_slots" in lp
                else None
            )
            quant_moe = "moe_wi_q" in lp  # int8 expert banks: einsum path only
            y, cnt, drop = moe_block(
                cfg, h, lp["router"],
                lp["moe_wi_q" if quant_moe else "moe_wi"],
                lp["moe_wo_q" if quant_moe else "moe_wo"],
                eplb=eplb,
                matmul_impl=None if quant_moe else moe_matmul_impl,
                token_mask=(positions >= 0),
                wi_scale=lp["moe_wi_scale"] if quant_moe else None,
                wo_scale=lp["moe_wo_scale"] if quant_moe else None,
                dispatch_impl=moe_dispatch_impl,
                return_dropped=True,
            )
            if cfg.moe_num_shared_experts:
                if "shared_wi_q" in lp:
                    def _shared_mm(key, pattern, xin):
                        return _mm({"wi": "shared_wi",
                                    "wo_mlp": "shared_wo"}[key], pattern, xin)

                    y = y + swiglu(h, None, None, mm=_shared_mm)
                else:
                    y = y + swiglu(h, lp["shared_wi"], lp["shared_wo"])
        else:
            cnt = jnp.zeros((0,), jnp.int32)
            drop = jnp.zeros((), jnp.int32)
            y = swiglu(h, None, None, mm=_mm) if "wi_q" in lp else swiglu(
                h, lp["wi"], lp["wo_mlp"])
        x = x + y
        return (x, flat_cache), (cnt, drop)

    (x, flat_cache), (expert_counts, dropped) = lax.scan(
        body,
        (x, cache.reshape(Ptot * ps, HkC, Dhp)),
        (layer_params, jnp.arange(cfg.num_layers, dtype=jnp.int32)),
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, flat_cache.reshape(Ptot, ps, HkC, Dhp), expert_counts, dropped.sum()


def unembed(cfg: ModelConfig, params: dict[str, jax.Array], hidden: jax.Array) -> jax.Array:
    """hidden [..., D] → logits [..., vocab] (fp32)."""
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
