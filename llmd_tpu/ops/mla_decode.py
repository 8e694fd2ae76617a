"""Latent-width Pallas decode attention for the single-plane MLA pool.

Absorbed MLA decode is MQA whose "head dim" is the latent width rank+rope
(288–640 on DeepSeek-class shapes) — past the upstream ragged-paged-attention
kernel's supported head sizes, which is why MLA decode historically fell back
to the XLA gather+mask reference (`models.transformer.ragged_paged_attention_xla`).
This module is the Pallas path that closes that gap.

Why a bespoke kernel is *easier* here than for GQA:

- the pool is a SINGLE plane per token (`init_cache` HkC == 1): keys and
  values are the same [c_kv ; k_rope] latent row, so one page DMA feeds both
  the score dot and the p@V product — the kernel streams each page once,
- decode is one query row per sequence (N == B), so the grid is simply
  (sequences, pages) with the page table scalar-prefetched to drive the KV
  block index_map — Pallas double-buffers consecutive page fetches,
- **latent width needs no lane alignment games**: the pool pads the latent to
  ``padded_head_dim(rank+rope)`` with zeros and `forward_core` zero-pads the
  query the same way, so full padded-width dot products equal the real-width
  dots exactly — the same slot-placement algebra `ops/packed_kv.py` uses
  ([0…q…0]·[kv|0…0] = q·kv; the cross terms multiply exact zeros). The kernel
  just runs at Dhp and parity with the reference is bitwise in fp32.

Softmax is the standard online (flash) recurrence over pages with VMEM
scratch carrying (m, l, acc) per sequence; rows whose kv_len is 0 (idle
decode slots) produce exact zeros. ``interpret`` is the caller's choice:
the engine passes True only when the platform is CPU, so CPU-mesh tests and
parity pins execute the same kernel body the TPU compiles.

Scope: DECODE shapes only (one query per sequence, causality == attend to
the whole resident prefix). Mixed prefill/chunk batches keep the XLA
reference path — the engine installs this impl on the fused-decode program
alone (`engine._select_attn_impl`).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmd_tpu.ops.paged_attention import VMEM_LIMIT, shard_over_heads

# Large-negative finite mask value: -inf would make the m/alpha recurrence
# produce nan on fully masked pages (exp(-inf - -inf)); masked probabilities
# are zeroed explicitly as well.
NEG_INF = -0.7 * float(np.finfo(np.float32).max)

# Minor (lane) width of the m/l scratch rows. TPU vector ops want a 128-lane
# minor dim; only column 0 is meaningful.
_MINOR = 128


def _decode_kernel(page_tables_ref, kv_lens_ref,  # scalar prefetch
                   q_ref, kv_ref, o_ref,          # blocks
                   m_ref, l_ref, acc_ref):        # VMEM scratch
    """Grid (b, p): sequence b consumes its p-th page. Scratch carries the
    online-softmax state across the page axis; p == 0 resets it, the last
    page normalizes and writes the output row."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    num_page_steps = pl.num_programs(1)
    ps = kv_ref.shape[0]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tokens on this page that exist: [p*ps, min((p+1)*ps, kv_len))
    n_valid = jnp.clip(kv_lens_ref[b] - p * ps, 0, ps)

    @pl.when(n_valid > 0)
    def _page():
        q = q_ref[0].astype(jnp.float32)        # [H, Dhp] (pre-scaled)
        kv = kv_ref[...].astype(jnp.float32)    # [ps, Dhp] shared latent: k == v
        s = jax.lax.dot_general(                # [H, ps]
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = tok < n_valid
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                     # [H, _MINOR]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        pij = jnp.exp(s - m_new[:, :1])
        pij = jnp.where(mask, pij, 0.0)         # fully masked rows stay 0
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(pij, axis=1, keepdims=True), alpha.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            pij, kv, (((1,), (0,)), ((), ())),  # p @ V, V == the same latents
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == num_page_steps - 1)
    def _finish():
        l = l_ref[:, :1]
        # kv_len == 0 (idle slot): l stays 0 → exact-zero output row, the
        # same contract as the XLA reference (callers ignore idle rows)
        o_ref[0] = jnp.where(
            l > 0.0, acc_ref[...] / jnp.where(l > 0.0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


def mla_decode_pallas(
    q: jax.Array,            # [B, H, Dhp] one query row per sequence
    layer_cache: jax.Array,  # [P, ps, 1, Dhp] single-plane latent pool
    page_tables: jax.Array,  # [B, maxp] (already clamped >= 0)
    kv_lens: jax.Array,      # [B] tokens resident incl. this step's
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel invocation (decode shapes). Returns [B, H, Dhp]; lanes past
    the real latent width come back zero (acc only mixes stored rows, whose
    pad lanes are zero)."""
    B, H, Dhp = q.shape
    _, ps, planes, _ = layer_cache.shape
    assert planes == 1, "mla_decode_pallas serves the single-plane latent pool"
    maxp = page_tables.shape[1]
    # fold sm_scale into q once (f32 exact: scale is a power-free float but
    # the same value the reference multiplies into the scores)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, H, Dhp), lambda b, p, pt, kl: (b, 0, 0)),
            # one KV page per grid step, gathered through the prefetched page
            # table (Pallas pipelines the next page's DMA behind this page's
            # compute); the plane axis is squeezed away
            pl.BlockSpec((None, ps, None, Dhp),
                         lambda b, p, pt, kl: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dhp), lambda b, p, pt, kl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, _MINOR), jnp.float32),  # m
            pltpu.VMEM((H, _MINOR), jnp.float32),  # l
            pltpu.VMEM((H, Dhp), jnp.float32),     # acc
        ],
    )
    kern = pl.pallas_call(
        _decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dhp), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # revisit-heavy grid: neither axis is parallelizable (scratch
            # carries state across pages; output blocks revisit across b)
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        name="mla_latent_decode_kernel",
    )
    return kern(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
                q, layer_cache)


def mla_paged_attention_latent(
    q: jax.Array,            # [N, H, Dhp] flat query tokens (lane-padded)
    layer_cache: jax.Array,  # [P, ps, 1, Dhp]
    page_tables: jax.Array,  # [B, maxp] (-1 = unmapped)
    positions: jax.Array,    # [N] (unused: decode attends to the full prefix)
    seq_slots: jax.Array,    # [N] (unused: row i IS sequence i at decode)
    kv_lens: jax.Array,      # [B]
    *,
    scale: float,
    cu_q_lens: "jax.Array | None" = None,   # unused (uniform impl signature)
    num_seqs: "jax.Array | None" = None,    # unused (uniform impl signature)
    chunk_k: "jax.Array | None" = None,     # unused (ring-attn impls only)
    chunk_v: "jax.Array | None" = None,     # unused (ring-attn impls only)
    interpret: bool = False,  # True only when the selecting platform is CPU
    mesh=None,  # engine mesh: the kernel runs per device under shard_map
) -> jax.Array:
    """Uniform-signature adapter (drop-in for ragged_paged_attention_xla) for
    DECODE calls on MLA engines: one query row per batch slot. The engine
    installs this on the fused-decode program only; unified/verify/embed
    programs (mixed chunk shapes) keep the reference impl.
    """
    del positions, seq_slots, cu_q_lens, num_seqs, chunk_k, chunk_v
    assert q.shape[0] == page_tables.shape[0], (
        "latent decode kernel requires one query row per sequence "
        f"(got N={q.shape[0]}, B={page_tables.shape[0]}); route mixed "
        "batches through the XLA reference impl")
    # -1 marks unmapped table entries; those pages lie at/past kv_len so the
    # kernel never weighs them — clamp for the prefetched DMA's sake only
    page_tables = jnp.maximum(page_tables, 0)
    if layer_cache.dtype == jnp.float8_e4m3fn:
        # fp8 latent pages: mirror the GQA kernel's in-VMEM dequant semantics.
        # write_kv stores the latent at scale 1.0, so upcasting at use is the
        # whole dequant; the kernel's f32 compute path does it for free.
        layer_cache = layer_cache.astype(q.dtype)
    call = functools.partial(mla_decode_pallas, scale=scale,
                             interpret=interpret)
    if mesh is not None:
        # heads split over tp; the latent plane is replicated (every head's
        # shard needs the whole latent — the engine's MLA cache layout)
        call = shard_over_heads(call, mesh, q, layer_cache, shard_kv=False)
    return call(q, layer_cache, page_tables, kv_lens)
