"""TPU ragged paged attention: the TPU-native answer to FlashInfer (SURVEY.md §2.5
N8, docker/Dockerfile.cuda:70-71).

The heavy lifting is the Pallas ragged-paged-attention kernel that ships with JAX
(`jax.experimental.pallas.ops.tpu.ragged_paged_attention` — the vLLM-TPU production
kernel): flash-decoding over a paged KV cache with double-buffered HBM→VMEM page
streaming, online softmax, and mixed prefill+decode in one flat token batch. This
module owns the serving-stack integration:

- the uniform attention-impl signature shared with the XLA-reference fallback
  (`models.transformer.ragged_paged_attention_xla`) so the engine can swap impls,
- **block-size selection**: the upstream tuned table has no entry for every
  (chip, shape) pair and its default (128 KV pages/block) is pathological for
  decode — measured on v5e (llama-1b shapes, B=32, kv_len 384): default blocks
  1,676 µs/layer vs 15-18 µs/layer with (bkv=8, bq=32). We clamp KV pages per
  block to the sequence page budget and keep it small,
- the VMEM budget (the kernel's scratch exceeds the 16 MB scoped-vmem default on
  larger head counts; vLLM-TPU ships 100 MB, we follow),
- the combined KV layout contract [P, page_size, 2*Hk, Dhp] (K even / V odd) with
  head_dim lane-padded — see `models.transformer.init_cache`.

Requires queries to be each sequence's LAST `q_len` tokens (true for chunked
prefill and decode — causality is derived as kv_len - q_len + local index).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from llmd_tpu.ops import attn_tune

VMEM_LIMIT = 100 * 1024 * 1024


@functools.cache
def _kernel():
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention as rpa,
    )

    return rpa


def shard_over_heads(fn, mesh, q, layer_cache, *, shard_kv: bool):
    """Run ``fn(q, layer_cache, *replicated)`` per device under ``shard_map``.

    A Mosaic call inside a jit that spans several devices cannot be
    partitioned by GSPMD, so the Pallas attention kernels carry their own
    partitioning: query heads split over ``tp`` and the pool follows the
    engine's own layout (``shard_kv``: combined KV heads over ``tp`` for
    GQA; replicated for the MLA latent plane). Every other operand — and
    the token dim on the dp/sp/ep axes — is replicated: the ragged kernel's
    ``cu_q_lens`` contract does not split on a flat token boundary.
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    planes = layer_cache.shape[2]
    if q.shape[1] % tp or (shard_kv and planes % (2 * tp)):
        raise ValueError(
            f"attention heads do not split over tp={tp}: {q.shape[1]} query "
            f"heads, {planes} combined KV planes (K/V pairs must stay on one "
            "device)")
    heads = P(None, "tp", None)
    cache_spec = P(None, None, "tp", None) if shard_kv else P()

    def sharded(q, layer_cache, *rest):
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(heads, cache_spec) + (P(),) * len(rest),
            out_specs=heads, check_vma=False)(q, layer_cache, *rest)

    return sharded


def pick_block_sizes(num_tokens: int, page_size: int, pages_per_seq: int,
                     *, head_layout: "str | None" = None) -> tuple[int, int]:
    """(num_kv_pages_per_block, num_queries_per_block) for our serving shapes.

    Resolution order, weakest to strongest:

    1. **heuristic** — KV blocks sized ~128 tokens keep decode DMAs overlapped
       without predicating past short sequences (v5e sweep above); q blocks of
       32 cover a full decode batch row budget per program, 64+ for big
       prefill batches,
    2. **auto-tune table** (`ops.attn_tune`, loaded from
       ``LLMD_ATTN_TUNE_FILE`` / `EngineConfig.attn_tune_file`) — bench.py's
       on-chip tuner's per-(batch, page_size, head layout) winners; an exact
       batch match replaces the heuristic, so b128 and long-context shapes
       stop running block sizes swept at b32,
    3. ``LLMD_ATTN_BKV`` / ``LLMD_ATTN_BQ`` env overrides — the operator
       escape hatch (and the legacy single-shape tuner export), applied at
       decode-gate shapes only (see deploy/ENV_VARS.md).
    """
    import os

    bkv = max(1, min(pages_per_seq, max(1, 128 // page_size)))
    bq = 32 if num_tokens <= 512 else 64
    table = attn_tune.active_table()
    if table is not None:
        # exact (batch, page_size, head_layout) key; nearest pages_per_seq —
        # non-tuned shapes (e.g. prefill token budgets) miss and keep policy
        hit = table.lookup(num_tokens, page_size, pages_per_seq, head_layout)
        if hit is not None:
            bkv, bq = hit
    try:
        decode_n = int(os.environ.get("LLMD_ATTN_DECODE_N", "128"))
    except ValueError:
        decode_n = 128
    if num_tokens <= decode_n:
        # overrides are tuned at the DECODE shape (one query per sequence,
        # num_tokens == batch); the tuner exports that batch size as
        # LLMD_ATTN_DECODE_N so the gate tracks the shape it validated.
        # Token batches above it — prefill budgets — keep the swept policy
        # (short tail chunks below the gate share the decode policy; a
        # perf-only approximation on the rare last chunk of a prompt).
        def _env_int(name: str):
            raw = os.environ.get(name)
            if not raw:
                return None
            try:
                return int(raw)
            except ValueError:
                return None  # malformed operator input: keep the policy

        env_bkv = _env_int("LLMD_ATTN_BKV")
        env_bq = _env_int("LLMD_ATTN_BQ")
        if env_bkv:
            bkv = max(1, min(pages_per_seq, env_bkv))
        if env_bq:
            bq = max(1, env_bq)
    return bkv, min(bq, num_tokens)


def paged_attention_tpu(
    q: jax.Array,  # [N, H, Dhp] flat query tokens (lane-padded)
    layer_cache: jax.Array,  # [P, ps, 2*Hk, Dhp]
    page_tables: jax.Array,  # [B, max_pages]
    positions: jax.Array,  # [N] (unused — causality derives from kv/cu lens)
    seq_slots: jax.Array,  # [N] (unused on this path)
    kv_lens: jax.Array,  # [B] tokens resident incl. this step's
    *,
    scale: float,
    cu_q_lens: jax.Array,  # [B+1] cumulative query lengths
    num_seqs: jax.Array,  # [1]
    chunk_k: "jax.Array | None" = None,  # unused (ring-attn impls only)
    chunk_v: "jax.Array | None" = None,  # unused (ring-attn impls only)
    mesh=None,  # engine mesh: the kernel runs per device under shard_map
) -> jax.Array:
    """Uniform-signature adapter over the Pallas kernel (drop-in for
    models.transformer.ragged_paged_attention_xla on TPU)."""
    del positions, seq_slots, chunk_k, chunk_v
    N = q.shape[0]
    _, ps, planes, _ = layer_cache.shape
    bkv, bq = pick_block_sizes(
        N, ps, page_tables.shape[1],
        head_layout=attn_tune.head_layout_key(q.shape[1], q.shape[2], planes))
    # -1 marks unmapped table entries in engine convention; the kernel's scalar-
    # prefetched DMA would read out of bounds — clamp to page 0 (never attended:
    # those entries lie at/past kv_len).
    page_tables = jnp.maximum(page_tables, 0)
    extra = {}
    if layer_cache.dtype == jnp.float8_e4m3fn:
        # fp8 pages: unit scales make the kernel dequantize each KV block in
        # VMEM right after the page DMA (write_kv stores at scale 1.0 — e4m3's
        # dynamic range covers K/V activations), halving the HBM KV stream.
        # Kernel precondition: combined heads % 4 == 0 (strided fp8 load
        # packing). True for llama-1b both padded (16) and packed (8); NOT for
        # tiny CI models with 2 combined heads, which the kernel rejects.
        extra = {"k_scale": 1.0, "v_scale": 1.0}
    call = functools.partial(
        _kernel(),
        sm_scale=scale,
        num_kv_pages_per_block=bkv,
        num_queries_per_block=bq,
        vmem_limit_bytes=VMEM_LIMIT,
        **extra,
    )
    if mesh is not None:
        call = shard_over_heads(call, mesh, q, layer_cache, shard_kv=True)
    return call(
        q,
        layer_cache,
        kv_lens.astype(jnp.int32),
        page_tables.astype(jnp.int32),
        cu_q_lens.astype(jnp.int32),
        num_seqs.astype(jnp.int32),
    )
