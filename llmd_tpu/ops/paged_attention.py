"""TPU ragged paged attention: the TPU-native answer to FlashInfer (SURVEY.md §2.5
N8, docker/Dockerfile.cuda:70-71).

The heavy lifting is the Pallas ragged-paged-attention kernel that ships with JAX
(`jax.experimental.pallas.ops.tpu.ragged_paged_attention` — the vLLM-TPU production
kernel): flash-decoding over a paged KV cache with double-buffered HBM→VMEM page
streaming, online softmax, and mixed prefill+decode in one flat token batch. This
module owns the serving-stack integration:

- the uniform attention-impl signature shared with the XLA-reference fallback
  (`models.transformer.ragged_paged_attention_xla`) so the engine can swap impls,
- **block-size selection** (`pick_block_sizes`): how many KV pages one block
  fetches (bkv) and how many query rows one block carries (bq). The upstream
  tuned table has no entry for our shapes (16-token pages at model lengths of
  4,096-8,192; decode batches of 64 rows), so the rule here comes from a sweep
  on the v5e at the four shapes the benchmark's cells serve
  (`tools/attn_sweep.py`; chip, PR 25; us per layer call, contexts drawn from
  the cells' traffic):

      shape (heads q/kv, N, context tokens)   (8, 32) before   rule      us
      Qwen2.5-1.5B 12/2, N=64 decode, 39.5k        566        (32, 8)    183
      Qwen2.5-1.5B 12/2, N=256 unified, 40.7k      582        (32, 16)   212
      Mistral-7B 32/8, N=64 decode, 129.7k        2215        (32, 8)    894
      Mistral-7B 32/8, N=256 unified, 137.0k      2731        (32, 16)  1253

  A KV block has a fixed cost (descriptors and DMA starts and waits for each
  page, the loop turn, the l/m/acc update) that 128-token blocks never
  amortised, and a decode row owns one query row however many its block
  carries. Time per call falls to 32 pages a block, is flat to 64 and turns up
  at 128 (the unrolled per-page DMA loop spills); PERF.md section 6 has the
  whole table,
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


# A KV block of 512 tokens, and never more than 32 pages: the fetch loop is
# unrolled per page, and past 64 pages a block it spills (module docstring).
KV_BLOCK_TOKENS = 512
KV_BLOCK_MAX_PAGES = 32


def pick_block_sizes(num_tokens: int, page_size: int,
                     pages_per_seq: int) -> tuple[int, int]:
    """(num_kv_pages_per_block, num_queries_per_block) for our serving shapes.

    A function of the call's static shapes only, from the sweep in the module
    docstring. bkv: `KV_BLOCK_TOKENS` of context a block, at most
    `KV_BLOCK_MAX_PAGES` pages and at most the sequence's page budget (a
    short model length is one block a sequence; nothing past its page table
    is fetched). bq by the token budget N, which is all a trace can see of
    which step program calls: up to 128 (the fused decode call, one query row
    a sequence) 8 rows; up to 512 (the unified step: decode rows, then
    prefill chunks, each of which reads its whole context once per query
    block) 16; larger prefill budgets keep the 64 they had (not swept). Both
    head layouts swept (12/2 and 32/8 heads of 128) want the same pair, so
    the rule does not read the layout. `tools/attn_sweep.py` re-measures it.
    """
    bkv = max(1, min(pages_per_seq, KV_BLOCK_MAX_PAGES,
                     KV_BLOCK_TOKENS // page_size))
    bq = 8 if num_tokens <= 128 else 16 if num_tokens <= 512 else 64
    return bkv, min(bq, num_tokens)


def call_geometry(q_shape, cache_shape, pages_per_seq: int) -> tuple[int, int]:
    """`pick_block_sizes` for a call with these static shapes: what
    `paged_attention_tpu` traces a step program with, and what the engine
    reports on ``llmd_tpu:engine_attn_backend{geometry}``."""
    return pick_block_sizes(q_shape[0], cache_shape[1], pages_per_seq)


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
    bkv, bq = call_geometry(q.shape, layer_cache.shape, page_tables.shape[1])
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
