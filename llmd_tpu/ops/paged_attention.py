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

  (The unified rows are the step as one call, which it was until PR 44:
  "A unified step is two calls", below.)

  A third head layout, 28/4 heads of 128 (SmallThinker: 7 query heads a KV
  head, 32 KB a page; `--cells smallthinker --windows 0,4096 --bkv
  16,32,48,64,96 --bq 4,8,16,32`, chip, PR 33; 53 rows decoding, 425.7k
  tokens given to a full layer's decode call and 448.3k to its unified call,
  217.5k and 230.0k to a window layer's over page tables shifted past a
  4,096-token window; `.` = the pair of the two layouts above, `*` = the
  rule's since PR 33; the last column is what one call site costs every
  launch, seconds to trace and lower it on the chip's host):

      us a call     N=64 decode, full        N=64 decode, window 4096    s
      bkv / bq      4     8     16    32      4     8     16    32
      16          3052  3824  5843 10976    1668  2083  3168  5911    1.7
      32          1790 .2220  3313  6018    1030 .1278  1890  3413    2.2
      48          1493  1877  2758  4893     920  1129  1661  2922    2.8
      64         *1322  1561  2252  4027    *861   992  1407  2480    3.6
      96          1411  1408  1996  3394     983   979  1333  2266    5.0
                    N=256 unified, full      N=256 unified, window 4096
      16          5685  5562  7270 12602    3311  3158  4034  6854
      32          3258  3167 .4076  6848    1964  1869 .2354  3892
      48          2785  2678  3395  5553    1744  1658  2047  3304
      64          2374 *2214  2741  4524    1543 *1414  1718  2770
      96          2526  1981  2397  3780    1784  1368  1628  2489

  (64, 4) in decode is 0.60 / 0.67 of (32, 8) and reads at 80% of the byte
  bound, where bq 2 and 1 read too (1,322 and 1,327 us): the DMA's floor.
  (64, 8) in the unified step is 0.54 / 0.60 of (32, 16). What about 28/4
  asks for another pair, from the same shapes with other query head counts
  over the same pool (`--heads 4/4,12/4,20/4,24/4,32/4`):

      heads a KV head   decode (32,8)  (64,8)  (64,4)   unified (32,16)  (32,8)  (64,8)
      1  (4/4)              1347        1329     -           1659         1912    1861
      3  (12/4)             1885        1343    1331         2947         2713    1867
      5  (20/4)             2025        1437    1346         3475         2890    2003
      6  (24/4)             1772        1334     -           2543         2547    1864
      7  (28/4)             2220        1561    1322         4076         3167    2214
      8  (32/4)             1426        1331     -           1961         2040    1857

  The kernel folds a KV head's query heads into rows (`fold_on_2nd_minor`,
  `[bq, H/Hk, 128] -> [bq * H/Hk, 128]`) inside its loop over KV blocks, and
  where H/Hk is not a multiple of the bf16 packing it casts them to float32
  first: an odd ratio pays a cast, a relayout and a float32 operand once a KV
  block, for all bq rows of the block though a decode sequence owns one. So
  odd ratios are the slowest at (32, 8) (3 reads above 6), only they care
  for bq (16 -> 8 in the unified step: 0.92, 0.83, 0.78 at 3, 5, 7; nothing
  at 6; worse at 1 and 8), and a block of twice the pages halves how often
  they pay. The rule keys on that: H/Hk odd, as far as swept (3, 5, 7). 64
  pages also read a quarter faster at 6 heads a KV head over this pool, which
  Qwen's 12/2 at its own short contexts did not (PR 25: flat): an even ratio
  keeps its pair until a sweep of its cell says otherwise (PERF.md section 7).

  A fourth, 20/1 heads of 128 (Jamba2-3B's two attention layers of 28: twenty
  query heads on one KV head, 4 KB a page; `--cells jamba --bkv 16,32,64 --bq
  4,8,16,32`, chip, PR 34; 58 rows decoding, 47.8k tokens given to the decode
  call and 48.4k to the unified call, 30 us at the byte bound; `.` = the
  rule's pair):

      us a call     N=64 decode              N=256 unified
      bkv / bq      4     8     16    32      4     8     16    32
      16           177   190   273   459     251   229   294   474
      32           150  .160   221   368     189   178  .237   373
      64           163   179   230   342     214   212   256   351

  An even ratio, and it keeps the pair it has: (32, 4) would take 10 us off a
  decode call; the unified step's (32, 16) reads 237 where (32, 8) reads 178,
  59 us a call on two layers of 28, 0.12 ms of a step of 20 ms and
  more, not worth a fourth branch of the rule. One KV head's pages are 4 KB
  and a row's context 830 tokens, so every pair reads at a fifth of the byte
  bound or less: the calls are the fixed costs of their blocks.

  **A unified step is two calls** (`step_geometry`; PR 44). Since PR 38 some
  58 of a unified step's 64 rows bring one query, and one call carried them
  at the bq chosen for the chunk beside them (16; 8 at seven heads a KV head)
  where the fused decode call carries such rows at 8 (4): a decode row owns
  one row of its query block and pays for all of them, a chunk reads its
  whole context once a query block and wants many. So the step's leading
  one-query rows go to the kernel as a call of their own at the fused decode
  call's pair, and the rest at a bq of their own, over one bkv: every query
  walks the KV blocks it walked, and the result is the one call's bit for
  bit (`diff` 0.0 in every row below). The unified step of each cell as one
  call and as two by the chunks' bq (`--programs unified --split 0,1`, chip,
  PR 44; us a layer call; `.` = the one call as it was served, `*` = the
  rule's; the last column is the best of two calls against `.`):

      heads q/kv, rows, context tokens    one call, bq =     two calls, chunk bq =
                                            8    16    32      8    16    32    64
      Qwen 12/2, 51 rows, 40.7k             -   .219   267     -    213   204  *205   -6%
      Mistral 32/8, 33 rows, 137.0k         -  .1246  1453     -   1215  1154 *1140   -9%
      SmallThinker 28/4, 56 rows, 448.3k .2210  2738  4513   2018 *1863  1912    -   -16%
      the same, window 4096, 230.0k      .1430  1713  2761   1330 *1210  1237    -   -15%
      Jamba 20/1, 61 rows, 48.4k            -   .228   373     -    181   195   188  (-21%)

  Even ratios take 64 for the chunks, the swept odd ones 16
  (`CHUNK_QUERIES_PER_BLOCK`); at 64 a call of seven heads a KV head takes
  15 s to compile (PR 43's sweep) and reads slower than at 16. Qwen's 12/2
  reads 6% faster in two calls at 64 or at 32, so it takes the rule every
  layout takes. Jamba's row is its layout, not its engine: a model with
  recurrent layers hands the kernel its rows cut at KV blocks
  (`split_rows_at_kv_blocks`), up to twice the rows and no longer decode rows
  first, and keeps the one call (two attention layers of 28: 0.09 ms a step).
  After it Mistral's decode rows in a unified step read at 85% of their
  KV-byte bound (PERF.md section 6, PR 44).

  **One bkv an engine.** The kernel's online softmax blocks a row's keys from
  its page table's first entry, so two programs, or a cold and a cached
  request, that block differently part at near ties (PR 32 read
  `cold_equals_cached: false` on the chip). bkv therefore reads the layout,
  the page size and the page budget, never the token budget or a window, and
  a window layer's page table is shifted by whole blocks of that bkv
  (`window_align_pages`): at 64 pages a window layer is given up to 1,023
  tokens more than its window, not 511, the price of the larger block.

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


# A KV block of 512 tokens, and never more than 32 pages (twice both at a
# swept odd number of query heads a KV head): the fetch loop is unrolled per
# page, and past 64 pages a block it spills (module docstring).
KV_BLOCK_TOKENS = 512
KV_BLOCK_MAX_PAGES = 32


# Odd numbers of query heads a KV head that a sweep covered (module docstring).
SWEPT_ODD_HEADS_PER_KV = (3, 5, 7)


def pick_block_sizes(num_tokens: int, page_size: int, pages_per_seq: int,
                     heads_per_kv: int = 0) -> tuple[int, int]:
    """(num_kv_pages_per_block, num_queries_per_block) for our serving shapes.

    A function of the call's static shapes only, from the sweeps in the
    module docstring. bkv: `KV_BLOCK_TOKENS` of context a block, at most
    `KV_BLOCK_MAX_PAGES` pages and at most the sequence's page budget (a
    short model length is one block a sequence; nothing past its page table
    is fetched). bq by the token budget N, which is all a trace can see of
    which step program calls: up to 128 (the fused decode call, one query row
    a sequence) 8 rows; up to 512 (a unified step whose rows go to the kernel
    in one call, decode rows and chunks together: a model with recurrent
    layers; every other unified step makes two calls, `step_geometry`) 16;
    larger prefill budgets keep the 64 they had (not swept).

    ``heads_per_kv`` (query heads a KV head; 0 = not given) is the one thing
    the rule reads of the head layout. Even ratios (12/2 and 32/8 heads of
    128) want the pair above. A swept odd one (28/4: seven) makes the kernel
    fold its queries in float32 once a KV block, for all bq rows of the
    block: such a layout takes blocks of twice the tokens and pages and half
    the query rows, (64, 4) in the decode call and (64, 8) in the unified
    step. bkv reads the layout, the page size and the page budget and never
    N or a window: an engine's programs, and a cold and a cached request,
    must block a row's keys alike (module docstring). `tools/attn_sweep.py`
    re-measures it.
    """
    odd = heads_per_kv in SWEPT_ODD_HEADS_PER_KV
    blocks = 2 if odd else 1
    bkv = max(1, min(pages_per_seq, blocks * KV_BLOCK_MAX_PAGES,
                     blocks * KV_BLOCK_TOKENS // page_size))
    bq = 8 if num_tokens <= 128 else 16 if num_tokens <= 512 else 64
    if odd and num_tokens <= 512:
        bq //= 2
    return bkv, min(bq, num_tokens)


def call_geometry(q_shape, cache_shape, pages_per_seq: int) -> tuple[int, int]:
    """`pick_block_sizes` for a call with these static shapes (``[N, H,
    Dhp]`` and ``[P, page, 2*Hk, Dhp]``): what `paged_attention_tpu` traces a
    step program with, and what the engine reports on
    ``llmd_tpu:engine_attn_backend{geometry}``. Of the head layout it keys on
    the query heads a KV head (a single-plane latent pool is one head that
    every query head shares): `shard_over_heads` splits both head counts over
    ``tp``, so a shard has the ratio of the whole model and the pair is the
    same under ``tp`` as on one device, which planes a device are not."""
    return pick_block_sizes(q_shape[0], cache_shape[1], pages_per_seq,
                            _heads_per_kv(q_shape, cache_shape))


def _heads_per_kv(q_shape, cache_shape) -> int:
    return q_shape[1] // max(1, cache_shape[2] // 2)


def window_align_pages(q_shape, cache_shape, pages_per_seq: int) -> int:
    """Pages a window layer's page table is shifted by a multiple of: the
    kernel's KV block at this layout, which no call's token budget changes
    (any ``q_shape[0]`` gives the same). What the engine counts a window
    layer's reads with (``attn_kv_tokens_total``)."""
    return call_geometry(q_shape, cache_shape, pages_per_seq)[0]


# Query rows a block of the chunk rows' call in a unified step that makes two
# calls (`step_geometry`): the sweep's best at the even ratios, a quarter of it
# at the swept odd ones (module docstring).
CHUNK_QUERIES_PER_BLOCK = 64


def step_geometry(q_shape, cache_shape, num_rows: int, pages_per_seq: int,
                  split_at_kv_blocks: bool = False
                  ) -> tuple[tuple[int, int], ...]:
    """The kernel calls `paged_attention_tpu` makes for a call of these
    static shapes over ``num_rows`` rows, a (bkv, bq) pair each, with one bkv.

    One call at `call_geometry`'s pair where a row brings one query (the
    fused decode call: no more tokens than rows), where the call has one row,
    and where the rows are re-cut at their KV blocks' ends
    (``split_at_kv_blocks``: a model with recurrent layers; the cut makes up
    to twice the rows of another structure, and such a model's attention
    layers are few). Every other call is a unified step's, whose rows are
    one-query decode rows first and prefill chunks after them, and makes
    two: the decode rows as a call of ``num_rows`` tokens at the fused decode
    call's pair, and the rest at `CHUNK_QUERIES_PER_BLOCK` (a quarter of it
    at a swept odd number of query heads a KV head). What a decode row wants
    of bq (few rows: it owns one of the block's) and what a chunk wants (many:
    it reads its whole context once a query block) conflict, and no one pair
    serves both (module docstring)."""
    num_tokens = q_shape[0]
    if split_at_kv_blocks or not 1 < num_rows < num_tokens:
        return (call_geometry(q_shape, cache_shape, pages_per_seq),)
    rows = call_geometry((num_rows, *q_shape[1:]), cache_shape, pages_per_seq)
    odd = _heads_per_kv(q_shape, cache_shape) in SWEPT_ODD_HEADS_PER_KV
    chunk_bq = CHUNK_QUERIES_PER_BLOCK // 4 if odd else CHUNK_QUERIES_PER_BLOCK
    return (rows, (rows[0], min(chunk_bq, num_tokens)))


def format_geometry(pairs) -> str:
    """``32x8+32x64``: a call's pairs as the ``geometry`` label names them."""
    return "+".join(f"{bkv}x{bq}" for bkv, bq in pairs)


def decode_rows_and_chunks(page_tables, kv_lens, cu_q_lens, num_seqs):
    """A unified step's rows as two calls' rows: ``(n_dec, decode, chunks)``,
    each of the two the ``(kv_lens, page_tables, cu_q_lens, num_seqs)`` of a
    kernel call over the step's own query tokens.

    ``n_dec`` is the length of the longest prefix of live rows that bring one
    query (the engine's plan puts decode rows first; a chunk of one token
    that follows them rides along: the result is the same). The decode call
    is told of those rows, over the first ``B`` query tokens. The chunk call
    keeps the token axis as it is and takes the rows from the last decode row
    on: that row stands in for all of them, a row of ``n_dec`` queries over
    one token of context, so the first chunk's queries stay where they are
    (no copy of q, none of the output) at the price of one KV block a query
    block up to ``n_dec``. What the stand-in row computes is not read.

    Each call is told of one row at the least: told of none, the upstream
    kernel leaves the page copy it starts for row 0 unwaited and the chip
    halts. A step with no decode row hands the decode call its first row as
    one query over one token; a step with nothing else hands the chunk call
    the stand-in alone."""
    B = kv_lens.shape[0]
    row = jnp.arange(B, dtype=jnp.int32)
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    one = (row < num_seqs[0]) & (q_len == 1)
    n_dec = jnp.sum(jnp.cumprod(one.astype(jnp.int32))).astype(jnp.int32)
    told = jnp.maximum(n_dec, 1)
    decode = (jnp.where(row < n_dec, kv_lens, 1), page_tables,
              jnp.minimum(jnp.arange(B + 1, dtype=jnp.int32), told),
              told[None])
    shift = jnp.maximum(n_dec - 1, 0)
    lens = jnp.roll(kv_lens, -shift)
    cu = cu_q_lens[jnp.minimum(jnp.arange(B + 1, dtype=jnp.int32) + shift, B)]
    chunks = (lens.at[0].set(jnp.where(n_dec > 0, 1, lens[0])),
              jnp.roll(page_tables, -shift, axis=0),
              cu.at[0].set(0), num_seqs.astype(jnp.int32) - shift)
    return n_dec, decode, chunks


def split_rows_at_kv_blocks(page_tables, kv_lens, cu_q_lens, num_seqs,
                            block_tokens: int, num_tokens: int):
    """A call's rows cut where their queries cross a KV block's end, so that
    no query is handed a KV block past its own: ``(page_tables, kv_lens,
    cu_q_lens, num_seqs)`` of ``B * parts`` rows, ``parts`` the most blocks
    ``num_tokens`` queries of one row can touch.

    The kernel walks every KV block of a row up to its ``kv_len`` for all the
    row's queries and renormalises its accumulator a block (``(l * o) / l``):
    a block that lies wholly past a query is masked out, but the
    renormalisation re-rounds that query's result. Whether a prompt's token
    meets such a block depends on where the chunk that brought it ends (a
    chunk 298..552 hands token 349 the block from 512, a chunk 256..511 does
    not), so a token's result depended on its prompt's chunking, in the last
    bit and rarely (12/2 heads, chip, PR 34: 3 tokens of 939 by one bf16 step).
    Cut at the block ends, a row's part ``[lo, hi)`` is a row of its own with
    ``kv_len = hi`` over the same pages: every query sees the blocks up to its
    own and no other, whatever the chunking. A decode row (one query) is
    never cut."""
    B = kv_lens.shape[0]
    parts = (num_tokens - 1) // block_tokens + 2
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    start = kv_lens - q_len  # the position of a row's first query
    row = jnp.arange(B, dtype=jnp.int32)
    cut = (row < num_seqs[0]) & (q_len > 0)
    n = jnp.where(cut, (kv_lens - 1) // block_tokens - start // block_tokens + 1, 1)
    first = jnp.cumsum(n) - n  # a row's first part among the new rows
    j = jnp.arange(B * parts, dtype=jnp.int32)
    owner = jnp.clip(jnp.searchsorted(first, j, side="right") - 1, 0, B - 1)
    block = start[owner] // block_tokens + (j - first[owner])
    lo = jnp.maximum(start[owner], block * block_tokens)
    hi = jnp.minimum(kv_lens[owner], (block + 1) * block_tokens)
    live = (j < first[B - 1] + n[B - 1]) & cut[owner]
    new_q = jnp.where(live, hi - lo, 0)
    new_cu = jnp.concatenate([cu_q_lens[:1], cu_q_lens[0] + jnp.cumsum(new_q)])
    return (page_tables[owner], jnp.where(live, hi, kv_lens[owner]),
            new_cu.astype(cu_q_lens.dtype),
            (num_seqs + jnp.sum(jnp.where(cut, n - 1, 0))).astype(num_seqs.dtype))


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
    sliding_window: "int | None" = None,  # static: a window layer's window
    split_at_kv_blocks: bool = False,  # static: `split_rows_at_kv_blocks`
) -> jax.Array:
    """Uniform-signature adapter over the Pallas kernel (drop-in for
    models.transformer.ragged_paged_attention_xla on TPU).

    ``split_at_kv_blocks`` hands the kernel each row cut at its KV blocks'
    ends (`split_rows_at_kv_blocks`): what a model asks for whose greedy
    tokens must not depend on a prompt's chunking to the last bit (recurrent
    layers carry a one-step difference on; the engine sets it for them in the
    unified step).

    ``sliding_window`` is the kernel's own static mask (key j visible to the
    query at position i iff i - window < j). The kernel still walks every KV
    block from a sequence's first page to ``kv_len``, so a window layer's
    call is handed its page table and length without the pages before the
    window (``models.transformer.window_view``, rounded down to whole KV
    blocks of ``bkv`` pages so that the blocks that remain are the unshifted
    call's and the result is its result bit for bit); causality derives from
    ``kv_len - q_len``, which the shift leaves as it was."""
    del positions, seq_slots, chunk_k, chunk_v
    B = page_tables.shape[0]
    pairs = step_geometry(q.shape, layer_cache.shape, B, page_tables.shape[1],
                          split_at_kv_blocks)
    bkv = pairs[0][0]
    if sliding_window is not None:
        from llmd_tpu.models.transformer import window_view

        page_tables, kv_lens, _ = window_view(
            page_tables, kv_lens, cu_q_lens, sliding_window,
            layer_cache.shape[1], align_pages=bkv)
    # -1 marks unmapped table entries in engine convention; the kernel's scalar-
    # prefetched DMA would read out of bounds — clamp to page 0 (never attended:
    # those entries lie at/past kv_len).
    page_tables = jnp.maximum(page_tables, 0)
    if split_at_kv_blocks:
        assert sliding_window is None, "a window layer's rows are not cut"
        page_tables, kv_lens, cu_q_lens, num_seqs = split_rows_at_kv_blocks(
            page_tables, kv_lens, cu_q_lens, num_seqs,
            bkv * layer_cache.shape[1], q.shape[0])
    extra = {}
    if layer_cache.dtype == jnp.float8_e4m3fn:
        # fp8 pages: unit scales make the kernel dequantize each KV block in
        # VMEM right after the page DMA (write_kv stores at scale 1.0 — e4m3's
        # dynamic range covers K/V activations), halving the HBM KV stream.
        # Kernel precondition: combined heads % 4 == 0 (strided fp8 load
        # packing). True for llama-1b both padded (16) and packed (8); NOT for
        # tiny CI models with 2 combined heads, which the kernel rejects.
        extra = {"k_scale": 1.0, "v_scale": 1.0}

    def kernel(q, bq, kv_lens, page_tables, cu_q_lens, num_seqs):
        call = functools.partial(
            _kernel(),
            sm_scale=scale,
            num_kv_pages_per_block=bkv,
            num_queries_per_block=bq,
            vmem_limit_bytes=VMEM_LIMIT,
            sliding_window=sliding_window,
            **extra,
        )
        if mesh is not None:
            call = shard_over_heads(call, mesh, q, layer_cache, shard_kv=True)
        return call(q, layer_cache, kv_lens.astype(jnp.int32),
                    page_tables.astype(jnp.int32), cu_q_lens.astype(jnp.int32),
                    num_seqs.astype(jnp.int32))

    if len(pairs) == 1:
        return kernel(q, pairs[0][1], kv_lens, page_tables, cu_q_lens, num_seqs)
    # both calls walk a row's KV blocks of bkv pages from its table's first
    # entry, as the one call did: each token's result is that call's, bit for
    # bit, from whichever call owns its row
    n_dec, decode, chunks = decode_rows_and_chunks(
        page_tables, kv_lens, cu_q_lens, num_seqs)
    head = kernel(q[:B], pairs[0][1], *decode)
    out = kernel(q, pairs[1][1], *chunks)
    head = jnp.where((jnp.arange(B) < n_dec)[:, None, None], head, out[:B])
    return jax.lax.dynamic_update_slice_in_dim(out, head, 0, axis=0)
