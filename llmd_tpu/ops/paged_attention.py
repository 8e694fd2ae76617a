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
  KV-byte bound (PERF.md section 6, PR 44): a bound that counted a shared
  prompt once a row ("The one-query rows on a kernel of the repo's", below).

  **The one-query rows on a kernel of the repo's** (`rows_attention`; PR 50).
  Rows behind one tenant's cached system prompt name the same pages first,
  and the upstream kernel walks every row's whole table: 8 lanes behind a
  2,048-token prompt fetch its 4 KV blocks 8 times. The one-query call (a
  unified step's head call, the fused decode call) therefore goes to a kernel
  that walks the rows in groups of up to `GROUP_ROWS` whose tables start
  alike (`ops/row_groups.py`, the latent kernel's rule, derived once a
  program: `plan`): a KV block the group's rows all name is fetched once and
  multiplied, a KV head at a time, with their query heads stacked as a query
  block's tokens (``[G * H/Hk, Dh]``), then each row walks its own blocks,
  its state stored to its own rows. The arithmetic of a block is the
  upstream's statement for statement (`_rows_kernel`), so every row's result
  is the upstream call's bit for bit (``diff`` 0.0 in every row below; D16
  stays open) and cold and cached requests, and the chunks' call beside it,
  block alike. Page copies are started 8 a loop turn and waited for once a
  block; q and the output are whole in VMEM; idle seats of a fused call
  (one token, no page) all name the same nothing and walk as groups too.
  `tools/attn_sweep.py --split 1 --shared <lanes a tenant> --groups 4,8,16`
  (chip, PR 50, TPU v5 lite; us a call, the upstream call in the same
  process first; the cells' contexts as above, 32 live rows of 64 in
  Mistral's decode call; blocks = KV blocks fetched / once a row; the last
  column seconds to trace and lower a call site, upstream first in its
  process > rows kernel; the unified step's after the decode call's in one
  process: `rows_attention` is jitted as the upstream wrapper is, so a
  second site of the same shape costs neither a second trace):

      layout, call, lanes a tenant   upstream    G = 4     G = 8*   blocks    s
      32/8 decode, 8                  897         562       507^    161/301  4.3>2.1
      32/8 decode, 4                  904         562     544-557   177/301
      32/8 decode, none shared      897-902       823     818-828   273/301
      32/8 unified, 8              1131-1139       -        853     151/255  4.6>0.5
      32/8 unified, 4                1131         887     892-902   167/255
      32/8 unified, none shared    1125-1135     1128    1140-1145  255/255
      12/2 decode, none shared      180-185     185-187   188-195   100/114  2.5>1.0
      12/2 unified, none shared     200-204     219-224   227-234   106/106
      28/4 full decode, 4            1324         682     816-854   211/453  4.3>2.5
      28/4 full decode, none       1324-1335   1365-1375   1670     445/453
      28/4 full unified, none      1861-1870   1918-1934     -      442/442
      28/4 window decode, alone     847-857     878-887      -

  (`^` G = 16 read 680 where G = 8 read 617, both at one page copy a loop
  turn; 28/4 with all 64 copies unrolled, its best: 8 a turn reads 4% slower
  there.) The rule
  (`rows_kernel_serves`): a layout takes the kernel where its rows that
  share nothing are no slower than the upstream call, within 2%, and bit for
  bit. 32/8 passes (the decode call 9% faster unshared: 32 idle seats walk
  as 4 groups; the unified step +0.9%). 12/2 (11-16% slower in the unified
  step: rows of two KV blocks, where a group's fixed cost, some 0.5 us,
  shows) and 28/4 (3-4% slower unshared, though its full layer's decode call
  halves behind a shared prompt) keep the upstream call, as do a window
  layer's rows (the batch's groups do not describe its shifted tables, and
  alone they are 3% slower), a model with recurrent layers (no prefix reuse:
  no two rows name one page), fp8 pages and a mesh. What did not move the
  fixed cost: the groups as a loop inside one grid step (and 0.9 s more to
  trace), the five group arrays as one scalar-prefetch operand (kept: fewer
  operands), the rows' last keys as a column in place of a matrix (kept).

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
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmd_tpu.ops.row_groups import row_groups

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


# One-query rows a group of the rows kernel (`rows_attention`): a tenant's
# lanes in the sessions traffic, and at four query heads a KV head the 32
# matrix rows the upstream call's query block of 8 already multiplies. A
# constant of the layout, swept by `tools/attn_sweep.py --groups` (module
# docstring).
GROUP_ROWS = 8

# Query heads a KV head whose one-query rows take the rows kernel
# (`rows_kernel_serves`): the layouts `tools/attn_sweep.py --groups` showed
# no slower unshared (within 2%) and bit for bit on the chip. 32/8 passed;
# 12/2 and 28/4 read the upstream call's bits too but 11-16% and 3-4% slower
# on rows that share nothing, and keep it (module docstring).
ROWS_KERNEL_HEADS_PER_KV = (4,)

# Page copies a turn of the rows kernel's fetch loop (the upstream kernel
# unrolls all bkv in Python, 2.2 s to trace and lower a call site at 32
# pages): 8 read as fast as all 32 at 32/8 and trace 0.8 s a call site
# sooner; one page a turn is a scalar loop the products cannot hide (+10%).
PAGES_A_TURN = 8


def rows_kernel_serves(heads_per_kv: int, cache_dtype, mesh) -> bool:
    """Whether a layout's one-query rows go to the repo's kernel
    (`rows_attention`) or keep the upstream call: what `tools/attn_sweep.py`
    showed no slower on rows that share nothing (within 2%) and bit for bit
    (module docstring). A function of what `pick_block_sizes` reads and of
    nothing a user sets. A pool that is not bf16 (fp8 pages are dequantized
    by the upstream kernel) and a mesh (`shard_over_heads`) keep the upstream
    call: no cell runs them."""
    return (mesh is None and jnp.dtype(cache_dtype) == jnp.bfloat16
            and heads_per_kv in ROWS_KERNEL_HEADS_PER_KV)


def plan(page_tables, kv_lens, cu_q_lens, num_seqs, page_size: int, *,
         heads_per_kv: int) -> dict:
    """What `models.transformer.forward_core` asks the impl for once a
    program, before its layers: keyword arguments of every layer's call. The
    groups of the rows the one-query call is told of (`decode_rows_and_chunks`:
    the leading one-query rows of a unified step, every row of a fused decode
    call), from the tables as the engine packed them (an unmapped entry, -1,
    is no layer's page 0), as the one array the kernel prefetches: ``members
    [B * G]``, ``size [B]``, ``shared [B]`` (`row_groups`), then the rows
    that lead a group in row order (``[B]``; past them rows that lead
    nothing) and how many there are (``[1]``)."""
    _, (lens, _, cu, told), _ = decode_rows_and_chunks(
        page_tables, kv_lens, cu_q_lens, num_seqs)
    bkv, _ = pick_block_sizes(0, page_size, page_tables.shape[1],
                              heads_per_kv)
    members, size, shared = row_groups(
        page_tables, lens, cu, told[0], page_size, bkv, GROUP_ROWS)
    return {"groups": jnp.concatenate([
        members.reshape(-1), size, shared,
        jnp.argsort(size == 0, stable=True).astype(jnp.int32),
        jnp.sum(size > 0, dtype=jnp.int32)[None]])}


def _rows_kernel(pt_ref, kv_lens_ref, cu_ref, groups_ref,  # SMEM (`plan`)
                 q_ref,  # [N, H, Dh] VMEM, whole
                 pool_hbm,  # [P, ps, 2 * Hk, Dh]
                 o_ref,  # [N, H, Dh] VMEM, whole
                 kv_bufs, sems, l_ref, m_ref, acc_ref, q_buf, slot_ref,
                 *, bkv: int, maxp: int, G: int, sm_scale: float,
                 mask_value: float):
    """The one-query rows of a call, a group a grid step (`rows_attention`).

    The arithmetic of a KV block is the upstream kernel's
    (``kernel.py::flash_attention``), statement for statement, on the shapes
    it has there at ``num_queries_per_block = G``: a group's queries take the
    places of a query block's tokens. What differs is which KV blocks a
    group's rows meet and in which order each row's state is stored."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention.kernel import (
        get_dtype_packing,
    )

    i = pl.program_id(0)
    _, _, ps, planes, Dh = kv_bufs.shape
    Hk = planes // 2
    B, H = kv_lens_ref.shape[0], q_ref.shape[1]
    hpk = H // Hk
    T, R = bkv * ps, G * hpk
    # the one array of `plan`: members, size, shared, leaders, their count
    at_size, at_shared, at_lead = B * G, B * G + B, B * G + 2 * B
    count = groups_ref[B * G + 3 * B]

    def fetch(row, j, slot):
        """Start the page copies of row ``row``'s KV block ``j`` into buffer
        ``slot``."""
        pages = math.gcd(bkv, PAGES_A_TURN)

        def turn(p, _):
            for u in range(pages):
                pltpu.make_async_copy(
                    pool_hbm.at[pt_ref[row * maxp + j * bkv + p * pages + u]],
                    kv_bufs.at[slot, p * pages + u], sems.at[slot]).start()
            return 0

        if pages == bkv:
            turn(0, 0)
        else:
            lax.fori_loop(0, bkv // pages, turn, 0)

    def wait(slot):
        """One wait for a buffer's bkv page copies: a wait reads the bytes
        its descriptor names, not the pages."""
        pltpu.make_async_copy(kv_bufs.at[slot], kv_bufs.at[slot],
                              sems.at[slot]).wait()

    @pl.when(i == 0)
    def _first():
        o_ref[...] = jnp.zeros_like(o_ref)  # a row no group owns stays zero
        slot_ref[0] = 0

        @pl.when(count > 0)
        def _():
            fetch(groups_ref[at_lead], 0, 0)

    def strided_load_kv(ref, start, step):
        # (the upstream's, bf16: a K row and the V row behind it are one
        # uint32 row)
        assert ref.dtype == jnp.bfloat16 and step % 2 == 0
        b = ref.bitcast(jnp.uint32)[start // 2::step // 2, :]
        k = pltpu.bitcast(b << 16, jnp.float32).astype(jnp.bfloat16)
        v = pltpu.bitcast(b & jnp.uint32(0xFFFF0000),
                          jnp.float32).astype(jnp.bfloat16)
        return k, v

    def fold_on_2nd_minor(vec):
        if vec.shape[-2] % get_dtype_packing(vec.dtype) != 0:
            vec = vec.astype(jnp.float32)
        return vec.reshape(-1, vec.shape[-1])

    def flash_attention(q, k, v, head_l_ref, head_m_ref, head_acc_ref, *,
                        kv_blk_idx, kv_end, row_ids, store_start, store_end):
        """``kernel.py::flash_attention`` with the row's own ``kv_len``,
        last key and stored rows handed in (``kv_end``, ``row_ids``,
        ``store_start`` / ``store_end`` in members)."""
        kv_len_start = kv_blk_idx * T

        def masked_store(ref, val, start, end, group=1):
            iota = lax.broadcasted_iota(jnp.int32, ref.shape, 0) // group
            pltpu.store(ref, val,
                        mask=jnp.logical_and(iota >= start, iota < end))

        def load_with_init(ref, init_val):
            return jnp.where(kv_blk_idx == 0, jnp.full_like(ref, init_val),
                             ref[...])

        kv_mask = (lax.broadcasted_iota(jnp.int32, k.shape, 0)
                   < kv_end - kv_len_start)
        k = jnp.where(kv_mask, k.astype(jnp.float32), 0).astype(k.dtype)
        v = jnp.where(kv_mask, v.astype(jnp.float32), 0).astype(v.dtype)
        qk = jnp.einsum("nd,md->nm", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
        col_ids = kv_len_start + lax.broadcasted_iota(jnp.int32, (R, T), 1)
        causal_mask = row_ids < col_ids
        qk += jnp.where(causal_mask, mask_value, 0.0)
        m_curr = jnp.max(qk, axis=1, keepdims=True)
        s_curr = jnp.exp(qk - m_curr)
        qkv = jnp.dot(s_curr, v, preferred_element_type=jnp.float32)
        lm_store_shape = head_m_ref.shape
        m_curr = jnp.broadcast_to(m_curr, lm_store_shape)
        l_curr = jnp.broadcast_to(s_curr.sum(axis=1, keepdims=True),
                                  lm_store_shape)
        m_prev = load_with_init(head_m_ref, -jnp.inf)
        l_prev = load_with_init(head_l_ref, 0.0)
        m_next = jnp.maximum(m_prev, m_curr)
        masked_store(head_m_ref, m_next, store_start, store_end, hpk)
        alpha = jnp.exp(m_prev - m_next)
        beta = jnp.exp(m_curr - m_next)
        l_alpha = alpha * l_prev
        l_next = l_alpha + beta * l_curr
        l_next_safe = jnp.where(l_next == 0.0, 1.0, l_next)
        masked_store(head_l_ref, l_next_safe, store_start, store_end, hpk)

        def broadcast_to_shape(arr, shape):
            if arr.shape == shape:
                return arr
            return jnp.concatenate(
                [arr for _ in range(shape[1] // arr.shape[1])], axis=1)

        o_curr = load_with_init(head_acc_ref, 0.0).reshape(-1, Dh)
        l_alpha = broadcast_to_shape(l_alpha, qkv.shape)
        beta = broadcast_to_shape(beta, qkv.shape)
        l_next_safe = broadcast_to_shape(l_next_safe, qkv.shape)
        out = lax.div(l_alpha * o_curr + beta * qkv, l_next_safe)
        masked_store(head_acc_ref, out.reshape(head_acc_ref.shape),
                     store_start, store_end)

    def group(b, n, shared):
        """The ``n`` one-query rows row ``b`` leads: their ``shared`` leading
        KV blocks fetched once for the members' queries stacked as a query
        block's tokens, then each member's own blocks, stored to its own rows
        of the state. One loop over the visits ``t``: the shared blocks, then
        member after member's tail."""
        member = [groups_ref[b * G + g] for g in range(G)]
        lens = [kv_lens_ref[r] for r in member]  # seats past n: the leader's
        tail = [jnp.where(g < n, (lens[g] - 1) // T + 1 - shared, 0)
                for g in range(G)]
        ends = [shared + sum(tail[:g + 1]) for g in range(G)]
        longest = functools.reduce(jnp.maximum, lens)

        def place(t):
            """(page-table row, KV block, member) of visit ``t``; a shared
            block is the leader's, member 0."""
            past = [t >= e for e in ends[:-1]]
            g = sum(p.astype(jnp.int32) for p in past)
            start = shared + sum(jnp.where(p, n_t, 0)
                                 for p, n_t in zip(past, tail))
            return (groups_ref[b * G + g],
                    jnp.where(t < shared, t, shared + t - start), g)

        for g, r in enumerate(member):
            q_buf[pl.ds(g, 1)] = q_ref[pl.ds(cu_ref[r], 1)]
        # a stacked row's query position: its member's last key
        row = lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        row_ids = functools.reduce(
            lambda v, g: jnp.where(row >= g * hpk, lens[g] - 1, v),
            range(1, G), jnp.full((R, 1), lens[0] - 1, jnp.int32))
        slot0 = slot_ref[0]
        nxt = groups_ref[at_lead + jnp.minimum(i + 1, B - 1)]

        def visit(t, here):
            slot = (slot0 + t) % 2
            ahead = place(t + 1)
            more = t + 1 < ends[-1]

            # the next visit's block, or the next group's first
            @pl.when(jnp.logical_or(more, i + 1 < count))
            def _prefetch():
                fetch(jnp.where(more, ahead[0], nxt),
                      jnp.where(more, ahead[1], 0), 1 - slot)

            wait(slot)
            r, j, g = here
            together = t < shared
            kv_ref = kv_bufs.at[slot].reshape(T * planes, Dh)
            for h in range(Hk):
                k, v = strided_load_kv(kv_ref, 2 * h, planes)
                heads = slice(h * hpk, (h + 1) * hpk)
                flash_attention(
                    fold_on_2nd_minor(q_buf[:, heads, :]), k, v,
                    l_ref.at[h], m_ref.at[h], acc_ref.at[:, heads, :],
                    kv_blk_idx=j,
                    kv_end=jnp.where(together, longest, kv_lens_ref[r]),
                    row_ids=row_ids,
                    store_start=jnp.where(together, 0, g),
                    store_end=jnp.where(together, n, g + 1))
            return ahead

        # the first visit is the leader's first block, shared or its own
        lax.fori_loop(0, ends[-1], visit, (b, jnp.int32(0), jnp.int32(0)))
        slot_ref[0] = (slot0 + ends[-1]) % 2
        # the leader last: the seats past n are its row again
        for g in reversed(range(G)):
            o_ref[pl.ds(cu_ref[member[g]], 1)] = acc_ref[pl.ds(g, 1)].astype(
                o_ref.dtype)

    @pl.when(i < count)
    def _group():
        b = groups_ref[at_lead + i]
        group(b, groups_ref[at_size + b], groups_ref[at_shared + b])


# (jitted as the upstream wrapper is: a process traces and lowers the kernel
# once for all its call sites of one shape, the fused call's body twice over)
@functools.partial(jax.jit, static_argnames=("sm_scale", "bkv", "interpret"))
def rows_attention(q, layer_cache, kv_lens, page_tables, cu_q_lens, groups,
                   *, sm_scale: float, bkv: int,
                   interpret: bool = False) -> jax.Array:
    """Attention of a call's one-query rows over the combined pool ``[P, ps,
    2 * Hk, Dh]`` (K even, V odd), the rows walked in ``groups`` (`plan`'s
    array of the call): a KV block of ``bkv`` pages that a group's rows name
    alike is fetched once and multiplied with their query heads stacked in
    one matrix a KV head, then each row walks its own remaining blocks. Every
    row's result is the upstream kernel's at the same ``bkv``, bit for bit; a
    row that shares nothing is a group of one on the same walk. A row of
    ``q`` that no group owns (an idle seat, a row of more than one query)
    comes back zero."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention.kernel import (
        DEFAULT_MASK_VALUE,
    )

    N, H, Dh = q.shape
    _, ps, planes, _ = layer_cache.shape
    B, maxp = page_tables.shape
    G = (groups.shape[0] - 1) // B - 3
    hpk = H // (planes // 2)
    # whole KV blocks a row of the table: a last block's entries past the
    # table are page 0, as the unmapped ones (never attended)
    tables = jnp.pad(page_tables, ((0, 0), (0, -maxp % bkv)))
    whole = pl.BlockSpec((N, H, Dh), lambda i, *_: (0, 0, 0))
    lm = pltpu.VMEM((planes // 2, G * hpk, 128), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _rows_kernel, bkv=bkv, maxp=tables.shape[1], G=G,
            sm_scale=sm_scale, mask_value=DEFAULT_MASK_VALUE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),  # the groups, leaders first
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, bkv, ps, planes, Dh), layer_cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                lm, lm,  # l, m
                pltpu.VMEM((G, H, Dh), jnp.float32),  # acc
                pltpu.VMEM((G, H, Dh), q.dtype),  # a group's queries
                pltpu.SMEM((1,), jnp.int32),  # the buffer the next group reads
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="ragged_paged_attention_rows",
    )(tables.reshape(-1).astype(jnp.int32), kv_lens.astype(jnp.int32),
      cu_q_lens.astype(jnp.int32), groups, q, layer_cache)


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
    one_query_rows: bool = False,  # static: the fused decode call's rows
    groups=None,  # `plan` of the batch: the one-query rows' groups
    interpret: bool = False,  # the rows kernel's; True only on the CPU
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
    ``kv_len - q_len``, which the shift leaves as it was.

    ``groups`` (`plan`, once a program) sends the one-query rows to the
    repo's kernel (`rows_attention`) in the upstream call's place: a unified
    step's leading one-query rows, and every row of a call that says its
    rows bring one query each (``one_query_rows``: the fused decode call).
    The groups are the batch's tables'; a window layer hands the kernel
    shifted tables of its own, which they do not describe, and rows that
    walk alone are no faster there than in the upstream call (module
    docstring): its rows keep it."""
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

    def rows(q, bq, kv_lens, page_tables, cu_q_lens, num_seqs):
        if groups is None or sliding_window is not None:
            return kernel(q, bq, kv_lens, page_tables, cu_q_lens, num_seqs)
        return rows_attention(q, layer_cache, kv_lens, page_tables,
                              cu_q_lens, groups, sm_scale=scale, bkv=bkv,
                              interpret=interpret)

    if len(pairs) == 1:
        call = rows if one_query_rows else kernel
        return call(q, pairs[0][1], kv_lens, page_tables, cu_q_lens, num_seqs)
    # both calls walk a row's KV blocks of bkv pages from its table's first
    # entry, as the one call did: each token's result is that call's, bit for
    # bit, from whichever call owns its row
    n_dec, decode, chunks = decode_rows_and_chunks(
        page_tables, kv_lens, cu_q_lens, num_seqs)
    head = rows(q[:B], pairs[0][1], *decode)
    out = kernel(q, pairs[1][1], *chunks)
    head = jnp.where((jnp.arange(B) < n_dec)[:, None, None], head, out[:B])
    return jax.lax.dynamic_update_slice_in_dim(out, head, 0, axis=0)
