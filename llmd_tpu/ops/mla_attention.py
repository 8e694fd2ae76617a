"""Latent attention over the single-plane pool for ragged rows: one Pallas
kernel for the unified step (chunks of a prefill and decode rows in one flat
batch) and the fused decode call.

Absorbed MLA is MQA whose one key/value head is the pool's row ``[c_kv ;
k_rope]`` (lane-padded, 576 -> 640 at GLM-4.7-Flash's widths): a token's row
is read once and serves the score product and the weighted sum. The XLA
reference (`models.transformer.ragged_paged_attention_xla`) gathers every page
of ``max_model_len`` for each of 32 query tokens and casts it to float32; the
page-a-grid-step decode kernel (`ops/mla_decode.py`) takes one 16-token page
(20 KB) a grid step. Neither survives contexts of 16k tokens and more. This
kernel walks a row's keys in blocks of ``bkv`` pages.

The grid is the batch rows; everything else is loops inside the kernel over
what the row really holds, with q, the pool and the output left in HBM:

- a chunk's queries go in blocks of ``bq`` tokens, loaded with all (padded)
  heads of a token as rows of one matrix ``[bq * Hp, Dhp]`` (Hp: the heads
  padded to a power of two at least 32, so that this fold is a relabelling of
  tiles and not a relayout). Where the model's own heads fill whole tiles
  (``bq * H`` a multiple of 16: 320 rows for 512 at 20 heads and bq 16) a
  chunk's block is compacted to them once a query block, by a product with a
  0/1 selection matrix (exact: one input times 1.0 plus zeros), and the output
  block is spread back the same way: the two products of every KV block then
  see no padded head (`chunk_fold`),
- one-query rows (a decode row: 32 padded head rows) walk in groups of up to
  ``GROUP_ROWS`` whose tables start on the same pages (`ops/row_groups.py`,
  the rule the GQA kernel's one-query rows share, derived from
  the page tables with ``jax.numpy`` once a program and scalar-prefetched: no
  option, rows behind one cached document or system prompt are seen as they
  come). The group's first row leads: the leading KV blocks all its members
  name alike are fetched once and multiplied with the members' queries
  stacked in one matrix ``[GROUP_ROWS * Hp, Dhp]``, then each member's own
  blocks with its own queries on its own rows of the scratch; the other
  members' grid steps do nothing. A row's blocks come in the order and the
  size they had, a product's row depends on its own input row alone, and m, l
  and acc are a row's own, so a row's result is bit for bit what it is alone.
  A row that shares nothing is a group of one with nothing shared,
- for each query block (or group), the KV blocks up to its last query's
  position, fetched page by page into one of two VMEM buffers while the other is
  computed on (the page table is scalar-prefetched),
- the score product runs over all ``Dhp`` lanes, the weighted sum over the
  value lanes only (``V``: the latent rank rounded up to a lane tile, 512 of
  640 at GLM's widths; the rope keys' lanes and the padding are nobody's
  values, `value_lanes`), and the output's lanes past ``V`` are zero,
- online softmax in float32 (m, l, acc in VMEM scratch); a block that lies
  wholly past a query multiplies its state by exactly 1 and adds exactly 0,
  so a token's result does not depend on the chunk that brought it or on the
  rows beside it: cold and prefix-cached requests, and the two step programs,
  block a row's keys alike as long as they share ``bkv``
  (`ops/paged_attention.py`, "One bkv an engine"),
- the output block leaves token by token, only for tokens the row has; rows of
  the flat batch that no sequence owns stay zero (the output aliases a zeroed
  buffer).

Causality derives from ``kv_len - q_len + local index`` as in the GQA kernel:
a row's queries are its last ``q_len`` tokens. ``-1`` page-table entries are
clamped to page 0 for the DMA's sake; they lie past ``kv_len`` and are masked.

What the two extents are worth (`tools/mla_attn_sweep.py --bkv 64`, TPU v5
lite, PR 45; 64 rows over 1.14 M cached tokens of 16.4k-19.5k a row, 64 pages
a KV block; us a call, before = the parent commit's file in the same chip
call, after = this file; every row's result equal to the padded kernel's in
the value lanes, ``diff`` 0.0; the last column is the first call's seconds, a
call site's trace, lower and compile):

    shape                          bq   before   after   first call s
    fused decode call (64 x 1)      1    2,523   2,480   1.6 -> 1.9
    + one 128-token chunk           8    3,876   3,430   3.5 -> 3.9
      (63 rows decode, 256 tokens) 16*   3,702   3,236   3.8 -> 3.9
                                   32    3,644   3,188   5.9 -> 6.0
    + one 256-token chunk           8    5,232   4,386   4.2 -> 3.5
      (63 rows decode, 320 tokens) 16*   4,907   4,021   4.6 -> 4.3
                                   32    4,752   3,897   5.9 -> 5.2

(the 256-token chunk's "before" is the padded kernel kept in the tool, which
read the parent's own numbers within 0.5% at the other two shapes). A chunk's
part of a call, the call less the decode call: 1,179 -> 757 us at 128 tokens
and 2,384 -> 1,541 at 256 (0.64 of it stays, where the matrix work is 0.56:
what is left beside the products is a KV block's weights latched once for
fewer rows). The decode rows move by 2%: a one-query row waits on its 64 page
fetches a block, not on the matrix unit.

What the shared fetch is worth (`tools/mla_attn_sweep.py --bkv 64 --bq 16
--shared 4 --groups 4,8 --parent <the parent commit's file>`, TPU v5 lite,
PR 49; the same 64 rows, every four behind one document of 16,384 tokens and
1.0-3.0k tokens of their own: 1,139 KV blocks once a row, 371 fetched; us a
call, parent = the parent commit's file in the same chip call; every row's
result equal to the parent's in the value lanes, ``diff`` 0.0; first call s as
above, parent -> this file):

    shape                        parent   G = 4*   G = 8   first call s
    fused decode call (64 x 1)    2,475    1,118   1,525   1.7 -> 2.6
    + one 128-token chunk         3,250    1,936   2,336   5.0 -> 5.7
    + one 256-token chunk         4,038    2,718   3,119   4.6 -> 5.4
    rows that share nothing (``--shared 0``), G = 4:
    fused decode call             2,484    2,429
    + one 128-token chunk         3,249    3,203
    + one 256-token chunk         4,008    3,994

A shared block's visit costs 3.3 us where its 64 page copies take 2.3: the
two products over 128 stacked rows are not hidden behind the copies. Issuing
the next block's copies inside the products' branch, and dropping the mask
where a block lies before every member's end, each read within 1% of this
(1,125-1,131 us beside 1,122 in one chip call; not kept). At 4 lanes a
document a group of 8 is half padding (256 rows a product): slower. One walk
serves rows with and without company: a second walk for the groups beside the
parent's for single rows cost 1.7 s more a call site to trace and lower, every
launch (15 s of ``glm47flash-docs``' ``setup_s``), and was given up; with the
one walk ``setup_s`` reads as the parent's.

The kernel's name on the device trace is ``mla_ragged_paged_attention``: the
benchmark's ``attn_dev_share`` reads attention by the pattern
``ragged_paged_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmd_tpu.ops.paged_attention import VMEM_LIMIT, shard_over_heads
from llmd_tpu.ops.row_groups import row_groups

NEG_INF = -0.7 * float(np.finfo(np.float32).max)
_MINOR = 128  # lane width of the m / l scratch rows; column 0 is meaningful
HEAD_TILE = 32  # heads are padded to a power of two at least this: whole bf16 tiles
ROW_TILE = 16  # rows of a bf16 tile

# Pages a KV block: 1,024 tokens at pages of 16. A page is one DMA of 20 KB
# (640 lanes of bf16), a third of the GQA cells' pages, and the calls are
# bound by the page fetches' fixed costs: at 64 rows decoding over 1.14 M
# tokens 16 / 32 / 64 pages a block read 3,925 / 2,947 / 2,529 us a call
# against a byte floor of 1,608, and a unified step with one 128-token chunk
# beside them 5,383 / 4,121 / 3,699 (`tools/mla_attn_sweep.py`, chip, PR 39).
# The fetch loop is unrolled per page: a block of 64 costs 3.8 s to trace and
# lower a call site where 32 costs 2.4.
KV_BLOCK_TOKENS = 1024
KV_BLOCK_MAX_PAGES = 64

# One-query rows a group: rows whose leading KV blocks name the same pages
# have those blocks fetched once and their queries stacked in one matrix of
# GROUP_ROWS x Hp rows (128 at 32 padded heads: one pass of a 128-row matrix
# unit where a single row fills a quarter of one).
GROUP_ROWS = 4


def pick_block_sizes(num_tokens: int, num_rows: int, page_size: int,
                     pages_per_seq: int) -> tuple[int, int]:
    """(pages a KV block, query tokens a query block) of a call with these
    static shapes. bkv reads the page size and a row's page budget and never
    the token budget: an engine's programs, and a cold and a cached request,
    must block a row's keys alike. bq reads the token budget: one query row a
    sequence (the fused decode call: ``num_tokens == num_rows``) takes a block
    of 1, a unified step blocks of 16 (a chunk reads its context once a query
    block; its decode rows take the block-of-1 path inside the kernel)."""
    bkv = max(1, min(pages_per_seq, KV_BLOCK_MAX_PAGES,
                     KV_BLOCK_TOKENS // page_size))
    while pages_per_seq % bkv:  # whole blocks of the page table
        bkv -= 1
    return bkv, 1 if num_tokens <= num_rows else min(16, num_tokens)


def padded_heads(heads: int) -> int:
    """The heads a token's rows are padded to in HBM and in the query and
    output buffers: a power of two, whole bf16 tiles."""
    return max(HEAD_TILE, 1 << (heads - 1).bit_length())


def chunk_fold(bq: int, heads: int) -> int:
    """Rows a token takes in the matrix a chunk's query block hands the two
    products: the model's heads where ``bq`` tokens of them are whole bf16
    tiles and fewer than the padded ones, else the padded heads (a power of
    two at least 32 keeps the load's own fold)."""
    hp = padded_heads(heads)
    return heads if heads < hp and (bq * heads) % ROW_TILE == 0 else hp


def value_lanes(rank: "int | None", lanes: int) -> int:
    """Lanes of a pool row that are values: the latent rank rounded up to a
    lane tile (a static slice at a tile's edge), never more than the row. A
    caller that names no rank gets the whole row."""
    return lanes if rank is None else min(lanes, -(-rank // _MINOR) * _MINOR)


def _kernel(pt_ref, kv_lens_ref, cu_ref, nseq_ref,  # scalar prefetch (SMEM)
            member_ref, size_ref, shared_ref,  # the groups (`row_groups`)
            q_hbm, pool_hbm, o_init_hbm,  # HBM
            o_hbm,  # HBM, aliased to o_init_hbm
            q_buf, kv_buf, o_buf, m_ref, l_ref, acc_ref,  # VMEM scratch
            q_sem, kv_sem, o_sem,
            *, bq: int, hq: int, bkv: int, maxp: int, G: int, scale: float):
    del o_init_hbm
    b = pl.program_id(0)
    ps = kv_buf.shape[1] // bkv
    T = bkv * ps
    Hp, Dhp = q_buf.shape[1], q_buf.shape[2]
    V = o_buf.shape[2]
    q_start = cu_ref[b]
    q_len = cu_ref[b + 1] - q_start
    kv_len = kv_lens_ref[b]

    def fetch(row, j, slot):
        """The page copies of row ``row``'s KV block ``j`` into buffer
        ``slot``."""
        return [pltpu.make_async_copy(
            pool_hbm.at[pt_ref[row * maxp + j * bkv + i]],
            kv_buf.at[slot, pl.ds(i * ps, ps)], kv_sem.at[slot])
            for i in range(bkv)]

    def attend(q, slot, j, rows, last_key):
        """KV block ``j`` (in buffer ``slot``) into the online softmax of the
        scratch rows ``rows``, whose queries are the matrix ``q``."""
        R = q.shape[0]
        # [T, Dhp]: keys, and in the first V lanes values
        s = lax.dot_general(q, kv_buf[slot], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        key = j * T + lax.broadcasted_iota(jnp.int32, (R, T), 1)
        mask = key <= last_key
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha[:, :1] + lax.dot_general(
            p.astype(kv_buf.dtype), kv_buf[slot, :, pl.ds(0, V)],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    def reset(R: int):
        m_ref[pl.ds(0, R)] = jnp.full((R, _MINOR), NEG_INF, jnp.float32)
        l_ref[pl.ds(0, R)] = jnp.zeros((R, _MINOR), jnp.float32)
        acc_ref[pl.ds(0, R)] = jnp.zeros((R, V), jnp.float32)

    def query_block(qb, nq: int, h: int):
        """Query block ``qb`` of the row in blocks of ``nq`` tokens of ``h``
        rows each (both static; ``h`` is Hp, or the chunks' ``hq``)."""
        R = nq * h
        t0 = q_start + qb * nq  # the block's first row of the flat batch
        n_valid = jnp.minimum(nq, q_len - qb * nq)
        first_pos = kv_len - q_len + qb * nq
        n_kv = (first_pos + n_valid - 1) // T + 1
        load = pltpu.make_async_copy(q_hbm.at[pl.ds(t0, nq)],
                                     q_buf.at[pl.ds(0, nq)], q_sem)
        load.start()
        for c in fetch(b, 0, 0):
            c.start()
        reset(R)
        # a row's token, and with it the last key it may see
        row = lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        tok = sum((row >= t * h).astype(jnp.int32) for t in range(1, nq))
        last_key = first_pos + tok
        load.wait()
        q = q_buf[pl.ds(0, nq)].reshape(nq * Hp, Dhp)
        if h < Hp:
            # row r of the matrix is padded row r + tok * (Hp - h)
            sel = (lax.broadcasted_iota(jnp.int32, (R, nq * Hp), 1)
                   == row + tok * (Hp - h)).astype(q.dtype)
            q = lax.dot_general(sel, q, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ).astype(q.dtype)

        def kv_block(j, _):
            slot = j % 2

            @pl.when(j + 1 < n_kv)
            def _prefetch():
                for c in fetch(b, j + 1, 1 - slot):
                    c.start()

            for c in fetch(b, j, slot):
                c.wait()
            attend(q, slot, j, pl.ds(0, R), last_key)
            return 0

        lax.fori_loop(0, n_kv, kv_block, 0)
        out = (acc_ref[pl.ds(0, R)] / l_ref[pl.ds(0, R)][:, :1]).astype(
            o_buf.dtype)
        if h < Hp:
            # back to the padded rows; a padded head's row selects nothing
            out = lax.dot_general(sel, out, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32
                                  ).astype(o_buf.dtype)
        o_buf[pl.ds(0, nq)] = out.reshape(nq, Hp, V)
        # token by token, and only the row's own: the flat batch's next rows
        # are another sequence's. Lanes past V keep the zeros they alias.
        stores = [pltpu.make_async_copy(
            o_buf.at[t], o_hbm.at[t0 + t, :, pl.ds(0, V)], o_sem)
            for t in range(nq)]
        for t, c in enumerate(stores):
            pl.when(t < n_valid)(c.start)
        for t, c in enumerate(stores):
            pl.when(t < n_valid)(c.wait)

    def group(n, shared):
        """The ``n`` one-query rows row ``b`` leads: their ``shared`` leading
        KV blocks fetched once for the members' queries stacked in one matrix,
        then each member's own blocks on its own rows of the scratch. One loop
        over the visits ``t``: the shared blocks, then member after member's
        tail, so the page copies have the call sites of one row's walk."""
        R = G * Hp
        member = [member_ref[b * G + g] for g in range(G)]
        last = [kv_lens_ref[r] - 1 for r in member]  # the key a member ends on
        tail = [jnp.where(g < n, last[g] // T + 1 - shared, 0)
                for g in range(G)]
        ends = [shared + sum(tail[:g + 1]) for g in range(G)]

        def place(t):
            """(page-table row, KV block, member) of visit ``t``; a shared
            block is the leader's, member 0."""
            past = [t >= e for e in ends[:-1]]
            g = sum(p.astype(jnp.int32) for p in past)
            start = shared + sum(jnp.where(p, n_t, 0)
                                 for p, n_t in zip(past, tail))
            return (member_ref[b * G + g],
                    jnp.where(t < shared, t, shared + t - start), g)

        loads = [pltpu.make_async_copy(q_hbm.at[pl.ds(cu_ref[r], 1)],
                                       q_buf.at[pl.ds(g, 1)], q_sem)
                 for g, r in enumerate(member)]
        for c in loads:
            c.start()
        for c in fetch(b, 0, 0):
            c.start()
        reset(R)
        row = lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        last_key = functools.reduce(
            lambda v, g: jnp.where(row >= g * Hp, last[g], v), range(1, G),
            jnp.full((R, 1), last[0], jnp.int32))
        for c in loads:
            c.wait()

        def visit(t, here):
            slot = t % 2
            ahead = place(t + 1)

            @pl.when(t + 1 < ends[-1])
            def _prefetch():
                for c in fetch(*ahead[:2], 1 - slot):
                    c.start()

            for c in fetch(b, 0, slot):  # a wait reads the bytes, not the pages
                c.wait()

            @pl.when(t < shared)
            def _together():
                attend(q_buf[pl.ds(0, G)].reshape(R, Dhp), slot, t,
                       pl.ds(0, R), last_key)

            @pl.when(t >= shared)
            def _alone():
                r, j, g = here
                attend(q_buf[g], slot, j,
                       pl.ds(pl.multiple_of(g * Hp, Hp), Hp),
                       kv_lens_ref[r] - 1)

            return ahead

        # the first visit is the leader's first block, shared or its own
        lax.fori_loop(0, ends[-1], visit, (b, jnp.int32(0), jnp.int32(0)))
        o_buf[pl.ds(0, G)] = (
            acc_ref[pl.ds(0, R)] / l_ref[pl.ds(0, R)][:, :1]).astype(
                o_buf.dtype).reshape(G, Hp, V)
        stores = [pltpu.make_async_copy(
            o_buf.at[g], o_hbm.at[cu_ref[r], :, pl.ds(0, V)], o_sem)
            for g, r in enumerate(member)]
        for g, c in enumerate(stores):
            pl.when(g < n)(c.start)
        for g, c in enumerate(stores):
            pl.when(g < n)(c.wait)

    # a live one-query row leads its group (of one, where it shares nothing)
    # or is walked by the row that does: size 0, as a chunk's and an idle seat's
    @pl.when(size_ref[b] > 0)
    def _decode_rows():
        group(size_ref[b], shared_ref[b])

    if bq > 1:
        @pl.when((b < nseq_ref[0]) & (kv_len > 0) & (q_len > 1))
        def _chunk():
            def body(qb, _):
                query_block(qb, bq, hq)
                return 0

            lax.fori_loop(0, (q_len + bq - 1) // bq, body, 0)


def mla_ragged_pallas(
    q: jax.Array,  # [N, H, Dhp] flat query tokens (lane-padded latent width)
    layer_cache: jax.Array,  # [P, ps, 1, Dhp] single-plane latent pool
    kv_lens: jax.Array,  # [B] tokens resident incl. this step's
    page_tables: jax.Array,  # [B, maxp], clamped >= 0
    cu_q_lens: jax.Array,  # [B+1]
    num_seqs: jax.Array,  # [1]
    *groups: jax.Array,  # `row_groups` of the call, where the caller has them
    scale: float,
    rank: "int | None" = None,
    interpret: bool = False,
) -> jax.Array:
    """The raw kernel call. Returns [N, H, Dhp]: the latent-weighted sums in
    the value lanes (`value_lanes` of ``rank``; zero past them), zero for
    rows of the flat batch that no live sequence owns."""
    N, H, Dhp = q.shape
    P, ps, planes, _ = layer_cache.shape
    assert planes == 1, "the single-plane latent pool"
    B, maxp = page_tables.shape
    bkv, bq = pick_block_sizes(N, B, ps, maxp)
    Hp, V, hq = padded_heads(H), value_lanes(rank, Dhp), chunk_fold(bq, H)
    members, size, shared = groups or plan(
        page_tables, kv_lens, cu_q_lens, num_seqs, ps)["groups"]
    G = members.shape[1]
    # heads padded to whole tiles, and bq rows past the batch's end so that a
    # query block's load stays in bounds (what it reads there is not stored)
    qp = jnp.pad(q, ((0, bq), (0, Hp - H), (0, 0)))
    kernel = functools.partial(_kernel, bq=bq, hq=hq, bkv=bkv, maxp=maxp,
                               G=G, scale=scale)
    rows = max(G * Hp, bq * hq)  # a group's one-query rows, or a chunk's block
    nq = max(G, bq)  # tokens the query and output buffers hold
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(B,),
            in_specs=[hbm, hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((nq, Hp, Dhp), q.dtype),  # q block
                pltpu.VMEM((2, bkv * ps, Dhp), layer_cache.dtype),
                pltpu.VMEM((nq, Hp, V), q.dtype),  # output block
                pltpu.VMEM((rows, _MINOR), jnp.float32),  # m
                pltpu.VMEM((rows, _MINOR), jnp.float32),  # l
                pltpu.VMEM((rows, V), jnp.float32),  # acc
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N + bq, Hp, Dhp), q.dtype),
        # the output starts as zeros: a row no sequence owns is never written
        input_output_aliases={9: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="mla_ragged_paged_attention",
    )(page_tables.reshape(-1).astype(jnp.int32), kv_lens.astype(jnp.int32),
      cu_q_lens.astype(jnp.int32), num_seqs.astype(jnp.int32),
      members.reshape(-1), size, shared,
      qp, layer_cache.reshape(P, ps, Dhp), jnp.zeros_like(qp))
    return out[:N, :H]


def mla_paged_attention(
    q: jax.Array,  # [N, H, Dhp] flat query tokens (lane-padded)
    layer_cache: jax.Array,  # [P, ps, 1, Dhp]
    page_tables: jax.Array,  # [B, maxp] (-1 = unmapped)
    positions: jax.Array,  # [N] (unused: causality derives from kv/cu lens)
    seq_slots: jax.Array,  # [N] (unused on this path)
    kv_lens: jax.Array,  # [B]
    *,
    scale: float,
    cu_q_lens: jax.Array,  # [B+1]
    num_seqs: jax.Array,  # [1]
    chunk_k: "jax.Array | None" = None,  # unused (ring-attn impls only)
    chunk_v: "jax.Array | None" = None,  # unused (ring-attn impls only)
    rank: "int | None" = None,  # the model's kv_lora_rank: the value lanes
    interpret: bool = False,  # True only when the selecting platform is CPU
    mesh=None,  # engine mesh: the kernel runs per device under shard_map
    groups=None,  # `row_groups` of the batch, where the program derived them
) -> jax.Array:
    """Uniform-signature adapter (drop-in for ragged_paged_attention_xla) for
    a latent-attention engine's step programs, mixed batches and decode calls
    alike."""
    del positions, seq_slots, chunk_k, chunk_v
    page_tables = jnp.maximum(page_tables, 0)
    if layer_cache.dtype == jnp.float8_e4m3fn:
        # fp8 latent pages are stored at scale 1.0: upcasting is the dequant
        layer_cache = layer_cache.astype(q.dtype)
    call = functools.partial(mla_ragged_pallas, scale=scale, rank=rank,
                             interpret=interpret)
    if mesh is not None:
        # heads split over tp; the latent plane is replicated
        call = shard_over_heads(call, mesh, q, layer_cache, shard_kv=False)
    return call(q, layer_cache, kv_lens, page_tables, cu_q_lens, num_seqs,
                *(groups or ()))


def plan(page_tables, kv_lens, cu_q_lens, num_seqs, page_size: int) -> dict:
    """What `models.transformer.forward_core` asks an attention impl for once
    a program, before its layers: keyword arguments of every layer's call."""
    # from the tables as the engine packed them: an unmapped entry (-1) is
    # no layer's page 0, which a layer's clamped table could not tell apart
    bkv, _ = pick_block_sizes(0, 0, page_size, page_tables.shape[1])
    return {"groups": row_groups(page_tables, kv_lens, cu_q_lens, num_seqs,
                                 page_size, bkv, GROUP_ROWS)}
