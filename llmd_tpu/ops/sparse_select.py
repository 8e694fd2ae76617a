"""Block-sparse attention over the paged pool: compressed keys, block
scores, top-k, and the selected page tables the attention kernel is handed.

A query at position ``t`` sees ``n = t + 1`` keys. With ``n <
sparse_dense_len`` it attends to all of them (today's call). Otherwise, per
KV head (its query heads share one selection), it attends to the visible
tokens of the blocks in

    S = {0 .. init_blocks - 1} U {b : b_t - window_blocks < b <= b_t} U top-k of the rest

where a block is ``block_size`` tokens, ``b_t = t // block_size``, and the
rest are ranked by ``s_b = max over the kernels j that overlap block b of sum
over the KV head's query heads of p_(h, j)``, ``p_(h, .) = softmax_j(q_h . c_j
* scale)`` over the whole kernels visible to ``t``, ``c_j = mean(k[j * stride
: j * stride + kernel_size])``. Fewer candidates than top-k: all are taken;
ties: the lower block first.

What makes this cheap over a paged pool:

- ``kernel_stride`` is the page size and ``kernel_size`` two pages, so kernel
  ``j`` is pages ``j`` and ``j + 1`` of its sequence and the **compressed-key
  plane** holds one key a page (``[folds * P, Dhp]``, row = the page's row in
  the folded pool). Page ``j``'s entry is written by the call that computes
  the last token of page ``j + 1`` and never rewritten; a query sees whole
  kernels only, so it never reads an entry its own sequence has not written.
- the attention layers apply no positional encoding, so attention over a set
  of blocks is attention over a **compacted page table** that lists the
  selected blocks' pages in order, the block that holds ``t`` last, with
  ``kv_len`` cutting that block's tail. Each (query, KV head) past
  ``dense_len`` is a row of its own for the ragged kernel: one query, a table
  of at most ``(init + window + top-k) * pages a block`` pages.
- the pool folds a layer's KV heads into its page axis (``ModelConfig.
  kv_pool_folds``): a KV head's pages are pages of their own, so a call reads
  only the head whose selection it was given.
- a prefill chunk's queries past ``dense_len`` select differently, together
  nearly every block of their row: they take one **block-masked call** over
  the row's whole table (``masked_chunk_attention``: the row's keys once, a
  block of ``CHUNK_KEYS`` at a time, each query's own blocks by a mask), in
  XLA. As rows of their own they cost 3.7 ms a KV head and layer on the chip
  (250 one-query rows of 13 KV blocks each) against 0.25 ms for the dense
  call of the same chunk.

Selection scores are float32 sums of exact products of the stored (model
type) queries and compressed keys; the sums over a kernel's keys and over a
KV head's query heads are written as trees of adds, whose order no program's
shapes can change.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
NEG = -1e30
# queries a step's selection is computed for where no more are past
# dense_len (the decode rows of 32 or 64 seats): else for every token
SELECT_FEW = 64
# blocks of keys a turn of the block-masked call (1,024 tokens at blocks of 64)
CHUNK_BLOCKS = 16


def geometry(cfg, page_size: int) -> dict:
    """The selection's sizes in pages and blocks, at this page size."""
    if cfg.sparse_kernel_stride != page_size:
        raise ValueError(
            f"sparse_kernel_stride={cfg.sparse_kernel_stride} must be the "
            f"page size ({page_size}): a compressed key a page")
    ppb = cfg.sparse_block_size // page_size
    wb = cfg.sparse_window // cfg.sparse_block_size
    return {"ppb": ppb, "window_blocks": wb,
            "max_blocks": cfg.sparse_init_blocks + wb + cfg.sparse_topk,
            "max_pages": (cfg.sparse_init_blocks + wb + cfg.sparse_topk) * ppb}


def selected_tokens(cfg, n):
    """Tokens the selected table of a query that sees ``n`` keys holds
    (a numpy array or a whole number, elementwise): ``n`` itself below
    ``sparse_dense_len``. What the host books ``attn_kv_tokens_total{layers=
    "sparse"}`` with."""
    t = n - 1
    bs = cfg.sparse_block_size
    b_t = t // bs
    wb = cfg.sparse_window // bs
    init = np.minimum(cfg.sparse_init_blocks, b_t + 1)
    window = np.minimum(wb, b_t + 1 - init)
    rest = np.maximum(0, b_t + 1 - init - window)
    blocks = init + window + np.minimum(cfg.sparse_topk, rest)
    return np.where(n < cfg.sparse_dense_len, n,
                    (blocks - 1) * bs + t % bs + 1)


def _tree_sum(x, axis: int):
    """The sum over ``axis`` as a tree of adds of halves (a length that is a
    power of two), so that its order is the program's text and not the
    compiler's choice for a shape."""
    n = x.shape[axis]
    if n & (n - 1):
        return jnp.sum(x, axis=axis)
    while n > 1:
        n //= 2
        x = (lax.slice_in_dim(x, 0, n, axis=axis)
             + lax.slice_in_dim(x, n, 2 * n, axis=axis))
    return jnp.squeeze(x, axis)


def write_compressed_keys(ck, flat_cache, page_tables, positions, seq_slots,
                          base, page_size: int):
    """The compressed keys that this call's tokens complete, written into
    the plane. ``ck`` [folds * P, Dhp]; ``flat_cache`` [folds * P * ps, 2,
    Dhp] the pool by token slot, this call's keys already written;
    ``page_tables`` [B, maxp] the rows' own page ids; ``base`` [Hk] the first
    pool page of this layer's KV heads. The token at position p completes
    kernel ``j = (p + 1) // ps - 2`` when it is the last of page ``j + 1``."""
    ps = page_size
    B, maxp = page_tables.shape
    b = jnp.clip(seq_slots, 0, B - 1)
    done = (positions >= 2 * ps - 1) & (positions % ps == ps - 1)
    j = jnp.clip((positions + 1) // ps - 2, 0, maxp - 2)
    pt = jnp.maximum(page_tables, 0)[b]  # [N, maxp]
    pages = jnp.stack([jnp.take_along_axis(pt, (j + d)[:, None], axis=1)[:, 0]
                       for d in (0, 1)], axis=1)  # [N, 2]
    rows = base[None, :, None] + pages[:, None, :]  # [N, Hk, 2]
    slots = (rows[..., None] * ps + jnp.arange(ps, dtype=jnp.int32)).reshape(
        rows.shape[0], rows.shape[1], 2 * ps)
    keys = flat_cache.at[slots, 0].get(mode="promise_in_bounds")
    mean = _tree_sum(keys.astype(F32), 2) * (1.0 / (2 * ps))  # [N, Hk, Dhp]
    at = jnp.where(done[:, None], rows[..., 0], ck.shape[0])
    return ck.at[at].set(mean.astype(ck.dtype), mode="drop")


def select_blocks(cfg, q, ck_tok, positions, page_size: int, scale: float):
    """The blocks every (query, KV head) of a call attends to.

    q: [N, H, Dhp] (after the q-norm); ck_tok: [N, Hk, maxp, Dhp] each
    query's own sequence's compressed keys, kernel j at index j, or [Hk,
    maxp, Dhp] where all are one sequence's (a chunk's); positions: [N].
    Returns ``(sel [N, Hk, nb] whether block b is selected, sparse [N]
    whether the query is past ``sparse_dense_len``)``; a query that is not
    sparse gets its first block."""
    N, H, D = q.shape
    Hk, maxp = ck_tok.shape[-3], ck_tok.shape[-2]
    G = H // Hk
    geo = geometry(cfg, page_size)
    ppb, wb = geo["ppb"], geo["window_blocks"]
    nb = maxp // ppb
    bs, init = cfg.sparse_block_size, cfg.sparse_init_blocks
    sparse = positions + 1 >= cfg.sparse_dense_len
    t = jnp.maximum(positions, 0)
    # whole kernels visible to t: the last one ends at or before t
    nk = jnp.maximum((t + 1) // page_size - 1, 0)  # [N]
    if ck_tok.ndim == 4:
        s = jnp.einsum("nghd,ngjd->nghj", q.reshape(N, Hk, G, D), ck_tok,
                       preferred_element_type=F32) * scale
    else:
        s = jnp.einsum("nghd,gjd->nghj", q.reshape(N, Hk, G, D).astype(F32),
                       ck_tok.astype(F32)) * scale
    kern = jnp.arange(maxp, dtype=jnp.int32)
    seen = kern[None, :] < nk[:, None]  # [N, maxp]
    s = jnp.where(seen[:, None, None, :], s, NEG)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    e = jnp.where(seen[:, None, None, :], e, 0.0)
    z = jnp.einsum("nghj,j->ngh", e, jnp.ones((maxp,), F32),
                   precision=lax.Precision.HIGHEST)
    p = _tree_sum(e / jnp.maximum(z, 1e-30)[..., None], 2)  # [N, Hk, maxp]
    p = jnp.where(seen[:, None, :], p, -1.0)
    # block b's kernels: the ppb that start in it and the one before them
    p = p[..., :nb * ppb]
    inner = jnp.max(p.reshape(N, Hk, nb, ppb), axis=-1)
    before = jnp.pad(p[..., ppb - 1::ppb][..., :nb - 1],
                     ((0, 0), (0, 0), (1, 0)), constant_values=-1.0)
    score = jnp.maximum(inner, before)  # [N, Hk, nb]
    blk = jnp.arange(nb, dtype=jnp.int32)[None, :]
    b_t = (t // bs)[:, None]
    first = blk < init
    window = (blk > b_t - wb) & (blk <= b_t)
    cand = (blk >= init) & (blk <= b_t - wb)
    # the top-k of the candidates by rank, not by a sort (a sort of [N, Hk,
    # 320] took a millisecond on the chip, and the selection needs two): block
    # i is beaten by every block with a higher score, and by a lower-numbered
    # one at a tie
    sc = jnp.where(cand[:, None, :], score, NEG)
    lower = (blk[0][None, :] < blk[0][:, None])  # [i, j]: j < i
    beats = (sc[..., None, :] > sc[..., :, None]) | (
        (sc[..., None, :] == sc[..., :, None]) & lower)
    rank = jnp.sum(beats, axis=-1, dtype=jnp.int32)  # [N, Hk, nb]
    picked = cand[:, None, :] & (rank < cfg.sparse_topk)
    sel = picked | ((first | window) & (blk <= b_t))[:, None, :]
    return jnp.where(sparse[:, None, None], sel, blk[None] == 0), sparse


def select_pages(cfg, q, ck_tok, positions, page_size: int, scale: float):
    """The selection of every (query, KV head) of a call as the places of a
    compacted table: ``(onehot [N, Hk, max_blocks, nb]: the selected blocks
    in order, block b at place i (``table_of`` makes the page table of it),
    kv_len [N, Hk] the tokens the compacted table holds for the query, sparse
    [N])``; a query that is not sparse gets ``kv_len`` 1."""
    sel, sparse = select_blocks(cfg, q, ck_tok, positions, page_size, scale)
    return (*places_of(cfg, sel, sparse, positions, page_size), sparse)


def places_of(cfg, sel, sparse, positions, page_size: int):
    """``(onehot, kv_len)`` of ``select_pages`` from the blocks ``sel`` [N,
    Hk, nb] that ``select_blocks`` chose."""
    geo = geometry(cfg, page_size)
    nb, bs = sel.shape[-1], cfg.sparse_block_size
    t = jnp.maximum(positions, 0)
    blk = jnp.arange(nb, dtype=jnp.int32)
    lower = blk[None, :] < blk[:, None]  # [i, j]: j < i
    n_sel = jnp.sum(sel, axis=-1, dtype=jnp.int32)  # [N, Hk]
    # the selected blocks in order: block b goes to the place that counts
    # the selected blocks before it
    place = jnp.sum(sel[..., None, :] & lower, axis=-1, dtype=jnp.int32)
    slots = jnp.arange(min(geo["max_blocks"], nb), dtype=jnp.int32)
    onehot = sel[..., None, :] & (place[..., None, :] == slots[:, None])
    kv_len = jnp.where(sparse[:, None], (n_sel - 1) * bs + (t % bs)[:, None] + 1,
                       1)
    return onehot, kv_len.astype(jnp.int32)


def table_of(onehot, pages):
    """The compacted page table of a selection: ``onehot`` [N, Hk, places,
    nb] says which block goes to which place, ``pages`` [N, nb * ppb] are each
    query's own sequence's page ids; returns [N, Hk, places * ppb]. One
    product with a 0/1 matrix in place of a gather by element (page ids are
    whole numbers under 2^24, exact in float32 at the highest precision)."""
    N, Hk, places, nb = onehot.shape
    got = jnp.einsum("ngib,nbr->ngir", onehot.astype(F32),
                     pages.reshape(N, nb, -1).astype(F32),
                     precision=lax.Precision.HIGHEST)
    return got.astype(jnp.int32).reshape(N, Hk, -1)


def masked_chunk_attention(cfg, q, pool, pages, positions, sel, kv_len,
                           page_size: int, scale: float):
    """Attention of every query over ONE row's keys, each query its own
    blocks: the block-masked call a prefill chunk past ``dense_len`` takes.

    q: [N, H, Dhp]; ``pool`` [pages, ps, 2, Dhp]; ``pages`` [Hk, maxp] the
    row's page ids in the pool, a KV head each; ``sel`` [N, Hk, nb] the
    blocks each query attends to; ``kv_len`` the row's resident tokens.
    Returns [N, H, Dhp] (what a query that is not the row's reads is not
    used). The keys come ``CHUNK_BLOCKS`` blocks a turn, from the row's first
    on, under an online softmax whose turn for keys a query does not see
    leaves its sums as they were to the bit (a factor of one, a sum of
    zeros): a query's result does not depend on where its chunk ends."""
    N, H, D = q.shape
    Hk, maxp = pages.shape
    G, bs = H // Hk, cfg.sparse_block_size
    ppb = bs // page_size
    kp, kb = CHUNK_BLOCKS * ppb, CHUNK_BLOCKS * bs  # pages, keys a turn
    turns = -(-maxp // kp)
    pages = jnp.pad(pages, ((0, 0), (0, turns * kp - maxp)))
    sel = jnp.pad(sel, ((0, 0), (0, 0),
                        (0, turns * CHUNK_BLOCKS - sel.shape[-1])))
    qg = q.reshape(N, Hk, G, D)

    def turn(i, carry):
        m, l, acc = carry
        pg = lax.dynamic_slice_in_dim(pages, i * kp, kp, axis=1)
        kv = pool.at[pg].get(mode="promise_in_bounds")  # [Hk, kp, ps, 2, D]
        k = kv[:, :, :, 0].reshape(Hk, kb, D)
        v = kv[:, :, :, 1].reshape(Hk, kb, D)
        # (float32 operands that hold the model type's values: the products
        # are the stored values' on every backend)
        s = jnp.einsum("nghd,gkd->nghk", qg.astype(F32), k.astype(F32)) * scale
        key = i * kb + jnp.arange(kb, dtype=jnp.int32)
        mine = lax.dynamic_slice_in_dim(sel, i * CHUNK_BLOCKS, CHUNK_BLOCKS,
                                        axis=2)
        ok = (jnp.repeat(mine, bs, axis=2)
              & (key[None, :] <= positions[:, None])[:, None, :])[:, :, None, :]
        m_new = jnp.maximum(m, jnp.max(jnp.where(ok, s, NEG), axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "nghk,gkd->nghd", p.astype(v.dtype).astype(F32), v.astype(F32))
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, (kv_len + kb - 1) // kb, turn,
        (jnp.full((N, Hk, G), NEG, F32), jnp.zeros((N, Hk, G), F32),
         jnp.zeros((N, Hk, G, D), F32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(N, H, D).astype(q.dtype)


def sparse_paged_attention(cfg, q, flat_cache, ck, page_tables, positions,
                           seq_slots, kv_lens, cu_q_lens, num_seqs, layer,
                           pages_per_fold: int, page_size: int, scale: float,
                           attn_impl, query_attn_impl):
    """Attention of a layer with sparse selection over the folded pool.

    q: [N, H, Dhp]; ``flat_cache`` [folds * P * ps, 2, Dhp] with this call's
    keys and values written; ``ck`` the compressed-key plane with this call's
    entries written; ``layer`` the attention layer's ordinal (traced).
    ``attn_impl`` serves the call's own rows (today's call, a KV head at a
    time), ``query_attn_impl`` the one-query rows of the selected tables.
    Returns [N, H, Dhp].

    Three paths, by the query: below ``dense_len`` today's call; past it, a
    decode row's (one query a row) a selected page table; a prefill chunk's
    the block-masked call over its row (a step has one such row: a second
    chunk is a prompt's first, below ``dense_len``; should there be more,
    the row with the most such queries takes it and the rest go as rows of
    their own). Which path a token takes follows from its position and from
    whether it came in a chunk or alone, so the engine's plan leaves no
    prompt a last chunk of one token."""
    N, H, D = q.shape
    Hk = cfg.num_kv_heads
    G = H // Hk
    B, maxp = page_tables.shape
    P, ps = pages_per_fold, page_size
    pool = flat_cache.reshape(-1, ps, 2, D)
    base = (layer * Hk + jnp.arange(Hk, dtype=jnp.int32)) * P  # [Hk]
    b = jnp.clip(seq_slots, 0, B - 1)
    safe = jnp.maximum(page_tables, 0)
    q_lens = cu_q_lens[1:B + 1] - cu_q_lens[:B]
    with jax.named_scope("sparse_select"):
        rows = base[None, :, None] + safe[:, None, :]  # [B, Hk, maxp]
        ck_row = ck.at[rows].get(mode="promise_in_bounds")  # [B, Hk, maxp, D]
        sparse = positions + 1 >= cfg.sparse_dense_len
        # the chunk's row: a fused decode call (N == B) has none
        past = jnp.clip(kv_lens - jnp.maximum(kv_lens - q_lens,
                                              cfg.sparse_dense_len - 1),
                        0, q_lens)
        past = jnp.where((q_lens > 1) & (jnp.arange(B) < num_seqs[0]), past, 0)
        row = jnp.argmax(past).astype(jnp.int32)
        chunk = (past[row] > 0) if N > B else jnp.bool_(False)
        alone = sparse & ~(chunk & (b == row) & (positions >= 0))
        # the one-query rows first: the kernel's work follows the rows it is
        # told of, and so does the selection's (below)
        order = jnp.argsort(jnp.logical_not(alone), stable=True)
        back = jnp.argsort(order)
        # (one row at the least: told of no row, the upstream kernel leaves
        # the copy it starts for its first row unwaited and the chip halts)
        count = jnp.maximum(jnp.sum(alone), 1).astype(jnp.int32)
        q_sel, pos_sel, b_sel = q[order], positions[order], b[order]

        def tables(n: int):
            """The first ``n`` queries' tables, padded to N (a query that is
            not told of gets one page and one token)."""
            onehot, sel_len, _ = select_pages(
                cfg, q_sel[:n], ck_row[b_sel[:n]], pos_sel[:n], ps, scale)
            nb = onehot.shape[-1]
            pages = table_of(onehot, safe[b_sel[:n]][:, :nb * (maxp // nb)])
            return (jnp.pad(pages, ((0, N - n), (0, 0), (0, 0))),
                    jnp.pad(sel_len, ((0, N - n), (0, 0)), constant_values=1))

        # a step's one-query rows past dense_len are its decode rows (a seat
        # each): the selection of N queries costs N / few times that of the
        # few (a millisecond a layer at 256)
        few = min(N, SELECT_FEW)
        sel_pages, sel_len = tables(N) if few == N else lax.cond(
            count <= few, lambda: tables(few), lambda: tables(N))
        live = jnp.arange(N, dtype=jnp.int32) < count
        sel_pos = jnp.where(live[:, None], sel_len - 1, -1)
        sel_cu = jnp.minimum(jnp.arange(N + 1, dtype=jnp.int32), count)

        def masked():
            sel, _ = select_blocks(cfg, q, ck_row[row], positions, ps, scale)
            return masked_chunk_attention(
                cfg, q, pool, rows[row], positions, sel, kv_lens[row], ps,
                scale)

    together = lax.cond(chunk, masked, lambda: jnp.zeros_like(q)) \
        if N > B else None
    # today's call for the rows below dense_len: a row whose every query is
    # past it keeps its own tokens only (its result is not read)
    all_sparse = kv_lens - q_lens + 1 >= cfg.sparse_dense_len
    dense_lens = jnp.where(all_sparse, jnp.maximum(q_lens, 1), kv_lens)
    dense_pos = jnp.where((positions >= 0) & all_sparse[b],
                          positions - (kv_lens - q_lens)[b], positions)
    ids = jnp.arange(N, dtype=jnp.int32)
    outs = []
    for g in range(Hk):
        qg = lax.slice_in_dim(q, g * G, (g + 1) * G, axis=1)
        dense = attn_impl(
            qg, pool, jnp.where(page_tables >= 0, page_tables + base[g], -1),
            dense_pos, seq_slots, dense_lens, cu_q_lens=cu_q_lens,
            num_seqs=num_seqs, scale=scale, chunk_k=None, chunk_v=None)
        picked = query_attn_impl(
            lax.slice_in_dim(q_sel, g * G, (g + 1) * G, axis=1), pool,
            sel_pages[:, g] + base[g], sel_pos[:, g], ids, sel_len[:, g],
            cu_q_lens=sel_cu, num_seqs=count[None], scale=scale,
            chunk_k=None, chunk_v=None)
        out = jnp.where(alone[:, None, None], picked[back], dense)
        if together is not None:
            out = jnp.where((sparse & ~alone)[:, None, None],
                            lax.slice_in_dim(together, g * G, (g + 1) * G,
                                             axis=1), out)
        outs.append(out)
    return jnp.concatenate(outs, axis=1)
