"""Which one-query rows of a batch walk their leading KV blocks together: the
one grouping rule of the two attention kernels that fetch a KV block once for
the stacked queries of the rows that name it alike (`ops/mla_attention.py`,
the latent kernel; `ops/paged_attention.py`, the GQA kernel's one-query rows).

Rows behind one cached document or system prompt name the same pages first
(the prefix cache holds the prompt once). The rule reads that off the page
tables as they come, with ``jax.numpy`` once a program on the device
(`row_groups`, scalar-prefetched by the kernels) and with ``numpy`` on the
host for the counters (`decode_kv_blocks`); a kernel brings its own KV block
(``bkv`` pages) and its own rows a group (``G``).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _groups(xp, page_tables, kv_lens, q_lens, num_seqs, bkv: int, ps: int,
            G: int):
    """The grouping rule, over ``numpy`` or ``jax.numpy`` (``xp``). One-query
    rows whose tables start on the same page form a sharing set, cut in row
    order into groups of up to ``G``; a group's first row leads it.
    Returns ``(members [B, G], size [B], shared [B], n_kv [B])``: a leader's
    members (itself first, the seats past ``size`` itself again), its size
    (0 for every row that leads nothing: a member, a chunk, an idle seat) and
    the leading KV blocks all its members name alike below each one's own
    ``n_kv``, the blocks a one-query row walks. The unmapped entries of two
    rows look alike (``-1``, or page 0 once the kernel's call has clamped
    them): only blocks below ``n_kv`` count."""
    B, maxp = page_tables.shape
    nb = -(-maxp // bkv)  # a table's last block may be short: unmapped past it
    rows, seats, walk = xp.arange(B), xp.arange(G), xp.arange(nb)
    if nb * bkv > maxp:
        page_tables = xp.pad(page_tables, ((0, 0), (0, nb * bkv - maxp)),
                             constant_values=-1)
    blocks = page_tables.reshape(B, nb, bkv)
    n_kv = xp.maximum(kv_lens - 1, 0) // (bkv * ps) + 1
    one = (rows < num_seqs) & (q_lens == 1) & (kv_lens > 0)
    first = blocks[:, 0, 0]  # a prefix cache shares a page with all before it
    same = one[:, None] & one[None, :] & (first[:, None] == first[None, :])
    # a row's place in its sharing set, and the rows up to G places on from it
    pos = (same & (rows[None, :] < rows[:, None])).sum(1)
    hit = same[:, None, :] & (
        pos[None, None, :] == (pos[:, None] + seats)[:, :, None])
    found = hit.any(-1)
    members = xp.where(found, hit.argmax(-1), rows[:, None])
    size = xp.where(one & (pos % G == 0), found.sum(-1), 0)
    leader = xp.where(one, (same & (
        pos[None, :] == (pos - pos % G)[:, None])).argmax(-1), rows)
    alike = (blocks == blocks[leader]).all(-1) & (
        walk < xp.minimum(n_kv, n_kv[leader])[:, None])
    # the first block a row names otherwise than its leader, then the least
    # over a leader's members
    shared = xp.where(alike, nb, walk).min(-1)[members].min(-1)
    shared = xp.where(size > 1, shared, 0)  # a row alone walks its own blocks
    return (members.astype(xp.int32), size.astype(xp.int32),
            shared.astype(xp.int32), n_kv)


def row_groups(page_tables, kv_lens, cu_q_lens, num_seqs, page_size: int,
               bkv: int, G: int):
    """``(members, size, shared)`` of a call (`_groups`, on the device): what
    a kernel is told of its one-query rows. A function of the batch's layout
    alone and the same for every layer's slice of the pool (a layer's page
    ids are the batch's plus the layer's offset), so a program derives it
    once and hands it to each layer's call (``plan``)."""
    return _groups(jnp, page_tables, kv_lens, cu_q_lens[1:] - cu_q_lens[:-1],
                   num_seqs, bkv, page_size, G)[:3]


def decode_kv_blocks(page_tables, kv_lens, q_lens, page_size: int, bkv: int,
                     G: int) -> tuple[int, int]:
    """(KV blocks once a row, KV blocks the kernel fetches) of a call's
    one-query rows, from the page tables the step packed (numpy arrays, all
    three): the numpy twin of what `row_groups` derives on the device (the
    two ``*_decode_kv_blocks_total`` series)."""
    one = (q_lens == 1) & (kv_lens > 0)
    page_tables, kv_lens = page_tables[one], kv_lens[one]
    n_kv = np.maximum(kv_lens - 1, 0) // (bkv * page_size) + 1
    walked = int(n_kv.sum())
    # rows that start on pages of their own share nothing: no rule to ask
    # (the host's turn of a step has no 0.3 ms to spare where it is the
    # longer side, and there nothing is shared)
    if len(np.unique(page_tables[:, 0])) == len(kv_lens):
        return walked, walked
    # the rule, over the one-query rows and the blocks they walk
    members, size, shared, n_kv = _groups(
        np, page_tables[:, :int(n_kv.max()) * bkv], kv_lens,
        np.ones_like(kv_lens), len(kv_lens), bkv, page_size, G)
    tails = (n_kv[members] - shared[:, None]) * (
        np.arange(G) < size[:, None])
    return walked, int((shared * (size > 0) + tails.sum(1)).sum())
