"""Ring attention: context-parallel causal attention over the ``sp`` mesh axis.

The long-context design the task calls first-class: a sequence too long for one
chip's HBM shards across the ``sp`` axis; each device holds S/N query and KV
tokens, and attention runs in N ring steps — compute the partial attention of
local queries against the resident KV block, then ``ppermute`` the KV block to
the next device, overlapping the collective with the next block's compute (XLA
schedules the permute against the matmuls; ICI bandwidth hides behind MXU time
at serving block sizes).

Numerics: online softmax (flash-attention style running max/denominator), so
the result is exact attention — not an approximation — regardless of ring
order. Causality is resolved block-wise: a KV block strictly newer than every
local query contributes nothing (its lanes are masked), the diagonal block gets
the triangular mask, older blocks attend fully.

This is the context-parallel ATTENTION OP for the sharded long-prefill path —
self-contained and oracle-tested here; engine integration (routing sp-sharded
prefill chunks through it instead of the GSPMD-gathered path) is the follow-up.
The serving engine's paged decode keeps per-sequence KV local either way
(decode reads are tiny — sp parallelism pays off in prefill, where the S² term
lives). `sp_flash_prefill` below is the jittable entry: q/k/v arrive already
sharded on the sequence axis under `shard_map`.

Reference framing: the CUDA stacks reach for ring/context parallelism via NCCL
P2P; here the ring is `jax.lax.ppermute` over ICI — the collective the "How to
Scale Your Model" recipe prescribes for sequence parallelism.

Load balance: contiguous sharding leaves the causal ring imbalanced (the last
shard computes at every ring step while shard 0 computes once, and ppermute
synchronizes each step). ``sp_flash_prefill`` therefore defaults to ZIG-ZAG
partitioning — each device holds one chunk from each END of the sequence, so
causal work is ~equal per device per step — with the natural↔zig-zag
permutation handled inside the entry point (identical results either way,
oracle-tested).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, mask, m_prev, l_prev, acc_prev, scale):
    """One KV block's contribution under online softmax, GQA-native.

    q: [Sq, Hk, G, D] (query heads grouped under their KV head — head h of
    the flat [Sq, H] layout is (h // G, h % G) here); k/v: [Sk, Hk, D];
    mask: [Sq, Sk] (True = attend). Carries m (running max, [Sq, Hk, G]),
    l (running denom), acc ([Sq, Hk, G, D]). Keeping k/v at Hk heads is what
    the grouped layout buys: the ring's ppermute moves Hk-width KV blocks
    over ICI instead of H-width repeats (4x less wire traffic at llama
    shapes), while every query head still attends its group's KV.
    """
    s = jnp.einsum("qhgd,khd->qhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale  # [Sq, Hk, G, Sk]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))  # [Sq, Hk, G]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    alive = m_new > NEG_INF / 2
    p = jnp.exp(jnp.where(alive[..., None], s - m_new[..., None], NEG_INF))
    correction = jnp.exp(jnp.where(alive, m_prev - m_new, 0.0))
    l_new = l_prev * correction + p.sum(axis=-1)
    acc_new = acc_prev * correction[..., None] + jnp.einsum(
        "qhgk,khd->qhgd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def _guarded_attn(pred, q, k, v, mask, m, l, acc, scale):
    """Run the block attend only when ``pred`` (traced bool) says it can
    contribute; identity carry otherwise — masked-out blocks never touch the
    MXU."""
    return lax.cond(
        pred,
        lambda args: _block_attn(*args, scale),
        lambda args: (args[4], args[5], args[6]),
        (q, k, v, mask, m, l, acc),
    )


def ring_attention_sharded(q, k, v, *, axis_name: str, scale: float,
                           shard_index: Optional[jax.Array] = None,
                           zigzag: bool = False):
    """Exact causal attention for sequence-sharded q/k/v inside ``shard_map``.

    q: [S_local, H, D]; k, v: [S_local, Hk, D] with H a multiple of Hk (GQA;
    Hk == H is plain MHA) — this device's slice of the sequence. Contiguous
    layout: shard s holds positions s*S_local... Zig-zag layout
    (``zigzag=True``): shard s holds chunk s then chunk 2n-1-s (each C =
    S_local/2 rows) — the balanced schedule where every device runs exactly
    two C×C sub-attends per ring step (lo-key→hi-query always; plus lo→lo when
    src≤my or hi→hi when src≥my), instead of the contiguous ring's worst shard
    paying the full block at every step. Returns [S_local, H, D].
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name) if shard_index is None else shard_index
    S, H, D = q.shape
    Hk = k.shape[1]
    G = H // Hk  # flat head h lives at (h // G, h % G) in the grouped layout
    q = q.reshape(S, Hk, G, D)

    def step_contiguous(carry, i):
        kv, m, l, acc = carry
        kb, vb = kv
        src = (my - i) % n  # whose block we hold at ring step i
        # causality by GLOBAL position: queries attend keys at k_pos <= q_pos
        q_pos = my * S + jnp.arange(S)
        k_pos = src * S + jnp.arange(S)
        mask = k_pos[None, :] <= q_pos[:, None]
        # strictly-future blocks skip the einsums entirely: causal ring does
        # ~n²/2 useful block-attends and the rest must stay off the MXU
        m, l, acc = _guarded_attn(src <= my, q, kb, vb, mask, m, l, acc, scale)
        return _rotate(kv, kb, vb, m, l, acc, i)

    def step_zigzag(carry, i):
        kv, m, l, acc = carry
        kb, vb = kv
        src = (my - i) % n
        C = S // 2
        ar = jnp.arange(C)
        q_lo_pos, q_hi_pos = my * C + ar, (2 * n - 1 - my) * C + ar
        k_lo_pos, k_hi_pos = src * C + ar, (2 * n - 1 - src) * C + ar
        (q_lo, q_hi), (k_lo, k_hi), (v_lo, v_hi) = (
            (t[:C], t[C:]) for t in (q, kb, vb))
        m_lo, m_hi = m[:C], m[C:]
        l_lo, l_hi = l[:C], l[C:]
        a_lo, a_hi = acc[:C], acc[C:]
        # (k_lo → q_lo): same-or-older low chunk; triangular iff src == my
        m_lo, l_lo, a_lo = _guarded_attn(
            src <= my, q_lo, k_lo, v_lo,
            k_lo_pos[None, :] <= q_lo_pos[:, None], m_lo, l_lo, a_lo, scale)
        # (k_lo → q_hi): every low chunk precedes every high chunk — always on
        m_hi, l_hi, a_hi = _block_attn(
            q_hi, k_lo, v_lo, k_lo_pos[None, :] <= q_hi_pos[:, None],
            m_hi, l_hi, a_hi, scale)
        # (k_hi → q_hi): high chunks order REVERSES with shard id
        m_hi, l_hi, a_hi = _guarded_attn(
            src >= my, q_hi, k_hi, v_hi,
            k_hi_pos[None, :] <= q_hi_pos[:, None], m_hi, l_hi, a_hi, scale)
        # (k_hi → q_lo): strictly future for every pair — never computed
        m = jnp.concatenate([m_lo, m_hi])
        l = jnp.concatenate([l_lo, l_hi])
        acc = jnp.concatenate([a_lo, a_hi])
        return _rotate(kv, kb, vb, m, l, acc, i)
    def _rotate(kv, kb, vb, m, l, acc, i):
        # rotate KV around the ring: device d hands its block to d+1. The final
        # iteration's rotation would feed nothing — skip the collective (i is
        # uniform across devices, so every device takes the same branch).
        kv = lax.cond(
            i < n - 1,
            lambda t: jax.tree.map(
                lambda x: lax.ppermute(
                    x, axis_name, [(j, (j + 1) % n) for j in range(n)]), t),
            lambda t: t,
            (kb, vb),
        )
        return (kv, m, l, acc), None

    step = step_zigzag if zigzag else step_contiguous

    # the zero-init carries are device-invariant but the loop outputs vary
    # over the ring axis — shard_map's varying-axes check requires the carry
    # types to agree up front
    def _mark_varying(x):
        return lax.pcast(x, axis_name, to="varying")

    m0 = _mark_varying(jnp.full((S, Hk, G), NEG_INF, jnp.float32))
    l0 = _mark_varying(jnp.zeros((S, Hk, G), jnp.float32))
    acc0 = _mark_varying(jnp.zeros((S, Hk, G, D), jnp.float32))
    (kv, m, l, acc), _ = lax.scan(
        step, ((k, v), m0, l0, acc0), jnp.arange(n, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(S, H, D).astype(q.dtype)


def sp_flash_prefill(q, k, v, mesh, *, scale: Optional[float] = None,
                     axis_name: str = "sp", zigzag: bool = True):
    """Jittable entry: full-sequence q [S, H, D], k/v [S, Hk, D] (GQA when
    Hk < H) → causal attention [S, H, D], computed ring-parallel over
    ``mesh``'s ``axis_name`` axis. S must divide evenly by 2× the axis size
    (pad upstream — the engine's chunking already works in page multiples).

    ``zigzag=True`` (default) assigns each device one chunk from EACH END of
    the sequence (device d holds chunks d and 2n-1-d), so causal work is
    ~equal per device per ring step — the contiguous layout leaves the last
    shard computing at every step while shard 0 idles behind the ppermute
    barrier, ~2× the wall clock for identical results."""
    from jax.sharding import PartitionSpec as P

    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(axis_name, None, None)
    n = mesh.shape[axis_name]
    S = q.shape[0]

    use_zigzag = zigzag and n > 1 and S % (2 * n) == 0
    if zigzag and n > 1 and not use_zigzag:
        # zig-zag needs S divisible by 2n; contiguous only needs n. Degrade
        # loudly-enough (perf property, not correctness) rather than truncate.
        import warnings

        warnings.warn(f"ring attention: S={S} not divisible by 2*{n}; "
                      "using the contiguous (imbalanced) layout")
    if S % n != 0:
        raise ValueError(f"sequence length {S} must divide by the {axis_name} "
                         f"axis size {n} (pad upstream)")
    if use_zigzag:
        C = S // (2 * n)
        # device d's rows: chunk d then chunk 2n-1-d (natural→zigzag gather is
        # a GSPMD permute at prefill scale — negligible next to the S² attends)
        chunk_ids = jnp.stack(
            [jnp.arange(n), 2 * n - 1 - jnp.arange(n)], axis=1).reshape(-1)
        perm = (chunk_ids[:, None] * C + jnp.arange(C)[None, :]).reshape(-1)
        inv = jnp.argsort(perm)
    else:
        perm = inv = None

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def run(qs, ks, vs):
        return ring_attention_sharded(qs, ks, vs, axis_name=axis_name,
                                      scale=scale, zigzag=use_zigzag)

    if perm is None:
        return run(q, k, v)
    out = run(q[perm], k[perm], v[perm])
    return out[inv]


def make_ring_attn_impl(mesh, axis_name: str = "sp", zigzag: bool = True):
    """Uniform-signature attention impl (drop-in for the engine's
    ``attn_impl`` seam) that computes the step's attention ring-parallel over
    ``mesh``'s sp axis, from the chunk's own q/k/v instead of the paged cache.

    Valid ONLY for the self-contained prefill regime the engine gates host-side
    (`LLMEngine._step_unified`): a single fresh sequence packed at offset 0,
    positions 0..n-1, no prior KV — there, causality by row index equals
    causality by position, trailing pad rows attend nothing real (their keys
    sit strictly in every real query's future), and in-chunk q/k/v ARE the
    whole attention problem. KV still lands in the paged cache (write_kv runs
    before the attn call), so decode continues from the cache as usual.

    GQA-native: k/v ride the ring at their Hk head count (the grouped-head
    schedule in ``_block_attn``) — ppermute moves Hk-width KV blocks over
    ICI, not H-width repeats (4x less ring traffic at llama shapes).
    """

    def impl(q, layer_cache, page_tables, positions, seq_slots, kv_lens, *,
             scale, cu_q_lens=None, num_seqs=None, chunk_k=None, chunk_v=None):
        del layer_cache, page_tables, positions, seq_slots, kv_lens
        del cu_q_lens, num_seqs
        if chunk_k is None or chunk_v is None:
            raise ValueError("ring attn impl needs the chunk's raw k/v "
                             "(forward_core passes chunk_k/chunk_v)")
        return sp_flash_prefill(q, chunk_k, chunk_v, mesh, scale=scale,
                                axis_name=axis_name, zigzag=zigzag)

    return impl


def reference_causal_attention(q, k, v, scale: Optional[float] = None):
    """Dense causal attention (the correctness oracle for the ring path);
    GQA k/v are repeated up to the query head count here — the oracle pays
    the bandwidth the ring exists to avoid."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k.shape[1] != q.shape[1]:
        reps = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    S = q.shape[0]
    s = jnp.einsum("qhd,khd->qhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("qhk,khd->qhd", p, v.astype(jnp.float32)).astype(q.dtype)
