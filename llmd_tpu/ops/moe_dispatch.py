"""Token-sorted, drop-free MoE dispatch — the TPU answer to DeepEP's
low-latency all-to-all (SURVEY §2.4/§3.3, wide-ep decode.yaml
`--enable-dbo` / NVSHMEM buffers; ROADMAP item 1).

The legacy path in ``models.transformer.moe_block`` materialises dense
one-hot dispatch/combine tensors of shape ``[T, S, C]`` and pays
O(T·S·C·D) in the two routing einsums — at decode shapes that dwarfs the
expert GEMMs themselves, and any token routed past capacity ``C`` is
silently dropped. This module replaces it:

* argsort the flat ``(token, k)`` assignments by physical slot id
  (EPLB's replica choice already happened upstream, so the sort key IS
  the load-balanced placement),
* scatter activations into a block-aligned buffer whose per-slot
  segments start at multiples of the GEMM block size ``bc`` — static
  shapes, data-dependent fill, zero drops,
* run experts as a ragged grouped GEMM over the blocks (Pallas on TPU,
  gathered batched einsum on CPU/int8),
* combine by the inverse permutation, weighted by router probs.

Single device / ``ep == 1``: pure gather/scatter by sorted index, no
collective. ``ep > 1``: bounded per-rank buckets exchanged with
``lax.all_to_all`` inside ``shard_map`` — each EP rank owns a static
``1/ep`` slice of the token range, sends every routed copy to the rank
owning its slot (capacity = all of a rank's copies, so nothing can
drop), computes local experts token-sorted, and returns results over the
same buckets.

DBO: callers split the batch in half and invoke this path per half; the
two halves share no intermediate values, so half A's all-to-all is
data-independent of half B's expert GEMMs and XLA's scheduler may
overlap them. Each stage runs under a ``jax.named_scope`` of its part of
the model (``moe_dispatch``, ``moe_experts``, ``moe_combine``:
``models/parts.py``) and is callable standalone
(``tests/test_moe_dispatch.py``). A device trace's events do not carry the
scope; the compiled program's text does, and the engine publishes which
instruction belongs to which part (``obs/program_parts.py``), which is where
the stages' split of device time is read.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .grouped_gemm import ragged_grouped_gemm


def pick_block_size(tokens_k: int, slots: int, pallas: bool) -> int:
    """GEMM block rows: about one slot's expected share, power of two.

    The padded buffer is ``Tk + S*bc`` rows, so small ``bc`` keeps the
    drop-free layout near-dense at decode shapes (Tk ~ S) while prefill
    (Tk >> S) gets MXU-sized blocks. Pallas tiles need >= 8 sublanes.
    """
    bc = 1
    while bc * slots < tokens_k and bc < 128:
        bc *= 2
    return max(8, bc) if pallas else bc


def plan_blocks(copies: int, slots: int, bc: int) -> int:
    """Blocks of ``bc`` rows in the padded buffer of ``copies`` routed copies
    over ``slots`` expert slots: the worst case (every slot's last block
    part-empty), so the shape is static."""
    return (copies + bc - 1) // bc + slots


def _row_plan(slot: jax.Array, S: int, bc: int, sources: bool = False):
    """Static-shape placement of N routed copies into a block-aligned
    buffer. ``slot`` is [N] int32 in [0, S]; S is the padding sentinel.

    Returns (row [N], block_slot [nb], block_rows [nb], Tp): ``row[i]``
    is entry i's row in the padded buffer (== Tp for sentinels, which a
    mode="drop" scatter discards); block b holds rows of expert slot
    ``block_slot[b]`` with ``block_rows[b]`` of them real. With ``sources``
    a fifth result, ``src`` [Tp]: the entry each row of the padded buffer
    holds (N where it holds none), the inverse of ``row``.
    """
    N = slot.shape[0]
    order = jnp.argsort(slot, stable=True)
    ss = slot[order]
    cnt = jnp.zeros((S + 1,), jnp.int32).at[slot].add(1)[:S]
    cnt_pad = ((cnt + bc - 1) // bc) * bc
    starts = jnp.cumsum(cnt) - cnt            # raw sorted-order starts
    starts_pad = jnp.cumsum(cnt_pad) - cnt_pad  # block-aligned starts
    Tp = plan_blocks(N, S, bc) * bc           # worst-case padding, static
    sc = jnp.minimum(ss, S - 1)
    pos_in_slot = jnp.arange(N, dtype=jnp.int32) - starts[sc]
    row_sorted = jnp.where(ss < S, starts_pad[sc] + pos_in_slot, Tp)
    row = jnp.zeros((N,), jnp.int32).at[order].set(row_sorted)
    nb = Tp // bc
    bstart = jnp.arange(nb, dtype=jnp.int32) * bc
    # segments are bc-aligned, so each block belongs to exactly one slot:
    # the last one whose padded start is <= the block start
    block_slot = jnp.clip(
        jnp.searchsorted(starts_pad, bstart, side="right").astype(jnp.int32) - 1,
        0, S - 1)
    block_rows = jnp.clip(starts_pad[block_slot] + cnt[block_slot] - bstart,
                          0, bc)
    if not sources:
        return row, block_slot, block_rows, Tp
    # block b's rows hold the entries that follow, in sorted order, the
    # ones its slot's earlier blocks hold
    j = jnp.arange(bc, dtype=jnp.int32)
    first = starts[block_slot] + bstart - starts_pad[block_slot]
    at = jnp.clip(first[:, None] + j[None, :], 0, N - 1)
    src = jnp.where(j[None, :] < block_rows[:, None], order[at], N)
    return row, block_slot, block_rows, Tp, src.reshape(Tp)


def expert_hidden(gate_up, act, gated: bool):
    """What an expert's second product reads: ``act(gate) * up`` of the
    first product's halves, or, without a gate, ``act`` of all of it."""
    if not gated:
        return act(gate_up)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return act(gate) * up


def _experts_xla(xb, block_slot, block_rows, wi, wo, wi_scale, wo_scale,
                 act=jax.nn.silu, gated: bool = True):
    """Gathered batched-einsum expert MLP over [nb, bc, D] blocks — the
    CPU / int8 backend. Dead rows are zero in ``xb`` and act(0)*0 == 0 for
    the model's gate activation ``act`` (silu, relu; without a gate, relu^2
    of 0 is 0 too), so no masking is needed; per-slot int8 scales gather
    with the bank."""
    dt = xb.dtype
    gate_up = jnp.einsum("bcd,bdf->bcf", xb, wi[block_slot].astype(dt))
    if wi_scale is not None:
        gate_up = gate_up * wi_scale[block_slot][:, None, :].astype(dt)
    ye = jnp.einsum("bcf,bfd->bcd", expert_hidden(gate_up, act, gated),
                    wo[block_slot].astype(dt))
    if wo_scale is not None:
        ye = ye * wo_scale[block_slot][:, None, :].astype(dt)
    return ye


def _experts_pallas(xb, block_slot, block_rows, wi, wo, wi_scale, wo_scale,
                    interpret, act=jax.nn.silu, gated: bool = True):
    """Pallas ragged grouped GEMM backend (bf16 banks; int8 stays on the
    XLA path, mirroring the engine's einsum-path policy)."""
    gate_up = ragged_grouped_gemm(xb, wi, block_slot, block_rows,
                                  interpret=interpret)
    ye = ragged_grouped_gemm(expert_hidden(gate_up, act, gated), wo, block_slot,
                             block_rows, interpret=interpret)
    return ye


# --------------------------------------------------------------------------
# Stage functions (standalone so the engine phase probe can time each)
# --------------------------------------------------------------------------


def dispatch_stage(x, idx, topw, valid, S: int, bc: int,
                   gather_rows: bool = False):
    """Sort + scatter: flat (token, k) copies into the block buffer.

    ``gather_rows``: the buffer is gathered, each of its rows from the copy
    `_row_plan` says it holds, and no scatter is made; the same buffer bit
    for bit. XLA compiles the scatter of 384 rows as a sort of their
    indices, a gather and a scatter in that order, and in the fused decode
    call of a stack of Mamba-2, attention and expert layers that scatter did
    not return from the chip when idle seats led the live ones (PR 47,
    PERF.md section 6: the same indices with the live rows leading ran, the
    dropped copies sent in bounds did not, the gathered buffer did)."""
    T, D = x.shape
    k = idx.shape[1]
    slot = jnp.where(valid > 0, idx, S).reshape(T * k)
    row, block_slot, block_rows, Tp, *src = _row_plan(slot, S, bc, gather_rows)
    tok = (jnp.arange(T * k, dtype=jnp.int32) // k)
    if gather_rows:
        of = jnp.minimum(src[0], T * k - 1) // k  # the token a row holds
        xs = jnp.where((src[0] < T * k)[:, None], x[of], 0).astype(x.dtype)
    else:
        xs = jnp.zeros((Tp, D), x.dtype).at[row].set(x[tok], mode="drop")
    wf = jnp.where(slot < S, topw.reshape(T * k), 0).astype(x.dtype)
    return xs, row, tok, wf, block_slot, block_rows


def experts_stage(xs, block_slot, block_rows, wi, wo, wi_scale=None,
                  wo_scale=None, *, use_pallas: bool = False,
                  interpret: bool = False, act=jax.nn.silu,
                  gated: bool = True):
    """Per-block expert MLP on the sorted buffer: [Tp, D] -> [Tp, D];
    ``act`` is the gate's activation, or with ``gated`` false the activation
    between an expert's two products (``wi`` [S, D, F])."""
    Tp, D = xs.shape
    bc = Tp // block_slot.shape[0]
    xb = xs.reshape(-1, bc, D)
    if use_pallas and wi_scale is None:
        ye = _experts_pallas(xb, block_slot, block_rows, wi, wo, wi_scale,
                             wo_scale, interpret, act, gated)
    else:
        ye = _experts_xla(xb, block_slot, block_rows, wi, wo, wi_scale,
                          wo_scale, act, gated)
    return ye.reshape(Tp, D)


def combine_stage(ye, row, tok, wf, T: int):
    """Inverse permutation + router-prob weighting back to [T, D]."""
    Tp, D = ye.shape
    g = ye[jnp.minimum(row, Tp - 1)]
    return jnp.zeros((T, D), ye.dtype).at[tok].add(g * wf[:, None])


def combine_in_order(ye, row, wf, T: int):
    """``combine_stage`` with a token's copies added in the order of its
    choice, in float32, as elementwise adds: the copies of token t are rows
    ``t*k .. t*k + k - 1`` of the flat (token, k) order, so the sum is a
    reshape and k - 1 adds, and a token's result depends on nothing beside
    it. ``combine_stage``'s scatter-add rounds to the model's type after
    every add and applies a token's updates in an order that follows the
    array's size: on the chip a decode row's sum through a 64-row call and
    through a 256-row call parted by a bf16 step (PR 39: `cold_equals_cached`
    read false), where the expert GEMMs' rows were equal bit for bit."""
    Tp, D = ye.shape
    g = (ye[jnp.minimum(row, Tp - 1)] * wf[:, None]).reshape(T, -1, D)
    acc = g[:, 0].astype(jnp.float32)
    for j in range(1, g.shape[1]):
        acc = acc + g[:, j].astype(jnp.float32)
    return acc.astype(ye.dtype)


def sorted_moe_local(x, idx, topw, valid, wi, wo, wi_scale=None,
                     wo_scale=None, *, use_pallas: bool = False,
                     interpret: bool = False,
                     bc: Optional[int] = None, act=jax.nn.silu,
                     slot_offset=None, num_slots: Optional[int] = None,
                     ordered_combine: bool = False, gated: bool = True,
                     gather_rows: bool = False):
    """Single-shard token-sorted MoE: gather/scatter only, no collective.

    ``slot_offset`` (a traced scalar) with ``num_slots``: ``wi``/``wo`` (and
    their scales) hold every layer's bank along the slot axis, ``[L*S, ...]``,
    and this layer's ``num_slots`` slots begin at ``slot_offset``. The expert
    GEMMs index the bank by a block's slot, so they read the layer's experts
    out of the whole stack, where a layer's bank sliced out of it first is a
    copy of the bank every step (as long as the GEMMs themselves on the
    chip). ``ordered_combine``: `combine_in_order` in `combine_stage`'s
    place. ``gather_rows``: `dispatch_stage`'s."""
    T, D = x.shape
    S = num_slots or wi.shape[0]
    if bc is None:
        bc = pick_block_size(T * idx.shape[1], S, use_pallas and wi_scale is None)
    with jax.named_scope("moe_dispatch"):
        xs, row, tok, wf, block_slot, block_rows = dispatch_stage(
            x, idx, topw, valid, S, bc, gather_rows)
        if slot_offset is not None:
            block_slot = block_slot + slot_offset
    with jax.named_scope("moe_experts"):
        ye = experts_stage(xs, block_slot, block_rows, wi, wo, wi_scale,
                           wo_scale, use_pallas=use_pallas, interpret=interpret,
                           act=act, gated=gated)
    with jax.named_scope("moe_combine"):
        if ordered_combine:
            return combine_in_order(ye, row, wf, T)
        return combine_stage(ye, row, tok, wf, T)


# --------------------------------------------------------------------------
# Wide-EP path: bounded per-rank buckets over lax.all_to_all in shard_map
# --------------------------------------------------------------------------


def _sorted_rows(xr, lslot, Sl, bc, wi_l, wo_l, wis_l, wos_l, use_pallas,
                 interpret, act):
    """Receiver-side expert compute: rows already expanded per copy, one
    local slot id each. Output row i corresponds to input row i."""
    n, D = xr.shape
    row, block_slot, block_rows, Tp = _row_plan(lslot, Sl, bc)
    xs = jnp.zeros((Tp, D), xr.dtype).at[row].set(xr, mode="drop")
    ye = experts_stage(xs, block_slot, block_rows, wi_l, wo_l, wis_l, wos_l,
                       use_pallas=use_pallas, interpret=interpret, act=act)
    return ye[jnp.minimum(row, Tp - 1)]


def _ep_moe_body(xl, idxl, wl, vl, wi_l, wo_l, wis_l, wos_l, *, ep: int,
                 S: int, k: int, use_pallas: bool, interpret,
                 act=jax.nn.silu):
    """Per-device body under shard_map. ``xl`` is this (dp, sp) cell's
    token shard (replicated across ep/tp); ``wi_l`` holds the ``S/ep``
    expert slots this EP rank owns.

    DeepEP-analog exchange: rank r owns the r-th static 1/ep slice of the
    token range. Every routed copy of an owned token is bucketed by the
    rank owning its slot (bucket capacity = ALL of a rank's copies, so the
    exchange is drop-free by construction), shipped with one
    ``all_to_all``, computed token-sorted on the owner, and shipped back
    over the same buckets. Weighting/combine stay at the origin rank.
    """
    tl, D = xl.shape
    Sl = wi_l.shape[0]
    r = lax.axis_index("ep")
    if ep == 1:
        return sorted_moe_local(xl, idxl, wl, vl, wi_l, wo_l, wis_l, wos_l,
                                use_pallas=use_pallas, interpret=interpret,
                                act=act)
    tpc = tl // ep  # caller pads: tl % ep == 0
    with jax.named_scope("moe_dispatch"):
        x_o = lax.dynamic_slice_in_dim(xl, r * tpc, tpc, 0)
        idx_o = lax.dynamic_slice_in_dim(idxl, r * tpc, tpc, 0)
        w_o = lax.dynamic_slice_in_dim(wl, r * tpc, tpc, 0)
        v_o = lax.dynamic_slice_in_dim(vl, r * tpc, tpc, 0)
        n = tpc * k
        cap = n  # bounded bucket: worst case all copies target one rank
        slot = jnp.where(v_o > 0, idx_o, S).reshape(n)
        dest = jnp.where(slot < S, slot // Sl, ep)  # sentinel: not sent
        order = jnp.argsort(dest, stable=True)
        dsort = dest[order]
        dcnt = jnp.zeros((ep + 1,), jnp.int32).at[dest].add(1)[:ep]
        dstart = jnp.cumsum(dcnt) - dcnt
        pos = jnp.arange(n, dtype=jnp.int32) - dstart[jnp.minimum(dsort, ep - 1)]
        sendrow = jnp.where(dsort < ep, dsort * cap + pos, ep * cap)
        entry_tok = (order // k).astype(jnp.int32)
        send_x = jnp.zeros((ep * cap, D), xl.dtype).at[sendrow].set(
            x_o[entry_tok], mode="drop").reshape(ep, cap, D)
        send_slot = jnp.full((ep * cap,), -1, jnp.int32).at[sendrow].set(
            slot[order], mode="drop").reshape(ep, cap)
        recv_x = lax.all_to_all(send_x, "ep", 0, 0, tiled=True)
        recv_slot = lax.all_to_all(send_slot, "ep", 0, 0, tiled=True)
    with jax.named_scope("moe_experts"):
        rs = recv_slot.reshape(ep * cap)
        lslot = jnp.where(rs >= 0, rs - r * Sl, Sl)  # -1 pad -> sentinel
        bc = pick_block_size(ep * cap, Sl, use_pallas and wis_l is None)
        ye = _sorted_rows(recv_x.reshape(ep * cap, D), lslot, Sl, bc,
                          wi_l, wo_l, wis_l, wos_l, use_pallas, interpret,
                          act)
    with jax.named_scope("moe_combine"):
        back = lax.all_to_all(ye.reshape(ep, cap, D), "ep", 0, 0, tiled=True)
        outrow = back.reshape(ep * cap, D)
        g = outrow[jnp.minimum(sendrow, ep * cap - 1)]
        wf = (w_o.reshape(n)[order]
              * (dsort < ep).astype(xl.dtype)).astype(xl.dtype)
        y_o = jnp.zeros((tpc, D), xl.dtype).at[entry_tok].add(g * wf[:, None])
        return lax.all_gather(y_o, "ep", axis=0, tiled=True)  # [tl, D]


def make_sorted_dispatch(mesh=None, *, use_pallas: bool = False,
                         interpret: bool = False):
    """Build a ``moe_block`` dispatch_impl closure.

    ``impl(x, idx, topw, valid, wi, wo, wi_scale, wo_scale, act=silu) ->
    y`` (``act``: the gate's activation, which ``moe_block`` passes from the
    model's config): the
    router / top-k / EPLB replica choice happened upstream (shared with
    the einsum path, so routing decisions are identical by construction);
    this only moves tokens, runs experts, and combines. With a mesh the
    body runs under shard_map over the full mesh — tokens split over
    (dp, sp), expert slots over ep (tp is gathered: wide-EP keeps expert
    banks EP-pure, matching the reference deployment) — and pads the
    token dim so every axis divides.
    """
    if mesh is None:
        def impl(x, idx, topw, valid, wi, wo, wi_scale=None, wo_scale=None,
                 act=jax.nn.silu, slot_offset=None, num_slots=None,
                 ordered_combine=False, gated=True, gather_rows=False):
            return sorted_moe_local(x, idx, topw, valid, wi, wo, wi_scale,
                                    wo_scale, use_pallas=use_pallas,
                                    interpret=interpret, act=act,
                                    slot_offset=slot_offset,
                                    num_slots=num_slots,
                                    ordered_combine=ordered_combine,
                                    gated=gated, gather_rows=gather_rows)
        # forward_core may hand this impl the whole stack of banks and a
        # layer's offset (sorted_moe_local); the mesh impl below shards one
        # layer's bank over ep and takes it sliced
        impl.stacked_banks = True
        impl.ordered_combine = True  # takes the argument
        return impl

    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    dpsp = shape.get("dp", 1) * shape.get("sp", 1)
    ep = shape.get("ep", 1)

    def impl(x, idx, topw, valid, wi, wo, wi_scale=None, wo_scale=None,
             act=jax.nn.silu):
        T, D = x.shape
        k = idx.shape[1]
        S = wi.shape[0]
        mult = dpsp * ep
        Tp = ((T + mult - 1) // mult) * mult
        if Tp != T:
            pad = ((0, Tp - T),)
            x = jnp.pad(x, pad + ((0, 0),))
            idx = jnp.pad(idx, pad + ((0, 0),))
            topw = jnp.pad(topw, pad + ((0, 0),))
            valid = jnp.pad(valid, pad + ((0, 0),))  # pad rows invalid

        def body(xl, idxl, wl, vl, wi_l, wo_l, *scales):
            wis_l = scales[0] if wi_scale is not None else None
            wos_l = scales[1] if wi_scale is not None else None
            return _ep_moe_body(xl, idxl, wl, vl, wi_l, wo_l, wis_l, wos_l,
                                ep=ep, S=S, k=k, use_pallas=use_pallas,
                                interpret=interpret, act=act)

        tok = P(("dp", "sp"), None)
        in_specs = [tok, tok, tok, tok,
                    P("ep", None, None), P("ep", None, None)]
        args = [x, idx, topw, valid, wi, wo]
        if wi_scale is not None:
            in_specs += [P("ep", None), P("ep", None)]
            args += [wi_scale, wo_scale]
        y = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                          out_specs=tok, check_vma=False)(*args)
        return y[:T]

    return impl
