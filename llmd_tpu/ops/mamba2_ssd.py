"""The Mamba-2 recurrence (state-space duality form) over a ragged batch whose
rows keep a matrix state a head in a pool of slots, as one Pallas TPU kernel
and as the plain XLA form it is tested against (and that the CPU runs).

For row ``b`` of a call, tokens ``t = cu_q_lens[b] .. cu_q_lens[b + 1] - 1``
in order, head ``h`` of group ``g = h // (H / G)`` with state ``S`` [P, N]
float32 (P = head_dim channels, N = d_state) and ``A_h < 0``:

    S   <- exp(dt_t A_h) S + (dt_t x_t) B_t^T        (x_t [P], B_t = B[t, g] [N])
    y_t  = S C_t                                      (C_t = C[t, g] [N])

``S`` starts from the row's slot of ``pool`` unless the row is ``fresh`` (its
first position is 0: zeros) and is written back after the row's last token. A
row that is not ``live`` (padding, an idle seat, a row the fused decode call
has frozen) leaves its slot bit for bit and gives ``y = 0``. The skip ``D x``,
the gate and the norm are the mixer's (models/transformer.mamba2_mixer).

The pool holds a slot's state as ``[G, N, H / G * P]``: a group after the
other, d_state on the sublanes, the channels of the group's heads side by side
on the lanes (a head's matrix transposed; the Mamba-1 pool's layout a group,
ops/selective_scan). A token that comes alone then updates its state with
broadcasts and multiply-adds on whole vector registers and reads ``y`` as a
sum over sublanes: x and the decay are rows as they arrive, and B and C become
columns by one transposition a group. A group's block is one contiguous
piece of the pool.

The kernel runs a row in blocks of ``BLOCK`` tokens counted from the row's
first token. A block of one token (a decode row of either step program, or
the last token of a prompt of ``k BLOCK + 1``) takes the update above as it
stands, on the vector unit. A longer block takes the chunked form, its
products on the matrix unit, with ``c_i = sum_(j <= i) dt_j A`` kept as
logarithms so that nothing underflows:

    Y     = ((C B^T) * L) (dt * X) + diag(exp(c_i)) C S_prev,  L_ij = exp(c_i - c_j), i >= j
    S_new = exp(c_r) S_prev + sum_i exp(c_r - c_i) B_i (dt_i x_i)^T

``C B^T`` is one product a group, not a head. A block groups its sums by its
own boundaries, so a token's result depends on where its block starts; the
engine therefore starts a prompt's every chunk on a multiple of ``BLOCK``
(``engine.py``, the plan of a unified step), as for the lightning layers: a
prompt is cut into the same blocks however it is chunked and whatever else
the step holds, and greedy tokens served alone and in a batch do not part at
near ties. x, B and C arrive in the model's type (exact in bfloat16); ``dt``,
the cumulative sums and the state are float32, and a float32 operand of a
product goes to the matrix unit as the sum of bfloat16 pieces (two for a
value, three for the cumulative sum's addends, which stand in an exponent).

The kernel's name in a device trace is ``mamba2_ssd`` (the benchmark's
``mamba2_ssd_dev_share`` and both rooflines match on it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
BF16 = jnp.bfloat16
LANE = 128
# tokens of a block; what a prompt's chunks are aligned to. Of 16, 64 and 128
# on the chip (tools/mamba2_sweep.py, PERF.md section 6, PR 47) a 256-token
# chunk cost 125, 70 and 55 us a layer call; 128 would cut a prompt coarser
BLOCK = 64
_VMEM_LIMIT = 64 * 1024 * 1024


def mamba2_ssd_xla(x, dt, A, Bm, Cm, pool, slots, cu_q_lens, live, fresh):
    """The recurrence as a ``lax.scan`` over the call's flat tokens.

    x: [NT, H, P]; dt: [NT, H] float32 (after softplus); A: [H] float32
    (negative); Bm, Cm: [NT, G, N]; pool: [S, G, N, H / G * P]; slots: [B]
    int32 row of ``pool`` per batch row; cu_q_lens: [B + 1]; live, fresh: [B]
    bool. Returns (y [NT, H, P] float32, pool)."""
    nt, nh, p = x.shape
    g, n = Bm.shape[1:]
    nb, hb = slots.shape[0], nh // g
    s0 = pool[slots].astype(F32).reshape(nb, g, n, hb, p)
    s0 = jnp.where(fresh[:, None, None, None, None], 0.0, s0)
    t = jnp.arange(nt, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cu_q_lens[1:], t, side="right"), 0, nb - 1)
    ok = live[row] & (t < cu_q_lens[nb])
    dt = dt.astype(F32)
    decay = jnp.exp(dt * A.astype(F32)).reshape(nt, g, 1, hb, 1)
    dtx = (dt[:, :, None] * x.astype(F32)).reshape(nt, g, 1, hb, p)

    def step(s, inp):
        a_t, dtx_t, b_t, c_t, r, ok_t = inp
        sr = s[r]  # [G, N, hb, P]
        sn = a_t * sr + b_t[:, :, None, None] * dtx_t
        y = jnp.sum(sn * c_t[:, :, None, None], axis=1)  # [G, hb, P]
        return s.at[r].set(jnp.where(ok_t, sn, sr)), jnp.where(ok_t, y, 0.0)

    s, y = lax.scan(step, s0, (decay, dtx, Bm.astype(F32), Cm.astype(F32),
                               row, ok))
    idx = jnp.where(live, slots, pool.shape[0])
    return y.reshape(nt, nh, p), pool.at[idx].set(
        s.reshape((nb,) + pool.shape[1:]).astype(pool.dtype), mode="drop")


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=F32)


def _pieces(x, n: int):
    """Float32 ``x`` as ``n`` bfloat16 pieces whose sum holds 8 n bits of
    it: what a float32 operand goes to the matrix unit as."""
    out = []
    for _ in range(n):
        hi = x.astype(BF16)
        out.append(hi)
        x = x - hi.astype(F32)
    return out


def _dot22(a, b):
    """``a . b`` with both float32, each as two bfloat16 pieces; the product
    of the two low pieces (2^-16 of the result) is left out."""
    (ah, al), (bh, bl) = _pieces(a, 2), _pieces(b, 2)
    return _dot(ah, bh, _NN) + _dot(ah, bl, _NN) + _dot(al, bh, _NN)


_NT = ((1,), (1,))  # x . y^T
_NN = ((1,), (0,))  # x . y
_TN = ((0,), (0,))  # x^T . y


def _kernel(cu_ref, slots_ref, flags_ref, dtx_ref, da_ref, b_ref, c_ref,
            s_in_ref, y_ref, s_out_ref, s_scr, dtx_scr, da_scr, b_scr, c_scr,
            *, gb: int, hb: int, p: int, blk: int):
    del slots_ref  # read by the index maps
    b = pl.program_id(1)
    gw = hb * p  # a group's lanes
    w = min(LANE, gw)  # lanes worked on at once: whole heads
    hpt = w // p  # heads a lane tile
    n = s_scr.shape[1]
    # (group of the step, lanes of the group's state, the same lanes of the
    # token rows, the group's lanes of da and of B and C)
    tiles = [(gi, slice(k * w, (k + 1) * w),
              slice(gi * gw + k * w, gi * gw + (k + 1) * w), k)
             for gi in range(gb) for k in range(gw // w)]

    @pl.when(b == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    flag = flags_ref[b]
    live = (flag & 1) == 1

    @pl.when(live)
    def _():
        start, end = cu_ref[b], cu_ref[b + 1]
        fresh = (flag & 2) == 2
        rows = lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        i = lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        j = lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
        tril = (i >= j).astype(BF16)
        triu = (i <= j).astype(BF16)

        def by_head(k, col):
            """[rows, w]: lane l of a group's lane tile ``k`` holds
            ``col(h)`` [rows, 1] of its head ``h`` of the group."""
            out = col(k * hpt)
            for q in range(1, hpt):
                out = jnp.where(lane >= q * p, col(k * hpt + q), out)
            return out

        def one_token(t, load, store):
            # S <- a S + B (dt x)^T, y = S C on the vector unit, a lane tile
            # of a group's state at a time: ``load(gi, lanes)`` the state
            # before, ``store(gi, lanes, s)`` the state after. B and C are
            # wanted as columns against [n, w] tiles: the row in every
            # sublane, transposed, is the column in every lane
            dtx_scr[0:1, :] = dtx_ref[pl.ds(t, 1), :]
            da_scr[0:1, :] = da_ref[pl.ds(t, 1), :]
            b_scr[0:1, :] = b_ref[pl.ds(t, 1), :]
            c_scr[0:1, :] = c_ref[pl.ds(t, 1), :]
            ys, cols = [], {}
            for gi, lanes, at, k in tiles:
                if gi not in cols:
                    cols[gi] = [jnp.broadcast_to(
                        r[0:1, gi * n:(gi + 1) * n], (w, n)).T
                        for r in (b_scr, c_scr)]  # [n, w] each
                bcol, ccol = cols[gi]
                a = jnp.exp(by_head(k, lambda h: da_scr[
                    0:1, gi * LANE + h:gi * LANE + h + 1]))
                s = a * load(gi, lanes) + bcol * dtx_scr[0:1, at]
                store(gi, lanes, s)
                ys.append(jnp.sum(s * ccol, axis=0, keepdims=True))
            y_ref[pl.ds(t, 1), :] = jnp.concatenate(ys, axis=1)

        def held(gi, lanes):
            return s_scr[gi, :, lanes]

        def hold(gi, lanes, s):
            s_scr[gi, :, lanes] = s

        def block(m, carry):
            t0 = start + m * blk
            r = jnp.minimum(blk, end - t0)

            @pl.when(r == 1)
            def _():
                one_token(t0, held, hold)

            @pl.when(r > 1)
            def _():
                valid = rows < r
                # a block starts wherever its row does in the flat batch, and
                # a load of several rows wants a start on a sublane tile: the
                # rows are brought one by one (the rows past r are the next
                # row's, or the padding's; they are masked)
                for k in range(blk):
                    dtx_scr[k:k + 1, :] = dtx_ref[pl.ds(t0 + k, 1), :]
                    da_scr[k:k + 1, :] = da_ref[pl.ds(t0 + k, 1), :]
                    b_scr[k:k + 1, :] = b_ref[pl.ds(t0 + k, 1), :]
                    c_scr[k:k + 1, :] = c_ref[pl.ds(t0 + k, 1), :]
                ys, grp = [], {}
                for gi, lanes, at, k in tiles:
                    if gi not in grp:
                        of = slice(gi * n, (gi + 1) * n)
                        bb = jnp.where(valid, b_scr[:, of], 0.0).astype(BF16)
                        cb = c_scr[:, of].astype(BF16)
                        # c_i = sum_(j <= i) dt_j A, a head a lane, as a
                        # column [blk, LANE] and as a row [LANE, blk]; past
                        # r it stands still
                        da3 = _pieces(jnp.where(
                            valid, da_scr[:, gi * LANE:(gi + 1) * LANE], 0.0),
                            3)
                        grp[gi] = (bb, cb,
                                   sum(_dot(tril, x, _NN) for x in da3),
                                   sum(_dot(x, triu, _TN) for x in da3),
                                   _dot(cb, bb, _NT))  # C B^T: one a group
                    bb, cb, cs, cst, g = grp[gi]
                    st = s_scr[gi, :, lanes]  # [n, w] float32
                    dtx = jnp.where(valid, dtx_scr[:, at], 0.0)
                    y = sum(_dot(cb, x, _NN) for x in _pieces(st, 2)) \
                        * jnp.exp(by_head(k, lambda h: cs[:, h:h + 1]))
                    for q in range(hpt):
                        h = k * hpt + q
                        ell = jnp.where(i >= j, jnp.exp(
                            cs[:, h:h + 1] - cst[h:h + 1, :]), 0.0)
                        mine = (lane >= q * p) & (lane < (q + 1) * p)
                        y = y + _dot22(g * ell, jnp.where(mine, dtx, 0.0))
                    last = by_head(k, lambda h: cs[blk - 1:blk, h:h + 1])
                    kw = dtx * jnp.exp(last - by_head(
                        k, lambda h: cs[:, h:h + 1]))
                    s_scr[gi, :, lanes] = jnp.exp(last) * st + sum(
                        _dot(bb, x, _TN) for x in _pieces(kw, 2))
                    ys.append(y)
                yb = jnp.concatenate(ys, axis=1)
                for k in range(blk):
                    @pl.when(k < r)
                    def _(k=k):
                        y_ref[pl.ds(t0 + k, 1), :] = yb[k:k + 1, :]

            return carry

        @pl.when(end - start == 1)
        def _():  # a decode row: from the slot's block to it, no scratch
            def before(gi, lanes):
                return jnp.where(fresh, 0.0,
                                 s_in_ref[0, gi, :, lanes].astype(F32))

            def after(gi, lanes, s):
                s_out_ref[0, gi, :, lanes] = s.astype(s_out_ref.dtype)

            one_token(start, before, after)

        @pl.when(end - start > 1)
        def _():
            s_scr[...] = jnp.where(fresh, 0.0, s_in_ref[0].astype(F32))
            lax.fori_loop(0, (end - start + blk - 1) // blk, block, 0)
            s_out_ref[0] = s_scr[...].astype(s_out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out_ref[0] = s_in_ref[0]


# groups of a slot's state a grid step holds (1, 2, 8 on the chip: a decode-
# shaped call of 64 rows 504, 462, 460 us)
GROUPS_A_STEP = 2


@functools.partial(jax.jit, static_argnames=("interpret", "block", "groups"))
def mamba2_ssd_pallas(x, dt, A, Bm, Cm, pool, slots, cu_q_lens, live, fresh,
                      *, interpret: bool = False, block: int = BLOCK,
                      groups: int = GROUPS_A_STEP):
    """``mamba2_ssd_xla`` as one Pallas call, ``pool`` updated in place.

    The grid is (groups of B and C, ``groups`` a step; row), rows innermost:
    the groups' ``dt x``, B, C and y stay in VMEM while the rows pass, and
    each row's state block ``[groups, N, H / G * P]`` comes from and goes
    back to its slot through the block specs' index maps (scalar-prefetched
    ``slots``), double-buffered by the pipeline; the state is carried from
    block to block of a row in a VMEM scratch. Rows that are not live copy
    their block through, so rows that share a slot nothing reads (the
    packer's scratch slot for padding rows) are harmless, and consecutive
    ones cost one fetch."""
    nt, nh, p = x.shape
    g, n = Bm.shape[1:]
    nb = slots.shape[0]
    hb = nh // g
    gb = groups if g % groups == 0 else 1
    assert hb <= LANE and (hb * p) % min(LANE, hb * p) == 0, (nh, g, p)
    assert pool.shape[1:] == (g, n, hb * p), (pool.shape, g, n, hb * p)
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    dt = dt.astype(F32)
    # a row's last block reads and writes up to block - 1 rows past its end
    rows = lambda a: jnp.pad(a, ((0, block), (0, 0)))  # noqa: E731
    dtx = rows((dt[:, :, None] * x.astype(F32)).reshape(nt, nh * p))
    da = rows(jnp.pad((dt * A.astype(F32)).reshape(nt, g, hb),
                      ((0, 0), (0, 0), (0, LANE - hb))).reshape(nt, g * LANE))
    flat = lambda m: rows(m.astype(F32).reshape(nt, g * n))  # noqa: E731
    tok = lambda c, b, *_: (0, c)  # noqa: E731
    state = lambda c, b, cu, sl, fl: (sl[b], c, 0, 0)  # noqa: E731
    y, pool = pl.pallas_call(
        functools.partial(_kernel, gb=gb, hb=hb, p=p, blk=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(g // gb, nb),
            in_specs=[
                pl.BlockSpec((nt + block, gb * hb * p), tok),
                pl.BlockSpec((nt + block, gb * LANE), tok),
                pl.BlockSpec((nt + block, gb * n), tok),
                pl.BlockSpec((nt + block, gb * n), tok),
                pl.BlockSpec((1, gb, n, hb * p), state),
            ],
            out_specs=[
                pl.BlockSpec((nt + block, gb * hb * p), tok),
                pl.BlockSpec((1, gb, n, hb * p), state),
            ],
            scratch_shapes=[pltpu.VMEM((gb, n, hb * p), F32),
                            pltpu.VMEM((block, gb * hb * p), F32),
                            pltpu.VMEM((block, gb * LANE), F32),
                            pltpu.VMEM((block, gb * n), F32),
                            pltpu.VMEM((block, gb * n), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((nt + block, nh * p), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},  # the pool, after 3 prefetched + 4 inputs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mamba2_ssd",
    )(cu_q_lens.astype(jnp.int32), slots.astype(jnp.int32), flags,
      dtx, da, flat(Bm), flat(Cm), pool)
    return y[:nt].reshape(nt, nh, p), pool


def make_mamba2_ssd(impl: str, interpret: bool = False):
    """The recurrence a forward pass is given: ``"pallas"`` or ``"xla"``."""
    if impl == "pallas":
        return functools.partial(mamba2_ssd_pallas, interpret=interpret)
    if impl == "xla":
        return mamba2_ssd_xla
    raise ValueError(f"unknown mamba2-ssd impl {impl!r}")
