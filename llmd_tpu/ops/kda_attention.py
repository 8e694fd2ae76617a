"""Delta-rule linear attention with a decay a channel (KDA, Kimi Linear,
arXiv:2510.26692) over a ragged batch whose rows keep a matrix state a head in
a pool of slots, as one Pallas TPU kernel and as the plain XLA form it is
tested against (and that the CPU runs).

For row ``r`` of a call, tokens ``t = cu_q_lens[r] .. cu_q_lens[r + 1] - 1`` in
order, head ``h`` with state ``S`` [D, D] float32, log-decay ``g_t`` [D] (< 0,
a channel of the key), write strength ``b_t`` (a scalar in (0, 1)):

    S   <- (I - b_t k_t k_t^T) Diag(exp g_t) S + b_t k_t v_t^T
    o_t  = S^T q_t

``S`` starts from the row's slot of ``pool`` unless the row is ``fresh`` (its
first position is 0: zeros) and is written back after the row's last token. A
row that is not ``live`` (padding, an idle seat, a row the fused decode call
has frozen) leaves its slot bit for bit and gives ``o = 0``. The pool holds a
head's state TRANSPOSED, ``pool[slot, h] = S^T`` [value lane, key lane]: the
decay, k and q then broadcast along a tile's rows, and neither form ever
turns a vector from lanes to sublanes.

The kernel runs a row in blocks of ``BLOCK`` tokens counted from the row's
first token (the chunked WY / UT form). With ``G_i = sum_{j <= i} g_j`` inside
a block, ``K~ = exp(G) * K``, ``K^ = exp(-G) * K``, ``Q~ = exp(G) * Q``:

    T     = (I + StrictTril((b * K~) K^^T))^-1                      [B, B]
    U     = T (b * V - (b * K~) S_prev)                             [B, D]
    O     = Q~ S_prev + Tril(Q~ K^^T) U
    S_new = Diag(exp G_B) S_prev + (exp(G_B - G) * K)^T U

``T`` is the Neumann product ``(I + N)(I + N^2)(I + N^4)(I + N^8)``, ``N`` the
negated strictly lower triangle, which is nilpotent at 16 rows. ``exp(-G)``
is what the bound on the gate is for: a channel's log-decay is above -5 a
token (``ModelConfig.kda_gate_lower_bound``, refused below that), so ``BLOCK``
tokens sum above -80 and ``exp(80)`` is a float32.

A block of ONE token (every decode row of either step program, and a chunk's
last token where it stands alone) is the recurrence itself, on the vector
unit, in one pass over the head's tile ``S^T`` [value lane e, key lane d]
(16 registers of 8 x 128 at heads of 128) and with no matrix product: at 8 d^2
operations beside 128 KB of state a head it is bound by the state's bytes,
and two products a head padded to eight rows were not (tools/kda_sweep.py):

    sp      = S^T * a                     a = exp(g), along every row
    u[e]    = b v[e] - sum_d sp[e, d] (b k)[d]
    S^T    <- sp + u[:, None] * k[None, :]
    o[e]    = sum_d S^T[e, d] q[d]

``a``, ``b k``, ``k`` and ``q`` lie along lanes as they arrive and broadcast
along the tile's rows; the two sums are lane reductions, whose results come
back along every lane, so ``u`` needs no broadcast. Only ``v`` and ``o`` are
columns of the tile (one value a row of it): a grid step's heads' ``b v`` are
turned once a token ([heads, d] -> [d, heads], one small transpose) and head
``h``'s column is added into lane ``h`` of the tile of products before its
reduction, so that ``u`` comes out of the sum whole; the heads' ``o`` columns
are gathered in a tile lane by lane and turned back the same way.

A block groups its sums by its own boundaries, so a token's result depends on
where its block starts. The engine therefore starts a prompt's every chunk on
a multiple of ``BLOCK`` (``engine.py``, the plan of a unified step), as for
the lightning layers. q, k and v arrive in the model's type, g and b in
float32; every product runs in float32 (a block of several tokens on the
matrix unit at the highest precision, a block of one in the vector unit's
float32 lanes) and the state is float32 between blocks and in the pool
(``ModelConfig.lightning_state_dtype``, rounded to it once a call).

The kernel's name in a device trace is ``kda_attention`` (the benchmark's
``kda_attention_dev_share`` and both rooflines match on it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens of a block, which a prompt's chunks are aligned to: the lightning
# layers' (the engine has one rule for a matrix state's blocks)
from llmd_tpu.ops.lightning_attention import BLOCK

F32 = jnp.float32
assert BLOCK == 16, "the Neumann product below is written out for 16 rows"
_VMEM_LIMIT = 64 * 1024 * 1024
_HI = lax.Precision.HIGHEST


def kda_attention_xla(q, k, v, g, b, pool, slots, cu_q_lens, live, fresh):
    """The recurrence as a ``lax.scan`` over the call's flat tokens.

    q, k, v: [NT, H, D]; g: [NT, H, D] float32 log-decay; b: [NT, H] float32;
    pool: [S, H, D, D] (a head's state transposed); slots: [B] int32 row of
    ``pool`` per batch row; cu_q_lens: [B + 1]; live, fresh: [B] bool.
    Returns (o [NT, H, D] float32, pool)."""
    nt = q.shape[0]
    nb = slots.shape[0]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[slots].astype(F32))
    t = jnp.arange(nt, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cu_q_lens[1:], t, side="right"), 0, nb - 1)
    ok = live[row] & (t < cu_q_lens[nb])

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t, r, ok_t = inp
        sr = s[r]  # [H, E, D]: value lane by key lane
        sp = sr * jnp.exp(g_t)[:, None, :]
        ks = jnp.einsum("hed,hd->he", sp, k_t, precision=_HI)
        w = b_t[:, None] * (v_t - ks)
        sn = sp + w[:, :, None] * k_t[:, None, :]
        o = jnp.einsum("hed,hd->he", sn, q_t, precision=_HI)
        return s.at[r].set(jnp.where(ok_t, sn, sr)), jnp.where(ok_t, o, 0.0)

    s, o = lax.scan(step, s0, (q.astype(F32), k.astype(F32), v.astype(F32),
                               g.astype(F32), b.astype(F32), row, ok))
    idx = jnp.where(live, slots, pool.shape[0])
    return o, pool.at[idx].set(s.astype(pool.dtype), mode="drop")


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=F32)


_NT = ((1,), (1,))  # x . y^T
_NN = ((1,), (0,))  # x . y
_TN = ((0,), (0,))  # x^T . y


def _kernel(cu_ref, slots_ref, flags_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
            s_in_ref, o_ref, s_out_ref, s_scr, q_scr, k_scr, v_scr, g_scr,
            b_scr, *, hb: int, d: int, blk: int):
    del slots_ref  # read by the index maps
    r_id = pl.program_id(1)

    @pl.when(r_id == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    flag = flags_ref[r_id]
    live = (flag & 1) == 1

    @pl.when(live)
    def _():
        s_scr[...] = jnp.where((flag & 2) == 2, 0.0, s_in_ref[0].astype(F32))
        start, end = cu_ref[r_id], cu_ref[r_id + 1]
        rows = lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        i = lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        j = lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        eye = (i == j).astype(F32)
        lower = (i >= j).astype(F32)

        def one_token(t0):
            """A block of one token: the recurrence in float32 lanes, one
            pass over each head's tile of the state (the module's words)."""
            q1, k1, v1, g1, b1 = (x[pl.ds(t0, 1), :] for x in (
                q_ref, k_ref, v_ref, g_ref, b_ref))  # [1, hb * d] each
            a1, kb, bv = jnp.exp(g1), k1 * b1, b1 * v1
            # b v is wanted down a tile's rows and arrives along lanes: the
            # heads' rows as a [d, d] tile, turned, hold head h in lane h
            bv = jnp.concatenate(
                [bv[:, h * d:(h + 1) * d] for h in range(hb)]
                + [jnp.zeros((d - hb, d), F32)], axis=0).T
            # q, k, a and b k pass through row 0 of the block scratches: a
            # head's lanes of a value loaded at t0 do not broadcast along a
            # tile's rows (Mosaic: "Invalid input layout"), a static load does
            for scr, x in ((q_scr, q1), (k_scr, k1), (g_scr, a1), (b_scr, kb)):
                scr[0:1, :] = x
            lane = lax.broadcasted_iota(jnp.int32, (d, d), 1)
            o = jnp.zeros((d, d), F32)
            for h in range(hb):
                lanes = slice(h * d, (h + 1) * d)
                sp = s_scr[h] * g_scr[0:1, lanes]  # [E, D] float32
                # u = b v - (b a k) S: head h's b v joins lane h of the
                # products, and the sum hands u back along every lane
                u = jnp.sum(jnp.where(lane == h, bv, 0.0)
                            - sp * b_scr[0:1, lanes], axis=1, keepdims=True)
                sn = sp + u * k_scr[0:1, lanes]
                s_scr[h] = sn
                o = jnp.where(lane == h, jnp.sum(
                    sn * q_scr[0:1, lanes], axis=1, keepdims=True), o)
            o = o.T  # head h in row h
            o_ref[pl.ds(t0, 1), :] = jnp.concatenate(
                [o[h:h + 1] for h in range(hb)], axis=1)

        def many_tokens(t0, r):
            valid = rows < r
            # a block starts wherever its row does in the flat batch, and a
            # load of several rows wants a start on a sublane tile: the rows
            # are brought one by one (the rows past r are the next row's, or
            # the padding's; they are masked)
            for n in range(blk):
                for scr, ref in ((q_scr, q_ref), (k_scr, k_ref),
                                 (v_scr, v_ref), (g_scr, g_ref),
                                 (b_scr, b_ref)):
                    scr[n:n + 1, :] = ref[pl.ds(t0 + n, 1), :]
            qb = q_scr[...]
            kb, vb, gb, bb = (jnp.where(valid, x[...], 0.0)
                              for x in (k_scr, v_scr, g_scr, b_scr))
            outs = []
            for h in range(hb):
                lanes = slice(h * d, (h + 1) * d)
                qh, kh, vh, gh, bh = (x[:, lanes]
                                      for x in (qb, kb, vb, gb, bb))
                gc = _dot(lower, gh, _NN)  # [blk, D]: G_i, inclusive
                up, down = jnp.exp(gc), jnp.exp(-gc)
                total = gc[blk - 1:blk]  # [1, D]: G of the whole block
                kbt, khat = kh * bh * up, kh * down
                st = s_scr[h]  # [E, D] float32
                # one product for both triangles, one for both reads of S
                tri = _dot(jnp.concatenate([qh * up, kbt], axis=0), khat, _NT)
                aq = tri[:blk] * lower
                n1 = -tri[blk:] * (lower - eye)  # N = -StrictTril
                t_inv = eye + n1
                p = n1
                for _ in range(3):  # (I + N)(I + N^2)(I + N^4)(I + N^8)
                    p = _dot(p, p, _NN)
                    t_inv = t_inv + _dot(t_inv, p, _NN)
                both = _dot(jnp.concatenate([qh * up, kbt], axis=0), st, _NT)
                u = _dot(t_inv, vh * bh - both[blk:], _NN)  # [blk, E]
                outs.append(both[:blk] + _dot(aq, u, _NN))
                s_scr[h] = st * jnp.exp(total) + _dot(
                    u, kh * jnp.exp(total - gc), _TN)
            ob = jnp.concatenate(outs, axis=1)
            for n in range(blk):
                @pl.when(n < r)
                def _(n=n):
                    o_ref[pl.ds(t0 + n, 1), :] = ob[n:n + 1, :]

        def block(m, carry):
            t0 = start + m * blk
            r = jnp.minimum(blk, end - t0)

            @pl.when(r == 1)
            def _():
                one_token(t0)

            @pl.when(r > 1)
            def _():
                many_tokens(t0, r)

            return carry

        lax.fori_loop(0, (end - start + blk - 1) // blk, block, 0)
        s_out_ref[0] = s_scr[...].astype(s_out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out_ref[0] = s_in_ref[0]


def head_block(num_heads: int) -> int:
    """Heads a grid step holds: a state block of 512 KB at heads of 128.
    tools/kda_sweep.py on the chip, a decode call of 64 rows at 8 / 4 / 2 / 1
    heads a step: 0.52 / 0.61 / 0.81 / 1.38 ms (PR 53; 0.93 / 1.03 / 1.22 /
    1.60 before a one-token block left the matrix unit). What is left at 8 is
    the grid's: a call with 32 live rows of 64 takes what one with 55 does,
    2.0 us a step for the 1 MB it moves in and out. 16 heads a step read 0.48
    ms and cost every call site 0.65 s more of trace and lower a launch, so 8
    stays."""
    return next(n for n in (8, 4, 2, 1) if num_heads % n == 0)


@functools.partial(jax.jit, static_argnames=("interpret", "hb"))
def kda_attention_pallas(q, k, v, g, b, pool, slots, cu_q_lens, live, fresh,
                         *, interpret: bool = False, hb: int = 0):
    """``kda_attention_xla`` as one Pallas call, ``pool`` updated in place.

    The grid is (head block, row), rows innermost, as the lightning kernel's:
    a head block's q, k, v, g, b and o stay in VMEM while the rows pass, and
    each row's state block ``[hb, D, D]`` comes from and goes back to its
    slot through the block specs' index maps (scalar-prefetched ``slots``),
    double-buffered by the pipeline; the state is carried from block to block
    of a row in a VMEM scratch. Rows that are not live copy their block
    through. ``b`` is handed over broadcast to a head's lanes, so that every
    token array is ``[NT, H * D]``. ``hb`` (0: ``head_block``) is the sweep's
    knob."""
    nt, nh, d = q.shape
    nb = slots.shape[0]
    hb = hb or head_block(nh)
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    # a row's last block reads and writes up to BLOCK - 1 rows past its end
    flat = lambda x: jnp.pad(x.astype(F32).reshape(nt, nh * d),  # noqa: E731
                             ((0, BLOCK), (0, 0)))
    tok = lambda c, r, *_: (0, c)  # noqa: E731
    state = lambda c, r, cu, sl, fl: (sl[r], c, 0, 0)  # noqa: E731
    wide = pl.BlockSpec((nt + BLOCK, hb * d), tok)
    o, pool = pl.pallas_call(
        functools.partial(_kernel, hb=hb, d=d, blk=BLOCK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nh // hb, nb),
            in_specs=[wide] * 5 + [pl.BlockSpec((1, hb, d, d), state)],
            out_specs=[wide, pl.BlockSpec((1, hb, d, d), state)],
            scratch_shapes=[pltpu.VMEM((hb, d, d), F32)]
            + [pltpu.VMEM((BLOCK, hb * d), F32)] * 5,
        ),
        out_shape=[jax.ShapeDtypeStruct((nt + BLOCK, nh * d), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},  # the pool, after 3 prefetched + 5 inputs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_attention",
    )(cu_q_lens.astype(jnp.int32), slots.astype(jnp.int32), flags,
      flat(q), flat(k), flat(v), flat(g),
      flat(jnp.broadcast_to(b[:, :, None], (nt, nh, d))), pool)
    return o[:nt].reshape(nt, nh, d), pool


def make_kda_attention(impl: str, interpret: bool = False):
    """The recurrence a forward pass is given: ``"pallas"`` or ``"xla"``."""
    if impl == "pallas":
        return functools.partial(kda_attention_pallas, interpret=interpret)
    if impl == "xla":
        return kda_attention_xla
    raise ValueError(f"unknown kda-attention impl {impl!r}")
