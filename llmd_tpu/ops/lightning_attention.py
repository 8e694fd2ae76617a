"""Lightning linear attention over a ragged batch whose rows keep a matrix
state a head in a pool of slots, as one Pallas TPU kernel and as the plain
XLA form it is tested against (and that the CPU runs).

For row ``b`` of a call, tokens ``t = cu_q_lens[b] .. cu_q_lens[b + 1] - 1``
in order, head ``h`` with state ``S`` [D, D] float32 and decay ``lam =
exp(-slope_h)``:

    S   <- lam * S + k_t^T v_t
    o_t  = (scale * q_t) S

``S`` starts from the row's slot of ``pool`` unless the row is ``fresh`` (its
first position is 0: zeros) and is written back after the row's last token. A
row that is not ``live`` (padding, an idle seat, a row the fused decode call
has frozen) leaves its slot bit for bit and gives ``o = 0``.

The kernel runs a row in blocks of ``BLOCK`` tokens counted from the row's
first token, the products inside a block on the matrix unit:

    O     = ((Q K^T) * D) V + diag(lam^(i+1)) Q S_prev,   D_ij = lam^(i-j), i >= j
    S_new = lam^r S_prev + sum_i lam^(r-1-i) k_i^T v_i     (r tokens in the block)

A block groups its sums by its own boundaries, so a token's result depends on
where its block starts. The engine therefore starts a prompt's every chunk on
a multiple of ``BLOCK`` (``engine.py``, the plan of a unified step): a prompt
is then cut into the same blocks however it is chunked and whatever else the
step holds, a decode token is a block of its own in both step programs, and
greedy tokens served alone and in a batch do not part at near ties. q, k and
v arrive in the model's type; the state is float32 between blocks and in the
pool (``ModelConfig.lightning_state_dtype``, rounded to it once a call), and a
float32 operand of a product goes to the matrix unit as the sum of two
bfloat16 halves.

The kernel's name in a device trace is ``lightning_attention`` (the
benchmark's ``lightning_attention_dev_share`` and both rooflines match on it).
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
# tokens of a block; what a prompt's chunks are aligned to
BLOCK = 16
_VMEM_LIMIT = 64 * 1024 * 1024


def head_slopes(num_heads: int) -> jax.Array:
    """Lightning Attention-2's slopes, ``s_h = 2^(-8 (h + 1) / H)``: head h
    forgets at ``lam_h = exp(-s_h)`` a token."""
    h = jnp.arange(1, num_heads + 1, dtype=F32)
    return jnp.exp2(-8.0 * h / num_heads)


def lightning_attention_xla(q, k, v, slopes, pool, slots, cu_q_lens, live,
                            fresh, *, scale: float):
    """The recurrence as a ``lax.scan`` over the call's flat tokens.

    q, k, v: [NT, H, D]; slopes: [H] float32; pool: [S, H, D, D]; slots: [B]
    int32 row of ``pool`` per batch row; cu_q_lens: [B + 1]; live, fresh: [B]
    bool. Returns (o [NT, H, D] float32, pool)."""
    nt = q.shape[0]
    nb = slots.shape[0]
    lam = jnp.exp(-slopes.astype(F32))[:, None, None]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[slots].astype(F32))
    t = jnp.arange(nt, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cu_q_lens[1:], t, side="right"), 0, nb - 1)
    ok = live[row] & (t < cu_q_lens[nb])

    def step(s, inp):
        q_t, k_t, v_t, r, ok_t = inp
        sr = s[r]
        sn = lam * sr + k_t[:, :, None] * v_t[:, None, :]
        o = jnp.einsum("hd,hde->he", q_t * scale, sn,
                       precision=lax.Precision.HIGHEST)
        return s.at[r].set(jnp.where(ok_t, sn, sr)), jnp.where(ok_t, o, 0.0)

    s, o = lax.scan(step, s0, (q.astype(F32), k.astype(F32), v.astype(F32),
                               row, ok))
    idx = jnp.where(live, slots, pool.shape[0])
    return o, pool.at[idx].set(s.astype(pool.dtype), mode="drop")


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=F32)


def _halves(x):
    """Float32 ``x`` as two bfloat16 halves whose sum holds 16 bits of it:
    what a float32 operand goes to the matrix unit as."""
    hi = x.astype(BF16)
    return hi, (x - hi.astype(F32)).astype(BF16)


def _dot2(a, b, dims):
    """``a . b`` with ``a`` float32 and ``b`` exact in bfloat16."""
    hi, lo = _halves(a)
    return _dot(hi, b, dims) + _dot(lo, b, dims)


_NT = ((1,), (1,))  # x . y^T
_NN = ((1,), (0,))  # x . y
_TN = ((0,), (0,))  # x^T . y


def _kernel(cu_ref, slots_ref, flags_ref, slopes_ref, q_ref, k_ref, v_ref,
            s_in_ref, o_ref, s_out_ref, s_scr, q_scr, k_scr, v_scr, *,
            hb: int, d: int, blk: int, scale: float):
    del slots_ref  # read by the index maps
    c, b = pl.program_id(0), pl.program_id(1)

    @pl.when(b == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    flag = flags_ref[b]
    live = (flag & 1) == 1

    @pl.when(live)
    def _():
        s_scr[...] = jnp.where((flag & 2) == 2, 0.0, s_in_ref[0].astype(F32))
        start, end = cu_ref[b], cu_ref[b + 1]
        rows = lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        i = lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        j = lax.broadcasted_iota(jnp.int32, (blk, blk), 1)

        def block(m, carry):
            t0 = start + m * blk
            r = jnp.minimum(blk, end - t0)
            valid = rows < r
            # a block starts wherever its row does in the flat batch, and a
            # load of several rows wants a start on a sublane tile: the rows
            # are brought one by one (the rows past r are the next row's, or
            # the padding's; they are masked)
            for n in range(blk):
                q_scr[n:n + 1, :] = q_ref[pl.ds(t0 + n, 1), :]
                k_scr[n:n + 1, :] = k_ref[pl.ds(t0 + n, 1), :]
                v_scr[n:n + 1, :] = v_ref[pl.ds(t0 + n, 1), :]
            qb = q_scr[...]
            kb = jnp.where(valid, k_scr[...], 0.0)
            vb = jnp.where(valid, v_scr[...], 0.0)
            outs = []
            for h in range(hb):
                s_h = slopes_ref[c * hb + h]
                lanes = slice(h * d, (h + 1) * d)
                qh, kh, vh = (x[:, lanes].astype(BF16) for x in (qb, kb, vb))
                dec = jnp.where(i >= j, jnp.exp(-s_h * (i - j).astype(F32)), 0.0)
                a = _dot(qh, kh, _NT) * dec  # [blk, blk]
                st = s_scr[h]  # [D, D] float32
                hi, lo = _halves(st)
                o_h = _dot2(a, vh, _NN) + (
                    _dot(qh, hi, _NN) + _dot(qh, lo, _NN)) * jnp.exp(
                        -s_h * (rows + 1).astype(F32))
                outs.append(o_h * scale)
                w = jnp.where(valid, jnp.exp(-s_h * (r - 1 - rows).astype(F32)),
                              0.0)
                kw = kh.astype(F32) * w
                s_scr[h] = jnp.exp(-s_h * r.astype(F32)) * st + _dot2(
                    kw, vh, _TN)
            ob = jnp.concatenate(outs, axis=1)
            for n in range(blk):
                @pl.when(n < r)
                def _(n=n):
                    o_ref[pl.ds(t0 + n, 1), :] = ob[n:n + 1, :]
            return carry

        lax.fori_loop(0, (end - start + blk - 1) // blk, block, 0)
        s_out_ref[0] = s_scr[...].astype(s_out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out_ref[0] = s_in_ref[0]


def head_block(num_heads: int) -> int:
    """Heads a grid step holds: a state block of 512 KB at heads of 128."""
    return next(n for n in (8, 4, 2, 1) if num_heads % n == 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def lightning_attention_pallas(q, k, v, slopes, pool, slots, cu_q_lens, live,
                               fresh, *, scale: float,
                               interpret: bool = False):
    """``lightning_attention_xla`` as one Pallas call, ``pool`` updated in
    place.

    The grid is (head block, row), rows innermost: a head block's q, k, v and
    o stay in VMEM while the rows pass, and each row's state block ``[hb, D,
    D]`` comes from and goes back to its slot through the block specs' index
    maps (scalar-prefetched ``slots``), double-buffered by the pipeline; the
    state is carried from block to block of a row in a VMEM scratch. Rows
    that are not live copy their block through, so rows that share a slot
    nothing reads (the packer's scratch slot for padding rows) are harmless,
    and consecutive ones cost one fetch."""
    nt, nh, d = q.shape
    nb = slots.shape[0]
    hb = head_block(nh)
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    # a row's last block reads and writes up to BLOCK - 1 rows past its end
    flat = lambda x: jnp.pad(x.astype(F32).reshape(nt, nh * d),  # noqa: E731
                             ((0, BLOCK), (0, 0)))
    tok = lambda c, b, *_: (0, c)  # noqa: E731
    state = lambda c, b, cu, sl, fl: (sl[b], c, 0, 0)  # noqa: E731
    o, pool = pl.pallas_call(
        functools.partial(_kernel, hb=hb, d=d, blk=BLOCK, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nh // hb, nb),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((nt + BLOCK, hb * d), tok),
                pl.BlockSpec((nt + BLOCK, hb * d), tok),
                pl.BlockSpec((nt + BLOCK, hb * d), tok),
                pl.BlockSpec((1, hb, d, d), state),
            ],
            out_specs=[
                pl.BlockSpec((nt + BLOCK, hb * d), tok),
                pl.BlockSpec((1, hb, d, d), state),
            ],
            scratch_shapes=[pltpu.VMEM((hb, d, d), F32)]
            + [pltpu.VMEM((BLOCK, hb * d), F32)] * 3,
        ),
        out_shape=[jax.ShapeDtypeStruct((nt + BLOCK, nh * d), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},  # the pool, after 3 prefetched + 4 inputs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="lightning_attention",
    )(cu_q_lens.astype(jnp.int32), slots.astype(jnp.int32), flags,
      slopes.astype(F32), flat(q), flat(k), flat(v), pool)
    return o[:nt].reshape(nt, nh, d), pool


def make_lightning_attention(impl: str, interpret: bool = False):
    """The recurrence a forward pass is given: ``"pallas"`` or ``"xla"``."""
    if impl == "pallas":
        return functools.partial(lightning_attention_pallas,
                                 interpret=interpret)
    if impl == "xla":
        return lightning_attention_xla
    raise ValueError(f"unknown lightning-attention impl {impl!r}")
