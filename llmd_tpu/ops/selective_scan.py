"""The selective-scan recurrence of a Mamba layer over a ragged batch whose
rows keep their state in a pool of slots, as one Pallas TPU kernel and as the
plain XLA form it is tested against (and that the CPU runs).

For row ``b`` of a call, tokens ``t = cu_q_lens[b] .. cu_q_lens[b + 1] - 1``
in order, with ``h`` the row's state ``[N, Di]`` (``N`` = d_state on the
sublanes, ``Di`` = d_inner on the lanes):

    h   <- exp(dt_t[None, :] * A) * h + (dt_t * x_t)[None, :] * B_t[:, None]
    y_t  = sum_n h[n, :] * C_t[n]

``h`` starts from the row's slot of ``pool`` unless the row is ``fresh`` (its
first position is 0: zeros), and is written back to the slot after the row's
last token. A row that is not ``live`` (padding, an idle seat, a row the fused
decode call has frozen) leaves its slot bit for bit and gives ``y = 0``.

Time runs sequentially inside the kernel, one token after another, and the
parallelism is over channels (vector lanes, channel blocks of the grid) and
rows (the grid): a token's update is the same arithmetic whatever chunk it
arrives in and whatever else the step holds, so the state after a prompt does
not depend on how the prompt was chunked, and greedy tokens served alone and
in a batch do not part at near ties. (A blocked or associative scan groups
its sums by the chunk's boundaries and re-rounds them when those move.)

Everything here is float32 but the pool, which is held in the type the model
states (``ModelConfig.mamba_state_dtype``) and rounded to it once a call, at
the write-back.

The kernel's name in a device trace is ``selective_scan`` (the benchmark's
``selective_scan_dev_share`` and ``selective_scan_roofline`` match on it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
F32 = jnp.float32
# what one resident [NT, tc] float32 block of x, dt or y may take: the three
# are double-buffered, so six of these and the B/C blocks share the kernel's
# VMEM with the state blocks
_BLOCK_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def row_flags(positions: jax.Array, cu_q_lens: jax.Array) -> tuple:
    """(live [B], fresh [B]) of a call's rows from what every step packs: a
    row is live if it has tokens and its first one a position (-1 marks
    padding, an idle seat and a frozen row), fresh if that position is 0."""
    nt = positions.shape[0]
    start = cu_q_lens[:-1]
    first = positions[jnp.clip(start, 0, nt - 1)]
    live = (cu_q_lens[1:] > start) & (first >= 0)
    return live, live & (first == 0)


def selective_scan_xla(x, dt, Bm, Cm, A, pool, slots, cu_q_lens, live, fresh):
    """The recurrence as a ``lax.scan`` over the call's flat tokens.

    x, dt: [NT, Di] float32; Bm, Cm: [NT, N] float32; A: [N, Di] float32
    (negative); pool: [S, N, Di]; slots: [B] int32 row of ``pool`` per batch
    row; cu_q_lens: [B + 1]; live, fresh: [B] bool. Returns (y [NT, Di]
    float32, pool)."""
    nt = x.shape[0]
    nb = slots.shape[0]
    h0 = pool[slots].astype(F32)
    h0 = jnp.where(fresh[:, None, None], 0.0, h0)
    t = jnp.arange(nt, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cu_q_lens[1:], t, side="right"), 0, nb - 1)
    ok = live[row] & (t < cu_q_lens[nb])

    def step(h, inp):
        x_t, dt_t, b_t, c_t, r, ok_t = inp
        hr = h[r]
        hn = jnp.exp(dt_t[None, :] * A) * hr + (dt_t * x_t)[None, :] * b_t[:, None]
        y = jnp.sum(hn * c_t[:, None], axis=0)
        h = h.at[r].set(jnp.where(ok_t, hn, hr))
        return h, jnp.where(ok_t, y, 0.0)

    h, y = lax.scan(step, h0, (x, dt, Bm, Cm, row, ok))
    idx = jnp.where(live, slots, pool.shape[0])
    return y, pool.at[idx].set(h.astype(pool.dtype), mode="drop")


def _kernel(cu_ref, slots_ref, flags_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
            h_in_ref, y_ref, h_out_ref):
    del slots_ref  # read by the index maps
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    flag = flags_ref[b]
    live = (flag & 1) == 1

    @pl.when(live)
    def _():
        a = a_ref[...]
        h0 = jnp.where((flag & 2) == 2, 0.0, h_in_ref[0].astype(F32))

        def step(t, h):
            x_t = x_ref[pl.ds(t, 1), :]  # [1, tc]
            dt_t = dt_ref[pl.ds(t, 1), :]
            h = jnp.exp(dt_t * a) * h + (dt_t * x_t) * b_ref[t]
            y_ref[pl.ds(t, 1), :] = jnp.sum(h * c_ref[t], axis=0,
                                            keepdims=True)
            return h

        h = lax.fori_loop(cu_ref[b], cu_ref[b + 1], step, h0)
        h_out_ref[0] = h.astype(h_out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        h_out_ref[0] = h_in_ref[0]


def channel_block(nt: int, di: int) -> int:
    """Channels a grid step holds: the widest whole number of lane tiles
    that divides ``di`` and keeps an [nt, tc] float32 block within
    ``_BLOCK_BYTES``."""
    tiles = di // LANE
    for n in range(1, tiles + 1):
        if tiles % n == 0 and nt * (di // n) * 4 <= _BLOCK_BYTES:
            return di // n
    return LANE


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_pallas(x, dt, Bm, Cm, A, pool, slots, cu_q_lens, live,
                          fresh, *, interpret: bool = False):
    """``selective_scan_xla`` as one Pallas call, ``pool`` updated in place.

    The grid is (channel block, row), rows innermost: a channel block's x,
    dt and y stay in VMEM while the rows pass, and each row's state block
    ``[N, tc]`` comes from and goes back to its slot through the block specs'
    index maps (scalar-prefetched ``slots``), double-buffered by the
    pipeline. Rows that are not live copy their block through, so rows that
    share a slot nothing reads (the packer's scratch slot for padding rows)
    are harmless, and consecutive ones cost one fetch."""
    nt, di = x.shape
    n = A.shape[0]
    nb = slots.shape[0]
    assert di % LANE == 0, f"d_inner {di} must be a multiple of {LANE} lanes"
    tc = channel_block(nt, di)
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    # B_t and C_t are wanted as columns [N, 1] against [N, tc] state blocks:
    # a token a leading index, d_state on the sublanes
    col = lambda m: m.astype(F32)[:, :, None]  # noqa: E731
    tok = lambda c, b, *_: (0, c)  # noqa: E731
    state = lambda c, b, cu, sl, fl: (sl[b], 0, c)  # noqa: E731
    y, pool = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(di // tc, nb),
            in_specs=[
                pl.BlockSpec((nt, tc), tok),
                pl.BlockSpec((nt, tc), tok),
                pl.BlockSpec((nt, n, 1), lambda c, b, *_: (0, 0, 0)),
                pl.BlockSpec((nt, n, 1), lambda c, b, *_: (0, 0, 0)),
                pl.BlockSpec((n, tc), tok),
                pl.BlockSpec((1, n, tc), state),
            ],
            out_specs=[
                pl.BlockSpec((nt, tc), tok),
                pl.BlockSpec((1, n, tc), state),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((nt, di), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},  # the pool, after 3 prefetched + 5 inputs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="selective_scan",
    )(cu_q_lens.astype(jnp.int32), slots.astype(jnp.int32), flags,
      x.astype(F32), dt.astype(F32), col(Bm), col(Cm), A.astype(F32), pool)
    return y, pool


def make_selective_scan(impl: str, interpret: bool = False):
    """The scan a forward pass is given: ``"pallas"`` or ``"xla"``."""
    if impl == "pallas":
        return functools.partial(selective_scan_pallas, interpret=interpret)
    if impl == "xla":
        return selective_scan_xla
    raise ValueError(f"unknown selective-scan impl {impl!r}")
