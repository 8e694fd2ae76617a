"""Pallas grouped (per-expert) GEMM — the TPU-native answer to DeepGEMM's masked
grouped FP8 GEMM (SURVEY.md §2.5 N7, docker/Dockerfile.cuda:68-69, wide-ep
decode.yaml `--moe-backend deep_gemm`).

``out[g] = x[g] @ w[g]`` for every expert group g, with a per-group valid count:
groups that received zero tokens this step skip their MXU work entirely
(``@pl.when`` on a scalar-prefetched count — the Pallas equivalent of DeepGEMM's
masked launch). Dense einsum can't do that: it always pays for all E experts even
when top-k routing touched a handful.

Layout: grid ``(G, C/bc, F/bf)``; each program computes one [bc, bf] output tile
with a single [bc, D] x [D, bf] MXU dot (fp32 accumulation, bf16 in). D is kept
whole — MoE expert widths (D <= 8k) fit VMEM at these tile sizes. That is the
dense capacity form (``grouped_gemm``), which re-reads a group's bank once a
row tile and which no benchmark cell runs.

The form the served path runs is ``ragged_grouped_gemm``, over the
token-sorted blocks of ``ops/moe_dispatch``: grid ``(F/bf, nb)`` with the
block axis inner and ``bf`` from `pick_bank_tile`, the whole F wherever an
expert's bank fits VMEM double-buffered. Blocks of one expert are adjacent
(``block_slot`` is sorted), the bank tile's index stands still across them,
and Pallas copies an input block in only when its index moved: an expert's
bank comes in once a call. A block without rows (the plan's worst-case
padding: 38-48 of 112 blocks at smallthinker-21b-a3b's shapes) keeps the
operands of the last block that had rows, so it fetches nothing.

Why, to the byte (PERF.md section 6, PR 35): the grid was ``(nb, F/256)`` with
the F tile inner, so the tile's index moved at every grid step and every one
of 112 blocks, padding included, re-read a whole ``[D, F]`` bank: 881 MB a
``moe_wi`` call where the 64 experts' banks are 503 MB, 440 MB a ``moe_wo``
call where they are 252. The sweep (`tools/gemm_sweep.py --cells smallthinker`,
TPU v5 lite, 819 GB/s; us a call, two drawn plans a shape: top-6 of 64 experts,
53 of 64 rows routing at decode, a 256-token chunk unified; 63-64 blocks
fetch, 0-2 reuse, 47-48 padding; stack of 4 x 64 slots, the plan offset into
layer 2; ``bf`` order = block outer as it was, ``fb`` = F tile outer):

    x [112, bc, 2560] x moe_wi [256, 2560, 1536]   bc 8 (decode)    bc 32 (unified)
      bf, tile  256 (the parent's)                  1192-1193        1192-1195
      bf, tile  512 / 768                           1192-1206        1195-1210
      fb, tile  256                                  719-723          722-730
      fb, tile  512 / 768                            703-719          714-723
      fb, tile 1536 (whole F: the rule)              690-702          702-704
    x [112, bc, 768] x moe_wo [256, 768, 2560]
      bf, tile  256 (the parent's)                   662-663          680-682
      bf, tile  512 / 1280                           606-613          607-612
      fb, tile  256                                  440-442          458-467
      fb, tile  512                                  383              385-393
      fb, tile 1280                                  368-372          375
      fb, tile 2560 (whole F: the rule)              365-368          367-370

The whole F in the parent's order reads the same as in ours (696-702 and
359-364: one F tile leaves nothing to order). At the rule's tile ``moe_wi``
moves its 503 MB at 87-88% of the DMA rate and ``moe_wo`` its 252 MB at 83-84%;
every row's largest difference from the parent's kernel is 0.0 (the same
product a block, D whole, float32 accumulation, one cast). What is left: a
block's DMA is issued when the block before it starts, so some 1.3 us an
expert is not overlapped; this Pallas takes no third buffer
(``pl.Buffered(3)``: "Only single (1) and double (2) buffering are supported").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gg_kernel(counts_ref, x_ref, w_ref, o_ref):
    g = pl.program_id(0)

    @pl.when(counts_ref[g] > 0)
    def _compute():
        acc = jax.lax.dot_general(
            x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0] = acc.astype(o_ref.dtype)

    @pl.when(counts_ref[g] == 0)
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_gemm(
    x: jax.Array,  # [G, C, D]
    w: jax.Array,  # [G, D, F]
    counts: jax.Array,  # [G] int32 — tokens routed to each group this step
    interpret: bool = False,  # the selecting caller passes True on CPU only
) -> jax.Array:  # [G, C, F]
    """Per-group matmul with zero-token groups skipped on the MXU."""
    G, C, D = x.shape
    _, _, F = w.shape

    bc = min(128, 8 * ((C + 7) // 8))   # capped: a [bc, D] block must fit VMEM
    bf = min(256, 128 * ((F + 127) // 128))
    # pad C and F up to tile multiples (token capacity C is often small/ragged)
    Cp, Fp = -(-C // bc) * bc, -(-F // bf) * bf
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, 0)))
    if Fp != F:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Fp - F)))

    out = pl.pallas_call(
        _gg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, Cp // bc, Fp // bf),
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda g, i, j, counts: (g, i, 0)),
                pl.BlockSpec((1, D, bf), lambda g, i, j, counts: (g, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, bc, bf), lambda g, i, j, counts: (g, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((G, Cp, Fp), x.dtype),
        interpret=interpret,
    )(counts.astype(jnp.int32), x, w)
    return out[:, :C, :F]


def _rgg_kernel(slots_ref, rows_ref, live_ref, x_ref, w_ref, o_ref):
    b = pl.program_id(1)

    @pl.when(rows_ref[b] > 0)
    def _compute():
        acc = jax.lax.dot_general(
            x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0] = acc.astype(o_ref.dtype)

    @pl.when(rows_ref[b] == 0)
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


# VMEM a call may ask for (v5e has 128 MiB; one expert's [2560, 1536] bf16
# bank double-buffered, 15.7 MB, leaves nothing of Mosaic's default 16 MiB of
# scoped VMEM) and how much of it the tiles of `pick_bank_tile` may fill
RGG_VMEM_LIMIT = 64 * 1024 * 1024
RGG_TILE_BUDGET = 40 * 1024 * 1024
# the grid's order, as the engine's label names it: F tile outer, block inner
RGG_ORDER = "fb"


def rgg_vmem_bytes(bc: int, D: int, bf: int, itemsize: int) -> int:
    """What one grid step of `ragged_grouped_gemm` holds in VMEM: the bank
    tile, the activation block and the output block, each double-buffered,
    and the float32 product before its cast."""
    return 2 * itemsize * (D * bf + bc * D + bc * bf) + 4 * bc * bf


def pick_bank_tile(D: int, F: int, bc: int, itemsize: int = 2) -> int:
    """``bf``, the width of the ``[D, bf]`` bank tile `ragged_grouped_gemm`
    fetches: a function of the call's static shapes only (module docstring
    has the sweep). The widest tile that divides F into lane-aligned parts
    and fits `RGG_TILE_BUDGET` double-buffered: the whole F where it fits (one
    DMA an expert, and the activation block read once), else the widest
    multiple of 128 that divides F. An F that no multiple of 128 divides is
    taken whole (a block as wide as the array needs no alignment)."""
    tiles = [F] + [t for t in range(F - F % 128, 0, -128) if F % t == 0]
    for bf in tiles:
        if rgg_vmem_bytes(bc, D, bf, itemsize) <= RGG_TILE_BUDGET:
            return bf
    return tiles[-1]


def bank_fetch_plan(counts, bc: int, nb: int) -> tuple[int, int, int]:
    """(fetch, reuse, padding) blocks of `ragged_grouped_gemm` calls whose
    plans `ops/moe_dispatch._row_plan` laid out from ``counts`` ``[..., S]``
    (routed copies by slot; leading axes are calls, e.g. layers), each over
    ``nb`` blocks of ``bc`` rows. A slot's copies fill ``ceil(cnt / bc)``
    adjacent blocks: the first brings the slot's bank tile in (``fetch``), the
    others find it resident (``reuse``); what is left of ``nb`` holds no row
    and keeps the last real block's operands (``padding``: no fetch, no
    product). The three sum to ``nb`` a call. Host-side, numpy."""
    counts = np.asarray(counts).reshape(-1, np.shape(counts)[-1])
    fetch = int((counts > 0).sum())
    blocks = int((-(-counts // bc)).sum())
    return fetch, blocks - fetch, nb * counts.shape[0] - blocks


@functools.partial(jax.jit, static_argnames=("interpret", "bf"))
def ragged_grouped_gemm(
    x: jax.Array,  # [nb, bc, D] — token-sorted block-aligned activations
    w: jax.Array,  # [S, D, F] — expert slot bank
    block_slot: jax.Array,  # [nb] int32 — expert slot owning each block
    block_rows: jax.Array,  # [nb] int32 — real rows in each block
    interpret: bool = False,  # the selecting caller passes True on CPU only
    bf: int | None = None,  # bank tile width (the sweep's); None = the rule's
) -> jax.Array:  # [nb, bc, F]
    """Block-ragged grouped GEMM for the token-sorted dispatch path
    (ops/moe_dispatch): each [bc, D] block multiplies the weight of the
    slot it belongs to — the slot id rides in scalar prefetch so the
    weight DMA is indexed per block, and fully-padded blocks skip their
    MXU work just like zero-count groups in ``grouped_gemm``.

    Grid ``(F/bf, nb)``, the block axis inner: the bank tile's index
    ``(slot[b], 0, j)`` stands still from one block to the next of the same
    slot, and Pallas copies an input block in only when its index moved, so a
    tile is fetched once for the whole run of adjacent blocks that share it
    (``block_slot`` is sorted: `_row_plan`). A block with no rows takes the
    operands of the last block before it that had rows: no fetch, no product,
    a block of zeros written."""
    nb, bc, D = x.shape
    _, _, F = w.shape
    if bf is None:
        bf = pick_bank_tile(D, F, bc, x.dtype.itemsize)
    if F % bf:
        raise ValueError(f"bank tile bf={bf} does not divide F={F}")

    rows = block_rows.astype(jnp.int32)
    ids = jnp.arange(nb, dtype=jnp.int32)
    live = jax.lax.cummax(jnp.where(rows > 0, ids, 0))
    slots = block_slot.astype(jnp.int32)[live]

    return pl.pallas_call(
        _rgg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(F // bf, nb),
            in_specs=[
                pl.BlockSpec((1, bc, D),
                             lambda j, b, slots, rows, live: (live[b], 0, 0)),
                pl.BlockSpec((1, D, bf),
                             lambda j, b, slots, rows, live: (slots[b], 0, j)),
            ],
            out_specs=pl.BlockSpec((1, bc, bf),
                                   lambda j, b, slots, rows, live: (b, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, bc, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=RGG_VMEM_LIMIT),
        interpret=interpret,
    )(slots, rows, live, x, w)


def make_moe_matmul(interpret: bool = False):
    """Adapter with the ``moe_block`` matmul_impl signature."""
    def impl(xe, we, slot_counts):
        return grouped_gemm(xe, we, slot_counts, interpret=interpret)
    return impl
