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
whole — MoE expert widths (D <= 8k) fit VMEM at these tile sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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


def _rgg_kernel(slots_ref, rows_ref, x_ref, w_ref, o_ref):
    b = pl.program_id(0)

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


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_grouped_gemm(
    x: jax.Array,  # [nb, bc, D] — token-sorted block-aligned activations
    w: jax.Array,  # [S, D, F] — expert slot bank
    block_slot: jax.Array,  # [nb] int32 — expert slot owning each block
    block_rows: jax.Array,  # [nb] int32 — real rows in each block
    interpret: bool = False,  # the selecting caller passes True on CPU only
) -> jax.Array:  # [nb, bc, F]
    """Block-ragged grouped GEMM for the token-sorted dispatch path
    (ops/moe_dispatch): each [bc, D] block multiplies the weight of the
    slot it belongs to — the slot id rides in scalar prefetch so the
    weight DMA is indexed per block, and fully-padded blocks skip their
    MXU work just like zero-count groups in ``grouped_gemm``."""
    nb, bc, D = x.shape
    _, _, F = w.shape

    bf = min(256, 128 * ((F + 127) // 128))
    Fp = -(-F // bf) * bf
    if Fp != F:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Fp - F)))

    out = pl.pallas_call(
        _rgg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, Fp // bf),
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda b, j, slots, rows: (b, 0, 0)),
                pl.BlockSpec((1, D, bf),
                             lambda b, j, slots, rows: (slots[b], 0, j)),
            ],
            out_specs=pl.BlockSpec((1, bc, bf),
                                   lambda b, j, slots, rows: (b, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, bc, Fp), x.dtype),
        interpret=interpret,
    )(block_slot.astype(jnp.int32), block_rows.astype(jnp.int32), x, w)
    return out[:, :, :F]


def make_moe_matmul(interpret: bool = False):
    """Adapter with the ``moe_block`` matmul_impl signature."""
    def impl(xe, we, slot_counts):
        return grouped_gemm(xe, we, slot_counts, interpret=interpret)
    return impl
