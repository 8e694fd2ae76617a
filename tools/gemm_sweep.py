"""Sweep the experts' ragged grouped GEMM's grid order and bank tile at the
served shapes.

Times `ragged_grouped_gemm` as `sorted_moe_local` calls it in the cells that
have a mixture layer: ``x [nb, bc, D] x moe_wi [L*S, D, 2*width]`` and
``[nb, bc, width] x moe_wo [L*S, width, D]`` over the whole stack of banks,
``block_slot`` offset to one layer's slots, at the fused decode call's plan
(``max_batch_size`` rows, of which the cell's live rows route) and the
unified step's (a ``prefill_chunk`` of tokens). The routed copies are drawn as
the cell routes them (top-k of S experts by random logits) and laid out by
`_row_plan`, so the runs of adjacent blocks, the experts no copy reached and
the padding blocks at the end are what the program sees.

Orders: ``bf`` is the parent's kernel (block outer, F tile inner: the bank
tile's index moves at every grid step; a padding block points at the last
slot), kept here as the comparison; ``fb`` is the kernel's own (F tile outer,
block inner: a tile is fetched once for a run of blocks of one slot; a block
with no rows fetches nothing). Every (order, tile) is one Mosaic compile and
``--reps`` dependent calls in a ``fori_loop``; the report is microseconds a
call, the bank bytes the order moves over that time as a share of the chip's
DMA rate, and the largest difference from the parent's kernel (0.0: the
products and their order are the same). `pick_bank_tile` (the rule) is marked.

    python tools/gemm_sweep.py --cells smallthinker     # on the chip
    python tools/gemm_sweep.py --compile-only           # here: what Mosaic takes
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# configuration file of each cell with a mixture layer, and the rows that
# route in a fused decode call (PERF.md section 5: 53 rows decoding)
CELLS = {"smallthinker": "smallthinker-21b-a3b"}
LIVE_DECODE = {"smallthinker": 53}


def parent_kernel(x, w, block_slot, block_rows, bf):
    """`ragged_grouped_gemm` as it was before the grid was turned: grid
    ``(nb, F/bf)``, the bank tile indexed ``(slot[b], 0, j)`` with ``j``
    inner. The same product a block, so the results must be equal."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from llmd_tpu.ops.grouped_gemm import RGG_VMEM_LIMIT

    nb, bc, D = x.shape
    F = w.shape[2]

    def kernel(slots_ref, rows_ref, x_ref, w_ref, o_ref):
        b = pl.program_id(0)

        @pl.when(rows_ref[b] > 0)
        def _():
            o_ref[0] = jax.lax.dot_general(
                x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

        @pl.when(rows_ref[b] == 0)
        def _():
            o_ref[0] = jnp.zeros_like(o_ref[0])

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb, F // bf),
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda b, j, s, r: (b, 0, 0)),
                pl.BlockSpec((1, D, bf), lambda b, j, s, r: (s[b], 0, j))],
            out_specs=pl.BlockSpec((1, bc, bf), lambda b, j, s, r: (b, 0, j))),
        out_shape=jax.ShapeDtypeStruct((nb, bc, F), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=RGG_VMEM_LIMIT),
    )(block_slot, block_rows, x, w)


def build_case(cell: str, program: str, seed: int, layer: int):
    """One call's plan: block_slot / block_rows as `sorted_moe_local` hands
    them to the kernel, and the shapes of both banks."""
    import numpy as np

    from llmd_tpu.ops.moe_dispatch import _row_plan, pick_block_size

    with open(os.path.join(ROOT, "perfbench", "configs",
                           CELLS[cell] + ".json")) as f:
        cfg = json.load(f)
    S, k = cfg["moe_num_primary_experts"], cfg["moe_num_active_primary_experts"]
    D, width, L = (cfg["hidden_size"], cfg["moe_ffn_hidden_size"],
                   cfg["num_hidden_layers"])
    eng = cfg["engine"]
    T = eng["max_batch_size"] if program == "decode" else eng["prefill_chunk"]
    live = LIVE_DECODE[cell] if program == "decode" else T
    rng = np.random.default_rng(seed)
    idx = np.argsort(-rng.standard_normal((T, S)), axis=1)[:, :k]
    slot = np.where(np.arange(T)[:, None] < live, idx, S).reshape(T * k)
    bc = pick_block_size(T * k, S, True)
    _, block_slot, block_rows, Tp = _row_plan(slot.astype(np.int32), S, bc)
    cnt = np.bincount(slot, minlength=S + 1)[:S]
    return dict(cell=cell, program=program, seed=seed, T=T, live=live, bc=bc,
                nb=Tp // bc, S=S, L=L, D=D, width=width, counts=cnt,
                block_slot=np.asarray(block_slot) + layer * S,
                block_rows=np.asarray(block_rows))


def _loop(fn, reps):
    """``reps`` calls of ``fn``, each reading a value of the one before (one
    element of x: the chain costs no pass over the activations)."""
    import jax

    def f(x, w, slots, rows):
        def body(_, x):
            out = fn(x, w, slots, rows)
            return x.at[0, 0, 0].add(out[0, 0, 0] * 0)

        x = jax.lax.fori_loop(0, reps - 1, body, x)
        return fn(x, w, slots, rows)

    return jax.jit(f)


def measure(fn, reps, shapes, operands):
    import jax

    row = {}
    try:
        t0 = time.perf_counter()
        compiled = _loop(fn, reps).lower(
            *(shapes if operands is None else operands)).compile()
        row["compile_s"] = time.perf_counter() - t0
        if operands is None:
            return row
        row["out"] = jax.block_until_ready(compiled(*operands))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*operands))
            times.append(time.perf_counter() - t0)
        row["us_per_call"] = min(times) / reps * 1e6
    except Exception as e:  # the compiler's words are the result
        row["error"] = f"{type(e).__name__}: {e}"[:400]
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="smallthinker")
    ap.add_argument("--programs", default="decode,unified")
    ap.add_argument("--banks", default="moe_wi,moe_wo")
    ap.add_argument("--orders", default="bf,fb")
    ap.add_argument("--tiles", default="256,512,768,1280,0",
                    help="bank tile widths to try where they divide a bank's "
                         "F (0 = the whole F)")
    ap.add_argument("--layer", type=int, default=2,
                    help="whose slots of the stacked bank the plan points at")
    ap.add_argument("--reps", type=int, default=32)
    ap.add_argument("--seeds", default="0", help="one drawn plan per seed")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "gemm_sweep.json"))
    args = ap.parse_args()

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmd_tpu.obs.costmodel import chip_peaks
    from llmd_tpu.ops.grouped_gemm import (bank_fetch_plan, pick_bank_tile,
                                           ragged_grouped_gemm)

    chip = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        device = "TPU v5e (described, compile only)"
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("gemm_sweep: no TPU (use --compile-only here)")
        device = jax.devices()[0].device_kind
    _, peak_gbs = chip_peaks(device)
    if peak_gbs is None:
        raise SystemExit(f"gemm_sweep: no peaks for {device!r}")
    print(f"# device: {device}, {peak_gbs:.0f} GB/s", flush=True)

    report = {"device": device, "reps": args.reps, "shapes": []}
    for cell, program, seed, bank in itertools.product(
            args.cells.split(","), args.programs.split(","),
            map(int, args.seeds.split(",")), args.banks.split(",")):
        case = build_case(cell, program, seed, args.layer)
        nb, bc = case["nb"], case["bc"]
        D, F = ((case["D"], 2 * case["width"]) if bank == "moe_wi"
                else (case["width"], case["D"]))
        slots = case["L"] * case["S"]
        rule = pick_bank_tile(D, F, bc, 2)
        fetch, reuse, padding = bank_fetch_plan(case["counts"], bc, nb)
        # banks each (order, tile) moves: the parent's order a tile a grid
        # step (its padding blocks re-read the last slot's) unless one tile
        # is the whole F, where its index stands still over a run as ours does
        def moved(order, bf):
            return (fetch if order == "fb" or bf == F else nb) * D * F * 2

        shape = dict(cell=cell, program=program, seed=seed, bank=bank, nb=nb,
                     bc=bc, D=D, F=F, slots=slots, rule=rule, fetch=fetch,
                     reuse=reuse, padding=padding, results=[])
        print(f"\n## {cell} {program} {bank} seed={seed}: x [{nb}, {bc}, {D}] "
              f"x [{slots}, {D}, {F}], {case['live']} of {case['T']} rows "
              f"route; blocks fetch {fetch} reuse {reuse} padding {padding}; "
              f"a bank once {fetch * D * F * 2 / 1e6:.0f} MB = "
              f"{fetch * D * F * 2 / peak_gbs / 1e3:.0f} us at "
              f"{peak_gbs:.0f} GB/s; rule bf={rule}", flush=True)
        s = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
        shapes = (s((nb, bc, D), jnp.bfloat16), s((slots, D, F), jnp.bfloat16),
                  s((nb,), jnp.int32), s((nb,), jnp.int32))
        operands = None
        if not args.compile_only:
            k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
            x = jax.random.normal(k1, shapes[0].shape, jnp.bfloat16)
            # rows past a block's count are zero, as the dispatch leaves them
            keep = (np.arange(bc)[None, :] < case["block_rows"][:, None])
            x = x * jnp.asarray(keep[:, :, None], jnp.bfloat16)
            w = jax.random.normal(k2, shapes[1].shape, jnp.bfloat16) * D ** -0.5
            operands = (x, w, jnp.asarray(case["block_slot"], jnp.int32),
                        jnp.asarray(case["block_rows"], jnp.int32))
        tiles = sorted({int(t) or F for t in args.tiles.split(",")} | {rule})
        first = None  # the parent's output: every other is compared to it
        for order, bf in [("bf", 256)] * (F % 256 == 0) + [
                (o, t) for o in args.orders.split(",") for t in tiles
                if F % t == 0 and (o, t) != ("bf", 256)]:
            fn = (functools.partial(parent_kernel, bf=bf) if order == "bf"
                  else functools.partial(ragged_grouped_gemm, bf=bf))
            row = dict(order=order, bf=bf,
                       **measure(fn, args.reps, shapes, operands))
            mark = " <- rule" if (order, bf) == ("fb", rule) else ""
            if "us_per_call" in row:
                out = np.asarray(row.pop("out"), np.float32)
                first = out if first is None else first
                row["max_diff"] = float(np.abs(out - first).max())
                row["dma_share"] = (moved(order, bf) / (peak_gbs * 1e9)
                                    / (row["us_per_call"] * 1e-6))
                print(f"{order} bf={bf:5d}: {row['us_per_call']:8.1f} us/call, "
                      f"{moved(order, bf) / 1e6:6.0f} MB of banks at "
                      f"{100 * row['dma_share']:5.1f}% of the DMA rate, "
                      f"diff {row['max_diff']} (compile "
                      f"{row['compile_s']:.1f} s){mark}", flush=True)
            else:
                row.pop("out", None)
                print(f"{order} bf={bf:5d}: "
                      + (row.get("error")
                         or f"compiled in {row['compile_s']:.1f} s") + mark,
                      flush=True)
            shape["results"].append(row)
        report["shapes"].append(shape)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(f"\n# wrote {args.out}")


if __name__ == "__main__":
    main()
