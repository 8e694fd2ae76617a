#!/usr/bin/env python3
"""The Mamba-2 kernel (ops/mamba2_ssd.py) at Nemotron-3-Nano's published
shapes, on the chip: against its XLA form on both step programs' calls, rows
that are not live left bit for bit, and the time of a call by block size.

    chiprun -- python3 tools/mamba2_sweep.py            # ~3 min
    python3 tools/mamba2_sweep.py --compile-only        # here: which blocks Mosaic takes

One line of JSON a reading. ``decode``: 64 rows of one token (the fused
call); ``unified``: 63 one-token rows and a chunk of 256 tokens. ``us_a_call``
is one layer's call; ``gbps`` the live rows' states read and written over it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, P, G, N, LAYERS, SEATS = 64, 64, 8, 128, 6, 64


def calls(jnp, np, jax, seed=0):
    """{name: args of a call} at the published shapes."""
    out = {}
    for name, lens in (("decode", [1] * 64), ("unified", [1] * 63 + [256])):
        nt, nb = sum(lens), len(lens)
        k = jax.random.split(jax.random.PRNGKey(seed), 6)
        out[name] = dict(
            x=jax.random.normal(k[0], (nt, H, P)).astype(jnp.bfloat16),
            dt=jax.nn.softplus(jax.random.normal(k[1], (nt, H)) - 4.0),
            A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77)),
            Bm=jax.random.normal(k[3], (nt, G, N)).astype(jnp.bfloat16),
            Cm=jax.random.normal(k[4], (nt, G, N)).astype(jnp.bfloat16),
            pool=jax.random.normal(k[5], (LAYERS * (SEATS + 1), G, N, H // G * P)),
            slots=jnp.asarray(2 * (SEATS + 1) + np.arange(nb), jnp.int32),
            cu_q_lens=jnp.asarray(np.concatenate([[0], np.cumsum(lens)]),
                                  jnp.int32),
            live=jnp.asarray(np.arange(nb) % 7 != 3),
            fresh=jnp.asarray(np.arange(nb) % 11 == 5))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="16,32,64,128")
    ap.add_argument("--groups", default="2",
                    help="groups of a slot's state a grid step holds")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    blocks = [int(b) for b in args.blocks.split(",")]
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmd_tpu.ops.mamba2_ssd import mamba2_ssd_pallas, mamba2_ssd_xla

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        for name, a in calls(jnp, np, jax).items():
            shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                      for k, v in a.items()}
            for blk, cols in [(b, int(c)) for c in args.groups.split(",")
                              for b in blocks]:
                t = time.time()
                try:
                    jax.jit(lambda kw, blk=blk, cols=cols: mamba2_ssd_pallas(
                        **kw, block=blk, groups=cols)).lower(shapes).compile()
                    said = "compiles"
                except Exception as e:  # noqa: BLE001: the compiler's words
                    said = str(e)[:300]
                print(json.dumps({"call": name, "block": blk, "groups": cols,
                                  "mosaic": said,
                                  "seconds": round(time.time() - t, 1)}))
        return 0
    if not args.cpu and jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    interpret = args.cpu
    for name, a in calls(jnp, np, jax).items():
        want_y, want_pool = jax.jit(lambda kw: mamba2_ssd_xla(**kw))(a)
        dead = np.asarray(a["slots"])[~np.asarray(a["live"])]
        live_rows = int(np.asarray(a["live"]).sum())
        rest = {k: v for k, v in a.items() if k != "pool"}
        for blk, groups in [(b, int(c)) for c in args.groups.split(",")
                            for b in blocks]:
            # the pool is donated, as the engine's step programs donate it:
            # without, XLA copies the whole pool (0.8 GB) around every call
            f = jax.jit(lambda pool, kw, blk=blk, groups=groups:
                        mamba2_ssd_pallas(**kw, pool=pool, block=blk,
                                          groups=groups, interpret=interpret),
                        donate_argnums=0)
            y, pool = f(a["pool"] + 0.0, rest)
            jax.block_until_ready(pool)
            first = {"y_max_diff": float(jnp.abs(y - want_y).max()),
                     "y_scale": float(jnp.abs(want_y).max()),
                     "pool_max_diff": float(jnp.abs(pool - want_pool).max()),
                     "dead_slots_bit_for_bit": bool(
                         (pool[dead] == a["pool"][dead]).all())}
            n = 1 if args.cpu else 20
            t = time.time()
            for _ in range(n):
                y, pool = f(pool, rest)
            jax.block_until_ready(pool)
            us = (time.time() - t) / n * 1e6
            print(json.dumps({
                "call": name, "block": blk, "groups": groups,
                "us_a_call": round(us, 1),
                "gbps": round(live_rows * 2 * N * H * P * 4 / us / 1e3, 1),
                **first}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
