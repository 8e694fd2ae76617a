#!/usr/bin/env python3
"""The KDA kernel (ops/kda_attention.py) at the benchmark configuration's
published shapes (32 heads of 128, 6 layers of 65 slots folded into the
pool), on the chip: against its XLA form on both step programs' calls, rows
that are not live left bit for bit, a chunk whole against the same chunk in
two calls that start on the block, and the time of a call by heads a grid
step.

    chiprun -- python3 tools/kda_sweep.py               # ~2 min
    python3 tools/kda_sweep.py --compile-only           # here: what Mosaic takes

One line of JSON a reading. ``decode``: 64 rows of one token (the fused
call); ``unified``: 63 one-token rows and a chunk of 256 tokens. ``us_a_call``
is one layer's call; ``gbps`` the live rows' states read and written over it.
The block is the engine's (``ops/lightning_attention.BLOCK``, 16 tokens: the
Neumann product is written out for it), so the heads a grid step are the one
knob.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, D, LAYERS, SEATS = 32, 128, 6, 64


def calls(jnp, np, jax, seed=0):
    """{name: args of a call} at the published shapes."""
    out = {}
    for name, lens in (("decode", [1] * 64), ("unified", [1] * 63 + [256])):
        nt, nb = sum(lens), len(lens)
        k = jax.random.split(jax.random.PRNGKey(seed), 6)

        def unit(x):
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        out[name] = dict(
            q=unit(jax.random.normal(k[0], (nt, H, D))) * D ** -0.5,
            k=unit(jax.random.normal(k[1], (nt, H, D))),
            v=jax.random.normal(k[2], (nt, H, D)),
            g=-5.0 * jax.nn.sigmoid(
                2.0 * jax.random.normal(k[3], (nt, H, D)) - 3.0),
            b=jax.nn.sigmoid(jax.random.normal(k[4], (nt, H))),
            pool=jax.random.normal(k[5], (LAYERS * (SEATS + 1), H, D, D)),
            slots=jnp.asarray(2 * (SEATS + 1) + np.arange(nb), jnp.int32),
            cu_q_lens=jnp.asarray(np.concatenate([[0], np.cumsum(lens)]),
                                  jnp.int32),
            live=jnp.asarray(np.arange(nb) % 7 != 3),
            fresh=jnp.asarray(np.arange(nb) % 11 == 5))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="1,2,4,8",
                    help="heads of a slot's state a grid step holds")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    heads = [int(b) for b in args.heads.split(",")]
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmd_tpu.ops.kda_attention import (BLOCK, head_block,
                                            kda_attention_pallas,
                                            kda_attention_xla)

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        for name, a in calls(jnp, np, jax).items():
            shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                      for k, v in a.items()}
            for hb in heads:
                t = time.time()
                try:
                    jax.jit(lambda kw, hb=hb: kda_attention_pallas(
                        **kw, hb=hb)).lower(shapes).compile()
                    said = "compiles"
                except Exception as e:  # noqa: BLE001: the compiler's words
                    said = str(e)[:300]
                print(json.dumps({"call": name, "heads": hb, "mosaic": said,
                                  "seconds": round(time.time() - t, 1)}))
        return 0
    if not args.cpu and jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    interpret = args.cpu
    for name, a in calls(jnp, np, jax).items():
        want_y, want_pool = jax.jit(lambda kw: kda_attention_xla(**kw))(a)
        dead = np.asarray(a["slots"])[~np.asarray(a["live"])]
        live_rows = int(np.asarray(a["live"]).sum())
        rest = {k: v for k, v in a.items() if k != "pool"}
        for hb in heads:
            # the pool is donated, as the engine's step programs donate it:
            # without, XLA copies the whole pool (0.8 GB) around every call
            f = jax.jit(lambda pool, kw, hb=hb: kda_attention_pallas(
                **kw, pool=pool, hb=hb, interpret=interpret),
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
                "call": name, "heads": hb, "served": hb == head_block(H),
                "us_a_call": round(us, 1),
                "gbps": round(live_rows * 2 * H * D * D * 4 / us / 1e3, 1),
                **first}), flush=True)
    # a chunk whole and in two calls that start on the block: the same bits
    a = calls(jnp, np, jax)["unified"]
    chunk = slice(63, 63 + 256)
    one = {k: a[k][chunk] for k in "qkvgb"}
    row = dict(slots=jnp.asarray([7], jnp.int32), live=jnp.asarray([True]))
    f = jax.jit(lambda pool, kw: kda_attention_pallas(
        **kw, pool=pool, interpret=interpret))
    y, p = f(a["pool"], dict(one, **row, fresh=jnp.asarray([True]),
                             cu_q_lens=jnp.asarray([0, 256], jnp.int32)))
    ys, pool = [], a["pool"]
    for at, n in ((0, 5 * BLOCK), (5 * BLOCK, 256 - 5 * BLOCK)):
        part = {k: v[at:at + n] for k, v in one.items()}
        y2, pool = f(pool, dict(part, **row, fresh=jnp.asarray([at == 0]),
                                cu_q_lens=jnp.asarray([0, n], jnp.int32)))
        ys.append(y2)
    print(json.dumps({
        "check": "a chunk whole and in two calls",
        "y_bit_for_bit": bool((jnp.concatenate(ys) == y).all()),
        "state_bit_for_bit": bool((pool[7] == p[7]).all())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
