#!/usr/bin/env python3
"""The KDA kernel (ops/kda_attention.py) at the benchmark configuration's
published shapes (32 heads of 128, 6 layers of 65 slots folded into the
pool), on the chip: against its XLA form on both step programs' calls, rows
that are not live left bit for bit, a chunk whole against the same chunk in
two calls that start on the block, and the time of a call by heads a grid
step.

    chiprun -- python3 tools/kda_sweep.py               # ~2 min
    python3 tools/kda_sweep.py --compile-only           # here: what Mosaic takes
    git show <parent>:llmd_tpu/ops/kda_attention.py > .scratch/parent_kda.py
    chiprun -- python3 tools/kda_sweep.py --heads 8,4 \
        --parent .scratch/parent_kda.py                 # ~1.5 min

One line of JSON a reading. ``decode``: 64 rows of one token (the fused
call), 55 of them live; ``decode32``: the same with 32 live; ``unified``: 63
one-token rows and a chunk of 256 tokens. ``us_a_call`` is one layer's call;
``gbps`` the live rows' states read and written over it; ``first_call``
lines say what a call site costs a launch before the chip sees it (trace and
lower: every launch pays it, the compile cache keeps the Mosaic compile
alone). The block is the engine's (``ops/lightning_attention.BLOCK``, 16
tokens: the Neumann product is written out for it), so the heads a grid step
are the one knob. ``--parent <file>`` loads the parent commit's
``ops/kda_attention.py`` beside the tree's and times it in the same chip
call: ``parent_us_a_call`` and ``parent_gbps`` beside each reading,
``chunk_diff_vs_parent`` (the tokens and the slot of a row of more than one
token: must be 0.0, a block of several tokens is the parent's arithmetic)
and ``one_token_diff_vs_parent``.
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
    for name, lens in (("decode", [1] * 64), ("decode32", [1] * 64),
                       ("unified", [1] * 63 + [256])):
        nt, nb = sum(lens), len(lens)
        live = np.arange(nb) % 2 == 0 if name == "decode32" \
            else np.arange(nb) % 7 != 3
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
            live=jnp.asarray(live),
            fresh=jnp.asarray(np.arange(nb) % 11 == 5))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="1,2,4,8",
                    help="heads of a slot's state a grid step holds")
    ap.add_argument("--parent", default="",
                    help="the parent commit's ops/kda_attention.py: timed "
                    "beside the tree's in the same call")
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
            if name == "decode32":  # decode's shapes
                continue
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
    files = {"tree": kda_attention_pallas}
    if args.parent:
        import importlib.util

        spec = importlib.util.spec_from_file_location("parent_kda",
                                                      args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        files["parent"] = parent.kda_attention_pallas
    n = 1 if args.cpu else 20
    for name, a in calls(jnp, np, jax).items():
        want_y, want_pool = jax.jit(lambda kw: kda_attention_xla(**kw))(a)
        live = np.asarray(a["live"])
        dead = np.asarray(a["slots"])[~live]
        lens = np.diff(np.asarray(a["cu_q_lens"]))
        long_tokens = np.repeat(lens > 1, lens)  # of a row of several tokens
        nbytes = int(live.sum()) * 2 * H * D * D * 4
        rest = {k: v for k, v in a.items() if k != "pool"}
        for hb in heads:
            line = {}
            for which, kernel in files.items():
                # the pool is donated, as the engine's step programs donate
                # it: without, XLA copies the whole pool (0.8 GB) every call
                f = jax.jit(lambda pool, kw, hb=hb, kernel=kernel: kernel(
                    **kw, pool=pool, hb=hb, interpret=interpret),
                    donate_argnums=0)
                pool = a["pool"] + 0.0
                t = time.time()
                lowered = f.lower(pool, rest)
                t_lower = time.time() - t
                f = lowered.compile()
                print(json.dumps({
                    "first_call": name, "heads": hb, "file": which,
                    "trace_and_lower_s": round(t_lower, 2),
                    "compile_s": round(time.time() - t - t_lower, 2)}),
                    flush=True)
                y, pool = f(pool, rest)
                pre = "" if which == "tree" else "parent_"
                line[pre + "y_max_diff"] = float(jnp.abs(y - want_y).max())
                line[pre + "pool_max_diff"] = float(
                    jnp.abs(pool - want_pool).max())
                if which == "tree":
                    tree_y, tree_rows = y, pool[a["slots"]]
                    line.update(y_scale=float(jnp.abs(want_y).max()),
                                dead_slots_bit_for_bit=bool(
                                    (pool[dead] == a["pool"][dead]).all()))
                else:
                    dy = np.asarray(jnp.abs(tree_y - y).max(axis=(1, 2)))
                    dp = np.asarray(jnp.abs(
                        tree_rows - pool[a["slots"]]).max(axis=(1, 2, 3)))
                    line["one_token_diff_vs_parent"] = float(
                        dy[~long_tokens].max())
                    if long_tokens.any():
                        line["chunk_diff_vs_parent"] = float(max(
                            dy[long_tokens].max(),
                            dp[live & (lens > 1)].max()))
                t = time.time()
                for _ in range(n):
                    y, pool = f(pool, rest)
                jax.block_until_ready(pool)
                us = (time.time() - t) / n * 1e6
                line[pre + "us_a_call"] = round(us, 1)
                line[pre + "gbps"] = round(nbytes / us / 1e3, 1)
            print(json.dumps({"call": name, "heads": hb,
                              "served": hb == head_block(H), **line}),
                  flush=True)
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
    # a one-token row beside a chunk and in a decode-shaped call: the same
    # bits (a request's tokens pass through both step programs)
    y, p = f(a["pool"], {k: v for k, v in a.items() if k != "pool"})
    rows = {k: a[k][:64] for k in "qkvgb"}
    y2, p2 = f(a["pool"], dict(
        rows, slots=a["slots"], live=a["live"], fresh=a["fresh"],
        cu_q_lens=jnp.asarray(np.minimum(np.arange(65), 63), jnp.int32)))
    s63 = a["slots"][:63]
    print(json.dumps({
        "check": "63 one-token rows beside a chunk and in a decode call",
        "y_bit_for_bit": bool((y[:63] == y2[:63]).all()),
        "state_bit_for_bit": bool((p[s63] == p2[s63]).all())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
