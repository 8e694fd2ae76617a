#!/usr/bin/env python3
"""The control of a configuration's check: the reference put in the program's
place and computed one precision below the one the file states. What it reads
is what a limit has to stand under (steps 2 to 5 of "How correct is decided").

    chiprun -- python3 perfbench/tests/control.py \
        --config perfbench/tests/moe-shape.json --seeds 11,12,13

For each seed, in one process and with no server: the stack as the engine
would serve it (the program's ``init_params`` from the seed, quantised as the
file says), the configuration's check prompts, and the family's float32
reference at each prompt's last ``served_tokens`` positions. Then the same
reference over the stack one step lower:

  bfloat16 -> int8   weight-only, by the program's quantiser, a layer at a
                     time (its whole-stack call needs three float32 copies of
                     a leaf)
  int8 -> int4       the served int8 values rounded to 15 levels, scales kept
  float32 -> bf16    the leaves of ``weight_leaves`` rounded to bfloat16

and, for a mixture of experts, the sound stack with every token's weakest
routed copy dropped (``top_k-1``), a fault of dispatch and not of precision.

Read of each control, as ``run.py`` reads them of the served path:
``gap_error``, how far its gap between the sound reference's two best tokens
lies from the sound gap (``run.gap_summary``: lower quartile, median,
maximum over the positions), and ``argmax_agree``, at how many positions its
greedy token is the sound one. ``wrong_token`` is the least and the median
deficit that a token drawn at random reads at those positions (the prompts'
own): what the ``margin`` over a served token's worst deficit stands under.
The sound side of a limit is read from served runs (the ``check`` line of
``run.py``).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def int8_by_layer(cfg, params: dict) -> dict:
    """``quantize_params`` of a stack, a layer at a time: the same values
    (every scale is of one layer), without the whole leaf in float32."""
    import jax.numpy as jnp

    from llmd_tpu.models.quant import quantize_params
    from llmd_tpu.models.transformer import param_logical_axes

    axes = param_logical_axes(cfg)
    stacked = [k for k in params if axes[k][0] == "layers"]
    low, _ = quantize_params(
        cfg, {k: v for k, v in params.items() if k not in stacked})
    parts = [quantize_params(cfg, {k: params[k][l:l + 1] for k in stacked}
                             | {"embed": params["embed"][:1]})[0]
             for l in range(cfg.num_layers)]
    for k in parts[0]:
        if k.removesuffix("_q").removesuffix("_scale") in stacked:
            low[k] = jnp.concatenate([p[k] for p in parts])
    return low


def lower(cfg, conf: dict, leaves, params: dict) -> tuple:
    """(name, the stack one precision below the one ``conf`` states)."""
    import jax.numpy as jnp

    if conf["weights"]["quantize"] == "int8":
        low = dict(params)
        for k in [k for k in params if k.endswith("_q")]:
            low[k] = jnp.round(params[k].astype(jnp.float32) * (7 / 127)
                               ).astype(jnp.int8)
            s = k[:-2] + "_scale"
            low[s] = params[s] * (127 / 7)
        return "int4", low
    if conf["weights"]["dtype"] == "bfloat16":
        return "int8", int8_by_layer(cfg, params)
    return "bfloat16", dict(params, **{
        k: params[k].astype(jnp.bfloat16).astype(params[k].dtype)
        for k in leaves})


def read(conf: dict, seed: int, cpu: bool = False) -> dict:
    from llmd_tpu.jax_init import init_jax

    init_jax(cpu)
    import jax
    import jax.numpy as jnp

    import run as bench
    from llmd_tpu.models.quant import quantize_params
    from llmd_tpu.models.transformer import init_params
    from reference import dense_gqa

    family = importlib.import_module("reference." + conf["reference"])
    cfg, sizes = family.model_config(conf), family.sizes(conf)
    n = conf["check"]["served_tokens"]
    t0 = time.time()
    params = init_params(cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    if conf["weights"]["quantize"] == "int8":
        params, _ = quantize_params(cfg, params)
    prompts = [p for g in bench.check_prompts(conf["check"], seed,
                                              conf["vocab_size"]) for p in g]
    sound = family.readings(sizes, params, [p[:-n] for p in prompts],
                            [p[-n:] for p in prompts])
    top2 = [t for ts in sound["top2"] for t in ts]
    wrong = sorted(d for ds in sound["deficits"] for d in ds)
    out = {"seed": seed, "layers": cfg.num_layers, "positions": len(top2),
           "wrong_token": {"min": wrong[0], "median": wrong[len(wrong) // 2]}}

    def against(sz, stack) -> dict:
        rows = jnp.concatenate(dense_gqa.logits_many(
            sz, stack, [p[:-1] for p in prompts], [n] * len(prompts),
            family.make_block))
        a, b = (jnp.asarray([t[i] for t in top2]) for i in (0, 1))
        at = jnp.arange(len(top2))
        gap = jax.device_get(rows[at, a] - rows[at, b])
        return {"gap_error": bench.gap_summary(
                    abs(float(x) - t[2]) for x, t in zip(gap, top2)),
                "argmax_agree": int((rows.argmax(axis=-1) == a).sum())}

    name, low = lower(cfg, conf, family.weight_leaves(conf), params)
    out[name] = against(sizes, low)
    del low
    if "top_k" in sizes:
        out["top_k-1"] = against(dict(sizes, top_k=sizes["top_k"] - 1), params)
    out["seconds"] = time.time() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="replace a top-level key of the file, as a rehearsal "
                         "manifest's config overrides do")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        conf = json.load(f)
    for kv in args.set:
        k, v = kv.split("=", 1)
        conf[k] = json.loads(v)
    for seed in args.seeds.split(","):
        print(json.dumps(read(conf, int(seed), args.cpu)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
