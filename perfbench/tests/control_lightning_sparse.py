#!/usr/bin/env python3
"""The controls of a check on a model with lightning and sparse-attention
layers: the reference in the program's place over the same stack, one thing
wrong at a time.

  int8            the leaves of ``weight_leaves`` rounded to int8 levels with
                  a scale an output channel, under the file's bf16 name (the
                  nearest precision below the one stated; the program offers
                  no int8 for this family, so the rounding is made here)
  bf16_state      the lightning layers' matrix state rounded to bfloat16 after
                  every token, under the file's float32 name
  dense           no selection: every query attends to all its keys
  top_half        half the published top-k (top-32 for top-64)
  rope_on_sparse  RoPE applied to the sparse-attention layers (the model has
                  none)
  no_decay        the lightning layers' decay left out (lam = 1)

    chiprun -- python3 perfbench/tests/control_lightning_sparse.py \\
        --config perfbench/configs/minicpm-sala-9b.json --seeds 11

One process, no server, one stack a seed. Each control is read as ``run.py``
reads the served path: ``worst_deficit``, how far under the sound maximum the
control's own greedy token lies at its worst position, which is what the
check's ``margin`` would read of a served path with that fault; beside it
``gap_error`` (its gap between the sound reference's two best tokens against
the sound gap, over the check's served positions), ``argmax_agree``, and
``fails``: which of the file's own limits (``margin``, ``gap_probe.limit``)
the reading is over, empty for a control the check would pass.
``--faults a,b`` reads some of them only (a whole pass of the reference over
the check's prompts each).

``bf16_state`` stands for the served path only as far as decode goes: the
program rounds its state once a call, this control once a token. What a
served stack with a bfloat16 state reads is read through ``run.py`` itself:
``--manifest perfbench/tests/manifest-sala.json --workload control-bf16-state``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control  # noqa: E402,F401  (puts the repo and perfbench on the path)

# the axis a product contracts over, by leaf (the stacked axis is 0)
_IN_AXES = {"wq": (1,), "wk": (1,), "wv": (1,), "wg": (1,), "wo": (1, 2),
            "wi": (1,), "wo_mlp": (1,), "lin_wq": (2,), "lin_wk": (2,),
            "lin_wv": (2,), "lin_wg": (2,), "lin_wo": (1,)}


def int8_levels(params: dict, leaves) -> dict:
    """``params`` with every leaf of ``leaves`` rounded to 255 levels, a
    scale an output channel and layer, a layer at a time, kept in its type."""
    import jax
    import jax.numpy as jnp

    def one(w, axes):
        wf = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return (jnp.clip(jnp.round(wf / scale), -127, 127) * scale).astype(
            w.dtype)

    out = dict(params)
    for k in leaves:
        axes = tuple(a - 1 for a in _IN_AXES[k])
        f = jax.jit(lambda w, axes=axes: one(w, axes))
        out[k] = jnp.stack([f(params[k][i]) for i in range(params[k].shape[0])])
    return out


FAULTS = {"bf16_state": {"state_dtype": "bfloat16"}, "dense": {"sparse": False},
          "top_half": None, "rope_on_sparse": {"attn_rope": True},
          "no_decay": {"decay": False}}


def read(conf: dict, seed: int, faults: list, cpu: bool = False) -> dict:
    from llmd_tpu.jax_init import init_jax

    init_jax(cpu)
    import jax
    import jax.numpy as jnp

    import run as bench
    from llmd_tpu.models.transformer import init_params

    family = importlib.import_module("reference." + conf["reference"])
    cfg, sizes = family.model_config(conf), family.sizes(conf)
    n = conf["check"]["served_tokens"]
    t0 = time.time()
    params = init_params(cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    prompts = [p for g in bench.check_prompts(conf["check"], seed,
                                              conf["vocab_size"]) for p in g]

    def rows(sz, stack):
        return jnp.concatenate(family.logits_many(
            sz, stack, [p[:-1] for p in prompts], [n] * len(prompts)))

    sound = rows(sizes, params)
    top, at = jax.lax.top_k(sound, 2)
    a, b = at[:, 0], at[:, 1]
    gap = jax.device_get(top[:, 0] - top[:, 1])
    i = jnp.arange(sound.shape[0])
    out = {"seed": seed, "layers": cfg.num_layers, "positions": len(gap),
           "prompt_tokens": [min(map(len, prompts)), max(map(len, prompts))],
           "past_dense_len": sum(len(p) >= sizes["dense_len"]
                                 for p in prompts) * n}

    def against(sz, stack) -> dict:
        r = rows(sz, stack)
        g = jax.device_get(r[i, a] - r[i, b])
        own = r.argmax(axis=-1)
        got = {"gap_error": bench.gap_summary(
                   abs(float(x) - float(y)) for x, y in zip(g, gap)),
               "argmax_agree": int((own == a).sum()),
               "worst_deficit": float((top[:, 0] - sound[i, own]).max()),
               "seconds": time.time() - t0}
        # the file's own limits, as run.py's check_outputs holds them
        chk = conf["check"]
        got["fails"] = [name for name, bad in (
            ("margin", got["worst_deficit"] > chk["margin"]),
            ("gap_probe", "gap_probe" in chk and got["gap_error"]["median"]
             > chk["gap_probe"]["limit"])) if bad]
        return got

    for fault in faults:
        if fault == "int8":
            out[fault] = against(sizes, int8_levels(
                params, family.weight_leaves(conf)))
        else:
            over = FAULTS[fault] or {"topk": sizes["topk"] // 2}
            out[fault] = against(dict(sizes, **over), params)
        print(json.dumps({"seed": seed, fault: out[fault]}), flush=True)
    out["seconds"] = time.time() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--faults", default="int8," + ",".join(FAULTS))
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        conf = json.load(f)
    for kv in args.set:
        k, v = kv.split("=", 1)
        conf[k] = json.loads(v)
    for seed in args.seeds.split(","):
        print(json.dumps(read(conf, int(seed), args.faults.split(","),
                              args.cpu)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
