#!/usr/bin/env python3
"""The controls of a check on a model with Mamba layers: the reference in the
program's place over the same stack, one thing wrong at a time.

  int8            int8 weights under the file's bf16 name (the program's
                  quantiser, a stacked leaf's row at a time)
  bf16_state      the SSM state rounded to bfloat16 after every token, under
                  the file's float32 name
  no_inner_norms  the RMSNorms on dt, B and C left out
  no_conv_bias    the conv's bias left out
  no_d            the skip term D * x left out
  no_gate         the gate silu(z) left out
  rope_on_attn    RoPE applied to the attention layers (the model has none)
  state_reset     the state and the conv window dropped at every chunk's
                  start (``engine.prefill_chunk`` tokens)

    chiprun -- python3 perfbench/tests/control_hybrid_ssm.py \\
        --config perfbench/configs/jamba2-3b.json --seeds 11,12,13

One process, no server, one stack a seed. Each control is read as ``run.py``
reads the served path: ``gap_error`` (its gap between the sound reference's
two best tokens against the sound gap, over the check's served positions),
``argmax_agree``, and ``worst_deficit``: how far under the sound maximum the
control's own greedy token lies at its worst position, which is what the
check's ``margin`` would read of a served path with that fault.

``bf16_state`` stands for the served path only as far as decode goes: the
program rounds its state once a call (a chunk, or a decode step), this
control once a token. What a served stack with a bfloat16 state reads is read
through ``run.py`` itself: ``--manifest perfbench/tests/manifest-jamba.json
--workload control-bf16-state`` (the file's own size, ``state.ssm_dtype``
replaced).
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


def int8_by_row(cfg, params: dict) -> dict:
    """``quantize_params`` of a stack whose kinds of layer have leaves of
    their own depth, a row of a stacked leaf at a time: the same values
    (every scale is of one layer), without a whole leaf in float32."""
    import jax.numpy as jnp

    from llmd_tpu.models.quant import quantize_params
    from llmd_tpu.models.transformer import param_logical_axes

    axes = param_logical_axes(cfg)
    stacked = [k for k in params if axes[k][0] == "layers"]
    low, _ = quantize_params(
        cfg, {k: v for k, v in params.items() if k not in stacked})
    for k in stacked:
        rows = [quantize_params(cfg, {k: params[k][i:i + 1],
                                      "embed": params["embed"][:1]})[0]
                for i in range(params[k].shape[0])]
        for name in rows[0]:
            if name.removesuffix("_q").removesuffix("_scale") == k:
                low[name] = jnp.concatenate([r[name] for r in rows])
    return low


def read(conf: dict, seed: int, cpu: bool = False) -> dict:
    from llmd_tpu.jax_init import init_jax

    init_jax(cpu)
    import jax
    import jax.numpy as jnp

    import run as bench
    from llmd_tpu.models.transformer import init_params
    from reference import dense_gqa

    family = importlib.import_module("reference." + conf["reference"])
    cfg, sizes = family.model_config(conf), family.sizes(conf)
    n = conf["check"]["served_tokens"]
    t0 = time.time()
    params = init_params(cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    prompts = [p for g in bench.check_prompts(conf["check"], seed,
                                              conf["vocab_size"]) for p in g]

    def rows(sz, stack):
        return jnp.concatenate(dense_gqa.logits_many(
            sz, stack, [p[:-1] for p in prompts], [n] * len(prompts),
            family.make_block))

    sound = rows(sizes, params)
    top, at = jax.lax.top_k(sound, 2)
    a, b = at[:, 0], at[:, 1]
    gap = jax.device_get(top[:, 0] - top[:, 1])
    i = jnp.arange(sound.shape[0])
    out = {"seed": seed, "layers": cfg.num_layers, "positions": len(gap),
           "prompt_tokens": [min(map(len, prompts)), max(map(len, prompts))]}

    def against(sz, stack) -> dict:
        r = rows(sz, stack)
        g = jax.device_get(r[i, a] - r[i, b])
        own = r.argmax(axis=-1)
        return {"gap_error": bench.gap_summary(
                    abs(float(x) - float(y)) for x, y in zip(g, gap)),
                "argmax_agree": int((own == a).sum()),
                "worst_deficit": float((top[:, 0] - sound[i, own]).max())}

    low = int8_by_row(cfg, params)
    out["int8"] = against(sizes, low)
    del low
    for fault, sz in (
            ("bf16_state", dict(sizes, state_dtype="bfloat16")),
            ("no_inner_norms", dict(sizes, inner_norms=False)),
            ("no_conv_bias", dict(sizes, conv_bias=False)),
            ("no_d", dict(sizes, skip_d=False)),
            ("no_gate", dict(sizes, gate=False)),
            ("rope_on_attn", dict(sizes, attn_rope=True)),
            ("state_reset", dict(sizes, reset_every=conf["engine"][
                "prefill_chunk"]))):
        out[fault] = against(sz, params)
    out["seconds"] = time.time() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
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
