#!/usr/bin/env python3
"""The controls of a check on the latent-attention, leading-dense,
sigmoid-routed mixture family: what ``control.py`` reads (the reference one
precision lower in the program's place, and a dropped routed copy), and beside
them the faults of this family, each the sound stack under a reference with
one mechanism left out or misplaced (``reference/moe_mla.py``'s switches):

  softmax           softmax scores for sigmoid
  bias_dropped      the selection bias left out of the choice
  bias_in_weights   the selection bias added into the weights too
  no_scaling        routed_scaling_factor dropped
  no_renorm         the chosen weights not renormalised
  no_q_norm         q_a_layernorm dropped
  no_kv_norm        the latent's norm (kv_a_layernorm) dropped
  rope_on_nope      RoPE taken over the content lanes too
  no_shared         the shared expert dropped
  dense_as_expert   layer 0 run as an expert layer (the first mixture's leaves)

    chiprun -- python3 perfbench/tests/control_mla_moe.py \
        --config perfbench/configs/glm-4.7-flash.json --seeds 11,12,13

One process, no server, one stack a seed. Each control is read as ``run.py``
reads the served path: ``gap_error`` (its gap between the sound reference's
two best tokens against the sound gap, over the check's served positions),
``argmax_agree``, and ``worst_deficit``: how far under the sound maximum the
control's own greedy token lies at its worst position, which is what the
check's ``margin`` would read of a served path with that fault.
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

import control  # noqa: E402, F401  (puts the repo and perfbench on the path)

FAULTS = (
    ("softmax", {"scoring": "softmax"}),
    ("bias_dropped", {"bias_in_choice": False}),
    ("bias_in_weights", {"bias_in_weights": True}),
    ("no_scaling", {"scaling": 1.0}),
    ("no_renorm", {"norm_topk": False}),
    ("no_q_norm", {"q_norm": False}),
    ("no_kv_norm", {"kv_norm": False}),
    ("rope_on_nope", {"rope_on_nope": True}),
    ("no_shared", {"shared": False}),
    ("dense_as_expert", {"dense_as_expert": True}),
)


def int8_in_parts(cfg, params: dict) -> dict:
    """The stack with the program's int8 weight-only quantiser applied
    (``quantize_params``: the same values, every scale is of one layer, one
    expert and one output channel), a layer and sixteen experts at a time and
    the head 16,384 rows at a time, each bf16 leaf dropped from ``params`` as
    soon as its int8 form stands: ``control.int8_by_layer`` holds three
    float32 copies of a layer's leaf (4.8 GB for a bank of [64, 2048, 3072])
    beside the 9 GB stack, which a 16 GB chip does not hold."""
    import jax.numpy as jnp

    from llmd_tpu.models.quant import QUANTIZABLE_LAYER_KEYS, quantize_params

    tiny = {"embed": params["embed"][:1]}

    def parts(key, leaf, axis, step):
        outs = [quantize_params(cfg, {**tiny, key: jnp.take(
            leaf, jnp.arange(a, min(a + step, leaf.shape[axis])), axis=axis)})[0]
            for a in range(0, leaf.shape[axis], step)]
        return {k: jnp.concatenate([o[k] for o in outs], axis=min(
            axis, outs[0][k].ndim - 1)) for k in (key + "_q", key + "_scale")}

    low = dict(params)
    for key in [k for k in QUANTIZABLE_LAYER_KEYS if k in params]:
        leaf = low.pop(key)
        del params[key]
        by_layer = [parts(key, leaf[l:l + 1], 1, 16) if key.startswith("moe_")
                    else {k: v for k, v in quantize_params(
                        cfg, {**tiny, key: leaf[l:l + 1]})[0].items()
                        if k.startswith(key + "_")}
                    for l in range(leaf.shape[0])]
        del leaf
        for k in by_layer[0]:
            low[k] = jnp.concatenate([p[k] for p in by_layer])
    head = low.pop("unembed")
    del params["unembed"]
    low.update(parts("unembed", head, 1, 16384))
    return low


def read(conf: dict, seed: int, cpu: bool = False, only=None,
         errors: bool = False) -> dict:
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
           "prompt_tokens": [min(map(len, prompts)), max(map(len, prompts))]}

    probe = conf["check"].get("gap_probe", {})

    def as_probed(err) -> dict:
        """``clean_half`` as the served probe would read it: it finds no
        error over its ``width``."""
        if probe.get("judged") != "clean_half":
            return {}
        w = probe["width"]
        return {"clean_half": bench.clean_half(
            [min(e, w - w / 2 ** probe["rounds"]) for e in err], n,
            w / 2 ** probe["rounds"])}

    def against(sz, stack) -> dict:
        r = rows(sz, stack)
        g = jax.device_get(r[i, a] - r[i, b])
        own = r.argmax(axis=-1)
        err = [abs(float(x) - float(y)) for x, y in zip(g, gap)]
        return {"gap_error": {**bench.gap_summary(err), **as_probed(err)},
                "argmax_agree": int((own == a).sum()),
                "worst_deficit": float((top[:, 0] - sound[i, own]).max()),
                **({"errors": [round(e, 4) for e in err]} if errors else {})}

    for fault, switch in (("top_k-1", {"top_k": sizes["top_k"] - 1}),) + FAULTS:
        if only is None or fault in only:
            out[fault] = against(dict(sizes, **switch), params)
    # one precision lower, last: it takes the stack's bf16 leaves with it
    assert conf["weights"] == {**conf["weights"], "dtype": "bfloat16",
                               "quantize": None}, "a bf16 file's control"
    out["int8"] = against(sizes, int8_in_parts(cfg, params))
    out["seconds"] = time.time() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated faults to read beside int8")
    ap.add_argument("--errors", action="store_true",
                    help="every position's gap error too, in served order")
    args = ap.parse_args()
    with open(args.config) as f:
        conf = json.load(f)
    for kv in args.set:
        k, v = kv.split("=", 1)
        conf[k] = json.loads(v)
    for seed in args.seeds.split(","):
        print(json.dumps(read(
            conf, int(seed), args.cpu,
            None if args.only is None else args.only.split(","),
            args.errors)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
