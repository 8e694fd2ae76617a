#!/usr/bin/env python3
"""The controls of a check on the KDA, latent-attention and group-limited
mixture family (a recurrent mixer over experts in one layer, a share of the
experts held): what ``control_mla_moe.py`` reads of any mixture family (the
reference one precision lower in the program's place, ``int8``, and a dropped
routed copy, ``top_k-1``), and beside them the faults of this family, each the
sound stack under a reference with one mechanism left out or misplaced
(``reference/hybrid_kda_mla_moe.py``'s switches):

  bf16_state       the delta-rule state rounded to bfloat16 a token
  no_group_limit   plain top-k of all the experts (the group limit left out)
  no_delta         b = 0 in I - b k k^T: gated linear attention
  softplus_gate    the gate -exp(A_log) softplus(.) in the safe gate's place
  no_mla_rope      RoPE left off the latent layer
  no_head_gate     the latent layer's head-wise output gate dropped
  no_qk_l2         q and k not normed
  no_out_gate      the KDA layers' output gate dropped
  no_shared        the shared expert dropped
  no_scaling       routed_scaling_factor dropped
  bias_dropped     the selection bias left out of the choice
  absent_computed  the absent experts' copies not masked (they take the bank
                   slot their clipped index names)

    chiprun -- python3 perfbench/tests/control_kda_mla_moe.py \
        --config perfbench/configs/ling-3.0-flash-vl.json --seeds 11,12,13

One process, no server, one stack a seed. A control is read as ``run.py``
reads the served path and judged by the file's own limits, with ``run.py``'s
own functions: its gap between the tokens the family's ``probed_pair`` names
against the sound reference's gap there, cut off at the probe's width and
summed up by ``clean_half`` (``gap_error``; ``gap_error_top2`` is the same
over the sound reference's two best tokens, ``control_mla_moe.py``'s pair,
which the cell does not judge), ``argmax_agree`` and ``worst_deficit``.
``fails`` names the limits of the file's ``check`` that the control is over
(``gap_probe``, ``margin``) and ``correct`` is what the cell's line would say.
"""

from __future__ import annotations

import importlib
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_mla_moe as base  # noqa: E402

FAULTS = (
    ("bf16_state", {"state_dtype": "bfloat16"}),
    ("no_group_limit", {"group_limit": False}),
    ("no_delta", {"delta": False}),
    ("softplus_gate", {"safe_gate": False}),
    ("no_mla_rope", {"mla_rope": False}),
    ("no_head_gate", {"head_gate": False}),
    ("no_qk_l2", {"qk_l2": False}),
    ("no_out_gate", {"out_gate": False}),
    ("no_shared", {"shared": False}),
    ("no_scaling", {"scaling": 1.0}),
    ("bias_dropped", {"bias_in_choice": False}),
    ("absent_computed", {"absent_left_out": False}),
)


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
    chk = conf["check"]
    n, probe = chk["served_tokens"], chk["gap_probe"]
    width, res = probe["width"], probe["width"] / 2 ** probe["rounds"]
    t0 = time.time()
    params = init_params(cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    prompts = [p for g in bench.check_prompts(chk, seed, conf["vocab_size"])
               for p in g]

    def rows(sz, stack):
        return jnp.concatenate(family.logits_many(
            sz, stack, [p[:-1] for p in prompts], [n] * len(prompts)))

    sound = rows(sizes, params)
    i = jnp.arange(sound.shape[0])
    top, at = jax.lax.top_k(sound, 2)
    pairs = {"gap_error": family.probed_pair(
        sound, rows({**sizes, "mla_rope": False}, params)),
        "gap_error_top2": [[int(a), int(b), float(g)] for (a, b), g in zip(
            jax.device_get(at), jax.device_get(top[:, 0] - top[:, 1]))]}
    out = {"seed": seed, "layers": cfg.num_layers, "positions": len(i),
           "prompt_tokens": [min(map(len, prompts)), max(map(len, prompts))],
           "limits": {"gap_probe": probe["limit"], "margin": chk["margin"]}}

    def against(sz, stack) -> dict:
        r = rows(sz, stack)
        got = {}
        for name, pair in pairs.items():
            a, b, g = (jnp.asarray(c) for c in zip(*pair))
            err = [min(float(e), width - res)
                   for e in jax.device_get(jnp.abs(r[i, a] - r[i, b] - g))]
            got[name] = {**bench.gap_summary(err),
                         "clean_half": bench.clean_half(err, n, res)}
            if errors:
                got[name]["errors"] = [round(e, 4) for e in err]
        own = r.argmax(axis=-1)
        got["argmax_agree"] = int((own == at[:, 0]).sum())
        got["worst_deficit"] = float((top[:, 0] - sound[i, own]).max())
        got["fails"] = [k for k, v in (
            ("gap_probe", got["gap_error"][probe["judged"]]),
            ("margin", got["worst_deficit"])) if not v <= out["limits"][k]]
        got["correct"] = not got["fails"]
        return got

    for fault, switch in (("top_k-1", {"top_k": sizes["top_k"] - 1}),) + FAULTS:
        if only is None or fault in only:
            out[fault] = against(dict(sizes, **switch), params)
    # one precision lower, last: it takes the stack's bf16 leaves with it
    assert conf["weights"] == {**conf["weights"], "dtype": "bfloat16",
                               "quantize": None}, "a bf16 file's control"
    out["int8"] = against(sizes, base.int8_in_parts(cfg, params))
    out["seconds"] = time.time() - t0
    return out


if __name__ == "__main__":
    base.read = read
    sys.exit(base.main())
