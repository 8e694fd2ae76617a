"""Weight-only int8 quantization for the decode-bandwidth-bound serving regime.

Decode reads every weight byte once per step — on a v5e the 819 GB/s HBM
ceiling, not the MXU, bounds single-chip decode throughput. Symmetric per-output-channel int8 halves the weight
bytes against bf16, so the decode roofline doubles, at the cost of a <0.5%-
scale per-channel rounding error. The reference's headline baselines serve
fp8 on B200 (BASELINE.md row 5) — reduced-precision weights are parity, not
a shortcut.

Formulation keeps HBM traffic int8 end to end: with a per-OUTPUT-channel
scale ``s``, ``x @ (w_int8 * s) == (x @ w_int8) * s`` exactly, so the dot
consumes the int8 tensor (XLA fuses the int8→bf16 convert into the dot's
operand stream — no dequantized copy is ever materialised in HBM) and the
scale applies to the matmul OUTPUT, a [*, out] elementwise multiply that
fuses into the surrounding graph.

Quantized: the dense per-layer projections (wq/wk/wv/wo, wi/wo_mlp; a mamba
or mamba2 layer's in and out projections), the
MoE expert banks and shared experts (per-expert per-output-channel scales;
the expert GEMMs then run the einsum path — the Pallas grouped GEMM is
bf16-only), and the unembedding. Kept bf16: norms, biases and the router
(tiny), embed (gather table; also the tie_embeddings source), LoRA deltas
(numerically delicate low-rank). EPLB composes: the redundant-expert
regather moves each slot's weights and its per-expert scales by the same
slot map (engine._eplb_rebalance).

Cited reference behavior: quantized serving is table stakes in the
reference's model servers (vLLM --quantization; fp8 checkpoints on GPU).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

# key → axis NAMES contracted by its matmul (from param_logical_axes); the
# scale lives on every remaining (output/batch) axis — for expert banks that
# includes the experts axis, i.e. per-expert per-output-channel scales
_CONTRACT: dict[str, tuple[str, ...]] = {
    "wq": ("embed",),
    "wk": ("embed",),
    "wv": ("embed",),
    "wo": ("heads", "head_dim"),
    "wi": ("embed",),
    "wo_mlp": ("mlp",),
    "moe_wi": ("embed",),
    "moe_wo": ("expert_mlp",),
    "shared_wi": ("embed",),
    "shared_wo": ("mlp",),
    "mamba_in": ("embed",),
    "mamba_out": ("mamba_inner",),
    "m2_in": ("embed",),
    "m2_out": ("mamba_inner",),
    # (the unembedding quantizes via its own branch below: its source can be
    # embed.T under tie_embeddings, which has no entry in the axes dict)
}

QUANTIZABLE_LAYER_KEYS = ("wq", "wk", "wv", "wo", "wi", "wo_mlp",
                          "moe_wi", "moe_wo", "shared_wi", "shared_wo",
                          "mamba_in", "mamba_out", "m2_in", "m2_out")


def _quantize_one(w: jax.Array, contract_axes: tuple[int, ...]):
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=contract_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=contract_axes)


def quantize_params(cfg, params: dict[str, jax.Array],
                    base_axes: Optional[dict[str, Any]] = None,
                    ) -> tuple[dict[str, jax.Array], dict[str, Any]]:
    """Replace quantizable leaves with ``<key>_q`` int8 + ``<key>_scale`` f32.

    Returns (new params, logical-axes dict matching the NEW tree) so meshed
    engines can shard the quantized leaves exactly like their bf16 ancestors
    (scale axes = the weight's non-contracted axes).
    """
    from llmd_tpu.models.transformer import param_logical_axes

    axes = dict(base_axes or param_logical_axes(cfg))
    out = dict(params)
    for key in QUANTIZABLE_LAYER_KEYS:
        if key not in out:
            continue
        names = axes[key]
        contract = tuple(i for i, n in enumerate(names) if n in _CONTRACT[key])
        q, s = _quantize_one(out.pop(key), contract)
        out[key + "_q"], out[key + "_scale"] = q, s
        axes[key + "_q"] = names
        axes[key + "_scale"] = tuple(n for n in names if n not in _CONTRACT[key])
        del axes[key]

    # unembedding: the [D, V] logits matmul is ~6-10% of a dense model's
    # decode bytes. tie_embeddings models read embed.T — keep embed (the
    # gather table) bf16 and carry an int8 copy for the logits path.
    src = params["embed"].T if cfg.tie_embeddings else out.pop("unembed", None)
    if src is not None:
        q, s = _quantize_one(src, (0,))
        out["unembed_q"], out["unembed_scale"] = q, s
        axes["unembed_q"] = ("embed", "vocab")
        axes["unembed_scale"] = ("vocab",)
        axes.pop("unembed", None)
    return out, axes
