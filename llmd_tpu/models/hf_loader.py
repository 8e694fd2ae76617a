"""HF checkpoint loading: config.json → ModelConfig, safetensors → stacked params.

The reference serves HF checkpoints (Qwen3-32B, Llama-70B, gpt-oss-120b —
/root/reference/guides/optimized-baseline/README.md:22-28,
guides/wide-ep-lws/README.md:406-414) through vLLM's weight loader; this module is
the TPU-native equivalent feeding our scanned-stack layout
(``llmd_tpu.models.transformer``): per-layer HF tensors are transposed into the
matmul-ready ``[D, H, Dh]``-style orientations and stacked into single
``[num_layers, ...]`` leaves so the layer stack runs under one ``lax.scan``.

Supported architectures (config.json ``architectures[0]``):
- ``LlamaForCausalLM`` / ``MistralForCausalLM`` — GQA, SwiGLU, optional tied embeddings
- ``Qwen2ForCausalLM`` — adds q/k/v projection biases
- ``Qwen3ForCausalLM`` — adds per-head q/k RMSNorm and an explicit ``head_dim``
- ``JambaForCausalLM`` with ``num_experts: 1`` — Mamba layers (Mamba-1 with
  inner RMSNorms on dt, B and C) around one NoPE attention layer every
  ``attn_layer_period``, a dense SwiGLU MLP in every layer, each kind's leaves
  stacked over the layers of that kind (``_load_jamba_params``)
- ``Glm4MoeLiteForCausalLM`` / ``DeepseekV3ForCausalLM`` with one routing
  group — latent attention with a q-side low-rank projection, leading dense
  layers, sigmoid routing with a selection bias and a scaling factor, shared
  experts (``_load_latent_moe_params``); the multi-token prediction layer past
  ``num_hidden_layers`` is skipped

Handles single-file ``model.safetensors`` and sharded
``model.safetensors.index.json`` checkpoints; weights are cast to the target dtype
(bfloat16 for serving — MXU-native; float32 for parity tests against the HF
reference implementation).
"""

from __future__ import annotations

import json
import logging
import os
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from llmd_tpu.models.config import ModelConfig

_ARCH_FAMILY = {
    "LlamaForCausalLM": "llama",
    "MistralForCausalLM": "llama",
    "Qwen2ForCausalLM": "qwen2",
    "Qwen3ForCausalLM": "qwen3",
    "JambaForCausalLM": "jamba",
    "Glm4MoeLiteForCausalLM": "latent_moe",
    "DeepseekV3ForCausalLM": "latent_moe",
}

log = logging.getLogger(__name__)


def is_hf_checkpoint(path: str) -> bool:
    return os.path.isdir(path) and os.path.isfile(os.path.join(path, "config.json"))


def config_from_hf(path: str, dtype: str = "bfloat16") -> ModelConfig:
    """Translate an HF ``config.json`` into our ``ModelConfig``."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    archs = hf.get("architectures") or []
    arch = archs[0] if archs else "LlamaForCausalLM"
    family = _ARCH_FAMILY.get(arch)
    if arch == "NemotronHForCausalLM" or hf.get("model_type") == "nemotron_h":
        # The program serves the family (Mamba-2, attention and expert
        # layers of one sublayer each: ModelConfig.single_sublayer); what is
        # refused is the mapping of a checkpoint's tensors onto its leaves,
        # which waits until a checkpoint's tensor index is in the repository
        # to be written against.
        raise ValueError(
            f"model_type nemotron_h ({arch}) in {path}: the program serves "
            "this family from drawn weights (models/registry.py "
            "'tiny-nemotron-h', perfbench/configs/nemotron-3-nano-30b-a3b."
            "json), but this loader has no tensor map for nemotron_h "
            "checkpoints yet")
    if family is None:
        raise ValueError(
            f"unsupported architecture {arch!r}; supported: {sorted(_ARCH_FAMILY)}"
        )
    if family == "jamba":
        return _jamba_config(hf, path, arch, dtype)
    if family == "latent_moe":
        return _latent_moe_config(hf, path, arch, dtype)
    scaling = hf.get("rope_scaling")
    if scaling and scaling.get("rope_type", scaling.get("type", "default")) != "default":
        # Loading would succeed but produce silently wrong logits (scaled RoPE
        # frequencies are not applied) — refuse instead.
        raise ValueError(
            f"unsupported rope_scaling {scaling!r} in {path}; only default RoPE "
            "is implemented"
        )
    if hf.get("sliding_window") is not None and hf.get("use_sliding_window", True):
        # Same silent-corruption class: full attention past the window would
        # diverge from the reference implementation. The program serves
        # sliding-window layers (ModelConfig.attn_window_pattern); what is
        # refused here is the mapping, which this loader does not have: which
        # layers a checkpoint's one `sliding_window` applies to differs by
        # family (every layer for Mistral, those past `max_window_layers` for
        # Qwen2) and no checkpoint here tests either.
        raise ValueError(
            f"sliding_window={hf['sliding_window']} in {path}: the program "
            "serves window layers (ModelConfig.attn_window_pattern), but this "
            "loader maps no checkpoint's window onto its layers yet; it "
            "refuses rather than serve full attention under the window's name"
        )
    D = int(hf["hidden_size"])
    H = int(hf["num_attention_heads"])
    return ModelConfig(
        name=os.path.basename(os.path.normpath(path)) or arch,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=D,
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=H,
        num_kv_heads=int(hf.get("num_key_value_heads", H)),
        head_dim=int(hf.get("head_dim") or D // H),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=float(hf.get("rms_norm_eps", 1e-6)),
        max_position=int(hf.get("max_position_embeddings", 32768)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=dtype,
        qk_norm=family == "qwen3",
        # honour an explicit attention_bias on any family; qwen2's default is True
        attn_bias=bool(hf.get("attention_bias", family == "qwen2")),
    )


def _jamba_config(hf: dict, path: str, arch: str, dtype: str) -> ModelConfig:
    """JambaConfig -> ModelConfig; what the program cannot express is refused
    by the key's name."""
    only = {"num_experts": 1, "sliding_window": None, "mamba_proj_bias": False,
            "hidden_act": "silu"}
    for key, want in only.items():
        if hf.get(key, want) != want:
            raise ValueError(f"{key}={hf[key]!r} in {path}: the program has "
                             f"only {key}={want!r} for {arch}")
    D, H = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    per, off = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    rank = hf.get("mamba_dt_rank", "auto")
    return ModelConfig(
        name=os.path.basename(os.path.normpath(path)) or arch,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=D,
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=H,
        num_kv_heads=int(hf.get("num_key_value_heads", H)),
        head_dim=D // H,
        rms_eps=float(hf.get("rms_norm_eps", 1e-6)),
        max_position=int(hf.get("max_position_embeddings", 262144)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=dtype,
        rope_pattern=(False,),  # no positional encoding on any layer
        layer_kinds=tuple("attention" if l % per == off else "mamba"
                          for l in range(per)),
        mamba_d_inner=int(hf.get("mamba_expand", 2)) * D,
        mamba_d_state=int(hf.get("mamba_d_state", 16)),
        mamba_d_conv=int(hf.get("mamba_d_conv", 4)),
        mamba_dt_rank=-(-D // 16) if rank == "auto" else int(rank),
        mamba_conv_bias=bool(hf.get("mamba_conv_bias", True)),
    )


def _latent_moe_config(hf: dict, path: str, arch: str, dtype: str) -> ModelConfig:
    """Glm4MoeLiteConfig / DeepseekV3Config -> ModelConfig; what the program
    cannot express is refused by the key's name."""
    only = {"n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "attention_bias": False, "rope_scaling": None,
            "topk_method": "noaux_tc", "hidden_act": "silu",
            "partial_rotary_factor": 1, "moe_layer_freq": 1}
    for key, want in only.items():
        if hf.get(key, want) != want:
            # (the group-limited choice itself is served, ModelConfig.
            # moe_n_group / moe_topk_group, from drawn weights beside 'kda'
            # layers; what is refused here is this family's checkpoint with
            # it, which no test has loaded)
            raise ValueError(f"{key}={hf[key]!r} in {path}: this loader has "
                             f"only {key}={want!r} for {arch}")
    dn, dr = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    width = int(hf["moe_intermediate_size"])
    return ModelConfig(
        name=os.path.basename(os.path.normpath(path)) or arch,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=width,  # one shared expert's width
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=int(hf["num_attention_heads"]),
        num_kv_heads=int(hf["num_attention_heads"]),
        head_dim=dn + dr,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=float(hf.get("rms_norm_eps", 1e-6)),
        max_position=int(hf.get("max_position_embeddings", 32768)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=dtype,
        mla_kv_lora_rank=int(hf["kv_lora_rank"]),
        mla_rope_dim=dr,
        mla_qk_nope_dim=dn,
        mla_v_head_dim=int(hf["v_head_dim"]),
        mla_q_lora_rank=int(hf.get("q_lora_rank") or 0),
        moe_num_experts=int(hf["n_routed_experts"]),
        moe_top_k=int(hf["num_experts_per_tok"]),
        moe_intermediate_size=width,
        moe_num_shared_experts=int(hf.get("n_shared_experts") or 0),
        moe_leading_dense_layers=int(hf.get("first_k_dense_replace", 0)),
        moe_dense_intermediate_size=int(hf["intermediate_size"]),
        moe_scoring="sigmoid",
        moe_router_bias=True,
        moe_routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
    )


def _load_latent_moe_params(src: "_TensorSource", cfg: ModelConfig,
                            rope_interleave: bool) -> dict:
    """The family's published tensors onto the program's leaves.

    ``q_a_proj`` / ``q_a_layernorm`` / ``q_b_proj`` -> ``mla_wqa`` /
    ``mla_q_norm`` / ``mla_wqb`` (``q_proj`` -> ``mla_wq`` without a q rank);
    ``kv_a_proj_with_mqa`` [r + dr, D] -> ``mla_wdkv`` [D, r] and ``mla_wkr``
    [D, dr]; ``kv_a_layernorm`` -> ``mla_kv_norm``; ``kv_b_proj`` [H * (dn +
    dv), r] split a head into ``mla_wuk`` [H, dn, r] and ``mla_wuv`` [H, r,
    dv]; ``mlp.gate.weight`` / ``e_score_correction_bias`` -> ``router`` /
    ``router_bias`` (float32); ``mlp.experts.N.*`` -> the fused banks;
    ``mlp.shared_experts.*`` -> ``shared_wi`` / ``shared_wo``; a leading
    dense layer's ``mlp.*`` -> ``wi`` / ``wo_mlp``. Attention leaves are
    stacked over all layers, expert leaves over the mixture layers.

    ``rope_interleave``: the checkpoint pairs adjacent rope lanes (2i, 2i+1);
    the program pairs lane i with i + dr/2, so the rope columns of ``q_b_proj``
    and ``kv_a_proj_with_mqa`` are permuted (evens, then odds): the same
    rotation, and a dot product does not see a permutation both sides share.

    Tensors of layers past ``num_hidden_layers`` (the multi-token prediction
    layer, which the published modelling code drops on load too) are skipped
    with one log line; any other tensor this mapping does not consume is
    refused by name."""
    dt = cfg.jax_dtype
    L, D, H = cfg.num_layers, cfg.hidden_size, cfg.num_heads
    r, dr = cfg.mla_kv_lora_rank, cfg.mla_rope_dim
    dn, dv, rq = cfg.mla_qk_nope_dim, cfg.mla_v_head_dim, cfg.mla_q_lora_rank
    k, E = cfg.moe_leading_dense_layers, cfg.moe_num_experts
    used: set[str] = set()

    def g(name: str) -> np.ndarray:
        used.add(name)
        return src.get(name)

    lane = (np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
            if rope_interleave else np.arange(dr))

    def stack(fn, layers=range(L), dtype=dt) -> jax.Array:
        return jnp.asarray(np.stack([fn(f"model.layers.{l}.") for l in layers]),
                           dtype)

    def q_heads(w):  # [H * (dn + dr), in] -> [in, H, dn + dr], rope lanes paired
        w = w.T.reshape(-1, H, dn + dr)
        return np.concatenate([w[..., :dn], w[..., dn:][..., lane]], axis=-1)

    def fused(gate, up):
        return np.concatenate([gate.T, up.T], axis=-1)

    a = "self_attn."
    p = {
        "embed": jnp.asarray(g("model.embed_tokens.weight"), dt),
        "final_norm": jnp.asarray(g("model.norm.weight"), dt),
        "attn_norm": stack(lambda l: g(l + "input_layernorm.weight")),
        "mlp_norm": stack(lambda l: g(l + "post_attention_layernorm.weight")),
        "mla_wdkv": stack(lambda l: g(l + a + "kv_a_proj_with_mqa.weight")[:r].T),
        "mla_wkr": stack(
            lambda l: g(l + a + "kv_a_proj_with_mqa.weight")[r:][lane].T),
        "mla_kv_norm": stack(lambda l: g(l + a + "kv_a_layernorm.weight")),
        "mla_wuk": stack(lambda l: g(l + a + "kv_b_proj.weight").reshape(
            H, dn + dv, r)[:, :dn]),
        "mla_wuv": stack(lambda l: g(l + a + "kv_b_proj.weight").reshape(
            H, dn + dv, r)[:, dn:].transpose(0, 2, 1)),
        "wo": stack(lambda l: g(l + a + "o_proj.weight").T.reshape(H, dv, D)),
    }
    if rq:
        p["mla_wqa"] = stack(lambda l: g(l + a + "q_a_proj.weight").T)
        p["mla_q_norm"] = stack(lambda l: g(l + a + "q_a_layernorm.weight"))
        p["mla_wqb"] = stack(lambda l: q_heads(g(l + a + "q_b_proj.weight")))
    else:
        p["mla_wq"] = stack(lambda l: q_heads(g(l + a + "q_proj.weight")))
    mix = range(k, L)
    p["router"] = stack(lambda l: g(l + "mlp.gate.weight").T, mix)
    p["router_bias"] = stack(
        lambda l: g(l + "mlp.gate.e_score_correction_bias"), mix, jnp.float32)
    p["moe_wi"] = stack(lambda l: np.stack([
        fused(g(l + f"mlp.experts.{e}.gate_proj.weight"),
              g(l + f"mlp.experts.{e}.up_proj.weight")) for e in range(E)]), mix)
    p["moe_wo"] = stack(lambda l: np.stack([
        g(l + f"mlp.experts.{e}.down_proj.weight").T for e in range(E)]), mix)
    if cfg.moe_num_shared_experts:
        p["shared_wi"] = stack(lambda l: fused(
            g(l + "mlp.shared_experts.gate_proj.weight"),
            g(l + "mlp.shared_experts.up_proj.weight")), mix)
        p["shared_wo"] = stack(
            lambda l: g(l + "mlp.shared_experts.down_proj.weight").T, mix)
    if k:
        p["wi"] = stack(lambda l: fused(g(l + "mlp.gate_proj.weight"),
                                        g(l + "mlp.up_proj.weight")), range(k))
        p["wo_mlp"] = stack(lambda l: g(l + "mlp.down_proj.weight").T, range(k))
    if not cfg.tie_embeddings:
        p["unembed"] = jnp.asarray(g("lm_head.weight").T, dt)
    left = sorted(set(src.names()) - used)
    past = [n for n in left
            if (m := re.match(r"model\.layers\.(\d+)\.", n)) and int(m[1]) >= L]
    if past:
        log.info("%s: %d tensors of layers past num_hidden_layers=%d skipped "
                 "(the multi-token prediction layer is not served)",
                 src.path, len(past), L)
    unknown = sorted(set(left) - set(past))
    if unknown:
        raise ValueError(
            f"{src.path}: tensors this loader maps onto no leaf: "
            f"{unknown[:8]}{' ...' if len(unknown) > 8 else ''}")
    return p


def _load_jamba_params(src: "_TensorSource", cfg: ModelConfig) -> dict:
    """Jamba's published tensors onto the program's leaves: the norms and
    the MLP stacked over all layers, the attention projections over the
    attention layers, the mixer's tensors over the mamba layers. The mixer's
    weights are transposed to matmul-ready [in, out]; ``conv1d.weight``
    [Di, 1, K] becomes ``mamba_conv_w`` [K, Di] and ``A_log`` [Di, N] becomes
    ``mamba_a_log`` [N, Di] (the state is held [N, Di], d_inner on the
    lanes)."""
    dt = cfg.jax_dtype
    D, H, Hk, Dh = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kinds = [cfg.layer_kinds[l % len(cfg.layer_kinds)]
             for l in range(cfg.num_layers)]
    g = src.get

    def stack(fn, kind=None) -> jax.Array:
        return jnp.asarray(np.stack([
            fn(f"model.layers.{l}.") for l in range(cfg.num_layers)
            if kind is None or kinds[l] == kind]), dt)

    def attn(fn):
        return stack(fn, "attention")

    def mamba(fn):
        return stack(fn, "mamba")

    p = {
        "embed": jnp.asarray(g("model.embed_tokens.weight"), dt),
        "final_norm": jnp.asarray(g("model.final_layernorm.weight"), dt),
        "attn_norm": stack(lambda l: g(l + "input_layernorm.weight")),
        "mlp_norm": stack(lambda l: g(l + "pre_ff_layernorm.weight")),
        "wi": stack(lambda l: np.concatenate(
            [g(l + "feed_forward.gate_proj.weight").T,
             g(l + "feed_forward.up_proj.weight").T], axis=-1)),
        "wo_mlp": stack(lambda l: g(l + "feed_forward.down_proj.weight").T),
        "wq": attn(lambda l: g(l + "self_attn.q_proj.weight").T.reshape(D, H, Dh)),
        "wk": attn(lambda l: g(l + "self_attn.k_proj.weight").T.reshape(D, Hk, Dh)),
        "wv": attn(lambda l: g(l + "self_attn.v_proj.weight").T.reshape(D, Hk, Dh)),
        "wo": attn(lambda l: g(l + "self_attn.o_proj.weight").T.reshape(H, Dh, D)),
        "mamba_in": mamba(lambda l: g(l + "mamba.in_proj.weight").T),
        "mamba_conv_w": mamba(lambda l: g(l + "mamba.conv1d.weight")[:, 0, :].T),
        "mamba_x": mamba(lambda l: g(l + "mamba.x_proj.weight").T),
        "mamba_dt_norm": mamba(lambda l: g(l + "mamba.dt_layernorm.weight")),
        "mamba_b_norm": mamba(lambda l: g(l + "mamba.b_layernorm.weight")),
        "mamba_c_norm": mamba(lambda l: g(l + "mamba.c_layernorm.weight")),
        "mamba_dt": mamba(lambda l: g(l + "mamba.dt_proj.weight").T),
        "mamba_dt_bias": mamba(lambda l: g(l + "mamba.dt_proj.bias")),
        "mamba_a_log": mamba(lambda l: g(l + "mamba.A_log").T),
        "mamba_d": mamba(lambda l: g(l + "mamba.D")),
        "mamba_out": mamba(lambda l: g(l + "mamba.out_proj.weight").T),
    }
    if cfg.mamba_conv_bias:
        p["mamba_conv_b"] = mamba(lambda l: g(l + "mamba.conv1d.bias"))
    if not cfg.tie_embeddings:
        p["unembed"] = jnp.asarray(g("lm_head.weight").T, dt)
    return p


class _TensorSource:
    """Uniform tensor-by-name access over single-file or index-sharded safetensors.

    Reads stay on HOST memory (torch-CPU framework — handles bf16, which numpy
    can't): loading must never bounce checkpoint bytes through the accelerator;
    only the final stacked leaves are device_put once (as the serving dtype).
    """

    def __init__(self, path: str) -> None:
        from safetensors import safe_open

        self._open = safe_open
        self.path = path
        self._where: dict[str, str] = {}  # tensor name → shard file
        self._handles: dict[str, object] = {}
        index = os.path.join(path, "model.safetensors.index.json")
        if os.path.isfile(index):
            with open(index) as f:
                self._where = dict(json.load(f)["weight_map"])
        else:
            single = os.path.join(path, "model.safetensors")
            if not os.path.isfile(single):
                raise FileNotFoundError(
                    f"no model.safetensors or model.safetensors.index.json in {path}"
                )
            with safe_open(single, framework="torch", device="cpu") as f:
                for name in f.keys():
                    self._where[name] = "model.safetensors"

    def names(self) -> list[str]:
        return list(self._where)

    def get(self, name: str) -> np.ndarray:
        """Tensor as host float32 ndarray."""
        fname = self._where.get(name)
        if fname is None:
            raise KeyError(f"tensor {name!r} not in checkpoint {self.path}")
        h = self._handles.get(fname)
        if h is None:
            h = self._handles[fname] = self._open(
                os.path.join(self.path, fname), framework="torch", device="cpu"
            )
        import torch

        return h.get_tensor(name).to(torch.float32).numpy()


def load_params(
    path: str, cfg: Optional[ModelConfig] = None, dtype: Optional[str] = None
) -> dict[str, jax.Array]:
    """Load + restack checkpoint weights into the scanned-layer param dict.

    HF per-layer ``[out, in]`` projection matrices become matmul-ready stacked
    leaves: ``wq [L, D, H, Dh]``, ``wo [L, H, Dh, D]``, fused SwiGLU
    ``wi = concat(gate.T, up.T) [L, D, 2F]`` (our ``swiglu`` splits gate-first),
    ``wo_mlp [L, F, D]``; ``unembed`` is ``lm_head.T [D, V]`` unless embeddings
    are tied (then ``embed.T`` is used at unembed time, matching HF tying).
    """
    if cfg is None:
        cfg = config_from_hf(path, dtype=dtype or "bfloat16")
    dt = cfg.jax_dtype
    src = _TensorSource(path)
    if cfg.has_recurrent:
        return _load_jamba_params(src, cfg)
    if cfg.is_mla:
        with open(os.path.join(path, "config.json")) as f:
            interleave = bool(json.load(f).get("rope_interleave", True))
        return _load_latent_moe_params(src, cfg, interleave)
    D, H, Hk, Dh = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L, F = cfg.num_layers, cfg.intermediate_size

    def g(name: str) -> np.ndarray:
        return src.get(name)

    def stack(fn) -> jax.Array:
        return jnp.asarray(np.stack([fn(l) for l in range(L)]), dt)

    p: dict[str, jax.Array] = {
        "embed": jnp.asarray(g("model.embed_tokens.weight"), dt),
        "final_norm": jnp.asarray(g("model.norm.weight"), dt),
        "attn_norm": stack(lambda l: g(f"model.layers.{l}.input_layernorm.weight")),
        "mlp_norm": stack(
            lambda l: g(f"model.layers.{l}.post_attention_layernorm.weight")
        ),
        "wq": stack(
            lambda l: g(f"model.layers.{l}.self_attn.q_proj.weight").T.reshape(D, H, Dh)
        ),
        "wk": stack(
            lambda l: g(f"model.layers.{l}.self_attn.k_proj.weight").T.reshape(D, Hk, Dh)
        ),
        "wv": stack(
            lambda l: g(f"model.layers.{l}.self_attn.v_proj.weight").T.reshape(D, Hk, Dh)
        ),
        "wo": stack(
            lambda l: g(f"model.layers.{l}.self_attn.o_proj.weight").T.reshape(H, Dh, D)
        ),
        "wi": stack(
            lambda l: np.concatenate(
                [
                    g(f"model.layers.{l}.mlp.gate_proj.weight").T,
                    g(f"model.layers.{l}.mlp.up_proj.weight").T,
                ],
                axis=-1,
            )
        ),
        "wo_mlp": stack(lambda l: g(f"model.layers.{l}.mlp.down_proj.weight").T),
    }
    if cfg.qk_norm:
        p["q_norm"] = stack(lambda l: g(f"model.layers.{l}.self_attn.q_norm.weight"))
        p["k_norm"] = stack(lambda l: g(f"model.layers.{l}.self_attn.k_norm.weight"))
    if cfg.attn_bias:
        p["bq"] = stack(
            lambda l: g(f"model.layers.{l}.self_attn.q_proj.bias").reshape(H, Dh)
        )
        p["bk"] = stack(
            lambda l: g(f"model.layers.{l}.self_attn.k_proj.bias").reshape(Hk, Dh)
        )
        p["bv"] = stack(
            lambda l: g(f"model.layers.{l}.self_attn.v_proj.bias").reshape(Hk, Dh)
        )
        # llama-style attention_bias puts a bias on o_proj too; qwen2 does not
        names = set(src.names())
        p["bo"] = (
            stack(lambda l: g(f"model.layers.{l}.self_attn.o_proj.bias"))
            if "model.layers.0.self_attn.o_proj.bias" in names
            else jnp.zeros((L, D), dt)
        )
    if not cfg.tie_embeddings:
        p["unembed"] = jnp.asarray(g("lm_head.weight").T, dt)
    expected_fused = D * 2 * F
    got = p["wi"].shape[1] * p["wi"].shape[2]
    if got != expected_fused:
        raise ValueError(
            f"mlp shape mismatch: fused gate/up is {p['wi'].shape}, "
            f"config expects [L, {D}, {2 * F}]"
        )
    return p


def load_model(
    path: str, dtype: str = "bfloat16"
) -> tuple[ModelConfig, dict[str, jax.Array]]:
    """One-call load: (ModelConfig, stacked params) from an HF checkpoint dir."""
    cfg = config_from_hf(path, dtype=dtype)
    return cfg, load_params(path, cfg)


def main() -> None:  # pragma: no cover - CLI convenience
    import argparse

    ap = argparse.ArgumentParser(description="inspect an HF checkpoint dir")
    ap.add_argument("path")
    args = ap.parse_args()
    cfg = config_from_hf(args.path)
    params = load_params(args.path, cfg)
    n = sum(int(np.prod(v.shape)) for v in params.values())
    print(f"{cfg.name}: {cfg.num_layers}L d={cfg.hidden_size} "
          f"H={cfg.num_heads}/{cfg.num_kv_heads} dh={cfg.head_dim} "
          f"vocab={cfg.vocab_size} tie={cfg.tie_embeddings} "
          f"qk_norm={cfg.qk_norm} attn_bias={cfg.attn_bias} — "
          f"{n / 1e9:.3f}B params")


if __name__ == "__main__":
    main()
