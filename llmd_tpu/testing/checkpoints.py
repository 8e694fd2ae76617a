"""Generate genuine HF-format checkpoints locally (zero-egress test fixtures).

The image has no network, so no published checkpoint can be downloaded — but the
HF *format* (config.json + safetensors [+ sharded index] + tokenizer files) and
the HF *reference implementation* (transformers on torch CPU) are both available.
These fixtures build real ``save_pretrained`` checkpoints for each supported
architecture family so ``llmd_tpu.models.hf_loader`` and the engine can be
validated for logits parity against the HF forward — the exact validation a real
downloaded checkpoint would get (the loader path is identical; only the weight
values differ).

Also used by ``tools/make_checkpoint.py`` to materialise serving-scale
checkpoints (e.g. a Llama-3.2-1B-shaped model) for bench runs through the full
HF-load path.
"""

from __future__ import annotations

import os
from typing import Optional

_CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "llm-d is a kubernetes-native distributed inference serving stack",
    "tensor parallel expert parallel data parallel sequence parallel",
    "paged attention continuous batching chunked prefill speculative",
    "prefill decode disaggregation kv cache transfer routing scheduler",
    "0123456789 !?.,;:()[]{}<>@#$%^&*-_=+ abcdefghijklmnopqrstuvwxyz",
]


def make_hf_tokenizer(out_dir: str, vocab_size: int = 384) -> int:
    """Train + save a real byte-level BPE HF tokenizer; returns its vocab size."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<eos>", "<bos>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(_CORPUS * 4, trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, eos_token="<eos>", bos_token="<bos>"
    )
    fast.save_pretrained(out_dir)
    return len(fast)


def make_hf_checkpoint(
    out_dir: str,
    family: str = "llama",
    *,
    vocab_size: int = 384,
    hidden_size: int = 64,
    intermediate_size: int = 128,
    num_layers: int = 2,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    head_dim: Optional[int] = None,
    tie_embeddings: bool = True,
    rope_theta: float = 10000.0,
    max_position: int = 512,
    max_shard_size: Optional[str] = None,
    seed: int = 0,
    with_tokenizer: bool = True,
    torch_dtype: str = "float32",
    attention_bias: bool = False,
) -> str:
    """Build + save an HF checkpoint of the given family; returns ``out_dir``.

    ``max_shard_size`` (e.g. "50KB") forces a sharded model.safetensors.index.json
    checkpoint, exercising the loader's multi-shard path.
    """
    import torch
    import transformers

    torch.manual_seed(seed)
    common = dict(
        vocab_size=vocab_size,
        hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        num_hidden_layers=num_layers,
        num_attention_heads=num_heads,
        num_key_value_heads=num_kv_heads,
        max_position_embeddings=max_position,
        rms_norm_eps=1e-6,
        rope_theta=rope_theta,
        tie_word_embeddings=tie_embeddings,
    )
    if family == "llama":
        cfg = transformers.LlamaConfig(
            **common, head_dim=head_dim, attention_bias=attention_bias
        )
        model = transformers.LlamaForCausalLM(cfg)
    elif family == "qwen2":
        cfg = transformers.Qwen2Config(**common)
        model = transformers.Qwen2ForCausalLM(cfg)
    elif family == "qwen3":
        cfg = transformers.Qwen3Config(
            **common, head_dim=head_dim or hidden_size // num_heads
        )
        model = transformers.Qwen3ForCausalLM(cfg)
    elif family == "jamba":
        # Jamba's layer pattern at toy depth: (mamba, attention, mamba,
        # mamba) a period, one dense MLP a layer, the plain-torch mixer
        common.pop("rope_theta")
        cfg = transformers.JambaConfig(
            **common, num_experts=1, num_experts_per_tok=1,
            attn_layer_period=4, attn_layer_offset=1, use_mamba_kernels=False,
            mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
            mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
            sliding_window=None, pad_token_id=0, bos_token_id=1,
            eos_token_id=2)
        model = transformers.JambaForCausalLM(cfg)
    elif family == "glm4_moe_lite":
        # The latent-attention mixture block of GLM-4.7-Flash at toy widths,
        # built as DeepseekV3ForCausalLM (the block and the tensor names this
        # family shares with it; one routing group): a q-side rank, one
        # leading dense layer, sigmoid routing, one shared expert. The
        # selection bias is a trained buffer that starts at zero: drawn
        # non-zero here, so that a loader that dropped it would show.
        cfg = transformers.DeepseekV3Config(
            **{**common, "rms_norm_eps": 1e-5}, moe_intermediate_size=48,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            n_group=1, topk_group=1, norm_topk_prob=True,
            routed_scaling_factor=1.8, first_k_dense_replace=1,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=24, rope_scaling=None,
            attention_bias=False, pad_token_id=0, bos_token_id=1,
            eos_token_id=2)
        model = transformers.DeepseekV3ForCausalLM(cfg)
        with torch.no_grad():
            for layer in model.model.layers[cfg.first_k_dense_replace:]:
                layer.mlp.gate.weight.normal_(0.0, 0.5)
                layer.mlp.gate.e_score_correction_bias.normal_(0.0, 0.1)
    else:
        raise ValueError(f"unknown family {family!r}")
    model = model.to(getattr(torch, torch_dtype))
    os.makedirs(out_dir, exist_ok=True)
    kwargs = dict(safe_serialization=True)
    if max_shard_size is not None:
        kwargs["max_shard_size"] = max_shard_size
    model.save_pretrained(out_dir, **kwargs)
    if with_tokenizer:
        make_hf_tokenizer(out_dir, vocab_size=min(vocab_size, 384))
    return out_dir
