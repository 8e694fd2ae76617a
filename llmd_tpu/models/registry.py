"""Named model configs: test-size + flagship serving shapes.

The reference's guides serve Qwen3-32B (optimized-baseline), Llama-3-70B / gpt-oss-120b
(pd-disaggregation), DeepSeek-R1 (wide-ep-lws) via vLLM; here each family maps to a
config of our stack. Sizes marked `-sim` are scaled to fit the available chip while
keeping the architectural shape (GQA ratios, MoE top-k) of the original.
"""

from __future__ import annotations

from llmd_tpu.models.config import ModelConfig

MODEL_REGISTRY: dict[str, ModelConfig] = {
    # CI-size models (CPU-runnable, byte-level vocab)
    "tiny": ModelConfig(
        name="tiny", vocab_size=288, hidden_size=128, intermediate_size=384,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
    ),
    # VL shape for the encode-disagg (E/PD) path: tiny text stack + a real
    # (random-init) vision tower; 4 embedding tokens per media item.
    "tiny-vl": ModelConfig(
        name="tiny-vl", vocab_size=288, hidden_size=128, intermediate_size=384,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        mm_tokens=4, mm_placeholder_id=287, vision_patch=8, vision_image_size=32,
        vision_layers=2, vision_hidden=64, vision_heads=4,
    ),
    # Llama-3.2-ratio GQA at CI size: head_dim 64 (lane pad = one extra head)
    # exercises the packed KV layout (ops/packed_kv) on the serving surface.
    "tiny64": ModelConfig(
        name="tiny64", vocab_size=288, hidden_size=128, intermediate_size=384,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=64,
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe", vocab_size=288, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        moe_num_experts=8, moe_top_k=2, moe_intermediate_size=128,
        moe_num_shared_experts=1,
    ),
    # Flagship single-chip bench model (~1.1B params bf16 ≈ 2.2GB — fits v5e 16GB HBM
    # with room for KV pages). Llama-3.2-1B-shaped.
    "llama-1b": ModelConfig(
        name="llama-1b", vocab_size=32768, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=500000.0, tie_embeddings=True,
    ),
    # Llama-3-8B shape (multi-chip TP target).
    "llama-8b": ModelConfig(
        name="llama-8b", vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, tie_embeddings=False,
    ),
    # Qwen3-32B shape (optimized-baseline parity target).
    "qwen-32b": ModelConfig(
        name="qwen-32b", vocab_size=151936, hidden_size=5120, intermediate_size=25600,
        num_layers=64, num_heads=64, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, tie_embeddings=False,
    ),
    # DeepSeek-R1-class MoE shape scaled for wide-EP dry-runs (shape, not size).
    "moe-wide-sim": ModelConfig(
        name="moe-wide-sim", vocab_size=32768, hidden_size=1024, intermediate_size=2048,
        num_layers=4, num_heads=16, num_kv_heads=4, head_dim=64,
        moe_num_experts=32, moe_top_k=4, moe_intermediate_size=512,
        moe_num_shared_experts=1,
    ),
    # MLA at CI size (DeepSeek-V2/V3 attention family; ratios mirror V3's
    # 512-rank / 64-rope / 128-nope / 128-value at 1/8 scale).
    "tiny-mla": ModelConfig(
        name="tiny-mla", vocab_size=288, hidden_size=128, intermediate_size=384,
        num_layers=2, num_heads=4, num_kv_heads=4, head_dim=32,
        mla_kv_lora_rank=64, mla_rope_dim=16, mla_qk_nope_dim=16,
        mla_v_head_dim=16,
    ),
    # MLA x MoE at CI size: the wide-EP north-star STACK (latent attention +
    # expert banks) cheap enough for the multichip dryrun and stress tests.
    "tiny-mla-moe": ModelConfig(
        name="tiny-mla-moe", vocab_size=288, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=32, mla_kv_lora_rank=64, mla_rope_dim=16, mla_qk_nope_dim=16,
        mla_v_head_dim=16, moe_num_experts=8, moe_top_k=2,
        moe_intermediate_size=128, moe_num_shared_experts=1,
    ),
    # The latent-attention mixture family with every mechanism GLM-4.7-Flash
    # (glm4_moe_lite) and DeepSeek-V3 add, at CI size: a q-side low-rank
    # projection, one leading dense layer, sigmoid routing with a selection
    # bias and a scaling factor, one shared expert, untied head.
    "tiny-glm": ModelConfig(
        name="tiny-glm", vocab_size=288, hidden_size=128,
        intermediate_size=96, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=48, rms_eps=1e-5, tie_embeddings=False,
        mla_kv_lora_rank=64, mla_rope_dim=16, mla_qk_nope_dim=32,
        mla_v_head_dim=48, mla_q_lora_rank=48,
        moe_num_experts=8, moe_top_k=2, moe_intermediate_size=96,
        moe_num_shared_experts=1, moe_leading_dense_layers=1,
        moe_dense_intermediate_size=320, moe_scoring="sigmoid",
        moe_router_bias=True, moe_routed_scaling=1.8,
    ),
    # DeepSeek-R1/V3-class wide-EP shape with TRUE MLA latent KV (shape-
    # faithful scaled stand-in for the reference's north-star model,
    # guides/wide-ep-lws/README.md): per-token KV is rank+rope = 160 floats
    # shared across all heads vs 2*4*64 = 512 for the GQA sim above.
    "moe-wide-mla": ModelConfig(
        name="moe-wide-mla", vocab_size=32768, hidden_size=1024,
        intermediate_size=2048, num_layers=4, num_heads=16, num_kv_heads=16,
        head_dim=64, mla_kv_lora_rank=128, mla_rope_dim=32,
        mla_qk_nope_dim=32, mla_v_head_dim=32,
        moe_num_experts=32, moe_top_k=4, moe_intermediate_size=512,
        moe_num_shared_experts=1,
    ),
    # Mamba layers around one NoPE attention layer a period, over dense MLPs
    # (Jamba with num_experts 1), at CI size: (mamba, attention, mamba, mamba)
    # twice. The recurrent-state pool, the selective scan and the per-kind
    # stacked leaves on the serving surface.
    "tiny-jamba": ModelConfig(
        name="tiny-jamba", vocab_size=288, hidden_size=128,
        intermediate_size=256, num_layers=8, num_heads=4, num_kv_heads=1,
        head_dim=32, rope_pattern=(False,),
        layer_kinds=("mamba", "attention", "mamba", "mamba"),
        mamba_d_inner=256, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
    ),
    # AI21-Jamba2-3B at its published sizes (config.json: attn_layer_period
    # 14, attn_layer_offset 7, num_experts 1): 26 Mamba layers and 2 NoPE
    # attention layers (7 and 21), 3.03 B parameters, 6.06 GB of bf16.
    "jamba2-3b": ModelConfig(
        name="jamba2-3b", vocab_size=65536, hidden_size=2560,
        intermediate_size=8192, num_layers=28, num_heads=20, num_kv_heads=1,
        head_dim=128, max_position=262144, tie_embeddings=True,
        rope_pattern=(False,),
        layer_kinds=("mamba",) * 7 + ("attention",) + ("mamba",) * 6,
        mamba_d_inner=5120, mamba_d_state=16, mamba_d_conv=4,
        mamba_dt_rank=160,
    ),
    # Lightning linear-attention layers around block-sparse NoPE attention
    # layers (MiniCPM-SALA's two kinds, a sparse pair back to back), at CI
    # size with the selection's sizes shrunk so that it bites at a hundred
    # tokens (pages of 2): the matrix-state pool, the compressed-key plane and
    # the selected page tables on the serving surface.
    "tiny-sala": ModelConfig(
        name="tiny-sala", vocab_size=288, hidden_size=128,
        intermediate_size=256, num_layers=7, num_heads=4, num_kv_heads=2,
        head_dim=32, tie_embeddings=False, qk_norm=True, rope_pattern=(False,),
        layer_kinds=("lightning", "attention", "lightning", "lightning",
                     "attention", "attention", "lightning"),
        lightning_heads=4, lightning_head_dim=32, attn_output_gate=True,
        sparse_topk=2, sparse_block_size=8, sparse_kernel_size=4,
        sparse_kernel_stride=2, sparse_window=16, sparse_dense_len=64,
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5, logit_scale=0.25,
    ),
    # A stack of single sublayers at CI size (Nemotron-H's layout, the period
    # MEMEM*E twice): Mamba-2 layers (4 heads of 16 channels, 2 groups of B
    # and C, state 16), NoPE attention layers without a feed-forward, and
    # expert layers of 8 non-gated relu^2 experts, top-2 by sigmoid scores
    # with a selection bias and a scaling factor, beside a shared expert of
    # a width of its own. The Mamba-2 recurrence, the one-sublayer stack,
    # experts beside recurrent layers and (with ``moe_held_*``) a device's
    # share of the experts on the serving surface.
    "tiny-nemotron-h": ModelConfig(
        name="tiny-nemotron-h", vocab_size=288, hidden_size=128,
        intermediate_size=96, num_layers=14, num_heads=4, num_kv_heads=2,
        head_dim=32, rms_eps=1e-5, tie_embeddings=False,
        rope_pattern=(False,),
        layer_kinds=("mamba2", "experts", "mamba2", "experts", "mamba2",
                     "attention", "experts"),
        mamba2_heads=4, mamba2_head_dim=16, mamba2_groups=2,
        mamba2_d_state=16, mamba2_d_conv=4,
        moe_num_experts=8, moe_top_k=2, moe_intermediate_size=96,
        moe_num_shared_experts=1, moe_shared_intermediate_size=160,
        moe_gated=False, moe_activation="relu2", moe_scoring="sigmoid",
        moe_router_bias=True, moe_routed_scaling=2.5,
    ),
    # 'kda' delta-rule mixers beside one latent-attention layer in a period of
    # six, each over a mixture feed-forward, behind one leading 'kda' layer
    # over a dense SwiGLU, at CI size: 8 sigmoid-routed experts in 4 groups,
    # the best 2 groups kept and the top-2 taken among them, 4 of the 8 held
    # here, a shared expert, a head-wise gate on the latent layer's output.
    # The delta-rule recurrence on the matrix-state pool beside a latent KV
    # pool, a recurrent mixer over experts in one layer and the group-limited
    # choice on the serving surface.
    "tiny-ling": ModelConfig(
        name="tiny-ling", vocab_size=288, hidden_size=128,
        intermediate_size=64, num_layers=7, num_heads=4, num_kv_heads=4,
        head_dim=48, tie_embeddings=False, rope_theta=6e6,
        layer_kinds=("kda", "kda", "kda", "attention", "kda", "kda"),
        kda_heads=4, kda_head_dim=32, kda_d_conv=4,
        attn_output_gate=True,
        mla_kv_lora_rank=64, mla_rope_dim=16, mla_qk_nope_dim=32,
        mla_v_head_dim=32,
        moe_num_experts=8, moe_top_k=2, moe_intermediate_size=64,
        moe_num_shared_experts=1, moe_shared_intermediate_size=64,
        moe_leading_dense_layers=1, moe_dense_intermediate_size=192,
        moe_scoring="sigmoid", moe_router_bias=True, moe_routed_scaling=2.5,
        moe_n_group=4, moe_topk_group=2, moe_held_count=4,
    ),
}


def get_model_config(name: str) -> ModelConfig:
    try:
        return MODEL_REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown model {name!r}; known: {sorted(MODEL_REGISTRY)}") from None
