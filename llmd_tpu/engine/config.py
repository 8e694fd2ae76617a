"""Engine configuration (the vLLM flag-surface analogue, TPU-shaped)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from llmd_tpu.parallel.eplb import EPLBConfig
from llmd_tpu.parallel.mesh import MeshConfig


@dataclass
class EngineConfig:
    # Paged KV cache — page_size matches the reference's --block-size contract
    # (precise-prefix-cache-routing values: blockSize must equal engine block size).
    page_size: int = 16
    num_pages: int = 512
    max_model_len: int = 2048
    # Continuous batching
    max_batch_size: int = 8  # decode slots
    prefill_chunk: int = 128  # per-sequence chunked-prefill cap per step
    # Flat token budget of the unified step (--max-num-batched-tokens): decode
    # tokens + prefill chunks from MULTIPLE sequences pack into one program call.
    # None = max(prefill_chunk, max_batch_size) (one chunk + a decode batch).
    max_num_batched_tokens: "int | None" = None
    enable_prefix_caching: bool = True
    # Parallelism
    mesh: MeshConfig = field(default_factory=MeshConfig)
    # DP rank schedulers sharing THIS engine's single SPMD program (wide-EP: each
    # rank is a router-visible endpoint with its own queue/batch-slot-range/page
    # partition, while MoE layers share one all-to-all across mesh.dp × mesh.ep —
    # the reference's --data-parallel-size rank engines, composed the XLA way).
    # Requires max_batch_size and num_pages divisible by dp_ranks; offload tiers
    # are per-rank state and are not yet supported with dp_ranks > 1.
    dp_ranks: int = 1
    # Scheduling
    max_queue: int = 1024
    # Multi-step decode: a fused call runs up to N decode iterations in one
    # on-device loop (one host round-trip a call). N is the cap and the height
    # of the call's token buffer: the host gives each call the length at which
    # its first row is known to end by max_tokens or max_model_len
    # (engine.decode_call_steps, no shorter than engine.DECODE_MIN_STEPS);
    # stop tokens are handled post-hoc by truncation. Calls are dispatched
    # chained on the previous call's device-resident sampled tokens and read
    # one call later (engine.DECODE_CHAIN_DEPTH).
    decode_steps: int = 1
    # KV offload tier (pages of CPU-side cache; 0 = disabled) — K3 equivalent
    # (TPU_OFFLOAD_NUM_CPU_CHUNKS / STAGING_BLOCKS knobs of the reference connector).
    cpu_offload_pages: int = 0
    offload_staging_blocks: int = 16
    # Proactive drain: when the plain free list falls below this, demote the oldest
    # LRU pages to the CPU tier in one batched gather (keeps per-page D2H syncs off
    # the allocate() hot path).
    offload_watermark_pages: int = 8
    # FS tier below the CPU tier (llmd_fs_backend shared_storage_path; None = off).
    offload_fs_path: "str | None" = None
    # Out-of-tree KV connector (K5: LMCache/Mooncake/KVBM seam) — a name from
    # llmd_tpu.kv.connector_api's registry; the external engine covers prompt
    # suffixes beyond the local HBM + native CPU/FS tiers.
    kv_connector: "str | None" = None
    kv_connector_params: "dict | None" = None
    # P/D role (disaggregation/README.md roles kv_producer/kv_consumer/both)
    role: str = "both"
    # Attention kernel: "auto" = Pallas ragged-paged-attention on TPU / XLA
    # reference semantics elsewhere, "pallas" = force the Pallas kernel,
    # "reference" = gather+mask (models.transformer.ragged_paged_attention_xla).
    # A rule on the platform, never a trial compile: a selected kernel that
    # fails to compile fails the engine at its first step.
    # MLA models: every step program takes the latent kernel for ragged rows
    # (ops/mla_attention) on TPU under "auto" and anywhere under "pallas"
    # (interpreter mode on the CPU); the absorbed XLA impl is the CPU's.
    attn_impl: str = "auto"
    # Long-context sequence parallelism: when mesh.sp > 1, serve self-contained
    # single-sequence prefill steps through the zig-zag ring-attention program
    # (ops/ring_attention.py) instead of GSPMD-annotated paged attention. The
    # engine gates eligibility per step; decode always stays on the paged path.
    sp_ring_attention: bool = True
    # MoE expert GEMMs: "auto" = Pallas grouped GEMM on TPU / einsum elsewhere,
    # "pallas" = force (interpret mode on the CPU), "einsum" = XLA dot path.
    moe_matmul: str = "auto"
    # MoE token dispatch (ops/moe_dispatch): "sorted" = token-sorted drop-free
    # gather/scatter (all_to_all over the ep axis when ep > 1), "einsum" =
    # legacy dense one-hot capacity dispatch (silently drops tokens past
    # moe_capacity_factor — kept as parity reference and kill switch),
    # "auto" = LLMD_MOE_DISPATCH env override, else sorted everywhere.
    moe_dispatch: str = "auto"
    # Weight-only quantization (models/quant.py): "int8" halves decode's
    # HBM weight traffic — per-output-channel symmetric on the dense
    # projections, the unembedding, and the MoE expert banks (per-expert
    # scales; expert GEMMs then run the scaled-einsum path, and EPLB
    # regathers scales with their slots). None = serve checkpoint dtype.
    quantize_weights: "str | None" = None
    # KV-cache dtype: "fp8" stores pages as float8_e4m3fn — decode's OTHER
    # HBM stream (per-step KV reads rival the weight bytes at serving batch
    # sizes; at b=64/ctx 320 the bf16 KV read is ~1.3 GB/step on llama-1b).
    # The Pallas kernel dequantizes in VMEM after the page DMA (k_scale/
    # v_scale), so HBM traffic halves end to end. None = model dtype.
    kv_cache_dtype: "str | None" = None
    # KV pool lane layout (ops/packed_kv): "packed" stores f = Dhp/head_dim
    # real KV heads per 128-lane row instead of padding each head — for
    # head_dim-64 models that halves KV bytes again (the padding half of
    # every page DMA is zeros). "auto" packs whenever the model is eligible
    # (exact lane fit, Hk divisible); "padded" forces the one-head-per-row
    # layout; "packed" on an ineligible model is an error.
    kv_layout: str = "auto"
    # Expert-parallel load balancing with redundant experts (wide-ep --enable-eplb
    # {window_size, step_interval, num_redundant_experts}); None = disabled.
    eplb: Optional[EPLBConfig] = None
    # LoRA multi-adapter serving (model-servers.md:55-75); None = disabled.
    # Imported lazily to avoid a models<->engine import cycle at module load.
    lora: "object | None" = None  # llmd_tpu.models.lora.LoRAConfig
    # Speculative decoding (engine/spec.py): "off" = plain decode, "ngram" =
    # prompt-lookup drafting verified through the flat mixed-batch program.
    # Greedy acceptance keeps output bitwise identical to spec_mode="off";
    # sequences sampling at temperature > 0 fall back to plain decode.
    spec_mode: str = "off"
    # Max draft tokens proposed (and verified) per sequence per verify step.
    spec_tokens: int = 4
    # Suffix n-gram match lengths tried by the drafter, longest first.
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # Structured outputs (llmd_tpu/structured): "auto" = compile grammars for
    # requests that ask (guided_* / response_format / logit_bias ride the
    # biased sampler; everything else keeps the exact unbiased programs),
    # "off" = reject structured requests at admission (ValueError -> 400).
    structured_mode: str = "auto"
    # Constrained rows (grammar masks / logit_bias) ride the fused multi-step
    # decode program with the bias apply + FSM transition done on device
    # (structured/grammar.py dense_tables), and draft through the host
    # automaton into the grammar-masked verify program. This is the upper
    # bound on the staged mask-table size (G_pad * S_pad * V elements, f32
    # bias + i32 next ~= 8 bytes/element): past it, and for a row combining a
    # grammar AND a logit_bias, constrained rows fall back to 1-token unified
    # steps rather than staging a huge table.
    structured_table_max_elems: int = 1 << 23
    # Debug cross-check: after every masked verify step, re-derive each
    # constrained row's FSM state on host (StructuredState.sync over the
    # accepted tokens) and compare against the device-returned state; a
    # mismatch adopts the host value and bumps
    # stats.spec_fsm_crosscheck_mismatches (should stay 0).
    spec_structured_crosscheck: bool = False

    @property
    def max_pages_per_seq(self) -> int:
        return (self.max_model_len + self.page_size - 1) // self.page_size

    @property
    def batched_tokens(self) -> int:
        if self.max_num_batched_tokens is not None:
            return max(self.max_num_batched_tokens, self.max_batch_size)
        return max(self.prefill_chunk, self.max_batch_size)
