"""Model architecture config."""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    vocab_size: int = 288  # byte-level tokenizer (256 bytes + specials), padded to tile
    hidden_size: int = 128
    intermediate_size: int = 384
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    max_position: int = 32768
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # Per-head RMSNorm on q/k before RoPE (Qwen3-family checkpoints).
    qk_norm: bool = False
    # Bias terms on the q/k/v projections (Qwen2-family checkpoints).
    attn_bias: bool = False
    # Kinds of attention layer, as a repeating period: layer l takes entry
    # l % len(pattern) of each. All layers share the same parameter SHAPES, so
    # the stack still scans; layers of unequal behaviour scan as one period
    # written out in the scan body (transformer.forward_core), where each
    # layer's window and RoPE flag are static.
    # Sliding window in tokens, 0 = full causal attention: key j is visible to
    # query i iff i - window < j <= i.
    attn_window_pattern: tuple = (0,)
    # False = the layer applies no positional encoding to q/k (NoPE).
    rope_pattern: tuple = (True,)
    # Kinds of layer whose parameter SHAPES differ, as a repeating period:
    # layer l is of kind layer_kinds[l % len]. "attention" is the block above;
    # "mamba" is a selective state-space mixer (Mamba-1 with Jamba's three
    # inner norms) in attention's place, over the same dense MLP. Each kind
    # has its own stacked leaves (``[num_mamba_layers, ...]``,
    # ``[num_attn_layers, ...]``; norms and the MLP ``[num_layers, ...]``),
    # only attention layers have pages in the KV pool, and a mamba layer
    # keeps a recurrent state per seat beside it (transformer.init_state).
    # "lightning" is a linear-attention mixer in attention's place: a matrix
    # state a head a seat (ops/lightning_attention), leaves ``lin_*``
    # stacked ``[num_lightning_layers, ...]``. A period may be as long as the
    # stack (a published list of layer types that repeats nothing).
    # "mamba2" is a Mamba-2 mixer: heads of a matrix state [head_dim,
    # d_state] whose decay is a gate's output, a token (ops/mamba2_ssd;
    # leaves ``m2_*`` stacked ``[num_mamba2_layers, ...]``); "experts" is a
    # layer that is the mixture feed-forward alone (leaves ``[num_moe_layers,
    # ...]``). Either makes the stack one of single sublayers
    # (``single_sublayer``, below).
    layer_kinds: tuple = ("attention",)
    mamba_d_inner: int = 0  # channels of the mixer (expand * hidden_size)
    mamba_d_state: int = 16  # SSM state a channel
    mamba_d_conv: int = 4  # taps of the causal depthwise conv
    mamba_dt_rank: int = 0
    mamba_conv_bias: bool = True
    # What the recurrent SSM state is held in between steps; the conv window
    # is held in the model's dtype.
    mamba_state_dtype: str = "float32"
    # Mamba-2 layers: ``mamba2_heads`` heads of ``mamba2_head_dim`` channels,
    # B and C in ``mamba2_groups`` groups of ``mamba2_d_state`` (head h reads
    # group h // (heads / groups)), a causal depthwise conv of
    # ``mamba2_d_conv`` taps over x, B and C alike, a gated RMSNorm a group
    # before the output projection. The state [heads, head_dim, d_state] a
    # seat is held in ``mamba_state_dtype``, the conv window in the model's.
    mamba2_heads: int = 0
    mamba2_head_dim: int = 0
    mamba2_groups: int = 1
    mamba2_d_state: int = 0
    mamba2_d_conv: int = 4
    # Lightning layers: heads of ``lightning_head_dim`` lanes for q, k and v
    # alike, q/k RMSNorm a head then RoPE over all lanes, decay ``exp(-2^(-8
    # (h + 1) / H))`` a token, the output an RMSNorm a head and a sigmoid gate
    # from the layer's input. The state is held in this type between steps.
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_state_dtype: str = "float32"
    # KDA layers (Kimi Delta Attention): ``kda_heads`` heads of
    # ``kda_head_dim`` lanes for q, k and v alike behind a causal depthwise
    # conv of ``kda_d_conv`` taps and SiLU, q and k L2-normed a head, a
    # log-decay a channel ``kda_gate_lower_bound * sigmoid(exp(A_log) * (W_f x
    # + dt_bias))`` in (bound, 0), a write strength ``sigmoid(W_b x)`` a head,
    # the delta-rule state [head_dim, head_dim] a head in
    # ``lightning_state_dtype`` (the matrix-state pool is the lightning
    # layers', under its name), the output an RMSNorm a head and a sigmoid
    # gate a lane.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_d_conv: int = 4
    kda_gate_lower_bound: float = -5.0
    # Attention layers' output times ``sigmoid(h W_g)`` (leaf ``wg``) before
    # the output projection: a gate a lane ([D, H, Dh]), or on latent
    # attention one scalar a head ([D, H]; ``attn_gate_per_head``).
    attn_output_gate: bool = False
    # Block-sparse attention (0 = every layer attends to all keys). A query
    # that sees ``sparse_dense_len`` keys or more attends to the first
    # ``sparse_init_blocks`` blocks of ``sparse_block_size`` tokens, the
    # blocks of its window and the ``sparse_topk`` best of the rest, ranked
    # by compressed keys (the mean of ``sparse_kernel_size`` keys every
    # ``sparse_kernel_stride``; ops/sparse_select). A KV head's query heads
    # share one selection.
    sparse_topk: int = 0
    sparse_block_size: int = 64
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    # Scalars on the stream (muP): the embedding times ``embed_scale``, every
    # mixer's and feed-forward's output times ``residual_scale`` before it
    # joins the stream, the final hidden state times ``logit_scale`` before
    # the head. 1.0 leaves the program as it is without them.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # MoE (0 experts = dense). The mixture layers are of one shape; the first
    # ``moe_leading_dense_layers`` layers (DeepSeek's ``first_k_dense_replace``)
    # are dense SwiGLU layers of width ``moe_dense_intermediate_size`` instead,
    # run before the scanned expert stack with leaves of their own
    # (``wi`` / ``wo_mlp`` [k, ...]; the expert leaves are [L - k, ...]).
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_intermediate_size: int = 0
    moe_num_shared_experts: int = 0
    moe_capacity_factor: float = 1.25
    # The gate's activation in an expert: act(gate) * up. "silu" (SwiGLU) or
    # "relu" (ReGLU). With ``moe_gated`` false an expert (and the shared
    # expert) is two products with the activation between, ``W_down act(W_up
    # u)``, and "relu2" (the square of relu) is the one such activation.
    moe_activation: str = "silu"
    moe_gated: bool = True
    # The shared expert's width where it is not ``intermediate_size *
    # moe_num_shared_experts`` (0).
    moe_shared_intermediate_size: int = 0
    # The experts this device holds of every mixture layer: ``moe_held_count``
    # of them from ``moe_held_first`` on (0 = all). The router still scores
    # all ``moe_num_experts`` and takes its top-k of them; a routed copy
    # whose expert is not held is left out of the sum (another device's
    # part), costs no GEMM row and no bank fetch, and the banks are stacked
    # ``[layers, moe_held_count, ...]``.
    moe_held_first: int = 0
    moe_held_count: int = 0
    # What ``router_bias`` is drawn at (transformer.ROUTER_BIAS_SCALE has
    # the story); a family states its own where 0.1 skews its load.
    moe_router_bias_scale: float = 0.1
    # Which normed stream the router reads: "mlp_norm" (the expert block's own
    # input) or "attn_norm" (the layer's pre-attention normed stream: logits
    # are computed before attention and carried to the expert block).
    moe_router_input: str = "mlp_norm"
    moe_leading_dense_layers: int = 0
    moe_dense_intermediate_size: int = 0
    # How router logits become weights. "softmax": softmax over all experts,
    # top-k, renormalise. "sigmoid" (DeepSeek-V3's ``noaux_tc``): s =
    # sigmoid(logits); the choice is top-k of s + ``router_bias`` (a trained
    # buffer, ``e_score_correction_bias``; only with ``moe_router_bias``), the
    # weights are s at the choice, renormalised, times ``moe_routed_scaling``.
    moe_scoring: str = "softmax"
    moe_router_bias: bool = False
    moe_routed_scaling: float = 1.0
    # Group-limited choice (DeepSeek-V3's ``n_group`` / ``topk_group``; sigmoid
    # scoring): the experts stand in ``moe_n_group`` groups of consecutive
    # experts, a group's score is the sum of its two best ``s + bias``, the
    # best ``moe_topk_group`` groups are kept and the top-k is taken among
    # their experts. 1 group is the plain top-k.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Dual-batch overlap: split MoE tokens into two independent half-batches so XLA
    # overlaps one half's all-to-all with the other's expert GEMMs (--enable-dbo).
    moe_dbo: bool = False
    # Multimodal (vision tower): 0 mm_tokens = text-only. Each media item
    # contributes exactly mm_tokens placeholder positions (id mm_placeholder_id)
    # whose embeddings are injected from the encode stage — the E/PD contract
    # (guides/multimodal-serving/e-disaggregation/README.md: encode workers
    # produce embeddings consumed by prefill/decode alongside text tokens).
    mm_tokens: int = 0
    mm_placeholder_id: int = 0
    vision_patch: int = 8  # square patch edge (pixels)
    vision_image_size: int = 32  # inputs resized/cropped to this square edge
    vision_layers: int = 0
    vision_hidden: int = 0
    vision_heads: int = 4
    # Multi-head latent attention (DeepSeek-V2/V3 family — the architecture of
    # the reference's wide-EP north-star benchmarks, guides/wide-ep-lws). KV is
    # compressed to a shared per-token latent c_kv [mla_kv_lora_rank] plus a
    # decoupled RoPE key [mla_rope_dim]; attention runs ABSORBED (q projected
    # into latent space through W_UK, output re-expanded through W_UV), which
    # makes it exactly MQA with head_dim = rank + rope_dim over the paged pool
    # — per-token KV bytes shrink ~(2*Hk*Dh)/(rank+rope) vs GQA.
    # 0 = standard GQA attention.
    mla_kv_lora_rank: int = 0
    mla_rope_dim: int = 0
    mla_qk_nope_dim: int = 0  # per-head non-RoPE q/k dim (score dot in latent space)
    mla_v_head_dim: int = 0  # per-head value dim after W_UV re-expansion
    # q-side low-rank projection (``q_lora_rank``): q = RMSNorm(h W_qa) W_qb
    # with leaves ``mla_wqa`` / ``mla_q_norm`` / ``mla_wqb``; 0 = the one
    # matrix ``mla_wq``.
    mla_q_lora_rank: int = 0

    def __post_init__(self):
        # a file of published keys gives lists; the config must stay hashable
        object.__setattr__(self, "attn_window_pattern",
                           tuple(int(w) for w in self.attn_window_pattern))
        object.__setattr__(self, "rope_pattern",
                           tuple(bool(r) for r in self.rope_pattern))
        object.__setattr__(self, "layer_kinds",
                           tuple(str(k) for k in self.layer_kinds))
        if set(self.layer_kinds) - {"attention", "mamba", "lightning",
                                    "mamba2", "experts", "kda"} or \
                "attention" not in self.layer_kinds:
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: a period of 'attention', "
                "'mamba', 'lightning', 'mamba2', 'kda' and 'experts' layers "
                "with at least one attention layer")
        if self.single_sublayer and (
                set(self.layer_kinds) - {"attention", "mamba2", "experts"}
                or ("experts" in self.layer_kinds) != self.is_moe
                or self.sparse_topk or self.moe_leading_dense_layers
                or self.moe_router_input != "mlp_norm"):
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: 'mamba2' and 'experts' "
                "layers stand in a stack of single sublayers beside "
                "attention layers only, 'experts' exactly where the model "
                "is a mixture")
        if self.has_recurrent:
            if (self.num_layers - self.moe_leading_dense_layers) \
                    % len(self.layer_kinds) or \
                    len(self.attn_window_pattern) != 1:
                raise ValueError(
                    f"layer_kinds {self.layer_kinds} must be one period that "
                    f"divides num_layers={self.num_layers}, over attention "
                    "layers of one kind (attn_window_pattern and rope_pattern "
                    "of one entry)")
            if self.has_mamba and min(
                    self.mamba_d_inner, self.mamba_d_state, self.mamba_dt_rank,
                    self.mamba_d_conv - 1) < 1:
                raise ValueError(
                    "a model with mamba layers states mamba_d_inner, "
                    "mamba_d_state, mamba_dt_rank and mamba_d_conv >= 2")
            if self.has_lightning and min(self.lightning_heads,
                                          self.lightning_head_dim) < 1:
                raise ValueError(
                    "a model with lightning layers states lightning_heads "
                    "and lightning_head_dim")
            if self.has_mamba2 and (min(
                    self.mamba2_heads, self.mamba2_head_dim,
                    self.mamba2_d_state, self.mamba2_d_conv - 1) < 1
                    or self.mamba2_groups < 1
                    or self.mamba2_heads % self.mamba2_groups):
                raise ValueError(
                    "a model with mamba2 layers states mamba2_heads (a "
                    "whole number a group), mamba2_head_dim, "
                    "mamba2_d_state and mamba2_d_conv >= 2")
            if self.has_kda and (min(
                    self.kda_heads, self.kda_head_dim,
                    self.kda_d_conv - 1) < 1
                    or not -5.0 <= self.kda_gate_lower_bound < 0
                    or set(self.layer_kinds) - {"kda", "attention"}):
                # (the bound: ops/kda_attention takes exp of a block's
                # summed log-decay, 16 tokens, which must stay a float32)
                raise ValueError(
                    "a model with kda layers states kda_heads, kda_head_dim, "
                    "kda_d_conv >= 2 and a kda_gate_lower_bound in [-5, 0), "
                    "beside attention layers only")
            for key in ("mamba_state_dtype", "lightning_state_dtype"):
                if getattr(self, key) not in ("float32", "bfloat16"):
                    raise ValueError(f"{key}={getattr(self, key)!r}")
            if self.is_moe and not (self.single_sublayer
                                    or self.recurrent_over_mixture):
                raise ValueError(
                    "a mixture beside recurrent layers is a stack of single "
                    "sublayers with layers of kind 'experts', or 'kda' "
                    "mixers over a sigmoid-routed mixture feed-forward in "
                    "one layer; a 'mamba' or 'lightning' mixer over a "
                    "mixture feed-forward is not served")
            if self.attn_bias or (self.is_mla and not self.has_kda):
                raise ValueError(
                    "recurrent layers stand beside attention layers without "
                    "bias only, and latent attention (MLA) beside 'kda' "
                    "layers only (MLA beside 'mamba', 'mamba2' or "
                    "'lightning' layers and an attention bias beside "
                    "recurrent layers are not served)")
            if self.has_kda and (self.qk_norm or self.sparse_topk):
                raise ValueError(
                    "beside kda layers: attention without qk_norm or sparse "
                    "selection")
        if self.attn_gate_per_head and not self.has_kda:
            raise ValueError(
                "attn_output_gate on latent attention (its head-wise form, "
                "attn_gate_per_head) is served beside kda layers only")
        g = self.moe_n_group
        if (g, self.moe_topk_group) != (1, 1) and not (
                self.is_moe and self.moe_scoring == "sigmoid" and g > 1
                and self.moe_num_experts % g == 0
                and 0 < self.moe_topk_group <= g
                and self.moe_num_experts // g >= 2
                and self.moe_topk_group * (self.moe_num_experts // g)
                >= self.moe_top_k):
            raise ValueError(
                f"moe_n_group={g}, moe_topk_group={self.moe_topk_group}: "
                "sigmoid routing over whole groups of two experts or more, "
                "the kept groups holding at least moe_top_k experts")
        if self.sparse_topk:
            st = self.sparse_kernel_stride
            if self.is_mla or self.has_window or any(self.rope_pattern) or \
                    self.sparse_kernel_size != 2 * st or \
                    self.sparse_block_size % st or \
                    self.sparse_window % self.sparse_block_size or \
                    self.sparse_init_blocks < 0:
                raise ValueError(
                    "sparse_topk: GQA attention layers without RoPE or a "
                    "window (a compacted page table is exact only there), "
                    "kernels of two strides, blocks of whole strides and a "
                    "window of whole blocks")
        if len(self.attn_window_pattern) != len(self.rope_pattern) or \
                self.num_layers % len(self.rope_pattern):
            raise ValueError(
                f"attn_window_pattern {self.attn_window_pattern} and "
                f"rope_pattern {self.rope_pattern} must be one period of equal "
                f"length that divides num_layers={self.num_layers}")
        if self.moe_activation not in (
                ("silu", "relu") if self.moe_gated else ("relu2",)):
            raise ValueError(
                f"moe_activation={self.moe_activation!r} with "
                f"moe_gated={self.moe_gated}: gated experts take 'silu' or "
                "'relu', non-gated ones 'relu2'")
        if self.moe_held_count and not (
                self.is_moe and self.moe_held_first >= 0 and
                self.moe_held_first + self.moe_held_count
                <= self.moe_num_experts):
            raise ValueError(
                f"moe_held_first={self.moe_held_first} and moe_held_count="
                f"{self.moe_held_count}: a range of the model's "
                f"{self.moe_num_experts} experts")
        if self.moe_router_input not in ("mlp_norm", "attn_norm"):
            raise ValueError(f"moe_router_input={self.moe_router_input!r}")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring={self.moe_scoring!r}")
        if self.moe_scoring == "softmax" and (
                self.moe_router_bias or self.moe_routed_scaling != 1.0):
            raise ValueError(
                "moe_router_bias and moe_routed_scaling belong to "
                "moe_scoring='sigmoid'")
        if self.mla_q_lora_rank and not self.is_mla:
            raise ValueError("mla_q_lora_rank needs mla_kv_lora_rank")
        k = self.moe_leading_dense_layers
        if k and not (self.is_moe and 0 < k < self.num_layers
                      and self.moe_dense_intermediate_size > 0
                      and self.layer_period == 1
                      and (self.layer_kinds == ("attention",)
                           or self.recurrent_over_mixture)):
            raise ValueError(
                f"moe_leading_dense_layers={k}: a mixture model of more "
                "layers than that, with moe_dense_intermediate_size stated "
                "and attention layers of one kind (beside recurrent layers: "
                "'kda' mixers over the mixture, the leading layers 'kda')")

    @property
    def layer_period(self) -> int:
        """Layers in one period of the attention pattern (1 = one kind)."""
        return len(self.attn_window_pattern)

    @property
    def has_mamba(self) -> bool:
        return "mamba" in self.layer_kinds

    @property
    def has_lightning(self) -> bool:
        return "lightning" in self.layer_kinds

    @property
    def has_mamba2(self) -> bool:
        return "mamba2" in self.layer_kinds

    @property
    def attn_gate_per_head(self) -> bool:
        """``attn_output_gate`` is one scalar a head (``wg`` [D, H]): its
        form on latent attention, whose heads' outputs have no lanes of the
        hidden size's split to gate one by one."""
        return self.attn_output_gate and self.is_mla

    @property
    def has_kda(self) -> bool:
        return "kda" in self.layer_kinds

    @property
    def has_matrix_state(self) -> bool:
        """Some layer keeps a matrix state a head in the ``lin`` pool."""
        return self.has_lightning or self.has_kda

    @property
    def recurrent_over_mixture(self) -> bool:
        """Every layer is a mixer ('kda' or 'attention') and then a
        feed-forward that is the sigmoid-routed mixture, but for the first
        ``moe_leading_dense_layers`` layers, which are 'kda' mixers over a
        dense SwiGLU and stand BEFORE the periods of ``layer_kinds`` (layer
        ``k + j`` is of kind ``layer_kinds[j % len]``)."""
        return (self.has_kda and self.is_moe
                and self.moe_scoring == "sigmoid")

    @property
    def single_sublayer(self) -> bool:
        """A layer is ONE sublayer (``x += Sub(RMSNorm(x; attn_norm_l))``):
        a mixer of its kind or, kind "experts", the feed-forward; an
        attention layer then has no feed-forward behind it and the stack no
        ``mlp_norm``. So wherever a period holds a 'mamba2' or an 'experts'
        layer; elsewhere every layer is its mixer and then a feed-forward."""
        return bool({"mamba2", "experts"} & set(self.layer_kinds))

    @property
    def has_recurrent(self) -> bool:
        """Some layer keeps a recurrent state per sequence."""
        return (self.has_mamba or self.has_lightning or self.has_mamba2
                or self.has_kda)

    def _layers_of(self, kind: str) -> int:
        """(leading dense layers, of kind 'kda', stand before the periods)"""
        k = self.moe_leading_dense_layers if self.has_kda else 0
        return ((self.num_layers - k) // len(self.layer_kinds)
                * self.layer_kinds.count(kind)) + (k if kind == "kda" else 0)

    @property
    def num_mamba_layers(self) -> int:
        return self._layers_of("mamba")

    @property
    def num_lightning_layers(self) -> int:
        return self._layers_of("lightning")

    @property
    def num_mamba2_layers(self) -> int:
        return self._layers_of("mamba2")

    @property
    def num_kda_layers(self) -> int:
        return self._layers_of("kda")

    @property
    def kda_d_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def mamba2_d_inner(self) -> int:
        return self.mamba2_heads * self.mamba2_head_dim

    @property
    def mamba2_conv_dim(self) -> int:
        """Channels the Mamba-2 conv runs over: x, B and C side by side."""
        return self.mamba2_d_inner + 2 * self.mamba2_groups * self.mamba2_d_state

    @property
    def num_attn_layers(self) -> int:
        """Layers with pages in the KV pool."""
        return self._layers_of("attention")

    @property
    def kv_pool_folds(self) -> int:
        """What the KV pool folds into its page axis: the attention layers,
        and with sparse selection each layer's KV heads too (a head's pages
        are then pages of their own, which a selected page table names)."""
        return self.num_attn_layers * (
            self.num_kv_heads if self.sparse_topk else 1)

    @property
    def layer_runs(self) -> tuple:
        """One period of ``layer_kinds`` as runs of one kind:
        ``((kind, first layer of the run within the period, layers), ...)``."""
        runs: list = []
        for j, kind in enumerate(self.layer_kinds):
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, j, 1])
        return tuple(tuple(r) for r in runs)

    @property
    def has_window(self) -> bool:
        return any(w > 0 for w in self.attn_window_pattern)

    @property
    def has_vision(self) -> bool:
        return self.mm_tokens > 0 and self.vision_layers > 0

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @property
    def is_moe(self) -> bool:
        return self.moe_num_experts > 0

    @property
    def num_moe_layers(self) -> int:
        """Mixture layers: what the expert leaves and counts are stacked by."""
        if self.single_sublayer:
            return self._layers_of("experts")
        return (self.num_layers - self.moe_leading_dense_layers
                if self.is_moe else 0)

    @property
    def moe_bank_slots(self) -> int:
        """Expert slots a mixture layer's banks hold on this device."""
        return self.moe_held_count or self.moe_num_experts

    @property
    def moe_bank_width(self) -> int:
        """The F of the expert banks as stored. Non-gated experts' width is
        rounded up to whole lane tiles of 128 with zero columns of ``moe_wi``
        and zero rows of ``moe_wo`` (exact: the activation of 0 is 0 and
        meets a zero row): at 1,856 the TPU compiler copied the whole stack
        of banks, 3.7 GB, into a padded layout at every call's entry."""
        f = self.moe_intermediate_size or self.intermediate_size
        return f if self.moe_gated else -(-f // 128) * 128

    @property
    def mamba2_in_width(self) -> int:
        """Columns of a Mamba-2 layer's in-projection as stored: z, the
        conv's channels, and dt a head rounded up to a whole lane tile with
        zero columns (10,304 -> 10,368 at the published sizes: unaligned,
        the compiler transposed the stack at every call's entry)."""
        return (self.mamba2_d_inner + self.mamba2_conv_dim
                + -(-self.mamba2_heads // 128) * 128)

    @property
    def moe_shared_width(self) -> int:
        return self.moe_shared_intermediate_size or (
            self.intermediate_size * self.moe_num_shared_experts)

    @property
    def layered_init(self) -> bool:
        """The stack is drawn a layer at a time (transformer.init_params)."""
        return bool(self.mla_q_lora_rank or self.moe_leading_dense_layers
                    or self.moe_scoring != "softmax")

    @property
    def is_mla(self) -> bool:
        return self.mla_kv_lora_rank > 0

    @property
    def kv_cache_heads(self) -> int:
        """KV heads as stored in the paged pool (1 for MLA's shared latent)."""
        return 1 if self.is_mla or self.sparse_topk else self.num_kv_heads

    @property
    def kv_cache_head_dim(self) -> int:
        """Per-token per-head KV width in the pool (latent + rope key for MLA)."""
        return (self.mla_kv_lora_rank + self.mla_rope_dim) if self.is_mla \
            else self.head_dim
