"""Which kernel serves which step program of which architecture.

``resolve`` is the one place in the package that asks ``jax.default_backend()``,
reads ``EngineConfig.attn_impl`` / ``moe_matmul`` / ``moe_dispatch`` (and
``LLMD_MOE_DISPATCH``), or branches on an architecture's kind to pick a kernel.
It returns one frozen record, ``Backends``: the callables the step programs
bind (``engine/programs.py``), the facts the engine's hot path reads, and the
provenance labels the engine exports. There is no trial compile: a kernel the
rule selected compiles at the serving shape or the engine fails at its first
step.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.models.config import ModelConfig
from llmd_tpu.models.transformer import ragged_paged_attention_xla


@dataclass(frozen=True)
class Backends:
    """What ``resolve`` decided. The ``*_impl`` callables are forward_core's
    arguments of those names, ``attn_impl`` the unified-shape programs'
    (unified, verify, embed) and ``attn_decode_impl`` the fused decode
    calls'; ``core_kwargs`` is what only a model with recurrent layers hands
    forward_core (``scan_impl``, ``ssd_impl``, ``lin_impl`` or ``kda_impl``,
    ``query_attn_impl``)."""

    attn_impl: Callable
    attn_decode_impl: Callable
    moe_matmul_impl: Optional[Callable]  # None: the XLA einsum
    moe_dispatch_impl: Optional[Callable]  # None: the capacity einsum
    core_kwargs: dict
    ring_attn_impl: Optional[Callable]  # the sp ring, where wired
    # facts
    pallas_attn: bool  # a Pallas attention kernel serves (GQA or latent)
    pallas_interpret: bool  # Pallas kernels run in interpret mode (the CPU)
    window_align: int  # pages a window layer's page table is shifted by
    compiler_options: Optional[dict]  # of every step program's jit
    moe_gemm_plan: Optional[Callable]  # `bank_fetch_plan` of a unified step
    # (KV block pages, rows a group) of the kernel that walks its one-query
    # rows in groups (`ops/row_groups.py`); None where none does
    decode_groups: Optional[tuple[int, int]]
    # labels, as the engine exports them
    attn_backend: str
    attn_fallback_reason: Optional[str]
    attn_geometry: str
    moe_backend: str
    moe_fallback_reason: Optional[str]
    moe_dispatch: str  # "sorted" | "einsum" | "n/a (dense model)"
    moe_dispatch_fallback_reason: Optional[str]
    moe_gemm_geometry: str
    ssm_backend: Optional[str]
    ssm_state_dtype: Optional[str]
    sp_attn_backend: Optional[str]


def resolve(model_cfg: ModelConfig, engine_cfg: EngineConfig, mesh, *,
            kv_pack: int, cache_shape: tuple[int, ...],
            eplb_slots: Optional[int]) -> Backends:
    """Every kernel choice of an engine, from what its constructor knows
    before any program is traced: the model, the options, the mesh, the KV
    pool's pack factor and shape, and EPLB's slot count (None: no EPLB)."""
    # Pallas kernels run in interpret mode on the CPU platform only (an
    # explicit attn_impl/moe_matmul="pallas" under tests); on a TPU the
    # selected kernel goes through Mosaic or the engine fails
    platform = jax.default_backend()
    interpret = platform == "cpu"
    attn, attn_backend, attn_reason = _attn_impl(
        model_cfg, engine_cfg, mesh, platform, interpret)
    pallas_attn = attn_backend.startswith("pallas")
    gqa_kernel = attn_backend == "pallas_ragged_paged_attention"
    attn_geometry = _attn_blocks_label(model_cfg, engine_cfg, mesh,
                                       attn_backend, cache_shape)
    if kv_pack > 1:
        from llmd_tpu.ops.packed_kv import make_packed_attn

        # the paged impls (Pallas or XLA) run against the packed pool via
        # slot-placed queries; the ring program below stays unwrapped (it
        # attends over chunk activations, not the pool)
        attn = make_packed_attn(attn, model_cfg, kv_pack)
        attn_backend += f"+packed{kv_pack}"
    # the fused-decode-shaped programs take the same impl: the ragged
    # kernels (GQA and latent) serve one-row-a-sequence calls too
    attn_decode = attn
    if gqa_kernel and getattr(attn, "plan", None):
        # (a packed pool's wrapper has no plan: its rows keep the upstream
        # call) every row of the fused call brings one query
        attn_decode = functools.partial(attn, one_query_rows=True)
        attn_decode.plan = attn.plan
    if model_cfg.has_recurrent and gqa_kernel:
        # a recurrent layer carries the last bit of an attention layer's
        # result on, so a prompt's chunks are handed to the kernel cut at
        # its KV blocks' ends (ops/paged_attention.split_rows_at_kv_blocks);
        # the fused call's rows bring one query each and are never cut
        # (the latent kernel, beside 'kda' layers, gives a chunk whole and
        # in two calls the same bits: tools/mla_attn_sweep.py's third check)
        attn = functools.partial(attn, split_at_kv_blocks=True)
    # what only a model with recurrent layers hands forward_core: the
    # selective scan (the Pallas kernel wherever the Pallas attention
    # kernel serves, the XLA form elsewhere)
    core_kwargs: dict = {}
    ssm_backend = ssm_state_dtype = None
    if model_cfg.has_recurrent:
        impl = "pallas" if pallas_attn else "xla"
        if model_cfg.has_mamba:
            from llmd_tpu.ops.selective_scan import make_selective_scan

            core_kwargs["scan_impl"] = make_selective_scan(
                impl, interpret=interpret)
            ssm_backend = f"{impl}_selective_scan"
            ssm_state_dtype = model_cfg.mamba_state_dtype
        elif model_cfg.has_mamba2:
            from llmd_tpu.ops.mamba2_ssd import BLOCK, make_mamba2_ssd

            core_kwargs["ssd_impl"] = make_mamba2_ssd(
                impl, interpret=interpret)
            ssm_backend = f"{impl}_mamba2_ssd_block{BLOCK}"
            ssm_state_dtype = model_cfg.mamba_state_dtype
        elif model_cfg.has_kda:
            from llmd_tpu.ops.kda_attention import BLOCK, make_kda_attention

            core_kwargs["kda_impl"] = make_kda_attention(
                impl, interpret=interpret)
            ssm_backend = f"{impl}_kda_attention_block{BLOCK}"
            ssm_state_dtype = model_cfg.lightning_state_dtype
        else:
            from llmd_tpu.ops.lightning_attention import (
                make_lightning_attention,
            )

            core_kwargs["lin_impl"] = make_lightning_attention(
                impl, interpret=interpret)
            ssm_backend = f"{impl}_lightning_attention"
            ssm_state_dtype = model_cfg.lightning_state_dtype
    if model_cfg.sparse_topk:
        # the one-query rows of the selected page tables go to the impl
        # the fused decode call has (never cut at KV blocks)
        core_kwargs["query_attn_impl"] = attn_decode
    moe_matmul, moe_backend, moe_reason = _moe_matmul_impl(
        model_cfg, engine_cfg, platform, interpret)
    moe_dispatch_impl, moe_dispatch, moe_dispatch_reason = _moe_dispatch_impl(
        model_cfg, engine_cfg, mesh, eplb_slots,
        use_pallas=moe_backend == "pallas_grouped_gemm", interpret=interpret)
    # what a window layer's page table is shifted by is rounded down to
    # the ragged kernel's KV block; the XLA impl shifts by whole pages
    window_align = 1
    if gqa_kernel:
        from llmd_tpu.ops.paged_attention import window_align_pages

        window_align = window_align_pages(
            (engine_cfg.max_batch_size, model_cfg.num_heads, cache_shape[-1]),
            cache_shape, engine_cfg.max_pages_per_seq)
    moe_gemm_geometry, moe_gemm_plan = _moe_gemm_label_and_plan(
        model_cfg, engine_cfg, moe_backend, moe_dispatch,
        plannable=mesh is None and eplb_slots is None)
    decode_groups = None
    if attn_backend == "pallas_mla_ragged_paged_attention":
        from llmd_tpu.ops import mla_attention

        decode_groups = (mla_attention.pick_block_sizes(
            0, 0, engine_cfg.page_size, engine_cfg.max_pages_per_seq)[0],
            mla_attention.GROUP_ROWS)
    elif getattr(attn, "plan", None):  # the GQA rows kernel serves
        from llmd_tpu.ops.paged_attention import GROUP_ROWS

        decode_groups = (window_align, GROUP_ROWS)
        # the rows kernel serves the one-query rows, so many a group
        attn_geometry += f" groups={decode_groups[1]}"
    compiler_options = None
    if model_cfg.moe_scoring == "sigmoid" or model_cfg.has_lightning:
        # Every rounding the program states is made. Left free, XLA keeps
        # a bf16 value in float32 where it fuses producer and consumer,
        # and what it fuses follows a program's shapes: on the chip a
        # decode row's hidden state through the fused decode call and
        # through the unified step parted by a bf16 step inside a scanned
        # expert layer, the next layer chose another expert, and tokens
        # served cold and from the prefix cache parted (PR 39, seed
        # 2147485403). With this routing only, as `combine_in_order`: the
        # softmax models' compiled programs stay what their cells measured
        compiler_options = {"xla_allow_excess_precision": False}
    ring, sp_attn_backend = _ring_attn_impl(model_cfg, engine_cfg, mesh)
    return Backends(
        attn_impl=attn, attn_decode_impl=attn_decode,
        moe_matmul_impl=moe_matmul, moe_dispatch_impl=moe_dispatch_impl,
        core_kwargs=core_kwargs, ring_attn_impl=ring,
        pallas_attn=pallas_attn, pallas_interpret=interpret,
        window_align=window_align, compiler_options=compiler_options,
        moe_gemm_plan=moe_gemm_plan, decode_groups=decode_groups,
        attn_backend=attn_backend, attn_fallback_reason=attn_reason,
        attn_geometry=attn_geometry,
        moe_backend=moe_backend, moe_fallback_reason=moe_reason,
        moe_dispatch=moe_dispatch,
        moe_dispatch_fallback_reason=moe_dispatch_reason,
        moe_gemm_geometry=moe_gemm_geometry,
        ssm_backend=ssm_backend, ssm_state_dtype=ssm_state_dtype,
        sp_attn_backend=sp_attn_backend)


def _attn_impl(model_cfg: ModelConfig, engine_cfg: EngineConfig, mesh,
               platform: str, interpret: bool):
    """Pick the attention kernel by rule: the Pallas ragged-paged-attention
    kernel on TPU, the XLA gather+mask reference on CPU. Returns (impl,
    ``attn_backend``, ``attn_fallback_reason``)."""
    mode = engine_cfg.attn_impl
    if model_cfg.is_mla:
        # Absorbed MLA runs as MQA with head_dim = latent rank + rope dim
        # (288-640 lanes) over the single-plane pool: past the GQA Pallas
        # kernel's head sizes. On a TPU every step program (unified,
        # verify, embed and the fused decode call alike) takes the latent
        # kernel for ragged rows (ops/mla_attention); the XLA gather is
        # the CPU reference and serves no step on the chip.
        if mode == "reference" or (mode == "auto" and platform != "tpu"):
            # the CPU's designed backend, not a degradation: the reason
            # stays empty so that real fallbacks are observable
            return ragged_paged_attention_xla, "xla_mla_absorbed", None
        from llmd_tpu.ops import mla_attention

        impl = functools.partial(
            mla_attention.mla_paged_attention,
            rank=model_cfg.mla_kv_lora_rank, interpret=interpret, mesh=mesh)
        # the rows' groups are the batch's, not a layer's: once a program
        impl.plan = mla_attention.plan
        return impl, "pallas_mla_ragged_paged_attention", None
    if mode == "reference":
        return ragged_paged_attention_xla, "xla_reference", None
    if mode == "auto" and platform != "tpu":
        return (ragged_paged_attention_xla, "xla_reference",
                f"backend={platform} (non-TPU)")
    from llmd_tpu.ops import paged_attention as pa

    heads_per_kv = model_cfg.num_heads // model_cfg.num_kv_heads
    # a model with recurrent layers reuses no prefix (engine.py: no two of
    # its rows name one page) and keeps the upstream call for every row; of
    # the rest, the layouts the sweep passed hand their one-query rows to
    # the repo's kernel, in groups derived once a program
    if model_cfg.has_recurrent or not pa.rows_kernel_serves(
            heads_per_kv, _pool_dtype(model_cfg, engine_cfg), mesh):
        impl = functools.partial(pa.paged_attention_tpu, mesh=mesh)
    else:
        impl = functools.partial(pa.paged_attention_tpu, mesh=mesh,
                                 interpret=interpret)
        impl.plan = functools.partial(pa.plan, heads_per_kv=heads_per_kv)
    return impl, "pallas_ragged_paged_attention", None


def _pool_dtype(model_cfg: ModelConfig, engine_cfg: EngineConfig):
    return (jnp.float8_e4m3fn if engine_cfg.kv_cache_dtype == "fp8"
            else model_cfg.jax_dtype)


def _attn_blocks_label(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                       mesh, attn_backend: str,
                       cache_shape: tuple[int, ...]) -> str:
    """The (bkv, bq) block geometry the ragged Pallas kernel (the GQA
    one or the latent one) is traced with in the two step programs that
    carry the load, as ``unified=<bkv>x<bq> decode=<bkv>x<bq>``; a GQA
    unified step that hands its decode rows and its chunks to the kernel
    in two calls names both pairs, ``unified=32x8+32x64``
    (`ops/paged_attention.step_geometry`); the latent kernel adds what its
    two products see, the rows of a chunk's query block on a device and
    the value lanes, ``rows=320 v=512``; ``none`` where another backend
    serves. It is a function of static shapes, so it is known here. A
    model with window layers adds the period of windows its layers are
    traced with (any backend), as ``window=0,4096,4096,4096``. (Where the
    GQA kernel's one-query rows go to the repo's rows kernel, `resolve` adds
    the rows a group, ``groups=8``.)"""
    window = (" window=" + ",".join(map(str, model_cfg.attn_window_pattern))
              if model_cfg.has_window else "")
    programs = (("unified", engine_cfg.batched_tokens),
                ("decode", engine_cfg.max_batch_size))
    if attn_backend == "pallas_mla_ragged_paged_attention":
        from llmd_tpu.ops.mla_attention import (
            chunk_fold, pick_block_sizes, value_lanes)

        def pair(n):
            return pick_block_sizes(n, engine_cfg.max_batch_size,
                                    engine_cfg.page_size,
                                    engine_cfg.max_pages_per_seq)

        bq = pair(engine_cfg.batched_tokens)[1]
        heads = model_cfg.num_heads // (
            mesh.shape["tp"] if mesh is not None else 1)
        return " ".join(
            ["{}={}x{}".format(prog, *pair(n)) for prog, n in programs]
            + [f"rows={bq * chunk_fold(bq, heads)}",
               "v={}".format(value_lanes(model_cfg.mla_kv_lora_rank,
                                         cache_shape[-1]))])
    if attn_backend != "pallas_ragged_paged_attention":
        return "none" + window
    from llmd_tpu.ops.paged_attention import format_geometry, step_geometry

    # (with sparse selection a call brings one KV head's query heads; a
    # model with recurrent layers has its unified step's rows cut at KV
    # blocks, `resolve`)
    heads = model_cfg.num_heads // (
        model_cfg.num_kv_heads if model_cfg.sparse_topk else 1)
    return " ".join(
        prog + "=" + format_geometry(step_geometry(
            (n, heads, cache_shape[-1]), cache_shape,
            engine_cfg.max_batch_size, engine_cfg.max_pages_per_seq,
            model_cfg.has_recurrent))
        for prog, n in programs) + window


def _moe_matmul_impl(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                     platform: str, interpret: bool):
    """Pick the MoE expert-GEMM path by rule: Pallas grouped GEMM for
    bf16 banks on TPU, XLA einsum on CPU and for int8 banks. Returns (impl,
    ``moe_backend``, ``moe_fallback_reason``)."""
    if not model_cfg.is_moe:
        return None, "n/a (dense model)", None
    if engine_cfg.quantize_weights == "int8":
        # int8 expert banks run the scaled-einsum path (moe_block);
        # the Pallas grouped GEMM is bf16-only — an EXPLICIT pallas
        # request conflicts and must fail loudly, like every other
        # explicit-mode contract in backend selection
        if engine_cfg.moe_matmul == "pallas":
            raise ValueError(
                "moe_matmul='pallas' (grouped GEMM, bf16-only) is "
                "incompatible with quantize_weights='int8'")
        return (None, "xla_einsum (int8 weights)",
                "int8 weights (grouped GEMM is bf16-only)")
    mode = engine_cfg.moe_matmul
    if mode == "einsum":
        return None, "xla_einsum", None
    if mode == "auto" and platform != "tpu":
        return None, "xla_einsum", f"backend={platform} (non-TPU)"
    from llmd_tpu.ops.grouped_gemm import make_moe_matmul

    return make_moe_matmul(interpret=interpret), "pallas_grouped_gemm", None


def _moe_gemm_label_and_plan(
        model_cfg: ModelConfig, engine_cfg: EngineConfig, moe_backend: str,
        moe_dispatch: str, *,
        plannable: bool) -> tuple[str, Optional[Callable]]:
    """(label, plan) of the ragged grouped GEMM in the unified step, both
    functions of static shapes. The label is the kernel's grid, as
    ``<order>x<bf of moe_wi>x<bf of moe_wo>``; ``none`` where the Pallas
    kernel does not serve. The plan is `bank_fetch_plan` at the block rows
    and blocks a layer of a unified step's sorted dispatch, which
    `_moe_record` books ``moe_gemm_blocks_total`` with; None where the
    step's [L, E] counts do not say what the plan held (a mesh's shards,
    EPLB's replica slots: not ``plannable``; DBO's halves, the einsum
    dispatch)."""
    from llmd_tpu.ops.grouped_gemm import (RGG_ORDER, bank_fetch_plan,
                                           pick_bank_tile)
    from llmd_tpu.ops.moe_dispatch import pick_block_size, plan_blocks

    cfg = model_cfg
    if moe_dispatch != "sorted":
        return "none", None
    pallas = moe_backend == "pallas_grouped_gemm"
    copies = engine_cfg.batched_tokens * cfg.moe_top_k
    # (the slots a layer's bank holds here: all the experts, or the share
    # `moe_held_count` names, whose counts `moe_block` then returns by slot)
    bc = pick_block_size(copies, cfg.moe_bank_slots, pallas)
    plan = None
    if plannable and not cfg.moe_dbo:
        plan = functools.partial(
            bank_fetch_plan, bc=bc,
            nb=plan_blocks(copies, cfg.moe_bank_slots, bc))
    held = "" if not cfg.moe_held_count else " held={}-{}/{}".format(
        cfg.moe_held_first, cfg.moe_held_first + cfg.moe_held_count - 1,
        cfg.moe_num_experts)
    if not pallas:
        return "none" + held, plan
    item = jnp.dtype(cfg.jax_dtype).itemsize
    label = "{}x{}x{}".format(
        RGG_ORDER,
        pick_bank_tile(cfg.hidden_size, (2 if cfg.moe_gated else 1)
                       * cfg.moe_bank_width, bc, item),
        pick_bank_tile(cfg.moe_bank_width, cfg.hidden_size, bc, item))
    return label + held, plan


def _moe_dispatch_impl(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                       mesh, eplb_slots: Optional[int], *, use_pallas: bool,
                       interpret: bool):
    """Pick the MoE routing-dispatch path (orthogonal to the expert-GEMM
    backend above): token-sorted drop-free (ops/moe_dispatch) vs the
    legacy capacity-einsum reference. ``EngineConfig.moe_dispatch`` =
    auto|sorted|einsum; auto honours LLMD_MOE_DISPATCH and otherwise
    resolves to sorted everywhere — einsum stays as the parity
    reference and kill switch. Returns (the dispatch_impl closure, or
    None for einsum; ``moe_dispatch``; ``moe_dispatch_fallback_reason``)."""
    if not model_cfg.is_moe:
        return None, "n/a (dense model)", None
    mode = engine_cfg.moe_dispatch
    if mode == "auto":
        mode = os.environ.get("LLMD_MOE_DISPATCH", "") or "sorted"
    if mode not in ("sorted", "einsum"):
        raise ValueError(
            f"moe_dispatch must be auto|sorted|einsum, got {mode!r}")
    if mode == "einsum":
        return None, "einsum", None
    # slot dim must divide the ep axis for the bucketed all_to_all;
    # EPLB already rounds its slot count up (_init_eplb), so only the
    # bare expert count can mismatch
    ep = max(1, engine_cfg.mesh.ep) if mesh is not None else 1
    S = eplb_slots if eplb_slots is not None else model_cfg.moe_num_experts
    if S % ep:
        return (None, "einsum",
                f"expert slots ({S}) do not divide the ep axis ({ep})")
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

    # expert GEMMs ride the ragged Pallas kernel exactly when the
    # einsum path would have used the grouped Pallas kernel (bf16 on
    # TPU); CPU and int8 banks use the gathered-einsum block backend
    return (make_sorted_dispatch(mesh, use_pallas=use_pallas,
                                 interpret=interpret), "sorted", None)


def _ring_attn_impl(model_cfg: ModelConfig, engine_cfg: EngineConfig, mesh):
    """SP long-context prefill: a second unified program whose attention is
    the zig-zag ring over the sp axis (ops/ring_attention.py), engaged
    host-side for self-contained single-sequence prefill steps only —
    the regime where the S² attention term lives and context parallelism
    pays (SURVEY §5 long-context; compiled lazily on first eligible step).
    Returns (impl, ``sp_attn_backend``), both None where it is not wired."""
    NT, sp = engine_cfg.batched_tokens, engine_cfg.mesh.sp
    if not (mesh is not None and sp > 1 and engine_cfg.sp_ring_attention
            and NT % sp == 0
            and not model_cfg.has_window):  # the ring has no sliding window
        return None, None
    # MLA composes: absorbed attention is MQA over the latent (Hk=1,
    # G=H in the ring's grouped layout) and the latent rides the ICI
    # ring at rank+rope width — 4-8x fewer ring bytes than GQA KV.
    # Parity pinned by tests/test_mla.py::test_ring_prefill_parity_under_sp.
    from llmd_tpu.ops.ring_attention import make_ring_attn_impl

    # ONE layout decision, passed down — sp_flash_prefill would
    # otherwise re-derive it independently and a future change to its
    # degrade condition would make this provenance label lie
    layout = "zigzag" if NT % (2 * sp) == 0 else "contiguous"
    ring = make_ring_attn_impl(mesh, axis_name="sp",
                               zigzag=(layout == "zigzag"))
    return ring, f"ring_{layout}(sp={sp})"
