"""LLMEngine: continuous batching over the paged JAX model.

Mirrors the serving loop the reference drives through vLLM (SURVEY.md §3.1 'HOT LOOP:
continuous batching on accelerator'), built XLA-first:

- exactly two compiled programs after warmup — ``_unified_fn`` (flat mixed batch:
  several sequences' prefill chunks + decode tokens packed into a fixed
  ``max_num_batched_tokens`` budget, the --max-num-batched-tokens analogue) and
  ``_decode_multi_fn`` (fixed slot batch, up to k fused decode iterations in
  one device loop whose length the host works out for each call from its
  rows' budgets, ``decode_call_steps``) — both static-shaped; the host
  scheduler packs work into them,
- prefill batches ACROSS sequences: 32 arriving requests chunk-prefill together up
  to the token budget instead of one sequence per step,
- prefill never pays the [N, vocab] logits matmul — only each sequence's last
  hidden row is unembedded,
- both programs pick their own tokens (``sampling.sample_tokens`` inlined): one
  dispatch a step, and argmax alone when no row of the step samples; only a
  batch with a grammar or ``logit_bias`` row samples in a second program,
- the unified step runs one step ahead of the host: step n+1 is dispatched
  before step n's sampled tokens are read, its rows that ride on take their
  input token from step n's sampled array on the device, and the read, the
  finish checks and the outputs of step n happen under step n+1's device time
  (``_step_unified``; whoever needs host token state first calls
  ``_flush_pending_sample``),
- automatic prefix caching with chained block hashes + KV events (kv_manager),
- preemption by recompute when pages run out (vLLM semantics),
- kernel provenance: which attention / MoE implementation the platform/shape
  rule selected is recorded on the engine and exported on /metrics — a perf
  number without kernel provenance is undiagnosable,
- P/D roles: ``role=prefill`` stops after prompt processing and exports KV metadata
  (disagg connector picks it up); ``role=decode`` can import KV (disagg/transfer.py).
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from llmd_tpu.core.kv_events import KVEvent, block_keys_for_tokens
from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine.backends import resolve
from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.engine.kv_manager import PageAllocator, Sequence
from llmd_tpu.engine.programs import ProgramRegistry, build_step_programs
from llmd_tpu.engine.sampling import sample_tokens_biased
from llmd_tpu.engine.spec import propose_ngram_draft
from llmd_tpu.structured import (
    NEG_BIAS,
    StructuredState,
    compile_grammar,
    parse_logit_bias,
    structured_spec,
)
from llmd_tpu.models.config import ModelConfig
from llmd_tpu.obs.events import FlightRecorder
from llmd_tpu.obs.metrics import Registry, register_engine_metrics
from llmd_tpu.obs.tracing import global_tracer
from llmd_tpu.models.transformer import (
    init_cache,
    init_params,
    init_compressed_keys,
    init_state,
    param_logical_axes,
    window_first_page,
)
from llmd_tpu.ops.lightning_attention import BLOCK as LIGHTNING_BLOCK
from llmd_tpu.ops.mamba2_ssd import BLOCK as MAMBA2_BLOCK
from llmd_tpu.parallel.mesh import build_mesh


def attn_kv_tokens(model_cfg: ModelConfig, kv_lens, q_lens, page_size: int,
                   align_pages: int = 1) -> dict[str, int]:
    """Context tokens one attention layer of each kind is given by a call
    whose sequences have ``kv_lens`` tokens resident (this call's included)
    and ``q_lens`` query rows: ``full`` layers every token; ``window`` layers
    what ``models.transformer.window_view`` leaves them, whole pages from the
    first page their earliest query's window touches, rounded down to the
    attention backend's ``align_pages`` (the mean over the period's window
    layers, should their windows differ). A kind the model has no layer of is
    absent."""
    kv, q = np.asarray(kv_lens, np.int64), np.asarray(q_lens, np.int64)
    out: dict[str, int] = {}
    windows = [w for w in model_cfg.attn_window_pattern if w > 0]
    if len(windows) < model_cfg.layer_period:
        out["full"] = int(kv.sum())
    if windows:
        given = [kv - page_size * window_first_page(kv, q, w, page_size,
                                                    align_pages)
                 for w in windows]
        out["window"] = int(round(sum(int(g.sum()) for g in given)
                                  / len(windows)))
    return out


def expert_load_max_over_mean(cnt: np.ndarray) -> Optional[float]:
    """Of routed copies by layer and expert ``cnt`` [L, E]: the busiest
    expert's copies over the mean expert's, averaged over the layers that
    routed any; None where none did."""
    cnt = cnt.reshape(-1, cnt.shape[-1]).astype(np.float64)
    busy = cnt[cnt.sum(axis=1) > 0]
    if not len(busy):
        return None
    return float((busy.max(axis=1) / busy.mean(axis=1)).mean())


def _profile_phase(name: str):
    """Wrap a step-loop phase in a ``jax.profiler.TraceAnnotation`` so an
    on-demand capture (/debug/profile, obs/device.py) attributes host+device
    time to the same phase names the step-duration histogram exports. The
    annotation is a no-op TraceMe when no profiler session is active."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


# The parts a step program's host time is split into, in the order a unified
# step runs them; admission and the rest of step() have their own.
STEP_PARTS = ("plan", "pack", "stage", "transfer", "dispatch", "sample",
              "wait", "apply", "book")
ADMIT_PARTS = ("hash", "match", "place")
TURN_PARTS = ("route", "tail")

# Fused-decode calls kept in flight: one behind the running one, so the device
# goes back-to-back while the finished call's tokens cross back to the host.
# Costs up to DECODE_CHAIN_DEPTH * decode_steps speculative tokens a sequence
# at a stop token or stop string, which the host cannot foresee; an ending by
# max_tokens or max_model_len it can, and ``decode_call_steps`` ends the call
# there.
DECODE_CHAIN_DEPTH = 2

# The fewest steps a fused call is given while a row has more than that left.
# A chain's start costs the host a read, an apply, a deliver and a full pack
# (6-9 ms a call and 2.4-4.4 ms of deliver on the chip's host, PERF.md
# section 5: about one device step), which a call of a step or two cannot
# amortise. From the sweep of 2, 4, 8 and 16 on the chip (PERF.md section 6):
# the shorter the call, the sooner a finished row's seat and answer are free,
# and the more starts a token pays for.
DECODE_MIN_STEPS = 4


def decode_call_steps(left: list[int], cap: int) -> tuple[int, str]:
    """How many steps the next fused call runs, and what set that number.

    ``left`` holds, for each row of the call, the tokens it may still take
    once the calls in flight have landed (``max_tokens`` and ``max_model_len``
    both counted; zero or less: the calls in flight already end the row, and
    it takes no step of this one). The call ends where its first row is known
    to end, so that row's answer leaves and its seat is free: ``n`` is the
    smallest positive entry, no less than ``DECODE_MIN_STEPS``, no more than
    ``cap`` (``EngineConfig.decode_steps``) and no more than the largest
    entry, past which no row has a step to take. The second value is the
    ``bound`` label of ``llmd_tpu:decode_call_steps_total``: ``ending`` (a
    row's own budget), ``floor`` or ``cap``."""
    live = [r for r in left if r > 0]
    r_min, r_max = min(live, default=cap), max(live, default=cap)
    n = min(max(r_min, DECODE_MIN_STEPS), r_max, cap)
    return n, ("cap" if n == cap else
               "floor" if r_min < n < r_max else "ending")


class _StepParts:
    """One stretch of the step thread's time (a step program, admission, the
    rest of ``step()``, a turn of the loop), split into the parts ``names``
    on one set of ``perf_counter`` readings: ``to(part)`` closes the running
    part and opens the next (``None`` pauses, e.g. around a nested program).
    Each part is a ``<span>.<part>`` profiler annotation while it runs (free
    with no capture), and its seconds land in ``seconds`` for the part
    counter (``LLMEngine._book_parts``)."""

    __slots__ = ("program", "span", "seconds", "_part", "_t", "_ann")

    def __init__(self, program: str, span: str, part: Optional[str],
                 names: tuple[str, ...] = STEP_PARTS) -> None:
        self.program, self.span = program, span
        self.seconds = dict.fromkeys(names, 0.0)
        self._part: Optional[str] = None
        self._ann = None
        self.to(part)

    def to(self, part: Optional[str], annotate: bool = True) -> float:
        now = time.perf_counter()
        if self._part is not None:
            self.seconds[self._part] += now - self._t
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
        self._part, self._t = part, now
        if part is not None and annotate:
            self._ann = jax.profiler.TraceAnnotation(f"{self.span}.{part}")
            self._ann.__enter__()
        return now


def _step_phase(program: str, span: str, first: str, outer: bool = True):
    """A step-loop phase that splits its time: the method gets a running
    ``_StepParts`` as its first argument, closed and booked on every way
    out: a turn that dispatched nothing (an empty plan, a drained chain)
    leaves its seconds on the part counter and no duration sample. With
    ``outer`` the whole call also sits in the ``span`` annotation, as
    ``_profile_phase`` would put it."""
    def deco(fn):
        @functools.wraps(fn)
        def timed(self, *args, **kwargs):
            parts = _StepParts(program, span, first)
            try:
                return fn(self, parts, *args, **kwargs)
            finally:
                self._book_parts(parts)
        return _profile_phase(span)(timed) if outer else timed
    return deco


@dataclass
class EngineOutput:
    request_id: str
    new_token_ids: list[int]
    finished: bool
    finish_reason: Optional[str] = None
    num_cached_prompt_tokens: int = 0
    prompt_len: int = 0
    # perf_counter() at the end of the step() that produced this output: the
    # server observes stamp -> chunk written (llmd_tpu:stream_lag_seconds)
    t_step: float = 0.0


@dataclass
class EngineStats:
    num_waiting: int = 0
    num_running: int = 0
    kv_utilization: float = 0.0
    total_prefill_tokens: int = 0
    total_decode_tokens: int = 0
    # tokens produced by FUSED decode calls only (excludes the unified-step
    # degrade path, whose wall time lands in time_prefill_steps) — the only
    # numerator that matches time_decode_steps as a denominator
    decode_tokens_fused: int = 0
    total_preemptions: int = 0
    total_offload_loads: int = 0  # blocks pulled back from CPU/FS tiers
    eplb_rebalances: int = 0  # wide-EP expert-placement recomputes
    attn_backend: str = ""  # kernel provenance (bench/debug)
    moe_backend: str = ""
    moe_dispatch: str = ""  # "sorted" | "einsum" — routing-dispatch provenance
    moe_dropped_tokens: int = 0  # routed copies dropped past capacity (einsum
    # path only; the sorted path is drop-free by construction)
    kv_cache_dtype: str = ""  # "bf16" | "fp8" — pool dtype provenance
    kv_layout: str = ""  # "padded" | "packed-f" — pool lane layout provenance
    sp_attn_backend: Optional[str] = None  # ring layout when sp>1 wired in
    n_ring_prefill_steps: int = 0  # unified steps served by the ring program
    # Per-phase wall-time attribution (every serving-perf number must be
    # decomposable into where the time actually went):
    time_prefill_steps: float = 0.0  # wall inside unified (mixed/prefill) steps
    time_decode_steps: float = 0.0  # wall inside fused decode calls
    time_spec_steps: float = 0.0  # wall inside speculative verify steps
    # engine_step_part_seconds_total splits the same readings by part
    # (STEP_PARTS).
    n_unified_steps: int = 0
    n_decode_calls: int = 0  # fused decode calls PROCESSED (results applied)
    n_decode_dispatches: int = 0  # fused decode calls LAUNCHED; must equal
    # n_decode_calls once the engine drains — a gap means an in-flight record
    # was orphaned (its sampled tokens silently dropped)
    # Speculative decoding (spec_mode="ngram"): prompt-lookup drafts verified
    # through the flat mixed-batch program (engine/spec.py).
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_rejected: int = 0
    n_spec_verify_steps: int = 0
    # Speculation × structured compose (PERF.md Lever 13): the constrained
    # share of drafted/accepted (rows carrying a grammar or logit_bias),
    # plus the crosscheck mismatch count when spec_structured_crosscheck is
    # on (device-returned FSM state vs host StructuredState.sync; must be 0).
    spec_drafted_constrained: int = 0
    spec_accepted_constrained: int = 0
    spec_fsm_crosscheck_mismatches: int = 0
    # Structured outputs (llmd_tpu/structured): grammar-constrained requests
    # admitted, host-side mask builds (time_mask_build is the feature's only
    # per-step host cost — PERF.md compares it against step wall time), and
    # tokens observed outside the grammar (should stay 0; truncated
    # constrained generations count 1 at retirement).
    structured_requests: int = 0
    structured_mask_builds: int = 0
    structured_violations: int = 0
    time_mask_build: float = 0.0
    # Device-resident decode steady state (PERF.md Lever 12): host pack wall
    # that was hidden behind an in-flight device chain (a dispatch or process
    # was pending when the pack ran): everything of a chained dispatch before
    # its jitted call. A chain start's is serialized, and is not counted here.
    time_pack_overlap: float = 0.0
    # dispatches that reused the in-flight chain's device-resident outputs
    # (tokens/positions/kv-lens/FSM) instead of a full host re-pack
    n_chained_dispatches: int = 0
    # mask-table stagings for the fused constrained path (one per chain
    # start, not one per step — the per-step host mask build this replaces
    # is what time_mask_build used to count)
    structured_chain_stages: int = 0


class LLMEngine:
    """Single-process engine instance (one model replica over one mesh)."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        params: Optional[dict[str, jax.Array]] = None,
        event_sink: Optional[Callable[[list[KVEvent]], None]] = None,
        seed: int = 0,
        tokenizer: Optional[object] = None,
    ) -> None:
        self.model_cfg = model_cfg
        self.cfg = engine_cfg
        # Tokenizer for the structured-outputs vocab lift (structured/grammar):
        # optional — engines serving only unconstrained requests never need it,
        # and a structured request without one is rejected at add_request.
        self.tokenizer = tokenizer
        self.mesh = build_mesh(engine_cfg.mesh) if engine_cfg.mesh.num_devices > 1 else None
        R = max(1, engine_cfg.dp_ranks)
        self.num_ranks = R
        if R > 1:
            if engine_cfg.max_batch_size % R or engine_cfg.num_pages % R:
                raise ValueError(
                    f"max_batch_size ({engine_cfg.max_batch_size}) and num_pages "
                    f"({engine_cfg.num_pages}) must divide dp_ranks={R}")
            if engine_cfg.cpu_offload_pages > 0 or engine_cfg.offload_fs_path:
                raise ValueError("KV offload tiers are per-rank state; not yet "
                                 "supported with dp_ranks > 1")
            if engine_cfg.batched_tokens // R < 1:
                raise ValueError(
                    f"batched_tokens ({engine_cfg.batched_tokens}) must be at "
                    f"least dp_ranks={R} (each rank needs a token budget)")
        if model_cfg.has_recurrent:
            self._refuse_with_recurrent_layers(engine_cfg, model_cfg)
        # A cached page stands for a prefix only where every layer's state at
        # its boundary is in the pool: a recurrent layer's is not (snapshots
        # at block boundaries are not written), so such a model reuses none.
        self.prefix_reuse = (engine_cfg.enable_prefix_caching
                             and not model_cfg.has_recurrent)
        ppr = engine_cfg.num_pages // R
        self.allocs = [
            PageAllocator(
                ppr, engine_cfg.page_size,
                enable_prefix_caching=self.prefix_reuse,
                event_sink=event_sink, base_id=r * ppr,
            )
            for r in range(R)
        ]
        self.alloc = self.allocs[0]
        self.slots_per_rank = engine_cfg.max_batch_size // R
        # Shared metrics registry: the engine increments step-loop families
        # here and EngineServer renders them from its /metrics handler (in
        # wide-EP every frontend scrapes this same registry).
        self.registry = Registry()
        self.metrics = register_engine_metrics(self.registry)
        self.metrics.cache_config.labels(
            block_size=engine_cfg.page_size,
            num_gpu_blocks=engine_cfg.num_pages).set(1)
        # the children the step thread books at every turn, taken once: the
        # part counter's by (program, part) as each is first booked
        self._part_seconds: dict = {}
        self._admissions = {
            o: self.metrics.admissions.labels(outcome=o)
            for o in ("admitted", "no_seat", "no_pages", "never_fits")}
        self._admit_duration = self.metrics.step_duration.labels(phase="admit")
        self.tracer = global_tracer()
        # always-on per-request lifecycle timelines; EngineServer exposes
        # this recorder at /debug/requests (obs.events)
        self.flight = FlightRecorder.from_env(tracer=self.tracer)
        # latency attribution: every retired timeline folds into the phase
        # ledger and exports llmd_tpu:request_phase_seconds{phase,tenant,model}
        from llmd_tpu.obs.attribution import attach_phase_exporter

        attach_phase_exporter(self.flight, self.metrics.request_phase)
        # every XLA compile of this process, step program or not
        from llmd_tpu.obs.compiles import watch_xla_compiles

        self._xla_compiles = watch_xla_compiles(self.metrics, self.flight)
        # decision plane, engine view (obs/decisions.py): spec-decode
        # economics folded per request at retirement. Chained after the
        # phase exporter (on_finish is a single slot). The knob is cached
        # so the retire path reads one bool when the ledger is off.
        from llmd_tpu.obs.decisions import (
            attach_decision_exporter,
            decisions_enabled,
        )

        self._decisions_on = decisions_enabled()
        if self._decisions_on:
            attach_decision_exporter(self.flight, self.metrics,
                                     plane="engine")
        # utilization attribution plane (obs/costmodel.py): analytic roofline
        # costs stamped per dispatch + token-goodput/recompile ledgers. The
        # knob is read ONCE; off leaves self.util None so every dispatch
        # site pays a single `is not None` check and nothing else.
        from llmd_tpu.obs.costmodel import (
            UtilLedger,
            attach_util_exporter,
            util_ledger_enabled,
        )

        self.util = None
        if util_ledger_enabled():
            self.util = UtilLedger(
                model_cfg, device_kind=jax.devices()[0].device_kind,
                quantize_weights=engine_cfg.quantize_weights,
                kv_cache_dtype=engine_cfg.kv_cache_dtype)
            attach_util_exporter(self.util, self.metrics)
        # device-plane monitor (obs/device.py): attached by the owning
        # EngineServer at start(); the dispatch loop stamps its heartbeat
        self.monitor = None
        self.offload = None
        if engine_cfg.cpu_offload_pages > 0 or engine_cfg.offload_fs_path:
            from llmd_tpu.kv.fs_backend import FSKVBackend
            from llmd_tpu.kv.offload import KVOffloadConnector

            fs = FSKVBackend(engine_cfg.offload_fs_path) if engine_cfg.offload_fs_path else None
            self.offload = KVOffloadConnector(
                engine_cfg.cpu_offload_pages,
                staging_blocks=engine_cfg.offload_staging_blocks,
                fs_backend=fs, event_sink=event_sink,
                pages_per_layer=engine_cfg.num_pages,
                metrics=self.metrics, flight=self.flight,
            )
            self.alloc.evict_hook = lambda h, pid: self.offload.on_evict(self.cache, h, pid)
            store = self.offload.store
            self.metrics.offload_saves.set_function(lambda: store.saves)
            self.metrics.offload_loads.set_function(lambda: store.loads)
            self.metrics.offload_demotions.set_function(lambda: store.demotions)
            self.metrics.offload_cpu_blocks.set_function(lambda: len(store))
        # K5: out-of-tree connector — external engine behind the native tiers
        self.kv_connector = None
        self._connector_pool = None
        if engine_cfg.kv_connector:
            import concurrent.futures

            from llmd_tpu.kv.connector_api import build_kv_connector

            self.kv_connector = build_kv_connector(
                engine_cfg.kv_connector, engine_cfg.kv_connector_params)
            # one drain thread: saves stream out in retirement order without
            # ever blocking the locked engine step loop
            self._connector_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-connector")
        # N9: cluster-durable prefix tier — write-back queue + hardened client
        # over the remote store. Off unless LLMD_KV_DURABLE_STORE is set.
        self.durable = None
        self.writeback = None
        from llmd_tpu.kv.writeback import (DurableStoreClient,
                                           DurableStoreConfig, WritebackQueue)

        durable_cfg = DurableStoreConfig.from_env()
        if durable_cfg.enabled:
            self.durable = DurableStoreClient(durable_cfg)
            self.writeback = WritebackQueue(
                self.durable, max_blocks=durable_cfg.queue_blocks)
            if self.offload is not None:
                # eviction/demotion paths tee their already-materialized
                # host bytes into the flush queue (no extra device reads)
                self.offload.writeback = self.writeback
            else:

                def _durable_evict(h, pid):
                    P = self.cfg.num_pages
                    L = self.cache.shape[0] // P
                    rows = np.arange(L) * P + pid
                    self.writeback.offer([h], np.asarray(self.cache[rows])[None])

                self.alloc.evict_hook = _durable_evict
        self.waitq: list[deque[Sequence]] = [deque() for _ in range(R)]
        self.waiting = self.waitq[0]  # rank-0 alias (single-rank compat)
        self.running: list[Optional[Sequence]] = [None] * engine_cfg.max_batch_size
        self.seqs: dict[str, Sequence] = {}
        self.stats = EngineStats()
        # engine-emitted predictor training rows (drained by the server's
        # trace-forwarding loop or read directly by offline training)
        self.latency_trace: deque[dict] = deque(maxlen=4096)
        self._key = jax.random.PRNGKey(seed)
        self._outputs: list[EngineOutput] = []
        self._n_steps = 0  # step_num of the llmd.step annotation
        self._pending_decode: list[dict] = []  # in-flight pipelined decode calls
        # Device-resident decode steady state (PERF.md Lever 12): rotated
        # host-pack buffer sets — DECODE_CHAIN_DEPTH+1 of them so the buffers a
        # still-in-flight dispatch was packed from are never mutated while
        # jnp.asarray may still alias them (the CPU backend zero-copies).
        self._pack_bufs: list[dict[str, "np.ndarray"]] = []
        # staged dense mask tables, LRU-keyed by the participating grammars'
        # identities + pad shape; entries pin (bias_tab, next_tab) on device
        self._mask_tab_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # (spec probe arming is per-sequence — Sequence.spec_armed: a negative
        # prompt-lookup probe disarms that row until fresh tokens land for it,
        # removing redundant O(context) numpy scans without letting one
        # non-repetitive stream disarm drafting for the whole batch)
        # the unified step the host has not read yet (_step_unified runs one
        # step ahead: its sampled tokens, and the MoE counts beside them, are
        # read while the NEXT step is on the device). Its rows stay
        # schedulable: the next step takes their input token on the device.
        self._pending_sample: Optional[dict] = None
        # what a unified step is given for ``prev_sampled`` when no step is
        # in flight: zeros of a sampled array's shape, type and placement,
        # so the one compiled program serves both cases
        seats = (engine_cfg.max_batch_size,)
        self._zero_sampled = self._replicated(jnp.zeros(seats, jnp.int32))
        # the sampling state of a step in which no row samples (temperature
        # 0, top-k off, top-p 1, a key nothing draws from), resident on the
        # device: such a step uploads none and splits no key
        # (_sampling_state). The key is made as a sampling step makes its
        # own, so the first such step finds the split compiled.
        _, idle_key = jax.random.split(self._key)
        self._greedy_state = tuple(self._replicated(x) for x in (
            jnp.zeros(seats, jnp.float32), jnp.zeros(seats, jnp.int32),
            jnp.ones(seats, jnp.float32), idle_key))

        if params is None:
            params = init_params(model_cfg, jax.random.PRNGKey(seed))
        param_axes = param_logical_axes(model_cfg)
        if engine_cfg.quantize_weights:
            if engine_cfg.quantize_weights != "int8":
                raise ValueError(
                    f"unknown quantize_weights={engine_cfg.quantize_weights!r}"
                    " (supported: 'int8')")
            from llmd_tpu.models.quant import quantize_params

            # before sharding: the returned axes dict matches the new tree,
            # so meshed runs shard _q/_scale leaves like their bf16 ancestors
            params, param_axes = quantize_params(model_cfg, params,
                                                 base_axes=param_axes)
        self.quantization = engine_cfg.quantize_weights
        if self.mesh is not None:
            from llmd_tpu.parallel.mesh import shard_pytree

            params = shard_pytree(params, self.mesh, param_axes)
        self.params = params
        if engine_cfg.kv_cache_dtype not in (None, "fp8"):
            raise ValueError(
                f"unknown kv_cache_dtype={engine_cfg.kv_cache_dtype!r}"
                " (supported: 'fp8')")
        self.kv_dtype = (jnp.float8_e4m3fn if engine_cfg.kv_cache_dtype == "fp8"
                         else model_cfg.jax_dtype)
        from llmd_tpu.ops.packed_kv import pack_factor

        for name, known in (("kv_layout", ("auto", "padded", "packed")),
                            ("spec_mode", ("off", "ngram")),
                            ("structured_mode", ("auto", "off"))):
            if getattr(engine_cfg, name) not in known:
                raise ValueError(
                    f"unknown {name}={getattr(engine_cfg, name)!r} "
                    f"(supported: {', '.join(map(repr, known))})")
        # cumulative prefix-cache effectiveness (feeds the hit-ratio gauge)
        self._prefix_cached_total = 0
        self._prefix_prompt_total = 0
        self.kv_pack = (pack_factor(model_cfg)
                        if engine_cfg.kv_layout in ("auto", "packed") else 1)
        if engine_cfg.kv_layout == "packed" and self.kv_pack == 1:
            raise ValueError(
                "kv_layout='packed' requires padded_head_dim == f*head_dim "
                f"and num_kv_heads % f == 0; {model_cfg.name} is ineligible")
        self.cache = init_cache(model_cfg, engine_cfg.num_pages,
                                engine_cfg.page_size, dtype=self.kv_dtype,
                                pack=self.kv_pack)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # combined-head dim (2*Hk) shards over tp: K/V pairs stay together.
            # MLA replicates instead — its pool has ONE row (the shared
            # latent plane, axis size 1), and every head's shard needs the
            # full latent anyway (DeepSeek TP layout: heads shard, latent KV
            # replicates)
            spec = (P(None, None, None, None) if model_cfg.is_mla
                    else P(None, None, "tp", None))
            self.cache = jax.device_put(
                self.cache, NamedSharding(self.mesh, spec))
        # the recurrent-state pool beside the KV pool: a seat owns a slot
        # (no allocator), one more is the padding rows' scratch
        self.state: dict[str, jax.Array] = {}
        # tokens of a block of the recurrence's kernel, which a prompt's
        # chunks start on multiples of (0: the recurrence runs token by token)
        self._state_block = (LIGHTNING_BLOCK if model_cfg.has_matrix_state
                             else MAMBA2_BLOCK if model_cfg.has_mamba2 else 0)
        if model_cfg.has_recurrent:
            self.state = init_state(model_cfg, engine_cfg.max_batch_size)
            for has, gauge in (
                    (model_cfg.has_mamba or model_cfg.has_mamba2,
                     self.metrics.ssm_state_slots),
                    (model_cfg.has_matrix_state,
                     self.metrics.linear_state_slots)):
                if has:
                    gauge.set_function(
                        lambda: sum(s is not None for s in self.running))
        if model_cfg.sparse_topk:
            # the compressed-key plane, a key a page of the pool: it rides
            # with the state pools (both are donated with the KV pool)
            if not model_cfg.has_recurrent or self.mesh is not None or \
                    self.kv_pack > 1:
                raise ValueError(
                    "sparse_topk: served beside recurrent layers (whose "
                    "engine reuses no prefix and moves no page), on one "
                    "device, over the padded KV layout")
            from llmd_tpu.ops.sparse_select import geometry

            geometry(model_cfg, engine_cfg.page_size)  # the stride is a page
            self.state["ck"] = init_compressed_keys(
                model_cfg, engine_cfg.num_pages, dtype=self.kv_dtype)

        self._eplb = None
        self._eplb_slots: Optional[int] = None
        if engine_cfg.eplb is not None and model_cfg.is_moe:
            self._init_eplb()

        self.lora_registry = None
        self._lora_params: dict[str, jax.Array] = {}
        if engine_cfg.lora is not None:
            if model_cfg.is_mla:
                # the MLA attention branch applies no adapter deltas — serving
                # would silently return base-model outputs under adapter names
                raise ValueError(
                    "LoRA adapters are not supported on MLA models (the "
                    "absorbed-attention path has no adapter hook); remove "
                    "EngineConfig.lora or use a GQA model")
            from llmd_tpu.models.lora import LoRARegistry, init_lora_params

            self.lora_registry = LoRARegistry(engine_cfg.lora.max_adapters)
            # a displaced idle adapter's cached KV is invalid the moment its
            # slot is reassigned
            self.lora_registry.on_evict = lambda name: self._lora_forget(name)
            self._lora_params = init_lora_params(model_cfg, engine_cfg.lora)
            # name -> content-scoped hash key ("name@<weights-digest>"): KV only
            # matches KV computed under the SAME weights — stale generations can
            # never match (HBM, CPU tier, or FS files surviving a restart), while
            # P/D peers and restarts loading the same checkpoint stay compatible.
            self._lora_keys: dict[str, str] = {}
            if self.mesh is not None:
                from llmd_tpu.models.lora import lora_param_logical_axes
                from llmd_tpu.parallel.mesh import shard_pytree

                self._lora_params = shard_pytree(
                    self._lora_params, self.mesh, lora_param_logical_axes(model_cfg))

        # which kernel serves which program (engine/backends.py), once; the
        # labels stay on the engine for those that read them there
        # (perfbench/engine_child.py, serve.py's start-up line, tests)
        bk = self.backends = resolve(
            model_cfg, engine_cfg, self.mesh, kv_pack=self.kv_pack,
            cache_shape=self.cache.shape, eplb_slots=self._eplb_slots)
        self.attn_backend = self.stats.attn_backend = bk.attn_backend
        self.attn_fallback_reason = bk.attn_fallback_reason
        self.attn_geometry = bk.attn_geometry
        self.moe_backend = self.stats.moe_backend = bk.moe_backend
        self.moe_fallback_reason = bk.moe_fallback_reason
        self.moe_dispatch = self.stats.moe_dispatch = bk.moe_dispatch
        self.moe_dispatch_fallback_reason = bk.moe_dispatch_fallback_reason
        self.moe_gemm_geometry = bk.moe_gemm_geometry
        self.ssm_backend = bk.ssm_backend
        self.sp_attn_backend = self.stats.sp_attn_backend = bk.sp_attn_backend
        if bk.ssm_backend is not None:
            self.metrics.ssm_backend_info.labels(
                impl=bk.ssm_backend, state_dtype=bk.ssm_state_dtype,
                prefix_reuse="off").set(1)
        if model_cfg.is_moe:
            self.metrics.moe_backend_info.labels(
                backend=self.moe_backend, dispatch=self.moe_dispatch,
                gemm=self.moe_gemm_geometry).set(1)
        # kernel-vs-fallback visibility without scraping logs: an info-style
        # gauge keyed by the resolved backend and its block geometry (value 1)
        self.metrics.attn_backend_info.labels(
            backend=self.attn_backend,
            geometry=self.attn_geometry).set(1)
        self.stats.kv_cache_dtype = ("fp8" if self.kv_dtype == jnp.float8_e4m3fn
                                     else str(jnp.dtype(self.kv_dtype).name))
        self.stats.kv_layout = (f"packed-{self.kv_pack}" if self.kv_pack > 1
                                else "padded")
        use_lora = self.lora_registry is not None
        progs = build_step_programs(
            model_cfg, engine_cfg, self.mesh, bk, use_lora=use_lora,
            lora_scale=engine_cfg.lora.scale if use_lora else 1.0)
        # the registry (engine/programs.py): registration order is routing
        # priority, and a program without hooks is dispatched BY a routed
        # one. The `self._*_fn` aliases stay: tests and the hot-path linter
        # key on the `self._*_fn(...)` call spelling.
        self.programs = ProgramRegistry(
            on_dispatch=lambda name:
                self.metrics.program_dispatches.labels(program=name).inc())
        routed = {
            "unified": (LLMEngine._unified_eligible,
                        LLMEngine._run_unified_program),
            "verify": (lambda eng: eng.cfg.spec_mode == "ngram",
                       LLMEngine._run_verify_program),
            # terminal entry: always routable
            "decode": (lambda eng: True, LLMEngine._run_decode_program)}
        for name, fn in progs.items():
            eligible, run = routed.get(name, (None, None))
            self.programs.register(name, fn, eligible=eligible, run=run)
        # (as registered: a call notes a signature that compiled, for
        # read_compiled_programs)
        self._unified_fn = self.programs.fn("unified")
        self._verify_fn = self.programs.fn("verify")
        self._verify_masked_fn = self.programs.fn("verify_masked")
        self._decode_multi_fn = self.programs.fn("decode")
        self._decode_multi_masked_fn = self.programs.fn("decode_masked")
        self._embed_fn = self.programs.fn("embed")
        # the sp ring's (backends.py::_ring_attn_impl); None where not wired
        self._unified_ring_fn = self.programs.fn("unified_ring")

    def read_compiled_programs(self, keep_text: Optional[list] = None) -> int:
        """Which part of the model each instruction of the step programs
        that compiled since the last call belongs to (``ProgramRegistry.
        read_compiled``), published as ``llmd_tpu:program_part_ops``. Not
        part of ``step()``: the loop that drives the engine calls it after a
        step that left ``programs.unread`` non-empty, which is the warm-up."""
        n = self.programs.read_compiled(keep_text)
        if n:
            fam = self.metrics.program_part_ops
            with fam._lock:  # a scrape sees the old series or the new
                fam.clear()
                for labels, value in self.programs.parts.series():
                    fam.labels(**labels).set(value)
        return n

    # ----------------------------------------------------------------- EPLB
    # Wide-EP expert load balancing (reference --enable-eplb, wide-ep
    # decode.yaml:114-118). Physical slot weights + replica tables live beside the
    # logical params and are re-gathered every step_interval engine steps; all
    # shapes are fixed (R padded to its max) so the step programs never recompile.
    def _init_eplb(self) -> None:
        from llmd_tpu.parallel.eplb import ExpertLoadTracker

        e = self.cfg.eplb
        E, L = self.model_cfg.moe_num_experts, self.model_cfg.num_moe_layers
        ep = max(1, self.cfg.mesh.ep)
        S = E + e.num_redundant_experts
        S += (-S) % ep  # slot dim shards evenly over the ep axis
        self._eplb = e
        self._eplb_slots = S
        self._eplb_rmax = S - E + 1  # one expert could own every redundant slot
        self._eplb_tracker = ExpertLoadTracker(L, E, e.window_size)
        self._eplb_steps = 0
        self._eplb_active = False  # set when a forward actually routed tokens

        mesh = self.mesh

        def _gather(wi, wo, s2e, wi_s=None, wo_s=None):
            l = jnp.arange(wi.shape[0])[:, None]
            wi_p, wo_p = wi[l, s2e], wo[l, s2e]
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                wi_p = jax.lax.with_sharding_constraint(
                    wi_p, NamedSharding(mesh, P(None, "ep", None, "tp")))
                wo_p = jax.lax.with_sharding_constraint(
                    wo_p, NamedSharding(mesh, P(None, "ep", "tp", None)))
            if wi_s is None:
                return wi_p, wo_p
            # int8 expert banks: the per-expert scales regather by the SAME
            # slot map — slot weights and their scales move together
            wi_sp, wo_sp = wi_s[l, s2e], wo_s[l, s2e]
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                # scales shard with their weights' surviving axes: wi keeps
                # its tp-sharded output channels, wo's outputs are unsharded
                wi_sp = jax.lax.with_sharding_constraint(
                    wi_sp, NamedSharding(mesh, P(None, "ep", "tp")))
                wo_sp = jax.lax.with_sharding_constraint(
                    wo_sp, NamedSharding(mesh, P(None, "ep", None)))
            return wi_p, wo_p, wi_sp, wo_sp

        self._eplb_gather = jax.jit(_gather)
        self._eplb_rebalance()

    def _eplb_rebalance(self) -> None:
        from llmd_tpu.parallel.eplb import balance_ratio, rebalance

        ep = max(1, self.cfg.mesh.ep)
        loads = self._eplb_tracker.loads()
        # imbalance under the OUTGOING placement (what serving just ran with):
        # max/mean routed tokens per EP rank, averaged over layers — the
        # "before" half of the rebalance-effectiveness pair on /metrics
        if getattr(self, "_eplb_s2e", None) is not None:
            self.metrics.moe_ep_imbalance.labels(when="before").set(
                float(np.mean([
                    balance_ratio(loads[l], self._eplb_s2e[l],
                                  self._eplb_counts[l], ep)
                    for l in range(loads.shape[0])])))
        s2e, slots, counts = rebalance(loads, self._eplb_slots, ep)
        self.metrics.moe_ep_imbalance.labels(when="after").set(
            float(np.mean([
                balance_ratio(loads[l], s2e[l], counts[l], ep)
                for l in range(loads.shape[0])])))
        self._eplb_counts = counts
        L, E, R = slots.shape
        if R < self._eplb_rmax:  # pad replica dim to its fixed max (no recompiles)
            pad = np.repeat(slots[:, :, :1], self._eplb_rmax - R, axis=2)
            slots = np.concatenate([slots, pad], axis=2)
        if "moe_wi_q" in self.params:  # int8 expert banks
            wi_p, wo_p, wi_sp, wo_sp = self._eplb_gather(
                self.params["moe_wi_q"], self.params["moe_wo_q"],
                jnp.asarray(s2e), self.params["moe_wi_scale"],
                self.params["moe_wo_scale"])
            extra = {"moe_wi_q": wi_p, "moe_wo_q": wo_p,
                     "moe_wi_scale": wi_sp, "moe_wo_scale": wo_sp}
        else:
            wi_p, wo_p = self._eplb_gather(
                self.params["moe_wi"], self.params["moe_wo"], jnp.asarray(s2e))
            extra = {"moe_wi": wi_p, "moe_wo": wo_p}
        self._eplb_params = {
            **extra,
            "eplb_replica_slots": jnp.asarray(slots),
            "eplb_replica_counts": jnp.asarray(counts),
        }
        self._eplb_s2e = s2e
        self.stats.eplb_rebalances += 1

    def _run_params(self) -> dict[str, jax.Array]:
        """Params seen by the step programs: base weights, plus physical expert
        weights under EPLB, plus the LoRA adapter bank when enabled."""
        if self._eplb is None and not self._lora_params:
            return self.params
        merged = dict(self.params)
        if self._eplb is not None:
            merged.update(self._eplb_params)
        merged.update(self._lora_params)
        return merged

    # ----------------------------------------------------------------- LoRA
    # Dynamic adapter serving (model-servers.md:55-75; adapter-rollout.md:11-31).
    # Loading writes one slot of the fixed-shape device bank — step programs
    # never recompile as adapters come and go.
    def _lora_slot(self, seq: "Sequence") -> int:
        if self.lora_registry is None:
            return 0
        return self.lora_registry.slot_of(seq.lora_id)

    def _lora_hash_key(self, name: Optional[str]) -> Optional[str]:
        """The lora term used in block hashing: generation-scoped when LoRA
        serving is on, the plain name otherwise (test fixtures etc.)."""
        if name is None or self.lora_registry is None:
            return name
        return self._lora_keys.get(name, name)

    def _lora_forget(self, name: str) -> None:
        """Retire a name's KV: reclaim HBM pages now (from every rank's
        partition); the dropped generation key guarantees tiered copies (CPU/FS)
        never match again."""
        self._lora_keys.pop(name, None)
        for alloc in self.allocs:
            alloc.purge_lora(name)

    def load_lora_adapter(self, name: str, weights: Optional[dict] = None,
                          seed: Optional[int] = None) -> int:
        """Install an adapter into a free slot. ``weights`` maps
        lora_{A,B}_{target} -> [L, ...] arrays; None generates a random test
        double (the filesystem-resolver path loads real weights and calls this)."""
        if self.lora_registry is None:
            raise RuntimeError("engine built without EngineConfig.lora")
        from llmd_tpu.models.lora import make_adapter_weights

        if self.lora_registry.has(name):
            if self.lora_registry.running.get(name) or self.lora_registry.waiting.get(name):
                # same guard as unload: swapping weights under live sequences
                # would mix two checkpoints in one generation
                raise RuntimeError(f"adapter {name!r} has in-flight requests")
            self._lora_forget(name)  # old generation's KV must never match again
        slot = self.lora_registry.assign(name)
        import hashlib

        if weights is None:
            # deterministic per name (not per process): P/D peers generating the
            # same test double agree on weights, hence on the content digest
            name_seed = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "big")
            weights = make_adapter_weights(
                self.model_cfg, self.cfg.lora,
                jax.random.PRNGKey(seed if seed is not None else name_seed))

        digest = hashlib.sha256()
        for k in sorted(weights):
            digest.update(k.encode())
            digest.update(np.ascontiguousarray(np.asarray(weights[k])).tobytes())
        self._lora_keys[name] = f"{name}@{digest.hexdigest()[:16]}"
        for key in self._lora_params:  # zero first: partial weight sets must not
            if key not in weights:     # inherit a displaced adapter's leftovers
                self._lora_params[key] = self._lora_params[key].at[:, slot].set(0)
        for key, w in weights.items():
            if key not in self._lora_params:
                raise KeyError(f"unknown LoRA param {key!r}")
            self._lora_params[key] = self._lora_params[key].at[:, slot].set(
                jnp.asarray(w, self._lora_params[key].dtype))
        return slot

    def unload_lora_adapter(self, name: str) -> bool:
        if self.lora_registry is None:
            return False
        if self.lora_registry.running.get(name) or self.lora_registry.waiting.get(name):
            # in-flight guard: freeing the slot mid-generation would silently
            # switch live sequences to base weights (and let the slot be reused)
            raise RuntimeError(f"adapter {name!r} has in-flight requests")
        slot = self.lora_registry.remove(name)
        if slot is None:
            return False
        for key in self._lora_params:  # zero the slot: it is the null adapter again
            self._lora_params[key] = self._lora_params[key].at[:, slot].set(0)
        # reclaim HBM now; the dropped generation key keeps every tier safe
        self._lora_forget(name)
        return True

    def _eplb_record(self, cnt: jax.Array) -> None:
        self._eplb_tracker.record(np.asarray(cnt))
        self._eplb_active = True

    def _count_attn_kv(self, program: str, kv_lens, q_lens, *, page_tables,
                       seats=None, steps=None) -> None:
        """``attn_kv_tokens_total``, ``attn_query_tokens_total`` and
        ``attn_query_key_pairs_total`` of one dispatched call, from the
        lengths the step already packed; under a kernel that walks its
        one-query rows in groups (the latent kernel, the GQA rows kernel)
        also ``latent_`` / ``attn_decode_kv_blocks_total`` of those rows,
        from their page tables (the rows' own, or all ``seats``' of which the
        rows take theirs; a fused call at its first step)."""
        kv, q = np.asarray(kv_lens, np.int64), np.asarray(q_lens, np.int64)
        if self.backends.decode_groups is not None:
            from llmd_tpu.ops.row_groups import decode_kv_blocks

            if seats is not None:
                page_tables = page_tables[seats]
            series = (self.metrics.latent_decode_kv_blocks
                      if self.model_cfg.is_mla
                      else self.metrics.attn_decode_kv_blocks)
            for blocks, n in zip(("rows", "fetched"), decode_kv_blocks(
                    page_tables, kv, q, self.cfg.page_size,
                    *self.backends.decode_groups)):
                series.labels(blocks=blocks).inc(n)
        for kind, n in attn_kv_tokens(self.model_cfg, kv_lens, q_lens,
                                      self.cfg.page_size,
                                      self.backends.window_align).items():
            self.metrics.attn_kv_tokens.labels(program=program,
                                               layers=kind).inc(n)
        if self.model_cfg.sparse_topk:
            self._count_sparse_rows(program, kv_lens, q_lens, steps)
        # a row's queries are its last q tokens: query i of q sees kv - q + i
        # + 1 keys, q * kv - q * (q - 1) / 2 in all
        self.metrics.attn_query_tokens.labels(program=program).inc(
            int(q.sum()))
        self.metrics.attn_qk_pairs.labels(program=program).inc(
            int((q * kv - q * (q - 1) // 2).sum()))

    def _count_sparse_rows(self, program: str, kv_lens, q_lens,
                           steps=None) -> None:
        """``sparse_attn_rows_total``, ``sparse_attn_qk_pairs_total`` and
        ``attn_kv_tokens_total{layers="sparse"}`` of one dispatched call: its
        queries by the path they take, the (query, key) pairs the rule asks
        for, and the tokens their tables hold: a row with a query below
        ``sparse_dense_len`` its resident tokens once (today's call), and
        every query past it the tokens of its selected blocks, which is one
        table a query also where a chunk brings many: the sum can pass what
        ``layers="full"`` counts once a row. A fused decode call (``steps``
        [rows]: the steps each row has left) books its rows' queries step by
        step and the tokens at its first step, as ``layers="full"`` does."""
        from llmd_tpu.ops.sparse_select import selected_tokens

        kv, q = np.asarray(kv_lens, np.int64), np.asarray(q_lens, np.int64)
        cfg = self.model_cfg
        held = n_sparse = n_all = pairs = 0
        one = kv[q == 1]  # decode rows: one query, so like against like
        if len(one):
            self.metrics.sparse_decode_kv_tokens.labels(tokens="held").inc(
                int(selected_tokens(cfg, one).sum()))
            self.metrics.sparse_decode_kv_tokens.labels(tokens="context").inc(
                int(one.sum()))
        for i, (k, n) in enumerate(zip(kv, q)):
            if n <= 0:
                continue
            seen = np.arange(k - n + 1, k + 1)  # keys each query sees
            past = seen[seen >= cfg.sparse_dense_len]
            held += int(selected_tokens(cfg, past).sum()) + (
                int(k) if len(past) < n else 0)
            if steps is not None:  # one query a step, a key more each
                seen = k + np.arange(int(steps[i]))
                past = seen[seen >= cfg.sparse_dense_len]
            n_sparse += len(past)
            n_all += len(seen)
            pairs += int(selected_tokens(cfg, past).sum()
                         + seen[seen < cfg.sparse_dense_len].sum())
        for path, n in (("sparse", n_sparse), ("dense", n_all - n_sparse)):
            if n:
                self.metrics.sparse_attn_rows.labels(path=path).inc(n)
        self.metrics.sparse_attn_qk_pairs.labels(program=program).inc(pairs)
        self.metrics.attn_kv_tokens.labels(program=program,
                                           layers="sparse").inc(held)

    def _count_ssm_tokens(self, program: str, chunk: int, decode: int) -> None:
        """``ssm_scan_tokens_total`` of one dispatched call: the tokens one
        mamba layer's scan is given, by the kind of row that brings them
        (``linear_attn_tokens_total`` for a model with lightning or kda
        layers)."""
        linear = self.model_cfg.has_matrix_state
        for rows, n in (("chunk", chunk), ("decode", decode)):
            if n and linear:
                self.metrics.linear_attn_tokens.labels(
                    rows="prefill" if rows == "chunk" else rows).inc(n)
            elif n:
                self.metrics.ssm_scan_tokens.labels(program=program,
                                                    rows=rows).inc(n)

    def _pools(self):
        """What a step program takes as its donated ``cache``: the KV pool,
        with the recurrent-state pool beside it where the model has one."""
        return {"kv": self.cache, **self.state} if self.state else self.cache

    def _keep_pools(self, pools) -> None:
        """Keep what a step program returned in ``_pools``'s place."""
        if self.state:
            self.state = {k: v for k, v in pools.items() if k != "kv"}
            pools = pools["kv"]
        self.cache = pools

    @staticmethod
    def _refuse_with_recurrent_layers(engine_cfg: EngineConfig,
                                      model_cfg: ModelConfig) -> None:
        """What a model with recurrent layers cannot be combined with, each
        refused by its name: the state a seat's slot holds exists nowhere
        else, so nothing that rolls a sequence back, moves it or splits its
        channels can be served."""
        why = {
            "spec_mode": (engine_cfg.spec_mode != "off",
                          "a verify step cannot roll a recurrent state back "
                          "over the rejected tokens"),
            "cpu_offload_pages": (
                engine_cfg.cpu_offload_pages > 0
                or bool(engine_cfg.offload_fs_path),
                "an offloaded page would need the recurrent layers' state at "
                "its boundary, which is not kept"),
            "kv_connector": (bool(engine_cfg.kv_connector),
                             "a transferred sequence would need its "
                             "recurrent state sent too"),
            "role": (engine_cfg.role != "both",
                     "prefill/decode disaggregation transfers pages, not "
                     "recurrent state"),
            "lora": (engine_cfg.lora is not None,
                     "a recurrent mixer has no adapter hook"),
            "mesh.tp": (engine_cfg.mesh.tp > 1,
                        "one KV head and the mixer's channels are not "
                        "sharded"),
            # a mixture beside recurrent layers (a stack of single
            # sublayers) runs its experts on one device, without replicas
            "eplb": (engine_cfg.eplb is not None and model_cfg.is_moe,
                     "the hybrid stack indexes the expert banks by layer and "
                     "held slot; replica slots are not wired into it"),
            "moe_dbo": (model_cfg.moe_dbo,
                        "the two half-batches would each need the rows' "
                        "state plan"),
            "mesh.ep": (engine_cfg.mesh.ep > 1 and model_cfg.is_moe,
                        "a layer's experts over real devices need their "
                        "exchange; this engine holds a stated share of them "
                        "(moe_held_first, moe_held_count) on one device"),
        }
        for name, (bad, reason) in why.items():
            if bad:
                raise ValueError(
                    f"{name}: not supported for a model with recurrent "
                    f"layers ({reason})")

    def _moe_record(self, drop, cnt, gemm_plan=None) -> None:
        """What a step's mixture layers report. ``drop``: every routed copy
        the legacy einsum path dropped past capacity C (the sorted path
        returns a structural 0 — moe_check asserts the scrape stays 0); under
        sigmoid routing a vector that carries the bias's moved choices and
        the routed copies beside it (``moe_block``).
        ``cnt`` [L, E]: routed copies by layer and expert, whose busiest
        expert over the mean, averaged over layers, is the step's
        ``moe_expert_load_max_over_mean``. Called where the step's outputs
        are already host-synced (or one call behind on the pipelined decode
        path), so the two small reads add no device sync of their own.
        ``gemm_plan``: `bank_fetch_plan` at the geometry those counts were
        laid out in, where one step's counts say it (a unified step's:
        `Backends.moe_gemm_plan`), for ``moe_gemm_blocks_total``."""
        if not self.model_cfg.is_moe:
            return
        drop = np.asarray(drop)
        if drop.ndim:  # sigmoid routing: [dropped, bias_moved, routed]
            self.metrics.moe_bias_moved.inc(int(drop[1]))
            self.metrics.moe_routed_copies.inc(int(drop[2]))
            if drop.shape[0] > 3:  # and, of a share of the experts, [held]
                self.metrics.moe_held_copies.inc(int(drop[3]))
            if drop.shape[0] > 4:  # and, under the group limit, [kept]
                self.metrics.moe_group_kept_copies.inc(int(drop[4]))
            drop = drop[0]
        n = int(drop)
        self.stats.moe_dropped_tokens += n
        self.metrics.moe_dropped_tokens.labels(
            path=self.stats.moe_dispatch or "einsum").inc(n)
        cnt = np.asarray(cnt)
        ratio = expert_load_max_over_mean(cnt)
        if ratio is not None:
            self.metrics.moe_expert_load.set(ratio)
        if gemm_plan is not None and ratio is not None:
            for outcome, blocks in zip(("fetch", "reuse", "padding"),
                                       gemm_plan(cnt)):
                self.metrics.moe_gemm_blocks.labels(outcome=outcome).inc(blocks)

    def _eplb_tick(self) -> None:
        # Count only steps that routed tokens — idle wave steps (DP lockstep with
        # no local work) must not burn rebalances, each of which re-gathers the
        # full expert weights on device.
        if not self._eplb_active:
            return
        self._eplb_active = False
        self._eplb_steps += 1
        if self._eplb_steps % self._eplb.step_interval == 0:
            self._eplb_rebalance()

    # ------------------------------------------------------------------ API
    def add_request(
        self,
        request_id: str,
        token_ids: list[int],
        sampling: Optional[SamplingParams] = None,
        lora_id: Optional[str] = None,
        rank: int = 0,
        mm_items: Optional[list[tuple[bytes, np.ndarray]]] = None,
        trace_ctx: Optional[object] = None,
    ) -> None:
        sampling = sampling or SamplingParams()
        if not token_ids:
            raise ValueError("empty prompt")
        if not (0 <= rank < self.num_ranks):
            raise ValueError(f"rank {rank} out of range (dp_ranks={self.num_ranks})")
        if len(token_ids) >= self.cfg.max_model_len:
            token_ids = token_ids[: self.cfg.max_model_len - 1]
        ps = self.cfg.page_size
        if (len(token_ids) + 1 + ps - 1) // ps > self.allocs[rank].num_pages:
            raise ValueError(
                f"prompt needs more KV pages than the rank's pool "
                f"({len(token_ids)} tokens, {self.allocs[rank].num_pages} pages × {ps})"
            )
        if lora_id and self.lora_registry is not None and not self.lora_registry.has(lora_id):
            # vLLM returns 404 for unknown adapters; silently serving base
            # weights would also poison the prefix cache under this name
            raise ValueError(f"unknown LoRA adapter {lora_id!r}")
        mm_items = mm_items or []
        if mm_items:
            k = self.model_cfg.mm_tokens
            if k <= 0:
                raise ValueError("model has no vision tower (mm_tokens=0)")
            n_ph = sum(1 for t in token_ids if t == self.model_cfg.mm_placeholder_id)
            if n_ph != k * len(mm_items):
                raise ValueError(
                    f"{len(mm_items)} media items need {k * len(mm_items)} "
                    f"placeholder tokens, prompt has {n_ph}")
            for h, emb in mm_items:
                if emb.shape != (k, self.model_cfg.hidden_size):
                    raise ValueError(f"mm embedding shape {emb.shape} != "
                                     f"({k}, {self.model_cfg.hidden_size})")
        # Structured outputs: compile (or cache-fetch) the token grammar BEFORE
        # any engine state mutates, so a malformed spec raises ValueError (the
        # server's 400 path) without leaking a queued sequence.
        logit_bias = parse_logit_bias(sampling.logit_bias)
        structured: Optional[StructuredState] = None
        compile_meta: Optional[tuple[str, bool, float]] = None
        spec = structured_spec(sampling)
        if spec is not None:
            if self.cfg.structured_mode == "off":
                raise ValueError(
                    "structured outputs are disabled (structured_mode='off')")
            if self.tokenizer is None:
                raise ValueError(
                    "structured request needs a tokenizer-equipped engine "
                    "(LLMEngine(..., tokenizer=...))")
            kind, payload = spec
            tc0 = time.perf_counter()
            grammar, cache_hit = compile_grammar(
                kind, payload, self.tokenizer, self.model_cfg.vocab_size)
            compile_s = time.perf_counter() - tc0
            structured = StructuredState(grammar, kind)
            compile_meta = (kind, cache_hit, compile_s)
            self.stats.structured_requests += 1
            m = self.metrics
            m.structured_requests.labels(kind=kind).inc()
            (m.structured_cache_hits if cache_hit
             else m.structured_cache_misses).inc()
            m.structured_compile_seconds.observe(compile_s)
        seq = Sequence(
            request_id=request_id, token_ids=list(token_ids), prompt_len=len(token_ids),
            max_tokens=sampling.max_tokens, sampling=sampling, lora_id=lora_id,
            lora_key=self._lora_hash_key(lora_id), arrival_time=time.monotonic(),
            rank=rank, mm_items=mm_items, trace_ctx=trace_ctx,
        )
        seq.structured = structured
        seq.logit_bias = logit_bias
        # pod state as a router would have observed it at arrival — joined with
        # the observed latencies at retirement into one predictor training row
        inflight = sum(
            len(s.token_ids) for s in self.running if s is not None
        ) + sum(s.prompt_len for q in self.waitq for s in q)
        seq.admit_features = {
            "kv_usage": sum(a.num_active for a in self.allocs) / max(1, self.cfg.num_pages),
            "input_len": float(len(token_ids)),
            "queue_depth": float(sum(len(q) for q in self.waitq)),
            "running_requests": float(sum(1 for s in self.running if s is not None)),
            "inflight_tokens": float(inflight),
            "prefix_match_pct": 0.0,  # known at admission; patched there
        }
        self.seqs[request_id] = seq
        self.waitq[rank].append(seq)
        self.flight.start(request_id, model=self.model_cfg.name,
                          trace_id=getattr(trace_ctx, "trace_id", "") or "")
        self.flight.record(request_id, "arrival", prompt_len=len(token_ids),
                           rank=rank, lora=lora_id)
        if compile_meta is not None:
            kind, cache_hit, compile_s = compile_meta
            self.flight.record(request_id, "structured_compile", kind=kind,
                               cache_hit=cache_hit,
                               compile_ms=round(compile_s * 1e3, 3))
        if self.lora_registry is not None:
            self.lora_registry.on_waiting(lora_id)

    def abort(self, request_id: str) -> None:
        seq = self.seqs.pop(request_id, None)
        if seq is None:
            return
        self.flight.finish(request_id, event="aborted", status="aborted",
                           generated=seq.num_generated)
        if seq.slot >= 0:
            self.running[seq.slot] = None
            if self.lora_registry is not None:
                self.lora_registry.on_finished(seq.lora_id)
        elif self.lora_registry is not None and seq.lora_id:
            # aborted while queued: rewind the waiting counter
            if self.lora_registry.waiting.get(seq.lora_id, 0) > 0:
                self.lora_registry.waiting[seq.lora_id] -= 1
        try:
            self.waitq[seq.rank].remove(seq)
        except ValueError:
            pass
        self._free_seq(seq)

    def drain_latency_trace(self) -> list[dict]:
        """Return + clear the accumulated predictor training rows.

        popleft-until-empty: atomic per element, so concurrent appends from the
        engine thread are neither dropped nor do they break iteration."""
        rows: list[dict] = []
        while True:
            try:
                rows.append(self.latency_trace.popleft())
            except IndexError:
                return rows

    def has_work(self) -> bool:
        return (any(self.waitq) or any(s is not None for s in self.running)
                or bool(self._pending_decode)
                or self._pending_sample is not None)

    # ------------------------------------------------------- scheduling core
    def _free_seq(self, seq: Sequence) -> None:
        alloc = self.allocs[seq.rank]
        for pid in seq.pages:
            alloc.release(pid)
        seq.pages = []

    def _try_admit(self) -> None:
        """Move waiting → running while slots + pages allow; reuse cached prefixes.

        Each DP rank admits independently (own queue, own batch-slot range, own
        page partition) — a saturated rank never head-of-line-blocks another.

        Admission is a program of the step ledger: its seconds go to
        ``program="admit"`` by part (hash, match, place) and, from the same
        sum, to ``step_duration{phase="admit"}``; a step whose queues are
        empty books nothing."""
        if not any(self.waitq):
            return
        parts = _StepParts("admit", "llmd.admit", None, ADMIT_PARTS)
        try:
            for rank in range(self.num_ranks):
                self._try_admit_rank(rank, parts)
        finally:
            total = self._book_parts(parts)
        if total:
            self._admit_duration.observe(total)

    def _try_admit_rank(self, rank: int, parts: _StepParts) -> None:
        """One attempt on the queue's head at a time, each with one outcome
        on ``admissions_total``: ``place`` is the seat search and, after
        ``hash`` (the prompt's block keys) and ``match`` (what of them the
        cache tiers hold, and the page budget), everything that seats the
        sequence or turns it away."""
        waiting = self.waitq[rank]
        alloc = self.allocs[rank]
        lo = rank * self.slots_per_rank
        hi = lo + self.slots_per_rank
        while waiting:
            parts.to("place")
            slot = next((i for i in range(lo, hi) if self.running[i] is None), None)
            if slot is None:
                self._admissions["no_seat"].inc()
                return
            seq = waiting[0]
            if seq.pages:
                # a waiting seq must own nothing — preemption empties the
                # ledger via _free_seq. Anything still here is a scheduling
                # bug's strays, and they must release BEFORE the capacity
                # check below: strays hold refs, so a starved pool would
                # otherwise head-of-line block on the very pages the head
                # seq itself is leaking.
                self._free_seq(seq)
            ps = self.cfg.page_size
            # prefix-cache lookup over complete prompt blocks. A head held
            # for want of pages is hashed and matched again at every step
            # until pages free: admit_hashed_tokens_total growing with
            # admissions_total{outcome="no_pages"} is that made visible
            parts.to("hash")
            keys = block_keys_for_tokens(seq.token_ids[: seq.prompt_len], ps,
                                         seq.lora_key, seq.mm_hashes())
            self.metrics.admit_hashed_tokens.inc(seq.prompt_len)
            parts.to("match")
            hit_pages = alloc.match_prefix(keys) if self.prefix_reuse else []
            # never reuse the whole prompt — the final token's logits must be computed
            max_reuse = max(0, (seq.prompt_len - 1) // ps)
            hit_pages = hit_pages[:max_reuse]
            # tiered continuation: blocks evicted from HBM may live on in CPU/FS
            n_offload = 0
            if self.offload is not None and len(hit_pages) < max_reuse:
                n_offload = self.offload.match_suffix(keys[len(hit_pages) : max_reuse])
            # ...and past the native tiers, the out-of-tree connector's engine
            n_conn = 0
            if self.kv_connector is not None and len(hit_pages) + n_offload < max_reuse:
                n_conn = self.kv_connector.get_num_matched_blocks(
                    keys[len(hit_pages) + n_offload : max_reuse])

            need_new = (min(seq.prompt_len + 1, self.cfg.max_pages_per_seq * ps) + ps - 1) // ps - len(hit_pages)
            # acquire_cached pulls hit pages out of the evictable LRU, so they stop
            # counting toward num_free — admission must budget num_free minus those
            # pages or a request can consume the pool with its own hits and livelock.
            hits_in_lru = sum(
                1 for pid in hit_pages
                if (info := alloc.pages.get(pid)) is not None and info.refs == 0
            )
            parts.to("place")
            if need_new > alloc.num_pages:
                # can never fit (prompt + generated tokens outgrew the pool, e.g. after
                # a preemption late in generation): finish with length, don't starve
                waiting.popleft()
                seq.finished = True
                seq.finish_reason = "length"
                self.seqs.pop(seq.request_id, None)
                self.flight.finish(seq.request_id, event="retired",
                                   reason="length", generated=seq.num_generated)
                self._outputs.append(EngineOutput(
                    request_id=seq.request_id, new_token_ids=[], finished=True,
                    finish_reason="length", prompt_len=seq.prompt_len,
                ))
                self._admissions["never_fits"].inc()
                continue
            if alloc.num_free - hits_in_lru < need_new:
                # head-of-line blocks; FCFS admission (within this rank)
                self._admissions["no_pages"].inc()
                return
            for pid in hit_pages:
                alloc.acquire_cached(pid)
            n_hbm = len(hit_pages)
            off_pages = self._reload_offloaded(seq, keys, n_hbm, n_offload)
            conn_pages: list[int] = []
            if n_conn > 0 and len(off_pages) == n_offload:
                conn_pages = self._load_from_connector(
                    seq, keys, n_hbm + len(off_pages), n_conn)
            seq.pages = list(hit_pages) + off_pages + conn_pages
            seq.block_hashes = keys[: n_hbm + len(off_pages) + len(conn_pages)]
            seq.num_computed = (n_hbm + len(off_pages) + len(conn_pages)) * ps
            seq.num_cached_prompt = seq.num_computed
            # prefix-cache effectiveness: the hit data always existed here but
            # never reached /metrics (cached tokens / prompt tokens, plus a
            # cumulative hit-ratio gauge)
            self._prefix_cached_total += seq.num_cached_prompt
            self._prefix_prompt_total += seq.prompt_len
            self.metrics.prefix_cached_tokens.inc(seq.num_cached_prompt)
            self.metrics.prefix_prompt_tokens.inc(seq.prompt_len)
            self.metrics.prefix_hit_ratio.set(
                self._prefix_cached_total / max(1, self._prefix_prompt_total))
            if seq.admit_features is not None:
                seq.admit_features["prefix_match_pct"] = (
                    seq.num_cached_prompt / max(1, seq.prompt_len))
            seq.slot = slot
            self.running[slot] = seq
            waiting.popleft()
            self.flight.record(seq.request_id, "admitted", slot=slot,
                               rank=rank, cached_tokens=seq.num_cached_prompt,
                               pages=len(seq.pages))
            if self.lora_registry is not None:
                self.lora_registry.on_running(seq.lora_id)
            self._admissions["admitted"].inc()

    def _reload_offloaded(self, seq: Sequence, keys: list[int], n_hbm: int,
                          n_offload: int) -> list[int]:
        """Pull CPU/FS-tier blocks back into freshly allocated HBM pages and
        re-index them (they emit BlockStored gpu again — they're resident now)."""
        if n_offload <= 0:
            return []
        ps = self.cfg.page_size
        off_pids: list[int] = []
        for _ in range(n_offload):
            pid = self.alloc.allocate()
            if pid is None:
                break
            off_pids.append(pid)
        if not off_pids:
            return []
        self.cache, n_loaded = self.offload.load_into_cache(
            self.cache, keys[n_hbm : n_hbm + len(off_pids)], off_pids,
            request_id=seq.request_id,
        )
        for pid in off_pids[n_loaded:]:  # block vanished mid-way (FS evictor race)
            self.alloc.release(pid)
        off_pids = off_pids[:n_loaded]
        for i, pid in enumerate(off_pids):
            bi = n_hbm + i
            chunk = seq.token_ids[bi * ps : (bi + 1) * ps]
            parent = keys[bi - 1] if bi > 0 else None
            self.alloc.commit_block(pid, keys[bi], chunk, parent, seq.lora_key)
        self.stats.total_offload_loads += len(off_pids)
        return off_pids

    def _load_from_connector(self, seq: Sequence, keys: list[int], start: int,
                             n_conn: int) -> list[int]:
        """Pull blocks from the out-of-tree connector's engine into fresh HBM
        pages and commit them as prefix-cache entries (K5 load path)."""
        ps = self.cfg.page_size
        pids: list[int] = []
        for _ in range(n_conn):
            pid = self.alloc.allocate()
            if pid is None:
                break
            pids.append(pid)
        if not pids:
            return []
        self.cache, n_loaded = self.kv_connector.load_blocks(
            self.cache, keys[start : start + len(pids)], pids, self.cfg.num_pages)
        for pid in pids[n_loaded:]:  # external engine lost the tail meanwhile
            self.alloc.release(pid)
        pids = pids[:n_loaded]
        for i, pid in enumerate(pids):
            bi = start + i
            chunk = seq.token_ids[bi * ps : (bi + 1) * ps]
            parent = keys[bi - 1] if bi > 0 else None
            self.alloc.commit_block(pid, keys[bi], chunk, parent, seq.lora_key)
        return pids

    def _ensure_pages(self, seq: Sequence, upto_tokens: int) -> bool:
        ps = self.cfg.page_size
        need = (upto_tokens + ps - 1) // ps
        alloc = self.allocs[seq.rank]
        while len(seq.pages) < need:
            pid = alloc.allocate()
            if pid is None:
                self.metrics.kv_exhaustion.inc()
                return False
            seq.pages.append(pid)
        return True

    def _finish_if_outgrew_pool(self, seq: Sequence) -> None:
        """Termination backstop for a RUNNING seq that can never be scheduled
        again: its next token needs more pages than the rank's ENTIRE pool
        (generation outgrew the pool with nothing left to evict). Without
        this the step loop spins forever — plan empty, has_work() true —
        because the admission-path 'can never fit → finish length' backstop
        (see _try_admit_rank) only reaches seqs that went back to the waitq.
        Mirrors its semantics: finish with 'length', deliver what we have."""
        ps = self.cfg.page_size
        if (len(seq.token_ids) + ps - 1) // ps <= self.allocs[seq.rank].num_pages:
            return  # transient pressure: another seq's retirement will free pages
        self._retire(seq, "length")
        self._outputs.append(EngineOutput(
            request_id=seq.request_id, new_token_ids=[], finished=True,
            finish_reason="length", num_cached_prompt_tokens=seq.num_cached_prompt,
            prompt_len=seq.prompt_len,
        ))

    def _preempt_one(self, rank: int = 0,
                     exclude: Optional[Sequence] = None,
                     parts: Optional[_StepParts] = None) -> bool:
        """Evict the rank's most recently arrived running seq back to waiting
        (recompute semantics). Pages are rank-partitioned, so only a same-rank
        victim frees memory the caller can use. ``exclude`` is the seq the
        caller is trying to schedule: evicting it frees its own pages only to
        reset it to token zero — a thrash loop, never progress. ``parts`` is
        the caller's running plan, paused around the read of the step in
        flight, which books its own seconds."""
        # Read the unified step in flight BEFORE choosing a victim: evicting
        # a row whose token is not applied yet would drop that token — full
        # re-prefill, re-defer, re-evict, a tight-pool ping-pong with zero
        # forward progress. The flush makes per-seq progress monotonic again
        # (the recompute path preserves applied tokens), and leaves every
        # row's last token on the host, so no row is ever evicted with a
        # token in flight. Preemption is the rare slow path, so the extra
        # device read here is noise.
        if parts is not None:
            parts.to(None)
        self._flush_pending_sample()
        if parts is not None:
            parts.to("plan")
        victims = [s for s in self.running
                   if s is not None and s.rank == rank and s is not exclude]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.arrival_time)
        self.running[victim.slot] = None
        victim.slot = -1
        if self.lora_registry is not None:  # back to waiting: keep counters true
            self.lora_registry.on_finished(victim.lora_id)
            self.lora_registry.on_waiting(victim.lora_id)
        self._free_seq(victim)
        victim.recompute = True  # its next first chunk is no admission
        victim.num_computed = 0
        victim.block_hashes = []
        victim.num_cached_prompt = 0
        self.waitq[rank].appendleft(victim)
        self.stats.total_preemptions += 1
        self.metrics.preemptions.inc()
        self.flight.record(victim.request_id, "preempted", rank=rank,
                           generated=victim.num_generated)
        return True

    # --------------------------------------------------------------- stepping
    def step(self) -> list[EngineOutput]:
        """One engine iteration: admit, then run the first eligible step
        program (engine/programs.py registration order: unified while any
        sequence is prefilling or a constrained row needs the unified
        degrade, speculative verify when spec_mode="ngram", fused decode
        otherwise)."""
        self._n_steps += 1
        with jax.profiler.StepTraceAnnotation("llmd.step",
                                              step_num=self._n_steps):
            # what step() does outside admission and the step program, on the
            # same ledger (program="step"; spans llmd.route, llmd.tail), so
            # that the parts of all programs cover the step thread's turn
            turn = _StepParts("step", "llmd", "tail", TURN_PARTS)
            try:
                self._outputs = []
                if self.offload is not None:
                    self._offload_drain()
                turn.to(None)
                with jax.profiler.TraceAnnotation("llmd.admit"):
                    self._try_admit()
                turn.to("route")
                program = self.programs.route(self)
                turn.to(None)
                program.run(self)
                turn.to("tail")
                self.stats.num_waiting = sum(len(q) for q in self.waitq)
                self.stats.num_running = sum(
                    1 for s in self.running if s is not None)
                self.stats.kv_utilization = (
                    sum(a.num_active for a in self.allocs)
                    / max(1, self.cfg.num_pages))
                m = self.metrics
                m.requests_waiting.set(self.stats.num_waiting)
                m.requests_running.set(self.stats.num_running)
                m.kv_usage.set(self.stats.kv_utilization)
                m.batch_occupancy.labels(kind="running").observe(
                    self.stats.num_running)
                m.batch_occupancy.labels(kind="waiting").observe(
                    self.stats.num_waiting)
                if self._eplb is not None:
                    self._eplb_tick()
                now = time.perf_counter()
                for out in self._outputs:
                    out.t_step = now
                return self._outputs
            finally:
                self._book_parts(turn)

    def _book_parts(self, parts: _StepParts) -> float:
        """Close ``parts`` and move its seconds to the part counter; returns
        their sum, which is what the phase's step_duration sample must be so
        that the two agree. Booked seconds leave ``parts``: a second call
        books only what ran since."""
        parts.to(None)
        total = 0.0
        seconds, children = parts.seconds, self._part_seconds
        for part, sec in seconds.items():
            if sec:
                key = (parts.program, part)
                child = children.get(key)
                if child is None:
                    child = children[key] = (
                        self.metrics.step_part_seconds.labels(
                            program=parts.program, part=part))
                child.inc(sec)
                seconds[part] = 0.0
                total += sec
        return total

    # ------------------------------------------------- step-program run hooks
    # Eligibility predicates + run hooks for the routable registry entries.
    # route() calls them unbound (spec.eligible(engine) / spec.run(engine)),
    # so a custom program registered by a test or a future subsystem can pass
    # any callable of the same shape — adding a program is one registry entry.

    def _unified_eligible(self) -> bool:
        """The unified mixed step serves prefill chunks, and remains the
        1-token degrade for constrained rows the dense-table scheme can't
        express (a row combining grammar AND logit_bias, or tables past the
        structured_table_max_elems gate)."""
        if self._prefilling_seqs():
            return True
        return (any(s is not None and (s.structured is not None or s.logit_bias)
                    for s in self.running)
                and self._constrained_needs_unified())

    def _run_unified_program(self) -> None:
        # the mixed step reads host token state — apply any in-flight decode first
        self._flush_pending_decode()
        self._step_unified()

    def _run_verify_program(self) -> None:
        # decode/verify build their batch from host token state: the unified
        # step in flight (its tokens) must land first
        self._flush_pending_sample()
        # a verify step replaces this step's fused decode call when
        # prompt-lookup drafts exist; otherwise fall through to fused decode
        if not self._spec_try_verify():
            self._step_decode()

    def _run_decode_program(self) -> None:
        self._flush_pending_sample()
        self._step_decode()

    def _emit_step_spans(self, phase: str, seqs,
                         start_ns: int, batch_size: int, n_tokens: int) -> None:
        """Emit one `engine.step` child span per traced sequence in the batch,
        parented on the request span context carried in via add_request — the
        engine's step work shows up nested under `engine.generate`. ``seqs``
        is any iterable (callers pass a generator, so a disabled tracer costs
        no walk of the batch)."""
        tracer = self.tracer
        if tracer is None or not tracer.cfg.enabled:
            return  # no exporter: nothing would leave, so walk no batch
        for s in seqs:
            ctx = s.trace_ctx
            if ctx is None or not getattr(ctx, "sampled", False):
                continue
            span = tracer.start_span(
                "engine.step", parent=ctx,
                **{"llm_d.phase": phase, "llm_d.batch_size": batch_size,
                   "llm_d.step_tokens": n_tokens,
                   "llm_d.request_id": s.request_id})
            span.start_ns = start_ns
            span.end()

    def _trace_exemplar(self, seqs) -> Optional[dict]:
        """OpenMetrics exemplar labels from the first traced seq in a batch —
        feeds the step-duration histogram so a slow bucket links to a trace."""
        for s in seqs:
            ctx = s.trace_ctx
            if ctx is not None and getattr(ctx, "trace_id", ""):
                return {"trace_id": ctx.trace_id}
        return None

    def _offload_drain(self) -> None:
        """Keep the plain free list above the watermark by batch-demoting the oldest
        LRU pages (one gather per step) — evictions then rarely hit the per-page
        on_evict backstop inside allocate()."""
        need = self.cfg.offload_watermark_pages - len(self.alloc.free)
        if need <= 0 or not self.alloc.lru:
            return
        n = min(need, self.cfg.offload_staging_blocks, len(self.alloc.lru))
        pairs = self.alloc.demote_lru(n)
        self.offload.demote_batch(self.cache, pairs)

    def _prefill_target(self, seq: Sequence) -> int:
        """Tokens that must be processed chunk-wise before decode can take over.

        Fresh sequence: the whole prompt (last logits sample the first token).
        Preempted-with-generated-tokens: recompute through len-1; the decode path then
        feeds the final token and continues sampling (recompute semantics).
        """
        if len(seq.token_ids) == seq.prompt_len:
            return seq.prompt_len
        return len(seq.token_ids) - 1

    def _prefilling_seqs(self) -> list[Sequence]:
        cands = [
            s for s in self.running
            if s is not None and s.num_computed < self._prefill_target(s)
        ]
        return sorted(cands, key=lambda s: s.arrival_time)

    def _decode_ready(self, flying: Optional[dict] = None) -> list[Sequence]:
        """Running rows that can take a decode step. The input token of each
        is on the host (``num_computed == len(token_ids) - 1``); with
        ``flying`` (``_flying_rows()``: the unified step's view), also the
        rows of the step still in flight, whose token (position
        ``len(token_ids)``) is on the device until that step is read. A row
        that the token in flight ends by ``max_tokens`` or ``max_model_len``
        is known here and left out; one that a stop token may end is taken,
        and dropped at apply if it does."""
        flying = flying or {}
        out = []
        for s in self.running:
            if s is None or s.num_computed < s.prompt_len:
                continue
            n = len(s.token_ids)
            if s.num_computed == n - 1 or (
                    s.num_computed == n and id(s) in flying
                    and n + 1 - s.prompt_len < s.max_tokens
                    and n + 1 < self.cfg.max_model_len):
                out.append(s)
        return out

    def _flying_rows(self) -> dict[int, int]:
        """``id(seq)`` -> its row in the sampled array of the unified step in
        flight, for the rows that are still what that step dispatched."""
        rec = self._pending_sample
        if rec is None:
            return {}
        return {id(s): i for i, s, slot, _ in rec["rows"]
                if self._still_seated(s, slot)}

    def _still_seated(self, s: Sequence, slot: int) -> bool:
        """Whether ``s`` is the running sequence a step dispatched in
        ``slot``: not finished, preempted or aborted since."""
        return (not s.finished and s.slot == slot
                and self.running[slot] is s)

    @_step_phase("unified", "llmd.unified", "plan")
    def _step_unified(self, parts: _StepParts) -> None:
        """Pack decode tokens + prefill chunks (across sequences) into the flat
        token budget and run ONE compiled step, one step ahead of the host:
        this step is dispatched BEFORE the previous one's sampled tokens are
        read, and a row of that step rides along as a decode row whose input
        token the program takes on the device (``_unified``). ``parts``
        splits the host time in the order it runs: plan (row choice, pages,
        preemption), pack (numpy staging), stage (flight records, counters,
        the choice of the step function, the sampling parameters of a step in
        which a row samples), transfer (the ``jnp.asarray`` calls), dispatch
        (the asynchronous jitted call, which picks the step's tokens too,
        and nothing else), apply
        (this step's per-row state), sample (the record of what the step
        left to read; for a batch with a constrained row also its bias and
        the biased sampler's dispatch), wait (the blocking read of the
        PREVIOUS step's sampled tokens and MoE counts), apply again (that
        step's tokens, finish checks, outputs), book. So everything between two
        dispatches but the wait runs under device time, and so does what the
        loop does with the outputs after ``step()`` returns."""
        t0_ns = time.time_ns()
        if self._pending_sample is not None and any(
                s is not None and (s.structured is not None or s.logit_bias)
                for s in self.running):
            # a constrained row's bias is built from its previous token on
            # the host (_build_bias): such a batch reads before it plans
            self._flush_pending_sample(parts)
            parts.to("plan")
        NT = self.cfg.batched_tokens
        B = self.cfg.max_batch_size
        R = self.num_ranks
        # per-rank token budgets (the reference's per-rank-engine
        # --max-num-batched-tokens); single-rank engines keep the whole budget
        budgets = [NT // R] * R

        # decode rows first (keeps TPOT low while prompts stream in), then
        # prefill chunks oldest-first
        plan: list[tuple[Sequence, int, bool]] = []  # (seq, q_len, is_decode)
        # id(seq) -> row, for the rows of the step in flight: valid until a
        # preemption reads that step, after which no row needs it
        flying = self._flying_rows()
        for s in self._decode_ready(flying):
            if len(plan) >= B:
                break
            if s.slot < 0:
                # preempted (or, its token read by a preemption's flush,
                # retired) while packing an earlier row: the snapshot is
                # stale. Without this guard the zombie's _ensure_pages can
                # re-acquire pages onto a seq whose ledger _free_seq already
                # emptied — pages it carries into the waitq and leaks at
                # re-admission (measured: 4 pages/occurrence → pool exhaustion
                # → self-preempt livelock in tight pools)
                continue
            if budgets[s.rank] <= 0:
                continue
            # the row computes position num_computed, whether its token is
            # on the host (len(token_ids) - 1) or in flight (len(token_ids))
            if not self._ensure_pages(s, s.num_computed + 1):
                if (not self._preempt_one(s.rank, exclude=s, parts=parts)
                        or s.slot < 0):
                    self._finish_if_outgrew_pool(s)
                    continue
                if not self._ensure_pages(s, s.num_computed + 1):
                    continue
            plan.append((s, 1, True))
            budgets[s.rank] -= 1
        for s in self._prefilling_seqs():
            if len(plan) >= B:
                break
            if s.slot < 0:
                continue  # preempted while packing decode rows
            left = self._prefill_target(s) - s.num_computed
            n = min(self.cfg.prefill_chunk, left, budgets[s.rank])
            if self._state_block and n < left:
                # a lightning, kda or Mamba-2 layer groups its sums by blocks
                # counted from a chunk's first token: every chunk but a
                # prompt's last ends on a block's boundary, so the blocks
                # are the prompt's own
                n -= n % self._state_block
                if (left - n == 1 and n > LIGHTNING_BLOCK
                        and self.model_cfg.sparse_topk):
                    # a token that comes alone takes the selected-table call
                    # and one in a chunk the block-masked call: no prompt is
                    # left a last chunk of one token (but by a budget of one
                    # block, which has nothing to give)
                    n -= LIGHTNING_BLOCK
            if n <= 0:
                continue
            if not self._ensure_pages(s, s.num_computed + n):
                if (not self._preempt_one(s.rank, exclude=s, parts=parts)
                        or s.slot < 0):
                    self._finish_if_outgrew_pool(s)
                    continue
                if not self._ensure_pages(s, s.num_computed + n):
                    continue
            plan.append((s, n, False))
            budgets[s.rank] -= n
        plan = [(s, n, d) for (s, n, d) in plan if s.slot >= 0]
        if not plan:
            # nothing schedulable — the step in flight may be WHY (rows it
            # ends are not planned ahead, and hold slots/pages until it is
            # read): read it so the next step can make progress
            parts.to(None)
            self._flush_pending_sample()
            return
        # the step in flight, as the plan left it: a preemption reads it
        # first (_preempt_one), and then every row's token is on the host
        prev, self._pending_sample = self._pending_sample, None

        parts.to("pack")
        toks = np.zeros((NT,), np.int32)
        pos = np.full((NT,), -1, np.int32)
        sids = np.zeros((NT,), np.int32)
        lora_tok = np.zeros((NT,), np.int32)
        pts = np.full((B, self.cfg.max_pages_per_seq), -1, np.int32)
        lens = np.ones((B,), np.int32)
        cu = np.zeros((B + 1,), np.int32)
        # a model with recurrent layers: the state slot of each row (its
        # seat's; the scratch slot B for the rows the plan leaves empty)
        recurrent = bool(self.state)
        row_slots = np.full((B,), B, np.int32) if recurrent else None
        # only pay the mm staging buffers when this step actually carries media
        # prefill rows (text-only steps on a VL model jit a no-mm variant)
        is_vl = self.model_cfg.mm_tokens > 0 and any(
            s.mm_items and not is_decode for s, _, is_decode in plan)
        if is_vl:
            # row-aligned with the flat token batch: mm_embeds[i] replaces the
            # embedding of tokens[i] where mm_mask[i] (encode-stage injection)
            mm_embeds = np.zeros((NT, self.model_cfg.hidden_size), np.float32)
            mm_mask = np.zeros((NT,), np.bool_)
        off = 0
        first_chunks: list[Sequence] = []
        ahead_rows: set[int] = set()
        # (batch row, seq) of the rows whose last logits give a token: decode
        # rows, and a fresh prefill that this step's chunk completes
        sample_list: list[tuple[int, Sequence]] = []
        for i, (s, n, is_decode) in enumerate(plan):
            start = s.num_computed
            if not is_decode and start == s.num_cached_prompt:
                first_chunks.append(s)
            if is_decode or (len(s.token_ids) == s.prompt_len
                             and start + n == s.prompt_len):
                sample_list.append((i, s))
            if start == len(s.token_ids):
                # token in flight: name its row in the previous step
                toks[off] = -(flying[id(s)] + 1)
                ahead_rows.add(i)
            else:
                toks[off : off + n] = s.token_ids[start : start + n]
            pos[off : off + n] = np.arange(start, start + n)
            sids[off : off + n] = i
            lora_tok[off : off + n] = self._lora_slot(s)
            pts[i, : len(s.pages)] = s.pages
            lens[i] = start + n
            if recurrent:
                row_slots[i] = s.slot
                if start == 0:  # the row starts from a zero state
                    if self.model_cfg.has_matrix_state:
                        self.metrics.linear_state_resets.inc()
                    else:
                        self.metrics.ssm_state_resets.labels(
                            cause="recompute" if s.recompute else "admit").inc()
                    s.recompute = False
            if is_vl and s.mm_items and not is_decode:
                ph = self.model_cfg.mm_placeholder_id
                k = self.model_cfg.mm_tokens
                occ = sum(1 for t in s.token_ids[:start] if t == ph)
                for j in range(n):
                    if s.token_ids[start + j] == ph:
                        item, row = occ // k, occ % k
                        if item < len(s.mm_items):
                            mm_embeds[off + j] = s.mm_items[item][1][row]
                            mm_mask[off + j] = True
                        occ += 1
            off += n
            cu[i + 1] = off
        cu[len(plan) + 1 :] = off

        parts.to("stage")
        for s in first_chunks:
            # a (re)prefill's first chunk goes to the device now: the
            # ledger's schedule phase ends here
            self.flight.record(s.request_id, "dispatched")
        # ring-eligible: ONE fresh self-contained prefill chunk at offset 0
        # (positions 0..n-1, no prior KV) — the only regime where causality by
        # row index equals causality by position and in-chunk q/k/v are the
        # whole attention problem (see make_ring_attn_impl)
        step_fn, step_prog = self._unified_fn, "unified"
        if (self._unified_ring_fn is not None and len(plan) == 1
                and not plan[0][2] and plan[0][0].num_computed == 0
                and pos[0] == 0 and not is_vl):
            step_fn, step_prog = self._unified_ring_fn, "unified_ring"
            self.stats.n_ring_prefill_steps += 1
        parts.program = step_prog
        kv_read_tokens = int(lens[: len(plan)].sum())
        self.metrics.program_kv_read_tokens.labels(program=step_prog).inc(
            kv_read_tokens)
        self._count_attn_kv(step_prog, lens[: len(plan)],
                            np.diff(cu[: len(plan) + 1]),
                            page_tables=pts[: len(plan)])
        self.metrics.program_rows.labels(program=step_prog).inc(len(plan))
        # dispatched here, complete when its record is read (_sample_apply),
        # a step later: the ledger holds it in flight meanwhile
        self.programs.record_dispatch(step_prog)
        n_dec = sum(1 for _, _, d in plan if d)
        for where, n in (("device", len(ahead_rows)),
                         ("host", n_dec - len(ahead_rows))):
            if n:
                self.metrics.unified_decode_rows.labels(token=where).inc(n)
        prev_sampled = prev["sampled"] if prev is not None else None
        if prev_sampled is None:
            prev_sampled = self._zero_sampled
        if recurrent:
            self._count_ssm_tokens(step_prog, chunk=off - n_dec, decode=n_dec)
        sampling, samples = self._sampling_state(sample_list)
        # the step's arrays cross to the device here, one transfer each, and
        # not in the call's argument list: what `dispatch` then holds is the
        # call's own enqueue
        parts.to("transfer")
        mm_args = ((jnp.asarray(mm_embeds), jnp.asarray(mm_mask)) if is_vl else ())
        state_kw = ({"state_slots": jnp.asarray(row_slots)}
                    if recurrent else {})
        args = (jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(sids),
                jnp.asarray(pts), jnp.asarray(lens), jnp.asarray(cu),
                jnp.asarray([len(plan)], jnp.int32), jnp.asarray(lora_tok))
        parts.to("dispatch")
        logits, sampled, pools, cnt, moe_drop = step_fn(
            self._run_params(), self._pools(), *args,
            prev_sampled, *sampling, *mm_args, **state_kw,
        )
        self._keep_pools(pools)
        # the host's handles on the transferred arrays go here, on the
        # transfers' account (0.2-0.3 ms a step on the chip's host), and not
        # when the frame ends, which is after the parts are booked
        parts.to("transfer")
        del args, mm_args, state_kw
        parts.to("apply")

        # goodput classification reads pre-postprocess sequence state: the
        # first-chunk prefix credit (num_computed == num_cached_prompt only
        # holds before the loop advances num_computed) and re-prefill
        # detection (a prefill chunk on a seq carrying generated tokens is
        # recompute of preempted work, not fresh compute)
        util_saved = util_recompute = 0
        if self.util is not None:
            for s, n, is_decode in plan:
                if not is_decode:
                    if (s.num_computed == s.num_cached_prompt
                            and s.num_cached_prompt):
                        util_saved += s.num_cached_prompt
                    if len(s.token_ids) > s.prompt_len:
                        util_recompute += n

        for s, n, is_decode in plan:
            if is_decode:
                s.num_computed += 1
                # commits stop at the tokens the host holds: an ahead row's
                # block is committed when its token is read (_sample_apply)
                s.maybe_commit_blocks(self.allocs[s.rank])
                self.stats.total_decode_tokens += 1
            else:
                if s.num_computed == s.num_cached_prompt:
                    # first chunk of a (re)prefill — cached==computed only holds
                    # before any chunk lands (and again after preemption resets)
                    self.flight.record(s.request_id, "prefill_start",
                                       cached_tokens=s.num_cached_prompt)
                s.num_computed += n
                s.maybe_commit_blocks(self.allocs[s.rank])
                self.stats.total_prefill_tokens += n
                if s.num_computed >= self._prefill_target(s):
                    self.flight.record(s.request_id, "prefill_end",
                                       prefill_tokens=s.num_computed)
        # One step ahead: this step's tokens are picked on the device by the
        # program that is running; only now read and apply the PREVIOUS step,
        # under this one's device time. This step's record waits for the next
        # step, or for whoever needs host token state first
        # (_flush_pending_sample). Its rows stay schedulable meanwhile:
        # _decode_ready(_flying_rows()).
        parts.to("sample")
        rec = self._sample_dispatch(sample_list, logits, sampled, sampling,
                                    ahead_rows=ahead_rows)
        del logits  # its handle too goes inside a part (the record keeps none)
        rec["prog"] = step_prog
        self.metrics.sampler_steps.labels(
            program="unified",
            path=("biased" if rec["biased"] else
                  "topk" if samples else "argmax")).inc()
        if self._eplb is not None:
            rec["cnt"] = cnt
        if self.model_cfg.is_moe:
            rec["moe_drop"], rec["moe_cnt"] = moe_drop, cnt
            for arr in (moe_drop, cnt):  # read a step later, in `wait`
                try:
                    arr.copy_to_host_async()
                except (AttributeError, RuntimeError):
                    break
        if prev is not None:
            self._sample_apply(prev, parts)
        self._pending_sample = rec
        parts.to("book")
        wall = sum(parts.seconds.values())  # all of the step but its booking
        st = self.stats
        st.time_prefill_steps += wall
        st.n_unified_steps += 1
        n_pre = sum(n for _, n, d in plan if not d)
        if n_dec:
            self.metrics.decode_tokens.inc(n_dec)
        if n_pre:
            self.metrics.prefill_tokens.inc(n_pre)
        if self.util is not None:
            # analytic cost from the PACKED shape: the program computes all
            # NT positions (padding included); KV reads ≈ one pass over each
            # row's resident KV (exact for decode rows, a lower bound for
            # chunked prefill), writes = the real positions landed
            cost = self.util.cost(
                step_prog, slot_tokens=NT, weight_passes=1,
                kv_read_tokens=kv_read_tokens, kv_write_tokens=off)
            self.util.record(
                step_prog, cost, wall,
                committed=n_dec + n_pre - util_recompute,
                preempted_recompute=util_recompute,
                prefix_saved=util_saved,
                compile_counts=self.programs.compile_counts())
        self._emit_step_spans("unified", (s for s, _, _ in plan), t0_ns,
                              len(plan), n_pre + n_dec)
        # last, and from the parts' own sum, so that the histogram and the
        # part counter cover the same seconds (bookkeeping included)
        self.metrics.step_duration.labels(phase="unified").observe(
            self._book_parts(parts),
            exemplar=self._trace_exemplar([s for s, _, _ in plan]))

    @_step_phase("decode", "llmd.decode_dispatch", "plan", outer=False)
    def _step_decode(self, parts: _StepParts) -> None:
        """Fused multi-step decode with pipelined dispatch.

        Reading sampled tokens costs a host<->device round trip per call
        (PERF.md has the measured figure), so the host dispatches call N+1
        chained on call N's *device-resident* last tokens, then reads call
        N's results while N+1 runs — vLLM's async output processing, XLA-style.
        The chain holds only while the active set is unchanged; any membership
        change (finish, preemption, new prefill) flushes first. The
        unpipelined reading is ``_flush_pending_decode()`` after every step.

        A call runs ``n <= cfg.decode_steps`` steps, worked out for each call
        from the budgets of its rows and the steps in flight
        (``decode_call_steps``): it ends where its first row is known to end,
        so a flush waits out short calls and a finished row's seat is free
        at once. An ending the host cannot foresee (a stop token) is
        speculated past, up to ``DECODE_CHAIN_DEPTH`` calls.

        ``parts`` times the dispatch side (plan here, the rest in
        ``_decode_dispatch``); it is paused around every nested program,
        which keeps its own.
        """
        active = self._decode_ready()
        if not active:
            parts.to(None)
            self._flush_pending_decode()
            return
        k = max(1, self.cfg.decode_steps)
        q = self._pending_decode
        off = sum(rec["k"] for rec in q)

        # The host knows every row's HARD budget (max_tokens / max_model_len)
        # without any device read: if the steps already in flight cover it for
        # every row, one more speculative call would run its steps on rows
        # that are all spent — measured as 2 wasted calls (64 of 192
        # step-slots) per request wave at OSL 128 / k=32. Drain the oldest
        # call instead; its results change membership and the normal flush
        # path takes over. Checked BEFORE _ensure_pages so a provably-useless
        # call cannot demand pages (or degrade to a unified step) either.
        # (EOS-before-budget still speculates — that is the pipeline's
        # purpose; this clamp only removes provably-useless calls.)
        left = self._decode_budgets(active, off)
        if q and max(left) <= 0:
            parts.to(None)
            self._decode_process(q.pop(0))
            return
        # the same budgets say where the call's first row ends: the call is
        # that long, within DECODE_MIN_STEPS and k (decode_call_steps)
        n, bound = decode_call_steps(left, k)

        # If the pool can't cover the call's steps, flush and degrade to a
        # single unified step (decode rows only) rather than preempting
        # sequences that could progress.
        if not self._reserve_decode_pages(active, off, n):
            parts.to(None)
            self._flush_pending_decode()
            self._step_unified()
            return
        active = [s for s in active if s.slot >= 0]
        if not active:
            return
        if (any(s.structured is not None or s.logit_bias for s in active)
                and self._plan_chain_masks(active) is None):
            # raced out of fused-mask eligibility (a preemption above changed
            # the batch): degrade like the pool-pressure path rather than
            # letting a constrained row decode unmasked
            parts.to(None)
            self._flush_pending_decode()
            self._step_unified()
            return

        if q:
            same = {(s.request_id, s.slot) for s in active} == {
                (s.request_id, slot) for s, slot in q[-1]["rows"]}
            if same:
                rec = self._decode_dispatch(active, n, bound, chain=q[-1],
                                            parts=parts, off=off)
                q.append(rec)
                # keep DECODE_CHAIN_DEPTH calls in flight: the queued call
                # behind the running one lets the device go back-to-back while
                # the finished call's tokens cross back to the host
                if len(q) > DECODE_CHAIN_DEPTH:
                    self._decode_process(q.pop(0))
                return
            parts.to(None)
            self._flush_pending_decode()
            parts.to("plan")
            q = self._pending_decode  # flush rebinds the queue — drop the stale ref
            active = [s for s in self._decode_ready() if s.slot >= 0]
            if not active:
                return
            # the flush landed the chain's tokens and retired the rows they
            # ended: the new chain's first call is as long as the rows that
            # stay allow, which may be longer than the pages reserved above
            n, bound = decode_call_steps(self._decode_budgets(active, 0), k)
            if not self._reserve_decode_pages(active, 0, n):
                parts.to(None)
                self._step_unified()
                return
        q.append(self._decode_dispatch(active, n, bound, chain=None,
                                       parts=parts))

    def _decode_budgets(self, active: list[Sequence], off: int) -> list[int]:
        """The tokens each row may still take once the ``off`` steps in
        flight have landed, by ``max_tokens`` and by ``max_model_len``; zero
        or less for a row those steps already end."""
        cap = self.cfg.max_model_len
        return [min(s.max_tokens - (len(s.token_ids) + off - s.prompt_len),
                    cap - (len(s.token_ids) + off)) for s in active]

    def _reserve_decode_pages(self, active: list[Sequence], off: int,
                              n: int) -> bool:
        """Pages for a fused call of ``n`` steps behind ``off`` steps in
        flight: it writes KV for positions len-1 .. len+off+n-2, so a row
        needs len+off+n-1 slots. False when the pool cannot give them."""
        return all(
            self._ensure_pages(
                s, min(len(s.token_ids) + off + n - 1, self.cfg.max_model_len))
            for s in active if s.slot >= 0)

    def _flush_pending_decode(self) -> None:
        q, self._pending_decode = self._pending_decode, []
        for rec in q:
            self._decode_process(rec)
        if q:
            # one event per chain teardown (the admission/retire boundary
            # where the host re-enters the loop); a system event, not a
            # per-request one — the chain is batch-scoped, and its lead row
            # may have retired during this very drain (`retired` must stay
            # the terminal event on every request timeline)
            s, _slot = q[-1]["rows"][0]
            self.flight.record_system("chain_retire", calls=len(q),
                                      lead_request=s.request_id)

    # ------------------------------------------------------------ speculation
    def _verify_nt(self) -> int:
        """Static packed width of the verify programs. Every draft is clamped
        to ``spec_tokens`` (``_spec_propose``), so ``max_batch_size *
        (spec_tokens + 1)`` positions always hold the worst-case plan —
        padding verify to the full prefill width (``batched_tokens``) would
        pay a prefill-sized forward to land a handful of tokens per row
        (6.4x waste at the tiny smoke shape: 40 real positions in NT=256)."""
        return min(self.cfg.batched_tokens,
                   self.cfg.max_batch_size * (self.cfg.spec_tokens + 1))

    def _spec_propose(self, s: Sequence, max_draft: int) -> list[int]:
        """Prompt-lookup draft for one decode-ready seq, clamped so the
        verify step can land every accepted token: k drafts + 1 bonus token
        may append, so k is bounded by the remaining max_tokens /
        max_model_len budget minus one (the bonus token is the plain-decode
        token and is always in budget). Constrained rows draft too
        (spec × structured compose, PERF.md Lever 13): their proposal is
        trimmed to its longest constraint-legal prefix, so the masked verify
        program only ever checks tokens the grammar could emit."""
        k = min(self.cfg.spec_tokens, max_draft,
                s.max_tokens - s.num_generated - 1,
                self.cfg.max_model_len - len(s.token_ids) - 1)
        if k <= 0:
            return []
        draft = propose_ngram_draft(s.token_ids, k, self.cfg.spec_ngram_max,
                                    self.cfg.spec_ngram_min)[:k]
        if draft and (s.structured is not None or s.logit_bias):
            draft = self._spec_filter_draft(s, draft)
        return draft

    def _spec_filter_draft(self, s: Sequence, draft: list[int]) -> list[int]:
        """FSM-aware draft truncation for a constrained row: keep the longest
        prefix of ``draft`` its constraint allows. Grammar rows walk the host
        automaton from the synced cursor (an idempotent ``sync`` first — the
        cursor must reflect every committed token before extrapolating);
        logit_bias rows cut at the first effectively-banned token."""
        stt = s.structured
        if stt is not None:
            fresh = stt.sync(s.token_ids, s.prompt_len)
            if fresh:
                self.stats.structured_violations += fresh
                self.metrics.structured_violations.inc(fresh)
            return draft[:stt.grammar.legal_prefix_len(stt.state, draft)]
        for i, t in enumerate(draft):
            if s.logit_bias.get(t, 0.0) <= -100.0:
                return draft[:i]
        return draft

    @_step_phase("verify", "llmd.spec_verify", "plan", outer=False)
    def _spec_try_verify(self, parts: _StepParts) -> bool:
        """Decode-path speculation gate; True = a verify step ran (replacing
        this step's fused decode call).

        Probes the drafter on the current host view first: while pipelined
        fused calls are in flight that view is stale, but a stale no-match is
        a cheap signal to keep the pipelined decode path (non-echo workloads
        keep their dispatch chain). Only a positive probe pays the flush;
        drafts are then re-proposed on the landed state. After the flush the
        decode horizon is read from live ``len(token_ids)``, so the next
        fused call's clamp accounts for accepted-token jumps automatically.
        """
        active = self._decode_ready()
        if not active:
            return False
        # Constrained rows ride verify ONLY through the masked verify program
        # (grammar bias + FSM advance fused per packed position). When the
        # batch's mask plan is inexpressible as dense tables (combined
        # grammar+bias row, table-size gate), the batch falls back to the
        # fused decode path, which has its own masked/degrade handling.
        if (any(s.structured is not None or s.logit_bias for s in active)
                and self._plan_chain_masks(active) is None):
            return False
        # Greedy acceptance is only bitwise-equivalent to sequential decoding
        # for greedy rows; a batch with sampled sequences falls back to the
        # fused decode path.
        if any(s.sampling.temperature > 0.0 for s in active):
            return False
        # Probe arming (per sequence): the drafter is a pure function of each
        # row's token history, so a no-match verdict stays valid until fresh
        # tokens land for that row (_decode_process / _sample_apply / a
        # verify step re-arm it). Skipping the re-probe drops the per-step
        # O(context) numpy scans from the chained steady state — and one
        # non-repetitive row no longer disarms the rest of the batch.
        probed = False
        for s in active:
            if s.spec_armed:
                if self._spec_propose(s, self.cfg.spec_tokens):
                    probed = True
                else:
                    s.spec_armed = False
                    s.spec_flips += 1
        if not probed:
            return False
        parts.to(None)
        self._flush_pending_decode()
        parts.to("plan")
        active = [s for s in self._decode_ready() if s.slot >= 0]
        if not active:
            return True  # the flush retired/changed the batch; step done
        NT = self._verify_nt()
        R = self.num_ranks
        # every active row is guaranteed its plain token (batched_tokens >=
        # max_batch_size); drafts share the leftover per-rank budget
        spare = [NT // R] * R
        for s in active:
            spare[s.rank] -= 1
        plan: list[tuple[Sequence, list[int]]] = []
        for s in active:
            if len(plan) >= self.cfg.max_batch_size:
                break
            if s.slot < 0:
                continue  # preempted while packing an earlier row
            draft = (self._spec_propose(s, max(0, spare[s.rank]))
                     if s.spec_armed else [])
            if draft and not self._ensure_pages(s, len(s.token_ids) + len(draft)):
                draft = []  # shed the draft before shedding a sequence
            if not self._ensure_pages(s, len(s.token_ids)):
                if (not self._preempt_one(s.rank, exclude=s, parts=parts)
                        or s.slot < 0):
                    self._finish_if_outgrew_pool(s)
                    continue
                if not self._ensure_pages(s, len(s.token_ids)):
                    continue
            plan.append((s, draft))
            spare[s.rank] -= len(draft)
        plan = [(s, d) for s, d in plan if s.slot >= 0]
        if any(s.structured is not None or s.logit_bias for s, _ in plan):
            # a constrained row may have become decode-ready during the flush:
            # re-check masked-verify eligibility on the FINAL plan — an
            # ineligible row must never ride the unmasked verify program
            if self._plan_chain_masks([s for s, _ in plan]) is None:
                return False
        if not any(d for _, d in plan):
            # fresh state proposes nothing: plain decode instead — and no
            # re-probe for these rows until the next landing changes that
            for s, _ in plan:
                if s.spec_armed:
                    s.spec_armed = False
                    s.spec_flips += 1
            return False
        self._step_spec_verify(plan, parts)
        return True

    @_profile_phase("llmd.spec_verify")
    def _step_spec_verify(self, plan: list[tuple[Sequence, list[int]]],
                          parts: _StepParts) -> None:
        """Pack each sequence's draft as a short self-contained chunk (its
        last real token + the draft) through the verify program, accept the
        longest greedy-matching prefix plus one bonus token, and roll back
        the rejected tail — host token state never contains a draft token
        unless verification proved it, so ``maybe_commit_blocks`` can never
        commit an unverified page, and surplus draft pages release straight
        back to the allocator's free list. ``parts`` comes running from
        ``_spec_try_verify`` (plan) and goes on through pack, the train of
        every step program (stage, transfer, dispatch), wait (the read of
        the greedy tokens), apply and book."""
        parts.to("pack")
        t0_ns = time.time_ns()
        NT = self._verify_nt()
        B = self.cfg.max_batch_size
        toks = np.zeros((NT,), np.int32)
        pos = np.full((NT,), -1, np.int32)
        sids = np.zeros((NT,), np.int32)
        lora_tok = np.zeros((NT,), np.int32)
        pts = np.full((B, self.cfg.max_pages_per_seq), -1, np.int32)
        lens = np.ones((B,), np.int32)
        cu = np.zeros((B + 1,), np.int32)
        off = 0
        rows: list[tuple[Sequence, list[int], int, int]] = []
        for i, (s, draft) in enumerate(plan):
            start = len(s.token_ids) - 1
            chunk = [s.token_ids[-1]] + draft
            n = len(chunk)
            toks[off : off + n] = chunk
            pos[off : off + n] = np.arange(start, start + n)
            sids[off : off + n] = i
            lora_tok[off : off + n] = self._lora_slot(s)
            pts[i, : len(s.pages)] = s.pages
            lens[i] = start + n
            if draft:
                s.spec_drafted += len(draft)
                self.stats.spec_drafted += len(draft)
                if s.structured is not None or s.logit_bias:
                    self.stats.spec_drafted_constrained += len(draft)
                self.metrics.spec_drafted.inc(len(draft))
                self.flight.record(s.request_id, "spec_draft",
                                   drafted=len(draft))
            rows.append((s, draft, off, s.slot))
            off += n
            cu[i + 1] = off
        cu[len(plan) + 1 :] = off
        parts.to("stage")
        # constrained rows ride the masked variant: dense [G,S,V] bias/next
        # tables + per-packed-row FSM entry states (None = no constrained
        # row); their staging also accounts itself into time_mask_build
        mask = self._spec_stage_verify_masks(plan)
        prog = parts.program = "verify" if mask is None else "verify_masked"
        self.programs.record_dispatch(prog)
        parts.to("transfer")
        args = (jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(sids),
                jnp.asarray(pts), jnp.asarray(lens), jnp.asarray(cu),
                jnp.asarray([len(plan)], jnp.int32), jnp.asarray(lora_tok))
        parts.to("dispatch")
        if mask is None:
            fsm_out = None
            greedy, self.cache, cnt, moe_drop = self._verify_fn(
                self._run_params(), self.cache, *args)
        else:
            greedy, fsm_out, self.cache, cnt, moe_drop = self._verify_masked_fn(
                self._run_params(), self.cache, *args,
                mask["fsm0"], mask["gidx"], mask["bias_tab"], mask["next_tab"],
            )
        parts.to("transfer")
        del args  # the handles' release, as in _step_unified
        parts.to("wait")
        # llmd-lint: allow[hot-host-sync] designed sync point: verify needs the greedy tokens on host to accept/reject the draft
        g = np.asarray(greedy)  # [NT] (device sync point)
        # llmd-lint: allow[hot-host-sync] same designed sync point: the per-position FSM states ride the readback the greedy tokens already paid for
        fsm = np.asarray(fsm_out) if fsm_out is not None else None
        self.programs.record_complete(prog)
        parts.to("apply")
        if self._eplb is not None:
            self._eplb_record(cnt)
        if self.model_cfg.is_moe:
            self._moe_record(moe_drop, cnt)
        now = time.monotonic()
        spec_rej0 = self.stats.spec_rejected
        n_tokens = 0
        for s, draft, row0, slot in rows:
            if s.finished or s.slot != slot or self.running[slot] is not s:
                continue  # preempted while packing later rows
            kept: list[int] = []
            finished, reason = False, None
            # Row j's greedy token continues chunk position start+j: accept
            # drafts while they match it, append the first divergence (the
            # bonus token — exactly what sequential decode would emit).
            for j in range(len(draft) + 1):
                t = int(g[row0 + j])
                kept.append(t)
                s.token_ids.append(t)
                finished, reason = self._check_finish(s, t)
                if finished or j >= len(draft) or draft[j] != t:
                    break
            accepted = sum(1 for j, t in enumerate(kept)
                           if j < len(draft) and draft[j] == t)
            rejected = len(draft) - accepted
            # the newest token's KV is never written yet → computed = len - 1
            s.num_computed = len(s.token_ids) - 1
            if s.first_token_time is None:
                s.first_token_time = now
                self.flight.record(
                    s.request_id, "first_token",
                    ttft_ms=round((now - s.arrival_time) * 1e3, 3))
            s.maybe_commit_blocks(self.allocs[s.rank])
            self._spec_release_tail(s)
            constrained = s.structured is not None or bool(s.logit_bias)
            if fsm is not None and s.structured is not None:
                stt = s.structured
                dev_state = int(fsm[row0 + len(kept) - 1])
                if self.cfg.spec_structured_crosscheck:
                    # recovery path kept honest: re-derive the cursor on host
                    # from the accepted tokens and compare with the device
                    # state; a mismatch keeps the host value (and is a bug)
                    fresh = stt.sync(s.token_ids, s.prompt_len)
                    if fresh:
                        self.stats.structured_violations += fresh
                        self.metrics.structured_violations.inc(fresh)
                    if stt.state != dev_state:
                        self.stats.spec_fsm_crosscheck_mismatches += 1
                else:
                    # the state at the last kept position IS the
                    # post-acceptance automaton state: rejected tails rolled
                    # back for free, exactly as _spec_release_tail rolls back
                    # their KV pages. Adopt it in place of the host resync.
                    stt.state = dev_state
                    stt.n_seen = len(s.token_ids) - s.prompt_len
            s.spec_accepted += accepted
            if not s.spec_armed:
                s.spec_flips += 1
            s.spec_armed = True  # fresh tokens landed for this row: re-probe
            st = self.stats
            st.spec_accepted += accepted
            st.spec_rejected += rejected
            if constrained:
                st.spec_accepted_constrained += accepted
            st.total_decode_tokens += len(kept)
            n_tokens += len(kept)
            if accepted:
                self.metrics.spec_accepted.inc(accepted)
            if rejected:
                self.metrics.spec_rejected.inc(rejected)
            if draft:
                self.flight.record(s.request_id, "spec_verify",
                                   drafted=len(draft), accepted=accepted,
                                   n_tokens=len(kept),
                                   constrained=constrained,
                                   generated=s.num_generated)
            else:
                self.flight.record(s.request_id, "decode", n_tokens=len(kept),
                                   generated=s.num_generated)
            if finished:
                self._retire(s, reason)
            self._outputs.append(EngineOutput(
                request_id=s.request_id, new_token_ids=kept, finished=finished,
                finish_reason=reason,
                num_cached_prompt_tokens=s.num_cached_prompt,
                prompt_len=s.prompt_len,
            ))
        parts.to("book")
        sec = parts.seconds
        wall = sum(sec.values()) - sec["plan"]  # the step, without its plan
        st = self.stats
        st.time_spec_steps += wall
        st.n_spec_verify_steps += 1
        if n_tokens:
            self.metrics.decode_tokens.inc(n_tokens)
        if self.util is not None:
            # verify burns its whole NT budget (PR 15 measured 6.4x padding
            # here — the standing padding_efficiency series); kept tokens
            # commit, rejected draft positions are the speculation waste,
            # rows preempted mid-pack fall into the padding residual
            cost = self.util.cost(
                prog, slot_tokens=NT, weight_passes=1,
                kv_read_tokens=int(lens[: len(plan)].sum()),
                kv_write_tokens=off)
            self.util.record(
                prog, cost, wall,
                committed=n_tokens,
                spec_rejected=self.stats.spec_rejected - spec_rej0,
                compile_counts=self.programs.compile_counts())
        self._emit_step_spans("spec_verify", (s for s, _, _, _ in rows), t0_ns,
                              len(plan), n_tokens)
        # last, and from the parts' own sum (see _step_unified)
        self.metrics.step_duration.labels(phase="spec_verify").observe(
            self._book_parts(parts),
            exemplar=self._trace_exemplar([s for s, _, _, _ in rows]))

    def _spec_release_tail(self, s: Sequence) -> None:
        """Roll back KV pages grown for rejected draft tokens: trim the page
        ledger to what the accepted length needs. Trimmed pages carry refs=1
        and no block hash (commits never cover unverified tokens), so
        ``release`` returns them straight to the free list — the r05
        page-ledger consistency invariant holds through every rollback."""
        ps = self.cfg.page_size
        need = max((len(s.token_ids) + ps - 1) // ps, len(s.block_hashes))
        alloc = self.allocs[s.rank]
        while len(s.pages) > need:
            alloc.release(s.pages.pop())

    # ------------------------------------------------- fused constrained decode
    def _plan_chain_masks(self, active: list[Sequence]) -> Optional[dict]:
        """Table-slot assignment + size gate for the fused masked decode
        program. None = this batch's constrained rows cannot ride it and must
        degrade to 1-token unified steps: a row combines a grammar AND a
        logit_bias (two bias sources, one table slot), or the padded tables
        would exceed structured_table_max_elems.

        Tables are shared BY GRAMMAR, not by row — G is 1 (the zero no-op
        grammar unconstrained rows index) + distinct grammars + one slot per
        logit_bias row, so a batch of 64 rows sharing one JSON schema stages
        one [2ᵖ, S_pad, V] pair, not 64.
        """
        entries: list[tuple] = []  # table slot -1 -> ("g", grammar)|("b", items)
        rows: list[tuple] = []  # (seq, table slot) for constrained rows
        gram_slot: dict[int, int] = {}
        key_parts: list[tuple] = []
        smax = 1
        for s in active:
            has_g = s.structured is not None
            has_b = bool(s.logit_bias)
            if has_g and has_b:
                return None
            if has_g:
                g = s.structured.grammar
                gi = gram_slot.get(id(g))
                if gi is None:
                    gi = 1 + len(entries)
                    gram_slot[id(g)] = gi
                    entries.append(("g", g))
                    smax = max(smax, g.n_states)
                rows.append((s, gi))
                key_parts.append((s.slot, "g", id(g)))
            elif has_b:
                items = tuple(sorted(s.logit_bias.items()))
                gi = 1 + len(entries)
                entries.append(("b", items))
                rows.append((s, gi))
                key_parts.append((s.slot, "b", items))
        if not rows:
            return None  # nothing constrained: the plain program serves it
        def _pow2(n: int) -> int:
            return 1 << (n - 1).bit_length()
        G_pad, S_pad = _pow2(1 + len(entries)), _pow2(smax)
        V = self.model_cfg.vocab_size
        if G_pad * S_pad * V > self.cfg.structured_table_max_elems:
            return None
        return {"entries": entries, "rows": rows, "key": tuple(key_parts),
                "G_pad": G_pad, "S_pad": S_pad, "V": V}

    def _constrained_needs_unified(self) -> bool:
        """step() routing: True when this step's constrained rows must take
        the legacy unified degrade instead of the fused masked program."""
        active = self._decode_ready()
        if not any(s.structured is not None or s.logit_bias for s in active):
            return False  # no constrained row is decode-ready this step
        return self._plan_chain_masks(active) is None

    @_profile_phase("llmd.chain_stage")
    def _stage_chain_masks(self, active: list[Sequence]) -> Optional[dict]:
        """Stage the dense bias/transition tables + per-row automaton entry
        state for one fused masked chain. The [G_pad, S_pad, V] tables are
        LRU-cached across chains (the cache entry pins its grammar objects,
        so an id-keyed slot can never be reused by a different grammar while
        staged), leaving only the fresh [B] FSM-entry vector per chain start.
        The staging wall lands in time_mask_build — this is what replaces the
        per-STEP host mask build that stat used to count."""
        plan = self._plan_chain_masks(active)
        if plan is None:
            return None
        t0 = time.perf_counter()
        B = self.cfg.max_batch_size
        bias_dev, next_dev = self._mask_tables(plan)
        gidx = np.zeros((B,), np.int32)
        for s, gi in plan["rows"]:
            gidx[s.slot] = gi
        fsm0 = np.zeros((B,), np.int32)
        for s, _gi in plan["rows"]:
            stt = s.structured
            if stt is None:
                continue  # logit_bias row: enters (and stays) at state 0
            fresh = stt.sync(s.token_ids, s.prompt_len)
            if fresh:
                self.stats.structured_violations += fresh
                self.metrics.structured_violations.inc(fresh)
            fsm0[s.slot] = stt.state
            if not stt.mask_logged:
                stt.mask_logged = True  # first mask only: timeline, not spam
                self.flight.record(
                    s.request_id, "structured_mask", kind=stt.kind,
                    n_allowed=int(len(stt.grammar.allowed_ids(stt.state))))
        dt = time.perf_counter() - t0
        self.stats.time_mask_build += dt
        self.stats.structured_chain_stages += 1
        self.metrics.structured_mask_seconds.observe(dt)
        self.metrics.step_duration.labels(phase="chain_stage").observe(dt)
        return {"bias_tab": bias_dev, "next_tab": next_dev,
                "gidx": jnp.asarray(gidx), "fsm0": jnp.asarray(fsm0)}

    def _mask_tables(self, plan: dict) -> tuple:
        """Staged dense ``[G_pad, S_pad, V]`` bias/next tables for a mask
        plan, LRU-cached across chains AND verify steps (the key carries the
        participating constraints + pad shape; an entry pins its grammar
        objects so an id-keyed slot can never be reused by a different
        grammar while staged). Row-index vectors are NOT cached — the fused
        chain indexes by slot, the masked verify by packed row."""
        cache_key = (plan["key"], plan["G_pad"], plan["S_pad"])
        hit = self._mask_tab_cache.get(cache_key)
        if hit is not None:
            self._mask_tab_cache.move_to_end(cache_key)
            return hit[0], hit[1]
        G_pad, S_pad, V = plan["G_pad"], plan["S_pad"], plan["V"]
        bias_tab = np.zeros((G_pad, S_pad, V), np.float32)
        next_tab = np.zeros((G_pad, S_pad, V), np.int32)
        pins = []
        for gi, (kind, payload) in enumerate(plan["entries"], start=1):
            if kind == "g":
                g = payload
                pins.append(g)
                b, nx = g.dense_tables()
                S = g.n_states
                bias_tab[gi, :S] = b
                next_tab[gi, :S] = nx
            else:  # logit_bias row: state pinned at 0 (next stays 0)
                row = bias_tab[gi, 0]
                for tid, bval in payload:
                    if 0 <= tid < V:
                        # OpenAI semantics: -100 is an outright ban
                        row[tid] = (NEG_BIAS if bval <= -100.0
                                    else row[tid] + bval)
        bias_dev, next_dev = jnp.asarray(bias_tab), jnp.asarray(next_tab)
        self._mask_tab_cache[cache_key] = (bias_dev, next_dev, tuple(pins))
        while len(self._mask_tab_cache) > 8:
            self._mask_tab_cache.popitem(last=False)
        return bias_dev, next_dev

    def _spec_stage_verify_masks(self, plan) -> Optional[dict]:
        """Mask staging for one MASKED verify step: the same shared dense
        tables as the fused chain (same LRU), plus ``gidx``/``fsm0`` indexed
        by PACKED ROW (the verify plan's order — ``sids`` values), not by
        slot. ``fsm0`` is each constrained row's synced automaton state over
        its full committed history; padding rows keep gidx/fsm0 = 0 (the
        zero no-op grammar) and the program's validity mask stops them from
        touching any real row's state. Returns None when no row in the plan
        is constrained — the plain verify program serves it."""
        seqs = [s for s, _ in plan]
        if not any(s.structured is not None or s.logit_bias for s in seqs):
            return None
        mplan = self._plan_chain_masks(seqs)
        if mplan is None:
            return None  # raced: _spec_try_verify re-checks before dispatch
        t0 = time.perf_counter()
        B = self.cfg.max_batch_size
        bias_dev, next_dev = self._mask_tables(mplan)
        slot_of = {id(s): gi for s, gi in mplan["rows"]}
        gidx = np.zeros((B,), np.int32)
        fsm0 = np.zeros((B,), np.int32)
        for i, (s, _draft) in enumerate(plan):
            gi = slot_of.get(id(s))
            if gi is None:
                continue  # unconstrained row: zero no-op grammar
            gidx[i] = gi
            stt = s.structured
            if stt is not None:
                fresh = stt.sync(s.token_ids, s.prompt_len)
                if fresh:
                    self.stats.structured_violations += fresh
                    self.metrics.structured_violations.inc(fresh)
                fsm0[i] = stt.state
        dt = time.perf_counter() - t0
        self.stats.time_mask_build += dt
        self.metrics.structured_mask_seconds.observe(dt)
        return {"bias_tab": bias_dev, "next_tab": next_dev,
                "gidx": jnp.asarray(gidx), "fsm0": jnp.asarray(fsm0)}

    def _pack_buf(self) -> dict[str, np.ndarray]:
        """Rotated host-pack buffer set for the chained fast path. There are
        DECODE_CHAIN_DEPTH+1 sets, indexed by dispatch count: a set is never
        refilled until the dispatch that uploaded from it has been processed
        (the readback in ``_decode_process`` forces that computation), so the
        CPU backend's zero-copy ``jnp.asarray`` aliasing can never observe a
        mutation. Full packs (chain starts) use fresh arrays instead and need
        no rotation — they are never mutated after upload."""
        if not self._pack_bufs:
            B = self.cfg.max_batch_size
            self._pack_bufs = [
                {"steps_left": np.zeros((B,), np.int32)}
                for _ in range(DECODE_CHAIN_DEPTH + 1)]
        return self._pack_bufs[
            self.stats.n_decode_dispatches % len(self._pack_bufs)]

    @_profile_phase("llmd.decode_dispatch")
    def _decode_dispatch(self, active: list[Sequence], k: int, bound: str,
                         chain: Optional[dict], parts: _StepParts,
                         off: int = 0) -> dict:
        """Pack host state (+ the un-processed offset across ALL in-flight calls)
        and launch one fused decode call of ``k`` steps (``decode_call_steps``'
        length and what set it, ``bound``) chained on ``chain``'s
        device-resident outputs: every row's ``steps_left`` is clipped to
        ``k``, and the program runs as long as its longest row. Returns the
        in-flight record; results are NOT read.

        Two pack regimes (PERF.md Lever 12):

        * chain start: full host pack into fresh arrays — the admission/retire
          boundary where the host owns the loop.
        * chained fast path: the previous call's device-resident tokens,
          positions, kv lens, and FSM states feed straight back in; the host
          re-derives only ``steps_left`` (the per-row hard budget) and, when a
          row grew a page, the page tables. One small upload instead of nine,
          and the pack wall is overlapped with the in-flight device chain
          (accounted as time_pack_overlap).

        ``parts`` runs pack (numpy staging), then the train every step
        program issues: stage (mask tables, flight records, the key split),
        transfer (the ``jnp.asarray`` calls), dispatch (the jitted call
        alone), and book.
        """
        B = self.cfg.max_batch_size
        fast = chain is not None
        # the fast path's pack already sits in llmd.pack_overlap
        parts.to("pack", annotate=not fast)
        ctx_lens: list[int] = []  # context the call's first step reads, by row
        budgets = self._decode_budgets(active, off)
        if fast:
            with jax.profiler.TraceAnnotation("llmd.pack_overlap"):
                steps_left = self._pack_buf()["steps_left"]
                steps_left.fill(0)
                sig = chain["pages_sig"]
                pages_changed = False
                for j, s in enumerate(active):
                    ctx_lens.append(len(s.token_ids) + off)  # host view + in-flight
                    steps_left[s.slot] = max(0, min(budgets[j], k))
                    if len(s.pages) != sig[j]:
                        pages_changed = True
                if pages_changed:
                    pts_np = np.full((B, self.cfg.max_pages_per_seq), -1,
                                     np.int32)
                    for s in active:
                        pts_np[s.slot, : len(s.pages)] = s.pages
                    pages_sig = tuple(len(s.pages) for s in active)
                else:
                    pts_np, pages_sig = chain["pts_np"], sig
            parts.to("stage")
            mask = chain["mask"]
            fsm_in = chain["fsm_out"]
            sampler_path = chain["sampler_path"]
            self._key, sub = jax.random.split(self._key)
            parts.to("transfer")
            pts_dev = jnp.asarray(pts_np) if pages_changed else chain["pts_dev"]
            toks_in, pos_in, lens_in = (chain["last_toks"], chain["pos_out"],
                                        chain["lens_out"])
            temp_dev, tk_dev, tp_dev, lora_dev = (
                chain["temp_dev"], chain["tk_dev"], chain["tp_dev"],
                chain["lora_dev"])
            steps_dev = jnp.asarray(steps_left)
        else:
            pos = np.full((B,), -1, np.int32)
            pts_np = np.full((B, self.cfg.max_pages_per_seq), -1, np.int32)
            lens_np = np.ones((B,), np.int32)
            lora_idx = np.zeros((B,), np.int32)
            steps_left = np.zeros((B,), np.int32)
            temp = np.zeros((B,), np.float32)
            tk = np.zeros((B,), np.int32)
            tp = np.ones((B,), np.float32)
            toks = np.zeros((B,), np.int32)
            for s, budget in zip(active, budgets):
                i = s.slot
                eff_len = len(s.token_ids) + off  # host view + in-flight tokens
                ctx_lens.append(eff_len)
                toks[i] = s.token_ids[-1]
                pos[i] = eff_len - 1
                pts_np[i, : len(s.pages)] = s.pages
                lens_np[i] = eff_len
                lora_idx[i] = self._lora_slot(s)
                sp: SamplingParams = s.sampling
                temp[i], tk[i], tp[i] = sp.temperature, sp.top_k, sp.top_p
                steps_left[i] = max(0, min(budget, k))
            pages_sig = tuple(len(s.pages) for s in active)
            parts.to("stage")
            mask = (self._stage_chain_masks(active)
                    if any(s.structured is not None or s.logit_bias
                           for s in active) else None)
            fsm_in = mask["fsm0"] if mask is not None else None
            # the branch each of the call's k steps takes in the
            # sampler, by the test the unified step makes (_sampling_state)
            sampler_path = ("biased" if mask is not None else
                            "topk" if (temp > 0.0).any() else "argmax")
            for s in active:
                self.flight.record(s.request_id, "chain_dispatch", k=k,
                                   masked=mask is not None)
            self._key, sub = jax.random.split(self._key)
            parts.to("transfer")
            pts_dev = jnp.asarray(pts_np)
            pos_in, lens_in = jnp.asarray(pos), jnp.asarray(lens_np)
            temp_dev, tk_dev, tp_dev = (jnp.asarray(temp), jnp.asarray(tk),
                                        jnp.asarray(tp))
            lora_dev = jnp.asarray(lora_idx)
            steps_dev = jnp.asarray(steps_left)
            toks_in = jnp.asarray(toks)
        parts.to("dispatch")
        if mask is not None:
            (toks_out, last_toks, pos_out, lens_out, fsm_out, pools,
             cnt, moe_drop) = self._decode_multi_masked_fn(
                self._run_params(), self._pools(), toks_in, pos_in, pts_dev,
                lens_in, temp_dev, tk_dev, tp_dev, sub, steps_dev, lora_dev,
                fsm_in, mask["gidx"], mask["bias_tab"], mask["next_tab"],
            )
        else:
            (toks_out, last_toks, pos_out, lens_out, pools, cnt,
             moe_drop) = (
                self._decode_multi_fn(
                    self._run_params(), self._pools(), toks_in, pos_in, pts_dev,
                    lens_in, temp_dev, tk_dev, tp_dev, sub, steps_dev,
                    lora_dev,
                ))
            fsm_out = None
        self._keep_pools(pools)
        parts.to("book")
        sec = parts.seconds
        # the host's wall before the call: serialized at a chain start,
        # hidden behind the running call of the chain otherwise
        host_pack = sec["plan"] + sec["pack"] + sec["stage"] + sec["transfer"]
        if fast:
            self.stats.time_pack_overlap += host_pack
        self.metrics.step_duration.labels(
            phase="pack_overlap" if fast else "pack").observe(host_pack)
        self.stats.time_decode_steps += host_pack + sec["dispatch"]
        self.stats.n_decode_dispatches += 1
        prog = parts.program = "decode" if mask is None else "decode_masked"
        ctx_tokens = sum(ctx_lens)
        self.programs.record_dispatch(prog)
        self.metrics.program_kv_read_tokens.labels(program=prog).inc(ctx_tokens)
        self._count_attn_kv(prog, ctx_lens, np.ones(len(ctx_lens), np.int64),
                            page_tables=pts_np,
                            seats=[s.slot for s in active],
                            steps=[steps_left[s.slot] for s in active])
        if self.state:
            # a row takes as many of the call's k steps as it has left
            self._count_ssm_tokens(prog, chunk=0, decode=int(steps_left.sum()))
        self.metrics.program_rows.labels(program=prog).inc(len(active))
        self.metrics.sampler_steps.labels(program="decode",
                                          path=sampler_path).inc(k)
        self.metrics.decode_call_steps.labels(bound=bound).inc(k)
        if chain is not None:
            self.stats.n_chained_dispatches += 1
        # Start the device->host copy of everything _decode_process will read:
        # the tokens land on the host while the host loop does other work, so
        # the later np.asarray is a near-free read instead of a blocking one.
        host_reads = [toks_out]
        if self.model_cfg.is_moe:
            host_reads += [cnt, moe_drop]
        for arr in host_reads:
            try:
                arr.copy_to_host_async()
            except (AttributeError, RuntimeError):
                break
        # analytic cost of this call, from its packed shape: the loop runs k
        # steps over all B slots (masked rows compute too), each step streams
        # the weights once and each active row reads its resident KV per step.
        # Stashed on the rec; _decode_process joins it with the measured wall
        # and the kept-token count when the readback lands.
        util_cost = None
        if self.util is not None:
            util_cost = self.util.cost(
                prog, slot_tokens=B * k, weight_passes=k,
                kv_read_tokens=k * ctx_tokens,
                kv_write_tokens=int(steps_left.sum()))
        # last, and from the parts' own sum (see _step_unified)
        self.metrics.step_duration.labels(phase="decode_dispatch").observe(
            self._book_parts(parts), exemplar=self._trace_exemplar(active))
        return {
            "util_cost": util_cost,
            "rows": [(s, s.slot) for s in active], "prog": prog,
            "toks_out": toks_out, "last_toks": last_toks, "cnt": cnt, "k": k,
            # by slot; a copy: the fast path's array is a pack buffer
            "steps": steps_left.copy(), "moe_drop": moe_drop,
            # device-resident chain point for the next pipelined dispatch
            "pos_out": pos_out, "lens_out": lens_out, "fsm_out": fsm_out,
            "mask": mask, "pts_np": pts_np, "pts_dev": pts_dev,
            "pages_sig": pages_sig, "temp_dev": temp_dev, "tk_dev": tk_dev,
            "tp_dev": tp_dev, "lora_dev": lora_dev,
            "sampler_path": sampler_path,
        }

    @_step_phase("decode", "llmd.decode_process", "wait")
    def _decode_process(self, parts: _StepParts, rec: dict) -> None:
        """Read one in-flight decode call's results and apply them to host
        state: wait (the blocking read), apply (per row), book."""
        parts.program = rec["prog"]
        t1_ns = time.time_ns()
        n_tokens = 0
        if self._eplb is not None:
            self._eplb_record(rec["cnt"])
        # llmd-lint: allow[hot-host-sync] designed sync point: the one deferred readback per decode step (dispatch/process split hides it behind the next dispatch)
        toks_out = np.asarray(rec["toks_out"])  # [k, B] (device sync point)
        if self.model_cfg.is_moe:
            # the async copy was started at dispatch; toks_out above already
            # paid this step's sync, so the drop scalar read is free
            self._moe_record(rec["moe_drop"], rec["cnt"])
        parts.to("apply")
        now = time.monotonic()
        k, steps = rec["k"], rec["steps"]
        for s, slot in rec["rows"]:
            if s.finished or s.slot != slot or self.running[slot] is not s:
                continue  # aborted / preempted / replaced while in flight
            # a row's tokens are the first steps[slot] of its column: the
            # steps it was given, at most the call's k. Past them the buffer
            # holds no token (zeros from the step the call ended at)
            kept: list[int] = []
            finished, reason = False, None
            for t in toks_out[:steps[slot], slot].tolist():
                kept.append(t)
                s.token_ids.append(t)
                finished, reason = self._check_finish(s, t)
                if finished:
                    break
            # the newest token's KV is never written yet → computed = len - 1
            s.num_computed = len(s.token_ids) - 1
            if s.structured is not None:
                # replay the landed tokens through the host automaton: keeps
                # the cursor current for the next chain staging and counts
                # violations (device-masked sampling should make fresh == 0)
                fresh = s.structured.sync(s.token_ids, s.prompt_len)
                if fresh:
                    self.stats.structured_violations += fresh
                    self.metrics.structured_violations.inc(fresh)
            if s.first_token_time is None:
                s.first_token_time = now
                self.flight.record(
                    s.request_id, "first_token",
                    ttft_ms=round((now - s.arrival_time) * 1e3, 3))
            s.maybe_commit_blocks(self.allocs[s.rank])
            self.stats.total_decode_tokens += len(kept)
            self.stats.decode_tokens_fused += len(kept)
            if kept:
                if not s.spec_armed:
                    s.spec_flips += 1
                s.spec_armed = True  # fresh tokens landed: re-probe this row
            n_tokens += len(kept)
            # one progress event per fused k-step call (per-N decode progress)
            self.flight.record(s.request_id, "decode", n_tokens=len(kept),
                               generated=s.num_generated)
            if finished:
                self._retire(s, reason)
            self._outputs.append(EngineOutput(
                request_id=s.request_id, new_token_ids=kept, finished=finished,
                finish_reason=reason, num_cached_prompt_tokens=s.num_cached_prompt,
                prompt_len=s.prompt_len,
            ))
        parts.to("book")
        sec = parts.seconds
        wall = sec["wait"] + sec["apply"]
        st = self.stats
        st.time_decode_steps += wall
        st.n_decode_calls += 1
        self.programs.record_complete(rec["prog"])
        if n_tokens:
            self.metrics.decode_tokens.inc(n_tokens)
        # the call ran its k steps (rec["k"]: the length it was given) on
        # every seat: tokens kept, step-slots of rows whose sequence finished
        # before the k-th step or left in flight, and the slots of seats that
        # held no row
        n_rows = len(rec["rows"])
        seats = self.metrics.decode_seat_steps
        seats.labels(outcome="kept").inc(n_tokens)
        seats.labels(outcome="finished").inc(k * n_rows - n_tokens)
        seats.labels(outcome="empty").inc(
            k * (self.cfg.max_batch_size - n_rows))
        if self.util is not None and rec.get("util_cost") is not None:
            # kept tokens commit; everything else the B x k loop computed
            # (masked slots, post-EOS steps, rows preempted in flight) is the
            # padding residual
            self.util.record(
                rec["prog"], rec["util_cost"], wall, committed=n_tokens,
                compile_counts=self.programs.compile_counts())
        self._emit_step_spans("decode", (s for s, _ in rec["rows"]), t1_ns,
                              len(rec["rows"]), n_tokens)
        # last, and from the parts' own sum (see _step_unified)
        self.metrics.step_duration.labels(phase="decode_process").observe(
            self._book_parts(parts),
            exemplar=self._trace_exemplar([s for s, _ in rec["rows"]]))

    def _retire(self, seq: Sequence, reason: Optional[str]) -> None:
        """Shared retirement path: free slot + pages, drop from the live map."""
        seq.finished = True
        seq.finish_reason = reason
        if seq.structured is not None:
            # final automaton sync: a constrained generation that ends before
            # the grammar accepts (max_tokens/max_model_len truncation) is a
            # violation from the client's point of view — the text won't parse
            fresh = seq.structured.sync(seq.token_ids, seq.prompt_len)
            n_bad = fresh + (0 if seq.structured.complete else 1)
            if n_bad:
                self.stats.structured_violations += n_bad
                self.metrics.structured_violations.inc(n_bad)
        if seq.spec_drafted > 0:
            constrained = seq.structured is not None or bool(seq.logit_bias)
            self.metrics.spec_acceptance.labels(
                constrained="yes" if constrained else "no").observe(
                seq.spec_accepted / seq.spec_drafted)
        # decision-ledger attrs ride the terminal event (None-valued attrs
        # are dropped by the recorder, so untouched levers add nothing)
        decision_attrs = {}
        if self._decisions_on:
            decision_attrs = dict(
                spec_drafted=seq.spec_drafted or None,
                spec_accepted=(seq.spec_accepted
                               if seq.spec_drafted else None),
                spec_flips=seq.spec_flips or None,
                cached_tokens=seq.num_cached_prompt or None)
        self.flight.finish(
            seq.request_id, event="retired", reason=reason or "",
            generated=seq.num_generated,
            ttft_ms=round((seq.first_token_time - seq.arrival_time) * 1e3, 3)
            if seq.first_token_time is not None else None,
            **decision_attrs)
        if self.kv_connector is not None and seq.block_hashes:
            # K5 save path: dispatch the chunked staging here (cheap, same
            # helper as the P/D export path), drain + hand bytes to the
            # external engine on the connector thread off the locked step loop.
            try:
                from llmd_tpu.disagg.transfer import drain_staged, stage_pages

                n = len(seq.block_hashes)
                ps = self.cfg.page_size
                parts = stage_pages(self.cache, seq.pages[:n], self.cfg.num_pages,
                                    self.cfg.offload_staging_blocks)
                hashes = list(seq.block_hashes)
                chunks = [seq.token_ids[i * ps : (i + 1) * ps] for i in range(n)]
                rid = seq.request_id

                def _drain(parts=parts, hashes=hashes, chunks=chunks, rid=rid):
                    try:
                        self.kv_connector.save_blocks(hashes, chunks,
                                                      drain_staged(parts))
                    except Exception:
                        pass  # external engine down: never fails serving
                    try:
                        self.kv_connector.request_finished(rid)
                    except Exception:
                        pass

                self._connector_pool.submit(_drain)
            except Exception:
                pass  # dispatch failure must not fail retirement either
        if seq.admit_features is not None and seq.first_token_time is not None:
            # one predictor training row per completed request (engine-emitted
            # traces, not a synthetic generator — latency-predictor.md:58)
            now = time.monotonic()
            n_gen = max(1, seq.num_generated)
            self.latency_trace.append(dict(
                seq.admit_features,
                tokens_generated=float(n_gen),
                ttft_ms=(seq.first_token_time - seq.arrival_time) * 1e3,
                tpot_ms=((now - seq.first_token_time) / max(1, n_gen - 1)) * 1e3
                if n_gen > 1 else None,
            ))
        if seq.slot >= 0:
            self.running[seq.slot] = None
            seq.slot = -1
            if self.lora_registry is not None:
                self.lora_registry.on_finished(seq.lora_id)
        self._free_seq(seq)
        self.seqs.pop(seq.request_id, None)

    @_profile_phase("llmd.mask_build")
    def _build_bias(self, rows_and_seqs: list[tuple[int, "Sequence"]],
                    logits_shape: tuple) -> Optional[np.ndarray]:
        """Host-side additive ``[B, V]`` bias for a sample batch: the grammar
        allow-mask of each constrained row's current automaton state, plus any
        OpenAI ``logit_bias`` entries. Returns None when the batch carries no
        constrained row — the common case keeps the exact unbiased sampler
        program (no bias upload, no second compile)."""
        if not any(s.structured is not None or s.logit_bias
                   for _, s in rows_and_seqs):
            return None
        t0 = time.perf_counter()
        B, V = logits_shape[0], logits_shape[-1]
        bias = np.zeros((B, V), np.float32)
        for i, s in rows_and_seqs:
            st = s.structured
            if st is not None:
                fresh = st.sync(s.token_ids, s.prompt_len)
                if fresh:
                    self.stats.structured_violations += fresh
                    self.metrics.structured_violations.inc(fresh)
                st.grammar.fill_bias(bias[i], st.state)
                self.stats.structured_mask_builds += 1
                if not st.mask_logged:
                    st.mask_logged = True  # first mask only: timeline, not spam
                    self.flight.record(
                        s.request_id, "structured_mask", kind=st.kind,
                        n_allowed=int(len(st.grammar.allowed_ids(st.state))))
            if s.logit_bias:
                row = bias[i]
                for tid, b in s.logit_bias.items():
                    if 0 <= tid < V:
                        # OpenAI semantics: -100 is an outright ban
                        row[tid] = NEG_BIAS if b <= -100.0 else row[tid] + b
        dt = time.perf_counter() - t0
        self.stats.time_mask_build += dt
        self.metrics.structured_mask_seconds.observe(dt)
        return bias

    def _replicated(self, x: jax.Array) -> jax.Array:
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _sampling_state(self, rows_and_seqs: list[tuple[int, "Sequence"]],
                        ) -> tuple[tuple[jax.Array, ...], bool]:
        """``(temperature [B], top_k [B], top_p [B], key)`` for a step whose
        sampler sees ``rows_and_seqs`` (row, sequence), and whether any of
        them samples. Decided from the rows' own ``SamplingParams``: a step
        of greedy rows gets the device's constants (no transfer, no key
        split, and the sampler takes its argmax branch); one with a sampling
        row uploads the three arrays and splits ``_key`` once."""
        if not any(s.sampling.temperature > 0.0 for _, s in rows_and_seqs):
            return self._greedy_state, False
        B = self.cfg.max_batch_size
        temp = np.zeros((B,), np.float32)
        tk = np.zeros((B,), np.int32)
        tp = np.ones((B,), np.float32)
        for i, s in rows_and_seqs:
            sp: SamplingParams = s.sampling
            temp[i], tk[i], tp[i] = sp.temperature, sp.top_k, sp.top_p
        self._key, sub = jax.random.split(self._key)
        # placed as the constants are, so both kinds of step are one program
        return tuple(self._replicated(jnp.asarray(x))
                     for x in (temp, tk, tp, sub)), True

    def _sample_dispatch(self, rows_and_seqs: list[tuple[int, "Sequence"]],
                         logits: jax.Array, sampled: jax.Array,
                         sampling: tuple[jax.Array, ...],
                         ahead_rows: "set[int] | frozenset[int]" = frozenset(),
                         ) -> dict:
        """The record of what a unified step left to read: ``sampled``, the
        tokens its program picked, with the device->host copy started; no
        sync point here. With no row to sample (prefill chunks short of
        their prompts' ends) the record holds no array, and reading it reads
        only what else the step left. A batch with a constrained row takes
        its tokens from the biased sampler instead, over the step's logits
        and the host-built bias (``_build_bias``): the one second dispatch
        left, counted under ``program="sample"``.
        ``ahead_rows``: the batch rows whose input token was taken on the
        device, for the count of what riding ahead kept."""
        if not rows_and_seqs:
            return {"sampled": None, "rows": [], "biased": False}
        bias = self._build_bias(rows_and_seqs, logits.shape)
        if bias is not None:
            # biased program: grammar masks / logit_bias add ON DEVICE before
            # argmax — logits never leave the accelerator. Lazily jitted, so
            # engines that never see a constrained request never compile it.
            temp, tk, tp, key = sampling
            sampled = sample_tokens_biased(
                logits.astype(jnp.float32), jnp.asarray(bias), key, temp, tk, tp)
            self.programs.record_dispatch("sample")
        sampled = self._replicated(sampled)  # the next step's prev_sampled
        try:
            sampled.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        return {"sampled": sampled, "biased": bias is not None,
                "rows": [(i, s, s.slot, i in ahead_rows)
                         for i, s in rows_and_seqs]}

    def _flush_pending_sample(self, parts: Optional[_StepParts] = None) -> None:
        """Read and apply the unified step in flight, if any: whoever builds
        on host token state calls this first (the fused decode and verify
        programs, a preemption, a batch with a constrained row, an empty
        plan). After it every running row's last token is on the host."""
        rec, self._pending_sample = self._pending_sample, None
        if rec is None:
            return
        if parts is not None:  # inside a unified step: its own wait and apply
            self._sample_apply(rec, parts)
            return
        # outside a unified step the read is still the unified program's,
        # booked under program="sample" since no unified step_duration
        # sample covers it
        parts = _StepParts("sample", "llmd.unified", None)
        try:
            self._sample_apply(rec, parts)
        finally:
            self._book_parts(parts)

    def _sample_apply(self, rec: dict, parts: _StepParts) -> None:
        """Read what one unified step left on the device (the sync point:
        its sampled tokens, and with MoE its expert counts and drops) and
        apply it; the reads alone are ``parts``' wait, the per-row loop its
        apply. A row is skipped if its sequence left meanwhile; one that rode
        ahead and is skipped was computed for nothing, and counts as such."""
        parts.to("wait")
        if "cnt" in rec:
            self._eplb_record(rec["cnt"])
        if "moe_drop" in rec:
            self._moe_record(rec["moe_drop"], rec["moe_cnt"],
                             gemm_plan=self.backends.moe_gemm_plan)
        self.programs.record_complete(rec["prog"])
        if rec["sampled"] is None:
            parts.to("apply")
            return
        # llmd-lint: allow[hot-host-sync] designed sync point: the previous step's sample readback, under the next step's device time
        sampled = np.asarray(rec["sampled"])
        parts.to("apply")
        if rec["biased"]:
            self.programs.record_complete("sample")
        now = time.monotonic()
        kept = discarded = 0
        for i, s, slot, ahead in rec["rows"]:
            if not self._still_seated(s, slot):
                # aborted / preempted while the sample was in flight, or (a
                # row that rode ahead) ended by the stop token read since
                discarded += ahead
                continue
            kept += ahead
            tok = int(sampled[i])
            s.token_ids.append(tok)
            # the block this token completes, where the row's compute has
            # run past it already (it rides ahead in the step in flight)
            s.maybe_commit_blocks(self.allocs[s.rank])
            if not s.spec_armed:
                s.spec_flips += 1
            s.spec_armed = True  # fresh token landed: re-probe this row's drafter
            if s.structured is not None:
                fresh = s.structured.sync(s.token_ids, s.prompt_len)
                if fresh:  # masked sampling should make this unreachable
                    self.stats.structured_violations += fresh
                    self.metrics.structured_violations.inc(fresh)
            if s.first_token_time is None:
                s.first_token_time = now
                self.flight.record(
                    s.request_id, "first_token",
                    ttft_ms=round((now - s.arrival_time) * 1e3, 3))
            finished, reason = self._check_finish(s, tok)
            if finished:
                self._retire(s, reason)
            self._outputs.append(EngineOutput(
                request_id=s.request_id, new_token_ids=[tok], finished=finished,
                finish_reason=reason, num_cached_prompt_tokens=s.num_cached_prompt,
                prompt_len=s.prompt_len,
            ))
        if kept:
            self.metrics.unified_ahead_rows.labels(outcome="kept").inc(kept)
        if discarded:
            self.metrics.unified_ahead_rows.labels(
                outcome="discarded").inc(discarded)

    def _check_finish(self, seq: Sequence, tok: int) -> tuple[bool, Optional[str]]:
        sp: SamplingParams = seq.sampling
        if not sp.ignore_eos and tok in (sp.stop_token_ids or ()):
            return True, "stop"
        if seq.num_generated >= seq.max_tokens:
            return True, "length"
        if len(seq.token_ids) >= self.cfg.max_model_len:
            return True, "length"
        return False, None

    # ------------------------------------------------------------- embeddings
    def embed(self, token_ids: list[int], lora_id: Optional[str] = None,
              rank: int = 0) -> list[float]:
        """Mean-pooled, L2-normalised final hidden state (/v1/embeddings path).

        Runs chunk-wise through the compiled embed program (flat single-sequence
        batches), borrowing KV pages from the requesting rank's partition only
        for the duration of the call. The caller serialises against the step
        loop (run_locked in the server).
        """
        if not token_ids:
            raise ValueError("empty input")
        if self.state:
            raise ValueError(
                "embeddings: not supported for a model with recurrent layers "
                "(the embed program borrows pages, and has no state slot)")
        token_ids = token_ids[: self.cfg.max_model_len - 1]
        chunk = self.cfg.prefill_chunk
        ps = self.cfg.page_size
        need = (len(token_ids) + ps - 1) // ps
        alloc = self.allocs[rank if 0 <= rank < self.num_ranks else 0]
        pages: list[int] = []
        for _ in range(need):
            pid = alloc.allocate()
            if pid is None:
                for p in pages:
                    alloc.release(p)
                raise RuntimeError("no free KV pages for embedding request")
            pages.append(pid)
        try:
            pt = np.full((1, self.cfg.max_pages_per_seq), -1, np.int32)
            pt[0, : len(pages)] = pages
            lora_idx = np.full(
                (chunk,),
                self.lora_registry.slot_of(lora_id) if self.lora_registry else 0,
                np.int32)
            acc = np.zeros((self.model_cfg.hidden_size,), np.float64)
            for start in range(0, len(token_ids), chunk):
                n = min(chunk, len(token_ids) - start)
                toks = np.zeros((chunk,), np.int32)
                toks[:n] = token_ids[start : start + n]
                pos = np.full((chunk,), -1, np.int32)
                pos[:n] = np.arange(start, start + n)
                h_sum, self.cache = self._embed_fn(
                    self._run_params(), self.cache, jnp.asarray(toks),
                    jnp.asarray(pos), jnp.asarray(pt),
                    jnp.asarray([start + n], jnp.int32),
                    jnp.asarray([0, n], jnp.int32), jnp.asarray(lora_idx),
                )
                acc += np.asarray(h_sum, np.float64)
        finally:
            for p in pages:
                alloc.release(p)
        vec = acc / max(1, len(token_ids))
        norm = float(np.linalg.norm(vec))
        return (vec / norm if norm > 0 else vec).astype(float).tolist()

    # ------------------------------------------------------------- convenience
    def generate(self, prompts: list[list[int]], sampling: Optional[SamplingParams] = None) -> dict[str, list[int]]:
        """Blocking batch generation (tests/bench); returns request_id → generated ids."""
        for i, p in enumerate(prompts):
            self.add_request(f"req-{i}", p, sampling)
        done: dict[str, list[int]] = {f"req-{i}": [] for i in range(len(prompts))}
        while self.has_work():
            for out in self.step():
                done[out.request_id].extend(out.new_token_ids)
        # quiesce invariant: every launched fused call was processed — a gap
        # means a chained in-flight record was orphaned and its sampled
        # tokens silently dropped (engine.py n_decode_dispatches docstring)
        assert (self.stats.n_decode_dispatches == self.stats.n_decode_calls
                and not self._pending_decode), (
            f"decode pipeline leak at quiesce: dispatched="
            f"{self.stats.n_decode_dispatches} "
            f"processed={self.stats.n_decode_calls} "
            f"pending={len(self._pending_decode)}")
        # generalized form (programs.py): the per-program ledger must balance
        # for EVERY registry entry at every drain, not just the decode pair
        assert self.programs.quiesced(), (
            f"program ledger leak at quiesce: {self.programs.counters()}")
        return done
