"""Utilization attribution plane: analytic roofline cost model + ledgers.

The live analog of PERF.md's paper math. Three pieces:

1. **Analytic cost model** — per-dispatch FLOPs and HBM bytes derived from
   the model config and the dispatch's PACKED shape (the work the compiled
   program executes, padding included: NT positions for the mixed-batch and
   verify programs, B x k slot-steps for fused decode). The matmul term is
   ``2 * active_params`` per slot position.
   The byte term is weight passes + KV page traffic (read + write) from the
   pool's per-token width. Attention score/value FLOPs are O(len * Dh) per
   token against the O(params) matmul term and are deliberately excluded,
   matching the offline formula (documented in observability/utilization.md).

2. **UtilLedger** — joins each dispatch's analytic cost with the measured
   step wall at completion into per-program achieved FLOP/s and bytes/s over
   a rolling ``LLMD_UTIL_WINDOW_S`` window, exported as
   ``llmd_tpu:program_mfu`` / ``program_mbu`` against the device-generation
   peak table (CPU -> null peaks: families stay declared, gauges export no
   samples). Also the token-goodput accounting: every slot-token of every
   dispatch lands in exactly one of ``GOODPUT_KINDS`` (committed,
   spec_rejected, padding, preempted_recompute) plus the virtual
   prefix_saved class; per program the five partition (capacity + saved),
   so fractions sum to 1 by construction — PR 13's sum-to-wall discipline
   applied to tokens. And recompile observability: ``compile_counts()``
   deltas polled at completion feed ``llmd_tpu:program_compiles_total`` and
   a compile-time histogram.

3. **Peak table** — the single source of truth for device-generation peaks
   (bf16 TFLOP/s, HBM GB/s); ``LLMD_UTIL_PEAKS_FILE`` overlays a JSON map
   for new generations without a code change. tools/membw.py consumes
   :func:`chip_peaks` too.

Off-switch contract (mirrors obs/decisions.py): ``LLMD_UTIL_LEDGER=0``
(or ``off``/``false``/empty) is read ONCE at engine construction; the off
path constructs no ledger, stamps nothing per dispatch, and attaches no
exporter — zero overhead, test-asserted in tests/test_costmodel.py.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, Iterable, Optional, Tuple

# ---------------------------------------------------------------------------
# Knobs (deploy/ENV_VARS.md)
# ---------------------------------------------------------------------------


def util_ledger_enabled() -> bool:
    """Master switch, read once at engine construction (default on)."""
    return os.environ.get("LLMD_UTIL_LEDGER", "1") not in (
        "0", "false", "off", "")


def util_window_s() -> float:
    """Rolling window for the achieved-rate gauges (seconds)."""
    try:
        return max(1.0, float(os.environ.get("LLMD_UTIL_WINDOW_S", "60")))
    except ValueError:
        return 60.0


# ---------------------------------------------------------------------------
# Device-generation peak table
# ---------------------------------------------------------------------------

# (bf16 TFLOP/s, HBM GB/s) per device generation — matched by substring
# against jax's device_kind. Sources: public TPU spec sheets; v5e figures
# match the numbers PERF.md's roofline sections argue from.
CHIP_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v6e": (918.0, 1640.0),
}


def _peaks_overlay() -> Dict[str, Tuple[float, float]]:
    """CHIP_PEAKS overlaid with LLMD_UTIL_PEAKS_FILE (malformed file or rows
    degrade to the builtin table with a stderr note, never a crash)."""
    table = dict(CHIP_PEAKS)
    path = os.environ.get("LLMD_UTIL_PEAKS_FILE")
    if not path:
        return table
    try:
        with open(path) as f:
            raw = json.load(f)
        for kind, peaks in raw.items():
            tf, gb = float(peaks[0]), float(peaks[1])
            table[str(kind)] = (tf, gb)
    except (OSError, ValueError, TypeError, IndexError, KeyError) as e:
        import sys
        print(f"# costmodel: ignoring LLMD_UTIL_PEAKS_FILE {path!r}: {e}",
              file=sys.stderr)
    return table


def chip_peaks(
    device_kind: str,
) -> Tuple[Optional[float], Optional[float]]:
    """(bf16 TFLOP/s, HBM GB/s) for a device kind, or (None, None) when the
    generation is not in the table — the CPU and unlisted chips export null
    peaks so MFU/MBU gauges go absent rather than lie. There is no default
    peak."""
    table = _peaks_overlay()
    # longest-match first so "TPU v5 lite" wins over a hypothetical "TPU v5"
    for k in sorted(table, key=len, reverse=True):
        if k.lower() in (device_kind or "").lower():
            return table[k]
    return None, None


# ---------------------------------------------------------------------------
# Analytic model: params, FLOPs, bytes
# ---------------------------------------------------------------------------


def param_count(cfg) -> int:
    """Total weight parameters.

    Dense: qkvo + swiglu per layer, plus (un)tied embeddings. MoE adds the expert banks (+ shared
    experts) in place of the dense FFN, plus the router.
    """
    D, L = cfg.hidden_size, cfg.num_layers
    H, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = D * (H + 2 * Hk) * Dh + H * Dh * D
    if getattr(cfg, "is_moe", False):
        Fm = cfg.moe_intermediate_size
        banks = (cfg.moe_num_experts + cfg.moe_num_shared_experts)
        ffn = 3 * D * Fm * banks + D * cfg.moe_num_experts  # experts + router
    else:
        ffn = 3 * D * cfg.intermediate_size
    emb = cfg.vocab_size * D * (1 if cfg.tie_embeddings else 2)
    return (attn + ffn) * L + emb


def active_param_count(cfg) -> int:
    """Parameters touched per token (the MFU numerator's 2N): dense = all;
    MoE = attention + top_k + shared experts + router + embeddings."""
    if not getattr(cfg, "is_moe", False):
        return param_count(cfg)
    D, L = cfg.hidden_size, cfg.num_layers
    H, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = D * (H + 2 * Hk) * Dh + H * Dh * D
    Fm = cfg.moe_intermediate_size
    active = cfg.moe_top_k + cfg.moe_num_shared_experts
    ffn = 3 * D * Fm * active + D * cfg.moe_num_experts
    emb = cfg.vocab_size * D * (1 if cfg.tie_embeddings else 2)
    return (attn + ffn) * L + emb


def bytes_per_param(cfg, quantize_weights: Optional[str]) -> int:
    """Weight-stream bytes per parameter: int8 weight-only serves ~1 (per-
    channel scales are negligible), else checkpoint dtype width."""
    if quantize_weights == "int8":
        return 1
    return 2 if cfg.dtype == "bfloat16" else 4


def weight_bytes(cfg, quantize_weights: Optional[str] = None) -> int:
    """Bytes one full weight pass streams from HBM."""
    return param_count(cfg) * bytes_per_param(cfg, quantize_weights)


def flops_per_token(cfg) -> float:
    """Matmul FLOPs per slot position: 2 * active params (the shared
    numerator of bench's decode_mfu and the live program_mfu)."""
    return 2.0 * active_param_count(cfg)


def kv_bytes_per_token(cfg, kv_cache_dtype: Optional[str] = None) -> int:
    """Pool bytes per cached token: planes x heads x head-width x dtype.
    GQA stores k+v planes; MLA stores one latent(+rope) plane. fp8 KV
    halves the width."""
    dtype_bytes = 1 if kv_cache_dtype == "fp8" else (
        2 if cfg.dtype == "bfloat16" else 4)
    planes = 1 if getattr(cfg, "is_mla", False) else 2
    # (sparse selection keeps a KV head's pages apart: the pool's rows hold
    # one head, and a token has one row a head)
    heads = cfg.num_kv_heads if getattr(cfg, "sparse_topk", 0) \
        else cfg.kv_cache_heads
    return planes * heads * cfg.kv_cache_head_dim * dtype_bytes


def moe_comm_bytes_per_token(cfg) -> int:
    """MoE dispatch/combine traffic per slot token: every layer ships each
    of the top-k routed copies of the D-wide activation to its expert and
    back (2 hops — DeepEP's dispatch + combine, lax.all_to_all here). Dense
    models route nothing. Counted in ``dispatch_cost`` so ``program_mbu``
    sees the all-to-all bytes the roofline previously ignored."""
    if not getattr(cfg, "is_moe", False):
        return 0
    act_bytes = 2 if cfg.dtype == "bfloat16" else 4
    return (cfg.num_layers * cfg.moe_top_k * cfg.hidden_size
            * act_bytes * 2)


@dataclass(frozen=True)
class DispatchCost:
    """Analytic cost of ONE compiled-program dispatch, from its packed shape.

    ``slot_tokens`` is the padded capacity the program actually computes
    (NT, or B x k for fused decode) — the goodput denominator and the FLOPs
    multiplier: padding burns real FLOPs, which is exactly what MFU should
    see and goodput should indict.
    """

    flops: float
    hbm_bytes: float
    slot_tokens: int
    # MoE all-to-all dispatch+combine traffic (slot_tokens x k x D x bytes x
    # 2 hops x layers); already folded into hbm_bytes, kept separate so the
    # bench JSON / ledger can report the comm share on its own.
    moe_comm_bytes: float = 0.0


def dispatch_cost(cfg, *, slot_tokens: int, weight_passes: int = 1,
                  kv_read_tokens: int = 0, kv_write_tokens: int = 0,
                  quantize_weights: Optional[str] = None,
                  kv_cache_dtype: Optional[str] = None) -> DispatchCost:
    """Cost of one dispatch: ``2 * active_params`` FLOPs per slot token;
    bytes = weight passes + KV page reads/writes + MoE dispatch/combine
    comm. Monotone in every token argument (test-asserted)."""
    kvb = kv_bytes_per_token(cfg, kv_cache_dtype)
    moe_comm = float(moe_comm_bytes_per_token(cfg)) * max(0, slot_tokens)
    return DispatchCost(
        flops=flops_per_token(cfg) * max(0, slot_tokens),
        hbm_bytes=(float(weight_bytes(cfg, quantize_weights)) * weight_passes
                   + float(kvb) * (max(0, kv_read_tokens)
                                   + max(0, kv_write_tokens))
                   + moe_comm),
        slot_tokens=max(0, slot_tokens),
        moe_comm_bytes=moe_comm,
    )


# ---------------------------------------------------------------------------
# Goodput kinds
# ---------------------------------------------------------------------------

GOODPUT_KINDS = ("committed", "spec_rejected", "padding",
                 "preempted_recompute", "prefix_saved")


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------


class UtilLedger:
    """Per-program utilization + goodput + recompile accounting.

    The engine calls :meth:`record` once per completed dispatch from the
    step loop (single-threaded); gauges read through scrape-time callbacks
    from the metrics thread, so mutation happens under a lock. All inputs
    are host integers the dispatch sites already compute — no device reads.
    """

    def __init__(self, model_cfg, *, device_kind: str = "",
                 quantize_weights: Optional[str] = None,
                 kv_cache_dtype: Optional[str] = None,
                 window_s: Optional[float] = None,
                 peaks: Optional[Tuple[Optional[float],
                                       Optional[float]]] = None,
                 now=time.monotonic):
        self.cfg = model_cfg
        self.quantize_weights = quantize_weights
        self.kv_cache_dtype = kv_cache_dtype
        self.window_s = util_window_s() if window_s is None else window_s
        tf, gb = chip_peaks(device_kind) if peaks is None else peaks
        self.peak_flops = tf * 1e12 if tf else None
        self.peak_bytes = gb * 1e9 if gb else None
        self._now = now
        self._lock = threading.RLock()
        # program -> kind -> tokens
        self._tokens: Dict[str, Dict[str, int]] = {}
        # program -> [flops, bytes, busy_s, dispatches] cumulative
        self._cost: Dict[str, list] = {}
        # program -> deque[(t, flops, bytes)] for the rolling-rate gauges
        self._events: Dict[str, collections.deque] = {}
        # recompile watch: last compile_counts() snapshot + per-program total
        self._compiles_seen: Dict[str, int] = {}
        self._compiles: Dict[str, int] = collections.defaultdict(int)
        self._metrics = None  # bound by attach_util_exporter

    # -- recording ---------------------------------------------------------

    def cost(self, program: str, *, slot_tokens: int, weight_passes: int = 1,
             kv_read_tokens: int = 0, kv_write_tokens: int = 0) -> DispatchCost:
        """Dispatch-site helper: analytic cost with this engine's weight/KV
        byte widths baked in."""
        del program  # cost is shape-only; kept for call-site readability
        return dispatch_cost(
            self.cfg, slot_tokens=slot_tokens, weight_passes=weight_passes,
            kv_read_tokens=kv_read_tokens, kv_write_tokens=kv_write_tokens,
            quantize_weights=self.quantize_weights,
            kv_cache_dtype=self.kv_cache_dtype)

    def record(self, program: str, cost: DispatchCost, duration_s: float, *,
               committed: int = 0, spec_rejected: int = 0,
               preempted_recompute: int = 0, prefix_saved: int = 0,
               compile_counts: Optional[Dict[str, int]] = None) -> None:
        """Join one completed dispatch's analytic cost with its measured
        step wall and classify its slot-tokens. ``padding`` is the residual
        ``slot_tokens - (committed + spec_rejected + preempted_recompute)``,
        clamped at 0, so per-program fractions sum to 1 by construction."""
        real = committed + spec_rejected + preempted_recompute
        padding = max(0, cost.slot_tokens - real)
        t = self._now()
        with self._lock:
            tk = self._tokens.setdefault(
                program, {k: 0 for k in GOODPUT_KINDS})
            tk["committed"] += committed
            tk["spec_rejected"] += spec_rejected
            tk["padding"] += padding
            tk["preempted_recompute"] += preempted_recompute
            tk["prefix_saved"] += prefix_saved
            c = self._cost.setdefault(program, [0.0, 0.0, 0.0, 0, 0.0])
            c[0] += cost.flops
            c[1] += cost.hbm_bytes
            c[2] += max(0.0, duration_s)
            c[3] += 1
            c[4] += cost.moe_comm_bytes
            ev = self._events.setdefault(
                program, collections.deque())
            ev.append((t, cost.flops, cost.hbm_bytes))
            self._trim(ev, t)
        m = self._metrics
        if m is not None:
            gp = m.goodput_tokens
            for kind, n in (("committed", committed),
                            ("spec_rejected", spec_rejected),
                            ("padding", padding),
                            ("preempted_recompute", preempted_recompute),
                            ("prefix_saved", prefix_saved)):
                if n:
                    gp.labels(program=program, kind=kind).inc(n)
        if compile_counts is not None:
            self._note_compiles(program, compile_counts, duration_s)

    def _trim(self, ev: collections.deque, t: float) -> None:
        horizon = t - self.window_s
        while ev and ev[0][0] < horizon:
            ev.popleft()

    def _note_compiles(self, program: str, counts: Dict[str, int],
                       duration_s: float) -> None:
        """Fold a compile_counts() snapshot: any program whose cache grew
        since the last snapshot gets the delta counted; the program whose
        dispatch just completed additionally observes its step wall into the
        compile-time histogram (the compile dominated that step)."""
        m = self._metrics
        with self._lock:
            for prog, n in counts.items():
                prev = self._compiles_seen.get(prog, 0)
                if n > prev:
                    delta = n - prev
                    self._compiles[prog] += delta
                    if m is not None:
                        m.program_compiles.labels(program=prog).inc(delta)
                        if prog == program:
                            m.program_compile_seconds.labels(
                                program=prog).observe(max(0.0, duration_s))
                self._compiles_seen[prog] = max(prev, n)

    # -- reading -----------------------------------------------------------

    def programs(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._tokens))

    def totals(self) -> Dict[str, Dict[str, int]]:
        """program -> kind -> cumulative tokens (deep copy)."""
        with self._lock:
            return {p: dict(t) for p, t in self._tokens.items()}

    def fractions(self, program: str) -> Dict[str, float]:
        """Goodput fractions for one program; values sum to 1 (empty dict
        before the first dispatch)."""
        with self._lock:
            tk = self._tokens.get(program)
            if not tk:
                return {}
            total = sum(tk.values())
            if total <= 0:
                return {}
            return {k: v / total for k, v in tk.items()}

    def padding_efficiency(self, program: str) -> Optional[float]:
        """Real packed positions / slot capacity, cumulative. In (0,1] once
        the program has carried any real token; None before any dispatch."""
        with self._lock:
            tk = self._tokens.get(program)
            if not tk:
                return None
            real = (tk["committed"] + tk["spec_rejected"]
                    + tk["preempted_recompute"])
            cap = real + tk["padding"]
            if cap <= 0:
                return None
            return real / cap

    def achieved(self, program: str) -> Tuple[Optional[float],
                                              Optional[float]]:
        """(FLOP/s, bytes/s) over the rolling window; None before data."""
        t = self._now()
        with self._lock:
            ev = self._events.get(program)
            if not ev:
                return (None, None)
            self._trim(ev, t)
            if not ev:
                return (None, None)
            flops = sum(e[1] for e in ev)
            byts = sum(e[2] for e in ev)
            span = max(t - ev[0][0], 1e-3)
        return (flops / span, byts / span)

    def mfu(self, program: str) -> Optional[float]:
        if self.peak_flops is None:
            return None
        f, _ = self.achieved(program)
        return None if f is None else f / self.peak_flops

    def mbu(self, program: str) -> Optional[float]:
        if self.peak_bytes is None:
            return None
        _, b = self.achieved(program)
        return None if b is None else b / self.peak_bytes

    def compiles(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._compiles)

    def recompiles(self) -> int:
        """Compiles beyond the first per program — 0 in healthy steady
        state (the bench provenance key and the RecompileStorm numerator)."""
        with self._lock:
            return sum(max(0, n - 1) for n in self._compiles.values())

    # -- scrape-time callbacks --------------------------------------------

    def _gauge_samples(self, fn) -> Iterable[Tuple[Dict[str, str], float]]:
        for p in self.programs():
            v = fn(p)
            if v is not None:
                yield ({"program": p}, v)

    def mfu_samples(self):
        return self._gauge_samples(self.mfu)

    def mbu_samples(self):
        return self._gauge_samples(self.mbu)

    def flops_samples(self):
        return self._gauge_samples(lambda p: self.achieved(p)[0])

    def bytes_samples(self):
        return self._gauge_samples(lambda p: self.achieved(p)[1])

    def padding_samples(self):
        return self._gauge_samples(self.padding_efficiency)


def attach_util_exporter(ledger: UtilLedger, metrics) -> None:
    """Bind the ledger to an EngineMetrics: counters increment inline at
    record() time; the rate/ratio gauges attach scrape-time callbacks (the
    device-HBM-gauge pattern, so label sets track programs as they run)."""
    ledger._metrics = metrics
    metrics.program_mfu.set_labels_function(ledger.mfu_samples)
    metrics.program_mbu.set_labels_function(ledger.mbu_samples)
    metrics.program_flops.set_labels_function(ledger.flops_samples)
    metrics.program_bytes.set_labels_function(ledger.bytes_samples)
    metrics.padding_efficiency.set_labels_function(ledger.padding_samples)
