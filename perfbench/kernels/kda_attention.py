"""What a KDA (delta-rule linear attention) layer's recurrence demands of a
call, whatever implements it, as bytes and operations from shapes, and the
``kda_attention`` kernel's share of the roofline over a traced window
(``kda_attention*`` in a device trace).

One call is one KDA layer over R live rows that bring T tokens in all, H
heads of d lanes, a matrix state [d, d] a head:
  bytes      = R * 2 * H * d * d * s       each live row's state read once and
                                           written once, s bytes an element
             + T * H * d * (3 * 4 + 4)     q, k, v in (float32, as the mixer's
                                           conv and norms leave them) and the
                                           log-decay a channel (float32)
             + T * H * 4                   b, a scalar a head
             + T * H * d * 4               o out (float32)
  operations = T * H * 8 * d * d           the recurrence a token, the same
                                           however it is blocked: the decay
                                           of S (d d), k^T S (2 d d), the
                                           rank-one update (3 d d: v - ., the
                                           outer product, the sum) and S^T q
                                           (2 d d)
``s`` is the type the configuration's ``state`` block states (``kda_dtype``).
The least time is the larger of bytes over the chip's memory bandwidth and
operations over its bf16 matrix rate (the kernel's float32 products cannot
reach it; the decode rows are bound by the state's bytes by a factor of 60).

R and T, per dispatch of the program ``src["module"]`` names, from the
program's counters over the part of the window they cover (the part before
the capture; the mix is stationary):
  unified   decode rows ``llmd_tpu:unified_decode_rows_total`` (a token each)
            and prefill tokens ``llmd_tpu:linear_attn_tokens_total{rows=
            "prefill"}`` over ``engine_program_dispatches_total{program=
            "unified"}``; the prefill tokens of a step are counted as ONE row
            (a step seldom holds two chunks: with two, the demand has one
            more state and reads low, never high);
  decode    the fused call runs the kernel once a layer and STEP; its live
            row-steps are ``linear_attn_tokens_total{rows="decode"}`` less the
            unified step's decode rows, over the steps the calls ran,
            ``llmd_tpu:decode_call_steps_total``: rows a step, a token each.
A program without those counters reads nothing.

The kernel reads and writes every row's slot it is given, live or not, and
pads a row's last block: that is the implementation's cost and adds nothing
to the demand, so no reading can pass 100%.
"""

from __future__ import annotations

import json
import os
import re

from kernels.lightning_attention import grew
from kernels.ragged_paged_attention import HERE, least_seconds

PATTERN = "kda_attention"
_BYTES = {"float32": 4, "bfloat16": 2}


def cost(rows: float, tokens: float, heads: int, d: int,
         state_bytes: int = 4) -> tuple:
    """(operations, bytes) of one call of one layer over ``rows`` live rows
    that bring ``tokens`` tokens."""
    byts = (rows * 2 * heads * d * d * state_bytes
            + tokens * heads * (d * 5 * 4 + 4))
    return tokens * heads * 8.0 * d * d, byts


def demand(src: dict, ctx: dict):
    """(rows, tokens) a call of the program ``src["module"]``, or None."""
    if src["module"] == "decode":
        steps = grew(ctx, "llmd_tpu:decode_call_steps_total")
        dec = grew(ctx, "llmd_tpu:linear_attn_tokens_total", {"rows": "decode"})
        if not steps or dec is None:
            return None
        live = (dec - (grew(ctx, "llmd_tpu:unified_decode_rows_total") or 0.0)
                ) / steps
        return (live, live) if live > 0 else None
    n = grew(ctx, "llmd_tpu:engine_program_dispatches_total",
             {"program": src["module"]})
    dec = grew(ctx, "llmd_tpu:unified_decode_rows_total") or 0.0
    pre = grew(ctx, "llmd_tpu:linear_attn_tokens_total", {"rows": "prefill"})
    if not n or pre is None or not dec + pre:
        return None
    return (dec + (n if pre else 0.0)) / n, (dec + pre) / n


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]; None where there is nothing to read
    (no such call in the trace, no KDA sizes in the configuration, a program
    without the counters)."""
    tr, conf = ctx.get("trace"), ctx["config"]
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or peaks is None or "kda_lower_bound" not in conf:
        return None
    pat = re.compile(src.get("pattern", PATTERN))
    mod = re.compile(src["module"])
    calls = [o for m, md in tr.get("modules", {}).items() if mod.search(m)
             for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for o in calls)
    secs = sum(o["seconds"] for o in calls)
    per_call = demand(src, ctx)
    if not n_calls or not secs or per_call is None:
        return None
    ops, byts = cost(*per_call, conf["num_attention_heads"], conf["head_dim"],
                     _BYTES[conf.get("state", {}).get("kda_dtype", "float32")])
    return n_calls * least_seconds(ops, byts, peaks) / secs
