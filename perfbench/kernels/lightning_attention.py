"""What a lightning (linear-attention) layer's recurrence demands of a call,
whatever implements it, as bytes and operations from shapes, and the
``lightning_attention`` kernel's share of the roofline over a traced window
(``lightning_attention*`` in a device trace).

One call is one lightning layer over R rows that bring T tokens in all, H
heads of d lanes, a matrix state [d, d] a head:
  bytes      = R * 2 * H * d * d * s       each row's state read once and
                                           written once, s bytes an element
             + T * 3 * H * d * w           q, k, v in (the weights' type)
             + T * H * d * 4               o out (float32)
  operations = H * sum over rows (2 * C * C * d * 2 + 2 * C * d * d * 2)
                                           a row of C tokens: Q K^T and (.) V,
                                           Q S and K^T V
``s`` is the type the configuration's ``state`` block states. The least time
is the larger of bytes over the chip's memory bandwidth and operations over
its bf16 matrix rate.

R, T and the rows' lengths of the unified step (the fused decode program
runs in no cell's window while a prompt waits, so it has no metric): per
dispatch, from the program's counters over the part of the window they cover
(the part before the capture; the mix is stationary): decode rows
``llmd_tpu:unified_decode_rows_total`` (C = 1 each) and prefill tokens
``llmd_tpu:linear_attn_tokens_total{rows="prefill"}``, over
``engine_program_dispatches_total{program="unified"}``. The prefill tokens of
a step are counted as ONE row (a step seldom holds two chunks: with two, the
demand has one more state and reads low, never high). A program without those
counters reads nothing.

The kernel reads and writes every row's slot it is given, live or not, and
pads a row's last block: that is the implementation's cost and adds nothing
to the demand, so no reading can pass 100%.
"""

from __future__ import annotations

import json
import os
import re

import prom
from kernels.ragged_paged_attention import HERE, least_seconds

PATTERN = "lightning_attention"
_BYTES = {"float32": 4, "bfloat16": 2}


def cost(rows: list, heads: int, d: int, state_bytes: int = 4,
         elem_bytes: int = 2) -> tuple:
    """(operations, bytes) of one call of one layer; ``rows`` are (count,
    tokens a row) pairs."""
    n_rows = sum(n for n, _ in rows)
    tokens = sum(n * c for n, c in rows)
    byts = (n_rows * 2 * heads * d * d * state_bytes
            + tokens * heads * d * (3 * elem_bytes + 4))
    ops = heads * sum(n * (4.0 * c * c * d + 4.0 * c * d * d) for n, c in rows)
    return ops, byts


def grew(ctx: dict, name: str, labels=None):
    a = prom.total(ctx["before"].get("engine", []), name, labels)
    b = prom.total(ctx["after"].get("engine", []), name, labels)
    return None if b is None else b - (a or 0.0)


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]; None where there is nothing to read
    (no such call in the trace, no lightning sizes in the configuration, a
    program without the counters)."""
    tr, conf = ctx.get("trace"), ctx["config"]
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or peaks is None or "lightning_nh" not in conf:
        return None
    pat = re.compile(src.get("pattern", PATTERN))
    mod = re.compile(src["module"])
    calls = [o for m, md in tr.get("modules", {}).items() if mod.search(m)
             for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for o in calls)
    secs = sum(o["seconds"] for o in calls)
    if not n_calls or not secs:
        return None
    n = grew(ctx, "llmd_tpu:engine_program_dispatches_total",
              {"program": src["module"]})
    dec = grew(ctx, "llmd_tpu:unified_decode_rows_total")
    pre = grew(ctx, "llmd_tpu:linear_attn_tokens_total", {"rows": "prefill"})
    rows = None if not n or pre is None else (
        [((dec or 0.0) / n, 1)] + ([(1, pre / n)] if pre else []))
    if not rows:
        return None
    ops, byts = cost(rows, conf["lightning_nh"], conf["lightning_head_dim"],
                     _BYTES[conf.get("state", {}).get("linear_dtype",
                                                      "float32")])
    return n_calls * least_seconds(ops, byts, peaks) / secs
