"""What a Mamba-2 layer's recurrence demands of a call, whatever implements
it, as bytes and operations from shapes, and the ``mamba2_ssd`` kernel's share
of the roofline over a traced window (``mamba2_ssd*`` in a device trace).

One call is one Mamba-2 layer over R live rows that bring T tokens in all, H
heads of P channels, G groups of B and C of N entries, a state [P, N] a head:
  bytes      = R * 2 * H * P * N * s       each row's state read once and
                                           written once, s bytes an element
             + T * (2 * H * P + 2 * G * N) * w   x, z and B, C in (the
                                           weights' type)
             + T * H * 4                   dt in (float32)
             + T * H * P * 4               y out (float32)
  operations = T * H * P * N * 6           a token: the decay times the state,
                                           the outer product and its add, the
                                           product with C and its sum; one more
                                           for the exponentials
``s`` is the type the configuration's ``state`` block states. The operations
are the recurrence's, token by token: a blocked form spends more of them (its
``C B^T`` and decay matrices are quadratic in the block) to run on the matrix
unit, and what it spends beyond these is the implementation's, not the
demand's. The gate ``z`` is counted although the program applies it outside
the kernel: the demand is the model's, the time the kernel's alone, so the
share reads low by what it leaves to XLA, never high. The least time is the
larger of bytes over the chip's memory bandwidth and operations over its
bf16 matrix rate; at these sizes the bytes bound both programs (a row's state
is 2.1 MB; a token's operations 3.1 M).

R and T of the unified step (``module: unified``; in the cell a prompt is
prefilling in nearly every step of the window, so no fused decode call runs
there and that program has no metric: a traced line would lack it): per
dispatch, from the program's counters over the part of the window before the
capture (the mix is stationary), decode rows
``llmd_tpu:unified_decode_rows_total`` (one token each) and chunk tokens
``llmd_tpu:ssm_scan_tokens_total{program="unified", rows="chunk"}`` over
``engine_program_dispatches_total{program="unified"}``; the chunk tokens of a
step are counted as ONE row (a step seldom holds two chunks: with two the
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

from kernels.lightning_attention import grew
from kernels.ragged_paged_attention import HERE, least_seconds

PATTERN = "mamba2_ssd"
_BYTES = {"float32": 4, "bfloat16": 2}


def cost(rows: float, tokens: float, heads: int, head_dim: int, groups: int,
         d_state: int, state_bytes: int = 4, elem_bytes: int = 2) -> tuple:
    """(operations, bytes) of one call of one layer over ``rows`` live rows
    that bring ``tokens`` tokens in all."""
    hp = heads * head_dim
    byts = (rows * 2 * hp * d_state * state_bytes
            + tokens * ((2 * hp + 2 * groups * d_state) * elem_bytes
                        + heads * 4 + hp * 4))
    return 6.0 * tokens * hp * d_state, byts


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]; None where there is nothing to read
    (no such call in the trace, no Mamba-2 sizes in the configuration, a
    program without the counters)."""
    tr, conf = ctx.get("trace"), ctx["config"]
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or peaks is None or "mamba_num_heads" not in conf:
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
    pre = grew(ctx, "llmd_tpu:ssm_scan_tokens_total",
               {"program": src["module"], "rows": "chunk"})
    if not n or pre is None:
        return None
    rows = ((dec or 0.0) + (1.0 if pre else 0.0) * n) / n
    tokens = ((dec or 0.0) + pre) / n
    if not rows:
        return None
    ops, byts = cost(rows, tokens, conf["mamba_num_heads"],
                     conf["mamba_head_dim"], conf["n_groups"],
                     conf["ssm_state_size"],
                     _BYTES[conf.get("state", {}).get("ssm_dtype", "float32")],
                     _BYTES[conf["weights"]["dtype"]])
    return n_calls * least_seconds(ops, byts, peaks) / secs
