"""What a Mamba layer's recurrence demands of a decode step, whatever
implements it, as bytes and operations from shapes, and the selective-scan
kernel's share of the roofline over a traced window (``selective_scan*`` in a
device trace).

Counted for the executions inside the fused decode program only: there every
sequence brings one token, so what a call must do follows from the number
decoding alone. (Inside the unified step the same kernel takes prefill
chunks, whose lengths the client cannot know.)

One call is one Mamba layer over a batch of B decoding sequences, d_inner Di,
d_state N, conv taps K:
  bytes      = B * 2 * N * Di * s          each sequence's SSM state read once
                                           and written once, s bytes an element
             + B * 2 * (K - 1) * Di * w    and its conv window likewise
             + B * (3 * Di + 2 * N) * 4    x, delta, z and B, C in (float32)
             + B * Di * 4                  y out
  operations = B * N * Di * 7              exp(delta A); three products and an
                                           add for h; a product and an add for y
``s`` and ``w`` are the types the configuration's ``state`` block states
(float32, bfloat16). The conv window and the gate ``z`` are counted although
the program handles them outside the kernel: the demand is the model's, the
time is the kernel's alone, so the share reads low by what it leaves to XLA,
never high. The least time is the larger of bytes over the chip's memory
bandwidth and operations over its float32 vector rate; the published peaks
give no vector rate, so the bf16 matrix rate stands in (it is far above
anything the vector unit reaches: the bound is the bytes').

B comes from the client (``gen.decoding_mean``, the mean number of requests
decoding over the traced window). The program's call reads and writes every
seat's slot it is given, live or not, so an idle seat costs the kernel time
and adds nothing to the demand: under 100% by construction.
"""

from __future__ import annotations

import json
import os
import re

from kernels.ragged_paged_attention import HERE, least_seconds

_BYTES = {"float32": 4, "bfloat16": 2}


def cost(batch: float, d_inner: int, d_state: int, d_conv: int,
         state_bytes: int = 4, conv_bytes: int = 2) -> tuple:
    """(operations, bytes) of one call: one Mamba layer, one decode step."""
    byts = batch * (2 * d_state * d_inner * state_bytes
                    + 2 * (d_conv - 1) * d_inner * conv_bytes
                    + (3 * d_inner + 2 * d_state) * 4 + d_inner * 4)
    return 7.0 * batch * d_state * d_inner, byts


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]; None where the trace has no such
    call (a program without the kernel) or the configuration no Mamba sizes."""
    tr, gen, conf = ctx.get("trace"), ctx["gen"], ctx["config"]
    B = gen.get("decoding_mean")
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or not B or peaks is None or "mamba_d_state" not in conf:
        return None
    pat, mod = re.compile(src["pattern"]), re.compile(src["module"])
    calls = [o for m, md in tr.get("modules", {}).items() if mod.search(m)
             for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for o in calls)
    secs = sum(o["seconds"] for o in calls)
    if not n_calls or not secs:
        return None
    state = conf.get("state", {})
    ops, byts = cost(B, conf["mamba_expand"] * conf["hidden_size"],
                     conf["mamba_d_state"], conf["mamba_d_conv"],
                     _BYTES[state.get("ssm_dtype", "float32")],
                     _BYTES[state.get("conv_dtype", "bfloat16")])
    return n_calls * least_seconds(ops, byts, peaks) / secs
