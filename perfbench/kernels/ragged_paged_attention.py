"""Operations and bytes of the ragged paged-attention kernel
(``ragged_paged_attention_kernel*`` in a device trace), from shapes, and its
share of the roofline over a traced window.

Counted for the executions inside the fused decode program only: there every
sequence brings one query token, so what a call must do follows from the
context lengths alone. (Inside the unified step the same kernel takes prefill
chunks, whose share of the causal triangle the client cannot know.)

One call, one layer, a batch whose contexts sum to S tokens of which U are
distinct, H query heads and Hk KV heads of size d, cache elements of b bytes:
  bytes      = U * 2 * Hk * d * b      every DISTINCT cached key and value
                                       once a call
             + 2 * B * H * d * b       queries in, outputs out
  operations = 4 * H * d * S           q.k and p.v, a multiply-add as 2, once
                                       a row however the keys arrive
The least time the chip could take is the larger of bytes over its memory
bandwidth and operations over its bf16 rate; decode attention is bound by
bytes (an intensity of H/Hk operations a byte: 6 for 12/2 heads, 4 for 32/8;
S / U times that where rows share a prompt).

S, U and B come from the client, which knows every request's prompt, the
tenant it stands behind and how many tokens it has received: the harness
samples the rows that are decoding a few times a second through the traced
window (``kernels/cached_tokens.py``: rows behind one tenant's document or
system prompt count its tokens once; traffic without sessions shares nothing,
U = S). Tokens arrive in lumps of k, so a context is known to within k
tokens: under 2% of the contexts here.
"""

from __future__ import annotations

import json
import os
import re

from kernels.cached_tokens import decode_means

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cost(ctx_tokens: float, batch: float, heads: int, kv_heads: int,
         head_dim: int, elem_bytes: int = 2, unique_ctx=None) -> tuple:
    """(operations, bytes) of one call of the kernel for one layer:
    operations over every row's ``ctx_tokens``, bytes over the distinct
    ``unique_ctx`` of them (all, where none is named)."""
    unique_ctx = ctx_tokens if unique_ctx is None else unique_ctx
    byts = (unique_ctx * 2 * kv_heads * head_dim * elem_bytes
            + 2 * batch * heads * head_dim * elem_bytes)
    ops = 4.0 * heads * head_dim * ctx_tokens
    return ops, byts


def least_seconds(ops: float, byts: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops"], byts / peaks["hbm_bytes_per_s"])


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]: the least time the traced decode
    calls could have taken over the time they took."""
    tr, conf = ctx.get("trace"), ctx["config"]
    rows = decode_means(ctx)
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or not rows or peaks is None:
        return None
    pat, mod = re.compile(src["pattern"]), re.compile(src["module"])
    calls = [(n, o) for m, md in tr.get("modules", {}).items()
             if mod.search(m) for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for _, o in calls)
    secs = sum(o["seconds"] for _, o in calls)
    if not n_calls or not secs:
        return None
    S, U, B = rows
    ops, byts = cost(S, B, conf["num_attention_heads"],
                     conf["num_key_value_heads"], conf["head_dim"],
                     unique_ctx=U)
    return n_calls * least_seconds(ops, byts, peaks) / secs
