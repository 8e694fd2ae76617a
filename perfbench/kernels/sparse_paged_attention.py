"""What block-sparse attention over a paged pool demands of a unified step,
whatever implements it, as bytes and operations from the program's counters,
and the attention kernel's share of the roofline over a traced window
(``ragged_paged_attention*`` in a device trace of a model whose attention
layers select: every call of the kernel there is a sparse layer's, today's
call for the queries below ``dense_len`` and the selected tables' for the
rest, a KV head each).

One attention layer, one step, H query heads over Hk KV heads of d lanes, e
bytes an element:
  operations  4 * H * d a (query, key) pair the rule asks for: a query below
              ``dense_len`` the keys it sees, a query past it the tokens of
              its selected blocks (``llmd_tpu:sparse_attn_qk_pairs_total``)
  bytes       K and V of a decode row's table once (``sparse_decode_kv_tokens_
              total{tokens="held"}``: a row past ``dense_len`` its selected
              tokens) and of a chunk row's context once (``attn_kv_tokens_
              total{layers="full"}`` less the decode rows' ``{tokens=
              "context"}``: a chunk's queries past ``dense_len`` select
              differently, together nearly every block), 2 * Hk * d * e a
              token; every query in and its output out, 2 * H * d * e
The compressed keys a selection reads are left out (they are read by the
selection, not by the kernel), so the demand is low and no reading can pass
100%. The tokens are the program's count, once a row: exact for traffic that
shares nothing (``longdoc-closed``), where every cached token is distinct. A
cell whose rows stand behind one document would have to count a document
once first (``kernels/cached_tokens.py``, as the other attention rooflines
do), or a kernel that fetched it once could read over 100%. The least time is the larger of bytes over the chip's memory bandwidth
and operations over its bf16 matrix rate.

Per dispatch of the module, from the counters over the part of the window
they cover (the part before the capture; the mix is stationary). A layer's
step is 2 * Hk calls of the kernel, so the traced calls over 2 * Hk are the
layer-steps the demand is counted for. A program without those counters
reads nothing.
"""

from __future__ import annotations

import json
import os
import re

from kernels.lightning_attention import grew
from kernels.ragged_paged_attention import HERE, least_seconds

PATTERN = "ragged_paged_attention"


def cost(pairs: float, kv_tokens: float, queries: float, heads: int,
         kv_heads: int, d: int, elem_bytes: int = 2) -> tuple:
    """(operations, bytes) of one layer's step: ``pairs`` (query, key)
    pairs, ``kv_tokens`` tokens whose K and V are read (every KV head's ``d``
    lanes), ``queries`` in and out."""
    byts = (kv_tokens * 2 * kv_heads * d + queries * 2 * heads * d) * elem_bytes
    return 4.0 * heads * d * pairs, byts


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]; None where there is nothing to
    read."""
    tr, conf = ctx.get("trace"), ctx["config"]
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or peaks is None or "sparse" not in conf:
        return None
    prog = {"program": src["module"]}
    pat = re.compile(src.get("pattern", PATTERN))
    mod = re.compile(src["module"])
    calls = [o for m, md in tr.get("modules", {}).items() if mod.search(m)
             for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for o in calls)
    secs = sum(o["seconds"] for o in calls)
    n = grew(ctx, "llmd_tpu:engine_program_dispatches_total", prog)
    pairs = grew(ctx, "llmd_tpu:sparse_attn_qk_pairs_total", prog)
    full = grew(ctx, "llmd_tpu:attn_kv_tokens_total", dict(prog, layers="full"))
    queries = grew(ctx, "llmd_tpu:attn_query_tokens_total", prog)
    held = grew(ctx, "llmd_tpu:sparse_decode_kv_tokens_total",
                 {"tokens": "held"}) or 0.0
    seen = grew(ctx, "llmd_tpu:sparse_decode_kv_tokens_total",
                 {"tokens": "context"}) or 0.0
    if not n_calls or not secs or not n or not pairs or not full or not queries:
        return None
    hk = conf["num_key_value_heads"]
    ops, byts = cost(pairs / n, (max(0.0, full - seen) + held) / n,
                     queries / n, conf["num_attention_heads"], hk,
                     conf["head_dim"])
    return n_calls / (2 * hk) * least_seconds(ops, byts, peaks) / secs
