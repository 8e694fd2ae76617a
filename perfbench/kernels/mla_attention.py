"""What latent attention (MLA, absorbed or not) demands of a call, whatever
implements it, and a kernel's share of that roofline over a traced window.

One call is one layer's attention over a flat batch. The pool holds one row a
token, ``[c_kv ; k_rope]`` of ``kv_lora_rank + qk_rope_head_dim`` real lanes
(512 + 64 at GLM-4.7-Flash), which is key and value at once:

  bytes      = S * (r + dr) * b            every cached token's real lanes once
             + Q * H * ((r + dr) + r) * b  queries in (latent + rope lanes),
                                           outputs out (the latent-weighted sum)
  operations = 2 * H * ((r + dr) + r) * P  a query-key pair: the score over
                                           r + dr lanes, the weighted sum over r

with S the context tokens of the call's rows, Q its query tokens, P its
query-key pairs (causal: a row of q queries over kv tokens holds q * kv -
q (q - 1) / 2), H the heads, b the pool's bytes an element. The pool's lane
padding (576 -> 640), the heads' padding to whole tiles and whatever a kernel
reads twice are the implementation's cost, not the demand, so no reading can
pass 100%. The least time is the larger of bytes over the memory's rate and
operations over the bf16 rate: a decode call (one query a row, P = S) is bound
by bytes at 20 heads (38 operations a byte against the chip's 240); a unified
step whose chunk brings some 280 query tokens or more behind 64 long contexts
is bound by operations.

``module`` says which step program's calls are read:

- ``decode``: the fused decode program. One query a row, so the demand follows
  from the contexts alone; S and the rows come from the client
  (``gen.decode_ctx_tokens_mean``, ``gen.decoding_mean``), as
  ``ragged_paged_attention.py`` takes them.
- ``unified``: the unified step. A chunk's share of the causal triangle is
  the program's to know: S, Q and P per dispatch are the growth of
  ``llmd_tpu:program_kv_read_tokens_total``, ``attn_query_tokens_total`` and
  ``attn_query_key_pairs_total`` (program ``unified``) over the growth of
  ``engine_program_dispatches_total``, all over the part of the window that
  the counters cover (the part before the capture; the mix is stationary),
  applied to each of the traced calls. A program without those counters
  reads nothing.
"""

from __future__ import annotations

import json
import os
import re

import prom
from kernels.ragged_paged_attention import HERE, least_seconds

PATTERN = "mla_ragged_paged_attention"


def cost(ctx_tokens: float, queries: float, pairs: float, heads: int,
         rank: int, rope: int, elem_bytes: int = 2) -> tuple:
    """(operations, bytes) of one call for one layer."""
    byts = (ctx_tokens * (rank + rope)
            + queries * heads * (2 * rank + rope)) * elem_bytes
    return 2.0 * heads * (2 * rank + rope) * pairs, byts


def _per_dispatch(ctx: dict, series: str, program: str):
    def grew(name, labels):
        a = prom.total(ctx["before"].get("engine", []), name, labels)
        b = prom.total(ctx["after"].get("engine", []), name, labels)
        return None if b is None else b - (a or 0.0)

    n = grew("llmd_tpu:engine_program_dispatches_total", {"program": program})
    v = grew(series, {"program": program})
    return None if v is None or not n else v / n


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]: the least time the traced calls of
    the named step program could have taken over the time they took; None
    where there is nothing to read."""
    tr, gen, conf = ctx.get("trace"), ctx["gen"], ctx["config"]
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    if not tr or peaks is None or "kv_lora_rank" not in conf:
        return None
    pat = re.compile(src.get("pattern", PATTERN))
    mod = re.compile(src["module"])
    calls = [o for m, md in tr.get("modules", {}).items() if mod.search(m)
             for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for o in calls)
    secs = sum(o["seconds"] for o in calls)
    if not n_calls or not secs:
        return None
    if src["module"] == "decode":
        S, Q = gen.get("decode_ctx_tokens_mean"), gen.get("decoding_mean")
        P = S
    else:
        S, Q, P = (_per_dispatch(ctx, "llmd_tpu:" + s, src["module"])
                   for s in ("program_kv_read_tokens_total",
                             "attn_query_tokens_total",
                             "attn_query_key_pairs_total"))
    if not S or Q is None or not P:
        return None
    ops, byts = cost(S, Q, P, conf["num_attention_heads"],
                     conf["kv_lora_rank"], conf["qk_rope_head_dim"])
    return n_calls * least_seconds(ops, byts, peaks) / secs
