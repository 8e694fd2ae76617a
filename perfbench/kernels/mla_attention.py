"""What latent attention (MLA, absorbed or not) demands of a call, whatever
implements it, and a kernel's share of that roofline over a traced window.

One call is one layer's attention over a flat batch. The pool holds one row a
token, ``[c_kv ; k_rope]`` of ``kv_lora_rank + qk_rope_head_dim`` real lanes
(512 + 64 at GLM-4.7-Flash), which is key and value at once:

  bytes      = U * (r + dr) * b            every DISTINCT cached token's real
                                           lanes once a call
             + Q * H * ((r + dr) + r) * b  queries in (latent + rope lanes),
                                           outputs out (the latent-weighted sum)
  operations = 2 * H * ((r + dr) + r) * P  a query-key pair: the score over
                                           r + dr lanes, the weighted sum over r

with U the distinct context tokens of the call's rows (S, every row's context
once a row, less what rows behind one document share: a document's tokens are
the same rows of the pool for every lane that asks about it, so a kernel may
fetch them once for the stacked queries of those lanes;
``kernels/cached_tokens.py``), Q its query tokens, P its query-key pairs
(causal: a row of q queries over kv tokens holds q * kv - q (q - 1) / 2;
arithmetic is done once a pair however the keys arrive, so P is never cut by
sharing), H the heads, b the pool's bytes an element. The pool's lane padding
(576 -> 640), the heads' padding to whole tiles and whatever a kernel reads
twice, a shared document once a row among it, are the implementation's cost,
not the demand, so no reading can pass 100%. The least time is the larger of
bytes over the memory's rate and operations over the bf16 rate: a decode call
(one query a row, P = S) is bound by bytes at 20 heads (38 operations a byte
against the chip's 240, and 38 S / U where rows share: 122 with four lanes a
document); a unified step whose chunk brings some 280 query tokens or more
behind 64 long unshared contexts is bound by operations, and one of some 60
or more where four lanes share a document.

``module`` says which step program's calls are read:

- ``decode``: the fused decode program. One query a row, so the demand follows
  from the contexts alone; S, U and the rows come from the client's samples
  of the decoding rows (``cached_tokens.decode_means``), as
  ``ragged_paged_attention.py`` takes them.
- ``unified``: the unified step. A chunk's share of the causal triangle is
  the program's to know: S, Q and P per dispatch are the growth of
  ``llmd_tpu:program_kv_read_tokens_total``, ``attn_query_tokens_total`` and
  ``attn_query_key_pairs_total`` (program ``unified``) over the growth of
  ``engine_program_dispatches_total``, all over the part of the window that
  the counters cover (the part before the capture; the mix is stationary),
  applied to each of the traced calls. The program's S is once a row, and
  what its rows share is not the program's to say, so U is S times the
  client's U / S over the rows decoding through the capture. That is an
  approximation: a chunk's row is taken to share as the decoding rows do at
  the mean. It stands behind its lane's document as they do (a turn resends
  the document), and with four lanes a document and some 60 of 64 decoding
  that document is nearly always one a decoding row already brings, so the
  row truly adds its own tail alone where the ratio adds U / S of its whole
  context: U is over by some 4k tokens of 350k in ``docs-sessions-closed``,
  a hundredth. Where no decoding row brings its document, U is short by the
  document and the reading low. A program without those counters reads
  nothing.
"""

from __future__ import annotations

import json
import os
import re

import prom
from kernels.cached_tokens import decode_means
from kernels.ragged_paged_attention import HERE, least_seconds

PATTERN = "mla_ragged_paged_attention"


def cost(ctx_tokens: float, queries: float, pairs: float, heads: int,
         rank: int, rope: int, elem_bytes: int = 2, unique_ctx=None) -> tuple:
    """(operations, bytes) of one call for one layer: operations over the
    ``pairs``, bytes over the distinct ``unique_ctx`` of the rows'
    ``ctx_tokens`` (all, where none is named)."""
    unique_ctx = ctx_tokens if unique_ctx is None else unique_ctx
    byts = (unique_ctx * (rank + rope)
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
    tr, conf = ctx.get("trace"), ctx["config"]
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
    rows = decode_means(ctx)
    if src["module"] == "decode":
        S, Q = (rows[0], rows[2]) if rows else (None, None)
        P = S
    else:
        S, Q, P = (_per_dispatch(ctx, "llmd_tpu:" + s, src["module"])
                   for s in ("program_kv_read_tokens_total",
                             "attn_query_tokens_total",
                             "attn_query_key_pairs_total"))
    if not S or Q is None or not P:
        return None
    # of the rows' tokens the share that is distinct: the client's, for the
    # program's own count too (the module's docstring names what that takes)
    distinct = rows[1] / rows[0] if rows else 1.0
    ops, byts = cost(S, Q, P, conf["num_attention_heads"],
                     conf["kv_lora_rank"], conf["qk_rope_head_dim"],
                     unique_ctx=S * distinct)
    return n_calls * least_seconds(ops, byts, peaks) / secs
