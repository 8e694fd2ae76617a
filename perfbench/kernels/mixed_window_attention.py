"""What a model that mixes full and sliding-window attention layers demands
of decode attention, whatever implements it, as a share of the roofline over
a traced window.

Read from the ``ragged_paged_attention*`` calls inside the fused decode
program, as ``ragged_paged_attention.py`` reads them (one query token a
sequence, so the demand follows from the context lengths alone), with the
calls split by the configuration's ``sliding_window_layout``: of every
``len(layout)`` calls, as many are of window layers as the layout has ones.

One call of a FULL layer must read every distinct cached key and value of the
batch once: rows behind one tenant's system prompt count its tokens once
(``kernels/cached_tokens.py``; the client samples the decoding rows with their
tenants through the capture). One call of a WINDOW layer must read the last
``min(context, window)`` tokens of each sequence: a decode row at position i
sees keys i - window < j <= i and no others. Of a shared prompt only the part
inside a row's window is that layer's to read, so only that part counts as
shared: behind a 6,144-token system prompt a row at 8,000 tokens reads
(3,904, 8,000], of which (3,904, 6,144] is the tenant's, and the tenant's
rows together bring the stretch from their shortest row's window start to
the prompt's end once. Operations follow every row's own tokens in both
kinds. Queries in and outputs out are added to both
(``ragged_paged_attention.cost``).

A context that brings the client's means and no rows (a test's) is read once
a row, the window layers as ``min(S, B * window)``: exact when every
decoding context is at least the window.

The share is the least time the chip could take for that demand over the time
the calls took. An implementation that masks by window and still reads every
page reads low here, and so does one that fetches a shared prompt once a row;
none can read over 100%, since pages are read whole and the demand counts no
token twice and none outside a window.
"""

from __future__ import annotations

import json
import os
import re

from kernels.cached_tokens import decode_means
from kernels.ragged_paged_attention import HERE, cost, least_seconds


def demand_seconds(ctx: dict, conf: dict, peaks: dict):
    """(least seconds of one full layer's call, of one window layer's); None
    where no row was decoding."""
    shape = (conf["num_attention_heads"], conf["num_key_value_heads"],
             conf["head_dim"])
    out = []
    for window in (0, conf["sliding_window_size"]):
        rows = decode_means(ctx, window)
        if not rows:
            return None
        S, U, B = rows
        out.append(least_seconds(*cost(S, B, *shape, unique_ctx=U), peaks))
    return tuple(out)


def roofline(src: dict, ctx: dict):
    """Share of the roofline, in [0, 1]; None where the trace has no such
    call or the configuration no layout."""
    tr, conf = ctx.get("trace"), ctx["config"]
    kind = (ctx.get("device") or {}).get("kind")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f).get(kind)
    layout = conf.get("sliding_window_layout")
    if not tr or peaks is None or not layout:
        return None
    layout = layout[:conf["num_hidden_layers"]]
    pat, mod = re.compile(src["pattern"]), re.compile(src["module"])
    calls = [o for m, md in tr.get("modules", {}).items() if mod.search(m)
             for n, o in md["ops"].items() if pat.search(n)]
    n_calls = sum(o["count"] for o in calls)
    secs = sum(o["seconds"] for o in calls)
    demand = demand_seconds(ctx, conf, peaks)
    if not n_calls or not secs or not demand:
        return None
    full, window = demand
    share = sum(layout) / len(layout)  # of the calls, those of window layers
    return n_calls * ((1 - share) * full + share * window) / secs
