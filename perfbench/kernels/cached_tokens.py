"""The cached tokens an attention call has to read: every *distinct* one once,
whatever implements the call. The one place the attention rooflines take
their byte demand from (``ragged_paged_attention.py``,
``mixed_window_attention.py``, ``mla_attention.py``).

Rows that stand behind the same document or system prompt hold the same keys
and values at the same positions, so a kernel may fetch them once for all of
them: counted once a row, a kernel that does would read over 100%. What is
shared comes from the traffic's own structure and not from the program's
prefix cache, so the count stays the demand of attention and not the habit of
one implementation. A decoding row is ``(context tokens, tenant, shared)``:
of its context the first ``shared`` tokens are the tenant's (``sessions.
system_prompt`` of the traffic file: a document, a system prompt), the rest
its own; ``tenant`` is None where the mix has no sessions, and such a row
shares nothing. Operations are never counted here: arithmetic is done once a
query-key pair however the keys arrive.

A window layer's row reads the last ``window`` tokens of its context and no
others, so of a shared prefix only the part inside some row's window counts,
once for the rows whose windows hold it.
"""

from __future__ import annotations

from xplane import union_ns


def row_tokens(rows: list, window: int = 0) -> float:
    """Every row's context once a row (``min(context, window)`` of a window
    layer's): what the operations follow, and the bytes of rows that share
    nothing."""
    return float(sum(min(c, window) if window else c for c, _, _ in rows))


def unique_tokens(rows: list, window: int = 0) -> float:
    """Every distinct cached token of the rows once: each tenant's shared
    tokens that some row reads, and every row's own tokens past them. Never
    more than ``row_tokens``."""
    own, spans = 0, {}
    for ctx, tenant, shared in rows:
        start = max(0, ctx - window) if window else 0
        end = min(shared, ctx) if tenant is not None else 0
        own += ctx - max(start, end)
        if end > start:
            spans.setdefault(tenant, []).append((start, end))
    return float(own + sum(union_ns(s) for s in spans.values()))


def decode_means(ctx: dict, window: int = 0):
    """(tokens once a row, distinct tokens, rows decoding), each the mean
    over the samples of decoding rows the client took through the capture
    (``ctx["decode_rows"]``); None where nothing was decoding. A context that
    states the client's means alone (``gen.decode_ctx_tokens_mean``,
    ``gen.decoding_mean``: no rows, so nothing known to be shared) is read
    once a row, a window layer's as ``min(S, B * window)``."""
    samples = ctx.get("decode_rows")
    if samples:
        n = len(samples)
        per_row = sum(row_tokens(r, window) for r in samples) / n
        unique = sum(unique_tokens(r, window) for r in samples) / n
        return (per_row, unique, sum(len(r) for r in samples) / n) \
            if per_row else None
    S, B = ctx["gen"].get("decode_ctx_tokens_mean"), \
        ctx["gen"].get("decoding_mean") or 0.0
    if not S:
        return None
    if window:
        S = min(S, B * window)
    return S, S, B
