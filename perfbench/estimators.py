"""The arithmetic from what the client saw to the end-to-end metrics.

Kept here, with the benchmark, so that no PR that claims a gain can change how
its gain is counted.
"""

from __future__ import annotations

import math

INF = float("inf")


def window_rate(events: list, start: float, end: float):
    """Tokens per second over the window: every token that arrived inside
    [start, end), over the window's whole length. ``events``: (time, tokens)
    for every streamed chunk. All the work and all the time, so a stall
    anywhere in the window, its edges included, lowers it by what it cost.
    One fused decode call reports for every seat at once, so a window's edge
    cuts a lump of up to seats x k tokens: that is a spread of about one lump
    over the window's tokens from run to run, which the window's length and
    the bound are sized for. Returns (rate, events inside)."""
    inside = [n for t, n in events if start <= t < end]
    return sum(inside) / (end - start), len(inside)


def percentile(values: list, p: float):
    """Nearest-rank percentile over all requests: a failed or unfinished
    request stands at +inf, it is not dropped. None on no samples."""
    if not values:
        return None
    ys = sorted(values)
    return ys[max(0, math.ceil(p / 100.0 * len(ys)) - 1)]


def tpot_ms(first: float, last: float, n_out: int):
    """A request's time per output token after the first: a mean over the
    request, because a fused call of k steps makes its per-token gaps k-1
    zeros and one stall."""
    if n_out < 2:
        return None
    return (last - first) / (n_out - 1) * 1e3


def finite(x, cap: float = 1e9) -> float:
    """A percentile that landed on a failed request is printed as ``cap``:
    JSON has no infinity, and such a run prints ``correct: false`` anyway."""
    return cap if x is None or math.isinf(x) else x
