"""The demand the attention rooflines divide by
(``perfbench/kernels/cached_tokens.py``): every distinct cached token once a
call for the bytes, every query-key pair for the operations. The kernel that
fetches a shared KV block once for the rows behind it (``ops/mla_attention``,
PR 49) is judged by this count, so tier-1 holds it to hand counts.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# by path and for the import alone: perfbench/ has a tests/ of its own
sys.path.append(os.path.join(ROOT, "perfbench"))
try:
    from kernels import cached_tokens, mla_attention  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

DOC = 16384
TAILS = [40, 700, 1500, 3000]
BEHIND = [(DOC + t, "doc-0", DOC) for t in TAILS]  # four lanes a document


def test_four_rows_behind_one_document_read_it_once():
    once_a_row = cached_tokens.row_tokens(BEHIND)
    assert once_a_row == 4 * DOC + sum(TAILS)
    assert cached_tokens.unique_tokens(BEHIND) == DOC + sum(TAILS)
    # the bytes follow the distinct tokens, the operations every row's pairs
    S, U = once_a_row, cached_tokens.unique_tokens(BEHIND)
    ops, byts = mla_attention.cost(S, 4, S, 20, 512, 64, unique_ctx=U)
    assert byts == (U * 576 + 4 * 20 * 1088) * 2
    assert ops == 2 * 20 * 1088 * S


@pytest.mark.parametrize("rows", [
    [(DOC + t, None, 0) for t in TAILS],  # no sessions: nothing is shared
    [(DOC + t, f"doc-{i}", DOC) for i, t in enumerate(TAILS)],  # one a lane
    [(DOC + t, "doc-0", 0) for t in TAILS],  # a tenant with nothing shared
], ids=["no_tenant", "a_document_each", "an_empty_prefix"])
def test_rows_that_share_nothing_keep_the_old_numbers(rows):
    assert cached_tokens.unique_tokens(rows) == cached_tokens.row_tokens(rows)
    S = cached_tokens.row_tokens(rows)
    assert mla_attention.cost(S, 4, S, 20, 512, 64) == mla_attention.cost(
        S, 4, S, 20, 512, 64, unique_ctx=cached_tokens.unique_tokens(rows))


@pytest.mark.parametrize("rows,window", [
    (BEHIND, 0),
    (BEHIND + [(DOC // 2, "doc-0", DOC), (9, "doc-1", DOC)], 0),  # still inside
    (BEHIND, 4096),  # a window layer: the document lies before every window
    (BEHIND + [(DOC + 10, "doc-1", DOC)], 2048),
    ([], 0),
])
def test_distinct_is_never_over_once_a_row(rows, window):
    assert cached_tokens.unique_tokens(rows, window) <= cached_tokens.row_tokens(
        rows, window)


def test_a_row_that_ends_inside_the_document_shares_what_it_holds():
    rows = [(DOC + 40, "doc-0", DOC), (DOC // 2, "doc-0", DOC)]
    assert cached_tokens.unique_tokens(rows) == DOC + 40
    # the last window of a row past the document holds none of it
    assert cached_tokens.unique_tokens(BEHIND[2:], 1024) == 2 * 1024


def test_the_clients_samples_are_averaged_and_its_means_read_once_a_row():
    ctx = {"decode_rows": [BEHIND, BEHIND[:2]], "gen": {}}
    per_row, unique, n = cached_tokens.decode_means(ctx)
    assert n == 3.0
    assert per_row == (4 * DOC + sum(TAILS) + 2 * DOC + sum(TAILS[:2])) / 2
    assert unique == (DOC + sum(TAILS) + DOC + sum(TAILS[:2])) / 2
    means = {"gen": {"decode_ctx_tokens_mean": 70000.0, "decoding_mean": 4.0}}
    assert cached_tokens.decode_means(means) == (70000.0, 70000.0, 4.0)
    assert cached_tokens.decode_means({"gen": {}}) is None
