#!/usr/bin/env python3
"""MoE dispatch CI gate (stage ``moe-check``, ``make moe``).

Two tiny-moe CPU engines run the same greedy workload — one on the legacy
dense one-hot einsum dispatch (capacity-bounded, silently drops tokens past
``moe_capacity_factor``) and one on the token-sorted drop-free path
(ops/moe_dispatch.py) — then the dispatch plane's standing invariants are
asserted end to end:

1. ``moe_dispatch=auto`` resolves to the sorted path on a MoE model (the
   serving default actually selects the new dispatch)
2. greedy outputs are parity-matched between the two paths at matched routing
   decisions (einsum run at a capacity factor generous enough to keep every
   routed token — the sorted rewrite changes the schedule, not the math)
3. the sorted engine records ZERO dropped tokens — in ``EngineStats`` and in
   the scraped ``llmd_tpu:moe_dropped_tokens_total{path="sorted"}`` series
   (drop-free by construction; a non-zero series is a dispatch bug)
4. the einsum engine at tiny-moe's default capacity factor provably drops
   tokens on this workload (> 0 — the gap the sorted path closes), and its
   counter matches the engine ledger exactly

Run directly (CI) or via ``make moe``. Exit 0 = all checks pass.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from llmd_tpu.core.request import SamplingParams  # noqa: E402
from llmd_tpu.engine.config import EngineConfig  # noqa: E402
from llmd_tpu.engine.engine import LLMEngine  # noqa: E402
from llmd_tpu.models import get_model_config  # noqa: E402

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9]]
BASE = dict(page_size=8, num_pages=64, max_model_len=128, max_batch_size=4)


def _serve(moe_dispatch: str,
           capacity_factor: float | None = None) -> tuple[LLMEngine,
                                                          list[list[int]]]:
    cfg = get_model_config("tiny-moe")
    if capacity_factor is not None:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=capacity_factor)
    eng = LLMEngine(cfg, EngineConfig(moe_dispatch=moe_dispatch, **BASE),
                    seed=7)
    for i, p in enumerate(PROMPTS):
        eng.add_request(f"m-{i}", list(p),
                        SamplingParams(max_tokens=8, temperature=0.0))
    done: dict[str, list[int]] = {}
    while eng.has_work():
        for r in eng.step():
            done.setdefault(r.request_id, []).extend(r.new_token_ids)
    return eng, [done[f"m-{i}"] for i in range(len(PROMPTS))]


def _scrape_dropped(eng: LLMEngine) -> dict[str, float]:
    """path -> value of llmd_tpu:moe_dropped_tokens_total."""
    out: dict[str, float] = {}
    for name, labels, value in eng.metrics.registry.collect():
        if name != "llmd_tpu:moe_dropped_tokens_total":
            continue
        for part in labels.strip("{}").split(","):
            k, _, v = part.partition("=")
            if k == "path":
                out[v.strip('"')] = value
    return out


def main() -> int:
    t_start = time.monotonic()

    eng_s, out_s = _serve("auto")
    # (1) auto must resolve to the sorted path on a MoE model
    assert eng_s.moe_dispatch == "sorted", (
        "moe_dispatch=auto did not select the sorted path",
        eng_s.moe_dispatch, getattr(eng_s, "moe_dispatch_fallback_reason", None))
    assert eng_s.stats.moe_dispatch == "sorted", eng_s.stats.moe_dispatch
    print("moe-check: auto selected the sorted dispatch path")

    # (2) greedy parity at matched routing decisions: einsum gets a capacity
    # factor generous enough (C >= T*k) that it keeps every routed token, so
    # any divergence is dispatch math, not capacity drops
    eng_p, out_p = _serve("einsum", capacity_factor=8.0)
    assert eng_p.moe_dispatch == "einsum", eng_p.moe_dispatch
    assert eng_p.stats.moe_dropped_tokens == 0, (
        "parity reference still dropped tokens at capacity_factor=8.0",
        eng_p.stats.moe_dropped_tokens)
    assert out_s == out_p, ("sorted vs einsum greedy outputs diverged",
                            out_s, out_p)
    n_tok = sum(len(o) for o in out_s)
    print(f"moe-check: greedy outputs parity-matched across both paths "
          f"({n_tok} tokens)")

    # (3) sorted path is drop-free: engine ledger and scraped counter
    assert eng_s.stats.moe_dropped_tokens == 0, eng_s.stats.moe_dropped_tokens
    scraped_s = _scrape_dropped(eng_s)
    assert scraped_s.get("sorted", 0.0) == 0.0, scraped_s
    print("moe-check: sorted path dropped 0 tokens (stats + counter)")

    # (4) capacity-bounded einsum at the default factor provably drops on
    # this workload, and the counter matches the engine ledger exactly
    eng_e, _ = _serve("einsum")
    assert eng_e.moe_dispatch == "einsum", eng_e.moe_dispatch
    dropped = eng_e.stats.moe_dropped_tokens
    assert dropped > 0, (
        "einsum reference dropped nothing — the workload no longer "
        "exercises the capacity bound the sorted path removes")
    scraped_e = _scrape_dropped(eng_e)
    assert scraped_e.get("einsum", 0.0) == float(dropped), (scraped_e, dropped)
    print(f"moe-check: einsum reference dropped {dropped} tokens at "
          f"capacity; counter == ledger")

    print(f"moe-check: ALL OK ({time.monotonic() - t_start:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
