#!/usr/bin/env python3
"""Utilization-plane CI gate (stage ``util-check``, ``make util``).

One tiny CPU engine, two generate rounds (warmup + steady state), then the
utilization attribution plane's standing invariants are asserted end to end:

1. per-program goodput fractions sum to 1 +- 1e-6 (the sum-to-capacity
   construction of obs/costmodel.py actually holds through the live engine)
2. padding efficiency lands in (0, 1] for every program that dispatched
3. the MFU/MBU families are exposed through /metrics on the null-peak path
   (CPU has no peak-table entry: TYPE headers present, no samples — and the
   achieved-FLOP/s / bytes/s gauges DO carry samples)
4. the recompile counter stays flat across the steady-state round: every
   compiled program was built in warmup, so a delta is a recompile storm
5. ledger totals and the scraped ``llmd_tpu:goodput_tokens_total`` counters
   agree exactly, and a measured window's delta accounting reproduces the
   counter deltas token for token

Run directly (CI) or via ``make util``. Exit 0 = all checks pass.
"""

from __future__ import annotations

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
from llmd_tpu.models.config import ModelConfig  # noqa: E402
from llmd_tpu.obs.costmodel import GOODPUT_KINDS  # noqa: E402


def _run(eng: LLMEngine, n: int, salt: int) -> None:
    for i in range(n):
        eng.add_request(f"u{salt}-{i}", list(range(1, 24 + i)),
                        SamplingParams(max_tokens=10, temperature=0.0))
    while eng.has_work():
        eng.step()


def _scrape_goodput(eng: LLMEngine) -> dict:
    """program -> kind -> value from the live registry counters."""
    out: dict = {}
    for name, labels, value in eng.metrics.registry.collect():
        if name != "llmd_tpu:goodput_tokens_total":
            continue
        prog = _label(labels, "program")
        kind = _label(labels, "kind")
        out.setdefault(prog, {})[kind] = value
    return out


def _label(rendered: str, key: str) -> str:
    # rendered labels look like {program="decode",kind="committed"}
    for part in rendered.strip("{}").split(","):
        k, _, v = part.partition("=")
        if k == key:
            return v.strip('"')
    raise AssertionError(f"label {key} not in {rendered}")


def main() -> int:
    t_start = time.monotonic()
    cfg = ModelConfig()
    eng = LLMEngine(cfg, EngineConfig(
        page_size=16, num_pages=96, max_model_len=256, max_batch_size=4,
        prefill_chunk=32, decode_steps=4, max_num_batched_tokens=64))
    assert eng.util is not None, (
        "LLMD_UTIL_LEDGER unexpectedly off — the gate must run with the "
        "ledger enabled")

    _run(eng, 3, salt=1)  # warmup: compiles every program this workload uses
    compiles_warm = eng.util.compiles()
    assert compiles_warm, "no program compiles recorded during warmup"
    base_totals = eng.util.totals()
    base_scrape = _scrape_goodput(eng)

    _run(eng, 4, salt=2)  # steady state: same shapes, zero fresh compiles

    # (1) fractions sum to 1 per program
    for prog in eng.util.programs():
        fr = eng.util.fractions(prog)
        s = sum(fr.values())
        assert abs(s - 1.0) <= 1e-6, (prog, fr, s)
        # (2) padding efficiency in (0, 1]
        pe = eng.util.padding_efficiency(prog)
        assert pe is not None and 0.0 < pe <= 1.0, (prog, pe)
    print(f"util-check: goodput fractions sum to 1 across "
          f"{len(eng.util.programs())} programs; padding efficiency in (0,1]")

    # (3) families exposed on the null-peak path
    expo = eng.metrics.registry.expose()
    for fam in ("llmd_tpu:program_mfu", "llmd_tpu:program_mbu"):
        assert f"# TYPE {fam} gauge" in expo, f"{fam} family not declared"
        assert not any(ln.startswith(fam + "{") for ln in expo.splitlines()), (
            f"{fam} exported samples on CPU — null peaks must mean no series")
    for fam in ("llmd_tpu:program_flops_per_second",
                "llmd_tpu:program_bytes_per_second"):
        assert any(ln.startswith(fam + "{") for ln in expo.splitlines()), (
            f"{fam} carried no samples")
    print("util-check: MFU/MBU families declared with null peaks; "
          "achieved-rate gauges carry samples")

    # (4) recompile counter flat across steady state
    compiles_now = eng.util.compiles()
    assert compiles_now == compiles_warm, (
        "recompiles during steady-state decode", compiles_warm, compiles_now)
    print(f"util-check: compile counts flat across steady state "
          f"({compiles_now})")

    # (5) ledger == /metrics, exactly; bench-style deltas reproduce them
    totals = eng.util.totals()
    scraped = _scrape_goodput(eng)
    for prog, tk in totals.items():
        for kind, v in tk.items():
            got = scraped.get(prog, {}).get(kind, 0.0)
            if v == 0 and kind not in scraped.get(prog, {}):
                continue  # zero classes never create counter children
            assert got == v, (prog, kind, v, got)
    bench_delta = {k: 0 for k in GOODPUT_KINDS}
    for prog, tk in totals.items():
        base = base_totals.get(prog, {})
        for kind, v in tk.items():
            bench_delta[kind] += v - base.get(kind, 0)
    scrape_delta = {k: 0.0 for k in GOODPUT_KINDS}
    for prog, tk in scraped.items():
        base = base_scrape.get(prog, {})
        for kind, v in tk.items():
            scrape_delta[kind] += v - base.get(kind, 0.0)
    assert {k: float(v) for k, v in bench_delta.items()} == scrape_delta, (
        bench_delta, scrape_delta)
    print(f"util-check: ledger == /metrics exactly; window deltas match "
          f"token for token ({bench_delta})")

    print(f"util-check: ALL OK ({time.monotonic() - t_start:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
