#!/usr/bin/env python3
"""Run one cell several times and print each metric's median and spread.

    python3 perfbench/measure.py --workload <cell> --sets 2 [--seeds a,b,...]
                                 [--seconds S] [--out FILE] [-- extra run.py args]

A set is one run per seed, the same seeds in every set. A spread is the
distance between the first and third quartile (``statistics.quantiles(n=4)``)
as a share of the median: what the contract's bound is five times of. The
results of every run go to ``--out`` as JSON lines. Meant to be the command of
one chip call, so that the runs of a cell share a machine.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEEDS = "3000000019,2147483659,1234567891,987654321,42,7"


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", default=SEEDS)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("extra", nargs="*")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    sets: list = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            cmd = [sys.executable, os.path.join(HERE, "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"] + args.extra
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
                assert "metrics" in res
            except Exception:  # noqa: BLE001
                print(f"set {k} seed {seed}: no result (rc={p.returncode})\n"
                      + p.stdout[-1500:] + p.stderr[-1500:], flush=True)
                continue
            row = {n: m["value"] for n, m in res["metrics"].items()}
            notes = {}
            for ln in lines[:-1]:
                try:
                    d = json.loads(ln)
                except ValueError:
                    continue
                # beside the metrics: the client's latencies, the check's
                # worst deficit and what the reference cost
                if d.get("note") == "generator":
                    notes.update({k: d.get(k) for k in (
                        "out_tok_s", "tpot_p50_ms", "tpot_p95_ms",
                        "ttft_p50_ms", "ttft_p95_ms")})
                if d.get("note") == "check":
                    notes.update({k: d.get(k) for k in (
                        "reference_worst_deficit", "reference_s")})
            rec = {"set": k, "seed": seed, "correct": res["correct"],
                   "failed": res["failed"], "attempted": res["attempted"],
                   **row, "notes": notes,
                   "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
            print(json.dumps(rec), flush=True)
            if args.out:
                with open(os.path.join(ROOT, args.out), "a") as f:
                    f.write(json.dumps(rec) + "\n")
            rows.append(rec)
        sets.append(rows)
    names = [n for n in (sets[0][0] if sets and sets[0] else {})
             if n not in ("set", "seed", "correct", "failed", "attempted",
                          "notes", "memory_peak_bytes")]
    for n in names:
        per = []
        for rows in sets:
            vs = [r[n] for r in rows if r.get(n) is not None]
            if len(vs) >= 2:
                per.append((statistics.median(vs), spread(vs)))
        print(json.dumps({"metric": n,
                          "medians": [round(m, 4) for m, _ in per],
                          "spreads": [round(s, 4) for _, s in per]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
