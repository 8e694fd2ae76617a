"""Predictor accuracy artifact (VERDICT r4 missing / weak #6).

Serves a multi-regime workload on the engine, trains the GBDT latency
predictor from the engine-emitted traces (the reference's train-on-live-
traffic loop, docs/architecture/advanced/latency-predictor.md), evaluates on
a held-out interleaved slice, and writes ``PREDICTOR_ACCURACY.json`` with
TTFT/TPOT MAPE against the reference's ~5% headline figure
(latency-predictor.md:58). Run on TPU for the comparable number; CPU runs are
CI smoke (absolute latencies jitter with machine load — skill vs the
constant-mean baseline is the portable claim).

Usage: python tools/predictor_accuracy.py [--cpu] [--reps 12] [--model tiny]
                                          [--out PREDICTOR_ACCURACY.json]

Live mode (``--from-metrics URL-or-path``): instead of serving an offline
workload, read a router ``/metrics`` scrape (or a saved exposition file) and
report the decision plane's calibration accounting — the
``llmd_tpu:predictor_calibration_*`` families the live exporter
(obs/decisions.py) folds at every retirement. Same artifact shape, but the
numbers come from real traffic joined against real predictions.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CALIB_LINE = re.compile(
    r"^(llmd_tpu:predictor_calibration_(?:ape|error_ms_sum|error_ms_count))"
    r"\{([^}]*)\}\s+([0-9eE+.-]+)\s*$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def accuracy_from_metrics(text: str) -> dict:
    """Fold a Prometheus exposition into per-(objective, model) calibration:
    rolling APE (the gauge), sample count, and mean signed error (histogram
    sum/count). Returns {"<objective>/<model>": {...}} — empty when the
    calibration families carried no samples."""
    acc: dict[str, dict] = {}
    for line in text.splitlines():
        m = _CALIB_LINE.match(line.strip())
        if m is None:
            continue
        family, rawlabels, value = m.groups()
        labels = {k: v for k, v in _LABEL.findall(rawlabels)}
        key = f"{labels.get('objective', '?')}/{labels.get('model', '')}"
        entry = acc.setdefault(key, {})
        if family.endswith("_ape"):
            entry["rolling_ape"] = float(value)
        elif family.endswith("_sum"):
            entry["signed_error_sum_ms"] = float(value)
        elif family.endswith("_count"):
            entry["n"] = int(float(value))
    out = {}
    for key, entry in acc.items():
        n = entry.get("n", 0)
        if not n and "rolling_ape" not in entry:
            continue
        if n and "signed_error_sum_ms" in entry:
            entry["mean_signed_error_ms"] = round(
                entry.pop("signed_error_sum_ms") / n, 3)
        else:
            entry.pop("signed_error_sum_ms", None)
        out[key] = entry
    return out


def _from_metrics(source: str, out_path: str) -> int:
    if source.startswith("http://") or source.startswith("https://"):
        import urllib.request

        with urllib.request.urlopen(source, timeout=10.0) as resp:
            text = resp.read().decode()
    else:
        with open(source) as f:
            text = f.read()
    calib = accuracy_from_metrics(text)
    artifact = {
        "artifact": "predictor-accuracy",
        "mode": "live-metrics",
        "source": source,
        "calibration": calib,
        "reference_mape": 0.05,  # latency-predictor.md:58
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact))
    if not calib:
        print("WARNING: no predictor calibration samples in the scrape — "
              "is the decision ledger on and the predicted-latency-producer "
              "configured?", file=sys.stderr)
        return 1
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--reps", type=int, default=12,
                    help="workload regime repetitions (more = stabler MAPE)")
    ap.add_argument("--from-metrics", metavar="URL_OR_PATH",
                    help="read live llmd_tpu:predictor_calibration_* "
                         "families from a /metrics URL or a saved exposition "
                         "file instead of serving an offline workload")
    ap.add_argument("--out", default="PREDICTOR_ACCURACY.json")
    args = ap.parse_args()
    if args.from_metrics:
        raise SystemExit(_from_metrics(args.from_metrics, args.out))
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from llmd_tpu.core.request import SamplingParams
    from llmd_tpu.engine import EngineConfig, LLMEngine
    from llmd_tpu.models import get_model_config, resolve_model
    from llmd_tpu.predictor.model import LatencyModel
    from llmd_tpu.predictor.server import sample_from_dict

    cfg, params = resolve_model(args.model)
    eng = LLMEngine(cfg, EngineConfig(page_size=8, num_pages=512,
                                      max_model_len=512, max_batch_size=8,
                                      prefill_chunk=64), params=params)
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    rng = np.random.default_rng(0)
    rid = 0
    t0 = time.monotonic()

    def burst(n_reqs: int, prompt_len: int, shared: bool) -> None:
        nonlocal rid
        base = [int(t) for t in rng.integers(1, cfg.vocab_size - 1, prompt_len)]
        if shared:
            eng.add_request(f"r{rid}", list(base), sp)
            rid += 1
            while eng.has_work():
                eng.step()
        for _ in range(n_reqs):
            toks = list(base) if shared else [
                int(t) for t in rng.integers(1, cfg.vocab_size - 1, prompt_len)]
            eng.add_request(f"r{rid}", toks, sp)
            rid += 1
        while eng.has_work():
            eng.step()

    for _ in range(args.reps):
        burst(1, 32, False)    # idle pod, short prompt
        burst(8, 32, False)    # deep queue → queued TTFT
        burst(4, 128, False)   # long prompts → prefill-bound TTFT
        burst(4, 128, True)    # shared prefix → cache-cut TTFT
    serve_s = time.monotonic() - t0

    rows = eng.drain_latency_trace()
    samples = [sample_from_dict(r) for r in rows]
    train, test = samples[0::2] + samples[1::4], samples[3::4]
    model = LatencyModel()
    if not model.fit(train):
        raise SystemExit(f"too few trace rows to train: {len(train)}")

    def mape(y, pred):
        y, pred = np.asarray(y, float), np.asarray(pred, float)
        return float(np.mean(np.abs(pred - y) / np.maximum(y, 1e-6)))

    preds = model.predict(test)
    y_ttft = [s.ttft_ms for s in test]
    ttft_mape = mape(y_ttft, [p[0] for p in preds])
    ttft_mean_mape = mape(y_ttft, [float(np.mean([s.ttft_ms for s in train]))] * len(test))
    tpot_pairs = [(s.tpot_ms, p[1]) for s, p in zip(test, preds)
                  if s.tpot_ms is not None and p[1] is not None]
    tpot_mape = (mape([a for a, _ in tpot_pairs], [b for _, b in tpot_pairs])
                 if tpot_pairs else None)

    dev = jax.devices()[0]
    artifact = {
        "artifact": "predictor-accuracy",
        "device": getattr(dev, "device_kind", str(dev)),
        "model": args.model,
        "requests_served": rid,
        "serve_seconds": round(serve_s, 1),
        "n_train": len(train),
        "n_test": len(test),
        "ttft_mape": round(ttft_mape, 4),
        "tpot_mape": round(tpot_mape, 4) if tpot_mape is not None else None,
        "mean_baseline_ttft_mape": round(ttft_mean_mape, 4),
        "skill_vs_mean": round(ttft_mean_mape / max(ttft_mape, 1e-9), 2),
        "reference_mape": 0.05,  # latency-predictor.md:58, dedicated serving hw
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact))
    if ttft_mape >= ttft_mean_mape:
        print("WARNING: model shows no skill vs the mean baseline",
              file=sys.stderr)


if __name__ == "__main__":
    main()
