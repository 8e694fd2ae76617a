"""Real-replica warm-start probe: cold vs warm engine relaunch against one
persistent JAX compilation cache.

The pool controller's warm-start path (pool/controller.py, `pool_warm_start`
flight event) points a replica relaunch at a snapshot's compilation cache so
the engine's jitted programs deserialize instead of re-tracing. This probe
measures what that actually buys on a real replica: it launches the SAME
engine build twice in throwaway subprocesses sharing one
``jax_compilation_cache_dir`` — the first (cold) populates the cache, the
second (warm) is the relaunch the controller performs — and reports
ready-time (engine build + first compile-dominated generate) for both.

Prints ONE JSON line:
``{"metric": "warm_start_speedup", "value": <cold_ready/warm_ready>, ...}``
with the full cold/warm phase rows as provenance. A child that fails or
times out fails the probe (non-zero exit). The parent never imports JAX —
each child owns the device for its launch — and hands the children their
cache through ``JAX_COMPILATION_CACHE_DIR``, the one way a cache is placed
(llmd_tpu/jax_init.py).

Usage: python tools/warm_start_probe.py [--model tiny] [--cpu]
                                        [--cache-dir DIR] [--keep-cache]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time


def _child(args: argparse.Namespace) -> None:
    """One replica launch: build the engine, run the first generate, report
    phase walls. Runs in its own process so the in-memory jit cache of a
    prior launch can never masquerade as the persistent cache's win."""
    from llmd_tpu.jax_init import init_jax

    init_jax(args.cpu)  # the parent placed the cache via the environment

    from llmd_tpu.core.request import SamplingParams
    from llmd_tpu.engine import EngineConfig, LLMEngine
    from llmd_tpu.models import resolve_model

    t0 = time.monotonic()
    cfg, params = resolve_model(args.model)
    load_s = time.monotonic() - t0
    ecfg = EngineConfig(page_size=16, num_pages=256, max_model_len=512,
                        max_batch_size=4, prefill_chunk=64, decode_steps=8)
    t0 = time.monotonic()
    eng = LLMEngine(cfg, ecfg, params=params)
    build_s = time.monotonic() - t0
    prompts = [[(i * 131 + j) % (cfg.vocab_size - 2) + 1 for j in range(32)]
               for i in range(2)]
    t0 = time.monotonic()
    out = eng.generate(prompts, SamplingParams(max_tokens=16, temperature=0.0,
                                               ignore_eos=True))
    first_generate_s = time.monotonic() - t0  # compile-dominated when cold
    assert sum(len(v) for v in out.values()) == 2 * 16
    print(json.dumps({
        "load_s": round(load_s, 3),
        "build_s": round(build_s, 3),
        "first_generate_s": round(first_generate_s, 3),
        # the number the controller's relaunch budget cares about: engine up
        # AND serving its first tokens (weight load excluded — a snapshot
        # restore prices that separately)
        "ready_s": round(build_s + first_generate_s, 3),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny",
                    help="registry name or HF checkpoint dir (the replica "
                         "being relaunched)")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU platform (CI smoke; the cache round trip "
                         "is the same code, the speedup is only meaningful "
                         "on-device)")
    ap.add_argument("--cache-dir", default=None,
                    help="compilation cache both launches share (default: a "
                         "warm_start_probe directory inside the repo's "
                         "compile cache)")
    ap.add_argument("--keep-cache", action="store_true",
                    help="reuse an existing cache instead of wiping it first "
                         "(wiping is what makes the cold launch cold)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-launch subprocess budget in seconds")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if args.child:
        _child(args)
        return
    sys.path.insert(0, root)
    from llmd_tpu.jax_init import ENV_VAR, compile_cache_dir  # stdlib-only

    args.cache_dir = os.path.abspath(
        args.cache_dir or os.path.join(compile_cache_dir(), "warm_start_probe"))

    if not args.keep_cache and os.path.isdir(args.cache_dir):
        shutil.rmtree(args.cache_dir)
    os.makedirs(args.cache_dir, exist_ok=True)
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--model", args.model]
    if args.cpu:
        cmd.append("--cpu")
    rows: dict[str, dict] = {}
    for label in ("cold", "warm"):
        t0 = time.monotonic()
        env = {**os.environ, ENV_VAR: args.cache_dir,
               "PYTHONPATH": root + os.pathsep + os.environ.get("PYTHONPATH", "")}
        try:
            p = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                               env=env, timeout=args.timeout)
        except subprocess.TimeoutExpired:
            raise SystemExit(f"warm_start_probe: {label} launch timed out "
                             f"after {args.timeout:.0f}s")
        if p.returncode != 0:
            raise SystemExit(f"warm_start_probe: {label} launch failed "
                             f"rc={p.returncode}\n{(p.stderr or p.stdout)[-2000:]}")
        row = json.loads(p.stdout.strip().splitlines()[-1])
        row["wall_s"] = round(time.monotonic() - t0, 3)
        rows[label] = row
        print(f"# {label} launch: ready {row['ready_s']:.2f}s "
              f"(build {row['build_s']:.2f}s + first-generate "
              f"{row['first_generate_s']:.2f}s)", file=sys.stderr)
    entries = sum(len(fs) for _, _, fs in os.walk(args.cache_dir))
    cold, warm = rows["cold"], rows["warm"]
    print(json.dumps({
        "metric": "warm_start_speedup",
        "value": round(cold["ready_s"] / max(1e-9, warm["ready_s"]), 2),
        "unit": "x",
        "model": args.model,
        "cold": cold,
        "warm": warm,
        "cold_ready_s": cold["ready_s"],
        "warm_ready_s": warm["ready_s"],
        "cache_entries": entries,
        "cache_dir": args.cache_dir,
        "platform": "cpu" if args.cpu else "tpu",
    }))


if __name__ == "__main__":
    main()
