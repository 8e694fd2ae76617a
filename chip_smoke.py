#!/usr/bin/env python3
"""Chip smoke: the serving path, end to end, on the accelerator.

    python chip_smoke.py                      # llama-1b, int8, one TPU chip
    python chip_smoke.py --model moe-wide-mla --quantize none
    python chip_smoke.py --tensor-parallel-size 4          # four-chip host
    python chip_smoke.py --model moe-wide-sim --quantize none \\
                         --expert-parallel-size 4
    python chip_smoke.py --cpu                # tiny model, debug the script

Drives client -> router -> engine server -> jitted step programs -> Pallas
kernels through the entry points a user would call (``python -m
llmd_tpu.router.serve`` in front of ``python -m llmd_tpu.engine.serve``), at
the full width of one registry model with seeded random weights, and exits
non-zero unless every phase passed:

1. kernel parity (a short-lived child that owns the chip and exits): the
   Pallas kernels the model selects, partitioned over the same mesh the
   server will use, agree with the single-device XLA reference on a small
   input; also reports the device and the host<->device round trip;
2. cold launch: a streamed, a plain and a chat completion, a prompt spanning
   several prefill chunks, the same prompt again (prefix cache hit), and two
   identical bursts that fill every decode slot (no compile in the second);
   the engine's ``/metrics`` must show the chip (HBM series), the intended
   attention backend and no dropped MoE tokens;
3. warm launch against the same compile cache: start-to-first-token again.

This process never imports JAX: a parent that touched JAX would hold the
chip and starve the server. One child at a time owns the chip, and every
child is stopped before the script returns. On success — and only then — the
last two stdout lines are the run's summary and ``{"ok": true, "device":
{"platform": ..., "kind": ..., "count": ...}}`` as JAX reported the device.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from helpers.smoke_test import run_checks  # noqa: E402  (stdlib-only)
from llmd_tpu.jax_init import compile_cache_dir  # noqa: E402  (stdlib-only)

# the repo's own tolerance for bf16 Pallas-vs-reference attention
# (tests/test_ops.py); the MoE comparison is normalised to O(1) outputs
PARITY_TOL = 5e-2
# deadlines: a launch that is not ready, or a request that has not answered,
# by then is a failed phase. The slowest cold launch seen took ~120 s to its
# first token; 300 s each leaves the script room to report a hang inside the
# 1200 s the whole run is allowed.
READY_TIMEOUT_S = 300.0
REQUEST_TIMEOUT_S = 300.0


# --------------------------------------------------------------------------
# parity child: the only code in this file that imports JAX
# --------------------------------------------------------------------------

def parity_child(args) -> int:
    from llmd_tpu.jax_init import init_jax

    dev = init_jax(args.cpu)  # exits non-zero when asked for a TPU it lacks
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmd_tpu.models import get_model_config
    from llmd_tpu.models.transformer import (
        init_cache, padded_head_dim, ragged_paged_attention_xla, write_kv)
    from llmd_tpu.ops.packed_kv import make_packed_attn, pack_factor

    on_cpu = dev.platform == "cpu"
    cfg = get_model_config(args.model)
    # the server's mesh: under it the Pallas attention kernels run per device
    # (ops/paged_attention.py::shard_over_heads), and that split is what the
    # single-device reference below has to agree with
    mesh = None
    if args.tp * args.ep > 1:
        from llmd_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(ep=args.ep, tp=args.tp))
    out: dict = {
        "jax": jax.__version__,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "parity": {},
    }

    # host<->device round trip: dispatch + execute + scalar readback
    f = jax.jit(lambda x: x + 1)
    np.asarray(f(jnp.zeros(())))
    rtts = []
    for _ in range(20):
        t0 = time.perf_counter()
        np.asarray(f(jnp.zeros(())))
        rtts.append((time.perf_counter() - t0) * 1e3)
    out["host_device_rtt_ms"] = {"median": round(sorted(rtts)[10], 3),
                                 "min": round(min(rtts), 3)}

    rng = np.random.default_rng(0)
    dt = cfg.jax_dtype
    ps, maxp = 16, 8
    kv_lens = np.asarray([40, 9, 21, 100], np.int32)
    B = len(kv_lens)

    def paged_case(q_lens, heads, width, real_width):
        """A small ragged batch written through the engine's own write_kv
        (so the pool layout is the serving one) plus matching queries."""
        q_lens = np.asarray(q_lens, np.int32)
        pos = np.concatenate([np.arange(n - q, n) for n, q in
                              zip(kv_lens, q_lens)]).astype(np.int32)
        slots = np.repeat(np.arange(B, dtype=np.int32), q_lens)
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        pt = np.full((B, maxp), -1, np.int32)
        for b, n in enumerate(kv_lens):
            used = -(-int(n) // ps)
            pt[b, :used] = b * maxp + np.arange(used)
        q = np.zeros((len(pos), heads, width), np.float32)
        q[..., :real_width] = rng.standard_normal(
            (len(pos), heads, real_width))
        return (jnp.asarray(q, dt), jnp.asarray(pt), jnp.asarray(pos),
                jnp.asarray(slots), jnp.asarray(kv_lens), jnp.asarray(cu),
                jnp.asarray([B], jnp.int32))

    def fill_pool(pack, kv_heads, width, real_width):
        cache = init_cache(cfg, B * maxp, ps, pack=pack)
        cache = cache[: B * maxp]  # one layer's worth of pages
        tok_slots = np.concatenate(
            [b * maxp * ps + np.arange(n) for b, n in enumerate(kv_lens)]
        ).astype(np.int32)
        kv = np.zeros((2, len(tok_slots), kv_heads, width), np.float32)
        kv[..., :real_width] = rng.standard_normal(
            (2, len(tok_slots), kv_heads, real_width))
        flat = write_kv(cache.reshape(-1, *cache.shape[2:]),
                        jnp.asarray(kv[0], dt), jnp.asarray(kv[1], dt),
                        jnp.asarray(tok_slots))
        return flat.reshape(cache.shape)

    def compare(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(np.isfinite(got).all() and got.shape == want.shape
                  and np.allclose(got, want, rtol=PARITY_TOL, atol=PARITY_TOL))
        out["parity"][name] = {"ok": ok, "max_abs_err": round(err, 5),
                               "shape": list(got.shape)}

    if cfg.is_mla:
        from llmd_tpu.ops.mla_attention import mla_paged_attention

        real = cfg.mla_kv_lora_rank + cfg.mla_rope_dim
        dhp = padded_head_dim(real)
        q, pt, pos, slots, lens, cu, ns = paged_case(
            [8, 1, 1, 1], cfg.num_heads, dhp, real)
        cache = fill_pool(1, 1, dhp, real)
        kw = dict(scale=(cfg.mla_qk_nope_dim + cfg.mla_rope_dim) ** -0.5,
                  cu_q_lens=cu, num_seqs=ns)
        got = jax.jit(lambda *a: mla_paged_attention(
            *a, interpret=on_cpu, mesh=mesh, **kw))(q, cache, pt, pos, slots,
                                                    lens)
        want = jax.jit(lambda *a: ragged_paged_attention_xla(*a, **kw))(
            q, cache, pt, pos, slots, lens)
        compare("pallas_mla_ragged_paged_attention", got, want)
    elif not on_cpu:  # the upstream ragged kernel has no interpret mode
        from llmd_tpu.ops.paged_attention import paged_attention_tpu

        pack = pack_factor(cfg)
        dhp = padded_head_dim(cfg.head_dim)
        # decode rows ahead of a chunk, as the engine's plan orders a
        # unified step: the two-call form (ops/paged_attention.step_geometry)
        q, pt, pos, slots, lens, cu, ns = paged_case(
            [1, 1, 8, 1], cfg.num_heads, dhp, cfg.head_dim)
        cache = fill_pool(pack, cfg.num_kv_heads, dhp, cfg.head_dim)
        kw = dict(scale=cfg.head_dim ** -0.5, cu_q_lens=cu, num_seqs=ns)
        pallas = functools.partial(paged_attention_tpu, mesh=mesh)
        ref = ragged_paged_attention_xla
        if pack > 1:
            pallas = make_packed_attn(pallas, cfg, pack)
            ref = make_packed_attn(ref, cfg, pack)
        got = jax.jit(lambda *a: pallas(*a, **kw))(q, cache, pt, pos, slots,
                                                   lens)
        want = jax.jit(lambda *a: ref(*a, **kw))(q, cache, pt, pos, slots,
                                                 lens)
        compare("pallas_ragged_paged_attention"
                + (f"+packed{pack}" if pack > 1 else ""),
                got[..., :cfg.head_dim], want[..., :cfg.head_dim])

    if cfg.is_moe and args.quantize != "int8":
        from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

        T, D, F = 64, cfg.hidden_size, cfg.moe_intermediate_size
        E, k = cfg.moe_num_experts, cfg.moe_top_k
        x = jnp.asarray(rng.standard_normal((T, D)), dt)
        idx = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                    for _ in range(T)]), jnp.int32)
        topw = jnp.full((T, k), 1.0 / k, dt)
        valid = jnp.ones((T, k), jnp.int32)
        wi = jnp.asarray(rng.standard_normal((E, D, 2 * F)) * D ** -0.5, dt)
        wo = jnp.asarray(rng.standard_normal((E, F, D)) * F ** -0.5, dt)
        got = jax.jit(make_sorted_dispatch(
            mesh, use_pallas=True, interpret=on_cpu))(x, idx, topw, valid,
                                                      wi, wo)
        want = jax.jit(make_sorted_dispatch(None, use_pallas=False))(
            x, idx, topw, valid, wi, wo)
        compare("pallas_grouped_gemm", got, want)

    # the backend the engine's platform rule must resolve to, restated here
    # so the smoke can hold the server's /metrics against it
    if cfg.is_mla:
        out["expect_attn"] = ("xla_mla_absorbed" if on_cpu
                              else "pallas_mla_ragged_paged_attention")
    else:
        pack = pack_factor(cfg)
        out["expect_attn"] = (
            ("xla_reference" if on_cpu else "pallas_ragged_paged_attention")
            + (f"+packed{pack}" if pack > 1 else ""))
    if not cfg.is_moe:
        out["expect_moe"] = "n/a (dense model)"
    elif args.quantize == "int8":
        out["expect_moe"] = "xla_einsum (int8 weights)"
    else:
        out["expect_moe"] = "xla_einsum" if on_cpu else "pallas_grouped_gemm"
    print(json.dumps(out))
    return 0 if all(p["ok"] for p in out["parity"].values()) else 4


# --------------------------------------------------------------------------
# parent: stdlib only
# --------------------------------------------------------------------------

class Smoke:
    def __init__(self) -> None:
        self.checks: list[dict] = []
        self.procs: list[subprocess.Popen] = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.checks.append({"name": name, "ok": bool(ok), "detail": detail})
        print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        return bool(ok)

    @property
    def ok(self) -> bool:
        return all(c["ok"] for c in self.checks)

    def spawn(self, argv: list[str], log_path: str) -> subprocess.Popen:
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(argv, cwd=ROOT, stdout=log,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
        finally:
            log.close()  # the child holds its own descriptor
        self.procs.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
                proc.wait(timeout=20)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=20)

    def stop_all(self) -> None:
        for proc in reversed(self.procs):
            self.stop(proc)
        self.procs.clear()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 60.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data, {"Content-Type": "application/json",
                    "x-request-timeout": str(int(timeout))})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def stream_completion(url: str, body: dict, timeout: float):
    """POST an SSE completion. Returns (seconds to the first token chunk,
    completion_tokens from the final usage chunk, chunk count)."""
    req = urllib.request.Request(
        url, json.dumps(dict(body, stream=True)).encode(),
        {"Content-Type": "application/json",
         "x-request-timeout": str(int(timeout))})
    t0 = time.monotonic()
    first = None
    usage = None
    chunks = 0
    done = False
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            chunk = json.loads(payload)
            chunks += 1
            if first is None:
                first = time.monotonic() - t0
            usage = chunk.get("usage") or usage
    if not done or usage is None:
        raise RuntimeError(f"stream ended early (chunks={chunks}, done={done})")
    return first, usage["completion_tokens"], chunks


_SERIES = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_metrics(text: str) -> list[tuple[str, dict, float]]:
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if not m:
            continue
        labels = dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', m.group(2) or ""))
        try:
            out.append((m.group(1), labels, float(m.group(3))))
        except ValueError:
            continue
    return out


def series(metrics, name: str, **want) -> list[tuple[dict, float]]:
    return [(lab, v) for n, lab, v in metrics
            if n == name and all(lab.get(k) == x for k, x in want.items())]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{path}: {e}>"


def wait_healthy(smoke: Smoke, name: str, proc: subprocess.Popen, url: str,
                 deadline_s: float, log_path: str) -> bool:
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        if proc.poll() is not None:
            return smoke.check(
                f"{name}:up", False,
                f"exited rc={proc.returncode} before ready\n{tail(log_path)}")
        try:
            with urllib.request.urlopen(url, timeout=2.0) as r:
                if r.status == 200:
                    return True
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.25)
    return smoke.check(f"{name}:up", False,
                       f"not ready within {deadline_s:.0f}s\n{tail(log_path)}")


def launch(smoke: Smoke, args, tag: str, out_dir: str):
    """Start the engine server and a router in front of it. Returns
    (engine_proc, engine_url, router_url, t_launch, engine_log) or None."""
    eport, rport = free_port(), free_port()
    engine_log = os.path.join(out_dir, f"engine_{tag}.log")
    router_log = os.path.join(out_dir, f"router_{tag}.log")
    argv = [sys.executable, "-m", "llmd_tpu.engine.serve",
            "--model", args.model, "--host", "127.0.0.1",
            "--port", str(eport),
            "--max-batch-size", str(args.max_batch_size),
            "--decode-steps", str(args.decode_steps),
            "--prefill-chunk", str(args.prefill_chunk),
            "--num-pages", str(args.num_pages),
            "--max-model-len", str(args.max_model_len),
            "--tensor-parallel-size", str(args.tp),
            "--expert-parallel-size", str(args.ep)]
    if args.quantize != "none":
        argv += ["--quantize", args.quantize]
    if args.cpu:
        argv.append("--cpu")
    t_launch = time.monotonic()
    engine = smoke.spawn(argv, engine_log)
    router = smoke.spawn(
        [sys.executable, "-m", "llmd_tpu.router.serve", "--host", "127.0.0.1",
         "--port", str(rport), "--endpoints", f"127.0.0.1:{eport}",
         "--poll-interval", "0.2"], router_log)
    eurl, rurl = f"http://127.0.0.1:{eport}", f"http://127.0.0.1:{rport}"
    if not wait_healthy(smoke, f"engine[{tag}]", engine, f"{eurl}/health",
                        READY_TIMEOUT_S, engine_log):
        return None
    print(f"chip_smoke: engine[{tag}] answered /health "
          f"{time.monotonic() - t_launch:.1f}s after its launch", flush=True)
    if not wait_healthy(smoke, f"router[{tag}]", router, f"{rurl}/health",
                        60.0, router_log):
        return None
    return engine, eurl, rurl, t_launch, engine_log


def first_token(smoke: Smoke, args, tag: str, rurl: str, t_launch: float,
                model_id: str):
    """The launch's first request: SSE-streamed through the router."""
    n = 8
    try:
        ttft, got, chunks = stream_completion(
            f"{rurl}/v1/completions",
            {"model": model_id, "prompt": "The quick brown fox", "max_tokens": n,
             "temperature": 0.0, "ignore_eos": True}, REQUEST_TIMEOUT_S)
    except Exception as e:  # noqa: BLE001 — any failure fails the phase
        smoke.check(f"stream[{tag}]", False, f"{type(e).__name__}: {e}")
        return None
    start_to_first = time.monotonic() - t_launch
    smoke.check(f"stream[{tag}]", got == n and chunks >= 1,
                f"{got}/{n} tokens in {chunks} SSE chunks, first token "
                f"{ttft:.2f}s after the request")
    return start_to_first


def burst(eurl: str, rurl: str, model_id: str, n: int, max_tokens: int,
          timeout: float):
    """``n`` concurrent completions through the router. Returns (token counts,
    errors, wall seconds, the running/waiting levels the engine reported
    while the burst was in flight)."""
    def one(i: int):
        return http_json(
            f"{rurl}/v1/completions",
            {"model": model_id, "prompt": f"request {i:03d}: count to ten",
             "max_tokens": max_tokens, "temperature": 0.0,
             "ignore_eos": True}, timeout)["usage"]["completion_tokens"]

    levels: list[tuple[float, int, int]] = []  # (s into the burst, running, waiting)
    done = threading.Event()

    def gauge(m, name: str) -> int:
        return int(sum(v for _, v in series(m, name)))

    def watch():
        # both gauges are set once per engine step and hold through the next
        # fused decode call, so a 10 ms poll sees every level they take
        while not done.is_set():
            try:
                m = parse_metrics(http_text(f"{eurl}/metrics", 5.0))
            except (urllib.error.URLError, OSError):
                return  # a dead engine fails the burst itself
            levels.append((round(time.monotonic() - t0, 3),
                           gauge(m, "vllm:num_requests_running"),
                           gauge(m, "vllm:num_requests_waiting")))
            done.wait(0.01)

    t0 = time.monotonic()
    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    with concurrent.futures.ThreadPoolExecutor(max_workers=n) as pool:
        futs = [pool.submit(one, i) for i in range(n)]
        got, errors = [], []
        for f in futs:
            try:
                got.append(f.result())
            except Exception as e:  # noqa: BLE001 — counted, then reported
                errors.append(f"{type(e).__name__}: {e}")
    wall = time.monotonic() - t0
    done.set()
    watcher.join()
    return got, errors, wall, levels


def compiles_total(eurl: str) -> float:
    m = parse_metrics(http_text(f"{eurl}/metrics"))
    return sum(v for _, v in series(m, "llmd_tpu:program_compiles_total"))


def drive(smoke: Smoke, args, parity: dict, eurl: str, rurl: str,
          engine_log: str) -> dict:
    """Every request phase of the cold launch + the /metrics verdicts."""
    report: dict = {}
    # liveness, model discovery, one inference each way (helpers/smoke_test)
    base = run_checks(rurl, None, "completions", 0.0, True,
                      REQUEST_TIMEOUT_S)
    for c in base["checks"]:
        smoke.check(f"router:{c['name']}", c["ok"], c["detail"])
    model_id = http_json(f"{rurl}/v1/models")["data"][0]["id"]

    def completion(name: str, path: str, body: dict, want_tokens: int):
        try:
            resp = http_json(f"{rurl}{path}", dict(
                body, model=model_id, max_tokens=want_tokens, temperature=0.0,
                ignore_eos=True), REQUEST_TIMEOUT_S)
        except Exception as e:  # noqa: BLE001 — any failure fails the phase
            smoke.check(name, False, f"{type(e).__name__}: {e}")
            return None
        usage = resp.get("usage", {})
        smoke.check(name, usage.get("completion_tokens") == want_tokens,
                    f"{usage.get('completion_tokens')}/{want_tokens} tokens, "
                    f"prompt {usage.get('prompt_tokens')}, cached "
                    f"{usage.get('cached_tokens')}")
        return resp

    completion("completion", "/v1/completions", {"prompt": "Hello, chip."}, 16)
    completion("chat", "/v1/chat/completions",
               {"messages": [{"role": "user", "content": "ping"}]}, 16)

    # a prompt several prefill chunks long, then the same prompt again
    n_long = int(args.prefill_chunk * 2.75)
    long_prompt = ("All work and no play makes Jack a dull boy. " * 200)[:n_long]
    first = completion("long-prompt", "/v1/completions",
                       {"prompt": long_prompt}, 16)
    again = completion("long-prompt-repeat", "/v1/completions",
                       {"prompt": long_prompt}, 16)
    if first and again:
        smoke.check("long-prompt:chunks",
                    first["usage"]["prompt_tokens"] > 2 * args.prefill_chunk,
                    f"{first['usage']['prompt_tokens']} prompt tokens over "
                    f"prefill chunks of {args.prefill_chunk}")
        smoke.check("prefix-cache", again["usage"]["cached_tokens"] > 0,
                    f"cached_tokens={again['usage']['cached_tokens']} on the "
                    "repeat")
        smoke.check("greedy-repeatable",
                    first["choices"][0]["text"] == again["choices"][0]["text"],
                    "cached and uncached runs of one greedy prompt agree")

    # two identical bursts that fill every decode slot. A slot stays empty
    # when a request arrives after an earlier one has finished, and a fused
    # decode call holds arrivals back for its whole length: the first burst
    # on the chip (cold connections) spread its arrivals over 0.9 s and
    # peaked at 60 of 64 with 128 tokens each. 256 tokens (eight fused calls
    # at the chip's settings) outlast that spread; one full burst is asked.
    B = args.max_batch_size
    n_out = 256
    walls, peaks = [], []
    report["burst_levels"] = {}
    for i in (1, 2):
        before = compiles_total(eurl)
        got, errors, wall, levels = burst(eurl, rurl, model_id, B, n_out,
                                          REQUEST_TIMEOUT_S)
        after = compiles_total(eurl)
        walls.append(round(wall, 2))
        peaks.append(max([r for _, r, _ in levels], default=0))
        # the timeline, run-length encoded: [s into the burst, running, waiting]
        report["burst_levels"][f"burst{i}"] = [
            lv for j, lv in enumerate(levels)
            if j == 0 or lv[1:] != levels[j - 1][1:]]
        smoke.check(f"burst{i}", not errors and got == [n_out] * B,
                    f"{len(got)}/{B} answered with {n_out} tokens each in "
                    f"{wall:.1f}s" + (f"; errors: {errors[:3]}" if errors else ""))
        if i == 2:
            smoke.check("burst2:no-compiles", after == before,
                        f"program_compiles_total {before:.0f} -> {after:.0f}")
    smoke.check("burst:slots-filled", max(peaks) == B,
                f"vllm:num_requests_running peaked at {peaks[0]} and "
                f"{peaks[1]} of {B} decode slots during the two bursts")
    report["burst_wall_s"] = walls

    m = parse_metrics(http_text(f"{eurl}/metrics"))
    backends = [lab["backend"] for lab, v in series(
        m, "llmd_tpu:engine_attn_backend") if v == 1]
    report["attn_backend"] = backends
    smoke.check("attn-backend", backends == [parity["expect_attn"]],
                f"/metrics says {backends}, the platform rule says "
                f"{parity['expect_attn']!r}")
    banner = re.search(r"\[attn=([^,]*), moe=([^,]*), moe_dispatch=([^\]]*)\]",
                       tail(engine_log, 400))
    report["moe_backend"] = banner.group(2) if banner else None
    report["moe_dispatch"] = banner.group(3) if banner else None
    smoke.check("moe-backend",
                banner is not None and banner.group(2) == parity["expect_moe"],
                f"server resolved moe={report['moe_backend']!r} "
                f"dispatch={report['moe_dispatch']!r}, the platform rule says "
                f"{parity['expect_moe']!r}")
    dropped = sum(v for _, v in series(m, "llmd_tpu:moe_dropped_tokens_total"))
    smoke.check("moe-dropped", dropped == 0, f"{dropped:.0f} routed copies dropped")
    report["program_compiles"] = {
        lab["program"]: v for lab, v in series(
            m, "llmd_tpu:program_compiles_total")}

    # the work was on the chip: one HBM series per device, comparable shares
    plat = parity["device"]["platform"]
    n_dev = args.tp * args.ep
    hbm = {lab["device"]: v for lab, v in series(
        m, "llmd_tpu:device_hbm_bytes_in_use")}
    report["hbm_bytes_in_use"] = hbm
    if plat == "cpu":
        smoke.check("hbm", not hbm, "CPU backend exports no HBM series")
    else:
        want = {f"{plat}:{i}" for i in range(n_dev)}
        used = [hbm.get(d, 0.0) for d in sorted(want)]
        smoke.check("hbm", want <= set(hbm) and min(used) > 0,
                    f"bytes in use per device: "
                    f"{ {d: round(hbm.get(d, 0) / 2**30, 2) for d in sorted(want)} } GiB")
        if n_dev > 1:
            smoke.check("hbm:spread", min(used) >= 0.5 * max(used),
                        f"min/max share {min(used) / max(used):.2f} "
                        "(weights and KV are not all on device 0)")
    return report


def count_entries(path: str) -> int:
    return sum(len(fs) for _, _, fs in os.walk(path)) if os.path.isdir(path) else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default=None,
                    help="registry model (default llama-1b; tiny under --cpu)")
    ap.add_argument("--quantize", default=None, choices=["int8", "none"],
                    help="default int8 (the serving default); none = bf16")
    ap.add_argument("--tensor-parallel-size", type=int, default=1, dest="tp")
    ap.add_argument("--expert-parallel-size", type=int, default=1, dest="ep")
    ap.add_argument("--cpu", action="store_true",
                    help="run the same script on the CPU at tiny size")
    ap.add_argument("--parity-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    args.model = args.model or ("tiny64" if args.cpu else "llama-1b")
    args.quantize = args.quantize or ("none" if args.cpu else "int8")
    # serving flags of the r05 default (serve.py has no token-budget flag:
    # the unified step's budget is the prefill chunk)
    if args.cpu:
        (args.max_batch_size, args.decode_steps, args.prefill_chunk,
         args.num_pages, args.max_model_len) = 8, 4, 32, 256, 512
    else:
        (args.max_batch_size, args.decode_steps, args.prefill_chunk,
         args.num_pages, args.max_model_len) = 64, 32, 256, 2048, 1024
    if args.parity_child:
        return parity_child(args)

    out_dir = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    cache_dir = compile_cache_dir()
    cache_before = count_entries(cache_dir)
    smoke = Smoke()
    summary: dict = {"model": args.model, "quantize": args.quantize,
                     "mesh": {"tp": args.tp, "ep": args.ep},
                     "compile_cache": cache_dir}
    print(f"chip_smoke: model={args.model} quantize={args.quantize} "
          f"tp={args.tp} ep={args.ep} cache={cache_dir} "
          f"({cache_before} entries)", flush=True)
    try:
        # phase 1: kernel parity + device report (child owns the chip, exits)
        child = [sys.executable, os.path.abspath(__file__), "--parity-child",
                 "--model", args.model, "--quantize", args.quantize,
                 "--tensor-parallel-size", str(args.tp),
                 "--expert-parallel-size", str(args.ep)]
        if args.cpu:
            child.append("--cpu")
        try:
            p = subprocess.run(child, cwd=ROOT, capture_output=True, text=True,
                               timeout=READY_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            smoke.check("parity", False, "child timed out")
            return 1
        lines = p.stdout.strip().splitlines()
        if p.returncode not in (0, 4) or not lines:
            smoke.check("parity", False,
                        f"child rc={p.returncode}\n{p.stderr[-3000:]}")
            return 1
        parity = json.loads(lines[-1])
        dev = parity["device"]
        summary.update(jax=parity["jax"], device=dev,
                       host_device_rtt_ms=parity["host_device_rtt_ms"],
                       parity=parity["parity"])
        print(f"chip_smoke: jax {parity['jax']} on {dev['platform']} "
              f"({dev['kind']} x{dev['count']}); host<->device round trip "
              f"{parity['host_device_rtt_ms']}", flush=True)
        smoke.check("devices", dev["count"] >= args.tp * args.ep,
                    f"{dev['count']} devices for tp={args.tp} ep={args.ep}")
        for name, res in parity["parity"].items():
            smoke.check(f"parity:{name}", res["ok"],
                        f"max |pallas - xla| = {res['max_abs_err']} over "
                        f"{res['shape']} (tol {PARITY_TOL}"
                        + (f"; kernel split over tp={args.tp} ep={args.ep}, "
                           "reference on one device)"
                           if args.tp * args.ep > 1 else ")"))
        if not smoke.ok:
            return 1

        # phase 2: cold launch, every request phase
        started = launch(smoke, args, "cold", out_dir)
        if started is None:
            return 1
        engine, eurl, rurl, t_launch, engine_log = started
        model_id = f"llmd-tpu/{args.model}"
        cold = first_token(smoke, args, "cold", rurl, t_launch, model_id)
        if cold is None:
            print(tail(engine_log), flush=True)
            return 1
        summary["cold_start_to_first_token_s"] = round(cold, 2)
        summary.update(drive(smoke, args, parity, eurl, rurl, engine_log))
        smoke.check("engine:alive", engine.poll() is None,
                    "engine server still running after every phase"
                    if engine.poll() is None
                    else f"engine exited rc={engine.returncode}\n{tail(engine_log)}")
        smoke.stop_all()
        if not smoke.ok:
            print(tail(engine_log), flush=True)
            return 1

        # phase 3: warm launch against the same compile cache
        cache_cold = count_entries(cache_dir)
        started = launch(smoke, args, "warm", out_dir)
        if started is None:
            return 1
        engine, eurl, rurl, t_launch, engine_log = started
        warm = first_token(smoke, args, "warm", rurl, t_launch, model_id)
        smoke.stop_all()
        if warm is None:
            print(tail(engine_log), flush=True)
            return 1
        summary["warm_start_to_first_token_s"] = round(warm, 2)
        summary["compile_cache_entries"] = {
            "before": cache_before, "after_cold": cache_cold,
            "after_warm": count_entries(cache_dir)}
        smoke.check("compile-cache:written", cache_cold > 0,
                    f"{cache_cold} entries under {cache_dir}")
        if cache_before == 0:
            smoke.check("warm-start", warm < cold,
                        f"start-to-first-token cold {cold:.1f}s, warm "
                        f"{warm:.1f}s")
        else:
            print(f"chip_smoke: the cache held {cache_before} entries before "
                  f"the first launch, so it was not cold (first {cold:.1f}s, "
                  f"second {warm:.1f}s)", flush=True)
    finally:
        smoke.stop_all()
        summary["checks"] = smoke.checks
        with open(os.path.join(out_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    if not smoke.ok:
        return 1
    summary.pop("checks")
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": summary["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
