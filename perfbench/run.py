#!/usr/bin/env python3
"""One run of one benchmark cell, from the client through the router.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one new process tree on one chip: this parent, which never imports
JAX (a parent that touched JAX would hold the chip); one engine child that
owns the chip (``engine_child.py``: the program's own ``EngineServer`` made
from the cell's configuration file); one router child (``python -m
llmd_tpu.router.serve``) in front of it; and the load generator, which runs in
this process and speaks ``/v1/completions`` with ``stream: true`` to the
router. No server outlives the run.

Set-up (all of it counted in ``setup_s``): start both children, wait until
they answer, serve the check prompts cold and again from the prefix cache
(which compiles or loads both step programs), run the float32 reference on
what was served, replay the histories of the conversations that are under way
when the schedule starts, and run the schedule's ramp. Then the window of
``--seconds``, then a drain with the load unchanged until every request that
was due inside the window is complete. ``--trace 1`` also polls the engine's
gauges through the window and captures a few seconds of it with the profiler
(``/debug/profile``: the window's last two fifths; the client's and the
counters' per-layer metrics are of the part before it), and prints the cell's per-layer metrics instead of its
end-to-end ones.

The last line of standard output is the result, one JSON object. Lines before
it say what the run did: the split of set-up, the counts behind every
percentile, the check (every position's gap error and deficit with it;
``--check-only`` stops there, for fitting a check's limit). The result's
last key, ``check``, holds every number ``correct`` compared beside its
limit, and standard error ends with the same. Exit code 0 only with a result; without a TPU, with
fewer chips than the cell asks for, or on a ``device_kind`` missing from
``peaks.json``, no result and a non-zero code. ``--cpu`` is for rehearsing the
harness at a tiny size (``tests/``): its result names the CPU and carries no
device metric.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import aiohttp  # noqa: E402

import estimators as est  # noqa: E402
import prom  # noqa: E402
import readers  # noqa: E402
import traffic  # noqa: E402
from kernels.cached_tokens import decode_means  # noqa: E402
from loadgen import Generator  # noqa: E402

READY_TIMEOUT_S = 1000.0  # a first run compiles; the driver allows it 1200 s
# The capture is the window's last two fifths (20 s of 50). The closed cells
# move in waves of 11 to 12 s, prefill phases against fused-decode phases,
# with up to 14.5 s between two fused decode calls: a capture shorter than a
# wave reads whichever phase it falls into. The profiler slows the host while
# it runs and stalls it for seconds when it stops, so the traced run takes
# what it reads from the client and from counters over the three fifths
# before the capture, and the stall falls into the drain.
TRACE_FROM = 0.6
POLL_S = 0.5


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Children:
    """The engine and router processes; stopped, and waited for, on exit."""

    def __init__(self, out_dir: str) -> None:
        self.out_dir = out_dir
        self.procs: dict = {}

    def start(self, name: str, cmd: list, env: dict) -> subprocess.Popen:
        log = open(os.path.join(self.out_dir, name + ".log"), "w")
        p = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                             stderr=subprocess.STDOUT,
                             start_new_session=True)
        self.procs[name] = (p, log)
        return p

    def tail(self, name: str, n: int = 30) -> str:
        try:
            with open(os.path.join(self.out_dir, name + ".log")) as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def stop(self) -> None:
        for p, _ in self.procs.values():
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 15
        for p, log in self.procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    p.kill()
                p.wait()
            log.close()


async def get_json(session, url: str, timeout: float = 30.0):
    async with session.get(url, timeout=aiohttp.ClientTimeout(total=timeout)) as r:
        return await r.json()


async def scrape(session, url: str) -> list:
    async with session.get(url + "/metrics",
                           timeout=aiohttp.ClientTimeout(total=30)) as r:
        return prom.parse(await r.text())


async def wait_up(session, url: str, proc, what: str, kids: Children) -> None:
    t0 = time.time()
    while time.time() - t0 < READY_TIMEOUT_S:
        if proc.poll() is not None:
            raise SystemExit(f"{what} exited with code {proc.returncode} "
                             f"before it answered:\n{kids.tail(what)}")
        try:
            async with session.get(
                    url, timeout=aiohttp.ClientTimeout(total=5)) as r:
                if r.status == 200:
                    return
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            pass
        await asyncio.sleep(0.25)
    raise SystemExit(f"{what} did not answer within {READY_TIMEOUT_S} s")


# ------------------------------------------------------------------- check

def check_prompts(chk: dict, seed: int, vocab: int) -> list:
    """The check's prompts, by group: ``shared_prefixes`` groups, each of
    ``tails_per_prefix`` prompts that begin with the group's prefix and go on
    with a tail of their own, tail lengths evenly spaced over ``tail_tokens``
    (the first of a group has the longest)."""
    def ids(tag: str, n: int) -> list:
        return traffic.token_ids(
            random.Random(f"{seed}-check-{tag}").getrandbits(48), n, vocab)

    n, lo, hi = (chk["tails_per_prefix"], chk["tail_tokens"]["min"],
                 chk["tail_tokens"]["max"])
    tails = [hi - round(j * (hi - lo) / max(1, n - 1)) for j in range(n)]
    groups = []
    for g in range(chk["shared_prefixes"]):
        prefix = ids(f"prefix-{g}", chk["prefix_tokens"])
        groups.append([prefix + ids(f"{g}-{j}", t)
                       for j, t in enumerate(tails)])
    return groups


LIFT = 50.0  # bias on both probed tokens, so that no third one is served


def gap_summary(errors) -> dict:
    """Of the positions' gap errors: the median, which is what is judged,
    beside the lower quartile and the maximum."""
    err = sorted(errors)
    q25, q50, top = (err[round(f * (len(err) - 1))] for f in (0.25, 0.5, 1.0))
    return {"positions": len(err), "median": q50, "q25": q25, "max": top}


def clean_half(errors, per_prompt: int, floor: float) -> float:
    """The arithmetic's share of the positions' gap errors, read where an
    expert choice that flipped has left it alone. A model with recurrent
    layers carries a flip on through the state to every later position of
    the same prompt, so flips foul whole prompts, more or fewer of them from
    seed to seed, and the median over all positions reads how many prompts
    were fouled (nemotron: 0.059 to 0.197 over sound seeds, PERF.md section
    2). Taken instead: of each prompt the lower quartile of its positions'
    errors (the positions a flip inside the served span has not reached), and
    of the half of the prompts that read lowest the geometric mean of those.
    A lower precision moves every prompt, the cleanest too; a fault that
    spares half the prompts is not this number's to find (``margin`` reads
    every position)."""
    err = list(errors)
    low = sorted(gap_summary(err[i:i + per_prompt])["q25"]
                 for i in range(0, len(err), per_prompt))
    low = [max(v, floor) for v in low[:max(1, len(low) // 2)]]
    return math.exp(sum(map(math.log, low)) / len(low))


JUDGED = ("median", "clean_half")


async def gap_probe(gen: Generator, probe: dict, prompts: list, served: list,
                    top2: list) -> dict:
    """How far the served path's logits lie from the reference's, read
    through the router with nothing but served tokens. At each position that
    served a token the reference names its two largest logits, tokens a and
    b, and their gap. The same context is asked for one token with a
    ``logit_bias`` that lifts a and b above every other token and b by a
    further x: b is served exactly when x is over the served path's own gap
    between the two. ``rounds`` bisections of x within ``width`` of the
    reference's gap find that gap to ``width / 2**rounds``; the error of a
    position is its distance from the reference's, at most ``width``.

    The number judged is the median over the positions, unless the file's
    ``gap_probe.judged`` names ``clean_half``. Arithmetic of a lower
    precision moves every position; an expert choice that flips at a near tie
    of the router moves the positions it touches by far more and leaves the
    others alone, so while under half the positions see a flip the median
    reads the arithmetic and the maximum reads the ties. Where a recurrent
    state carries a flip on, over half of them can: ``clean_half``."""
    ctx = [(list(p) + list(s[:j]), *t) for p, s, ts in
           zip(prompts, served, top2) for j, t in enumerate(ts)]
    w = probe["width"]
    lo, hi = [g - w for *_, g in ctx], [g + w for *_, g in ctx]
    for _ in range(probe["rounds"]):
        mid = [(l + h) / 2 for l, h in zip(lo, hi)]
        got = await asyncio.gather(*(
            gen.complete(p, 1, None, bias={str(a): LIFT, str(b): LIFT + x})
            for (p, a, b, _), x in zip(ctx, mid)))
        for i, ((_, a, b, _), out) in enumerate(zip(ctx, got)):
            if out not in ([a], [b]):
                raise SystemExit(f"gap probe: asked for {a} or {b}, "
                                 f"served {out}")
            if out == [b]:
                hi[i] = mid[i]
            else:
                lo[i] = mid[i]
    err = [abs((l + h) / 2 - g) for l, h, (*_, g) in zip(lo, hi, ctx)]
    res = w / 2 ** probe["rounds"]
    summary = gap_summary(err)
    judged = probe.get("judged", "median")
    if judged not in JUDGED:
        raise SystemExit(f"gap_probe.judged {judged!r} is none of {JUDGED}")
    if judged == "clean_half":
        if len({len(ts) for ts in top2}) != 1:
            raise SystemExit("clean_half: a prompt served fewer tokens")
        summary["clean_half"] = clean_half(err, len(top2[0]), res)
    return {"gap_error": summary, "judged": judged, "read": summary[judged],
            "limit": probe["limit"], "resolution": res,
            "errors": [round(e, 4) for e in err]}


async def check_outputs(gen: Generator, session, control: str, eurl: str,
                        conf: dict, seed: int) -> dict:
    """(a) The check's prompts, at the cells' own lengths, are served twice
    and give the same greedy tokens: first cold (one of each group alone,
    then the rest at once over the prefix those left in the cache), then all
    at once from the prefix cache. (b) Every token of the first serving is
    teacher-forced through the float32 reference and its reference logit lies
    within the configuration's margin of the reference maximum at its
    position; the served stack and KV pool have the stated types. (c) Where
    the configuration's check has a ``gap_probe``, the served path's gap
    between the reference's two best tokens lies within its limit of the
    reference's at the median of those positions (``gap_probe``)."""
    chk = conf["check"]
    groups = check_prompts(chk, seed, conf["vocab_size"])
    n_out = chk["served_tokens"]

    async def serve(prompts: list) -> list:
        return list(await asyncio.gather(
            *(gen.complete(p, n_out, None) for p in prompts)))

    async def cached_tokens() -> float:
        return prom.total(await scrape(session, eurl),
                          "llmd_tpu:engine_prefix_cached_tokens_total") or 0.0

    t0, c0 = time.time(), await cached_tokens()
    first = await serve([g[0] for g in groups])
    rest = await serve([p for g in groups for p in g[1:]])
    t1, c1 = time.time(), await cached_tokens()
    k = len(groups[0]) - 1
    cold = [[first[i]] + rest[i * k:(i + 1) * k] for i in range(len(groups))]
    prompts = [p for g in groups for p in g]
    cold = [c for g in cold for c in g]
    warm = await serve(prompts)
    t2, c2 = time.time(), await cached_tokens()
    async with session.post(control + "/reference",
                            json={"prompts": prompts, "served": cold},
                            timeout=aiohttp.ClientTimeout(total=900)) as r:
        ref = await r.json()
    t3 = time.time()
    setup = await get_json(session, control + "/setup")
    worst = max(d for ds in ref["deficits"] for d in ds)
    agree = sum(d == 0.0 for ds in ref["deficits"] for d in ds)
    out = {
        "cold_equals_cached": cold == warm,
        "lengths_ok": all(len(c) == n_out for c in cold),
        "reference_worst_deficit": worst, "margin": chk["margin"],
        "reference_argmax_agree": agree,
        "deficits": [round(d, 3) for ds in ref["deficits"] for d in ds],
        "served_tokens": sum(len(c) for c in cold),
        "prompt_tokens": [min(map(len, prompts)), max(map(len, prompts))],
        "prefix_cached_tokens": {"cold": c1 - c0, "cached": c2 - c1},
        "served_dtype_ok": setup["served_dtype_ok"],
        "attn_backend": setup["attn_backend"],
        "first_requests_s": t1 - t0, "cached_requests_s": t2 - t1,
        "reference_s": t3 - t2,
    }
    out["ok"] = bool(out["cold_equals_cached"] and out["lengths_ok"]
                     and worst <= chk["margin"] and out["served_dtype_ok"])
    if "gap_probe" in chk and out["lengths_ok"]:
        out["gap_probe"] = await gap_probe(gen, chk["gap_probe"], prompts,
                                           cold, ref["top2"])
        out["gap_probe"]["seconds"] = time.time() - t3
        out["ok"] = bool(out["ok"] and out["gap_probe"]["read"]
                         <= chk["gap_probe"]["limit"])
    out["engine_split"] = setup["split"]
    return out


# ------------------------------------------------------------------ metrics

def generator_facts(load, samples: list, until: float | None = None) -> dict:
    """What the client saw of the window's requests; with ``until``, of those
    that were complete by then (a failed one counts if it was due by then).
    ``samples`` are the decoding rows at each moment they were sampled
    (``Generator.decoding_rows``)."""
    win = [r for r in load.records if r.in_window and (
        until is None or (r.last <= until if r.ok else r.due <= until))]
    late = [(r.sent - r.free) * 1e3 for r in win if r.sent]
    done = [r for r in win if r.ok]
    tpot = [est.tpot_ms(r.first, r.last, r.n_out) if r.ok else est.INF
            for r in win]
    ttft = [(r.first - r.due) * 1e3 if r.ok else est.INF for r in win]
    tpot = [est.INF if v is None else v for v in tpot]
    facts = {
        "late_p99_ms": est.percentile(late, 99),
        "tpot_p50_ms": est.finite(est.percentile(tpot, 50)) if win else None,
        "tpot_p95_ms": est.finite(est.percentile(tpot, 95)) if win else None,
        "ttft_p50_ms": est.finite(est.percentile(ttft, 50)) if win else None,
        "ttft_p95_ms": est.finite(est.percentile(ttft, 95)) if win else None,
        "request_mean_ms": (sum(r.last - r.sent for r in done) / len(done)
                            * 1e3) if done else None,
        "out_tok_s": est.window_rate(load.events, load.t0,
                                     until or load.t1)[0],
        "prompt_tokens_sent": sum(r.prompt_tokens for r in win),
        "lane_blocked": load.lane_blocked,
    }
    means = decode_means({"decode_rows": samples}) if samples else None
    if means:
        (facts["decode_ctx_tokens_mean"],
         facts["decode_unique_ctx_tokens_mean"],
         facts["decoding_mean"]) = means
    return facts


def end_to_end(load, cell_metrics: list) -> tuple:
    """{name: value} of the cell's end-to-end metrics, and the notes that go
    on earlier lines (sample counts). ``out_tok_s`` is every token that
    arrived in the window over the window's length; ``tpot_p95_ms`` the 95th
    percentile, over every request due in the window, of the request's time
    per output token after the first (a failed request stands at +inf). The
    median of those times is on the ``generator`` line only: it spreads three
    times as widely as the 95th percentile (PERF.md section 2)."""
    win = [r for r in load.records if r.in_window]
    rate, n_ev = est.window_rate(load.events, load.t0, load.t1)
    vals = {"out_tok_s": rate,
            "tpot_p95_ms": generator_facts(load, [])["tpot_p95_ms"]}
    notes = {"requests_due_in_window": len(win),
             "completed": sum(r.ok for r in win),
             "token_events_in_window": n_ev,
             "tokens_in_window": rate * (load.t1 - load.t0),
             "offered_out_tok_s": sum(r.req.max_tokens for r in win)
             / (load.t1 - load.t0),
             "window_s": load.t1 - load.t0,
             "samples_beyond_p95": len(win) - max(
                 0, -(-95 * len(win) // 100)),
             "samples_beyond_p99": len(win) - max(
                 0, -(-99 * len(win) // 100)),
             "inflight_at_start": load.inflight_at_t0,
             "inflight_at_end": load.inflight_at_t1,
             "lane_blocked": load.lane_blocked}
    return {k: v for k, v in vals.items() if k in cell_metrics}, notes


# ---------------------------------------------------------------------- run

async def run(args, manifest: dict, cell: dict, conf: dict, mix: dict,
              kids: Children, out_dir: str) -> dict:
    eport, cport, rport = free_port(), free_port(), free_port()
    eurl, curl, rurl = (f"http://127.0.0.1:{p}" for p in (eport, cport, rport))
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["LLMD_PROFILE_DIR"] = os.path.join(out_dir, "profile")
    env["TPU_LOG_DIR"] = env.get("TPU_LOG_DIR", "disabled")
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
    conf_path = os.path.join(ROOT, cell["config_file"])
    engine = kids.start("engine", [
        sys.executable, os.path.join(HERE, "engine_child.py"),
        "--config", conf_path, "--seed", str(args.seed),
        "--port", str(eport), "--control-port", str(cport)]
        + (["--cpu"] if args.cpu else []), env)
    router = kids.start("router", [
        sys.executable, "-m", "llmd_tpu.router.serve", "--endpoints",
        f"127.0.0.1:{eport}", "--host", "127.0.0.1", "--port", str(rport)],
        env)
    split = {"spawn": time.time() - T_START}
    sched = traffic.build(mix, args.seed, args.seconds)
    say(note="traffic", mix=mix["name"], window=sched.totals("window"),
        pool=sched.totals("pool"), ramp=sched.totals("ramp"))
    async with aiohttp.ClientSession() as session:
        t = time.time()
        await wait_up(session, curl + "/device", engine, "engine", kids)
        await wait_up(session, rurl + "/health", router, "router", kids)
        split["servers_ready"] = time.time() - t
        device = await get_json(session, curl + "/device")
        want = "cpu" if args.cpu else "tpu"
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if device["platform"] != want or device["count"] < cell["chips"]:
            raise SystemExit(f"asked for {cell['chips']} {want} device(s), "
                             f"JAX reports {device}")
        if not args.cpu and device["kind"] not in peaks:
            raise SystemExit(f"device_kind {device['kind']!r} is not in "
                             "perfbench/peaks.json")
        async with Generator(rurl, conf["name"], conf["vocab_size"], sched,
                             args.seed, args.seconds) as gen:
            # the router learns of the endpoint by polling it: the first
            # request goes through once it has
            t = time.time()
            while True:
                try:
                    await gen.complete([2, 3, 4, 5], 1, None)
                    break
                except Exception as e:  # noqa: BLE001
                    if time.time() - t > 120 or engine.poll() is not None:
                        raise SystemExit(f"no request got through the "
                                         f"router: {e}\n{kids.tail('router')}")
                    await asyncio.sleep(0.25)
            split["first_request_through_router"] = time.time() - t
            t = time.time()
            check = await check_outputs(gen, session, curl, eurl, conf,
                                        args.seed)
            split["check_and_warm_up"] = time.time() - t
            say(note="check", **check)
            if args.check_only:
                return {"check_only": True, "ok": check["ok"]}
            t = time.time()
            warmed = await gen.warm_sessions()
            split["session_histories"] = time.time() - t
            before = {"engine": await scrape(session, eurl),
                      "router": await scrape(session, rurl)}
            polls: list = []
            samples: list = []
            captured: dict = {}

            async def on_window(t0: float, t1: float) -> None:
                t_cap = t0 + TRACE_FROM * (t1 - t0)

                async def capture() -> None:
                    await asyncio.sleep(max(0.0, t_cap - time.monotonic()))
                    captured["counters"] = {
                        "engine": await scrape(session, eurl),
                        "router": await scrape(session, rurl)}
                    captured["from"] = time.monotonic()
                    captured.update(await get_json(
                        session, eurl + f"/debug/profile?seconds="
                        f"{t1 - captured['from']:.1f}", timeout=200))
                    captured["to"] = time.monotonic()

                cap = asyncio.create_task(capture())
                while time.monotonic() < t1:
                    if time.monotonic() < t_cap:
                        polls.append((time.monotonic(),
                                      await scrape(session, eurl)))
                    samples.append((time.monotonic(), gen.decoding_rows()))
                    await asyncio.sleep(POLL_S)
                await cap

            t_ramp, t_ramp_mono = time.time(), time.monotonic()
            split["before_ramp_total"] = t_ramp - T_START
            load = await gen.run(on_window if args.trace else None)
            # process start to the first timed request: wall time to the
            # ramp's start, and the ramp on the clock the window is kept on
            setup_s = (t_ramp - T_START) + (load.t0 - t_ramp_mono)
            after = {"engine": await scrape(session, eurl),
                     "router": await scrape(session, rurl)}
            device = await get_json(session, curl + "/device")
        split["ramp"] = float(mix["ramp_s"])
        split["session_history_tokens"] = warmed
    kids.stop()

    cell_e2e = [m["name"] for m in manifest["end_to_end"]
                if cell["metrics_of"] in m.get("workloads",
                                               [cell["metrics_of"]])]
    vals, notes = end_to_end(load, cell_e2e)
    say(note="window", **notes)
    say(note="setup_split", setup_s=setup_s, **split,
        engine=check["engine_split"])
    win = [r for r in load.records if r.in_window]
    failed = [r for r in win if not r.ok]
    for r in failed[:5]:
        say(note="failed_request", index=r.req.index, error=r.error)
    compiles = (prom.total(after["engine"], "llmd_tpu:program_compiles_total")
                or 0) - (prom.total(before["engine"],
                                    "llmd_tpu:program_compiles_total") or 0)
    correct = bool(check["ok"] and not failed and compiles == 0
                   and notes["tokens_in_window"] > 0)
    # every number `correct` compared, beside its limit: the result line's
    # last key and this run's last lines on standard error
    probe = check.get("gap_probe")
    compared = {
        "cold_equals_cached": [check["cold_equals_cached"], True],
        "lengths_ok": [check["lengths_ok"], True],
        "served_dtype_ok": [check["served_dtype_ok"], True],
        "worst_deficit": [check["reference_worst_deficit"], check["margin"]],
        **({"gap_" + probe["judged"]: [probe["read"], probe["limit"]]}
           if probe else {}),
        "failed_requests": [len(failed), 0],
        "compiles_in_window": [compiles, 0],
        "tokens_in_window_over": [notes["tokens_in_window"], 0]}
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]
             + manifest["per_layer"]}
    result = {"correct": correct, "attempted": len(win),
              "failed": len(failed), "metrics": {},
              "device": {"platform": device["platform"],
                         "kind": device["kind"], "count": device["count"],
                         "memory_peak_bytes": device["memory_peak_bytes"]}}
    if not args.trace:
        # the client's latencies on every run's earlier lines, with the
        # count they were taken over (the window line's)
        say(note="generator", **generator_facts(load, []))
        vals["setup_s"] = setup_s
        for k, v in vals.items():
            result["metrics"][k] = {"value": est.finite(v), "unit": units[k]}
        result["check"] = compared
        return result

    # the traced run: reduce the trace in a process of its own, then let
    # every per-layer metric of this cell read what it reads
    trace = None
    if captured.get("files"):
        pb = [f for f in captured["files"] if f.endswith(".xplane.pb")]
        if pb:
            path = os.path.join(captured["dir"], pb[0])
            renv = dict(os.environ, JAX_PLATFORMS="cpu")
            out = subprocess.run(
                [sys.executable, os.path.join(HERE, "xplane.py"), path],
                env=renv, capture_output=True, text=True, timeout=300)
            if out.returncode == 0:
                trace = json.loads(out.stdout.strip().splitlines()[-1])
            else:
                say(note="trace_reduction_failed", stderr=out.stderr[-2000:])
    shutil.rmtree(os.path.join(out_dir, "profile"), ignore_errors=True)
    lo, hi = captured.get("from", load.t0), captured.get("to", load.t1)
    in_trace = [rows for t, rows in samples if lo <= t <= hi] or \
        [rows for _, rows in samples]
    ctx = {"gen": generator_facts(load, in_trace, captured.get("from")),
           "decode_rows": in_trace,
           "before": before, "after": captured.get("counters", after),
           "polls": {"engine": [p for _, p in polls]},
           "trace": trace, "device": device, "config": conf}
    say(note="generator", **ctx["gen"])
    for m in manifest["per_layer"]:
        # without a list of cells: every cell that reports what it moves
        if (cell["metrics_of"] not in m["workloads"] if "workloads" in m
                else m["moves"] not in cell_e2e):
            continue
        v = readers.read(readers.load(m["name"])["reads"], ctx)
        if v is None:
            say(note="metric_not_read", name=m["name"])
        else:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    if trace and "busy_s" in trace:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, o["seconds"]]
                           for n, o in list(trace["ops"].items())[:10]],
            "idle_gaps": [[n, s] for n, s in
                          list(trace["idle_gaps"].items())[:10]]}
        say(note="trace_modules", modules=trace["modules"])
    # the end-to-end numbers of a traced run, for the tracing overhead
    say(note="traced_end_to_end", **{k: est.finite(v) for k, v in vals.items()})
    result["check"] = compared
    return result


def with_overrides(conf: dict, over: dict) -> dict:
    """``conf`` with the keys of ``over`` replaced, one level into a block."""
    return {**conf, **{k: {**conf[k], **v} if isinstance(v, dict) else v
                       for k, v in over.items()}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU (tests only)")
    ap.add_argument("--check-only", action="store_true",
                    help="setting a check's limit only: stop after the "
                         "check line, with no window and no result")
    ap.add_argument("--rate", type=float, default=None,
                    help="knee sweep only: offer this rate instead of the "
                         "cell's")
    args = ap.parse_args()
    with open(args.manifest) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in {args.manifest}")
    cell = dict(cells[args.workload])
    cfg = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cell["config_file"] = cfg["file"]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        conf = json.load(f)
    mix = traffic.load_mix(cell["traffic"])
    mix.update(cell.get("overrides", {}))  # a rehearsal manifest's only
    if args.rate is not None:
        mix["rate_rps"] = args.rate
    out_dir = os.path.join(ROOT, "chiprun_out", "perfbench",
                           f"{cell['name']}-s{args.seed}-t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    # a rehearsal manifest's only, as the cell's overrides are: the metric
    # lists are BENCHMARK.json's unless it has its own, a cell reads the
    # metrics of the cell it names, and a configuration entry replaces keys
    # of its file (one level into a block), which the engine child then reads
    # from a copy in the run's directory
    if "per_layer" not in manifest:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest.update({k: v for k, v in json.load(f).items()
                             if k in ("end_to_end", "per_layer")})
    cell["metrics_of"] = cell.get("metrics_of", cell["name"])
    if "overrides" in cfg:
        conf = with_overrides(conf, cfg["overrides"])
        cell["config_file"] = os.path.join(out_dir, "config.json")
        with open(cell["config_file"], "w") as f:
            json.dump(conf, f)
    kids = Children(out_dir)
    try:
        result = asyncio.run(run(args, manifest, cell, conf, mix, kids,
                                 out_dir))
    finally:
        kids.stop()
    for name, (value, limit) in result.get("check", {}).items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
