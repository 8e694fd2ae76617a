"""Sweep the paged-attention kernel's block geometry at the served shapes.

Times `paged_attention_tpu` exactly as the engine calls it (bf16 pool
``[P, page, 2*Hk, 128]``, ``VMEM_LIMIT`` as is, page tables as wide as the
model length) at the four shapes the benchmark's cells serve: the fused decode
call (N = max_batch_size query rows, one a sequence) and the unified step
(N = prefill_chunk tokens: decode rows first, then prefill chunks) of each
configuration in ``perfbench/configs`` (and, on request, ``chunks``: a unified
step with no decode rows). Contexts are drawn from the cells'
own traffic files, so a row's context, the number of live rows and the chunk's
history are what the cells see, not a uniform length.

Every (bkv, bq) pair is one Mosaic compile and ``--reps`` dependent calls in a
``fori_loop``; the report is microseconds a call and microseconds per 128
tokens of context read, which says whether the fixed part of a KV block or the
work per query row sets the time, and beside them the seconds the pair took to
trace and lower (the kernel's page-fetch loop is unrolled in Python, so a
larger block costs every launch that much more per call site, compile cache
or not). The rule's own (`step_geometry`) is marked in the table. A pair the
compiler refuses is recorded with its error. ``--heads 12/4,24/4`` puts other
head counts over a cell's pool and contexts: which property of a layout the
best pair follows. ``--split 0,1`` times a unified step's rows as one call at
each pair and as two (the one-query rows at the fused decode call's pair, the
chunks at the pair's bq; `paged_attention_tpu`), the one call first: every
row's ``diff`` is against the first row's output and must read 0.0.

``--shared <lanes a tenant>`` lays the decode rows out as the sessions
traffic does (every so many consecutive decode rows name the same pages for
the tenant's system prompt and own the rest; without it every row owns its
pages and no group forms), ``--groups 4,8,16`` times each pair once more
with the one-query rows on the repo's kernel at those rows a group
(`rows_attention`; 0 = the kernel's own `GROUP_ROWS`; every timed row keeps
the upstream call's row first, so ``diff`` is against it), ``--pages-a-turn``
the page copies a turn of its fetch loop, and ``--parent <file>`` (the parent
commit's ``ops/paged_attention.py``, e.g. ``git show HEAD~1:llmd_tpu/ops/
paged_attention.py > .scratch/parent/paged_attention.py``) times that file
first in the same chip call; ``kv_blocks`` is what the counter's twin books
for the layout (blocks once a row, blocks fetched).

    python tools/attn_sweep.py                  # on the chip
    python tools/attn_sweep.py --cells mistral --bkv 32 --bq 64 --split 1 \
        --shared 8 --groups 4,8,16              # the rows kernel, ~3 min
    python tools/attn_sweep.py --compile-only   # here: which pairs Mosaic takes

``--compile-only`` compiles for a described v5e without a chip and runs nothing.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (configuration file, traffic file) of each cell in BENCHMARK.json
CELLS = {
    "qwen": ("qwen2.5-1.5b", "offline-closed"),
    "mistral": ("mistral-7b-v0.3", "sessions-closed"),
    "smallthinker": ("smallthinker-21b-a3b", "sessions-long-closed"),
    "jamba": ("jamba2-3b", "reasoning-closed"),
    # with --heads 16/1: a sparse layer's call brings one KV head's sixteen
    # query heads over that head's own pages (ModelConfig.kv_pool_folds)
    "sala": ("minicpm-sala-9b", "longdoc-closed"),
}
# live rows of a fused decode call and decode rows of a unified step
# (PERF.md section 5: decode_seat_steps_total, program_rows_total)
LIVE_DECODE = {"qwen": 48, "mistral": 32, "smallthinker": 53, "jamba": 58,
               "sala": 14}
UNIFIED_DECODE = {"qwen": 49, "mistral": 30, "smallthinker": 53, "jamba": 58,
                  "sala": 14}


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "perfbench", kind, f"{name}.json")) as f:
        return json.load(f)


def _lognormal(rng, spec: dict, n: int):
    import numpy as np

    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _contexts(rng, traffic: dict, n: int):
    """(history, new) per sequence: tokens already in the cache when the
    request arrived (a prefix-cache hit) and the prompt tokens it brought."""
    import numpy as np

    sess = traffic.get("sessions")
    if not sess:
        return np.zeros(n, np.int64), _lognormal(rng, traffic["prompt"], n)
    hist = np.full(n, sess["system_prompt"], np.int64)
    for i, t in enumerate(rng.integers(0, sess["turns"], n)):
        hist[i] += (_lognormal(rng, traffic["prompt"], t).sum()
                    + _lognormal(rng, traffic["output"], t).sum())
    return hist, _lognormal(rng, traffic["prompt"], n)


def draw_batch(rng, cell: str, program: str, eng: dict, traffic: dict):
    """kv_lens [B], cu_q_lens [B+1], num_seqs for one call of ``program``."""
    import numpy as np

    B, N = eng["max_batch_size"], eng["prefill_chunk"]
    limit = eng["max_model_len"]
    n_dec = {"decode": LIVE_DECODE, "unified": UNIFIED_DECODE}.get(
        program, {}).get(cell, 0)  # "chunks": a unified step of prefill only
    hist, new = _contexts(rng, traffic, n_dec)
    out = _lognormal(rng, traffic["output"], n_dec)
    dec = np.minimum(hist + new + (rng.random(n_dec) * out).astype(np.int64),
                     limit)
    kv_lens = np.ones(B, np.int64)  # an idle seat reads one token
    q_lens = np.zeros(B, np.int64)
    kv_lens[:n_dec], q_lens[:n_dec] = dec, 1
    rows = n_dec
    if program == "decode":
        rows, q_lens[:] = B, 1  # the fused call runs every seat
    else:
        budget = N - n_dec
        while budget > 0 and rows < B:
            h, p = (int(a[0]) for a in _contexts(rng, traffic, 1))
            done = int(rng.integers(0, p))  # prompt tokens of earlier chunks
            n = min(budget, p - done, N)
            kv_lens[rows], q_lens[rows] = min(h + done + n, limit), n
            rows, budget = rows + 1, budget - n
    cu = np.concatenate([[0], np.cumsum(q_lens)])
    cu[rows + 1:] = cu[rows]
    return kv_lens.astype(np.int32), cu.astype(np.int32), rows


def build_case(cell: str, program: str, seed: int, heads: str = "",
               shared: int = 0):
    import numpy as np

    cfg_name, traffic_name = CELLS[cell]
    cfg, traffic = _load("configs", cfg_name), _load("traffic", traffic_name)
    if heads:  # another head layout over the cell's contexts and pages
        cfg["num_attention_heads"], cfg["num_key_value_heads"] = map(
            int, heads.split("/"))
    eng = cfg["engine"]
    rng = np.random.default_rng(seed)
    kv_lens, cu, rows = draw_batch(rng, cell, program, eng, traffic)
    ps, P = eng["page_size"], eng["num_pages"]
    maxp = eng["max_model_len"] // ps
    pts = np.full((eng["max_batch_size"], maxp), -1, np.int32)
    free = rng.permutation(P)  # a pool after churn: a sequence's pages scatter
    off = 0
    # decode rows behind a tenant's system prompt name its pages first
    held = (traffic.get("sessions") or {}).get("system_prompt", 0) // ps
    n_dec = int((np.diff(cu)[:rows] == 1).sum()) if shared else 0
    live = rows if program != "decode" else LIVE_DECODE.get(cell, rows)
    for i, n in enumerate(-(-kv_lens // ps)):
        if i >= live:  # an idle seat of the fused call: no page, one token
            continue
        lead = i - i % shared if i < n_dec else i
        doc = held if lead != i and n >= held else 0
        pts[i, :doc] = pts[lead, :doc]
        pts[i, doc:n] = free[off:off + n - doc]
        off += n - doc
    N = eng["max_batch_size"] if program == "decode" else eng["prefill_chunk"]
    return dict(
        cell=cell, program=program, N=N, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        page_size=ps, num_pages=P, pages_per_seq=maxp, kv_lens=kv_lens, cu=cu,
        page_tables=pts, num_seqs=rows,
        ctx_tokens=int(kv_lens[:rows].sum()),
        kv_bytes_per_token=2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2)


def _attn_fn(pa, case, reps, rows_kernel=False):
    import jax

    scale = case["head_dim"] ** -0.5
    # a window layer's call: the impl shifts the page tables itself
    kw = {"sliding_window": case["window"]} if case.get("window") else {}
    if rows_kernel:
        kw["one_query_rows"] = case["program"] == "decode"

    def f(q, cache, pts, lens, cu, ns):
        # the groups once a program, as `forward_core` asks for them
        plan = pa.plan(pts, lens, cu, ns, case["page_size"],
                       heads_per_kv=case["heads"] // case["kv_heads"]
                       ) if rows_kernel else {}

        def body(_, qq):
            o = pa.paged_attention_tpu(qq, cache, pts, None, None, lens,
                                       scale=scale, cu_q_lens=cu, num_seqs=ns,
                                       **kw, **plan)
            return (qq * 0.5 + o * 0.5).astype(qq.dtype)

        return jax.lax.fori_loop(0, reps, body, q)

    return jax.jit(f)


def window_diff(pa, case, operands) -> float:
    """Largest difference, over the call's real rows, between a window
    layer's call as served (page tables shifted by whole KV blocks) and the
    kernel masking by window over the unshifted tables: 0.0 is what makes a
    row's result independent of the chunks its sequence was computed in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    scale, w = case["head_dim"] ** -0.5, case["window"]
    q, cache, pts, lens, cu, ns = operands

    @jax.jit
    def both(q):
        served = pa.paged_attention_tpu(q, cache, pts, None, None, lens,
                                        scale=scale, cu_q_lens=cu,
                                        num_seqs=ns, sliding_window=w)
        # (bq: the chunk rows' where the step makes two calls; any reads alike)
        bkv, bq = pa.step_geometry(q.shape, cache.shape, *pts.shape)[-1]
        masked = pa._kernel()(q, cache, lens, jnp.maximum(pts, 0), cu, ns,
                              sm_scale=scale, sliding_window=w,
                              num_kv_pages_per_block=bkv,
                              num_queries_per_block=bq,
                              vmem_limit_bytes=pa.VMEM_LIMIT)
        return served, masked

    served, masked = both(q)
    n = int(case["cu"][case["num_seqs"]])
    return float(np.abs(np.asarray(served[:n], np.float32)
                        - np.asarray(masked[:n], np.float32)).max())


def _shapes(case, sharding=None):
    import jax
    import jax.numpy as jnp

    B = case["page_tables"].shape[0]
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    return (s((case["N"], case["heads"], case["head_dim"]), jnp.bfloat16),
            s((case["num_pages"], case["page_size"], 2 * case["kv_heads"],
               case["head_dim"]), jnp.bfloat16),
            s(case["page_tables"].shape, jnp.int32), s((B,), jnp.int32),
            s((B + 1,), jnp.int32), s((1,), jnp.int32))


def _operands(case, seed):
    import jax
    import jax.numpy as jnp

    q, cache = _shapes(case)[:2]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, q.shape, q.dtype),
            jax.random.normal(k2, cache.shape, cache.dtype),
            jnp.asarray(case["page_tables"]), jnp.asarray(case["kv_lens"]),
            jnp.asarray(case["cu"]),
            jnp.asarray([case["num_seqs"]], jnp.int32))


def measure(pa, case, calls, reps, operands, chip, group=None):
    """One row of the report: compile (and, with operands, time) the kernel
    calls ``calls`` (one (bkv, bq) pair, or the decode rows' and the chunks').
    ``step_geometry`` is replaced for the trace, so the call goes through
    `paged_attention_tpu` as the engine's does. ``group``: the one-query rows
    on the rows kernel at so many rows a group (0: its own), None: on the
    upstream call."""
    import jax
    import numpy as np

    rule, own = pa.step_geometry, getattr(pa, "GROUP_ROWS", None)
    row = dict(zip(("bkv", "bq"), calls[-1]), split=len(calls) > 1,
               group=group)
    pa.step_geometry = lambda *a, **k: calls
    if group:
        pa.GROUP_ROWS = group
    try:
        # timed apart: a launch pays trace + lowering even on a cache hit
        t0 = time.perf_counter()
        lowered = _attn_fn(pa, case, reps, group is not None).lower(
            *(_shapes(case, chip) if operands is None else operands))
        t1 = time.perf_counter()
        fn = lowered.compile()
        row.update(lower_s=t1 - t0, compile_s=time.perf_counter() - t1)
        if operands is None:
            return row
        out = jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t0)
        us = min(times) / reps * 1e6
        row.update(us_per_call=us, us_per_128_ctx=us / case["ctx_tokens"] * 128,
                   roofline=case["floor_us"] / us,
                   out=np.asarray(out[:case["cu"][case["num_seqs"]]],
                                  np.float32))
    except Exception as e:  # the compiler's words are the result
        row["error"] = f"{type(e).__name__}: {e}"[:400]
    finally:
        pa.step_geometry = rule
        if group:
            pa.GROUP_ROWS = own
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="qwen,mistral")
    ap.add_argument("--programs", default="decode,unified",
                    help="decode, unified, or chunks (a unified step that "
                         "carries prefill chunks only: what the decode rows "
                         "and the chunks each want of bq)")
    ap.add_argument("--bkv", default="4,8,16,32,64")
    ap.add_argument("--bq", default="8,16,32,64")
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--seeds", default="0", help="one drawn batch per seed")
    ap.add_argument("--windows", default="0",
                    help="sliding windows to time each shape with (0 = full "
                         "attention): a window layer's call, page tables "
                         "shifted as forward_core shifts them")
    ap.add_argument("--heads", default="",
                    help="query/KV head counts to put in the cells' place, "
                         "e.g. 24/4,32/4: which property of a layout the "
                         "best pair follows (empty = the configuration's own)")
    ap.add_argument("--split", default="0",
                    help="0, 1 or 0,1: a unified step's rows as one call at "
                         "each pair, or as two (one-query rows at the fused "
                         "decode call's pair, chunks at the pair's bq)")
    ap.add_argument("--shared", type=int, default=0,
                    help="lanes a tenant: consecutive decode rows that name "
                         "the same pages for the traffic's system prompt "
                         "(0: every row owns its pages)")
    ap.add_argument("--groups", default="",
                    help="rows a group to time the rows kernel at beside the "
                         "upstream call, e.g. 4,8,16 (0: the kernel's own)")
    ap.add_argument("--pages-a-turn", default="0",
                    help="page copies a turn of the rows kernel's fetch "
                         "loop, e.g. 4,8,32 (0: the kernel's own)")
    ap.add_argument("--parent", default="",
                    help="the parent commit's ops/paged_attention.py: timed "
                         "first, every row's diff against it")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "attn_sweep.json"))
    args = ap.parse_args()

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    import llmd_tpu.ops.paged_attention as pa
    from llmd_tpu.obs.costmodel import chip_peaks
    from llmd_tpu.ops.row_groups import decode_kv_blocks

    own_turn = pa.PAGES_A_TURN
    turns = [int(t) for t in args.pages_a_turn.split(",")]
    parent = None
    if args.parent:
        import importlib.util

        spec = importlib.util.spec_from_file_location("parent_pa", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    groups = [int(g) for g in args.groups.split(",") if g]

    chip = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        device = "TPU v5e (described, compile only)"
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("attn_sweep: no TPU (use --compile-only here)")
        device = jax.devices()[0].device_kind
    _, peak_gbs = chip_peaks(device)
    if peak_gbs is None:
        raise SystemExit(f"attn_sweep: no peaks for {device!r}")
    print(f"# device: {device}, {peak_gbs:.0f} GB/s", flush=True)

    report = {"device": device, "reps": args.reps, "shapes": []}
    pairs = [(bkv, bq) for bkv in map(int, args.bkv.split(","))
             for bq in map(int, args.bq.split(","))]
    for cell, heads, program, seed, window in itertools.product(
            args.cells.split(","), args.heads.split(","),
            args.programs.split(","), map(int, args.seeds.split(",")),
            map(int, args.windows.split(","))):
        case = build_case(cell, program, seed, heads, args.shared)
        case["window"] = window
        if window:  # what the window needs read (whole pages), and its floor
            from llmd_tpu.models.transformer import window_first_page

            first = window_first_page(case["kv_lens"], np.diff(case["cu"]),
                                      window, case["page_size"])
            case["ctx_tokens"] = int((case["kv_lens"] - first * case["page_size"]
                                      )[:case["num_seqs"]].sum())
        q, cache = _shapes(case)[:2]
        rows = case["page_tables"].shape[0]
        chosen = pa.step_geometry(q.shape, cache.shape, rows,
                                  case["pages_per_seq"])
        case["floor_us"] = (case["ctx_tokens"] * case["kv_bytes_per_token"]
                            / (peak_gbs * 1e9) * 1e6)
        shape = {k: case[k] for k in (
            "cell", "program", "N", "heads", "kv_heads", "page_size",
            "pages_per_seq", "num_seqs", "ctx_tokens", "floor_us", "window")}
        shape.update(seed=seed, rule=[list(c) for c in chosen], results=[])
        print(f"\n## {cell} {case['heads']}/{case['kv_heads']} {program} "
              f"N={case['N']} seed={seed} window={window}: "
              f"{case['num_seqs']} rows, {case['ctx_tokens']} context tokens, "
              f"{case['floor_us']:.0f} us at {peak_gbs:.0f} GB/s; rule "
              f"{pa.format_geometry(chosen)}", flush=True)
        operands = None if args.compile_only else _operands(case, seed)
        # a split call's one-query rows take the fused decode call's bq
        rows_bq = pa.call_geometry((rows, *q.shape[1:]), cache.shape,
                                   case["pages_per_seq"])[1]
        grid = [((bkv, bq),) if split == "0" else ((bkv, rows_bq), (bkv, bq))
                for split in args.split.split(",") for bkv, bq in pairs
                if bkv <= case["pages_per_seq"] and bq <= case["N"]
                and (split == "0" or rows < case["N"])]
        first = None  # the first row's output: every other is compared to it
        todo = [(mod, calls, g, t)
                for calls in grid + [chosen] * (chosen not in grid)
                for mod, g, t in [(parent, None, 0)] * bool(parent)
                + [(pa, None, 0)] + [(pa, g, t) for t in turns for g in groups]]
        for mod, calls, g, t in todo:
            pa.PAGES_A_TURN = t or own_turn
            row = measure(mod, case, calls, args.reps, operands, chip, g)
            row["pages_a_turn"] = pa.PAGES_A_TURN
            name = pa.format_geometry(calls) + (
                " parent" if mod is parent else "" if g is None else
                f" rows G={g or pa.GROUP_ROWS} turn={pa.PAGES_A_TURN}")
            if g is not None:
                row["kv_blocks"] = decode_kv_blocks(
                    case["page_tables"], case["kv_lens"].astype(np.int64),
                    np.diff(case["cu"]) * (np.arange(rows) < case["num_seqs"]),
                    case["page_size"], calls[0][0], g or pa.GROUP_ROWS)
                name += " blocks {}/{}".format(*row["kv_blocks"][::-1])
            mark = " <- rule" if calls == chosen else ""
            if "us_per_call" in row:
                out = row.pop("out")
                first = out if first is None else first
                row["max_diff"] = float(np.abs(out - first).max())
                print(f"{name:>36}: {row['us_per_call']:8.1f} "
                      f"us/call {row['us_per_128_ctx']:6.3f} us/128tok "
                      f"{100 * row['roofline']:5.1f}% of bytes "
                      f"diff {row['max_diff']:.4f} "
                      f"(trace+lower {row['lower_s']:.2f} s, compile "
                      f"{row['compile_s']:.1f} s){mark}", flush=True)
            else:
                said = row.get("error") or (
                    f"trace+lower {row['lower_s']:.2f} s, compiled in "
                    f"{row['compile_s']:.1f} s")
                print(f"{name:>36}: {said}{mark}", flush=True)
            shape["results"].append(row)
        if window and operands is not None:
            shape["served_vs_masked_only"] = window_diff(pa, case, operands)
            print(f"served (shifted by whole KV blocks) vs masked only: "
                  f"max diff {shape['served_vs_masked_only']}", flush=True)
        report["shapes"].append(shape)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    pa.PAGES_A_TURN = own_turn
    print(f"\n# wrote {args.out}")


if __name__ == "__main__":
    main()
