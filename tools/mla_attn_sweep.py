"""Sweep the latent-attention kernel's block geometry at the served shapes,
and hold it against the XLA reference on the chip.

Times `ops.mla_attention.mla_paged_attention` as the engine calls it (a bf16
single-plane pool ``[P, 16, 1, 640]``, page tables as wide as the model
length) at the two shapes ``glm47flash-docs`` serves: the fused decode call
(64 query rows, one a sequence, contexts of 16.4k-19.5k tokens) and the
unified step (decode rows, then one chunk of a question behind its document,
of 128 and of 256 tokens). Every (bkv, bq) pair is two Mosaic compiles and
``--reps`` calls of each: the kernel as the engine binds it (a chunk's query
block folded to the model's 20 heads, the weighted sum over the 512 value
lanes: ``us_a_call``) and the same kernel told of 32 heads and of no rank,
which is the kernel as it was before PR 45 (heads padded in the fold, the
weighted sum over all 640 lanes: ``us_padded``, kept here as the comparison);
``diff`` is the largest difference between the two in the value lanes (must
be 0.0). Beside them the call's byte and operation floors
(`perfbench/kernels/mla_attention.py`: what the benchmark's roofline metrics
divide by). ``*`` marks the rule's pair (`pick_block_sizes`).

``--shared <lanes a document>`` lays the rows out as the cell's traffic does
(every so many consecutive rows name the same pages for their first 16,384
tokens and own the rest; without it every row owns random pages, which no
group can form over), ``--groups 4,8`` times the kernel at those
``GROUP_ROWS``, and ``--parent <file>`` (the parent commit's
``ops/mla_attention.py``, e.g. ``git show HEAD~1:llmd_tpu/ops/mla_attention.py
> .scratch/parent/mla_attention.py``) times that file in the padded kernel's
place in the same chip call, every row's ``diff`` against it (must be 0.0);
``kv_blocks`` is what the counter's twin books for the layout (blocks once a
row, blocks fetched), ``--shapes decode`` keeps to one shape.

Before the sweep, four checks that need the chip: the kernel against the XLA
gather on a short mixed batch (bf16; the largest difference and the
reference's own scale), the same batch through the padded kernel, a chunk
computed whole against the same chunk in two calls, and a decode row through
the decode call's geometry against the same row through the unified step's
(the last three bit for bit: a token must not depend on the rows or lanes
beside it, on its chunking or on the program that decoded it).

    python tools/mla_attn_sweep.py --bkv 64         # on the chip, ~4 min
    python tools/mla_attn_sweep.py --bkv 64 --bq 16 --shared 4 --groups 4,8 \
        --parent .scratch/parent/mla_attention.py   # ~4 min
    python tools/mla_attn_sweep.py --compile-only   # here: what Mosaic takes
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

H, DHP, RANK, ROPE, PS = 20, 640, 512, 64, 16
SCALE = (192 + 64) ** -0.5


DOC = 16384  # tokens of a shared document (`docs-sessions-closed`)


def batch(rng, np, q_lens, kv_lens, N, B, maxp, pages, shared=0):
    """(page_tables, positions, seq_slots, kv_lens, cu_q_lens, num_seqs) of a
    flat batch whose rows own random pages (disjoint while the pool lasts:
    1.1 M tokens of context over a pool of 459k). With ``shared`` lanes a
    document, every ``shared`` consecutive rows name the same pages for their
    first ``DOC`` tokens, as the cell's rows behind one cached document do,
    and own the rest."""
    pt = np.full((B, maxp), -1, np.int32)
    perm, at = rng.permutation(pages), 0
    for b, kl in enumerate(kv_lens):
        n, lead = -(-kl // PS), b - b % shared if shared else b
        doc = DOC // PS if lead != b and min(kl, kv_lens[lead]) >= DOC else 0
        pt[b, :doc] = pt[lead, :doc]
        pt[b, doc:n] = perm[(at + np.arange(n - doc)) % pages]
        at += n - doc
    cu = np.zeros(B + 1, np.int32)
    cu[1:len(q_lens) + 1] = np.cumsum(q_lens)
    cu[len(q_lens) + 1:] = cu[len(q_lens)]
    kl = np.zeros(B, np.int32)
    kl[:len(kv_lens)] = kv_lens
    pos, slots = np.full(N, -1, np.int32), np.zeros(N, np.int32)
    for b, (ql, k) in enumerate(zip(q_lens, kv_lens)):
        pos[cu[b]:cu[b + 1]] = np.arange(k - ql, k)
        slots[cu[b]:cu[b + 1]] = b
    return pt, pos, slots, kl, cu, np.asarray([len(q_lens)], np.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bkv", default="16,32,64")
    ap.add_argument("--bq", default="8,16,32")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shapes", default="decode,unified128,unified256")
    ap.add_argument("--shared", type=int, default=0,
                    help="lanes a document: rows that share their first "
                    "16,384 tokens' pages (0: every row owns its pages)")
    ap.add_argument("--groups", default="",
                    help="one-query rows a group to time, e.g. 4,8 "
                    "(default: the kernel's own GROUP_ROWS)")
    ap.add_argument("--parent", default="",
                    help="the parent commit's ops/mla_attention.py: timed in "
                    "the padded kernel's place, every row's diff against it")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--checks-only", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        from llmd_tpu.jax_init import init_jax

        init_jax(args.cpu)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kernels.mla_attention import cost
    from llmd_tpu.models.transformer import ragged_paged_attention_xla
    from llmd_tpu.ops import mla_attention as mod
    from llmd_tpu.ops.row_groups import decode_kv_blocks

    rule = mod.pick_block_sizes
    rng = np.random.default_rng(0)
    B, maxp, pages = 64, 1280, 28672
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]

    def call(q, pool, b, interpret=False, rank=RANK, mod=mod):
        pt, pos, slots, kl, cu, ns = (jnp.asarray(a) for a in b)
        return mod.mla_paged_attention(
            q, pool, pt, pos, slots, kl, scale=SCALE, cu_q_lens=cu,
            num_seqs=ns, rank=rank, interpret=interpret)

    def padded(q, pool, b, interpret=False):
        """The kernel as it was before PR 45: 32 heads, every lane a value."""
        return call(jnp.pad(q, ((0, 0), (0, mod.HEAD_TILE - H), (0, 0))),
                    pool, b, interpret, rank=None)[:, :H]

    # what a timed row is held against: the parent commit's file, or the
    # padded kernel
    versus, other = "padded", padded
    if args.parent:
        import importlib.util

        spec = importlib.util.spec_from_file_location("parent_mla",
                                                      args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        versus, other = "parent", functools.partial(call, mod=parent)

    def geometry(bkv, bq):
        for m in (mod, parent) if args.parent else (mod,):
            m.pick_block_sizes = lambda n, rows, ps, mp: (
                bkv, 1 if n <= rows else min(bq, n))

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

        b = batch(rng, np, [1] * 63 + [128], [17000] * 64, 256, B, maxp, pages)
        for bkv in map(int, args.bkv.split(",")):
            for bq in map(int, args.bq.split(",")):
                geometry(bkv, bq)
                t = time.time()
                try:
                    jax.jit(lambda q, pool, *b: call(q, pool, b)).lower(
                        jax.ShapeDtypeStruct((256, H, DHP), jnp.bfloat16,
                                             sharding=one),
                        jax.ShapeDtypeStruct((pages, PS, 1, DHP),
                                             jnp.bfloat16, sharding=one),
                        *map(sds, b)).compile()
                    print(json.dumps({"bkv": bkv, "bq": bq, "compiled_s":
                                      round(time.time() - t, 1)}), flush=True)
                except Exception as e:  # noqa: BLE001: the compiler's words
                    print(json.dumps({"bkv": bkv, "bq": bq,
                                      "refused": str(e)[-400:]}), flush=True)
        return 0

    interp = jax.default_backend() == "cpu"
    pool = jnp.zeros((pages, PS, 1, DHP), jnp.bfloat16).at[..., :RANK + ROPE].set(
        jnp.asarray(rng.standard_normal((pages, PS, 1, RANK + ROPE)),
                    jnp.bfloat16))

    def queries(n):
        q = np.zeros((n, H, DHP), np.float32)
        q[..., :RANK + ROPE] = rng.standard_normal((n, H, RANK + ROPE))
        return jnp.asarray(q, jnp.bfloat16)

    # (1) against the XLA gather, on short contexts (it gathers max_model_len)
    sp = 64
    q_lens, kv_lens = [1, 1, 40, 1, 23], [700, 16, 1000, 513, 23]
    b = batch(rng, np, q_lens, kv_lens, 128, 8, sp, pages)
    q = queries(128)
    want = ragged_paged_attention_xla(
        q, pool, *map(jnp.asarray, b[:4]), scale=SCALE,
        cu_q_lens=jnp.asarray(b[4]), num_seqs=jnp.asarray(b[5]))
    got = call(q, pool, b, interp)
    n = int(b[4][len(q_lens)])
    print(json.dumps({"check": "kernel_vs_xla_bf16", "max_abs_diff": float(
        jnp.abs(got[:n, :, :RANK].astype(jnp.float32)
                - want[:n, :, :RANK].astype(jnp.float32)).max()),
        "reference_abs_mean": float(jnp.abs(want[:n].astype(
            jnp.float32)).mean()),
        "rows_no_sequence_owns_are_zero": bool(
            (got[n:] == 0).all())}), flush=True)
    old = padded(q, pool, b, interp)
    print(json.dumps({
        "check": "real_extents_vs_padded",
        "same_bits_in_the_value_lanes": bool(
            (got[..., :RANK] == old[..., :RANK]).all()),
        "zeros_past_them": bool((got[..., RANK:] == 0).all()),
        "padded_rope_lanes_abs_max": float(jnp.abs(
            old[:n, :, RANK:RANK + ROPE].astype(jnp.float32)).max())}),
        flush=True)
    # (2) a chunk whole and in two calls; (3) a decode row by both programs
    rng2 = np.random.default_rng(1)
    one = batch(rng2, np, [40], [1000], 128, 8, sp, pages)
    rng2 = np.random.default_rng(1)
    a = batch(rng2, np, [17], [977], 128, 8, sp, pages)
    qq = jnp.zeros((128, H, DHP), jnp.bfloat16)
    whole = call(qq.at[:40].set(q[:40]), pool, one, interp)[:40]
    first = call(qq.at[:17].set(q[:17]), pool, a, interp)[:17]
    second = call(qq.at[:23].set(q[17:40]), pool,
                  (one[0],) + batch(np.random.default_rng(1), np, [23], [1000],
                                    128, 8, sp, pages)[1:], interp)[:23]
    dec = batch(np.random.default_rng(2), np, [1] * 8, [700 + 37 * i for i in
                                                        range(8)], 8, 8, sp,
                pages)
    uni = batch(np.random.default_rng(2), np, [1] * 8, [700 + 37 * i for i in
                                                        range(8)], 128, 8, sp,
                pages)
    d = call(q[:8], pool, dec, interp)
    u = call(qq.at[:8].set(q[:8]), pool, uni, interp)[:8]
    print(json.dumps({
        "check": "bit_for_bit",
        "chunk_whole_equals_two_calls": bool(
            (jnp.concatenate([first, second]) == whole).all()),
        "decode_call_equals_unified_step": bool((d == u).all())}), flush=True)

    if args.checks_only:
        return 0
    # the sweep, at the cell's shapes
    ctx = [int(c) for c in rng.integers(16448, 19456, size=64)]
    shapes = {"decode": ([1] * 64, ctx, 64),
              "unified128": ([1] * 63 + [128], ctx, 256),
              "unified256": ([1] * 63 + [256], ctx, 320)}
    own_groups = mod.GROUP_ROWS
    groups = [int(g) for g in args.groups.split(",") if g] or [own_groups]
    for name, (q_lens, kv_lens, N) in shapes.items():
        if name not in args.shapes.split(","):
            continue
        b = batch(rng, np, q_lens, kv_lens, N, B, maxp, pages, args.shared)
        q = queries(N)
        # the bytes' demand: a shared document's tokens once
        first = [int(p) for p in b[0][:len(kv_lens), 0]]
        S = float(sum(kv_lens) - DOC * (len(first) - len(set(first))))
        Q = float(sum(q_lens))
        P = float(sum(ql * kl - ql * (ql - 1) // 2
                      for ql, kl in zip(q_lens, kv_lens)))
        ops, byts = cost(float(sum(kv_lens)), Q, P, H, RANK, ROPE,
                         unique_ctx=S)
        floors = {"bytes_us": byts / peaks["hbm_bytes_per_s"] * 1e6,
                  "ops_us": ops / peaks["bf16_flops"] * 1e6}
        print(json.dumps({"shape": name, "context_tokens": S, "queries": Q,
                          "pairs": P, "shared": args.shared, **floors}),
              flush=True)
        bqs = [1] if name == "decode" else list(map(int, args.bq.split(",")))
        for bkv, bq, G in ((bkv, bq, G)
                           for bkv in map(int, args.bkv.split(","))
                           for bq in bqs for G in groups):
            geometry(bkv, bq)
            mod.GROUP_ROWS = G
            try:
                us, first_s, outs = {}, {}, {}
                for kind, fn in (("a_call", call), (versus, other)):
                    f = jax.jit(functools.partial(fn, b=b,
                                                  interpret=interp))
                    t = time.time()
                    f(q, pool).block_until_ready()
                    first_s[kind] = round(time.time() - t, 2)
                    t = time.time()
                    for _ in range(args.reps):
                        out = f(q, pool)
                    out.block_until_ready()
                    us[kind] = (time.time() - t) / args.reps * 1e6
                    outs[kind] = out[..., :RANK].astype(jnp.float32)
                mark = "*" if (bkv, bq) == rule(N, B, PS, maxp) else ""
                rows, fetched = decode_kv_blocks(
                    b[0], b[3], np.diff(b[4]), PS, bkv, G)
                print(json.dumps({
                    "shape": name, "bkv": bkv, "bq": bq, "rule": mark,
                    "G": G, "kv_blocks": [rows, fetched],
                    "us_a_call": round(us["a_call"], 1),
                    "us_" + versus: round(us[versus], 1),
                    "diff": float(jnp.abs(outs["a_call"]
                                          - outs[versus]).max()),
                    "first_call_s": first_s,
                    "roofline_share": round(
                        max(floors.values()) / us["a_call"], 3)}),
                    flush=True)
            except Exception as e:  # noqa: BLE001: the compiler's words
                print(json.dumps({"shape": name, "bkv": bkv, "bq": bq,
                                  "refused": str(e)[-400:]}), flush=True)
    mod.pick_block_sizes, mod.GROUP_ROWS = rule, own_groups
    return 0


if __name__ == "__main__":
    sys.exit(main())
