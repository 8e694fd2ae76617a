"""Attribute the fused-decode step cost on the real chip (bench.py directive #3).

Builds the llama-1b decode program at bench shapes and times ablated variants:
  full        — forward + unembed + sample (what serving runs)
  no-sample   — forward + unembed + argmax feedback
  no-unembed  — forward only (constant token feedback)
  no-attn     — forward with the attention kernel replaced by identity
                (isolates the paged-attention kernel + KV reads)
  weights-probe — touch every big weight leaf once (HBM roofline probe)

Differences between adjacent variants attribute per-step time to sampling,
unembed, attention, and the matmul body; the probe bounds achievable HBM
bandwidth. --quantize int8 profiles the serving default's weight path.

Usage: python tools/profile_decode.py [--batch 64] [--steps 16] [--kvlen 320]
                                      [--quantize int8]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmd_tpu.obs.costmodel import chip_peaks  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--kvlen", type=int, default=320)
    ap.add_argument("--model", default="llama-1b")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--quantize", default="none", choices=["none", "int8"])
    ap.add_argument("--prefill", type=int, default=None, metavar="NT",
                    help="also time a packed prefill chunk of NT tokens "
                         "(B sequences x NT/B) with and without attention")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from llmd_tpu.engine.sampling import sample_tokens
    from llmd_tpu.models import get_model_config
    from llmd_tpu.models.transformer import (
        forward_core,
        init_cache,
        init_params,
        ragged_paged_attention_xla,
        unembed,
    )

    cfg = get_model_config(args.model)
    B, k, kvlen = args.batch, args.steps, args.kvlen
    ps, num_pages = 16, 2048
    max_pages = 1024 // ps
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        from llmd_tpu.ops.paged_attention import paged_attention_tpu as attn
    else:
        attn = ragged_paged_attention_xla

    params = init_params(cfg, jax.random.PRNGKey(0))
    if args.quantize == "int8":
        from llmd_tpu.models.quant import quantize_params

        params, _ = quantize_params(cfg, params)
    toks0 = jnp.ones((B,), jnp.int32)
    pos0 = jnp.full((B,), kvlen - 1, jnp.int32)
    # disjoint page tables per sequence (row-major page grid)
    import numpy as np

    pts_np = np.full((B, max_pages), -1, np.int32)
    need = (kvlen + k + ps - 1) // ps
    for b in range(B):
        for j in range(need):
            pid = b * need + j
            pts_np[b, j] = pid if pid < num_pages else -1
    pts = jnp.asarray(pts_np)
    lens0 = jnp.full((B,), kvlen, jnp.int32)
    seq_slots = jnp.arange(B, dtype=jnp.int32)
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    ns = jnp.array([B], jnp.int32)
    temp = jnp.zeros((B,), jnp.float32)
    tk = jnp.zeros((B,), jnp.int32)
    tp = jnp.ones((B,), jnp.float32)
    key = jax.random.PRNGKey(1)

    def null_attn(q, cache, pt, positions, seq_slots, kv_lens, *, cu_q_lens,
                  num_seqs, scale, chunk_k=None, chunk_v=None):
        # identity pass-through: keeps the dataflow (so XLA cannot fold the
        # downstream wo matmul away) while skipping the kernel + KV reads
        return q * scale

    def make_fn(mode):
        attn_impl = null_attn if mode == "no-attn" else attn

        def step(params, carry, _):
            cache, toks, pos, lens = carry
            hidden, cache, _, _ = forward_core(
                cfg, params, cache, toks, pos, seq_slots, pts, lens,
                cu_q_lens=cu, num_seqs=ns, attn_impl=attn_impl)
            if mode in ("no-unembed", "no-attn"):
                nxt = toks
            else:
                logits = unembed(cfg, params, hidden)
                if mode == "no-sample":
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                else:
                    nxt = sample_tokens(logits, key, temp, tk, tp)
            return (cache, nxt, pos + 1, lens + 1), nxt

        def fn(params, cache, toks, pos, lens):
            (cache, toks, pos, lens), out = jax.lax.scan(
                lambda c, x: step(params, c, x), (cache, toks, pos, lens),
                None, length=k)
            return out, cache

        return jax.jit(fn, donate_argnums=(1,))

    # shared peak table (obs/costmodel.py): one source of truth for roofline
    # context; (None, None) off-table (CPU) degrades the prints gracefully
    peak_tf, peak_gbs = chip_peaks(jax.devices()[0].device_kind)
    print(f"# {args.model} B={B} k={k} kvlen={kvlen} "
          f"attn={'pallas' if on_tpu else 'xla'} on {jax.devices()[0].device_kind}")
    base = None
    for mode in ["full", "no-sample", "no-unembed", "no-attn"]:
        fn = make_fn(mode)
        cache = init_cache(cfg, num_pages, ps)
        out, cache = fn(params, cache, toks0, pos0, lens0)  # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out, cache = fn(params, cache, toks0, pos0, lens0)
        jax.block_until_ready(out)
        t = (time.perf_counter() - t0) / args.reps
        delta = "" if base is None else f"  (delta {(base - t)/k*1e3:+6.2f} ms/step)"
        if base is None:
            base = t
        print(f"{mode:12s}: {t*1e3:8.2f} ms/call  {t/k*1e3:6.2f} ms/step{delta}")
        del cache

    # Prefill attribution: one packed chunk of B sequences x (NT/B) tokens
    # through forward_core (+ last-row unembed, mirroring the engine's unified
    # step), vs the MXU roofline 2*params*NT. The bench shows prefill at ~18%
    # MFU — this pins whether the loss is the model program or engine overhead,
    # and the no-attn variant splits out the ragged-attention share.
    if args.prefill:
        NT = args.prefill
        T = max(1, NT // B)
        assert T <= kvlen + k, (
            f"--prefill {NT} needs {T} tokens/seq but the page tables cover "
            f"kvlen+k={kvlen + k}; raise --kvlen")
        toks_p = jnp.ones((B * T,), jnp.int32)
        pos_p = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
        slots_p = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
        lens_p = jnp.full((B,), T, jnp.int32)
        cu_p = jnp.arange(B + 1, dtype=jnp.int32) * T
        n_params = sum(int(v.size) for kk, v in params.items()
                       if not kk.endswith("_scale"))
        for mode in ["prefill", "prefill-no-attn"]:
            impl = null_attn if mode == "prefill-no-attn" else attn

            def pf(params, cache, toks):
                hidden, cache, _, _ = forward_core(
                    cfg, params, cache, toks, pos_p, slots_p, pts, lens_p,
                    cu_q_lens=cu_p, num_seqs=ns, attn_impl=impl)
                last = hidden[cu_p[1:] - 1]
                return jnp.argmax(unembed(cfg, params, last), -1), cache

            jpf = jax.jit(pf, donate_argnums=(1,))
            cache = init_cache(cfg, num_pages, ps)
            out, cache = jpf(params, cache, toks_p)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for r in range(args.reps):
                out, cache = jpf(params, cache, toks_p + r + 1)
            jax.block_until_ready(out)
            t = (time.perf_counter() - t0) / args.reps
            tf = 2 * n_params * B * T / 1e12
            mfu = f" ({tf/t/peak_tf*100:.0f}% of {peak_tf:.0f} TF/s)" \
                if peak_tf else ""
            print(f"{mode:16s}: {t*1e3:8.2f} ms for NT={B*T} "
                  f"-> {B*T/t:,.0f} tok/s, {tf/t:.1f} TF/s{mfu}")
            del cache

    # HBM roofline probe: touch every big weight leaf once per call. A traced
    # scalar multiplies each leaf before the reduction so XLA cannot fold the
    # reads away; dtype-agnostic, so it measures the int8 stream under
    # --quantize int8 exactly as decode streams it.
    big = {k: v for k, v in params.items() if v.size * v.dtype.itemsize > 1 << 20}

    @jax.jit
    def wprobe(p, s):
        return sum(jnp.sum(v.astype(jnp.float32) * s) for v in p.values())

    out = wprobe(big, jnp.float32(1.0))
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for r in range(args.reps):
        out = wprobe(big, jnp.float32(2.0 + r))
    jax.block_until_ready(out)
    t = (time.perf_counter() - t0) / args.reps
    gb = sum(v.size * v.dtype.itemsize for v in big.values()) / 1e9
    mbu = f", {gb/t/peak_gbs*100:.0f}% of {peak_gbs:.0f} GB/s" if peak_gbs else ""
    print(f"weights-probe: {t*1e3:8.2f} ms for {gb:.2f} GB -> {gb/t:.0f} GB/s "
          f"({len(big)} leaves{mbu})")


if __name__ == "__main__":
    main()
