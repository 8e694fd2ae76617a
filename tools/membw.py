"""Raw HBM bandwidth + decode-matmul microbenchmarks (roofline calibration).

Measures what the chip actually delivers: pure streaming reads (sum over a big
bf16 array), and the decode-shaped matmul [B, D] x [D, V] at serving sizes.
A weights-bandwidth utilization is only meaningful against the measured number.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmd_tpu.obs.costmodel import chip_peaks  # noqa: E402


def t(fn, *a, n=10):
    import jax

    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> None:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    # shared peak table (obs/costmodel.py) for %-of-peak context; off-table
    # device kinds (CPU) get (None, None) and the bare numbers
    peak_tf, peak_gbs = chip_peaks(dev.device_kind)
    hdr = f" (peak ~{peak_gbs:.0f} GB/s HBM, {peak_tf:.0f} TF/s)" if peak_gbs else ""
    print(f"# {dev.device_kind}{hdr}")

    def pct(gbs: float) -> str:
        return f"  ({gbs/peak_gbs*100:.0f}% of peak)" if peak_gbs else ""

    for gb in (0.5, 2.0):
        n = int(gb * 1e9 / 2)
        x = jnp.ones((n,), jnp.bfloat16)

        f = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
        dt = t(f, x)
        print(f"stream-sum {gb:4.1f} GB bf16: {dt*1e3:7.2f} ms -> "
              f"{gb/dt:6.0f} GB/s{pct(gb/dt)}")
        del x

    for B in (1, 8, 32, 128):
        D, V = 2048, 32768
        x = jnp.ones((B, D), jnp.bfloat16)
        w = jnp.ones((D, V), jnp.bfloat16)
        f = jax.jit(lambda x, w: x @ w)
        dt = t(f, x, w)
        gb = D * V * 2 / 1e9
        print(f"matmul [{B:3d},{D}]x[{D},{V}]: {dt*1e3:7.2f} ms -> "
              f"{gb/dt:6.0f} GB/s weights-stream{pct(gb/dt)}")

    # stacked per-layer weights, scan-style matmul (decode body shape)
    L, D, F = 16, 2048, 8192
    w = jnp.ones((L, D, 2 * F), jnp.bfloat16)
    x = jnp.ones((32, D), jnp.bfloat16)

    def scan_mm(x, w):
        def body(c, wl):
            y = x @ wl
            return c + jnp.sum(y[:, :D] * 0) , None
        import jax.lax as lax
        c, _ = lax.scan(body, jnp.zeros((), jnp.float32), w)
        return c

    f = jax.jit(scan_mm)
    dt = t(f, x, w)
    gb = L * D * 2 * F * 2 / 1e9
    print(f"scan-matmul [32,{D}]x[{L},{D},{2*F}]: {dt*1e3:7.2f} ms -> "
          f"{gb/dt:6.0f} GB/s{pct(gb/dt)}")


if __name__ == "__main__":
    main()
