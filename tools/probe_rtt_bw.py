"""Separate per-call dispatch overhead from true HBM bandwidth on the chip.

Every blocking jit call pays a host<->device round trip, so a naive rep-loop
timing (tools/membw.py) mixes dispatch overhead into the bandwidth figure.
This probe:

  1. times a trivial jit call (scalar add on fresh inputs) -> per-call floor
  2. runs K chained full-weight reads inside ONE jit via lax.scan, with the
     carry feeding each read so nothing folds or caches; fits T(K) = a + b*K
     -> b is the true per-pass HBM read time for the model-sized weights.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")


def main() -> None:
    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    print(f"# {dev.device_kind}")

    # 1. per-call floor: dispatch + execute + scalar readback
    f = jax.jit(lambda x: x * 1.000001 + 1.0)
    x = jnp.float32(0.0)
    x = f(x)
    jax.device_get(x)
    for _ in range(3):
        t0 = time.perf_counter()
        x = f(x)
        jax.device_get(x)
        print(f"trivial-call: {(time.perf_counter() - t0)*1e3:7.2f} ms")

    # 2. K chained weight reads in one call (llama-1b-ish: 1.04 GB of bf16)
    n = int(1.04e9)
    w = jnp.arange(n, dtype=jnp.int32).astype(jnp.bfloat16)  # 2.08 GB

    def reads(w, seed, K):
        def body(c, _):
            # c perturbs the read so iterations are serialized & unfoldable
            return jnp.sum((w[:: 1024 * 1024] + c).astype(jnp.float32)) * 1e-9 + jnp.sum(
                w.astype(jnp.float32).reshape(-1, 1024).sum(axis=0)
            ) * 1e-12 + c * 0.5, None

        c, _ = lax.scan(body, seed, None, length=K)
        return c

    results = []
    for K in (1, 4, 16):
        g = jax.jit(lambda w, s, K=K: reads(w, s, K))
        s = jnp.float32(0.1)
        jax.device_get(g(w, s))  # compile
        times = []
        for rep in range(3):
            s = jnp.float32(0.1 + rep * 0.01)
            t0 = time.perf_counter()
            jax.device_get(g(w, s))
            times.append(time.perf_counter() - t0)
        dt = min(times)
        results.append((K, dt))
        print(f"K={K:3d} chained 2.08 GB reads: {dt*1e3:8.2f} ms")

    (k0, t0_), (k1, t1_) = results[0], results[-1]
    b = (t1_ - t0_) / (k1 - k0)
    a = t0_ - b * k0
    print(f"fit: per-call overhead {a*1e3:.1f} ms, per-2.08GB-read {b*1e3:.2f} ms "
          f"-> {2.08/b:.0f} GB/s effective HBM read")


if __name__ == "__main__":
    main()
