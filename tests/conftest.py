"""Test harness: force an 8-device virtual CPU mesh so all sharding paths
(tp/dp/ep/sp, shard_map collectives) compile and execute without TPU hardware —
the analogue of the reference's `simulated-accelerators` CI filter
(.github/workflows/ci-kustomize-dry-run.yaml:22-60) and `tpu_chips: 0` mode.

Must run before the first `import jax` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# One OpenMP thread a test process. scikit-learn's gradient boosting (the
# predictor's fits) runs on the libgomp it ships with, a thread a core, and a
# libgomp thread spins while it waits for the next parallel region: six xdist
# workers' pools on eight cores spin against each other (one fit of 2,000
# rows: 10 s alone, not done after 20 min as one of six; 6 s each at one
# thread). No program code reads the name; XLA's CPU pool is not OpenMP.
os.environ.setdefault("OMP_NUM_THREADS", "1")

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def event_loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


def run_async(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()
