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
