"""JAX start-up for entry points (``engine/serve.py``, ``chip_smoke.py``'s
children, the benchmark's engine child): which platform,
and where compiled programs persist. One rule, one module.

Platform. JAX falls back to the CPU by itself when it finds no accelerator,
and a run that was asked for the chip would then report CPU behaviour under
device names. So the platform is explicit: ``cpu=True`` (tests, CI smokes)
pins the CPU and checks JAX obeyed; otherwise the first device must be a TPU.
Either way a mismatch exits non-zero.

Compile cache. The cache key is the compiled program's own hash, metadata
(scopes, source lines) included, so the directory only has to stay put: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself — nothing is set in
code), otherwise one fixed git-ignored directory in the checkout. A
per-config, per-pid or temporary directory can only guarantee misses.

Stdlib-only at import: ``chip_smoke.py`` and the warm-start probe's parent
resolve the cache path without touching JAX (they must never hold the chip).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
FIXED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory this process's compiled programs persist to."""
    return os.environ.get(ENV_VAR) or FIXED_DIR


def init_jax(cpu: bool):
    """Pin the CPU or require a TPU, then place the compile cache. Call
    before anything initialises a JAX backend. Returns ``jax.devices()[0]``.

    ``python -m llmd_tpu.engine.serve`` has imported jax (through the
    package's ``__init__``) before its ``main`` can run, and JAX reads
    ``JAX_PLATFORMS`` once, at import — so the pin goes through
    ``jax.config``, which holds until the first backend is initialised.
    """
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    want = "cpu" if cpu else "tpu"
    if dev.platform != want:
        raise SystemExit(
            f"asked for the {want.upper()}, JAX initialised {dev.platform!r}"
            + ("" if cpu else "; pass --cpu to run on the CPU"))
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", FIXED_DIR)
    # persist every program: the default one-second compile-time floor would
    # keep the small step programs out of the cache, and a relaunch would
    # then recompile exactly those
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the key holds the program's metadata too. JAX leaves it out by default,
    # so an executable that another tree compiled from the same operations
    # under other scopes (or none) would be loaded with that tree's
    # ``op_name`` paths, and the engine reads which part of the model an
    # instruction belongs to from exactly those (obs/program_parts.py)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return dev
