"""Bring-up contracts: which platform JAX runs on and where the compile cache
lives (``llmd_tpu/jax_init.py``), and a step loop that
dies loudly (failed streams, refused requests, a process that exits
non-zero) instead of leaving a healthy-looking server in front of a dead
engine."""

import asyncio
import os
import tempfile
import threading

import jax
import pytest

from llmd_tpu import jax_init
from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine.async_engine import AsyncLLMEngine, EngineDeadError
from llmd_tpu.obs.metrics import Registry, register_engine_metrics
from tests.conftest import run_async


# ------------------------------------------------------------ platform pin
@pytest.fixture
def jax_config_restored():
    keys = ("jax_platforms", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield before
    for k, v in before.items():
        jax.config.update(k, v)


def test_init_jax_pins_the_cpu_after_jax_was_imported(
        monkeypatch, jax_config_restored):
    """``python -m llmd_tpu.engine.serve --cpu`` has imported jax before
    main() runs, and JAX reads JAX_PLATFORMS once, at import: the pin has
    to land in jax.config, not in the environment."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    jax.config.update("jax_platforms", None)
    dev = jax_init.init_jax(cpu=True)
    assert jax.config.jax_platforms == "cpu"
    assert dev.platform == "cpu"
    assert "JAX_PLATFORMS" not in os.environ


class _Dev:
    def __init__(self, platform):
        self.platform = platform
        self.device_kind = platform


@pytest.mark.parametrize("cpu,found", [(True, "tpu"), (False, "cpu")])
def test_init_jax_exits_when_jax_picked_another_platform(
        monkeypatch, jax_config_restored, cpu, found):
    """--cpu on a host whose backend is already the chip, or no --cpu where
    JAX fell back to the CPU: exit non-zero, never carry on."""
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(found)])
    with pytest.raises(SystemExit, match=f"initialised '{found}'"):
        jax_init.init_jax(cpu=cpu)


# ------------------------------------------------------------ compile cache
def test_compile_cache_env_wins_and_code_sets_nothing(
        monkeypatch, tmp_path, jax_config_restored):
    monkeypatch.setenv(jax_init.ENV_VAR, str(tmp_path))
    assert jax_init.compile_cache_dir() == str(tmp_path)
    jax_init.init_jax(cpu=True)
    # the directory is JAX's own reading of the variable, not ours
    assert (jax.config.jax_compilation_cache_dir
            == jax_config_restored["jax_compilation_cache_dir"])


def test_compile_cache_fixed_path_otherwise(monkeypatch, jax_config_restored):
    monkeypatch.delenv(jax_init.ENV_VAR, raising=False)
    jax_init.init_jax(cpu=True)
    path = jax_init.compile_cache_dir()
    assert path == jax_init.FIXED_DIR == jax.config.jax_compilation_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # one fixed git-ignored place in the checkout: never a temp dir, never
    # a name made from the pid (either only guarantees misses)
    assert path == os.path.join(root, ".jax_cache")
    assert not path.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in path
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ------------------------------------------------------------ fatal step
class _RaisingEngine:
    """The smallest engine surface AsyncLLMEngine drives; step() raises the
    way a Mosaic refusal at the first real step does."""

    def __init__(self):
        self.seqs = {}
        self.monitor = None
        self.metrics = register_engine_metrics(Registry())

    def add_request(self, rid, *a, **kw):
        self.seqs[rid] = object()

    def has_work(self):
        return bool(self.seqs)

    def step(self):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    def abort(self, rid):
        raise AssertionError("abort() on a dead engine")


def test_raising_step_fails_streams_and_refuses_new_requests():
    eng = AsyncLLMEngine(_RaisingEngine())
    fatal = threading.Event()
    eng.on_fatal = lambda exc: fatal.set()
    eng.start()

    async def one(rid):
        async for _ in eng.generate(rid, [1, 2, 3], SamplingParams()):
            pass

    async def main():
        # an open stream fails with the step's error instead of hanging
        with pytest.raises(EngineDeadError, match="Mosaic failed"):
            await asyncio.wait_for(one("a"), timeout=10)
        # and a later request is refused at admission
        with pytest.raises(EngineDeadError):
            await asyncio.wait_for(one("b"), timeout=10)

    run_async(main())
    assert fatal.wait(timeout=10)
    assert isinstance(eng.fatal, RuntimeError)
    eng._thread.join(timeout=10)
    assert not eng._thread.is_alive()


def test_server_process_exits_nonzero_when_the_step_loop_dies(monkeypatch):
    from llmd_tpu.engine import serve

    class Exited(BaseException):
        pass

    codes, stopped = [], []

    def fake_exit(code):
        codes.append(code)
        raise Exited

    monkeypatch.setattr(serve.os, "_exit", fake_exit)
    eng = AsyncLLMEngine(_RaisingEngine())

    async def stop():
        stopped.append(True)

    async def main():
        waiter = asyncio.ensure_future(serve._serve_until_fatal(eng, stop))
        await asyncio.sleep(0.05)
        assert not waiter.done()  # a healthy loop serves forever
        eng._die(RuntimeError("device fault"))
        with pytest.raises(Exited):
            await asyncio.wait_for(waiter, timeout=10)

    run_async(main())
    assert codes == [1] and stopped == [True]
