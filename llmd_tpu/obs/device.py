"""Device-plane observability: HBM telemetry, fabric liveness, step watchdog,
and on-demand profiler capture.

The host plane (metrics registry, flight recorder, tracing) sees everything
*around* the accelerator but nothing *inside* it: a device that stops
answering mid-run shows up only as a client timeout, and the pool controller's ``/health`` sweep retires
killed replicas but cannot see an engine whose asyncio loop is alive while
its TPU is hung mid-step. ``DeviceMonitor`` closes that gap with four
coordinated parts, all surfaced through the same metrics/events/health
contracts the rest of the stack already uses:

* **HBM telemetry** — ``device.memory_stats()`` sampled on a poll thread and
  exported as per-device gauges via scrape-time callbacks
  (``llmd_tpu:device_hbm_bytes_in_use|peak_bytes|limit_bytes{device=...}``).
  Backends without memory stats (CPU) simply export no series — never crash.
* **Fabric liveness** — a tiny device op executed on a dedicated worker
  thread under ``LLMD_FABRIC_PROBE_TIMEOUT_S``. A wedged fabric parks the
  worker, not the caller: the scheduler times out, flips
  ``llmd_tpu:device_fabric_alive`` to 0, increments the failure counter, and
  emits a ``fabric_dead`` flight event. The worker finishing later flips it
  back (``fabric_recovered``).
* **Step watchdog** — the engine dispatch loop stamps ``heartbeat()`` once
  per iteration (a bare monotonic attribute write, no lock). A watchdog
  thread seeing pending work with no heartbeat for ``LLMD_WATCHDOG_STALL_S``
  emits ``engine_stalled``, sets the stall gauge, and makes
  ``unhealthy_reason()`` non-None — the engine server turns that into a 503
  ``/health`` with a structured reason, which the PoolController health sweep
  and router circuit breakers already route around. Device fault → automatic
  replica retirement, no new control-plane machinery.
* **Profiler capture** — ``capture_profile(seconds)`` wraps
  ``jax.profiler.start_trace``/``stop_trace`` into ``LLMD_PROFILE_DIR`` (one
  capture at a time; the server returns 409 while busy). The engine loop and
  step are annotated (``llmd.step`` > ``llmd.admit`` / ``llmd.route`` /
  ``llmd.unified`` > ``llmd.unified.<part>`` / ``llmd.decode_dispatch`` /
  ``llmd.decode_process`` / ``llmd.spec_verify`` / ``llmd.mask_build``, and
  ``llmd.loop.*`` between steps; observability/device-plane.md lists them)
  so a capture names host and device time by the same phases and parts the
  step histogram and part counter export. Two ``llmd.clock`` marks carry the
  wall and monotonic clocks onto the trace.

Threading: the watchdog and telemetry threads never touch the engine lock (a
hung ``step()`` holds it — that's the failure being detected). Pending work
is read via an injected ``pending_fn`` whose default is a GIL-atomic dict
truthiness check, and the heartbeat is a bare attribute. Metric mutations and
flight emissions happen *outside* ``self._lock`` — the registry has its own
lock and the scrape path reads our HBM cache through it, so nesting them
would order registry-lock → monitor-lock against monitor-lock →
registry-lock.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from llmd_tpu.obs.metrics import Registry, register_device_metrics

__all__ = ["DeviceMonitor", "ProfileBusy", "default_probe_op"]


def _stop_trace(out_dir: str) -> None:
    """``jax.profiler.stop_trace`` that writes the capture's ``.xplane.pb``
    and nothing else. The public call also converts the capture for the trace
    viewer (``trace.json.gz``), which nothing here reads and which costs a
    quarter as long again as collecting the events: a capture of 8 s of a
    device-bound program (a million device operations) took 127 s to stop on
    the chip's host, some 25 s of it that conversion (PR 34). Where this
    JAX keeps its session elsewhere, the public call is what runs."""
    import socket

    import jax

    try:
        from jax._src import profiler as _jp

        state = _jp._profile_state
        with state.lock:
            if state.profile_session is None:
                raise RuntimeError("No profile started")
            xspace = state.profile_session.stop()
            state.reset()
    except (ImportError, AttributeError):
        jax.profiler.stop_trace()
        return
    run_dir = os.path.join(out_dir, "plugins", "profile",
                           time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, socket.gethostname() + ".xplane.pb"),
              "wb") as f:
        f.write(xspace)


class ProfileBusy(RuntimeError):
    """A profiler capture is already in progress (one window at a time)."""


def default_probe_op() -> None:
    """The in-process liveness op: a tiny multiply forced to completion.

    Small enough to be free on a healthy device (microseconds), but it
    round-trips dispatch → execute → readback, which is exactly the path a
    wedged fabric hangs."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((8, 8), dtype=jnp.float32)
    jax.block_until_ready(x * 2.0)


def _clock_mark() -> dict:
    """Write one ``llmd.clock`` annotation whose arguments are the wall and
    the monotonic clock, read together; returns the same pair."""
    import jax

    mark = {"unix_ns": time.time_ns(), "mono_ns": time.monotonic_ns()}
    with jax.profiler.TraceAnnotation("llmd.clock", **mark):
        pass
    return mark


def _env_f(name: str, default: str) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return float(default)


class DeviceMonitor:
    """Per-replica device-plane monitor. Owned by the engine server that
    created the engine; wide-EP frontends sharing an engine share the
    monitor via ``engine.monitor``."""

    def __init__(self, registry: Registry,
                 flight=None,
                 devices=None,
                 probe_op: Optional[Callable[[], None]] = None,
                 pending_fn: Optional[Callable[[], bool]] = None,
                 stall_s: Optional[float] = None,
                 probe_interval_s: Optional[float] = None,
                 probe_timeout_s: Optional[float] = None,
                 poll_s: Optional[float] = None,
                 profile_dir: Optional[str] = None) -> None:
        self.metrics = register_device_metrics(registry)
        self.flight = flight
        self._devices = devices  # None → jax.local_devices() at start()
        self._probe_op = probe_op or default_probe_op
        self._pending_fn = pending_fn
        self.stall_s = (float(stall_s) if stall_s is not None
                        else _env_f("LLMD_WATCHDOG_STALL_S", "120"))
        self.probe_interval_s = (
            float(probe_interval_s) if probe_interval_s is not None
            else _env_f("LLMD_FABRIC_PROBE_INTERVAL_S", "30"))
        self.probe_timeout_s = (
            float(probe_timeout_s) if probe_timeout_s is not None
            else _env_f("LLMD_FABRIC_PROBE_TIMEOUT_S", "20"))
        self.poll_s = max(0.05, float(poll_s) if poll_s is not None
                          else _env_f("LLMD_DEVICE_POLL_S", "10"))
        self.profile_dir = (profile_dir
                            or os.environ.get("LLMD_PROFILE_DIR",
                                              "/tmp/llmd-profiles"))
        self._lock = threading.Lock()
        # heartbeat: bare monotonic stamp, written lock-free by the dispatch
        # loop (heartbeat()) and read lock-free by the watchdog — a hung
        # step() holds the engine lock, so nothing here may wait on one.
        self._beat = time.monotonic()
        self._stalled = False            # guarded by _lock
        self._stall_age_s = 0.0          # guarded by _lock
        self._fabric_alive = True        # guarded by _lock
        self._hbm: Dict[str, Tuple[float, float, float]] = {}  # guarded by _lock
        self._profiling = False          # guarded by _lock
        self._probe_busy = False   # worker-owned bool; scheduler reads it
        self._probe_result: Tuple[bool, float] = (True, 0.0)
        self._probe_req = threading.Event()
        self._probe_done = threading.Event()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._started = False

    # ---------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._started:
            return
        self._started = True
        if self._devices is None:
            try:
                import jax
                self._devices = list(jax.local_devices())
            except Exception:
                self._devices = []
        self.metrics.fabric_alive.set(1)
        self.metrics.engine_stalled.set(0)
        self.metrics.heartbeat_age.set_function(
            lambda: max(0.0, time.monotonic() - self._beat))
        self.metrics.hbm_bytes_in_use.set_labels_function(
            lambda: self._hbm_field(0))
        self.metrics.hbm_peak_bytes.set_labels_function(
            lambda: self._hbm_field(1))
        self.metrics.hbm_limit_bytes.set_labels_function(
            lambda: self._hbm_field(2))
        if self.stall_s > 0:
            t = threading.Thread(target=self._watchdog_loop,
                                 name="llmd-watchdog", daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._telemetry_loop,
                             name="llmd-device-telemetry", daemon=True)
        t.start()
        self._threads.append(t)
        if self.probe_interval_s > 0:
            t = threading.Thread(target=self._probe_worker,
                                 name="llmd-fabric-probe", daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        # the probe worker may be wedged inside the device op — that is the
        # scenario being monitored — so joins are bounded, never indefinite
        for t in self._threads:
            t.join(timeout=1.0)
        self._threads = []

    # ---------------------------------------------------------- heartbeat
    def heartbeat(self) -> None:
        """Stamped by the engine dispatch loop once per iteration. Bare
        attribute write: must stay lock-free (see module docstring)."""
        self._beat = time.monotonic()

    def unhealthy_reason(self) -> Optional[dict]:
        """Structured health verdict for the engine server's ``/health``:
        None when fine, else a dict the PoolController sweep can log."""
        with self._lock:
            if self._stalled:
                return {"reason": "engine_stalled",
                        "heartbeat_age_s": round(self._stall_age_s, 3),
                        "stall_s": self.stall_s}
            if not self._fabric_alive:
                return {"reason": "fabric_dead",
                        "probe_timeout_s": self.probe_timeout_s}
        return None

    # ----------------------------------------------------------- watchdog
    def _watchdog_loop(self) -> None:
        tick = min(1.0, max(0.05, self.stall_s / 4.0))
        while not self._stop.wait(tick):
            age = time.monotonic() - self._beat
            try:
                pending = bool(self._pending_fn()) if self._pending_fn else False
            except Exception:
                pending = False
            stalled = pending and age >= self.stall_s
            with self._lock:
                was = self._stalled
                self._stalled = stalled
                if stalled:
                    self._stall_age_s = age
            if stalled and not was:
                self.metrics.engine_stalled.set(1)
                self.metrics.engine_stalls.inc()
                if self.flight is not None:
                    self.flight.record_system(
                        "engine_stalled",
                        heartbeat_age_s=round(age, 3), stall_s=self.stall_s)
            elif was and not stalled:
                self.metrics.engine_stalled.set(0)
                if self.flight is not None:
                    self.flight.record_system(
                        "engine_recovered", heartbeat_age_s=round(age, 3))

    # -------------------------------------------------- telemetry + probe
    def _telemetry_loop(self) -> None:
        last_probe = -float("inf")  # probe immediately on startup
        while not self._stop.is_set():
            self._poll_hbm()
            now = time.monotonic()
            if (self.probe_interval_s > 0
                    and now - last_probe >= self.probe_interval_s):
                last_probe = now
                self._run_probe_cycle()
            self._stop.wait(self.poll_s)

    def _poll_hbm(self) -> None:
        samples: Dict[str, Tuple[float, float, float]] = {}
        for d in self._devices or ():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue  # CPU / backends without stats: export nothing
            label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
            samples[label] = (
                float(stats.get("bytes_in_use", 0)),
                float(stats.get("peak_bytes_in_use", 0)),
                float(stats.get("bytes_limit", 0)),
            )
        with self._lock:
            self._hbm = samples

    def _hbm_field(self, idx: int) -> List[Tuple[dict, float]]:
        """Scrape-time callback body for the per-device HBM gauges."""
        with self._lock:
            snap = dict(self._hbm)
        return [({"device": dev}, vals[idx]) for dev, vals in snap.items()]

    def _probe_worker(self) -> None:
        """Persistent worker executing the device op; a wedged fabric parks
        this thread, never the scheduler that timed out waiting on it."""
        while not self._stop.is_set():
            if not self._probe_req.wait(timeout=0.1):
                continue
            self._probe_req.clear()
            self._probe_busy = True
            t0 = time.monotonic()
            try:
                self._probe_op()
                ok = True
            except Exception:
                ok = False
            self._probe_result = (ok, time.monotonic() - t0)
            self._probe_busy = False
            self._probe_done.set()

    def _run_probe_cycle(self) -> None:
        if self._probe_busy:
            # previous probe still wedged inside the device op — don't stack
            # requests, just count the cycle as failed
            self._apply_probe(False, None)
            return
        self._probe_done.clear()
        self._probe_req.set()
        if self._probe_done.wait(timeout=self.probe_timeout_s):
            ok, dt = self._probe_result
            self._apply_probe(ok, dt)
        else:
            self._apply_probe(False, None)

    def _apply_probe(self, ok: bool, dt: Optional[float]) -> None:
        with self._lock:
            was = self._fabric_alive
            self._fabric_alive = ok
        if ok:
            self.metrics.fabric_alive.set(1)
            if dt is not None:
                self.metrics.fabric_probe_seconds.observe(dt)
            if not was and self.flight is not None:
                self.flight.record_system("fabric_recovered")
        else:
            self.metrics.fabric_alive.set(0)
            self.metrics.fabric_probe_failures.inc()
            if was and self.flight is not None:
                self.flight.record_system(
                    "fabric_dead", probe_timeout_s=self.probe_timeout_s)

    # ------------------------------------------------------------ profile
    def capture_profile(self, seconds: float,
                        python_tracer: bool = True) -> dict:
        """Capture one ``jax.profiler`` window into ``profile_dir``.

        Blocking (the caller runs it in an executor); one capture at a time —
        a concurrent call raises :class:`ProfileBusy` and the server maps
        that to 409. Returns ``{dir, files, bytes, seconds, python_tracer,
        clock}`` describing the artifact. ``python_tracer=False`` leaves the
        profiler's Python-frame tracer off: the ``llmd.*`` annotations and
        the device planes are unaffected, and the host runs at nearly its
        untraced speed. ``clock`` holds the two ``llmd.clock`` marks written
        into the trace (``start`` and ``end``, each ``unix_ns`` and
        ``mono_ns`` read together), so flight-recorder events
        (``time.monotonic``) and OTel spans (``time_ns``) can be laid on the
        trace's own clock."""
        seconds = max(0.1, min(float(seconds), 60.0))
        with self._lock:
            if self._profiling:
                raise ProfileBusy("a profiler capture is already in progress")
            self._profiling = True
        try:
            import jax
            out_dir = os.path.join(
                self.profile_dir,
                time.strftime("%Y%m%d-%H%M%S", time.gmtime()))
            os.makedirs(out_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            if not python_tracer:
                options.python_tracer_level = 0
            jax.profiler.start_trace(out_dir, profiler_options=options)
            clock = {}
            try:
                clock["start"] = _clock_mark()
                time.sleep(seconds)
                clock["end"] = _clock_mark()
            finally:
                _stop_trace(out_dir)
            files: List[str] = []
            total = 0
            for root, _dirs, names in os.walk(out_dir):
                for name in names:
                    path = os.path.join(root, name)
                    files.append(os.path.relpath(path, out_dir))
                    total += os.path.getsize(path)
            self.metrics.profile_captures.inc()
            if self.flight is not None:
                self.flight.record_system(
                    "profile_capture", seconds=seconds, dir=out_dir,
                    files=len(files), bytes=total)
            return {"dir": out_dir, "files": sorted(files), "bytes": total,
                    "seconds": seconds, "python_tracer": python_tracer,
                    "clock": clock}
        finally:
            with self._lock:
                self._profiling = False
