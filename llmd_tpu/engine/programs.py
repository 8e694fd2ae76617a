"""Step-program registry: compiled-program lifecycle + per-step routing.

The engine's forward work is a small zoo of compiled programs (unified mixed
step, speculative verify, fused decode, their masked/ring variants, the
embedding pool). Before this module, each arrived with ad-hoc wiring: an
``if``-ladder in ``step()`` picked which one ran, attention-impl selection
for the fused-decode shape lived in a private engine method, and the quiesce
invariant tracked exactly one program pair (``n_decode_dispatches ==
n_decode_calls``). Adding a program meant touching all three.

``ProgramRegistry`` makes the set declarative:

* ``register(name, fn, ...)`` stores a compiled (jitted) callable plus its
  routing metadata — an *eligibility predicate* over the engine and a *run*
  hook. jax.jit is lazy, so registering a program costs nothing until its
  first dispatch (``spec_mode=off`` engines never compile the verify
  programs; unconstrained serving never compiles the masked ones).
* ``route(engine)`` returns the first registered program (registration
  order = priority) whose predicate holds — the whole ``step()`` ladder.
  Programs without a ``run`` hook (masked/ring variants, embed) are
  dispatched *by* a routable program, never routed to directly.
* ``record_dispatch``/``record_complete`` count per-program issue/landing;
  ``quiesced()`` generalizes the PR 12 invariant to every program at once —
  asserted at every drain, it catches any dispatch whose result the host
  never read (a leaked in-flight call).
* ``compile_counts()`` exposes each program's jit cache size, the
  recompile-storm probe ``test_paged_attention.py`` pins for fused decode.

(The fused-decode attention-impl selector that lived here is gone with
PR 39: the ragged Pallas kernels, GQA and latent, serve mixed batches and
one-row-a-sequence calls alike, so every program takes the engine's one impl.)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional


@dataclass
class ProgramSpec:
    """One registry entry: a compiled program plus its routing metadata.

    ``attn`` is provenance only ("mixed" = a unified-shape program,
    "decode" = a fused-decode-shaped one; both bind the engine's one
    attention impl); the actual kernel was bound when the program was traced.
    """

    name: str
    fn: Optional[Callable] = None
    attn: str = "mixed"
    # eligibility predicate over the live engine; None = never routed to
    # directly (the program is dispatched by another program's run hook)
    eligible: Optional[Callable[[Any], bool]] = None
    run: Optional[Callable[[Any], None]] = None


@dataclass
class _Counters:
    dispatched: int = 0
    completed: int = 0


class ProgramRegistry:
    """Ordered program table + per-program dispatch/completion accounting."""

    def __init__(self, on_dispatch: Optional[Callable[[str], None]] = None):
        self._specs: dict[str, ProgramSpec] = {}
        self._counters: dict[str, _Counters] = {}
        self._on_dispatch = on_dispatch

    # ----------------------------------------------------------- registration
    def register(self, name: str, fn: Optional[Callable] = None, *,
                 attn: str = "mixed",
                 eligible: Optional[Callable[[Any], bool]] = None,
                 run: Optional[Callable[[Any], None]] = None) -> Optional[Callable]:
        """Add a program. Returns ``fn`` so the engine can keep its
        ``self._*_fn`` aliases (tests and the hot-path linter key on the
        ``self._*_fn(...)`` call spelling)."""
        if name in self._specs:
            raise ValueError(f"program {name!r} already registered")
        self._specs[name] = ProgramSpec(name=name, fn=fn, attn=attn,
                                        eligible=eligible, run=run)
        self._counters[name] = _Counters()
        return fn

    def fn(self, name: str) -> Optional[Callable]:
        return self._specs[name].fn

    def specs(self) -> list[ProgramSpec]:
        return list(self._specs.values())

    # ---------------------------------------------------------------- routing
    def route(self, engine) -> ProgramSpec:
        """First registered program whose eligibility predicate holds.
        Registration order is the priority order; the last routable program
        must be unconditionally eligible (the engine registers fused decode
        with ``eligible=lambda eng: True``)."""
        for spec in self._specs.values():
            if spec.run is not None and spec.eligible is not None \
                    and spec.eligible(engine):
                return spec
        raise RuntimeError("no eligible step program (registry misconfigured: "
                           "the final routable entry must always be eligible)")

    # ------------------------------------------------------------- accounting
    def record_dispatch(self, name: str) -> None:
        """Count one issued call of ``name``. Unregistered names are allowed
        (pseudo-programs like the unified step's sample read) — counters
        auto-create so the quiesce invariant covers them too."""
        c = self._counters.setdefault(name, _Counters())
        c.dispatched += 1
        if self._on_dispatch is not None:
            self._on_dispatch(name)

    def record_complete(self, name: str) -> None:
        c = self._counters.setdefault(name, _Counters())
        c.completed += 1

    def quiesced(self) -> bool:
        """True iff every program's dispatches have been consumed by the host
        — the generalized PR 12 invariant, asserted at every drain."""
        return all(c.dispatched == c.completed for c in self._counters.values())

    def counters(self) -> dict[str, tuple[int, int]]:
        return {n: (c.dispatched, c.completed)
                for n, c in sorted(self._counters.items())}

    def compile_counts(self) -> dict[str, int]:
        """Per-program jit cache sizes (0 for never-traced lazy programs) —
        the recompile-storm probe, now registry-wide."""
        out = {}
        for name, spec in self._specs.items():
            size = getattr(spec.fn, "_cache_size", None)
            if callable(size):
                out[name] = size()
        return out
