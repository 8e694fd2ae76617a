"""The step programs and their registry.

The engine's forward work is a small zoo of compiled programs (unified mixed
step, speculative verify, fused decode, their masked/ring variants, the
embedding pool). ``build_step_programs`` makes them: it binds ``forward_core``
once to what ``engine/backends.py::resolve`` chose and returns the jitted
bodies, each of which passes only what differs. The two verify programs share
the function that runs the core and unembeds; the two fused decode programs
are one body that takes the pick. The jitted functions' names are read
outside the repo's Python: the benchmark's trace reader finds a program's
device time by its module name (``jit__unified``, ``jit__decode_multi``).

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
* ``read_compiled()`` reads the text of every signature that compiled since
  it was last called and folds it into ``parts`` (``obs/program_parts.py``):
  which part of the model (``MODEL_PARTS``, ``models/parts.py``) each
  instruction of each compiled program belongs to. A registered program is
  a ``StepProgram``: its call notes the arguments' shapes when the jit cache
  grew, and nothing else; the text is read outside ``step()``, by the loop
  that drives the engine.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from llmd_tpu.engine.backends import Backends
from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.engine.sampling import (greedy_tokens, sample_tokens,
                                      sample_tokens_biased)
from llmd_tpu.models.config import ModelConfig
from llmd_tpu.models.parts import MODEL_PARTS, part  # noqa: F401 (the vocabulary)
from llmd_tpu.models.transformer import forward_core, unembed
from llmd_tpu.obs.program_parts import ProgramParts

log = logging.getLogger(__name__)


def _signature(tree):
    """The shapes of a call's arguments, as ``.lower`` takes them: what the
    jit cache keyed the call on (type, weak type, the sharding of a committed
    array), so that lowering them again finds the call's own executable.
    Shapes outlive donation; Python scalars and None stay as they are."""
    def shape(x):
        if not hasattr(x, "shape") or not hasattr(x, "dtype"):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
            sharding=x.sharding if getattr(x, "committed", False) else None)
    return jax.tree.map(shape, tree)


class StepProgram:
    """A registered program's jitted function. Calling it calls the function;
    a call that grew the jit cache (a new signature compiled) leaves the
    arguments' shapes in ``unread`` for ``ProgramRegistry.read_compiled``.
    Everything else (``lower``, ``_cache_size``) is the function's own."""

    def __init__(self, fn: Callable, unread: list) -> None:
        self.fn, self._unread = fn, unread
        self._size = getattr(fn, "_cache_size", None)

    def __call__(self, *args, **kwargs):
        if self._size is None:  # not a jitted function: nothing to read
            return self.fn(*args, **kwargs)
        n = self._size()
        out = self.fn(*args, **kwargs)
        if self._size() != n:
            self._unread.append((self.fn, _signature((args, kwargs))))
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


@dataclass
class ProgramSpec:
    """One registry entry: a compiled program plus its routing metadata."""

    name: str
    fn: Optional[Callable] = None
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
        # (function, argument shapes) of each signature that compiled and
        # whose text has not been read yet; the maps of those that were
        self.unread: list = []
        self.parts = ProgramParts()

    # ----------------------------------------------------------- registration
    def register(self, name: str, fn: Optional[Callable] = None, *,
                 eligible: Optional[Callable[[Any], bool]] = None,
                 run: Optional[Callable[[Any], None]] = None) -> None:
        """Add a program (``fn`` is kept as a ``StepProgram``)."""
        if name in self._specs:
            raise ValueError(f"program {name!r} already registered")
        if fn is not None:
            fn = StepProgram(fn, self.unread)
        self._specs[name] = ProgramSpec(name=name, fn=fn, eligible=eligible,
                                        run=run)
        self._counters[name] = _Counters()

    def fn(self, name: str) -> Optional[Callable]:
        """The registered program's callable, None where none is."""
        spec = self._specs.get(name)
        return spec.fn if spec is not None else None

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

    def read_compiled(self, keep_text: Optional[list] = None) -> int:
        """Read the compiled text of every signature in ``unread`` into
        ``parts``; returns how many were read. Lowering the noted shapes
        again finds the jit's own lowering and its executable (no trace, no
        compile), except under ``compiler_options``, where JAX compiles a
        lowering anew each time it is asked: a load from the persistent
        cache. Called outside ``step()`` by whoever drives the engine
        (``AsyncLLMEngine``'s loop), after a step in which a program
        compiled, which in a served engine is the warm-up. ``keep_text``, a
        list, gets ``(module name, text)`` a signature (the operator's tool).
        A text that cannot be read is logged and skipped: serving goes on."""
        n = 0
        while self.unread:
            fn, (args, kwargs) = self.unread.pop(0)
            try:
                text = fn.lower(*args, **kwargs).compile().as_text()
            except Exception:  # noqa: BLE001: a map must never stop serving
                log.exception("no compiled text of %s",
                              getattr(fn, "__name__", fn))
                continue
            module = self.parts.add(text)
            if keep_text is not None:
                keep_text.append((module, text))
            n += 1
        return n

    def compile_counts(self) -> dict[str, int]:
        """Per-program jit cache sizes (0 for never-traced lazy programs) —
        the recompile-storm probe, now registry-wide."""
        out = {}
        for name, spec in self._specs.items():
            size = getattr(spec.fn, "_cache_size", None)
            if callable(size):
                out[name] = size()
        return out


def build_step_programs(cfg: ModelConfig, engine_cfg: EngineConfig, mesh,
                        backends: Backends, *, use_lora: bool,
                        lora_scale: float) -> dict[str, Callable]:
    """The jitted step programs of an engine, by the name it registers each
    under (``unified_ring`` only where the sp ring is wired). Every program
    takes ``(params, cache, ...)`` and has its cache donated."""
    NT = engine_cfg.batched_tokens
    B = engine_cfg.max_batch_size
    k_steps = max(1, engine_cfg.decode_steps)
    # forward_core as every body calls it: what differs by body is passed
    # there (attn_impl, lora_indices, mm_*, state_slots)
    core = functools.partial(
        forward_core, cfg, moe_matmul_impl=backends.moe_matmul_impl,
        moe_dispatch_impl=backends.moe_dispatch_impl, lora_scale=lora_scale,
        **backends.core_kwargs)

    def _bind(x, *axes):
        """GSPMD sharding constraint by mesh axis names (no-op off-mesh)."""
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))

    def _make_unified(attn_fn):
        def _unified(params, cache, tokens, positions, seq_slots, page_tables,
                     kv_lens, cu_q_lens, num_seqs, lora_tok, prev_sampled,
                     temp, top_k, top_p, key, mm_embeds=None, mm_mask=None,
                     state_slots=None):
            """Flat mixed batch (prefill chunks + decode tokens); returns each
            sequence's last-row logits [B, vocab] and the token picked
            from them [B], by the sampler every program shares
            (``sample_tokens``: argmax alone when no row samples). The
            logits are read only by a batch with a constrained row, whose
            bias the host builds (``_sample_dispatch``).

            The step runs one ahead of the host: a decode row whose input
            token is still on the device packs ``-(row + 1)``, the row it
            had in the previous step, and takes the token from that
            step's ``prev_sampled [B]`` here (zeros after a flush: no
            token is negative then, and the program is the same)."""
            tokens = jnp.where(
                tokens < 0,
                prev_sampled[jnp.clip(-tokens - 1, 0, B - 1)].astype(jnp.int32),
                tokens)
            # flat token dim shards over dp×sp jointly: data-parallel decode
            # rows and sequence-parallel long prefills ride the same constraint
            tokens = _bind(tokens, ("dp", "sp"))
            positions = _bind(positions, ("dp", "sp"))
            seq_slots = _bind(seq_slots, ("dp", "sp"))
            hidden, cache, cnt, drop = core(
                params, cache, tokens, positions, seq_slots, page_tables,
                kv_lens, cu_q_lens=cu_q_lens, num_seqs=num_seqs,
                attn_impl=attn_fn,
                lora_indices=lora_tok if use_lora else None,
                mm_embeds=mm_embeds, mm_mask=mm_mask,
                # rows are in plan order, not seat order: a model with
                # recurrent layers is sent each row's state slot
                state_slots=state_slots,
            )
            last_rows = jnp.clip(cu_q_lens[1 : B + 1] - 1, 0, NT - 1)  # [B]
            logits = unembed(cfg, params, hidden[last_rows])  # [B, vocab]
            with part("sample"):
                sampled = sample_tokens(logits.astype(jnp.float32), key, temp,
                                        top_k, top_p)
            return logits, sampled, cache, cnt, drop

        return _unified

    def _verify_logits(params, cache, tokens, positions, seq_slots,
                       page_tables, kv_lens, cu_q_lens, num_seqs, lora_tok):
        """What both verify programs run: the same flat mixed-batch packing
        as ``_unified`` through the model, and the logits at EVERY packed
        position ``[NT, vocab]``, which never leave the device."""
        tokens = _bind(tokens, ("dp", "sp"))
        positions = _bind(positions, ("dp", "sp"))
        seq_slots = _bind(seq_slots, ("dp", "sp"))
        hidden, cache, cnt, drop = core(
            params, cache, tokens, positions, seq_slots, page_tables,
            kv_lens, cu_q_lens=cu_q_lens, num_seqs=num_seqs,
            attn_impl=backends.attn_impl,
            lora_indices=lora_tok if use_lora else None,
        )
        return unembed(cfg, params, hidden), cache, cnt, drop

    def _verify(params, cache, tokens, positions, seq_slots, page_tables,
                kv_lens, cu_q_lens, num_seqs, lora_tok):
        """Speculative verify: the same flat mixed-batch packing as
        ``_unified``, extended to return the greedy token at EVERY
        packed position instead of only each sequence's last row —
        prompt-lookup drafts are checked against the continuation of
        every chunk position. The [NT, vocab] logits never leave the
        device; the host reads only [NT] int32 argmax tokens."""
        logits, cache, cnt, drop = _verify_logits(
            params, cache, tokens, positions, seq_slots, page_tables,
            kv_lens, cu_q_lens, num_seqs, lora_tok)
        with part("sample"):
            return greedy_tokens(logits), cache, cnt, drop  # [NT]

    def _verify_masked(params, cache, tokens, positions, seq_slots,
                       page_tables, kv_lens, cu_q_lens, num_seqs,
                       lora_tok, fsm0, gidx, bias_tab, next_tab):
        """``_verify`` with the structured-outputs glue fused in: per
        packed position, gather the row's grammar bias at its CURRENT
        FSM state (advanced along the draft via ``next_tab``), apply
        it before the greedy argmax, and return the would-be state
        after each greedy token — so acceptance is computed against
        grammar-legal tokens only and the host adopts the state at
        the last accepted position instead of resyncing the automaton
        (rejected tails roll back FSM state for free, exactly as
        ``_spec_release_tail`` rolls back KV pages).

        ``fsm0/gidx [B]`` are indexed by PACKED ROW (the verify
        plan's order, same as ``sids``), not by slot: ``fsm0`` is the
        state after the row's full committed history — its first
        packed token is the last committed token, so position 0
        masks with ``fsm0`` directly and position j>0 masks with
        ``fsm0`` advanced through draft[0..j-1]. Slot 0 of both
        tables is the zero no-op grammar: unconstrained rows gather
        a zero bias and the f32 cast is monotonic, so their argmax
        is bitwise the unmasked ``greedy_tokens`` result.
        """
        logits, cache, cnt, drop = _verify_logits(
            params, cache, tokens, positions, seq_slots, page_tables,
            kv_lens, cu_q_lens, num_seqs, lora_tok)
        with part("sample"):
            logits = logits.astype(jnp.float32)  # [NT, V]
            valid = positions >= 0  # padding rows must not touch any state
            first = jnp.concatenate(
                [jnp.ones((1,), bool), seq_slots[1:] != seq_slots[:-1]])

            # FSM states depend only on the INPUT draft tokens, not on the
            # argmax results, so a scalar scan over packed positions
            # suffices: each row's running state advances through its own
            # draft (position j masks with the state after draft[0..j-1]).
            def advance(st, x):
                tok, row, is_first, ok = x
                cur = jnp.where(is_first, st[row],
                                next_tab[gidx[row], st[row], tok])
                st = st.at[row].set(jnp.where(ok, cur, st[row]))
                return st, jnp.where(ok, cur, 0)

            _, cur_states = jax.lax.scan(
                advance, fsm0, (tokens, seq_slots, first, valid))
            g_rows = gidx[seq_slots]  # [NT]
            greedy = jnp.argmax(logits + bias_tab[g_rows, cur_states],
                                axis=-1).astype(jnp.int32)
            fsm_next = next_tab[g_rows, cur_states, greedy]  # [NT]
        return greedy, fsm_next, cache, cnt, drop

    def _live_pos(pos, i, steps_left):
        """The positions a fused call's step ``i`` hands the model. A row
        that has spent its steps keeps its position in the carry and
        computes on (its KV write lands on a position it will write
        again); a recurrent layer's state must not take that step, and
        forward_core leaves the slot of a row at position -1 untouched:
        the model with recurrent layers is told so."""
        if not cfg.has_recurrent:
            return pos
        return jnp.where(i < steps_left, pos, -1)

    def _fused_steps(body, carry, steps_left):
        """Run a fused call's ``body(carry, i) -> (carry, (tokens [B],
        expert counts, drops))`` for ``max(steps_left)`` steps, at most
        ``k_steps``: the call is as long as its longest row, which the
        host decides (``decode_call_steps``), and the program is one
        whatever that length. Returns the last carry, the tokens by step
        ``[k_steps, B]`` (zeros from the first step that did not run),
        and the counts and drops summed over the steps that ran."""
        n_steps = jnp.minimum(jnp.max(steps_left), k_steps)
        tok, cnt, drop = jax.eval_shape(
            lambda c: body(c, jnp.int32(0))[1], carry)

        def step(i, st):
            carry, toks_out, cnts, drops = st
            carry, (nxt, cnt, drop) = body(carry, i)
            return (carry, toks_out.at[i].set(nxt), cnts + cnt,
                    drops + drop)

        return jax.lax.fori_loop(
            0, n_steps, step,
            (carry, jnp.zeros((k_steps,) + tok.shape, tok.dtype),
             jnp.zeros(cnt.shape, cnt.dtype),
             jnp.zeros(drop.shape, drop.dtype)))

    def _fused_decode(pick, params, cache, tokens, positions, page_tables,
                      kv_lens, key, steps_left, lora_idx, *extra):
        """Both fused decode programs: up to k decode iterations on the
        device (``_fused_steps``), each step's token fed back as the next
        step's input. ``pick(logits [B, vocab], key, *extra) -> (key, tokens
        [B], *extra)`` draws a step's tokens and advances what else the loop
        carries (``extra``: nothing, or the FSM states); a row whose
        ``steps_left`` is spent holds its position, its length and its
        ``extra``. Returns ``(toks_out, last_toks, pos_out, lens_out,
        *extra_out, cache, cnts, drops)``."""
        tokens = _bind(tokens, "dp")
        positions = _bind(positions, "dp")
        page_tables = _bind(page_tables, "dp", None)
        kv_lens = _bind(kv_lens, "dp")
        seq_slots = jnp.arange(B, dtype=jnp.int32)
        cu = jnp.arange(B + 1, dtype=jnp.int32)
        ns = jnp.array([B], jnp.int32)

        def body(carry, i):
            cache, toks, pos, lens, key, *held = carry
            hidden, cache, cnt, drop = core(
                params, cache, toks, _live_pos(pos, i, steps_left),
                seq_slots, page_tables, lens,
                cu_q_lens=cu, num_seqs=ns,
                attn_impl=backends.attn_decode_impl,
                lora_indices=lora_idx if use_lora else None,
            )
            logits = unembed(cfg, params, hidden)  # [B, vocab]
            with part("sample"):
                key, nxt, *moved = pick(logits, key, *held)
            act = i < steps_left
            held = [jnp.where(act, new, old) for new, old in zip(moved, held)]
            nxt = jnp.where(act, nxt, 0)
            pos = jnp.where(act, pos + 1, pos)
            lens = jnp.where(act, lens + 1, lens)
            return (cache, nxt, pos, lens, key, *held), (nxt, cnt, drop)

        ((cache, last_toks, pos_out, lens_out, _, *extra_out), toks_out, cnts,
         drops) = _fused_steps(
            body, (cache, tokens, positions, kv_lens, key, *extra),
            steps_left)
        # last_toks/pos_out/lens_out: device-resident chain point for the
        # next pipelined call — a chained dispatch reuses them instead of
        # re-packing positions and kv lens on the host
        return (toks_out, last_toks, pos_out, lens_out, *extra_out, cache,
                cnts, drops)

    def _decode_multi(params, cache, tokens, positions, page_tables, kv_lens,
                      temp, top_k, top_p, key, steps_left, lora_idx):
        """Up to k decode iterations fused on-device (``_fused_steps``):
        feed the sampled token back each step; one host round-trip a call
        instead of per token.

        ``steps_left [B]`` caps each row device-side (0 = idle slot): rows
        freeze once their per-row budget (max_tokens / max_model_len
        remaining, clipped to the length the host gave the call) is spent,
        so a fused call may safely overrun a sequence's end — required by
        the pipelined dispatch path, where the host reads results one call
        behind — and the call ends with its longest row.
        """
        def pick(logits, key):
            key, sub = jax.random.split(key)
            return key, sample_tokens(logits, sub, temp, top_k, top_p)

        return _fused_decode(pick, params, cache, tokens, positions,
                             page_tables, kv_lens, key, steps_left, lora_idx)

    def _decode_multi_masked(params, cache, tokens, positions, page_tables,
                             kv_lens, temp, top_k, top_p, key, steps_left,
                             lora_idx, fsm_state, gidx, bias_tab, next_tab):
        """``_decode_multi`` with the structured-outputs glue fused in:
        per step, each row gathers its grammar's bias row at its current
        FSM state from ``bias_tab [G, S, V]``, samples through the same
        biased sampler the host path uses (f32 cast first — bitwise parity
        with ``_sample_dispatch``), and advances its automaton through
        ``next_tab [G, S, V] i32``. Slot 0 of both tables is the zero
        no-op grammar, so unconstrained rows ride along unbiased.

        The FSM state is part of the loop's carry and of the return value:
        a chained dispatch passes the previous call's ``fsm_out`` back in,
        keeping the automaton device-resident for the whole chain. Frozen
        rows (``steps_left`` spent) hold their state, mirroring the
        host-side freeze in ``StructuredState.sync``.
        """
        def pick(logits, key, st):
            logits = logits.astype(jnp.float32)
            row_bias = bias_tab[gidx, st]  # [B, vocab]
            key, sub = jax.random.split(key)
            nxt = sample_tokens_biased(logits, row_bias, sub, temp, top_k,
                                       top_p)
            return key, nxt, next_tab[gidx, st, nxt]  # [B] each

        return _fused_decode(pick, params, cache, tokens, positions,
                             page_tables, kv_lens, key, steps_left, lora_idx,
                             fsm_state)

    def _embed(params, cache, tokens, positions, page_tables, kv_lens,
               cu_q_lens, lora_idx):
        """Prefill chunk returning the sum of valid positions' final hidden
        states — the pooling accumulator for /v1/embeddings."""
        tokens = _bind(tokens, ("dp", "sp"))
        positions = _bind(positions, ("dp", "sp"))
        seq_slots = jnp.zeros_like(tokens)
        hidden, cache, _cnt, _drop = core(
            params, cache, tokens, positions, seq_slots, page_tables,
            kv_lens, cu_q_lens=cu_q_lens, num_seqs=jnp.array([1], jnp.int32),
            attn_impl=backends.attn_impl,
            lora_indices=lora_idx if use_lora else None,
        )
        valid = (positions >= 0).astype(jnp.float32)[:, None]
        return jnp.sum(hidden.astype(jnp.float32) * valid, axis=0), cache

    donate = dict(donate_argnums=(1,))  # cache is donated — updated in place in HBM
    if backends.compiler_options is not None:
        donate["compiler_options"] = backends.compiler_options
    bodies = {"unified": _make_unified(backends.attn_impl), "verify": _verify,
              "verify_masked": _verify_masked, "decode": _decode_multi,
              "decode_masked": _decode_multi_masked, "embed": _embed}
    if backends.ring_attn_impl is not None:
        bodies["unified_ring"] = _make_unified(backends.ring_attn_impl)
    return {name: jax.jit(body, **donate) for name, body in bodies.items()}
