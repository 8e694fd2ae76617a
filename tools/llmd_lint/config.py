"""llmd-lint repo configuration: hot-path set, blocking-call catalog, and the
central allowlist for findings that have no single source line.

Adding a hot-path file
----------------------
``HOT_PATHS`` maps a repo-relative glob to the functions checked in it:
``"*"`` means every function/method in the file is on the hot path (kernels);
a list restricts checking to the named functions plus any name carrying one
of the listed prefixes (``"_spec_"`` covers ``_spec_propose`` etc.). New
per-step or per-request code paths belong here the moment they exist —
docs/static-analysis.md walks through the procedure.
"""

from __future__ import annotations

from .core import AllowEntry

# ------------------------------------------------------------------ hot path
# The compiled-program serving path: one stray host sync or re-jit here costs
# more than any kernel win. engine.py's step/dispatch/verify/sample functions
# and every op kernel are checked; startup/config/loader code is not.
HOT_PATHS: dict[str, object] = {
    "llmd_tpu/ops/*.py": "*",
    "llmd_tpu/engine/engine.py": [
        "step",
        "has_work",
        "_step_",          # _step_unified/_step_decode/_step_spec_verify
        "_decode_dispatch",
        "_decode_process",
        "_decode_ready",
        "_flush_pending_",  # _flush_pending_decode/_flush_pending_sample
        "_sample_dispatch",
        "_sampling_state",
        "_sample_apply",
        "_plan_chain_masks",
        "_stage_chain_masks",
        "_mask_tables",
        "_constrained_needs_unified",
        "_unified_eligible",
        "_run_",           # _run_unified/_run_verify/_run_decode_program
        "_verify_nt",
        "_pack_buf",
        "_spec_",          # propose/try_verify/release_tail
        "_build_bias",
        "_check_finish",
        "_prefilling_seqs",
        "_prefill_target",
        "_emit_step_spans",
        "_trace_exemplar",
    ],
    "llmd_tpu/engine/spec.py": "*",
    # step-program registry: the dispatch/complete ledger and routing run
    # once per engine step.
    "llmd_tpu/engine/programs.py": [
        "record_dispatch",
        "record_complete",
        "route",
        "quiesced",
    ],
    # Hot-path exclusions audit (PR 18): kv/writeback.py is deliberately NOT
    # listed. The only serving-path-adjacent entry point is
    # WritebackQueue.offer (evict/demote tee) — an append under a condition
    # variable with zero socket/device work; every blocking call (store RPC,
    # retry sleep) lives on the dedicated kv-writeback worker thread or in
    # drain-time flushing, which runs in the server's executor off the step
    # loop. DurableStoreClient.probe is router-side (kvplane/plane.py), not
    # engine-step code. If offer() ever grows IO, list the file here.
}

# Direct device->host synchronization spellings. float()/int()/bool() on
# values produced by jnp/jax calls are detected separately by local dataflow.
SYNC_CALL_ATTRS = {"item", "tolist", "block_until_ready"}
SYNC_CALL_NAMES = {
    "np.asarray", "np.array", "np.ascontiguousarray", "numpy.asarray",
    "numpy.array", "jax.device_get",
}

# ------------------------------------------------------------ blocking calls
# Calls that park the holding thread while a lock is held: every other thread
# queueing on that lock inherits the full wait (and time.sleep under an
# asyncio lock stalls the whole event loop).
BLOCKING_CALL_NAMES = {
    "time.sleep", "subprocess.run", "subprocess.check_output",
    "subprocess.check_call", "socket.create_connection",
    "urllib.request.urlopen",
}
BLOCKING_CALL_ATTRS = {"block_until_ready", "sendall", "recv", "urlopen"}
BLOCKING_BARE_NAMES = {"sleep", "urlopen"}  # from-imports of the above

# --------------------------------------------------------- central allowlist
# For findings with no single line to annotate (lock-order cycles, contract
# rows). match is a substring of the finding message; the justification is
# mandatory and echoed by the lint output.
ALLOWLIST: list[AllowEntry] = [
    AllowEntry(
        "lock-unguarded-read", "PoolController.",
        "event-loop confined: every read runs on the controller's loop "
        "between awaits; the asyncio lock only serializes the multi-await "
        "reconcile/retire sections (writes stay lint-enforced)"),
]
