"""The parts of the model a compiled step program's operations belong to.

One vocabulary, ``MODEL_PARTS``. Wherever the model's work is traced
(``models/transformer.py``, ``ops/moe_dispatch.py``, ``ops/sparse_select.py``,
the token choice in ``engine/programs.py``) it runs under a
``jax.named_scope`` of one of these names, ``part(name)``. A scope is
metadata: it moves no instruction, but the compiled executable's text keeps
it as every instruction's ``op_name`` path
(``jit(_unified)/while/body/closed_call/attn_qkv/dot_general``), which the
device trace's events do not carry. ``obs/program_parts.py`` reads that text
once a compiled program and gives every instruction the INNERMOST part on its
path, or ``unscoped``; a trace's ``fusion.<n>`` rows are then device time by
part (``observability/device-plane.md``, "Device time by part of the model").

  embed         token (and multimodal) embedding rows
  norm          a layer's pre-mixer / pre-feed-forward RMSNorm, the final norm
  attn_qkv      attention's input projections, q/k norms, RoPE, the latent
                down- and up-projections of q and of the compressed KV
  kv_write      the scatter of new K/V (or latent) rows into the paged pool
  attn          the attention kernel's call (and its XLA reference's work)
  sparse_select a sparse layer's block scores, top-k and selected tables
  attn_out      attention's output gate and output projection (the latent
                value up-projection with it)
  mixer_in      a recurrent mixer's input projections, conv window and gates
  mixer         the recurrence's kernel call and the state's bookkeeping
  mixer_out     a mixer's output norm, gate and output projection
  ffn           a dense feed-forward (and a mixture's shared expert)
  moe_router    router logits, top-k, selection bias and group limit
  moe_dispatch  the sorted dispatch's plan and row gather
  moe_experts   the experts' grouped products
  moe_combine   the weighted return of the experts' rows
  unembed       the head's logits
  sample        the token choice from the logits

The residual adds, the page-table arithmetic before the layers, a scan's
loop counters and a program's own glue (``prev_sampled``, a fused call's
carry) name no part: they are ``unscoped``.
"""

from __future__ import annotations

import jax

MODEL_PARTS = (
    "embed", "norm", "attn_qkv", "kv_write", "attn", "sparse_select",
    "attn_out", "mixer_in", "mixer", "mixer_out", "ffn", "moe_router",
    "moe_dispatch", "moe_experts", "moe_combine", "unembed", "sample")


def part(name: str):
    """``jax.named_scope(name)`` for a name of ``MODEL_PARTS``."""
    assert name in MODEL_PARTS, name
    return jax.named_scope(name)
