#!/usr/bin/env python3
"""The controls of a check on the Mamba-2, attention and non-gated-experts
family (one sublayer a layer, a share of the experts held): what
``control_mla_moe.py`` reads of any mixture family (the reference one
precision lower in the program's place, ``int8``, and a dropped routed copy,
``top_k-1``), and beside them the faults of this family, each the sound stack
under a reference with one mechanism left out or misplaced
(``reference/hybrid_mamba2_moe.py``'s switches):

  no_D             the skip D x dropped
  no_conv_bias     the conv's bias dropped
  gate_after_norm  the gate applied after the grouped norm
  group0_for_all   group 0's B and C read by every head
  bf16_state       the recurrent state rounded to bfloat16 a token
  relu             relu for relu^2 in the experts and the shared expert
  no_shared        the shared expert dropped
  no_scaling       routed_scaling_factor dropped
  bias_in_weights  the selection bias added into the weights too
  absent_computed  the absent experts' copies not masked (they take the bank
                   slot their clipped index names)
  rope_on          RoPE on the attention layers

    chiprun -- python3 perfbench/tests/control_mamba2_moe.py \
        --config perfbench/configs/nemotron-3-nano-30b-a3b.json --seeds 11,12,13

One process, no server, one stack a seed; the readings are
``control_mla_moe.py``'s (``gap_error``, ``argmax_agree``, ``worst_deficit``).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_mla_moe as base  # noqa: E402

FAULTS = (
    ("no_D", {"skip_d": False}),
    ("no_conv_bias", {"conv_bias": False}),
    ("gate_after_norm", {"gate_first": False}),
    ("group0_for_all", {"own_group": False}),
    ("bf16_state", {"state_dtype": "bfloat16"}),
    ("relu", {"act": "relu"}),
    ("no_shared", {"shared": False}),
    ("no_scaling", {"scaling": 1.0}),
    ("bias_in_weights", {"bias_in_weights": True}),
    ("absent_computed", {"absent_left_out": False}),
    ("rope_on", {"attn_rope": True}),
)

if __name__ == "__main__":
    base.FAULTS = FAULTS
    sys.exit(base.main())
