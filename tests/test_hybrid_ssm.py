"""Mamba layers and NoPE attention layers in one stack, a recurrent-state
pool a seat beside the paged KV pool, and the selective scan for mixed steps
and the fused decode call (ISSUE 34), held against the plain float32
reference of the family (``perfbench/reference/hybrid_ssm_gqa.py``) at a tiny
size on the CPU: two periods of (mamba, attention, mamba, mamba), d_inner 256,
d_state 16, pages of 4 tokens.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import replace

import conftest  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the family modules, by path and for the import alone: perfbench/ has a
# tests/ of its own, which must not shadow this package for the other files
sys.path.append(os.path.join(ROOT, "perfbench"))
try:
    from reference import hybrid_ssm_gqa  # noqa: E402
finally:
    sys.path.remove(os.path.join(ROOT, "perfbench"))

from llmd_tpu.core.request import SamplingParams  # noqa: E402
from llmd_tpu.engine import EngineConfig, LLMEngine  # noqa: E402
from llmd_tpu.models.config import ModelConfig  # noqa: E402
from llmd_tpu.models.quant import quantize_params  # noqa: E402
from llmd_tpu.models.transformer import (  # noqa: E402
    forward, forward_core, init_cache, init_params, init_state, unembed)
from llmd_tpu.ops.selective_scan import (  # noqa: E402
    row_flags, selective_scan_pallas, selective_scan_xla)
from llmd_tpu.parallel.mesh import MeshConfig  # noqa: E402

with open(os.path.join(ROOT, "perfbench", "tests", "tiny-jamba.json")) as f:
    CONF = dict(json.load(f), weights={"dtype": "float32", "quantize": None})
CFG = hybrid_ssm_gqa.model_config(CONF)
SIZES = hybrid_ssm_gqa.sizes(CONF)
PS, T = 4, 45  # page size; a sequence that spans several uneven chunks
SEATS, ROWS, MAXP = 4, 4, 16
# The flat token budget of the hand-packed steps. It is larger than any chunk
# they pack, so that no token sits in the last rows of the array: XLA's CPU
# backend runs the tail of a vectorised elementwise loop through another code
# path (no fused multiply-add), and a token there reads one unit in the last
# place off the same token elsewhere. That is the CPU compiler's, not the
# program's: on a budget a chunk fills to its last row, the states of two
# chunkings differ by 3e-8 in the first mamba layer and nowhere before it.
NT = 48
# float32 on both sides: what is left is the order of the sums. Read on the
# CPU over three seeds of weights and tokens (0, 1, 2): 1.76e-6 to 1.87e-6 on
# logits of standard deviation 0.225. The controls read, at their worst
# position: the SSM state held in bfloat16 1.8e-3 to 3.3e-3, int8 weights
# 3.7e-2 to 4.2e-2, each named fault 0.59 to 1.37. The limit stands 27 times
# above the sound readings and 36 times below the nearest control.
TOLERANCE = 5e-5


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in np.random.default_rng(0).integers(0, 288, size=T)]


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(hybrid_ssm_gqa.logits(SIZES, params, tokens))


def _pools(cfg=CFG, poison: float = 0.0):
    """Fresh pools; ``poison`` fills the state pool, so that a row that
    failed to start from zero, or a slot that was touched, shows."""
    state = init_state(cfg, SEATS)
    return {"kv": init_cache(cfg, 64, PS),
            **{k: v + jnp.asarray(poison, v.dtype) for k, v in state.items()}}


def _serve(cfg, params, tokens, chunks, pools=None, slot=2, scan_impl=None):
    """One sequence through ``forward_core`` in chunks of the given sizes,
    as batch row 1 of 4 (row 0 and the rows after it are padding, mapped to
    the scratch slot); returns (logits of every position, pools)."""
    pools = _pools(cfg, poison=7.0) if pools is None else pools
    pt = np.full((ROWS, MAXP), -1, np.int32)
    pt[1] = np.arange(MAXP) + 5
    step = jax.jit(lambda pools, *a: forward_core(
        cfg, params, pools, *a[:5], cu_q_lens=a[5], num_seqs=a[6],
        state_slots=a[7], scan_impl=scan_impl))
    out, start = [], 0
    for n in chunks:
        toks, pos = np.zeros((NT,), np.int32), np.full((NT,), -1, np.int32)
        toks[:n], pos[:n] = tokens[start:start + n], np.arange(start, start + n)
        lens = np.ones((ROWS,), np.int32)
        lens[1] = start + n
        hidden, pools, _, _ = step(
            pools, jnp.asarray(toks), jnp.asarray(pos),
            jnp.ones((NT,), jnp.int32), jnp.asarray(pt), jnp.asarray(lens),
            jnp.asarray([0, 0, n, n, n], jnp.int32), jnp.asarray([2], jnp.int32),
            jnp.asarray([SEATS, slot, SEATS, SEATS], jnp.int32))
        # every row unembedded, then cut: one shape of product whatever n
        out.append(np.asarray(unembed(cfg, params, hidden))[:n])
        start += n
    return np.concatenate(out), pools


def _slots(pools, k: str, s):
    """Slots ``s`` (one or a list) of the state pool ``k``: the slot is the
    SSM pool's second axis, and the third of the conv window's, which keeps a
    layer's slots as the rows of its K - 1 planes."""
    return np.take(np.asarray(pools[k]), s, axis=2 if k == "conv" else 1)


def _worst(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# ------------------------------------------------------- (a) the family

def test_the_family_maps_the_published_keys():
    with open(os.path.join(ROOT, "perfbench", "configs", "jamba2-3b.json")) as f:
        conf = json.load(f)
    kinds = hybrid_ssm_gqa.layer_kinds(conf)
    assert [l for l, k in enumerate(kinds) if k == "attention"] == [7, 21]
    cfg = hybrid_ssm_gqa.model_config(conf)
    assert (cfg.num_layers, cfg.num_mamba_layers, cfg.num_attn_layers) == (28, 26, 2)
    assert cfg.layer_runs == (("mamba", 0, 7), ("attention", 7, 1), ("mamba", 8, 6))
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank, cfg.head_dim) == (5120, 16, 4, 160, 128)
    assert cfg.rope_pattern == (False,) and cfg.tie_embeddings
    assert cfg.mamba_state_dtype == "float32" and cfg.dtype == "bfloat16"
    # the tiny size keeps the pattern: one attention layer a period of four
    assert CFG.layer_runs == (("mamba", 0, 1), ("attention", 1, 1), ("mamba", 2, 2))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert shapes["mamba_in"].shape == (26, 2560, 10240)
    assert shapes["wq"].shape == (2, 2560, 20, 128)
    assert shapes["wi"].shape == (28, 2560, 16384)
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert abs(n - 3.029e9) < 2e6  # the issue's count, at full depth
    pool = jax.eval_shape(lambda: init_cache(cfg, 16, 16))
    assert pool.shape[0] == 2 * 16  # the KV pool folds the attention layers


def test_the_registry_names_the_model_at_its_published_sizes():
    from llmd_tpu.models import get_model_config

    with open(os.path.join(ROOT, "perfbench", "configs", "jamba2-3b.json")) as f:
        conf = json.load(f)
    assert get_model_config("jamba2-3b") == hybrid_ssm_gqa.model_config(conf)
    assert replace(get_model_config("tiny-jamba"), name="", max_position=0,
                   dtype="") == replace(CFG, name="", max_position=0, dtype="")


def test_the_published_block_is_what_transformers_computes(tmp_path):
    """A toy JambaForCausalLM checkpoint (the published modelling code's own
    plain-torch mixer), its norms, D and biases moved off their initial
    values, loaded by the published tensor names: the reference and the
    program both give the logits ``transformers`` gives. float32 on all
    sides; read 3.7e-6 (reference) and 4.1e-6 (program) on logits of
    standard deviation 0.51."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from llmd_tpu.models.hf_loader import config_from_hf, load_params
    from llmd_tpu.testing.checkpoints import make_hf_checkpoint

    d = make_hf_checkpoint(str(tmp_path / "jamba"), "jamba", num_layers=8,
                           num_kv_heads=1, with_tokenizer=False)
    model = transformers.AutoModelForCausalLM.from_pretrained(
        d, local_files_only=True, dtype=torch.float32)
    torch.manual_seed(0)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if any(k in name for k in ("layernorm", "mamba.D", "conv1d.bias",
                                       "dt_proj.bias")):
                p.add_(torch.randn_like(p) * 0.3)
            elif "A_log" not in name:
                p.mul_(3.0)  # an initial deviation of 0.02 hides the mixers
    model.save_pretrained(d, safe_serialization=True)
    ids = [int(i) for i in np.random.default_rng(0).integers(3, 300, size=37)]
    with torch.no_grad():
        want = model.eval()(torch.tensor([ids])).logits[0].float().numpy()
    cfg = config_from_hf(d, dtype="float32")
    assert cfg.layer_kinds == ("mamba", "attention", "mamba", "mamba")
    params = load_params(d, cfg)
    with open(os.path.join(d, "config.json")) as f:
        conf = dict(json.load(f), name=cfg.name,
                    weights={"dtype": "float32", "quantize": None})
    assert hybrid_ssm_gqa.model_config(conf) == cfg
    ref = hybrid_ssm_gqa.logits(hybrid_ssm_gqa.sizes(conf), params, ids)
    assert _worst(ref, want) < TOLERANCE
    got, _ = _serve(cfg, params, ids, [16, 21])
    assert _worst(got, want) < TOLERANCE


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("sliding_window", 4096), ("mamba_proj_bias", True),
    ("hidden_act", "gelu"), ("num_hidden_layers", 6)])
def test_model_config_refuses_by_the_keys_name(key, value):
    with pytest.raises(ValueError, match=key):
        hybrid_ssm_gqa.model_config(dict(CONF, **{key: value}))


def test_model_config_refuses_what_mamba_layers_do_not_stand_beside():
    with pytest.raises(ValueError, match="layer_kinds"):
        ModelConfig(layer_kinds=("mamba", "mamba"))
    with pytest.raises(ValueError, match="mamba_d_inner"):
        ModelConfig(layer_kinds=("mamba", "attention"))
    with pytest.raises(ValueError, match="mixture"):
        replace(CFG, moe_num_experts=4)
    with pytest.raises(ValueError, match="forward_core"):
        forward(CFG, {}, None, jnp.zeros((1, 4), jnp.int32), None, None, None)


# ------------------------------------------- (b) against the reference

def test_forward_agrees_with_the_reference(params, tokens, want):
    got, _ = _serve(CFG, params, tokens, [T])
    assert _worst(got, want) < TOLERANCE


def test_a_bf16_state_fails_the_tolerance(params, tokens, want):
    """The SSM state held in bfloat16 between steps, under the float32
    name: rounded once a call, at the write-back."""
    cfg = replace(CFG, mamba_state_dtype="bfloat16")
    got, _ = _serve(cfg, params, tokens, [7, 9, 11, 18])
    assert _worst(got, want) > 5 * TOLERANCE


def test_int8_weights_fail_the_tolerance(params, tokens, want):
    got, _ = _serve(CFG, quantize_params(CFG, params)[0], tokens, [T])
    assert _worst(got, want) > 5 * TOLERANCE


FAULTS = [("inner_norms", False), ("conv_bias", False), ("skip_d", False),
          ("gate", False), ("attn_rope", True), ("reset_every", 16)]


@pytest.mark.parametrize("key,value", FAULTS, ids=[k for k, _ in FAULTS])
def test_each_named_fault_fails_and_the_reference_has_the_mechanism(
        key, value, params, tokens, want):
    """The reference with one mechanism left out (the three inner norms,
    the conv bias, D, the gate, NoPE, the state carried over a chunk's
    start) lies far from the program, so the program has the mechanism and
    the comparison sees its absence."""
    faulty = hybrid_ssm_gqa.logits(dict(SIZES, **{key: value}), params, tokens)
    got, _ = _serve(CFG, params, tokens, [T])
    assert _worst(got, faulty) > 1000 * TOLERANCE
    assert _worst(want, faulty) > 1000 * TOLERANCE


# ------------------------------- (c) chunks, then decode through the slots

SPLITS = [[45], [1, 3, 7, 20, 14], [32, 13], [7, 1, 1, 1, 35], [16, 16, 13]]


@pytest.mark.parametrize("chunks", SPLITS[1:], ids=str)
def test_the_state_after_a_prompt_does_not_depend_on_the_split(
        chunks, params, tokens, want):
    """Chunked prefill at uneven boundaries equals the full forward, the
    logits and the state after the prompt bit for bit: time runs one token
    after another in the scan, and the conv reads the same rows from the
    window as from the chunk."""
    whole, p0 = _serve(CFG, params, tokens, SPLITS[0])
    got, p1 = _serve(CFG, params, tokens, chunks)
    assert _worst(got, want) < TOLERANCE
    assert np.array_equal(got, whole)
    for k in ("ssm", "conv"):
        assert np.array_equal(_slots(p0, k, 2), _slots(p1, k, 2))
        # the poisoned slots of the other seats and the scratch slot
        assert np.array_equal(_slots(p1, k, [0, 1, 3, 4]),
                              _slots(_pools(poison=7.0), k, [0, 1, 3, 4]))


def test_decode_rows_through_the_slots_equal_the_full_forward(params, tokens,
                                                               want):
    """A prompt in two chunks, then one token a step as the fused decode
    call packs it (row b is seat b, no row -> slot map), with an idle seat
    and a frozen one beside it: both leave their slots bit for bit."""
    n0 = 30
    _, pools = _serve(CFG, params, tokens, [13, n0 - 13], slot=1)
    before = {k: np.asarray(v) for k, v in pools.items() if k != "kv"}
    pt = np.full((SEATS, MAXP), -1, np.int32)
    pt[1] = np.arange(MAXP) + 5
    step = jax.jit(lambda pools, toks, pos, lens: forward_core(
        CFG, params, pools, toks, pos, jnp.arange(SEATS, dtype=jnp.int32),
        jnp.asarray(pt), lens, cu_q_lens=jnp.arange(SEATS + 1, dtype=jnp.int32),
        num_seqs=jnp.asarray([SEATS], jnp.int32)))
    for t in range(n0, T):
        toks = jnp.asarray([0, tokens[t], 0, 0], jnp.int32)
        pos = jnp.asarray([-1, t, -1, -1], jnp.int32)
        hidden, pools, _, _ = step(pools, toks, pos,
                                   jnp.asarray([1, t + 1, 1, 1], jnp.int32))
        assert _worst(unembed(CFG, params, hidden[1]), want[t]) < TOLERANCE
    for k in ("ssm", "conv"):
        assert np.array_equal(_slots(pools, k, [0, 2, 3, 4]),
                              _slots(before, k, [0, 2, 3, 4]))
        assert not np.array_equal(_slots(pools, k, 1), _slots(before, k, 1))


# ------------------------------------ (c') the window's pool, plane by plane

def _mixer_case(chunk):
    """A mamba layer's call as both step programs pack it. ``chunk`` None:
    the fused decode call (row b is seat b, one token each): a row under way,
    a fresh row, a frozen row (position -1) and another under way. Otherwise
    a unified step over NT flat tokens: a chunk of ``chunk`` tokens from
    position 5 in slot 2, a fresh chunk as long in slot 0, a frozen row of
    one token in slot 3 and a padding row on the scratch slot.
    Returns (positions [N], seq_slots [N], cu_q_lens [B + 1], row_slots)."""
    if chunk is None:
        pos = np.asarray([9, 0, -1, 30], np.int32)
        return (pos, np.arange(ROWS, dtype=np.int32),
                np.arange(ROWS + 1, dtype=np.int32), None)
    n = 2 * NT
    cu = np.asarray([0, chunk, 2 * chunk, 2 * chunk + 1, 2 * chunk + 1],
                    np.int32)
    pos, seq = np.full((n,), -1, np.int32), np.full((n,), ROWS - 1, np.int32)
    pos[:chunk], pos[chunk:2 * chunk] = 5 + np.arange(chunk), np.arange(chunk)
    for b in range(ROWS):
        seq[cu[b]:cu[b + 1]] = b
    return pos, seq, cu, np.asarray([2, 0, 3, SEATS], np.int32)


def _window_by_slot(cu, row_slots, live, fresh):
    """``conv_window``'s semantics, plainly and slot-major, for a call whose
    packing is known (numpy arrays): a slot's window [K - 1, Di] is its last
    K - 1 pre-conv rows, oldest first. A live row's tokens read
    [its window ; its chunk] (a fresh row's window is zeros), and the last
    K - 1 rows of that are its window afterwards; any other row leaves its
    slot alone. Takes and returns the pool of planes, transposed on the way
    in and out."""
    def conv_window(conv, o, xr, *_):
        k1 = conv.shape[1]
        window = conv[o].transpose(1, 0, 2)  # [S, K - 1, Di]
        taps = jnp.zeros((k1 + 1,) + xr.shape, xr.dtype)
        for b in np.flatnonzero(live):
            slot = b if row_slots is None else row_slots[b]
            old = jnp.zeros_like(window[slot]) if fresh[b] else window[slot]
            n = cu[b + 1] - cu[b]
            seq = jnp.concatenate([old, xr[cu[b]:cu[b + 1]]])
            for k in range(k1 + 1):  # the row K - 1 - k tokens back
                taps = taps.at[k, cu[b]:cu[b + 1]].set(seq[k:k + n])
            window = window.at[slot].set(seq[n:])
        return list(taps), conv.at[o].set(window.transpose(1, 0, 2))
    return conv_window


@pytest.mark.parametrize("chunk", [None, 1, 2, 3, 40],
                         ids=["fused_decode", "chunk_1", "chunk_2", "chunk_3",
                              "chunk_40"])
def test_the_mixer_equals_a_slot_major_reference_bit_for_bit(chunk, params,
                                                             monkeypatch):
    """``mamba_mixer`` over the pool of planes [Lm, K - 1, S, Di] against the
    same mixer with a plain slot-major window in ``conv_window``'s place: the
    output of every live token, the window pool and the state pool are equal
    to the last bit, in both step programs' packings, for chunks shorter
    than, as long as and longer than the window, a fresh row, a frozen row
    (slot untouched) and a padding row (no slot written, the scratch slot
    included)."""
    from llmd_tpu.models import transformer
    from llmd_tpu.models.transformer import (
        _weight_mm, mamba_mixer, mamba_vectors, window_plan)

    layer = 4  # the ordinal among the mamba layers: not the pool's first
    pos, seq, cu, row_slots = _mixer_case(chunk)
    live, fresh = row_flags(jnp.asarray(pos), jnp.asarray(cu))
    rng = np.random.default_rng(5)
    state = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype)
             for k, v in init_state(CFG, SEATS).items()}
    ssm = state["ssm"].reshape((-1,) + state["ssm"].shape[2:])
    h = jnp.asarray(rng.normal(size=(len(pos), CFG.hidden_size)), jnp.float32)
    lp = {k: v[layer] for k, v in {
        **params, **mamba_vectors(CFG, params)}.items() if k.startswith("mamba")}

    def mixer(conv):
        slots = None if row_slots is None else jnp.asarray(row_slots)
        plan = window_plan(conv.shape, len(pos), slots, jnp.asarray(seq),
                           jnp.asarray(cu), live, fresh)
        return mamba_mixer(
            CFG, lp, h, conv, ssm, jnp.int32(layer), plan, slots,
            jnp.asarray(cu), live, fresh, selective_scan_xla,
            lambda key, pattern, x, out=None: _weight_mm(lp, key, pattern, x,
                                                         out))

    def run(window):
        """The mixer with ``window`` in ``conv_window``'s place, its taps
        behind a barrier: the CPU compiler otherwise fuses the conv's sum
        with whatever made the taps, and rounds it (a fused multiply-add here,
        none there) as the window's code is written."""
        def behind_a_barrier(*a):
            taps, pool = window(*a)
            return jax.lax.optimization_barrier(taps), pool
        monkeypatch.setattr(transformer, "conv_window", behind_a_barrier)
        # a function object each: ``jax.jit`` would hand the second run the
        # first one's program
        return jax.jit(lambda c: mixer(c))(state["conv"])

    out, conv, ssm1 = run(transformer.conv_window)
    want, want_conv, ssm0 = run(_window_by_slot(
        cu, row_slots, np.asarray(live), np.asarray(fresh)))
    tokens = np.concatenate([np.arange(cu[b], cu[b + 1])
                             for b in np.flatnonzero(live)])
    assert len(tokens) == (3 if chunk is None else 2 * chunk)
    assert np.array_equal(np.asarray(out)[tokens], np.asarray(want)[tokens])
    assert np.array_equal(ssm0, ssm1)
    assert np.array_equal(conv, want_conv)
    # the reference moved the live rows' slots of this layer and nothing else
    moved = {(int(l), int(s)) for l, _, s, _ in
             np.argwhere(np.asarray(want_conv != state["conv"]))}
    assert moved == {(layer, s) for s in ([0, 1, 3] if chunk is None
                                          else [2, 0])}


# ------------------------------------------------ (d) the kernel itself

def _ragged_case(dtype=jnp.float32, seed=0):
    """Rows: a fresh chunk of 7, a decode row, a chunk of 20 from position
    5, a frozen row (position -1), two padding rows on the scratch slot."""
    rng = np.random.default_rng(seed)
    nt, di, n, slots = 48, 256, 16, 9
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    cu = np.concatenate([[0], np.cumsum([7, 1, 20, 1, 0, 0])]).astype(np.int32)
    pos = np.full((nt,), -1, np.int32)
    pos[0:7], pos[7], pos[8:28] = np.arange(7), 33, np.arange(5, 25)
    live, fresh = row_flags(jnp.asarray(pos), jnp.asarray(cu))
    assert list(np.asarray(live)) == [True, True, True, False, False, False]
    assert list(np.asarray(fresh)) == [True] + [False] * 5
    return (f(nt, di), jax.nn.softplus(f(nt, di)), f(nt, n), f(nt, n),
            -jnp.exp(f(n, di)), f(slots, n, di).astype(dtype),
            jnp.asarray([3, 0, 5, 2, 8, 8], jnp.int32), jnp.asarray(cu),
            live, fresh)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32_state", "bf16_state"])
def test_the_pallas_kernel_equals_the_xla_form_on_a_ragged_batch(dtype):
    args = _ragged_case(dtype)
    y0, p0 = selective_scan_xla(*args)
    y1, p1 = selective_scan_pallas(*args, interpret=True)
    # the same float32 operations in the same order: equal to the last bit
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    assert np.array_equal(np.asarray(p0, np.float32), np.asarray(p1, np.float32))
    pool = np.asarray(args[5], np.float32)
    for got in (np.asarray(p0, np.float32), np.asarray(p1, np.float32)):
        for s in range(9):  # slots 3, 0, 5 advance; frozen, scratch and
            assert (s in (3, 0, 5)) != np.array_equal(got[s], pool[s])  # idle: not
    assert not np.asarray(y1)[28:].any()  # frozen and padding rows give zeros


def test_the_scan_does_not_depend_on_the_chunks_it_arrives_in():
    x, dt, bm, cm, a, pool, *_ = _ragged_case()

    def run(chunks, impl):
        p, ys, at = pool, [], 0
        for c in chunks:
            pad = lambda v: jnp.zeros_like(v).at[:c].set(v[at:at + c])  # noqa: E731
            pos = jnp.where(jnp.arange(48) < c, at + jnp.arange(48), -1)
            cu = jnp.asarray([0, 0, c, c], jnp.int32)
            y, p = impl(pad(x), pad(dt), pad(bm), pad(cm), a, p,
                        jnp.asarray([8, 4, 8], jnp.int32), cu,
                        *row_flags(pos, cu))
            ys.append(np.asarray(y[:c]))
            at += c
        return np.concatenate(ys), np.asarray(p)

    for impl in (selective_scan_xla,
                 lambda *a: selective_scan_pallas(*a, interpret=True)):
        y0, p0 = run([40], impl)
        y1, p1 = run([1, 3, 7, 20, 9], impl)
        assert np.array_equal(y0, y1) and np.array_equal(p0, p1)


# ----------------------------------- (d') rows cut at their KV blocks' ends

def _ragged_rows():
    """Four rows over pages of 4 tokens and KV blocks of 8: a chunk that
    crosses one block end (positions 5..10), one that crosses two (6..17), a
    decode row (position 12) and a chunk inside one block (16..19); rows 4
    and 5 are not in the call."""
    B, maxp = 6, 8
    start = np.array([5, 6, 12, 16, 0, 0])
    q_len = np.array([6, 12, 1, 4, 0, 0])
    kv = np.where(q_len > 0, start + q_len, 1).astype(np.int32)
    cu = np.concatenate([[0], np.cumsum(q_len)]).astype(np.int32)
    pt = np.stack([(np.arange(maxp) + 8 * b) for b in range(B)]).astype(np.int32)
    return pt, kv, cu, np.array([4], np.int32), start, q_len


def test_rows_are_cut_where_their_queries_cross_a_kv_block():
    from llmd_tpu.ops.paged_attention import split_rows_at_kv_blocks

    pt, kv, cu, ns, start, q_len = _ragged_rows()
    pt2, kv2, cu2, ns2 = (np.asarray(a) for a in split_rows_at_kv_blocks(
        *(jnp.asarray(a) for a in (pt, kv, cu, ns)), 8, 24))
    # 24 queries of one row touch at most 4 blocks of 8: 6 rows become 24
    assert pt2.shape[0] == kv2.shape[0] == cu2.shape[0] - 1 == 24
    n = int(ns2[0])
    assert n == 2 + 3 + 1 + 1
    lens = np.diff(cu2)
    # the same tokens in the same order, each part inside one block, its
    # kv_len that block's end or the row's own, over the row's own pages
    assert cu2[n] == cu[4] and (lens[n:] == 0).all()
    first = kv2[:n] - lens[:n]
    assert list(first) == [5, 8, 6, 8, 16, 12, 16]
    assert list(kv2[:n]) == [8, 11, 8, 16, 18, 13, 20]
    assert ((kv2[:n] - 1) // 8 == first // 8).all()
    assert [int(r[0]) // 8 for r in pt2[:n]] == [0, 0, 1, 1, 1, 2, 3]


def test_the_cut_rows_attend_as_the_whole_rows_do():
    """Through the XLA reference, which masks by position and is exact: the
    cut changes which row a token belongs to and nothing it attends to."""
    from llmd_tpu.models.transformer import ragged_paged_attention_xla
    from llmd_tpu.ops.paged_attention import split_rows_at_kv_blocks

    pt, kv, cu, ns, start, q_len = _ragged_rows()
    rng = np.random.default_rng(0)
    N, H, Hk, Dh = 24, 4, 1, 128
    q = jnp.asarray(rng.normal(size=(N, H, Dh)), jnp.float32)
    cache = jnp.asarray(rng.normal(size=(64, PS, 2 * Hk, Dh)), jnp.float32)
    pos = np.full((N,), -1, np.int32)
    for b in range(4):
        pos[cu[b]:cu[b + 1]] = start[b] + np.arange(q_len[b])

    def attend(pt, kv, cu):
        sids = np.clip(np.searchsorted(np.asarray(cu)[1:], np.arange(N),
                                       side="right"), 0, len(kv) - 1)
        return np.asarray(ragged_paged_attention_xla(
            q, cache, jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(sids),
            jnp.asarray(kv), scale=Dh ** -0.5))

    pt2, kv2, cu2, _ = split_rows_at_kv_blocks(
        *(jnp.asarray(a) for a in (pt, kv, cu, ns)), 8, N)
    whole, cut = attend(pt, kv, cu), attend(pt2, kv2, cu2)
    assert np.array_equal(whole[:cu[4]], cut[:cu[4]])


# ------------------------------------------------------- (e) the engine

def _engine(cfg=CFG, **kw) -> LLMEngine:
    base = dict(page_size=PS, num_pages=128, max_model_len=96,
                max_batch_size=4, prefill_chunk=16, decode_steps=4)
    base.update(kw)
    return LLMEngine(cfg, EngineConfig(**base), seed=3)


def _deficit(eng, prompts, served) -> float:
    d = hybrid_ssm_gqa.readings(SIZES, eng.params, prompts, served)["deficits"]
    return max(x for ds in d for x in ds)


PROMPTS = [list(range(5, 31)), list(range(60, 65)), list(range(140, 185)),
           list(range(200, 217)), list(range(90, 123))]
GREEDY = SamplingParams(max_tokens=11, temperature=0.0, ignore_eos=True)


def _served(out: dict) -> list:
    return [out[f"req-{i}"] for i in range(len(out))]


@pytest.fixture(scope="module")
def batched():
    eng = _engine()
    return eng, _served(eng.generate(PROMPTS, GREEDY))


def test_engine_in_a_batch_agrees_with_the_reference(batched):
    """Through ``LLMEngine``: five requests on four seats, prompts of one to
    three chunks, 11 tokens each through unified steps and fused decode
    calls of 4 steps (so a row freezes inside a call and goes on in the
    next): every served token is the reference's greedy token to within the
    tolerance."""
    eng, served = batched
    assert all(len(s) == 11 for s in served)
    assert _deficit(eng, PROMPTS, served) < TOLERANCE
    assert eng.stats.n_decode_dispatches > 0 and eng.stats.n_unified_steps > 0


def test_engine_alone_and_in_a_batch_gives_the_same_tokens(batched):
    _, served = batched
    eng = _engine()
    alone = [eng.generate([p], GREEDY)["req-0"] for p in PROMPTS]
    assert alone == served


def test_the_fused_calls_k_steps_equal_k_single_steps(batched):
    _, served = batched
    assert _served(_engine(decode_steps=1).generate(PROMPTS, GREEDY)) == served


def test_run_ahead_equals_the_flushed_run(batched):
    _, served = batched
    eng = _engine()
    for i, p in enumerate(PROMPTS):
        eng.add_request(f"req-{i}", p, GREEDY)
    got: dict = {}
    while eng.has_work():
        outs = eng.step()
        eng._flush_pending_sample()
        eng._flush_pending_decode()
        for out in outs:
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
    assert _served(got) == served


def test_engine_after_a_preemption_agrees_with_the_reference(batched):
    _, served = batched
    tight = _engine(num_pages=20, max_batch_size=3)
    got = _served(tight.generate(PROMPTS, GREEDY))
    assert tight.stats.total_preemptions > 0  # the point of the test
    assert got == served  # recomputed from position 0, from a zero state
    resets = _series(tight, "llmd_tpu:ssm_state_resets_total")
    assert resets['llmd_tpu:ssm_state_resets_total{cause="recompute"}'] \
        == tight.stats.total_preemptions
    assert resets['llmd_tpu:ssm_state_resets_total{cause="admit"}'] == 5


def test_the_pallas_scan_goes_where_the_pallas_attention_goes(params, tokens):
    """The engine's rule, and the kernel (interpret mode here) inside the
    whole stack: the same logits and state as the XLA form to the bit."""
    assert _engine(attn_impl="pallas").ssm_backend == "pallas_selective_scan"
    assert _engine().ssm_backend == "xla_selective_scan"
    kernel = lambda *a: selective_scan_pallas(*a, interpret=True)  # noqa: E731
    l0, p0 = _serve(CFG, params, tokens, [13, 1, 31])
    l1, p1 = _serve(CFG, params, tokens, [13, 1, 31], scan_impl=kernel)
    assert np.array_equal(l0, l1)
    assert all(np.array_equal(p0[k], p1[k]) for k in ("ssm", "conv"))


@pytest.mark.parametrize("name,kw", [
    ("spec_mode", dict(spec_mode="ngram")),
    ("cpu_offload_pages", dict(cpu_offload_pages=8)),
    ("kv_connector", dict(kv_connector="llmd_tpu.kv.connector_api:NullConnector")),
    ("role", dict(role="prefill")),
    ("lora", dict(lora=object())),
    ("mesh.tp", dict(mesh=MeshConfig(tp=2))),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_refused_combination_raises_by_its_name(name, kw):
    with pytest.raises(ValueError, match=f"^{name}: not supported for a "
                                         "model with recurrent layers"):
        _engine(**kw)


def test_embeddings_are_refused_by_name():
    with pytest.raises(ValueError, match="embeddings: not supported"):
        _engine().embed([1, 2, 3])


# ------------------------------------------------------ (f) the counters

def _series(eng, name):
    return {line.split(" ")[0]: float(line.split(" ")[1])
            for line in eng.metrics.registry.expose().splitlines()
            if line.startswith(name)}


def test_counters_against_hand_counts():
    """One request of 40 prompt tokens and 9 served: chunks of 16, 16 and 8
    (the last gives token 1), then 8 decode tokens through unified steps or
    fused calls; a mamba layer's scan is given 40 chunk tokens and 8 decode
    tokens, whatever program brought them."""
    eng = _engine()
    eng.generate([list(range(5, 45))], replace(GREEDY, max_tokens=9))
    scan = _series(eng, "llmd_tpu:ssm_scan_tokens_total")
    assert sum(v for k, v in scan.items() if 'rows="chunk"' in k) == 40
    assert sum(v for k, v in scan.items() if 'rows="decode"' in k) == 8
    assert any('program="decode"' in k for k in scan)
    assert _series(eng, "llmd_tpu:ssm_state_resets_total") == {
        'llmd_tpu:ssm_state_resets_total{cause="admit"}': 1.0}
    assert _series(eng, "llmd_tpu:ssm_state_slots_in_use") == {
        "llmd_tpu:ssm_state_slots_in_use": 0.0}  # the seat is free again
    info = _series(eng, "llmd_tpu:engine_ssm_backend")
    assert list(info) == ['llmd_tpu:engine_ssm_backend{impl="xla_selective_'
                          'scan",state_dtype="float32",prefix_reuse="off"}']
    # the two attention layers are full layers: given what was counted
    kv = _series(eng, "llmd_tpu:attn_kv_tokens_total")
    read = sum(_series(eng, "llmd_tpu:program_kv_read_tokens_total").values())
    assert kv and all('layers="full"' in k for k in kv)
    assert sum(kv.values()) == read > 0


def test_prefix_reuse_is_off_and_its_counters_stay_zero():
    eng = _engine()
    assert not eng.prefix_reuse and eng.cfg.enable_prefix_caching
    first = eng.generate([PROMPTS[2]], GREEDY)
    again = eng.generate([PROMPTS[2]], GREEDY)
    assert first == again
    assert eng._prefix_cached_total == 0
    cached = _series(eng, "llmd_tpu:engine_prefix_cached_tokens_total")
    assert sum(cached.values()) == 0
    assert not eng.alloc.cached  # no block was indexed
    from llmd_tpu.models import get_model_config

    dense = LLMEngine(get_model_config("tiny"), EngineConfig(
        page_size=8, num_pages=64, max_model_len=128, max_batch_size=2,
        prefill_chunk=32, decode_steps=4))
    assert dense.prefix_reuse and not dense.state
    assert not _series(dense, "llmd_tpu:engine_ssm_backend")


# ------------------- (g) the step programs of every other model are unmoved

# sha256 of the unified and the fused decode programs' StableHLO at f0f0e05
# (the tree before ISSUE 34), lowered as below from an engine of the named
# registry model: the state pool, the row -> slot map and the frozen rows'
# positions are handed only to a model with recurrent layers, so every other
# model's two step programs are the ones it had, operation for operation.
# The fused program's second hash is ISSUE 38's: its steps run in a loop as
# long as the call's longest row (a `while` with the token buffer carried)
# where they were a scan of decode_steps; the unified program's stands.
PARENT_STEP_PROGRAMS = {
    "tiny": ("c6a593f4a8fc26800d2accfb2deaa3e3f0d7dc6aa73a93abad61ac67bd0c00da",
             "ea8d380c49dfc3f3e905172c5aaac36ed9037902a09a3095f080b9a0eab0ebaa"),
    "tiny-moe": (
        "0b601467bfd68fda4e494b951a08b620dc35c6318cf1bed60edea59ad27812c9",
        "f1d71ea63053c9b39e2b296a7f8ca6573495871db7df6fe4e2f270414d0494ac"),
}


@pytest.mark.parametrize("model", sorted(PARENT_STEP_PROGRAMS))
def test_step_programs_without_recurrent_layers_lower_as_they_did(model):
    import hashlib

    from llmd_tpu.models import get_model_config

    eng = LLMEngine(get_model_config(model), EngineConfig(
        page_size=8, num_pages=64, max_model_len=128, max_batch_size=4,
        prefill_chunk=32, decode_steps=4))
    B, NT = 4, eng.cfg.batched_tokens

    def i(*shape):
        return jnp.zeros(shape, jnp.int32)

    g = eng._greedy_state
    unified = eng._unified_fn.lower(
        eng.params, eng.cache, i(NT), i(NT), i(NT), i(B, 16), i(B), i(B + 1),
        i(1), i(NT), eng._zero_sampled, *g).as_text()
    decode = eng._decode_multi_fn.lower(
        eng.params, eng.cache, i(B), i(B), i(B, 16), i(B), *g, i(B),
        i(B)).as_text()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()
                 for t in (unified, decode)) == PARENT_STEP_PROGRAMS[model]
