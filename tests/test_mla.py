"""Multi-head latent attention (MLA, DeepSeek-V2/V3 family).

The engine runs MLA ABSORBED (models/transformer.py): the paged pool stores
one shared [c_kv ; k_rope] vector per token, queries project into latent
space through W_UK, and attention is plain MQA with head_dim = rank+rope
over the unmodified ragged-paged impl; values are the latents, re-expanded
through W_UV after the weighted sum. These tests pin (1) the absorption
identity itself against a materialized-KV reference, (2) engine-level
serving semantics (chunked prefill, batching, prefix cache, preemption
recompute) on the tiny-mla registry shape, and (3) the latent pool actually
being smaller than the GQA pool it replaces.

Reference role: the wide-EP north-star model of
/root/reference/guides/wide-ep-lws/README.md (DeepSeek-R1) is this
architecture; llm-d serves it through vLLM's MLA support.
"""

from __future__ import annotations

import conftest  # noqa: F401

import numpy as np
import pytest

from llmd_tpu.core.request import SamplingParams
from llmd_tpu.engine import EngineConfig, LLMEngine
from llmd_tpu.models import get_model_config
from llmd_tpu.models.transformer import init_cache


def _engine(model="tiny-mla", **kw) -> LLMEngine:
    base = dict(page_size=8, num_pages=128, max_model_len=256, max_batch_size=4,
                prefill_chunk=32, decode_steps=4)
    base.update(kw)
    return LLMEngine(get_model_config(model), EngineConfig(**base))


PROMPTS = [list(range(3, 40)), list(range(50, 75)), list(range(80, 140))]


# ---------------------------------------------------------------- math level


def test_absorption_identity():
    """Absorbed scores/outputs == materialized-KV MLA, the identity the whole
    integration rests on: q_nope·(W_UK c) == (W_UK^T q_nope)·c and
    (Σ p·c) W_UV == Σ p·(c W_UV)."""
    rng = np.random.default_rng(0)
    H, dn, r, dv, T = 4, 16, 64, 16, 12
    q_nope = rng.normal(size=(H, dn)).astype(np.float32)
    c = rng.normal(size=(T, r)).astype(np.float32)
    wuk = rng.normal(size=(H, dn, r)).astype(np.float32)
    wuv = rng.normal(size=(H, r, dv)).astype(np.float32)

    # materialized: per-token per-head K/V
    k_mat = np.einsum("hdr,tr->thd", wuk, c)  # [T, H, dn]
    v_mat = np.einsum("tr,hrv->thv", c, wuv)  # [T, H, dv]
    s_mat = np.einsum("hd,thd->ht", q_nope, k_mat)
    p = np.exp(s_mat - s_mat.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out_mat = np.einsum("ht,thv->hv", p, v_mat)

    # absorbed: latent-space dot + post-softmax re-expansion
    q_lat = np.einsum("hd,hdr->hr", q_nope, wuk)
    s_abs = np.einsum("hr,tr->ht", q_lat, c)
    np.testing.assert_allclose(s_abs, s_mat, rtol=1e-4, atol=1e-4)
    out_abs = np.einsum("hr,hrv->hv", np.einsum("ht,tr->hr", p, c), wuv)
    np.testing.assert_allclose(out_abs, out_mat, rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- engine level


def test_single_request_greedy_deterministic():
    p = list(range(10, 30))
    out = _engine().generate([p], SamplingParams(max_tokens=8, temperature=0.0))
    out2 = _engine().generate([p], SamplingParams(max_tokens=8, temperature=0.0))
    assert out["req-0"] == out2["req-0"] and len(out["req-0"]) == 8


def test_chunked_prefill_matches_unchunked():
    """Cache write/read round-trip: chunked prefill + decode must equal the
    one-shot run — catches latent-slot addressing and rope-position bugs."""
    prompt = list(range(5, 70))
    o1 = _engine(prefill_chunk=128).generate([prompt], SamplingParams(max_tokens=6, temperature=0.0))
    o2 = _engine(prefill_chunk=16).generate([prompt], SamplingParams(max_tokens=6, temperature=0.0))
    assert o1["req-0"] == o2["req-0"]


def test_batch_equivalence():
    eng = _engine()
    batch = eng.generate(PROMPTS, SamplingParams(max_tokens=5, temperature=0.0))
    for i, p in enumerate(PROMPTS):
        solo = _engine().generate([p], SamplingParams(max_tokens=5, temperature=0.0))
        assert batch[f"req-{i}"] == solo["req-0"], f"seq {i} diverged in batch"


def test_prefix_cache_reuse_and_correctness():
    shared = list(range(1, 65))  # 8 full pages
    eng = _engine()
    a = eng.generate([shared + [70, 71]], SamplingParams(max_tokens=4, temperature=0.0))
    b = eng.generate([shared + [90, 91]], SamplingParams(max_tokens=4, temperature=0.0))
    fresh = _engine().generate([shared + [90, 91]], SamplingParams(max_tokens=4, temperature=0.0))
    assert b["req-0"] == fresh["req-0"]  # reused latent pages give same result
    # different suffixes must produce different continuations — a cache
    # addressing bug returning A's continuation for B would pass the reuse
    # check above while being completely wrong
    assert a["req-0"] != b["req-0"]


def test_preemption_recompute_continues():
    ref = _engine(num_pages=128, max_batch_size=2)
    prompts = [list(range(1, 30)), list(range(60, 95))]
    expected = ref.generate(prompts, SamplingParams(max_tokens=12, temperature=0.0))
    tight = _engine(num_pages=10, max_batch_size=2, enable_prefix_caching=False)
    got = tight.generate(prompts, SamplingParams(max_tokens=12, temperature=0.0))
    assert tight.stats.total_preemptions > 0
    for k in expected:
        assert got[k] == expected[k], k


def test_attn_backend_provenance():
    eng = _engine()
    # auto off-TPU: the absorbed XLA impl is the CPU's designed backend for
    # every step program (on a TPU all of them take ops/mla_attention), not a
    # fallback — the reason field must stay empty so real fallbacks are
    # observable
    assert eng.attn_backend == "xla_mla_absorbed"
    assert eng.attn_fallback_reason is None
    assert eng.kv_pack == 1  # nothing to pack: one shared latent head
    assert eng.sp_attn_backend is None  # no mesh on this engine → no sp ring
    assert eng.attn_geometry == "none"
    # the latent kernel's label ties a reading to what was traced: the block
    # pairs, the rows a chunk's query block hands the matrix unit (16 tokens
    # of 4 heads, not of 32 padded ones) and the value lanes (rank 64 -> 128)
    eng = _engine(attn_impl="pallas")
    geometry = "unified=32x16 decode=32x1 rows=64 v=128"
    assert eng.attn_geometry == geometry
    assert f'geometry="{geometry}"' in eng.metrics.registry.expose()


@pytest.mark.slow  # ~18s: MoE x MLA composed engine, two serving runs
def test_moe_mla_compose():
    """The wide-EP north-star shape: MoE expert banks + MLA latent KV in one
    stack (moe-wide-mla registry entry)."""
    eng = _engine(model="moe-wide-mla", page_size=8, num_pages=64,
                  max_model_len=128, max_batch_size=2, prefill_chunk=32)
    out = eng.generate([list(range(3, 30))], SamplingParams(max_tokens=4, temperature=0.0))
    assert len(out["req-0"]) == 4


# ------------------------------------------------------------------ KV bytes


def test_latent_pool_smaller_than_gqa():
    mla = get_model_config("tiny-mla")
    gqa = get_model_config("tiny")  # same layer count/hidden size family
    c_mla = init_cache(mla, num_pages=16, page_size=8)
    c_gqa = init_cache(gqa, num_pages=16, page_size=8)
    # tiny-mla stores ONE plane of rank+rope = 80 lanes (padded 128) per
    # token (k == v == the latent in absorbed attention); tiny stores 2 KV
    # heads x 2 planes x 32 lanes (each padded to 128) -> 4x the rows
    assert c_mla.shape[2] == 1  # single-plane pool
    per_tok_mla = c_mla.size // (mla.num_layers * 16 * 8)
    per_tok_gqa = c_gqa.size // (gqa.num_layers * 16 * 8)
    assert per_tok_mla == per_tok_gqa // 4


def test_int8_quant_composes_with_mla():
    """int8 weight-only quantization touches wo/wi/wo_mlp (+ unembed); the MLA
    projections stay bf16. The quantized engine must still serve."""
    eng = _engine(quantize_weights="int8")
    out = eng.generate([list(range(10, 40))], SamplingParams(max_tokens=4, temperature=0.0))
    assert len(out["req-0"]) == 4


def test_lora_on_mla_raises():
    import pytest

    from llmd_tpu.models.lora import LoRAConfig
    with pytest.raises(ValueError, match="LoRA.*MLA"):
        _engine(lora=LoRAConfig(max_adapters=2, rank=4))


# ------------------------------------- the latent Pallas kernel, decode rows


def _latent_op_inputs(dtype):
    """Build a paged latent pool at the tiny-mla decode shape: B=4 single-token
    queries over a single-plane pool, real width 80 (rank 64 + rope 16)
    zero-padded to the 128-lane boundary — the padding algebra both impls rely
    on (zero q lanes x zero kv lanes contribute nothing to any dot)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    B, H, Dhp, real, ps, maxp, P = 4, 4, 128, 80, 8, 6, 16
    q = np.zeros((B, H, Dhp), np.float32)
    q[..., :real] = rng.normal(size=(B, H, real))
    cache = np.zeros((P, ps, 1, Dhp), np.float32)
    cache[..., :real] = rng.normal(size=(P, ps, 1, real))
    kv_lens = np.array([1, 3, 17, 48], np.int32)  # partial/one/partial/full maxp
    pt = -np.ones((B, maxp), np.int32)
    nxt = 0
    for b in range(B):
        for j in range(-(-int(kv_lens[b]) // ps)):
            pt[b, j] = nxt
            nxt += 1
    return (jnp.asarray(q, dtype), jnp.asarray(cache, dtype),
            jnp.asarray(pt), jnp.asarray(kv_lens))


def _decode_rows(q, kv_lens) -> dict:
    """One query row a sequence, as the fused decode call packs them."""
    import jax.numpy as jnp

    B = q.shape[0]
    return dict(positions=kv_lens - 1, seq_slots=jnp.arange(B, dtype=jnp.int32),
                kv_lens=kv_lens, scale=(64 + 16) ** -0.5,
                cu_q_lens=jnp.arange(B + 1, dtype=jnp.int32),
                num_seqs=jnp.asarray([B], jnp.int32))


def _latent_parity(dtype, tol):
    import jax.numpy as jnp

    from llmd_tpu.models.transformer import ragged_paged_attention_xla
    from llmd_tpu.ops.mla_attention import mla_paged_attention

    q, cache, pt, kv_lens = _latent_op_inputs(dtype)
    kw = _decode_rows(q, kv_lens)
    ref = ragged_paged_attention_xla(q, cache, pt, **kw)
    got = mla_paged_attention(q, cache, pt, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_latent_decode_kernel_parity_fp32():
    """The latent Pallas kernel on decode rows vs the XLA reference,
    elementwise: the
    online-softmax accumulation over pages must match the gather+mask softmax
    at fp32 to float-roundoff, across empty/partial/full page tables."""
    import jax.numpy as jnp
    _latent_parity(jnp.float32, 2e-6)


def test_latent_decode_kernel_parity_bf16():
    import jax.numpy as jnp
    _latent_parity(jnp.bfloat16, 2e-2)


def test_latent_decode_kernel_tp4_split_matches_unsharded():
    """Under a mesh the latent kernel runs per device (shard_over_heads:
    query heads over tp, the latent plane replicated). One head per device
    here; the sharded result must equal the single-device one."""
    import functools

    import jax
    import jax.numpy as jnp

    from llmd_tpu.ops.mla_attention import mla_paged_attention
    from llmd_tpu.parallel.mesh import MeshConfig, build_mesh

    q, cache, pt, kv_lens = _latent_op_inputs(jnp.float32)
    kw = _decode_rows(q, kv_lens)
    want = mla_paged_attention(q, cache, pt, interpret=True, **kw)
    got = jax.jit(functools.partial(
        mla_paged_attention, scale=kw.pop("scale"), interpret=True,
        mesh=build_mesh(MeshConfig(tp=4))))(q, cache, pt, **kw)
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_explicit_pallas_latent_decode_serves_with_parity():
    """attn_impl='pallas' on MLA routes every step program (the unified step
    and the fused decode call) through the latent Pallas kernel, in interpret
    mode off-TPU. Greedy tokens must
    match the pure-reference engine exactly, and the backend/fallback
    provenance must show a deliberate selection, not a silent fallback."""
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    eng = _engine(attn_impl="pallas")
    assert eng.attn_backend == "pallas_mla_ragged_paged_attention"
    assert eng.attn_fallback_reason is None
    got = eng.generate(PROMPTS[:2], sp)
    ref = _engine(attn_impl="reference").generate(PROMPTS[:2], sp)
    assert got == ref


@pytest.mark.slow  # ~10s: ring prefill on the sp>1 virtual mesh
def test_ring_prefill_parity_under_sp():
    """MLA over the sp ring: absorbed attention is MQA (Hk=1, G=H in the
    ring's grouped layout), so the shared latent rides the ICI ring at
    rank+rope width. Greedy outputs must match the GSPMD paged path, and the
    ring program must actually engage for the self-contained prefill."""
    from llmd_tpu.parallel.mesh import MeshConfig

    def sp_engine(ring: bool) -> LLMEngine:
        return LLMEngine(get_model_config("tiny-mla"), EngineConfig(
            page_size=8, num_pages=64, max_model_len=256, max_batch_size=4,
            prefill_chunk=64, mesh=MeshConfig(dp=1, sp=2, ep=1, tp=1),
            sp_ring_attention=ring))

    prompt = list(range(7, 40))  # one fresh self-contained chunk
    ring_eng = sp_engine(True)
    assert ring_eng.sp_attn_backend == "ring_zigzag(sp=2)"
    out_ring = ring_eng.generate([prompt], SamplingParams(max_tokens=6, temperature=0.0))
    assert ring_eng.stats.n_ring_prefill_steps == 1
    base_eng = sp_engine(False)
    out_base = base_eng.generate([prompt], SamplingParams(max_tokens=6, temperature=0.0))
    assert base_eng.stats.n_ring_prefill_steps == 0
    assert out_ring == out_base


def test_tp2_parity_with_replicated_latent_pool():
    """TP shards heads (W_Q/W_UK/W_UV/W_O) while the single-plane latent pool
    replicates (engine cache spec): greedy outputs on a tp=2 mesh must match
    the unmeshed engine token-for-token."""
    from llmd_tpu.parallel.mesh import MeshConfig

    prompt = list(range(7, 40))
    meshed = LLMEngine(get_model_config("tiny-mla"), EngineConfig(
        page_size=8, num_pages=64, max_model_len=256, max_batch_size=4,
        prefill_chunk=32, mesh=MeshConfig(dp=1, sp=1, ep=1, tp=2)))
    out_tp = meshed.generate([prompt], SamplingParams(max_tokens=6, temperature=0.0))
    out_base = _engine().generate([prompt], SamplingParams(max_tokens=6, temperature=0.0))
    assert out_tp == out_base


def test_fp8_kv_single_plane_smoke():
    """fp8 pool + single-plane MLA write path (clip + convert on the shared
    latent row): serving is deterministic, and the quantized prompt KV still
    yields the bf16 pool's argmax for the FIRST generated token — the token
    whose logits read the whole fp8-written prefix, so a mis-scaled or
    mis-clipped write would flip it. Later tokens feed quantized context back
    on itself and legitimately diverge on this tiny random-weight model
    (near-uniform logits), so no full-sequence closeness is claimed."""
    prompt = list(range(10, 42))
    a = _engine(kv_cache_dtype="fp8").generate([prompt], SamplingParams(max_tokens=5, temperature=0.0))
    a2 = _engine(kv_cache_dtype="fp8").generate([prompt], SamplingParams(max_tokens=5, temperature=0.0))
    assert a == a2 and len(a["req-0"]) == 5
    ref = _engine().generate([prompt], SamplingParams(max_tokens=5, temperature=0.0))
    assert a["req-0"][0] == ref["req-0"][0]
