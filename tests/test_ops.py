"""Attention-impl correctness: XLA-reference ragged paged attention vs a numpy
brute-force oracle, plus engine-level consistency between the unified (mixed
prefill+decode) and fused-decode execution paths.

The Pallas kernel itself (ops.paged_attention.paged_attention_tpu) is TPU-only:
the engine selects it by platform, so CPU CI exercises the identical-contract
XLA reference. Its TPU lowering is checked from the CPU in
tests/test_tpu_lowering.py, and its agreement with this reference on the chip
by chip_smoke.py's parity phase.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from llmd_tpu.models.transformer import (
    init_cache,
    padded_head_dim,
    ragged_paged_attention_xla,
    write_kv,
)


def _np_oracle(q, kv_pages, page_tables, positions, seq_slots, kv_lens, scale):
    """Per-token brute force: gather the owning sequence's K/V in order, mask
    causally by global position."""
    N, H, Dhp = q.shape
    P, ps, HkC, _ = kv_pages.shape
    Hk = HkC // 2
    qpk = H // Hk
    out = np.zeros_like(q, dtype=np.float32)
    for n in range(N):
        if positions[n] < 0:
            continue
        b = seq_slots[n]
        pages = [p for p in page_tables[b] if p >= 0]
        k = kv_pages[pages][:, :, 0::2].reshape(-1, Hk, Dhp)[: kv_lens[b]]
        v = kv_pages[pages][:, :, 1::2].reshape(-1, Hk, Dhp)[: kv_lens[b]]
        key_pos = np.arange(k.shape[0])
        valid = key_pos <= positions[n]
        for h in range(H):
            kh = h // qpk
            s = (k[:, kh] @ q[n, h].astype(np.float32)) * scale
            s = np.where(valid, s, -1e30)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[n, h] = p @ v[:, kh].astype(np.float32)
    return out


def _mk_flat_case(seq_lens, q_lens, H, Hk, Dh, P, ps, max_pages, seed=0):
    """Random cache + a flat mixed batch; each seq's queries are its LAST q_len
    tokens (the kernel contract)."""
    rng = np.random.default_rng(seed)
    B = len(seq_lens)
    kv_pages = rng.standard_normal((P, ps, 2 * Hk, Dh)).astype(np.float32)
    all_pages = rng.permutation(P)[: B * max_pages].reshape(B, max_pages)
    pt = np.full((B, max_pages), -1, np.int32)
    kv_lens = np.asarray(seq_lens, np.int32)
    toks, pos, sids = [], [], []
    for b, (L, qn) in enumerate(zip(seq_lens, q_lens)):
        used = (L + ps - 1) // ps
        pt[b, :used] = all_pages[b, :used]
        pos.extend(range(L - qn, L))
        sids.extend([b] * qn)
    N = len(sids)
    q = rng.standard_normal((N, H, Dh)).astype(np.float32)
    return q, kv_pages, pt, np.asarray(pos, np.int32), np.asarray(sids, np.int32), kv_lens


@pytest.mark.parametrize("case", [
    dict(seq_lens=[40, 9], q_lens=[1, 1], H=8, Hk=2, Dh=128),       # decode GQA
    dict(seq_lens=[40, 16], q_lens=[16, 16], H=4, Hk=4, Dh=128),    # batched prefill
    dict(seq_lens=[33, 7, 20], q_lens=[8, 1, 1], H=8, Hk=4, Dh=128),  # mixed
])
def test_xla_reference_matches_oracle(case):
    q, kv, pt, pos, sids, lens = _mk_flat_case(
        case["seq_lens"], case["q_lens"], case["H"], case["Hk"], case["Dh"],
        P=32, ps=8, max_pages=8)
    scale = case["Dh"] ** -0.5
    got = np.asarray(ragged_paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(pt), jnp.asarray(pos),
        jnp.asarray(sids), jnp.asarray(lens), scale=scale))
    want = _np_oracle(q, kv, pt, pos, sids, lens, scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_xla_reference_padding_rows_ignored():
    """pos=-1 rows are masked padding — their output is irrelevant but the valid
    rows must be unaffected by their presence."""
    q, kv, pt, pos, sids, lens = _mk_flat_case([24, 12], [4, 1], 4, 2, 128,
                                               P=16, ps=8, max_pages=4, seed=1)
    scale = 128 ** -0.5
    base = np.asarray(ragged_paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(pt), jnp.asarray(pos),
        jnp.asarray(sids), jnp.asarray(lens), scale=scale))
    qp = np.concatenate([q, np.ones((3,) + q.shape[1:], np.float32)])
    posp = np.concatenate([pos, np.full((3,), -1, np.int32)])
    sidp = np.concatenate([sids, np.zeros((3,), np.int32)])
    padded = np.asarray(ragged_paged_attention_xla(
        jnp.asarray(qp), jnp.asarray(kv), jnp.asarray(pt), jnp.asarray(posp),
        jnp.asarray(sidp), jnp.asarray(lens), scale=scale))
    np.testing.assert_allclose(padded[: len(q)], base, rtol=1e-6, atol=1e-6)
    assert np.isfinite(padded).all()


def test_write_kv_interleave_and_padding_drop():
    flat_cache = jnp.zeros((32, 4, 128), jnp.float32)  # [S slots, 2*Hk=4, Dhp]
    k = jnp.ones((3, 2, 128)) * jnp.asarray([1.0, 2.0, 3.0])[:, None, None]
    v = -k
    slots = jnp.asarray([5, 17, -1], jnp.int32)  # third token is padding
    flat = np.asarray(write_kv(flat_cache, k, v, slots))
    np.testing.assert_array_equal(flat[5, 0::2], np.full((2, 128), 1.0))   # K even
    np.testing.assert_array_equal(flat[5, 1::2], np.full((2, 128), -1.0))  # V odd
    np.testing.assert_array_equal(flat[17, 0::2], np.full((2, 128), 2.0))
    # padding slot dropped: nothing else written
    mask = np.ones(32, bool)
    mask[[5, 17]] = False
    np.testing.assert_array_equal(flat[mask], 0.0)


def test_padded_head_dim_and_cache_shape():
    from llmd_tpu.models import get_model_config

    assert padded_head_dim(64) == 128
    assert padded_head_dim(128) == 128
    assert padded_head_dim(256) == 256
    cfg = get_model_config("tiny")
    c = init_cache(cfg, 8, 4)
    assert c.shape == (cfg.num_layers * 8, 4, 2 * cfg.num_kv_heads,
                       padded_head_dim(cfg.head_dim))


def test_engine_unified_vs_fused_decode_paths():
    """Greedy tokens must be identical whether decode runs through the fused
    k-step scan or through unified single steps (tiny token budget forces the
    unified path to carry decode rows alongside prefill chunks)."""
    from llmd_tpu.core.request import SamplingParams
    from llmd_tpu.engine.config import EngineConfig
    from llmd_tpu.engine.engine import LLMEngine
    from llmd_tpu.models import get_model_config

    cfg = get_model_config("tiny")
    mk = lambda **kw: LLMEngine(cfg, EngineConfig(
        page_size=8, num_pages=32, max_model_len=128, max_batch_size=2,
        prefill_chunk=16, **kw,
    ))
    prompts = [list(range(5, 40)), list(range(50, 63))]
    sp = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    out_fused = mk(decode_steps=4).generate(prompts, sp)
    out_single = mk(decode_steps=1).generate(prompts, sp)
    out_budget = mk(decode_steps=1, max_num_batched_tokens=18).generate(prompts, sp)
    assert out_fused == out_single == out_budget


def test_pick_block_sizes_bounds():
    """Block-size policy invariants the kernel's static validation requires:
    1 <= bkv <= pages_per_seq, a KV block of at most 512 tokens and 32 pages,
    1 <= bq <= N."""
    from llmd_tpu.ops.paged_attention import pick_block_sizes

    for ps in (4, 8, 16, 32, 64, 128, 256, 1024):
        for pages in (1, 2, 7, 64, 512):
            for n in (1, 31, 512, 2048):
                bkv, bq = pick_block_sizes(n, ps, pages)
                assert 1 <= bkv <= min(pages, 32)
                assert bkv * ps <= max(512, ps)
                assert 1 <= bq <= max(n, 1) and bq <= 64


def test_pallas_adapter_glue_with_stub_kernel(monkeypatch):
    """CPU-runnable check of paged_attention_tpu's adapter logic (arg mapping,
    page-table clamping, block-size forwarding) via a stub kernel — the kernel
    itself is TPU-only but the glue must not regress silently off-TPU."""
    import llmd_tpu.ops.paged_attention as pa

    captured = {}

    def stub(q, kv, kv_lens, page_tables, cu_q_lens, num_seqs, **kw):
        captured.update(kw, q=q, kv=kv, kv_lens=kv_lens,
                        page_tables=page_tables, cu_q_lens=cu_q_lens,
                        num_seqs=num_seqs)
        return jnp.zeros_like(q)

    monkeypatch.setattr(pa, "_kernel", lambda: stub)
    q, kv, pt, pos, sids, lens = _mk_flat_case([40, 9, 21], [8, 1, 1], 8, 4, 128,
                                               P=32, ps=16, max_pages=4)
    pt = pt.copy()
    assert (pt < 0).any(), "case must exercise unmapped (-1) page-table entries"
    cu = np.asarray([0, 8, 9, 10], np.int32)
    out = pa.paged_attention_tpu(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(pt), jnp.asarray(pos),
        jnp.asarray(sids), jnp.asarray(lens), scale=0.11,
        cu_q_lens=jnp.asarray(cu), num_seqs=jnp.asarray([3], np.int32))
    assert out.shape == q.shape
    # -1 entries clamped for the kernel's scalar-prefetched DMA
    assert (np.asarray(captured["page_tables"]) >= 0).all()
    np.testing.assert_array_equal(np.asarray(captured["kv_lens"]), lens)
    np.testing.assert_array_equal(np.asarray(captured["cu_q_lens"]), cu)
    np.testing.assert_array_equal(np.asarray(captured["num_seqs"]), [3])
    assert captured["sm_scale"] == 0.11
    # (the last of the step's two calls, the chunks': with a chunk in the
    # first row it is handed every row as it came)
    bkv, bq = pa.step_geometry(q.shape, kv.shape, *pt.shape)[-1]
    assert captured["num_kv_pages_per_block"] == bkv
    assert captured["num_queries_per_block"] == bq
    assert captured["vmem_limit_bytes"] == pa.VMEM_LIMIT


def _reference_as_kernel(q, kv, kv_lens, page_tables, cu_q_lens, num_seqs, *,
                         sm_scale, **_):
    """The XLA reference behind the upstream kernel's calling convention
    (each sequence's queries are its last q_len tokens), so the adapter's
    multi-device partitioning can be executed on the CPU mesh."""
    del num_seqs
    n = jnp.arange(q.shape[0], dtype=jnp.int32)
    slots = jnp.searchsorted(cu_q_lens[1:], n, side="right").astype(jnp.int32)
    slots = jnp.minimum(slots, kv_lens.shape[0] - 1)
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    pos = kv_lens[slots] - q_lens[slots] + (n - cu_q_lens[slots])
    return ragged_paged_attention_xla(q, kv, page_tables, pos, slots, kv_lens,
                                      scale=sm_scale)


def test_paged_attention_tp4_split_keeps_each_head_with_its_kv(monkeypatch):
    """paged_attention_tpu under a tp=4 mesh runs the kernel per device
    (shard_over_heads). Every query head must still meet its own K/V planes:
    against the brute-force oracle for the plain layout, and against the
    unsharded call for the llama-1b packed layout (32/8 heads of 64, two
    heads per lane row). The kernel is stubbed by the reference, so this
    pins the split, not Mosaic — chip_smoke.py repeats it with the real
    kernel on the chip."""
    import functools

    import jax

    import llmd_tpu.ops.paged_attention as pa
    from llmd_tpu.models import get_model_config
    from llmd_tpu.ops.packed_kv import make_packed_attn, pack_factor
    from llmd_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(pa, "_kernel", lambda: _reference_as_kernel)
    mesh = build_mesh(MeshConfig(tp=4))
    cu = jnp.asarray([0, 8, 9, 10], jnp.int32)
    ns = jnp.asarray([3], jnp.int32)

    # plain layout: 8 query heads over 4 KV heads of 128 -> one K/V pair
    # and its two query heads per device
    q, kv, pt, pos, sids, lens = _mk_flat_case([40, 9, 21], [8, 1, 1], 8, 4, 128,
                                               P=32, ps=16, max_pages=4)
    args = [jnp.asarray(a) for a in (q, kv, pt, pos, sids, lens)]
    got = jax.jit(functools.partial(pa.paged_attention_tpu, scale=0.09,
                                    mesh=mesh))(*args, cu_q_lens=cu,
                                                num_seqs=ns)
    want = _np_oracle(q, kv, pt, pos, sids, lens, 0.09)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)

    # llama-1b packed layout: 8 packed planes -> one packed K/V pair (two
    # real KV heads) and its eight query heads per device
    cfg = get_model_config("llama-1b")
    f = pack_factor(cfg)
    assert f == 2
    q, kv, pt, pos, sids, lens = _mk_flat_case(
        [40, 9, 21], [8, 1, 1], cfg.num_heads, cfg.num_kv_heads // f, 128,
        P=32, ps=16, max_pages=4, seed=1)
    q[..., cfg.head_dim:] = 0.0  # the padded-q contract forward_core keeps
    args = [jnp.asarray(a) for a in (q, kv, pt, pos, sids, lens)]
    kw = dict(scale=cfg.head_dim ** -0.5, cu_q_lens=cu, num_seqs=ns)
    got = jax.jit(make_packed_attn(
        functools.partial(pa.paged_attention_tpu, mesh=mesh), cfg, f),
        static_argnames="scale")(*args, **kw)
    want = make_packed_attn(ragged_paged_attention_xla, cfg, f)(*args, **kw)
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
