"""Lower every Pallas kernel for TPU from the CPU.

The suite forces the CPU platform, where Pallas runs in interpret mode and
the engine selects the XLA attention reference — so nothing that only
breaks on the Mosaic path (a renamed compiler-params class, a kernel called
bare inside a multi-device jit) is ever executed. Cross-lowering
(``jax.export`` with ``platforms=["tpu"]``, ``interpret=False``) runs the
real TPU lowering rules without a chip. It proves the call lowers; whether
Mosaic then accepts the kernel (VMEM, tiling) is what the compile for a
described v5e (the ``one_chip`` fixture) says for the attention kernel at the
benchmark's shapes, and ``chip_smoke.py`` on the chip for the rest.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llmd_tpu.models import get_model_config
from llmd_tpu.models.transformer import padded_head_dim
from llmd_tpu.ops.grouped_gemm import grouped_gemm, ragged_grouped_gemm
from llmd_tpu.ops.mla_attention import mla_paged_attention
from llmd_tpu.ops.packed_kv import make_packed_attn, pack_factor
from llmd_tpu.ops.paged_attention import paged_attention_tpu
from llmd_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lower_for_tpu(fn, *args):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    text = exported.mlir_module()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowered module"
    return text


def _spec(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _attn_args(q_shape, cache_shape, B, maxp, mesh=None, q_spec=None,
               cache_spec=None):
    def sh(spec):
        return NamedSharding(mesh, spec) if mesh is not None else None

    N = q_shape[0]
    return (
        _spec(q_shape, jnp.bfloat16, sh(q_spec)),
        _spec(cache_shape, jnp.bfloat16, sh(cache_spec)),
        _spec((B, maxp), jnp.int32, sh(P())),   # page_tables
        _spec((N,), jnp.int32, sh(P())),        # positions
        _spec((N,), jnp.int32, sh(P())),        # seq_slots
        _spec((B,), jnp.int32, sh(P())),        # kv_lens
        _spec((B + 1,), jnp.int32, sh(P())),    # cu_q_lens
        _spec((1,), jnp.int32, sh(P())),        # num_seqs
    )


def _llama_packed_attn(mesh=None):
    cfg = get_model_config("llama-1b")
    f = pack_factor(cfg)
    assert f == 2, "llama-1b must exercise the packed KV layout"
    inner = functools.partial(paged_attention_tpu, mesh=mesh)
    attn = make_packed_attn(inner, cfg, f)

    def fn(q, cache, pt, pos, slots, lens, cu, ns):
        return attn(q, cache, pt, pos, slots, lens, scale=cfg.head_dim ** -0.5,
                    cu_q_lens=cu, num_seqs=ns)

    dhp = padded_head_dim(cfg.head_dim)
    q_shape = lambda n: (n, cfg.num_heads, dhp)  # noqa: E731
    cache_shape = (64 * 4, 16, 2 * cfg.num_kv_heads // f, dhp)
    return fn, q_shape, cache_shape


@pytest.fixture(scope="module")
def one_chip():
    """A described, not attached, v5e chip: the TPU's own compiler runs here
    without one. Only in a fixture: the worker that is given this file loads
    the TPU's library, and no other."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (query heads, KV heads, pages a sequence) of the benchmark's configurations:
# heads of 128, 16-token pages, model lengths 4,096, 8,192 and 16,384
CELL_LAYOUTS = {"qwen2.5-1.5b": (12, 2, 256), "mistral-7b-v0.3": (32, 8, 512),
                "smallthinker": (28, 4, 1024), "jamba2-3b": (20, 1, 192)}
# the geometry the rule gives each at N = 64 (fused decode) and 256 (unified)
CELL_GEOMETRY = {"qwen2.5-1.5b": {64: (32, 8), 256: (32, 16)},
                 "mistral-7b-v0.3": {64: (32, 8), 256: (32, 16)},
                 "smallthinker": {64: (64, 4), 256: (64, 8)},
                 "jamba2-3b": {64: (32, 8), 256: (32, 16)}}


@pytest.mark.parametrize("n", [64, 256])  # fused decode seats; unified tokens
@pytest.mark.parametrize("config,window", [
    (c, 0) for c in sorted(CELL_LAYOUTS)] + [("smallthinker", 4096),
                                             ("jamba2-3b", -1)])
def test_cell_shapes_compile_for_v5e_with_the_rules_geometry(one_chip, config,
                                                             window, n):
    """The geometry `pick_block_sizes` gives the cells' step programs goes
    through Mosaic and the TPU compiler here: one it refuses (VMEM, tiling, an
    unaligned slice) fails this test and not the cell (at 256 tokens the
    program is the unified step's, two kernel calls where the rows are not
    cut: `test_a_unified_steps_two_calls_compile_for_v5e`). A window layer's call
    (the kernel's mask and page tables shifted by whole KV blocks) beside the
    full layer's, and (window -1) the call whose rows are cut at their KV
    blocks' ends, which a model with recurrent layers makes: twice the rows
    in the kernel's scalar memory."""
    from llmd_tpu.ops.paged_attention import call_geometry

    heads, kv_heads, maxp = CELL_LAYOUTS[config]
    kw = ({"split_at_kv_blocks": True} if window < 0 else
          {"sliding_window": window} if window else {})

    def fn(q, cache, pt, pos, slots, lens, cu, ns):
        return paged_attention_tpu(q, cache, pt, pos, slots, lens,
                                   scale=128 ** -0.5, cu_q_lens=cu,
                                   num_seqs=ns, **kw)

    q_shape, cache_shape = (n, heads, 128), (1024, 16, 2 * kv_heads, 128)
    assert call_geometry(q_shape, cache_shape, maxp) == CELL_GEOMETRY[config][n]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _attn_args(q_shape, cache_shape, 64, maxp)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "ragged_paged_attention_kernel" in text


@pytest.mark.parametrize("config,window,calls", [
    ("mistral-7b-v0.3", 0, ((32, 8), (32, 64))),
    ("smallthinker", 0, ((64, 4), (64, 16))),
    ("smallthinker", 4096, ((64, 4), (64, 16))),
])
def test_a_unified_steps_two_calls_compile_for_v5e(one_chip, config, window,
                                                   calls):
    """The unified step at Mistral's and SmallThinker's published widths is
    two kernel calls in the compiled program, the decode rows' over 64 query
    tokens and the chunks' over 256, at the pairs `step_geometry` names: the
    chunk pair (64 query rows a block at 32/8 heads: the kernel's largest
    scratch) is one no single call ran before."""
    import re

    from llmd_tpu.ops.paged_attention import step_geometry

    heads, kv_heads, maxp = CELL_LAYOUTS[config]
    kw = {"sliding_window": window} if window else {}

    def fn(q, cache, pt, pos, slots, lens, cu, ns):
        return paged_attention_tpu(q, cache, pt, pos, slots, lens,
                                   scale=128 ** -0.5, cu_q_lens=cu,
                                   num_seqs=ns, **kw)

    q_shape, cache_shape = (256, heads, 128), (1024, 16, 2 * kv_heads, 128)
    assert step_geometry(q_shape, cache_shape, 64, maxp) == calls
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _attn_args(q_shape, cache_shape, 64, maxp)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert sorted(int(n) for n in re.findall(
        r"%ragged_paged_attention_kernel\S* = bf16\[(\d+),", text)) == [64, 256]


@pytest.mark.parametrize("n", [64, 256], ids=["decode", "unified"])
@pytest.mark.parametrize("config,maxp,window", [
    ("qwen2.5-1.5b", 256, 0), ("mistral-7b-v0.3", 800, 0),
    ("smallthinker", 1024, 0), ("smallthinker", 1024, 4096)])
def test_the_rows_kernel_compiles_for_v5e_at_the_cells_shapes(
        one_chip, config, maxp, window, n):
    """The one-query rows of the three GQA cells through the repo's kernel
    (Mistral's layout takes it; the other two are kept compiling for the PR
    that passes them), planned and called as `forward_core` does: the fused
    decode call
    (one device operation, the rows kernel's) and the unified step (the rows
    kernel for its 64 leading query tokens, the upstream call for its
    chunks), at the cells' own page budgets (Mistral's 800 pages a sequence
    are 51,200 table entries in scalar memory); a window layer's rows keep
    the upstream call."""
    import re

    import llmd_tpu.ops.paged_attention as pa

    heads, kv_heads, _ = CELL_LAYOUTS[config]
    hpk = heads // kv_heads
    assert pa.rows_kernel_serves(hpk, jnp.bfloat16, None) == (hpk == 4)
    kw = {"sliding_window": window} if window else {}

    def fn(q, cache, pt, pos, slots, lens, cu, ns):
        plan = pa.plan(pt, lens, cu, ns, 16, heads_per_kv=hpk)
        return paged_attention_tpu(q, cache, pt, pos, slots, lens,
                                   scale=128 ** -0.5, cu_q_lens=cu,
                                   num_seqs=ns, one_query_rows=n == 64,
                                   **plan, **kw)

    q_shape, cache_shape = (n, heads, 128), (1024, 16, 2 * kv_heads, 128)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _attn_args(q_shape, cache_shape, 64, maxp)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = re.findall(r"%(ragged_paged_attention_\w+?)[.\d]* = bf16\[(\d+),",
                       text)
    rows = "kernel" if window else "rows"
    assert sorted(names) == [("ragged_paged_attention_kernel", "256")] * (
        n == 256) + [("ragged_paged_attention_" + rows, "64")]


@pytest.mark.parametrize("n,state", [(64, jnp.float32), (256, jnp.float32),
                                     (256, jnp.bfloat16)],
                         ids=["decode", "unified", "unified_bf16_state"])
def test_selective_scan_compiles_for_v5e_at_the_cells_shapes(one_chip, n,
                                                             state):
    """The Mamba layers' kernel at jamba2-3b's widths (d_inner 5120, d_state
    16, 26 layers of 65 slots folded into the pool) and both step programs'
    token budgets goes through Mosaic and the TPU compiler here: the dynamic
    single-row loads and stores, the [N, 1] columns of B and C and the VMEM
    the resident blocks take are what interpret mode cannot refuse."""
    from llmd_tpu.ops.selective_scan import channel_block, selective_scan_pallas

    di, ns, seats = 5120, 16, 64
    assert (channel_block(64, di), channel_block(256, di)) == (5120, 1280)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    args = (spec((n, di), f32), spec((n, di), f32), spec((n, ns), f32),
            spec((n, ns), f32), spec((ns, di), f32),
            spec((26 * (seats + 1), ns, di), state), spec((seats,), jnp.int32),
            spec((seats + 1,), jnp.int32), spec((seats,), jnp.bool_),
            spec((seats,), jnp.bool_))
    compiled = jax.jit(selective_scan_pallas, donate_argnums=(5,)).lower(
        *args).compile()
    assert "selective_scan" in compiled.as_text()
    # in place: the pool is neither copied nor a temporary of its own size
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


def test_selective_scan_lowers_for_tpu():
    from llmd_tpu.ops.selective_scan import selective_scan_pallas

    f32 = jnp.float32
    _lower_for_tpu(
        selective_scan_pallas, _spec((32, 256), f32), _spec((32, 256), f32),
        _spec((32, 16), f32), _spec((32, 16), f32), _spec((16, 256), f32),
        _spec((10, 16, 256), f32), _spec((4,), jnp.int32),
        _spec((5,), jnp.int32), _spec((4,), jnp.bool_), _spec((4,), jnp.bool_))


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_hybrid_step_programs_keep_no_window_row_on_the_sublanes(program):
    """A model with mamba layers (tiny-jamba, bf16, the Pallas scan) through
    ``forward_core`` as the fused decode call packs it (row b is seat b) and
    as a unified step does (a row -> slot map): the conv window's pool is
    planes of [slots, d_inner], so the lowered program holds no bf16 value
    whose second-minor extent is K - 1: the slot-major pool's shape, which
    the TPU compiler kept as planes of its own accord and transposed at
    every forward."""
    import re

    from llmd_tpu.models.transformer import (
        forward_core, init_cache, init_params, init_state)
    from llmd_tpu.ops.selective_scan import selective_scan_pallas

    cfg = get_model_config("tiny-jamba")
    B, N, maxp = 8, 8 if program == "decode" else 32, 4
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    pools = jax.eval_shape(lambda: {"kv": init_cache(cfg, 32, 16),
                                    **init_state(cfg, B)})
    assert pools["conv"].shape == (cfg.num_mamba_layers, cfg.mamba_d_conv - 1,
                                   B + 1, cfg.mamba_d_inner)

    def i32(*shape):
        return _spec(shape, jnp.int32)

    def step(params, pools, tokens, positions, seq_slots, pt, lens, cu, slots):
        return forward_core(
            cfg, params, pools, tokens, positions, seq_slots, pt, lens,
            cu_q_lens=cu, num_seqs=jnp.asarray([B], jnp.int32),
            state_slots=slots if program == "unified" else None,
            scan_impl=selective_scan_pallas)

    text = _lower_for_tpu(step, params, pools, i32(N), i32(N), i32(N),
                          i32(B, maxp), i32(B), i32(B + 1), i32(B))
    assert "selective_scan" in text
    rows = cfg.mamba_d_conv - 1
    assert rows not in (B, B + 1, N)  # or the pattern would find a batch
    found = sorted(set(re.findall(rf"tensor<(?:\d+x)*{rows}x\d+xbf16>", text)))
    assert not found, found


@pytest.mark.parametrize("heads,kv_heads,geometry,block,window", [
    (4, 2, (32, 8), 512, None),     # the even ratios' pair
    (14, 2, (64, 4), 1024, None),   # seven query heads a KV head
    (14, 2, (64, 4), 1024, 640),    # and as a window layer's call
])
def test_rules_geometry_matches_xla_reference_in_interpret_mode(
        monkeypatch, heads, kv_heads, geometry, block, window):
    """The upstream kernel at the rule's geometry (KV blocks of 32 or 64
    pages) against the XLA reference: decode rows whose contexts span one,
    two and three KV blocks, and a prefill chunk that crosses a query-block
    boundary, in one batch; with a window, through page tables shifted by
    whole KV blocks on the kernel's side and by whole pages on the
    reference's."""
    from jax.experimental import pallas as pl

    from llmd_tpu.models.transformer import ragged_paged_attention_xla
    from llmd_tpu.ops.paged_attention import call_geometry

    import numpy as np

    ps, H, Hk, D, N, B = 16, heads, kv_heads, 128, 32, 8
    maxp = 3 * block // ps
    seq_lens = [block * 19 // 32, block * 11 // 8, block * 17 // 8,
                block * 19 // 16]
    q_lens = [1, 1, 1, 20]
    rng = np.random.default_rng(0)
    P = sum(-(-L // ps) for L in seq_lens) + 3
    assert call_geometry((N, H, D), (P, ps, 2 * Hk, D), maxp) == geometry
    free = rng.permutation(P)
    pt = np.full((B, maxp), -1, np.int32)
    lens, cu = np.ones((B,), np.int32), np.zeros((B + 1,), np.int32)
    pos, sids = np.full((N,), -1, np.int32), np.zeros((N,), np.int32)
    off = used = 0
    for b, (L, qn) in enumerate(zip(seq_lens, q_lens)):
        n = -(-L // ps)
        pt[b, :n] = free[used:used + n]
        pos[off:off + qn], sids[off:off + qn] = np.arange(L - qn, L), b
        lens[b], used, off = L, used + n, off + qn
        cu[b + 1] = off
    cu[len(seq_lens) + 1:] = off
    args = (jnp.asarray(rng.standard_normal((N, H, D)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((P, ps, 2 * Hk, D)), jnp.bfloat16),
            jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(sids),
            jnp.asarray(lens))
    kw = dict(scale=D ** -0.5, cu_q_lens=jnp.asarray(cu),
              num_seqs=jnp.asarray([len(seq_lens)], jnp.int32))
    if window:
        kw["sliding_window"] = window
    want = np.asarray(ragged_paged_attention_xla(*args, **kw), np.float32)
    # the upstream wrapper takes no interpret flag: give its pallas_call one
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    got = np.asarray(paged_attention_tpu(*args, **kw), np.float32)
    np.testing.assert_allclose(got[:off], want[:off], atol=0.01)


def test_packed_paged_attention_lowers_for_tpu():
    """llama-1b head layout (32/8 heads of 64, packed 2 per lane row) at the
    decode shape (one token per slot) and a prefill chunk."""
    fn, q_shape, cache_shape = _llama_packed_attn()
    for n, B in ((64, 64), (256, 64)):
        _lower_for_tpu(fn, *_attn_args(q_shape(n), cache_shape, B, 64))


def test_paged_attention_lowers_under_tp4_sharding():
    """The engine's tp layout: query heads and combined KV heads over tp.
    Called bare, Mosaic refuses ("cannot be automatically partitioned");
    the ops module wraps the kernel in shard_map."""
    mesh = build_mesh(MeshConfig(tp=4))
    fn, q_shape, cache_shape = _llama_packed_attn(mesh)
    args = _attn_args(q_shape(64), cache_shape, 64, 64, mesh=mesh,
                      q_spec=P(None, "tp", None),
                      cache_spec=P(None, None, "tp", None))
    _lower_for_tpu(fn, *args)


@pytest.mark.parametrize("case", ["tp4", "fp8"])
def test_a_unified_steps_two_calls_lower_where_no_cell_runs_them(case):
    """The two-call form of a unified step (256 tokens over 64 rows) on the
    paths no benchmark cell runs: under the engine's tp layout (each call in
    its own shard_map, the decode rows' over a slice of the queries) and over
    fp8 pages, both through the packed-KV wrapper."""
    mesh = build_mesh(MeshConfig(tp=4)) if case == "tp4" else None
    fn, q_shape, cache_shape = _llama_packed_attn(mesh)
    args = list(_attn_args(q_shape(256), cache_shape, 64, 64, mesh=mesh,
                           q_spec=P(None, "tp", None),
                           cache_spec=P(None, None, "tp", None)))
    if case == "fp8":
        args[1] = _spec(cache_shape, jnp.float8_e4m3fn)
    text = _lower_for_tpu(fn, *args)
    assert text.count("tpu_custom_call") >= 2


def test_mla_latent_kernel_lowers_for_tpu():
    cfg = get_model_config("moe-wide-mla")
    dhp = padded_head_dim(cfg.mla_kv_lora_rank + cfg.mla_rope_dim)
    B, maxp = 16, 8

    def fn(q, cache, pt, pos, slots, lens, cu, ns):
        return mla_paged_attention(
            q, cache, pt, pos, slots, lens, scale=0.125, cu_q_lens=cu,
            num_seqs=ns, interpret=False)

    _lower_for_tpu(fn, *_attn_args((B, cfg.num_heads, dhp),
                                   (B * maxp, 16, 1, dhp), B, maxp))


def test_mla_latent_kernel_lowers_under_tp4_sharding():
    cfg = get_model_config("moe-wide-mla")
    dhp = padded_head_dim(cfg.mla_kv_lora_rank + cfg.mla_rope_dim)
    B, maxp = 16, 8
    mesh = build_mesh(MeshConfig(tp=4))

    def fn(q, cache, pt, pos, slots, lens, cu, ns):
        return mla_paged_attention(
            q, cache, pt, pos, slots, lens, scale=0.125, cu_q_lens=cu,
            num_seqs=ns, interpret=False, mesh=mesh)

    args = _attn_args((B, cfg.num_heads, dhp), (B * maxp, 16, 1, dhp), B,
                      maxp, mesh=mesh, q_spec=P(None, "tp", None),
                      cache_spec=P())
    _lower_for_tpu(fn, *args)


@pytest.mark.parametrize("n,H", [(64, 20), (256, 20), (256, 10), (256, 5)],
                         ids=["decode", "unified", "unified_a_shard_of_tp2",
                              "unified_a_shard_of_tp4"])
def test_mla_latent_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip, n,
                                                                H):
    """The latent kernel at glm-4.7-flash's shapes (20 heads, a chunk's
    query block folded to 320 rows by a 0/1 product and spread back by its
    transpose, the weighted sum over 512 of the 640 lanes of a single-plane
    pool of 7 x 28,672 pages, page tables of 1,280 entries for 64 rows in
    scalar memory, 64 pages a KV block) and both step programs' token
    budgets goes through Mosaic and the TPU compiler here: the manual page
    copies in dynamic loops, the scalar-prefetched table's size, the VMEM of
    two KV buffers, the lane slice of the value product and of the output's
    copies are what interpret mode cannot refuse."""
    from llmd_tpu.ops.mla_attention import (
        chunk_fold, pick_block_sizes, value_lanes)

    B, maxp, H, lanes, pages = 64, 1280, 20, 640, 7 * 28672
    assert pick_block_sizes(n, B, 16, maxp) == (64, 1 if n == B else 16)
    assert (chunk_fold(16, H), value_lanes(512, lanes)) == (20, 512)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, cache, pt, lens, cu, ns):
        return mla_paged_attention(q, cache, pt, None, None, lens,
                                   scale=1 / 16, cu_q_lens=cu, num_seqs=ns,
                                   rank=512)

    i32 = jnp.int32
    compiled = jax.jit(fn).lower(
        spec((n, H, lanes), jnp.bfloat16),
        spec((pages, 16, 1, lanes), jnp.bfloat16), spec((B, maxp), i32),
        spec((B,), i32), spec((B + 1,), i32), spec((1,), i32)).compile()
    assert "mla_ragged_paged_attention" in compiled.as_text()
    # the pool is read in place: no temporary of its size
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


def test_grouped_gemms_lower_for_tpu():
    G, C, D, F = 8, 32, 256, 512
    _lower_for_tpu(
        functools.partial(grouped_gemm, interpret=False),
        _spec((G, C, D), jnp.bfloat16), _spec((G, D, F), jnp.bfloat16),
        _spec((G,), jnp.int32))
    nb, bc = 12, 16
    _lower_for_tpu(
        functools.partial(ragged_grouped_gemm, interpret=False),
        _spec((nb, bc, D), jnp.bfloat16), _spec((G, D, F), jnp.bfloat16),
        _spec((nb,), jnp.int32), _spec((nb,), jnp.int32))


@pytest.mark.parametrize("bc", [8, 32], ids=["decode", "unified"])
@pytest.mark.parametrize("bank,D,F", [("moe_wi", 2560, 1536),
                                      ("moe_wo", 768, 2560)])
def test_ragged_grouped_gemm_compiles_for_v5e_at_the_cells_shapes(one_chip,
                                                                  bank, D, F,
                                                                  bc):
    """The experts' kernel at smallthinker-21b-a3b's widths (64 experts a
    layer, four layers' banks stacked: 256 slots; 112 blocks of 8 rows in the
    fused decode call and of 32 in the unified step) with the tile the rule
    gives, the whole F: an expert's bank double-buffered (15.7 MB for
    ``moe_wi``, 16.4 MB with the activation and output blocks) leaves
    nothing of Mosaic's default 16 MiB of scoped VMEM, so the call asks for
    its own limit and the compiler has to grant it."""
    from llmd_tpu.ops.grouped_gemm import (RGG_VMEM_LIMIT, pick_bank_tile,
                                           rgg_vmem_bytes)

    nb, slots = 112, 4 * 64
    assert pick_bank_tile(D, F, bc, 2) == F
    assert 16e6 < rgg_vmem_bytes(32, 2560, 1536, 2) < RGG_VMEM_LIMIT

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(ragged_grouped_gemm).lower(
        spec((nb, bc, D), jnp.bfloat16), spec((slots, D, F), jnp.bfloat16),
        spec((nb,), jnp.int32), spec((nb,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged_grouped_gemm" in text
    # the bank is read where it lies: no copy of it among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_attention_heads_must_split_over_tp():
    """K/V pairs of one head must stay on one device: a layout that cannot
    split is an error at trace time, never a silently wrong shard."""
    mesh = build_mesh(MeshConfig(tp=8))
    fn, q_shape, cache_shape = _llama_packed_attn(mesh)  # 4 packed heads / 8
    with pytest.raises(ValueError, match="do not split over tp=8"):
        jax.eval_shape(fn, *_attn_args(q_shape(64), cache_shape, 64, 64))


@pytest.mark.parametrize("n", [32, 256], ids=["decode", "unified"])
def test_lightning_attention_compiles_for_v5e_at_the_cells_shapes(one_chip, n):
    """The lightning layers' kernel at minicpm-sala-9b's widths (32 heads of
    128, 6 layers of 33 slots folded into the pool, float32 state) and both
    step programs' token budgets goes through Mosaic and the TPU compiler
    here: the row-by-row loads of a block that starts off a sublane tile, the
    transposed product k^T v at 16 rows and the VMEM of the resident blocks
    are what interpret mode cannot refuse."""
    from llmd_tpu.ops.lightning_attention import lightning_attention_pallas

    H, D, seats = 32, 128, 32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((n, H, D), jnp.bfloat16),) * 3 + (
        spec((H,), jnp.float32), spec((6 * (seats + 1), H, D, D), jnp.float32),
        spec((seats,), jnp.int32), spec((seats + 1,), jnp.int32),
        spec((seats,), jnp.bool_), spec((seats,), jnp.bool_))
    compiled = jax.jit(
        lambda *a: lightning_attention_pallas(*a, scale=D ** -0.5),
        donate_argnums=(4,)).lower(*args).compile()
    assert "lightning_attention" in compiled.as_text()
    # in place: the 0.42 GB pool is neither copied nor a temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("n,rows", [(64, 64), (320, 64)],
                         ids=["decode", "unified"])
def test_mamba2_ssd_compiles_for_v5e_at_the_cells_shapes(one_chip, n, rows):
    """The Mamba-2 layers' kernel at nemotron-3-nano-30b-a3b's widths (64
    heads of 64 channels, 8 groups of state 128, 6 layers of 65 slots folded
    into the pool, float32 state) at both step programs' token budgets goes
    through Mosaic and the TPU compiler here: the row-by-row loads of a block
    that starts off a sublane tile, the transposition that makes a token's B
    and C columns, the transposed products of the cumulative sums and of the
    state's update, and the VMEM of the resident blocks are what interpret
    mode cannot refuse."""
    from llmd_tpu.ops.mamba2_ssd import mamba2_ssd_pallas

    H, P, G, N, seats = 64, 64, 8, 128, 64

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((n, H, P), jnp.bfloat16), spec((n, H), jnp.float32),
            spec((H,), jnp.float32), spec((n, G, N), jnp.bfloat16),
            spec((n, G, N), jnp.bfloat16),
            spec((6 * (seats + 1), G, N, H // G * P), jnp.float32),
            spec((rows,), jnp.int32), spec((rows + 1,), jnp.int32),
            spec((rows,), jnp.bool_), spec((rows,), jnp.bool_))
    compiled = jax.jit(mamba2_ssd_pallas, donate_argnums=(5,)).lower(
        *args).compile()
    assert "mamba2_ssd" in compiled.as_text()
    # in place: the 0.82 GB pool is neither copied nor a temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


def test_mamba2_ssd_lowers_for_tpu():
    from llmd_tpu.ops.mamba2_ssd import mamba2_ssd_pallas

    n, H, P, G, N, rows = 48, 8, 32, 2, 16, 4
    _lower_for_tpu(
        mamba2_ssd_pallas, _spec((n, H, P), jnp.bfloat16),
        _spec((n, H), jnp.float32), _spec((H,), jnp.float32),
        _spec((n, G, N), jnp.bfloat16), _spec((n, G, N), jnp.bfloat16),
        _spec((9, G, N, H // G * P), jnp.float32), _spec((rows,), jnp.int32),
        _spec((rows + 1,), jnp.int32), _spec((rows,), jnp.bool_),
        _spec((rows,), jnp.bool_))


@pytest.mark.parametrize("bc", [8, 32], ids=["decode", "unified"])
@pytest.mark.parametrize("bank,D,F", [("moe_wi", 2688, 1920),
                                      ("moe_wo", 1920, 2688)])
def test_non_gated_banks_compile_for_v5e_where_they_lie(one_chip, bank, D, F,
                                                        bc):
    """The experts' kernel at nemotron-3-nano-30b-a3b's widths: 64 held
    experts a layer, six layers' banks stacked (384 slots), the width 1,856
    stored as 1,920 (``ModelConfig.moe_bank_width``). The tile is the whole
    F, and the bank is read where it lies: at 1,856 the compiler copied the
    whole stack into a padded layout at every call's entry (3.7 GB; the
    engine did not fit the chip)."""
    from llmd_tpu.models.config import ModelConfig
    from llmd_tpu.ops.grouped_gemm import pick_bank_tile
    from llmd_tpu.ops.moe_dispatch import pick_block_size, plan_blocks

    assert ModelConfig(
        name="x", vocab_size=8, hidden_size=8, intermediate_size=1856,
        num_layers=1, num_heads=1, num_kv_heads=1, head_dim=8,
        moe_num_experts=2, moe_top_k=1, moe_gated=False,
        moe_activation="relu2").moe_bank_width == 1920
    copies = {8: 64 * 6, 32: 320 * 6}[bc]
    assert pick_block_size(copies, 64, True) == bc
    nb, slots = plan_blocks(copies, 64, bc), 6 * 64
    assert pick_bank_tile(D, F, bc, 2) == F

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(ragged_grouped_gemm).lower(
        spec((nb, bc, D), jnp.bfloat16), spec((slots, D, F), jnp.bfloat16),
        spec((nb,), jnp.int32), spec((nb,), jnp.int32)).compile()
    assert "ragged_grouped_gemm" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_sparse_hybrid_step_programs_compile_for_v5e_at_the_cells_sizes(
        one_chip, program):
    """minicpm-sala-9b's whole forward (the file's layers at the published
    widths, the cell's pools: 32,768 pages a fold, 32 seats, tables of 1,280
    pages)
    through the TPU compiler as each step program packs it, with the Pallas
    attention and lightning kernels: the selected page tables' calls (256
    one-query rows of 388 pages in scalar memory), the KV heads folded into
    the pool, no stacked leaf copied whole at the call's entry (kept [D,
    heads, lanes], the lightning q, k, v and gate matrices were transposed
    there: 1.2 GB of temporaries a call), and no layer's matrix copied out
    of its stack before its product but the four that stay (ISSUE 56)."""
    import functools
    import json
    import sys

    from llmd_tpu.models.transformer import (
        forward_core, init_cache, init_compressed_keys, init_params,
        init_state)
    from llmd_tpu.ops.lightning_attention import lightning_attention_pallas

    sys.path.append(os.path.join(ROOT, "perfbench"))
    try:
        from reference import hybrid_lightning_sparse as family
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm-sala-9b.json")) as f:
        conf = json.load(f)
    cfg, e = family.model_config(conf), conf["engine"]
    B, N = e["max_batch_size"], e["max_batch_size"] if program == "decode" \
        else e["prefill_chunk"]
    maxp = e["max_model_len"] // e["page_size"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    pools = on_chip(jax.eval_shape(lambda: {
        "kv": init_cache(cfg, e["num_pages"], e["page_size"]),
        "ck": init_compressed_keys(cfg, e["num_pages"]),
        **init_state(cfg, B)}))
    attn = functools.partial(paged_attention_tpu,
                             split_at_kv_blocks=program == "unified")

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, pools, tokens, positions, seq_slots, pt, lens, cu, ns,
             slots):
        return forward_core(
            cfg, params, pools, tokens, positions, seq_slots, pt, lens,
            cu_q_lens=cu, num_seqs=ns, attn_impl=attn,
            query_attn_impl=paged_attention_tpu,
            state_slots=slots if program == "unified" else None,
            lin_impl=lightning_attention_pallas)[:2]

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, i32(N), i32(N), i32(N), i32(B, maxp), i32(B),
        i32(B + 1), i32(1), i32(B)).compile()
    text = compiled.as_text()
    assert "lightning_attention" in text
    assert "ragged_paged_attention_kernel" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 1024 * 1024
    # no layer's matrix is copied out of its stack before its product but
    # the sparse pair's [D, heads, lanes] leaves (``_hybrid_stack``'s
    # docstring: wq and wg, 33.5 MB, wk and wv, 2 MB); the parent copied the
    # lightning layers' four input matrices and the sparse ``wo`` too, and
    # relaid ``wg`` behind its slice (14 rows in the unified program)
    from llmd_tpu.obs.program_parts import weight_copies

    rows = weight_copies(text)
    assert len(rows) <= 4, [r["instruction"] for r in rows]
    assert all(r["result"].startswith(("bf16[1,4096,32,128]{3,2,1,0",
                                       "bf16[1,4096,2,128]{3,2,1,0"))
               for r in rows), [r["result"] for r in rows]


@pytest.mark.parametrize("n", [64, 320], ids=["decode", "unified"])
def test_kda_attention_compiles_for_v5e_at_the_cells_shapes(one_chip, n):
    """The kda layers' kernel at ling-3.0-flash-vl's widths (32 heads of 128,
    6 layers of 65 slots folded into the pool, float32 state) and both step
    programs' token budgets goes through Mosaic and the TPU compiler here:
    float32 products at the highest precision, the transposed product U^T K at
    16 and at 8 rows, a row of every head's lanes loaded at a dynamic
    sublane, and the VMEM of eight heads' resident blocks are what interpret
    mode cannot refuse."""
    from llmd_tpu.ops.kda_attention import kda_attention_pallas

    H, D, seats = 32, 128, 64

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((n, H, D), jnp.float32),) * 4 + (
        spec((n, H), jnp.float32),
        spec((6 * (seats + 1), H, D, D), jnp.float32),
        spec((seats,), jnp.int32), spec((seats + 1,), jnp.int32),
        spec((seats,), jnp.bool_), spec((seats,), jnp.bool_))
    compiled = jax.jit(kda_attention_pallas,
                       donate_argnums=(5,)).lower(*args).compile()
    assert "kda_attention" in compiled.as_text()
    # in place: the 0.82 GB pool is neither copied nor a temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_kda_latent_step_programs_compile_for_v5e_at_the_cells_sizes(
        one_chip, program):
    """ling-3.0-flash-vl's whole forward (the file's 7 layers at the
    published widths, 128 held experts a layer, the cell's pools: 49,152
    latent pages, 65 matrix-state slots a kda layer) through the TPU compiler
    as each step program packs it, with the Pallas kda, latent-attention and
    grouped-GEMM kernels: 10.5 GB of leaves and 1.85 GB of pools as
    arguments, and no stacked leaf or pool copied at the call's entry."""
    import functools
    import json
    import sys

    from llmd_tpu.models.transformer import (
        forward_core, init_cache, init_params, init_state)
    from llmd_tpu.ops import mla_attention
    from llmd_tpu.ops.grouped_gemm import make_moe_matmul
    from llmd_tpu.ops.kda_attention import kda_attention_pallas
    from llmd_tpu.ops.moe_dispatch import make_sorted_dispatch

    sys.path.append(os.path.join(ROOT, "perfbench"))
    try:
        from reference import hybrid_kda_mla_moe as family
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        conf = json.load(f)
    cfg, e = family.model_config(conf), conf["engine"]
    B = e["max_batch_size"]
    N = B if program == "decode" else B + e["prefill_chunk"]
    maxp = e["max_model_len"] // e["page_size"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    pools = on_chip(jax.eval_shape(lambda: {
        "kv": init_cache(cfg, e["num_pages"], e["page_size"]),
        **init_state(cfg, B)}))
    leaves = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert 10.4e9 < leaves < 10.6e9  # 5.23 G parameters in bf16
    attn = functools.partial(mla_attention.mla_paged_attention,
                             rank=cfg.mla_kv_lora_rank)
    attn.plan = mla_attention.plan

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, pools, tokens, positions, seq_slots, pt, lens, cu, ns,
             slots):
        return forward_core(
            cfg, params, pools, tokens, positions, seq_slots, pt, lens,
            cu_q_lens=cu, num_seqs=ns, attn_impl=attn,
            moe_matmul_impl=make_moe_matmul(),
            moe_dispatch_impl=make_sorted_dispatch(None, use_pallas=True),
            state_slots=slots if program == "unified" else None,
            kda_impl=kda_attention_pallas)[:2]

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, i32(N), i32(N), i32(N), i32(B, maxp), i32(B),
        i32(B + 1), i32(1), i32(B)).compile(
            compiler_options={"xla_allow_excess_precision": False})
    text = compiled.as_text()
    assert "kda_attention" in text and "mla_ragged_paged_attention" in text
    assert "ragged_grouped_gemm" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 1024 * 1024
