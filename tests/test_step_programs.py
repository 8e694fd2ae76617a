"""The step programs lower to the StableHLO they had.

Every served family's tiny preset x the programs its engine builds, lowered
on shapes only (no compile, no run), against a table of sha256 taken on the
parent commit. On the CPU ``"auto"`` binds the XLA impls, so ``unified`` and
``decode`` stand once more under ``attn_impl="pallas", moe_matmul="pallas"``
(interpret mode): the bindings the chip takes (rows cut at KV blocks, the
latent kernel, the ragged grouped GEMM, ``query_attn_impl``). The upstream
GQA kernel has no interpret mode, so a stand-in takes its place that folds
everything it is handed, static arguments included, into its result: a call
that changes changes the text.

``python tests/test_step_programs.py`` prints the table of the tree it runs on.
"""

import dataclasses
import functools
import hashlib
import os
import sys

if __name__ == "__main__":  # as conftest.py does for a test run
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import pytest

import llmd_tpu.ops.paged_attention as pa
from llmd_tpu.engine.config import EngineConfig
from llmd_tpu.engine.engine import LLMEngine
from llmd_tpu.models import get_model_config
from llmd_tpu.models.lora import LoRAConfig
from llmd_tpu.parallel.mesh import MeshConfig

BASE = dict(page_size=8, num_pages=64, max_model_len=128, max_batch_size=4,
            prefill_chunk=32, decode_steps=4)
PALLAS = dict(attn_impl="pallas", moe_matmul="pallas")
ALL = ("unified", "decode", "decode_masked", "verify", "verify_masked",
       "embed")
# no speculation and no embedding beside recurrent layers (the engine
# refuses both: neither program is handed the state pool)
STATEFUL = ("unified", "decode", "decode_masked")

# variant -> (preset, EngineConfig fields, programs)
VARIANTS = {
    "tiny": ("tiny", {}, ALL),
    "tiny-moe": ("tiny-moe", {}, ALL),
    "tiny-glm": ("tiny-glm", dict(page_size=4), ALL),
    "tiny-jamba": ("tiny-jamba", dict(num_pages=128, max_model_len=96,
                                      prefill_chunk=16), STATEFUL),
    "tiny-sala": ("tiny-sala", dict(page_size=2, num_pages=512,
                                    max_model_len=256), STATEFUL),
    "tiny-nemotron-h": ("tiny-nemotron-h", dict(num_pages=128), STATEFUL),
    "tiny-ling": ("tiny-ling", dict(num_pages=128), STATEFUL),
    "tiny-moe+pallas": ("tiny-moe", PALLAS, ("unified", "decode")),
    "tiny-glm+pallas": ("tiny-glm", dict(PALLAS, page_size=4),
                        ("unified", "decode")),
    "tiny-jamba+pallas": ("tiny-jamba", dict(
        PALLAS, num_pages=128, max_model_len=96, prefill_chunk=16),
        ("unified", "decode")),
    "tiny-sala+pallas": ("tiny-sala", dict(
        PALLAS, page_size=2, num_pages=512, max_model_len=256),
        ("unified", "decode")),
    "tiny-nemotron-h+pallas": ("tiny-nemotron-h", dict(PALLAS, num_pages=128),
                               ("unified", "decode")),
    "tiny-ling+pallas": ("tiny-ling", dict(PALLAS, num_pages=128),
                         ("unified", "decode")),
    # four query heads a KV head (Mistral's ratio): the one-query rows of
    # both programs on the repo's rows kernel, in groups planned once
    "tiny-gqa4+pallas": ("tiny", dict(PALLAS, model=dict(
        num_heads=8, num_kv_heads=2)), ("unified", "decode")),
    # what the bodies pass beside the bound core: adapter indices, the
    # multimodal arrays, a mesh's constraints and the ring variant
    "tiny+lora": ("tiny", dict(lora=LoRAConfig(max_adapters=2, rank=4)),
                  ("unified", "decode", "verify", "embed")),
    "tiny-vl": ("tiny-vl", {}, ("unified",)),
    "tiny+sp2": ("tiny", dict(prefill_chunk=64, mesh=MeshConfig(sp=2)),
                 ("unified", "unified_ring", "decode")),
}

# sha256 of each program's StableHLO text, lowered as below, on 434dc76 (the
# tree before ISSUE 46): the resolver of `engine/backends.py`, the programs'
# move to `engine/programs.py`, the core bound once and each masked pair's one
# body leave every program the operations it had, in the order it had them.
PARENT_STABLEHLO = {
    "tiny/unified":
        "b3a6cd69bdfcbf4b127b92d251f96485b41be411b31a983fb7eb53bbe29b5929",
    "tiny/decode":
        "9a06ed4de964c06d8f3b32739d73eb58e8ecff84cbe3b4684d6aee6bb924afff",
    "tiny/decode_masked":
        "a58b7e0b04caed316c73d68564057e5e8d11f3d87456563cca9571068d85d410",
    "tiny/verify":
        "f8a9d78bb58dd54e893f7653cb04a388163d4706bdc6a8b2c989550a77271c7d",
    "tiny/verify_masked":
        "8445dcd737f984eb6642487cf78e97ac8ebaa34c6a7b4b2114d526aa7c728eb9",
    "tiny/embed":
        "dc85aa4c7d1e32f72df457a8ba3aaf06000cdcd97b83945b0c9b97324b03e480",
    "tiny-moe/unified":
        "a12d6511cc7dbd54bfc338011900970a3b221a65c96ceb58dfeb655a02fabd42",
    "tiny-moe/decode":
        "8eca22da0936fde5466329f0f4f7abac01c0116fc5b801403fdab6270b04a57a",
    "tiny-moe/decode_masked":
        "a1c97c1139992e59179e74905a5deb11bf12fd516a5ab46f09323106201b4945",
    "tiny-moe/verify":
        "ba35e824aaf430b5b5b25ee328df8b2c6f27d2e59e469c91978eddf81d0e3c11",
    "tiny-moe/verify_masked":
        "c8c6b149c34d5984f604013738b66f293a1fe0ad97330c4438d363369ff1d72e",
    "tiny-moe/embed":
        "c8e123f36f127df5570572c8bf45df527f6094232028e5c301ef0afb12380c14",
    "tiny-glm/unified":
        "933f144b5ef82846ba1f2b9d5f76fbc08e650f4bc440404100ec465a00a73a4c",
    "tiny-glm/decode":
        "5c7bcf120ee30be352bcbd3829db26d53dfdbd4aa72d2c39d9aa1961703f323d",
    "tiny-glm/decode_masked":
        "5dc243ac4ee733584d6ed8c3b092fa346f58bc818681e2bd1454f38abd701194",
    "tiny-glm/verify":
        "692b08bcabb607ebce9f1946c6d528865e0a6877f3e8c6077b571fe44a893f4b",
    "tiny-glm/verify_masked":
        "e322a6043c6000ea4e5d17a07e58da3dd16ffe92db0a06208427bd6e137c4e99",
    "tiny-glm/embed":
        "0a36747a48efacc0989fd63eff862623e0ff4863b743b8c15cb16641d0d73c27",
    "tiny-jamba/unified":
        "ef4c54ed93410ee471dc526c9316b7ea44c8bbfd047e662a9e190ff79504dbcf",
    "tiny-jamba/decode":
        "2dd9aa7bee2f084f69500acc0566bc42953c3a683c2125e4e6079acabd69e329",
    "tiny-jamba/decode_masked":
        "0f34bb9f8a97aa0174dfcbabbc5c37c9e7cae2542cb2e76053ffcc302a0a6ea4",
    "tiny-moe+pallas/unified":
        "a9259b8d22aa2d2ee69764fa2429692b502a91d5d81712cff82b4b122717d98a",
    "tiny-moe+pallas/decode":
        "6cbb833fda7fff56129da41795d26c672b461517aeb9ebe281b73b4a36603ed8",
    "tiny-jamba+pallas/unified":
        "6acb3eea3c60c596d12757fc71a0a8af0bed767c38c641b0a514d4e792014a84",
    "tiny-jamba+pallas/decode":
        "812bb12af41155d4f948e86ff85d9b5bd28f050fe44eab93d1152e0fca1c781e",
    "tiny+lora/unified":
        "b9692ce03bb4b96875f8e10aadb1ea386d59643cb296b6cbf86b3ebc7b261e17",
    "tiny+lora/decode":
        "9421ac1d69f4bbef7d403185e0a3c17e173cbfd6533cd71f44bcbe93f70d3f10",
    "tiny+lora/verify":
        "66b6ec84d974af8ed70629367bc6293ba3e0dddefc5ddf3eae5dc058f85cfa2e",
    "tiny+lora/embed":
        "ec0816a6db52931425fe512608929a2ea08752d775ee2ebbdbaa19dc39f5f9fe",
    "tiny-vl/unified":
        "ce0f91f06f1d716e0e09bd52ec5b1e105a265740eb22a10814a4b6c250361648",
    "tiny+sp2/unified":
        "5a1266b9a71c5d47002357efaceeb86bd3d0b6231845abaec9b5b74ecf216d93",
    "tiny+sp2/unified_ring":
        "197efbc7e3898cdef077e5e8a742829eb93ba3951c2bb851c82e190ebacc8235",
    "tiny+sp2/decode":
        "6477f848a077b22d92af852454f576f08fcdd7ce24ff764c8a6d4fe4cbce2563",
    # new in ISSUE 47 (a stack of single sublayers: Mamba-2, attention and
    # non-gated expert layers), taken on that PR's tree; every row above is
    # as it was
    "tiny-nemotron-h/unified":
        "4ba0dc6c0da23074e2f942129dea71307c33af06922104fc2409a21b95f4278f",
    "tiny-nemotron-h/decode":
        "f6413cfafcf58280b1c9a140604b66b6a312594cf59f42fe86457a260d4dbe30",
    "tiny-nemotron-h/decode_masked":
        "b1dca1964bf690f96ecaeecd76e7ae2cf04b502320b1ff52c66444a7d2e70b40",
    "tiny-nemotron-h+pallas/unified":
        "9086d86eaa36ccd07f66dca287128194201b659f7a89866f87bd3582c9008def",
    "tiny-nemotron-h+pallas/decode":
        "ec22882aee6cd5dc18204cd2416d5966a212bff732d1a55ae71e211bc8ebe03a",
    # moved by ISSUE 49, taken on that PR's tree: the latent kernel's call
    # takes the groups of its one-query rows (three more scalar-prefetched
    # arrays, derived once a program before the layers) and walks those rows
    # a group at a time; every row above is as it was on 4c12ff7
    "tiny-glm+pallas/unified":
        "c98ad0ca4e1db12c72a178e9310e625ae62454c634767da6b5416f740bc74e41",
    "tiny-glm+pallas/decode":
        "3f82739da5e0b4ba4a5e1ec08c101c1fe912caef5566fadd228cf2bb8d49d1aa",
    # new in ISSUE 50, taken on that PR's tree: four query heads a KV head,
    # the layout whose one-query rows take the repo's rows kernel (the plan
    # derived once before the layers, the kernel in interpret mode in the
    # head call's and the fused call's place). No row above moved: the
    # grouping rule's move to `ops/row_groups.py` leaves tiny-glm+pallas its
    # text, and no other variant's layout takes the kernel
    "tiny-gqa4+pallas/unified":
        "f2b19aad33f9eff09243426bd6f58840e92f8577f2aa4c8c27c2043c085de669",
    "tiny-gqa4+pallas/decode":
        "ca67caa859ca21be992a361c676bcd723933e5a3a74fa3b19a9df2f86cc5f8a5",
    # new in ISSUE 51 ('kda' mixers beside a latent-attention layer, each
    # over a group-limited mixture, behind a leading dense layer), taken on
    # that PR's tree; every row above is as it was on 3f80dd3
    "tiny-ling/unified":
        "629b25e4075991c980911d7b8420664d3ab8a2b0a86fca8bdaea735cc52eace1",
    "tiny-ling/decode":
        "b96a4bfda76c295a2bd31dd4bbde2127a96da251aef3d9aa8a5857597e177f0f",
    "tiny-ling/decode_masked":
        "1cfd1e470d51de414c6d29ad5f9360abb107cdc7f92c9b497662efd9a337572a",
    # taken anew in ISSUE 53 on that PR's tree (the kda kernel's block of one
    # token left the matrix unit, and an interpreted kernel's body is part of
    # the program's text); every other row is as it was on bbe8e08
    "tiny-ling+pallas/unified":
        "36bd4ecf519f630a973c0af7ee6cac821539b217d57c3f6fa93a49d9651c97d7",
    "tiny-ling+pallas/decode":
        "b9d73867ae897c7ebabb79eb0a446a3b4fec42457f33e92433b090d456ee51d9",
    # moved by ISSUE 56, taken on that PR's tree: the lightning layers' four
    # input leaves are read a head apart from a view of the whole stack, the
    # sparse layers' ``wo`` flat from a view of its stack, their gate on the
    # flat rows (bit for bit the parent's results:
    # tests/test_minicpm_sala.py); every other row is as it was on 10d8257
    "tiny-sala/unified":
        "33ab7bd7d08896a9a9c95d8bc16a5ce93e264090d71ad6f51b730fd16992a701",
    "tiny-sala/decode":
        "35f5f6797735ec81399627855903226ad44072fbc3bbada61cc48655f3613fc1",
    "tiny-sala/decode_masked":
        "753a31dd34be4d7929cac23c239efb68d9b29f7d2f12ecf152bf584fe18f9ccd",
    "tiny-sala+pallas/unified":
        "88b34ab657a817dbecfbfc54a4948297f1213cff86da328654afa4c30beabb69",
    "tiny-sala+pallas/decode":
        "e363d483e42ca233307710fd32bce384e157a026041f1b0ed62cd2c80d5c2eec",
}


def _kernel_stand_in(q, kv, kv_lens, page_tables, cu_q_lens, num_seqs, **kw):
    """The upstream ragged kernel's place on the CPU: every operand and every
    static argument lands in the result, so each shows in the text."""
    static = sum((i + 1) * float(kw[k] or 0) for i, k in enumerate(sorted(kw)))
    dyn = (kv_lens.sum() + page_tables.sum() + cu_q_lens.sum()
           + num_seqs.sum()).astype(jnp.float32)
    return q * (dyn + static + kv.astype(jnp.float32).sum()).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _engine(variant: str) -> LLMEngine:
    preset, fields, _ = VARIANTS[variant]
    fields = dict(BASE, **fields)
    model = dataclasses.replace(get_model_config(preset),
                                **fields.pop("model", {}))
    return LLMEngine(model, EngineConfig(**fields), seed=3)


def _shapes(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def program_call(variant: str, program: str):
    """(the jitted step program, its arguments as shapes, its keywords)."""
    eng = _engine(variant)
    B, NT = eng.cfg.max_batch_size, eng.cfg.batched_tokens
    maxp, V = eng.cfg.max_pages_per_seq, eng.model_cfg.vocab_size

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    params = _shapes(eng._run_params())
    sampling = _shapes(eng._greedy_state)
    tables = (jax.ShapeDtypeStruct((2, 8, V), jnp.float32), i32(2, 8, V))
    if program in ("unified", "unified_ring"):
        kw = {"state_slots": i32(B)} if eng.state else {}
        mm = ()
        if eng.model_cfg.mm_tokens:
            mm = (jax.ShapeDtypeStruct((NT, eng.model_cfg.hidden_size),
                                       eng.model_cfg.jax_dtype),
                  jax.ShapeDtypeStruct((NT,), jnp.bool_))
        fn = eng._unified_fn if program == "unified" else eng._unified_ring_fn
        return fn, (
            params, _shapes(eng._pools()), i32(NT), i32(NT), i32(NT),
            i32(B, maxp), i32(B), i32(B + 1), i32(1), i32(NT),
            _shapes(eng._zero_sampled), *sampling, *mm), kw
    if program in ("decode", "decode_masked"):
        args = (params, _shapes(eng._pools()), i32(B), i32(B), i32(B, maxp),
                i32(B), *sampling, i32(B), i32(B))
        if program == "decode":
            return eng._decode_multi_fn, args, {}
        return eng._decode_multi_masked_fn, (*args, i32(B), i32(B),
                                             *tables), {}
    if program in ("verify", "verify_masked"):
        n = eng._verify_nt()
        args = (params, _shapes(eng.cache), i32(n), i32(n), i32(n),
                i32(B, maxp), i32(B), i32(B + 1), i32(1), i32(n))
        if program == "verify":
            return eng._verify_fn, args, {}
        return eng._verify_masked_fn, (*args, i32(B), i32(B), *tables), {}
    n = eng.cfg.prefill_chunk
    return eng._embed_fn, (params, _shapes(eng.cache), i32(n), i32(n),
                           i32(1, maxp), i32(1), i32(2), i32(n)), {}


def lowered_text(variant: str, program: str) -> str:
    fn, args, kw = program_call(variant, program)
    return fn.lower(*args, **kw).as_text()


def _sha(variant: str, program: str) -> str:
    return hashlib.sha256(lowered_text(variant, program).encode()).hexdigest()


CASES = [(v, p) for v, (_, _, programs) in VARIANTS.items() for p in programs]


@pytest.mark.parametrize("variant,program", CASES,
                         ids=[f"{v}-{p}" for v, p in CASES])
def test_step_program_lowers_to_the_stablehlo_it_had(monkeypatch, variant,
                                                     program):
    monkeypatch.setattr(pa, "_kernel", lambda: _kernel_stand_in)
    assert _sha(variant, program) == PARENT_STABLEHLO[f"{variant}/{program}"]


if __name__ == "__main__":
    pa._kernel = lambda: _kernel_stand_in
    for v, p in CASES:
        print(f'    "{v}/{p}":\n        "{_sha(v, p)}",', flush=True)
