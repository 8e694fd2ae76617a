"""CLI: python -m llmd_tpu.engine.serve --model tiny --port 8000 [--cpu] ...

The vLLM-serve analogue for the TPU engine (flag names mirror the reference's
modelserver args where they exist, e.g. --block-size / --kv-events-port).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny",
                    help="registry name (llmd_tpu.models.MODEL_REGISTRY) or a local "
                         "HF checkpoint dir (config.json + safetensors)")
    ap.add_argument("--served-model-name", default=None)
    # env-default ports: the container image / manifests configure pods via
    # LLMD_TPU_* (deploy/ENV_VARS.md contract); flags still win when passed
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int,
                    default=int(os.environ.get("LLMD_TPU_PORT", "8000")))
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=512)
    ap.add_argument("--max-model-len", type=int, default=2048)
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=128)
    ap.add_argument("--decode-steps", type=int, default=4)
    _env_kve = os.environ.get("LLMD_TPU_KV_EVENTS_PORT")
    ap.add_argument("--kv-events-port", type=int,
                    default=int(_env_kve) if _env_kve else None,
                    help="bind ZMQ KV-event PUB here (pod-discovery mode)")
    _env_kvt = os.environ.get("LLMD_TPU_KV_TRANSFER_PORT")
    ap.add_argument("--kv-transfer-port", type=int,
                    default=int(_env_kvt) if _env_kvt else None,
                    help="bind the P/D KV-transfer side channel here (0 = random; "
                         "TPU_KV_TRANSFER_PORT analogue, reference default 9100)")
    ap.add_argument("--advertise-host", default=None,
                    help="routable host for kv_transfer_params (defaults to --host "
                         "unless that is a bind-any address)")
    ap.add_argument("--tokenizer", default=None, help="local HF tokenizer dir")
    ap.add_argument("--role", default="both", choices=["both", "prefill", "decode"])
    ap.add_argument("--quantize", default=None, choices=["int8"],
                    help="weight-only quantization: halves decode's HBM "
                         "weight traffic (models/quant.py)")
    ap.add_argument("--kv-cache-dtype", default=None, choices=["fp8"],
                    help="fp8 KV pool: halves decode's per-step KV read "
                         "stream (the vLLM --kv-cache-dtype role)")
    ap.add_argument("--kv-layout", default="auto",
                    choices=["auto", "packed", "padded"],
                    help="KV pool lane layout (ops/packed_kv): auto packs "
                         "head_dim-64 models' KV pairs per 128-lane row")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "pallas", "reference"],
                    help="attention kernel selection (EngineConfig.attn_impl);"
                         " MLA decode takes the latent Pallas kernel on TPU "
                         "under auto, anywhere under pallas")
    ap.add_argument("--moe-dispatch",
                    default=os.environ.get("LLMD_MOE_DISPATCH", "") or "auto",
                    choices=["auto", "sorted", "einsum"],
                    help="MoE token dispatch (EngineConfig.moe_dispatch): "
                         "sorted = token-sorted drop-free path "
                         "(ops/moe_dispatch, all_to_all over ep), einsum = "
                         "legacy capacity dispatch (kill switch; drops past "
                         "capacity); auto = sorted")
    ap.add_argument("--cpu-offload-pages", type=int, default=0,
                    help="KV blocks of CPU offload tier (TPU_OFFLOAD_NUM_CPU_CHUNKS)")
    ap.add_argument("--offload-fs-path", default=None,
                    help="FS tier below the CPU tier (llmd_fs_backend path)")
    ap.add_argument("--spec-mode", default=os.environ.get("LLMD_SPEC_MODE", "off"),
                    choices=["off", "ngram"],
                    help="speculative decoding: 'ngram' = prompt-lookup drafts "
                         "verified through the mixed-batch step (engine/spec.py)")
    ap.add_argument("--spec-tokens", type=int,
                    default=int(os.environ.get("LLMD_SPEC_TOKENS", "4")),
                    help="max draft tokens proposed per sequence per verify step")
    ap.add_argument("--spec-ngram-max", type=int,
                    default=int(os.environ.get("LLMD_SPEC_NGRAM_MAX", "3")),
                    help="longest suffix n-gram the drafter matches")
    ap.add_argument("--spec-ngram-min", type=int,
                    default=int(os.environ.get("LLMD_SPEC_NGRAM_MIN", "1")),
                    help="shortest suffix n-gram the drafter falls back to")
    ap.add_argument("--structured-mode",
                    default=os.environ.get("LLMD_STRUCTURED_MODE", "auto"),
                    choices=["auto", "off"],
                    help="structured outputs (llmd_tpu/structured): 'auto' = "
                         "compile grammars for requests that ask, 'off' = "
                         "reject structured requests as 400")
    ap.add_argument("--structured-table-elems", type=int,
                    default=int(os.environ.get("LLMD_STRUCTURED_TABLE_ELEMS",
                                               str(1 << 23))),
                    help="max staged mask-table size (G_pad*S_pad*V elements) "
                         "before constrained rows degrade to unified steps")
    ap.add_argument("--spec-structured-crosscheck",
                    default=os.environ.get("LLMD_SPEC_STRUCTURED_CROSSCHECK",
                                           "off"),
                    choices=["on", "off"],
                    help="debug: re-derive FSM state on host after every "
                         "masked verify step and compare with the device "
                         "state (mismatches adopt the host value)")
    ap.add_argument("--enable-lora", action="store_true",
                    help="enable dynamic LoRA adapter serving")
    ap.add_argument("--max-loras", type=int, default=8)
    ap.add_argument("--max-lora-rank", type=int, default=8)
    ap.add_argument("--cpu", action="store_true",
                    help="serve on the CPU platform (dev/tests); without it "
                         "the server requires a TPU and exits non-zero "
                         "when JAX finds none")
    ap.add_argument("--predictor-train-url", default=None,
                    help="latency-predictor training server base URL; completed "
                         "requests' TTFT/TPOT rows stream to its POST /samples")
    ap.add_argument("--data-parallel-size", type=int, default=1, dest="dp",
                    help="wide-EP DP rank engines sharing one SPMD program; each "
                         "rank serves on port+rank (reference --data-parallel-size)")
    ap.add_argument("--expert-parallel-size", type=int, default=1, dest="ep")
    ap.add_argument("--tensor-parallel-size", type=int, default=1, dest="tp")
    ap.add_argument("--sequence-parallel-size", type=int, default=1, dest="sp")
    args = ap.parse_args()

    from llmd_tpu.jax_init import compile_cache_dir, init_jax

    dev = init_jax(args.cpu)  # exits non-zero when asked for a TPU it lacks
    import jax

    print(f"llmd-tpu engine: jax {jax.__version__} on {dev.platform} "
          f"({dev.device_kind} x{len(jax.devices())}), compile cache "
          f"{compile_cache_dir()}", flush=True)

    from llmd_tpu.engine.config import EngineConfig
    from llmd_tpu.engine.server import EngineServer
    from llmd_tpu.engine.tokenizer import load_tokenizer
    from llmd_tpu.models import resolve_model

    from llmd_tpu.parallel.mesh import MeshConfig

    model_cfg, params = resolve_model(args.model)
    engine_cfg = EngineConfig(
        page_size=args.block_size, num_pages=args.num_pages,
        max_model_len=args.max_model_len, max_batch_size=args.max_batch_size,
        prefill_chunk=args.prefill_chunk, decode_steps=args.decode_steps,
        role=args.role, cpu_offload_pages=args.cpu_offload_pages,
        offload_fs_path=args.offload_fs_path,
        mesh=MeshConfig(dp=args.dp, sp=args.sp, ep=args.ep, tp=args.tp),
        dp_ranks=args.dp,
        quantize_weights=args.quantize,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_layout=args.kv_layout,
        attn_impl=args.attn_impl,
        moe_dispatch=args.moe_dispatch,
        spec_mode=args.spec_mode, spec_tokens=args.spec_tokens,
        spec_ngram_max=args.spec_ngram_max, spec_ngram_min=args.spec_ngram_min,
        structured_mode=args.structured_mode,
        structured_table_max_elems=args.structured_table_elems,
        spec_structured_crosscheck=args.spec_structured_crosscheck == "on",
    )
    if args.enable_lora:
        from llmd_tpu.models.lora import LoRAConfig

        engine_cfg.lora = LoRAConfig(max_adapters=args.max_loras,
                                     rank=args.max_lora_rank)
    # an HF checkpoint dir carries its own tokenizer files
    tok_path = args.tokenizer or (args.model if params is not None else None)
    tokenizer = load_tokenizer(tok_path)
    if params is not None and type(tokenizer).__name__ != "HFTokenizer":
        # real weights + byte fallback = garbage completions that look healthy
        raise SystemExit(
            f"could not load an HF tokenizer from {tok_path!r} for real-weight "
            "serving; pass --tokenizer <dir> with tokenizer.json present"
        )
    if args.dp > 1:
        from llmd_tpu.engine.dp_group import WideEPEngineGroup

        group = WideEPEngineGroup(
            model_cfg, engine_cfg,
            model_name=args.served_model_name or f"llmd-tpu/{model_cfg.name}",
            host=args.host, port_base=args.port, tokenizer=tokenizer,
            params=params,
        )

        async def run_group() -> None:
            await group.start()
            print(f"llmd-tpu wide-EP group serving "
                  f"{args.dp} rank engines on {group.endpoints()} "
                  f"(mesh dp={args.dp} sp={args.sp} ep={args.ep} tp={args.tp})",
                  flush=True)
            await _serve_until_fatal(group.async_engine, group.stop)

        asyncio.run(run_group())
        return
    server = EngineServer(
        model_cfg, engine_cfg,
        model_name=args.served_model_name or f"llmd-tpu/{model_cfg.name}",
        host=args.host, port=args.port, kv_events_port=args.kv_events_port,
        kv_transfer_port=args.kv_transfer_port,
        tokenizer=tokenizer, params=params,
        predictor_train_url=args.predictor_train_url,
    )
    if args.advertise_host:
        server.advertise_host = args.advertise_host

    async def run() -> None:
        await server.start()
        prov = ""
        if args.quantize or args.kv_cache_dtype:
            prov = (f" [weights={args.quantize or 'ckpt-dtype'}, "
                    f"kv={args.kv_cache_dtype or 'ckpt-dtype'}]")
        eng = server.engine
        ssm = getattr(eng, "ssm_backend", None)
        print(f"llmd-tpu engine serving {server.model_name} on http://{server.address} "
              f"(kv-events port {server.kv_events_port}){prov} "
              f"[attn={eng.attn_backend}, moe={eng.moe_backend}, "
              f"moe_dispatch={eng.moe_dispatch}] "
              f"[attn_geometry {eng.attn_geometry}]"
              + (f" [moe_gemm {eng.moe_gemm_geometry}]"
                 if eng.model_cfg.is_moe else "")
              + (f" [ssm={ssm}, state={eng.model_cfg.mamba_state_dtype}, "
                 "prefix_reuse=off]" if ssm else ""), flush=True)
        await _serve_until_fatal(server.async_engine, server.stop)

    asyncio.run(run())


async def _serve_until_fatal(async_engine, stop) -> None:
    """Serve until the step loop dies, then take the process down non-zero.

    A step that raises (a Mosaic refusal on the first real step, a device
    fault) has already failed every open stream (AsyncLLMEngine._die); a
    process that stayed up behind it would answer /health and hang every
    later client. The exit is unconditional: a wedged executor thread must
    not keep a dead server alive."""
    loop = asyncio.get_running_loop()
    dead = asyncio.Event()
    async_engine.on_fatal = lambda exc: loop.call_soon_threadsafe(dead.set)
    if async_engine.fatal is not None:  # died before the hook was in place
        dead.set()
    await dead.wait()
    print(f"llmd-tpu engine: step loop died "
          f"({type(async_engine.fatal).__name__}: {async_engine.fatal}); "
          "exiting", file=sys.stderr, flush=True)
    try:
        # a moment for the failed streams' error responses to flush
        await asyncio.wait_for(stop(), timeout=5.0)
    except Exception:  # noqa: BLE001 — exiting regardless
        pass
    sys.stdout.flush()
    os._exit(1)


if __name__ == "__main__":
    main()
