"""The engine child of one benchmark run: the process that owns the chip.

    python perfbench/engine_child.py --config perfbench/configs/<name>.json \
        --seed N --port P --control-port C [--cpu]

The program's ``python -m llmd_tpu.engine.serve`` takes a registry name or a
checkpoint directory, not a file of sizes, so this is the benchmark's own thin
launcher around the same classes: ``init_jax`` (platform rule, compile cache),
a ``ModelConfig`` that the configuration's family module
(``reference/<conf["reference"]>.py``: ``model_config``, ``sizes``,
``weight_leaves``, ``readings``) makes from the file, so that no model key and
no leaf name lives here; an ``EngineConfig`` from the file's ``engine`` block;
``LLMEngine`` (which makes
the weights on the device from the seed with ``init_params`` and quantises
them as the file says), ``AsyncLLMEngine`` and ``EngineServer``. It adds a
second small HTTP server, the control port, for what only the process that
holds the chip can answer: the device as JAX reports it, its memory, the split
of this process's set-up, and the float32 reference check on the stack the
engine is serving.

Prints one JSON line ``{"ready": true, ...}`` when both ports answer, serves
until SIGTERM, and exits non-zero at once when the step loop dies.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import signal
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def served_dtype_ok(conf: dict, leaves, params: dict, cache=None) -> bool:
    """The stack the engine serves has the weight type the file states, and
    its KV pool the type the file states. ``leaves`` are the family's
    ``weight_leaves(conf)``: under ``weights.quantize == "int8"`` every one is
    there as ``<leaf>_q`` of int8 and none as a float leaf; otherwise every
    one is there with ``weights.dtype``."""
    if cache is not None and str(cache.dtype) != conf["engine"]["kv_cache_dtype"]:
        return False
    if conf["weights"]["quantize"] == "int8":
        return all(k not in params and k + "_q" in params
                   and str(params[k + "_q"].dtype) == "int8" for k in leaves)
    return all(k in params and str(params[k].dtype) == conf["weights"]["dtype"]
               for k in leaves)


def engine_config(conf: dict, **over):
    """The program's EngineConfig from a configuration file's ``engine``
    block and stated weight type; ``over`` replaces single fields."""
    from llmd_tpu.engine.config import EngineConfig

    e = conf["engine"]
    fields = dict(
        page_size=e["page_size"], num_pages=e["num_pages"],
        max_model_len=e["max_model_len"], max_batch_size=e["max_batch_size"],
        prefill_chunk=e["prefill_chunk"], decode_steps=e["decode_steps"],
        quantize_weights=conf["weights"]["quantize"])
    # a control's only (tests/manifest-*.json): fields the engine is given
    # behind the file's stated ones, which the check goes on reading
    return EngineConfig(**{**fields, **e.get("unstated", {}), **over})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--control-port", type=int, required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        conf = json.load(f)
    split = {"process_start_to_main": time.time() - T0}

    t = time.time()
    from llmd_tpu.jax_init import compile_cache_dir, init_jax

    dev = init_jax(args.cpu)  # exits non-zero when asked for a TPU it lacks
    import jax

    split["import_and_device_init"] = time.time() - t

    from aiohttp import web

    from idtok import IdTokenizer
    from llmd_tpu.engine.async_engine import AsyncLLMEngine
    from llmd_tpu.engine.engine import LLMEngine
    from llmd_tpu.engine.server import EngineServer

    family = importlib.import_module("reference." + conf["reference"])
    mcfg = family.model_config(conf)
    ecfg = engine_config(conf)
    tok = IdTokenizer(mcfg.vocab_size)

    t = time.time()
    # params=None: the engine draws the weights itself on the device from the
    # seed and drops the bf16 stack as it quantises, which a caller that
    # passed the stack in could not make it do
    engine = LLMEngine(mcfg, ecfg, seed=args.seed % (2 ** 31 - 1),
                       tokenizer=tok)
    jax.block_until_ready(engine.params)
    jax.block_until_ready(engine.cache)
    split["weights_and_cache"] = time.time() - t
    server = EngineServer(mcfg, ecfg, model_name=conf["name"],
                          host="127.0.0.1", port=args.port, tokenizer=tok,
                          engine=engine, async_engine=AsyncLLMEngine(engine))
    sizes, leaves = family.sizes(conf), family.weight_leaves(conf)
    chosen = {"attn_backend": engine.attn_backend,
              "moe_backend": engine.moe_backend,
              "moe_dispatch": engine.moe_dispatch}

    def device_info() -> dict:
        stats = [s for s in (d.memory_stats() for d in jax.devices()) if s]

        def most(key):  # on the fullest chip; None where the backend has none
            return max((s.get(key, 0) for s in stats), default=None)

        return {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices()),
                "memory_peak_bytes": most("peak_bytes_in_use"),
                "bytes_in_use": most("bytes_in_use"),
                "bytes_limit": most("bytes_limit")}

    async def h_device(_req):
        return web.json_response(device_info())

    async def h_setup(_req):
        return web.json_response(
            {"split": split, "compile_cache": compile_cache_dir(), **chosen,
             "served_dtype_ok": served_dtype_ok(conf, leaves, engine.params,
                                                engine.cache)})

    async def h_reference(req):
        body = await req.json()

        def run():
            t0 = time.time()
            out = family.readings(sizes, engine.params, body["prompts"],
                                  body["served"])
            return {**out, "seconds": time.time() - t0}

        return web.json_response(
            await asyncio.get_running_loop().run_in_executor(None, run))

    async def run() -> None:
        await server.start()
        app = web.Application(client_max_size=64 * 1024 * 1024)
        app.router.add_get("/device", h_device)
        app.router.add_get("/setup", h_setup)
        app.router.add_post("/reference", h_reference)
        runner = web.AppRunner(app)
        await runner.setup()
        await web.TCPSite(runner, "127.0.0.1", args.control_port).start()
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()
        loop.add_signal_handler(signal.SIGTERM, stop.set)
        loop.add_signal_handler(signal.SIGINT, stop.set)
        ae = server.async_engine
        ae.on_fatal = lambda exc: loop.call_soon_threadsafe(stop.set)
        split["to_ready"] = time.time() - T0
        print(json.dumps({"ready": True, "device": device_info(),
                          "split": split, **chosen}), flush=True)
        await stop.wait()
        fatal = ae.fatal
        try:
            await asyncio.wait_for(server.stop(), timeout=5.0)
        except Exception:  # noqa: BLE001: exiting regardless
            pass
        sys.stdout.flush()
        # a wedged executor thread must not keep the process alive
        os._exit(1 if fatal is not None else 0)

    asyncio.run(run())


if __name__ == "__main__":
    main()
