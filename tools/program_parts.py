#!/usr/bin/env python3
"""Which part of the model each instruction of a configuration's compiled
step programs belongs to: the map the engine publishes as
``llmd_tpu:program_part_ops`` (``llmd_tpu/obs/program_parts.py``, the one
implementation), for an operator who has a device trace's ``fusion.<n>`` rows
and wants to know what they compute.

    chiprun -- python3 tools/program_parts.py \
        --config perfbench/configs/jamba2-3b.json --write-text chiprun_out/hlo
    python3 tools/program_parts.py --cpu --model tiny-jamba        # here
    python3 tools/program_parts.py --text chiprun_out/hlo/jamba2-3b.jit__unified.0.hlo.txt --ops
    python3 tools/program_parts.py --text chiprun_out/pr56/hlo/*.hlo.txt --weight-copies

``--config`` builds the engine the benchmark's engine child builds from that
file (on the chip: the real widths, weights drawn on the device), ``--model``
one of the registry's presets. The engine serves three short prompts, which
compiles the unified step and the fused decode call; ``LLMEngine.read_compiled_programs`` then reads both executables' text
as a served engine's loop does. One JSON line a program: its module name (a
trace's ``XLA Modules`` line), whether it is stale, its instructions by part;
``--ops`` adds every instruction's name. ``--write-text DIR`` keeps the
compiled text (``<name>.<module>.<n>.hlo.txt``), which ``--text`` reads back
without an engine. Instruction names are the compiler's, so a map belongs to
one build of one configuration: read the map of the build that was traced
(its ``/metrics``), and use this tool to look inside a program
(``observability/device-plane.md``, "Device time by part of the model").

``--weight-copies`` (with ``--text``, or behind ``--config`` / ``--model``)
prints in the maps' place one JSON line for every operation that hands on one
layer of a stacked weight leaf and computes nothing (``weight_copies`` of
``llmd_tpu/obs/program_parts.py``: a ``dynamic-slice``, ``slice`` or ``copy``
outside any product's fusion, with the products that consume it), then one
line a program with their count and bytes. A product that reads its layer's
matrix where it lies in the stack leaves no row; a row is a matrix written
out at the memory's rate before the product starts (PR 56: fourteen such
copies were 13% of ``minicpmsala-longdoc``'s device time).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def engine_of(args):
    """(name, LLMEngine) of ``--config`` or ``--model``."""
    from llmd_tpu.jax_init import init_jax

    init_jax(args.cpu)
    from llmd_tpu.engine.engine import LLMEngine

    if args.config:
        sys.path.insert(0, os.path.join(ROOT, "perfbench"))
        from engine_child import engine_config

        with open(args.config) as f:
            conf = json.load(f)
        family = importlib.import_module("reference." + conf["reference"])
        name = os.path.splitext(os.path.basename(args.config))[0]
        return name, LLMEngine(family.model_config(conf), engine_config(conf),
                               seed=args.seed)
    from llmd_tpu.engine.config import EngineConfig
    from llmd_tpu.models import get_model_config

    return args.model, LLMEngine(
        get_model_config(args.model),
        EngineConfig(page_size=8, num_pages=256, max_model_len=256,
                     max_batch_size=4, prefill_chunk=32, decode_steps=4),
        seed=args.seed)


def main() -> int:
    ap = argparse.ArgumentParser()
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--config", help="a perfbench/configs/*.json file")
    src.add_argument("--model", help="a preset of llmd_tpu.models")
    src.add_argument("--text", nargs="+", help="compiled text kept earlier")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ops", action="store_true",
                    help="every instruction's name, by part")
    ap.add_argument("--write-text", metavar="DIR")
    ap.add_argument("--weight-copies", action="store_true",
                    help="the copies of one layer of a weight leaf, in the "
                         "maps' place")
    args = ap.parse_args()

    from llmd_tpu.obs.program_parts import ProgramParts, weight_copies

    texts: list = []  # (where it is from, compiled text)
    if args.text:
        parts = ProgramParts()
        for path in args.text:
            with open(path) as f:
                text = f.read()
            texts.append((path, text))
            parts.add(text)
    else:
        from llmd_tpu.core.request import SamplingParams

        name, eng = engine_of(args)
        chunk, vocab = eng.cfg.prefill_chunk, eng.model_cfg.vocab_size
        prompts = [[(7 * i + j) % (vocab - 8) + 4 for i in range(n)]
                   for j, n in enumerate((chunk // 2 + 8, 24, 9))]
        eng.generate(prompts, SamplingParams(
            max_tokens=2 * eng.cfg.decode_steps + 2, temperature=0.0))
        eng.read_compiled_programs(texts)
        parts = eng.programs.parts
        if args.write_text:
            os.makedirs(args.write_text, exist_ok=True)
            seen: dict = {}
            for module, text in texts:
                n = seen[module] = seen.get(module, -1) + 1
                with open(os.path.join(args.write_text,
                                       f"{name}.{module}.{n}.hlo.txt"),
                          "w") as f:
                    f.write(text)
    if args.weight_copies:
        for source, text in texts:
            rows = weight_copies(text)
            for row in rows:
                print(json.dumps({"program": source, **row}), flush=True)
            print(json.dumps({
                "program": source, "weight_copies": len(rows),
                "bytes": sum(r["bytes"] for r in rows),
                "bytes_not_async": sum(r["bytes"] for r in rows
                                       if not r["async"])}), flush=True)
        return 0
    by: dict = {}
    for labels, count in parts.series():
        line = by.setdefault(labels["program"], {
            "program": labels["program"], "stale": labels["stale"] == "1",
            "signatures": parts.signatures[labels["program"]],
            "instructions": {}, **({"ops": {}} if args.ops else {})})
        line["instructions"][labels["part"]] = count
        if args.ops:
            line["ops"][labels["part"]] = labels["ops"].split()
    for line in by.values():
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
