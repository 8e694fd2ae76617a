"""Materialise a serving-scale HF-format checkpoint for serve runs.

The image is zero-egress, so published weights cannot be downloaded; this writes a
genuine ``save_pretrained`` checkpoint (config.json + sharded safetensors +
trained BPE tokenizer) at a registry shape so the full HF-load path — the one a
real checkpoint takes — is what `-m llmd_tpu.engine.serve` exercises.
The loader itself is validated for logits parity against the HF reference in
tests/test_hf_loader.py; with network access, point --model at any downloaded
Llama/Qwen checkpoint instead.

Usage: python tools/make_checkpoint.py [--shape llama-1b] [--out checkpoints/llama-1b-hf]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="llama-1b",
                    help="registry shape to materialise (llmd_tpu.models.MODEL_REGISTRY)")
    ap.add_argument("--out", default=None, help="output dir (default checkpoints/<shape>-hf)")
    ap.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    out = args.out or os.path.join("checkpoints", f"{args.shape}-hf")
    if os.path.isfile(os.path.join(out, "config.json")):
        print(f"exists: {out}")
        return

    from llmd_tpu.models import get_model_config
    from llmd_tpu.testing.checkpoints import make_hf_checkpoint

    cfg = get_model_config(args.shape)
    if cfg.is_moe:
        raise SystemExit("HF export currently covers the dense families (llama/qwen)")
    make_hf_checkpoint(
        out, "llama",
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_layers=cfg.num_layers,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, tie_embeddings=cfg.tie_embeddings,
        rope_theta=cfg.rope_theta, max_position=2048,
        max_shard_size="500MB", seed=args.seed, torch_dtype=args.dtype,
    )
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
