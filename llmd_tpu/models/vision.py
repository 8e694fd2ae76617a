"""Vision tower for multimodal serving: pixels → mm embedding tokens.

The encode (E) stage of the reference's E/PD / E/P/D topologies
(`guides/multimodal-serving/e-disaggregation/README.md`): media is converted to
a FIXED number of embedding rows (``cfg.mm_tokens``) that prefill injects at
placeholder positions alongside text tokens. TPU-first choices:

- one jitted program per image: patchify (a reshaped matmul — MXU), add learned
  position embeddings, run a small pre-norm transformer, mean-pool patches into
  ``mm_tokens`` rows, project to the language ``hidden_size``;
- all shapes static: images are bilinearly resized to ``vision_image_size``²
  before entering jit, so any input resolution compiles exactly once;
- encode workers batch independent media items along a leading axis (the
  "parallelized across entries" property of the reference's encode workers —
  one program, N items).

Media bytes → pixels: raw RGB/grayscale arrays are accepted directly; arbitrary
byte payloads (we ship no image codec) map deterministically onto pseudo-pixels
via a seeded hash so identity, caching, and parity tests work end to end on any
payload. Real deployments plug a decoder in front; the serving contract (bytes →
[mm_tokens, hidden] rows keyed by content hash) is unchanged.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from llmd_tpu.models.config import ModelConfig


def init_vision_params(cfg: ModelConfig, key: jax.Array) -> dict[str, jax.Array]:
    D = cfg.vision_hidden
    L = cfg.vision_layers
    P = cfg.vision_patch
    n_patches = (cfg.vision_image_size // P) ** 2
    patch_dim = P * P * 3
    F = 4 * D
    dt = cfg.jax_dtype
    ks = iter(jax.random.split(key, 12))

    def norm(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dt)

    return {
        "v_patch": norm((patch_dim, D), patch_dim ** -0.5),
        "v_pos": norm((n_patches, D), 0.02),
        "v_norm1": jnp.ones((L, D), dt),
        "v_qkv": norm((L, D, 3 * D), D ** -0.5),
        "v_out": norm((L, D, D), D ** -0.5),
        "v_norm2": jnp.ones((L, D), dt),
        "v_mlp_in": norm((L, D, F), D ** -0.5),
        "v_mlp_out": norm((L, F, D), F ** -0.5),
        "v_final_norm": jnp.ones((D,), dt),
        "v_proj": norm((D, cfg.hidden_size), D ** -0.5),
    }


def _rms(x, w, eps=1e-6):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps)).astype(x.dtype) * w


def encode_images(cfg: ModelConfig, params: dict[str, jax.Array],
                  pixels: jax.Array) -> jax.Array:
    """[N, S, S, 3] float pixels in [0, 1] → [N, mm_tokens, hidden_size].

    Jittable; N is the encode-worker batch of independent media items.
    """
    N = pixels.shape[0]
    P = cfg.vision_patch
    S = cfg.vision_image_size
    D = cfg.vision_hidden
    H = cfg.vision_heads
    hd = D // H
    n_patches = (S // P) ** 2
    # patchify: [N, S/P, P, S/P, P, 3] → [N, n_patches, P*P*3]
    x = pixels.astype(cfg.jax_dtype).reshape(N, S // P, P, S // P, P, 3)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(N, n_patches, P * P * 3)
    x = x @ params["v_patch"] + params["v_pos"]

    def layer(x, lp):
        h = _rms(x, lp["v_norm1"])
        qkv = h @ lp["v_qkv"]  # [N, T, 3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(N, n_patches, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(N, n_patches, H, hd).transpose(0, 2, 1, 3)
        v = v.reshape(N, n_patches, H, hd).transpose(0, 2, 1, 3)
        s = jnp.einsum("nhqd,nhkd->nhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * hd ** -0.5
        a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("nhqk,nhkd->nhqd", a, v).transpose(0, 2, 1, 3).reshape(N, n_patches, D)
        x = x + o @ lp["v_out"]
        h = _rms(x, lp["v_norm2"])
        return x + jax.nn.gelu(h @ lp["v_mlp_in"]) @ lp["v_mlp_out"], None

    stacked = {k: params[k] for k in
               ("v_norm1", "v_qkv", "v_out", "v_norm2", "v_mlp_in", "v_mlp_out")}
    x, _ = jax.lax.scan(lambda c, lp: layer(c, lp), x, stacked)
    x = _rms(x, params["v_final_norm"])
    # pool patch groups into the fixed mm_tokens rows, then project to the LM width
    x = x.reshape(N, cfg.mm_tokens, n_patches // cfg.mm_tokens, D).mean(axis=2)
    return (x @ params["v_proj"]).astype(cfg.jax_dtype)  # [N, mm_tokens, hidden]


# ---------------------------------------------------------------------------
# Media bytes → pixels + identity
# ---------------------------------------------------------------------------


def mm_content_hash(data: bytes) -> bytes:
    """Stable media identity: folded into block keys + used as the cache key
    between encode workers and P/D engines."""
    return hashlib.sha256(data).digest()[:16]


def bytes_to_pixels(cfg: ModelConfig, data: bytes) -> np.ndarray:
    """Deterministic bytes → [S, S, 3] float32 pixels in [0, 1].

    A real decoder (JPEG/PNG) slots in here; absent one in this image, the
    payload seeds a generator so distinct media map to distinct pixel tensors
    (and identical media always encode identically — required for caching)."""
    S = cfg.vision_image_size
    seed = int.from_bytes(hashlib.sha256(data).digest()[:8], "little", signed=False)
    rng = np.random.default_rng(seed)
    return rng.random((S, S, 3), dtype=np.float32)
