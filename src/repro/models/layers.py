"""Shared building blocks: RMSNorm, RoPE (incl. an M-RoPE reduction),
gated MLPs, embeddings.  Pure functions over explicit param dicts.

Dtype discipline (paper Table 7): weights/activations bf16, reductions
(norm statistics, softmax, loss) in fp32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.notation import MlpKind, ModelSpec, YaRNSpec

Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=jnp.bfloat16, scale: Optional[float] = None) -> jnp.ndarray:
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(h: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((h,), dtype)}

def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-6,
            gemma_style: bool = False) -> jnp.ndarray:
    """Gemma parameterises the gain as (1 + scale); others as scale."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    g = p["scale"].astype(jnp.float32)
    g = 1.0 + g if gemma_style else g
    return (y * g).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's temperature factor ``0.1·mscale·ln(factor) + 1`` (1 where the
    context is not extended)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_dim(rotations: float, d: int, theta: float, length: int) -> float:
    """The rotary dim whose wavelength fits ``rotations`` times into the
    original ``length``."""
    return d * math.log(length / (rotations * 2 * math.pi)) \
        / (2 * math.log(theta))


def rope_freqs(d: int, theta: float,
               yarn: Optional[YaRNSpec] = None) -> jnp.ndarray:
    """Inverse frequencies of the d/2 rotary pairs.  Under YaRN the pairs
    that turn fewer than ``beta_slow`` times over the original length are
    interpolated (frequency / factor), those that turn more than
    ``beta_fast`` times are kept, with a linear ramp between."""
    base = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if yarn is None:
        return base
    n = yarn.original_max_position
    low = max(math.floor(_yarn_dim(yarn.beta_fast, d, theta, n)), 0)
    high = min(math.ceil(_yarn_dim(yarn.beta_slow, d, theta, n)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return base / yarn.factor * ramp + base * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0,
               yarn: Optional[YaRNSpec] = None) -> jnp.ndarray:
    """x: (..., seq, n_heads, d); positions: (..., seq).

    M-RoPE note (Qwen2-VL): multimodal rotary splits the head dim into
    temporal/height/width sections with separate position ids.  With the
    stubbed vision frontend all modalities collapse to the temporal stream,
    so M-RoPE reduces to 1-D RoPE over the interleaved token sequence —
    recorded in DESIGN.md as a frontend-stub consequence.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, yarn)                        # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., s, d/2)
    cos = jnp.cos(angles)[..., None, :]                        # broadcast heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key: jax.Array, spec: ModelSpec, d_ff: int,
             dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if spec.mlp in (MlpKind.SWIGLU, MlpKind.GEGLU):
        return {"gate": dense_init(k1, (spec.h, d_ff), dtype),
                "up": dense_init(k2, (spec.h, d_ff), dtype),
                "down": dense_init(k3, (d_ff, spec.h), dtype)}
    return {"fc1": dense_init(k1, (spec.h, d_ff), dtype),
            "fc2": dense_init(k2, (d_ff, spec.h), dtype)}

def mlp_apply(p: Params, spec: ModelSpec, x: jnp.ndarray) -> jnp.ndarray:
    if spec.mlp == MlpKind.SWIGLU:
        a = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
        return a @ p["down"]
    if spec.mlp == MlpKind.GEGLU:
        a = jax.nn.gelu(x @ p["gate"], approximate=True) * (x @ p["up"])
        return a @ p["down"]
    return jax.nn.gelu(x @ p["fc1"], approximate=True) @ p["fc2"]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_init(key: jax.Array, vocab: int, h: int, dtype=jnp.bfloat16) -> Params:
    # ~N(0, h^-1): keeps tied-embedding logits O(1) at init
    return {"w": dense_init(key, (vocab, h), dtype, scale=h ** -0.5)}

def embed_apply(p: Params, tokens: jnp.ndarray, scale_by_dim: bool = False,
                h: int = 0) -> jnp.ndarray:
    x = jnp.take(p["w"], tokens, axis=0)
    if scale_by_dim:  # gemma multiplies embeddings by sqrt(h)
        x = x * jnp.asarray(h ** 0.5, x.dtype)
    return x

def head_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Project to vocab logits in fp32 (loss numerics)."""
    return (x @ p["w"]).astype(jnp.float32)

def head_init(key: jax.Array, h: int, vocab: int, dtype=jnp.bfloat16) -> Params:
    return {"w": dense_init(key, (h, vocab), dtype)}
