"""Foundational layers: initializers, RMSNorm, RoPE, SwiGLU, MLPs.

No flax — parameters are plain pytrees (nested dicts of jax.Arrays), and
every layer is a pure function ``f(params, x, ...)``.  Initializers take an
explicit PRNG key and return the param subtree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import YarnScaling, yarn_mscale

__all__ = [
    "dense_init", "dense", "rms_norm_init", "rms_norm", "mlp_init", "mlp",
    "rope_frequencies", "apply_rope", "swiglu_init", "swiglu",
]


def _he(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) * (2.0 / fan_in) ** 0.5).astype(dtype)


def dense_init(key, d_in, d_out, dtype=jnp.bfloat16, bias=False):
    p = {"w": _he(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def rms_norm_init(d, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def mlp_init(key, dims, dtype=jnp.bfloat16, bias=True):
    """dims = (d_in, h1, ..., d_out); ReLU between layers."""
    keys = jax.random.split(key, len(dims) - 1)
    return {
        f"l{i}": dense_init(keys[i], dims[i], dims[i + 1], dtype, bias=bias)
        for i in range(len(dims) - 1)
    }


def mlp(p, x, act=jax.nn.relu):
    n = len(p)
    for i in range(n):
        x = dense(p[f"l{i}"], x)
        if i < n - 1:
            x = act(x)
    return x


def swiglu_init(key, d_model, d_ff, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": _he(k1, (d_model, d_ff), dtype),
        "w3": _he(k2, (d_model, d_ff), dtype),
        "w2": _he(k3, (d_ff, d_model), dtype),
    }


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10_000.0,
                     scaling: YarnScaling | None = None) -> jax.Array:
    """Inverse frequencies of the ``head_dim / 2`` rotary pairs; with
    ``scaling``, YaRN's blend as DeepSeek-V2 computes it
    (``DeepseekV2YarnRotaryEmbedding``): the fastest pairs keep their
    frequency, the slowest are divided by ``factor``, and a linear ramp
    between the correction dims ``beta_fast`` and ``beta_slow`` mixes the
    two."""
    base = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if scaling is None:
        return 1.0 / base

    def correction_dim(rotations):
        return (head_dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    return ((1.0 / (scaling.factor * base)) * (1.0 - extrapolate)
            + (1.0 / base) * extrapolate)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
               scaling: YarnScaling | None = None):
    """x: (..., S, H, Dh) or (..., S, Dh); positions: broadcastable to (..., S).

    Rotates the interleaved pairs ``(2i, 2i+1)`` by frequency ``i``.  With
    YaRN ``scaling``, cos and sin are also scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, as
    DeepSeek-V2 does (1 where the two are equal)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta, scaling)  # (Dh/2,)
    # The barrier keeps XLA from constant-folding cos/sin when the positions
    # are static: folded values come from the compiler's host math, which
    # differs from the TPU's in the last place, so a decode at a static
    # position (the batch engine) would not match the same decode at a
    # traced one (the continuous engine).
    positions = jax.lax.optimization_barrier(positions)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    if x.ndim == angles.ndim + 1:  # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
