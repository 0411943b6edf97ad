"""Shared model building blocks: norms, activations, positional encodings.

All models are pure-functional: params are nested dicts of jnp arrays with a
leading ``[L, ...]`` layer axis for scanned stacks.  Compute dtype is bf16 with
f32 accumulation in norms/softmax; parameters are stored in the config dtype.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Activation / logical-sharding helpers
# ---------------------------------------------------------------------------

_LOGICAL_RULES: Optional[dict] = None  # set by repro.distributed.sharding


def set_logical_rules(rules):
    """Install activation logical-axis → mesh-axis rules (hillclimb lever)."""
    global _LOGICAL_RULES
    _LOGICAL_RULES = rules


def logical_constraint(x, *names):
    """Apply ``with_sharding_constraint`` using installed logical rules.

    No-op when no rules are installed (single-device tests), when no mesh
    is active (``jax.set_mesh``), or when the name has no mapping.  Inside a
    mesh every error of the constraint raises.  ``names`` has one entry per
    axis of ``x`` (None = leave).  Axes whose size is not divisible by the
    mesh-axis extent are left unconstrained (GSPMD would PAD them — e.g.
    batch=1 padded 16×).
    """
    if _LOGICAL_RULES is None or jax.sharding.get_abstract_mesh().empty:
        return x
    from jax.sharding import PartitionSpec as P
    sizes = _LOGICAL_RULES.get("__sizes__", {})

    def extent(axis):
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= sizes.get(a, 1)
            return n
        return sizes.get(axis, 1)

    spec = []
    for dim, n in zip(x.shape, names):
        axis = _LOGICAL_RULES.get(n) if n else None
        if axis is not None and sizes and dim % max(extent(axis), 1) != 0:
            axis = None
        spec.append(axis)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32)) + bias.astype(jnp.float32)).astype(dtype)


def norm_apply(kind: str, x, p):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def norm_init(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.zeros((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# Positional encodings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, Dh]; positions: [..., S] int32 (broadcastable)."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta))                  # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., S, Dh/2]
    cos = jnp.cos(angles)[..., None, :]                         # [..., S, 1, Dh/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def alibi_slopes(num_heads: int):
    """ALiBi per-head slopes (BLOOM)."""
    def pow2slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]
    if math.log2(num_heads).is_integer():
        return np.asarray(pow2slopes(num_heads), np.float32)
    n = 2 ** math.floor(math.log2(num_heads))
    base = pow2slopes(n)
    extra = pow2slopes(2 * n)[0::2][: num_heads - n]
    return np.asarray(base + extra, np.float32)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def embed_init(key, shape, dtype):
    return (0.02 * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
