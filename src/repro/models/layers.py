"""Core layer primitives: norms, rotary embeddings, MLPs, embeddings.

Pure functional: every layer is an ``init_*`` returning a params dict and a
``*_fwd`` consuming it. No flax; params are nested dicts of jnp arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, YarnScaling


def truncated_normal(key, shape, dtype, scale):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# activation sharding constraints (no-ops without a mesh context)
# ---------------------------------------------------------------------------
def mesh_axis_sizes() -> dict[str, int]:
    """Sizes of the ambient mesh's axes that sharding may still use: none
    without a mesh, and none that ``shard_map`` made manual (inside it
    each device already holds its own block)."""
    mesh = jax.sharding.get_abstract_mesh()
    return {n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if t != jax.sharding.AxisType.Manual}


def constrain(x: jax.Array, *spec) -> jax.Array:
    """``with_sharding_constraint`` that degrades to identity when no mesh
    is set (CPU tests) and silently drops axes that are absent from the
    ambient mesh or don't divide the corresponding dim. ``spec`` entries are
    axis names, tuples of names, or None — one per array dim (trailing dims
    may be omitted)."""
    sizes = mesh_axis_sizes()
    if not sizes:
        return x
    parts = []
    for i, s in enumerate(spec):
        names = s if isinstance(s, tuple) else ((s,) if s else ())
        names = tuple(n for n in names if n in sizes)
        total = 1
        for n in names:
            total *= sizes[n]
        if names and x.shape[i] % total == 0 and x.shape[i] >= total:
            parts.append(names if len(names) > 1 else names[0])
        else:
            parts.append(None)
    from jax.sharding import PartitionSpec as _P
    return jax.lax.with_sharding_constraint(x, _P(*parts))


def constrain_batch(x: jax.Array) -> jax.Array:
    """Activation-stream constraint: batch over ("pod","data")."""
    return constrain(x, ("pod", "data"))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_fwd(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_fwd(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings (partial factor + theta per config)
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float,
                          s: YarnScaling) -> tuple[int, int]:
    """The rotary pairs between which YaRN's ramp runs: the pair that
    turns ``beta_fast`` times over the original context (floor) and the
    one that turns ``beta_slow`` times (ceil), clamped to [0, dim - 1]."""
    def pair(rotations):
        return dim * math.log(s.original_max_position_embeddings / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(pair(s.beta_fast)), 0),
            min(math.ceil(pair(s.beta_slow)), dim - 1))


def rope_freqs(head_dim: int, theta: float, partial: float = 1.0,
               scaling: YarnScaling | None = None) -> jax.Array:
    rot_dim = int(head_dim * partial) // 2 * 2
    # (rot_dim // 2,)
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2,
                                      dtype=jnp.float32) / rot_dim))
    if scaling is None:
        return inv
    # YaRN: pairs below the correction range keep their frequency,
    # pairs above it are interpolated by 1/factor, a linear ramp between
    lo, hi = yarn_correction_range(rot_dim, theta, scaling)
    hi = hi + 0.001 if lo == hi else hi
    ramp = jnp.clip((jnp.arange(rot_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               partial: float = 1.0,
               scaling: YarnScaling | None = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq). Rotates the
    two halves of the rotary dims (``rotate_half``); ``scaling`` is
    YaRN's, which also scales cos and sin by its mscale ratio."""
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta, partial, scaling)
    rot_dim = inv.shape[0] * 2
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., seq, rot//2)
    cos = jnp.cos(ang)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, dtype, gated: bool = True) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    p = {
        "w_up": truncated_normal(k2, (d_model, d_ff), dtype, s_in),
        "w_down": truncated_normal(k3, (d_ff, d_model), dtype, s_out),
    }
    if gated:
        p["w_gate"] = truncated_normal(k1, (d_model, d_ff), dtype, s_in)
    return p


def mlp_fwd(p: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    actfn = jax.nn.silu if act == "silu" else jax.nn.gelu
    up = jnp.einsum("...d,df->...f", x, p["w_up"])
    if "w_gate" in p:
        gate = actfn(jnp.einsum("...d,df->...f", x, p["w_gate"]))
        h = gate * up
    else:
        h = actfn(up)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------
def init_embed(key, vocab: int, d_model: int, dtype) -> dict:
    # 0.02 scale keeps tied-head logits O(1) at init
    return {"table": truncated_normal(key, (vocab, d_model), dtype, 0.02)}


def embed_fwd(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def init_linear(key, d_in: int, d_out: int, dtype, bias: bool = False) -> dict:
    p = {"w": truncated_normal(key, (d_in, d_out), dtype, d_in ** -0.5)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear_fwd(p: dict, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(cfg: ArchConfig, d: int, dtype) -> dict:
    if cfg.norm_type == "layernorm":
        return init_layernorm(d, dtype)
    return init_rmsnorm(d, dtype)


def norm_fwd(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    if "bias" in p:
        return layernorm_fwd(p, x, cfg.norm_eps)
    return rmsnorm_fwd(p, x, cfg.norm_eps)
