"""What every model family's file shares: the key from a run's seed, the
canonical tree drawn leaf by leaf from it, the check that a tree fits the
program's own, and the fp8 rounding of the control.

Nothing here knows a family: a family's file gives the shapes of its
canonical tree and its equations, and calls these.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

# canonical weight scales: the embedding as Hugging Face initialises it,
# the projections at 1/sqrt(fan_in) so that activations stay near unit
# scale through the full depth, norm gains drawn round 1 so that a gain
# applied to the wrong axis or left out shows in the logits
EMBED_STD = 0.02
GAIN_SPREAD = 0.2
BIAS_STD = 0.05


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, including ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % 2**31)
    return jax.random.fold_in(key, seed // 2**31)


def draw_leaf(path: str, shape: tuple, key: jax.Array) -> jax.Array:
    """One leaf of a canonical tree, its scale chosen by its path: gains
    (``…scale``, ``…_norm``) round 1, biases small, the embedding at
    ``EMBED_STD``, every other leaf a projection whose fan-in is its
    second-last axis."""
    if path.endswith("scale") or path.endswith("_norm"):
        return 1.0 + GAIN_SPREAD * (jax.random.uniform(key, shape) - 0.5) * 2
    if path.endswith("bias"):
        return BIAS_STD * jax.random.normal(key, shape)
    if path == "embed":
        return EMBED_STD * jax.random.normal(key, shape)
    fan_in = shape[-2]
    return jax.random.normal(key, shape) / math.sqrt(fan_in)


def draw_tree(shapes: dict, key: jax.Array, dtype) -> dict:
    """The tree of ``shapes`` (nested dicts of shape tuples), every leaf
    drawn by ``draw_leaf`` from ``key`` folded with its dotted path, so
    that each leaf is the same whatever else the tree holds. Call under
    ``jax.jit`` so that the tree is made on the device in one program."""
    def walk(node, prefix):
        if isinstance(node, dict):
            return {k: walk(v, f"{prefix}.{k}" if prefix else k)
                    for k, v in node.items()}
        leaf_key = jax.random.fold_in(key, _path_id(prefix))
        return draw_leaf(prefix, node, leaf_key).astype(dtype)
    return walk(shapes, "")


def _path_id(path: str) -> int:
    h = 0
    for ch in path.encode():
        h = (h * 131 + ch) % 2**31
    return h


def check_program_tree(params: Any, expected: Any) -> None:
    """Every leaf of the program's own ``init`` tree is filled, with its
    shape: a program that changes its layout fails here, not in a
    comparison."""
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(expected)[0]
    got_s = {jax.tree_util.keystr(p): tuple(a.shape) for p, a in got}
    want_s = {jax.tree_util.keystr(p): tuple(a.shape) for p, a in want}
    if got_s != want_s:
        missing = sorted(set(want_s) - set(got_s))
        extra = sorted(set(got_s) - set(want_s))
        wrong = sorted(k for k in set(got_s) & set(want_s)
                       if got_s[k] != want_s[k])
        raise ValueError(f"weights do not fit the program's tree: missing "
                         f"{missing}, extra {extra}, wrong shapes {wrong}")


def fp8(x: jax.Array, axis: int = -1) -> jax.Array:
    """Round to float8 e4m3 with a scale per slice along ``axis``, as an
    fp8 deployment stores weights and activations."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x: jax.Array, w: jax.Array, control: bool) -> jax.Array:
    """``x @ w``; for the fp8 control both operands are rounded to float8
    first: activations per token, weights per output column."""
    if control:
        x, w = fp8(x, -1), fp8(w, 0)
    return x @ w
