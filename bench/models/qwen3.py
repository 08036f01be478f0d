"""Qwen3 (hf:Qwen/Qwen3-0.6B): RMSNorm before attention and MLP, q and k
RMS-normalised per head before the rotary embedding, grouped-query
attention, SwiGLU MLP. A dense model: the equations are in ``dense.py``,
which this file binds to Qwen3's flags as the contract of
``bench/spec.py``."""
import jax.numpy as jnp

from bench.models import dense

FLAGS = {"norm": "rmsnorm", "qk_norm": True}


def program_config(cfg: dict, name: str):
    return dense.program_config(cfg, FLAGS, name)


def init_weights(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    return dense.init_weights(cfg, FLAGS, key, dtype)


def to_program(w: dict, cfg: dict) -> dict:
    return dense.to_program(w, cfg, FLAGS)


def reference_weights(cfg: dict, seed: int) -> dict:
    return dense.reference_weights(cfg, FLAGS, seed)


def Reference(cfg: dict, weights: dict, fp8: bool = False):
    return dense.Reference(cfg, FLAGS, weights, fp8)


work = dense.work
