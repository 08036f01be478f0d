"""Pallas TPU flash-attention (prefill) kernel.

Tiling: grid (batch, q_heads, Sq/BQ, Skv/BK); the last grid axis is the
TPU-sequential one, so the online-softmax running max / normaliser / output
accumulator live in VMEM scratch and are carried across KV tiles. Block
shapes default to (128, head_dim) — MXU-aligned on the contraction dims.

GQA is handled in the index map (kv head = q head // group); causal and
sliding-window masking is applied per tile, in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 block_q: int, block_k: int, seq_kv: int, seq_q: int):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)       # (BQ, K)
    k = k_ref[0, 0, :, :].astype(jnp.float32)       # (BK, K)
    v = v_ref[0, 0, :, :].astype(jnp.float32)       # (BK, K)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if softcap and softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (seq_kv - seq_q)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window and window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                             # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kv_idx == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret", "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    scale: float | None = None) -> jax.Array:
    """q: (B, Sq, H, K); k/v: (B, Skv, Hkv, Kv). Returns (B, Sq, H, Kv).
    ``scale`` multiplies the scores (default K**-0.5)."""
    B, Sq, H, K = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Kv = v.shape[3]
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    grid = (B, H, Sq // block_q, Skv // block_k)

    # layout: move heads ahead of seq so each tile is a contiguous (S, K) slab
    qt = jnp.moveaxis(q, 2, 1)  # (B, H, Sq, K)
    kt = jnp.moveaxis(k, 2, 1)  # (B, Hkv, Skv, K)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _attn_kernel, scale=K ** -0.5 if scale is None else scale,
        causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, seq_kv=Skv,
        seq_q=Sq)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, K), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, K),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, Kv),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Kv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Kv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Kv), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
