"""Pallas TPU kernel for absorbed-MLA decode (DeepSeek latent attention).

One query token attends to the LATENT cache: scores combine a latent-space
dot (r = kv_lora_rank, e.g. 512) and a shared-rope dot (dr, e.g. 64); the
context is re-read from the same latent tiles. Grid (batch, S/BS); the
sequence axis is TPU-sequential so the online softmax (m, l) and the
(H, r) context accumulator live in VMEM scratch — each ckv tile is read
from HBM exactly ONCE and used for both the score and the context matmul
(the jnp oracle reads it twice).

This is the hot decode loop of deepseek-v2-lite (§Perf carry-over: MLA
decode is latent-cache-read bound, so single-read tiling is the roofline
move the kernel encodes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_kernel(ql_ref, qr_ref, ckv_ref, kr_ref, valid_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ql = ql_ref[0].astype(jnp.float32)            # (H, r)
    qr = qr_ref[0].astype(jnp.float32)            # (H, dr)
    ckv = ckv_ref[0].astype(jnp.float32)          # (BS, r)
    kr = kr_ref[0].astype(jnp.float32)            # (BS, dr)
    valid = valid_ref[0, :, :]                    # (1, BS)

    s = jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())))
    s += jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())))
    s *= scale                                    # (H, BS)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())))         # (H, r)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_s", "interpret"))
def mla_decode_ctx(q_lat: jax.Array, q_rope: jax.Array, ckv: jax.Array,
                   k_rope: jax.Array, valid: jax.Array, *, scale: float,
                   block_s: int = 512, interpret: bool = False) -> jax.Array:
    """Shapes as in ref.mla_decode_ctx. Returns ctx (B, H, r)."""
    B, H, r = q_lat.shape
    S = ckv.shape[1]
    dr = q_rope.shape[2]
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    grid = (B, S // block_s)

    kernel = functools.partial(_mla_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, H, dr), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_s, r), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_s, dr), lambda b, j: (b, j, 0)),
            # (B, 1, S): a (1, block_s) block over (B, S) is refused by
            # the TPU lowering
            pl.BlockSpec((1, 1, block_s), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, r), jnp.float32),
        ],
        interpret=interpret,
    )(q_lat, q_rope, ckv, k_rope, valid.reshape(B, 1, S))
