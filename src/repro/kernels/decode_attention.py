"""Pallas TPU flash-decode kernel: one query token vs. a ring-buffer KV cache.

Grid (batch, kv_head, W/BK); the KV axis is TPU-sequential so the partial
softmax (running max / normaliser / accumulator) is carried in VMEM scratch
— the flash-decoding pattern adapted to a single grid pass. All ``group``
query heads of a kv head are processed together as the matmul M dimension
(group × BK hits the MXU as a skinny matmul; for kv-replicated GQA this is
the best obtainable shape without head-batching, which ops.py applies by
folding batch into the grid).

``valid`` marks live ring slots (slots whose reconstructed absolute position
is non-negative); dead slots are masked to -inf before the softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, *rest, scale: float,
                   softcap: float, quantized: bool):
    """One (batch, kv_head, kv-tile) step. ``quantized`` adds two scale
    refs after ``valid_ref``: int8 k/v tiles are dequantised in VMEM
    (per-token, per-head absmax scales), so HBM traffic is the int8
    bytes plus the scales. A token's k scale multiplies its score column
    and its v scale its probability column, which keeps the scales in
    the lane layout they arrive in."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)      # (G, K)
    k = k_ref[0, 0, :, :].astype(jnp.float32)      # (BK, K)
    v = v_ref[0, 0, :, :].astype(jnp.float32)      # (BK, K)
    valid = valid_ref[0, :, :]                     # (1, BK)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, BK)
    if quantized:
        s = s * ks_ref[0, 0, :, :].astype(jnp.float32)
    s = s * scale
    if softcap and softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    if quantized:
        p = p * vs_ref[0, 0, :, :].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode_call(q, k, v, valid, scales, *, softcap: float, block_k: int,
                 interpret: bool) -> jax.Array:
    """Shared pallas_call. The mask rides as ``(B, 1, W)`` and the scales
    as ``(B, Hkv, 1, W)``, so every block's last two dims are either the
    array's own or a ``(1, block_k)`` lane tile — the TPU lowering
    refuses a ``(1, block_k)`` block over a ``(B, W)`` array."""
    B, H, K = q.shape
    W, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    block_k = min(block_k, W)
    assert W % block_k == 0, (W, block_k)
    grid = (B, Hkv, W // block_k)

    qg = q.reshape(B, Hkv, G, K)
    kt = jnp.moveaxis(k, 2, 1)                     # (B, Hkv, W, K)
    vt = jnp.moveaxis(v, 2, 1)
    tile = pl.BlockSpec((1, 1, block_k, K), lambda b, h, j: (b, h, j, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, K), lambda b, h, j: (b, h, 0, 0)),
        tile, tile,
        pl.BlockSpec((1, 1, block_k), lambda b, h, j: (b, 0, j)),
    ]
    args = [qg, kt, vt, valid.reshape(B, 1, W)]
    for sc in scales:                              # (B, W, Hkv) each
        in_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                     lambda b, h, j: (b, h, 0, j)))
        args.append(jnp.moveaxis(sc, 2, 1).reshape(B, Hkv, 1, W))

    kernel = functools.partial(_decode_kernel, scale=K ** -0.5,
                               softcap=softcap, quantized=bool(scales))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, K), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, K), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, K), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, K)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "block_k", "interpret"))
def decode_attention_int8(q: jax.Array, k: jax.Array, v: jax.Array,
                          valid: jax.Array, k_scale: jax.Array,
                          v_scale: jax.Array, *, softcap: float = 0.0,
                          block_k: int = 512,
                          interpret: bool = False) -> jax.Array:
    """q: (B,H,K) fp; k/v: (B,W,Hkv,K) int8; scales: (B,W,Hkv) f32."""
    return _decode_call(q, k, v, valid, (k_scale, v_scale), softcap=softcap,
                        block_k=block_k, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *, softcap: float = 0.0,
                     block_k: int = 512, interpret: bool = False) -> jax.Array:
    """q: (B, H, K); k/v: (B, W, Hkv, K); valid: (B, W) bool -> (B, H, K)."""
    return _decode_call(q, k, v, valid, (), softcap=softcap,
                        block_k=block_k, interpret=interpret)
