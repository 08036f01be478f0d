"""Pallas TPU paged flash-decode kernel: one query token vs. a block-table
KV cache (vLLM-style paged attention).

Extends kernels/decode_attention.py to the paged layout: K/V live in a
shared physical page pool ``(P, block_size, Hkv, hd)`` and each sequence
owns a row of page indices (the block table). The gather happens INSIDE
the grid: the per-sequence block table and live lengths ride along as
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so the K/V
BlockSpec index maps can look the physical page up per grid step —
``(table[b, j], h, 0, 0)`` — and the DMA engine fetches exactly the pages
the sequence owns, in logical order. Grid is ``(batch, kv_head, nblk)``
with the block axis TPU-sequential, carrying the online-softmax partials
(running max / normaliser / accumulator) in VMEM scratch exactly like the
dense decode kernel.

Validity is reconstructed in-kernel from the prefetched lengths
(``j·bs + iota < len[b]``) instead of a materialised (B, W) mask — pages
past a sequence's live prefix (including the conventional scratch page)
are masked to -inf before the softmax, so their garbage contributes an
exact 0.0. The int8 variant dequantises pages in VMEM via scale pages
``(P, block_size, Hkv)``, with the same kernel body as the fp one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(table_ref, lengths_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, scale: float, softcap: float,
                  quantized: bool):
    """One (batch, kv_head, logical-block) step. ``quantized`` adds two
    scale-page refs after ``v_ref``; they scale the score and probability
    columns exactly as in ``decode_attention._decode_kernel``."""
    del table_ref  # consumed by the BlockSpec index maps (page lookup)
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)      # (G, K)
    k = k_ref[0, 0, :, :].astype(jnp.float32)      # (bs, K)
    v = v_ref[0, 0, :, :].astype(jnp.float32)      # (bs, K)
    # live slots of this logical block, from the prefetched lengths
    # (TPU iota must be >= 2D: broadcasted_iota over (1, bs))
    offs = jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    valid = j * block_size + offs < lengths_ref[b]  # (1, bs)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bs)
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


def _paged_call(q, k_pages, v_pages, scale_pages, table, lengths, *,
                softcap: float, interpret: bool) -> jax.Array:
    """Shared pallas_call. Scale pages ride as ``(P, Hkv, 1, bs)`` so
    their blocks' last two dims are the array's own — a ``(1, bs)`` block
    over ``(P, Hkv, bs)`` is refused by the TPU lowering."""
    B, H, K = q.shape
    P, bs, Hkv = k_pages.shape[:3]
    nblk = table.shape[1]
    G = H // Hkv
    grid = (B, Hkv, nblk)

    qg = q.reshape(B, Hkv, G, K)
    kt = jnp.moveaxis(k_pages, 2, 1)               # (P, Hkv, bs, K)
    vt = jnp.moveaxis(v_pages, 2, 1)
    page = pl.BlockSpec((1, 1, bs, K),
                        lambda b, h, j, t, ln: (t[b, j], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, K), lambda b, h, j, t, ln: (b, h, 0, 0)),
        page, page,
    ]
    args = [qg, kt, vt]
    for sc in scale_pages:                         # (P, bs, Hkv) each
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bs), lambda b, h, j, t, ln: (t[b, j], h, 0, 0)))
        args.append(jnp.moveaxis(sc, 2, 1).reshape(P, Hkv, 1, bs))

    kernel = functools.partial(_paged_kernel, block_size=bs,
                               scale=K ** -0.5, softcap=softcap,
                               quantized=bool(scale_pages))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # table, lengths
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, K),
                               lambda b, h, j, t, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, K), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, K), q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return out.reshape(B, H, K)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           lengths: jax.Array, *, softcap: float = 0.0,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, K); k_pages/v_pages: (P, bs, Hkv, K); table: (B, nblk)
    int32; lengths: (B,) int32 -> (B, H, K)."""
    return _paged_call(q, k_pages, v_pages, (), table, lengths,
                       softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention_int8(q: jax.Array, k_pages: jax.Array,
                                v_pages: jax.Array,
                                k_scale_pages: jax.Array,
                                v_scale_pages: jax.Array,
                                table: jax.Array, lengths: jax.Array, *,
                                softcap: float = 0.0,
                                interpret: bool = False) -> jax.Array:
    """q: (B,H,K) fp; k/v pages: (P, bs, Hkv, K) int8; scale pages:
    (P, bs, Hkv) f32; table: (B, nblk) int32; lengths: (B,) int32."""
    return _paged_call(q, k_pages, v_pages, (k_scale_pages, v_scale_pages),
                       table, lengths, softcap=softcap, interpret=interpret)
