"""Pallas TPU paged flash-decode kernel: one query token vs. a block-table
KV cache (vLLM-style paged attention).

Extends kernels/decode_attention.py to the paged layout: K/V live in a
shared physical page pool stored token-major with the kv heads flattened
into the lanes, ``(L, P, block_size, Hkv·hd)`` for a stack of ``L``
layers, and each sequence owns a row of page indices (the block table).
The gather happens INSIDE the grid: the block table, the live lengths and
the layer index ride along as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps look
the physical page up per grid step — ``(layer, table[b, j], 0, h)``, head
``h``'s ``hd`` lanes of the page — and the DMA engine reads exactly the
pages the sequence owns, in logical order, straight out of the stacked
pool. Nothing outside the kernel transposes, slices or copies the pool,
so a decode step's layer scan can carry the pool and update it in place.
Grid is ``(batch, kv_head, nblk)`` with the block axis TPU-sequential,
carrying the online-softmax partials (running max / normaliser /
accumulator) in VMEM scratch exactly like the dense decode kernel.

A head's lane block is legal on the TPU only when ``hd`` is a multiple
of 128 (the (8, 128) block rule) or there is one kv head; otherwise the
wrapper falls back to a head-major transpose of the layer's pool per
call.

Validity is reconstructed in-kernel from the prefetched lengths
(``j·bs + iota < len[b]``) instead of a materialised (B, W) mask — pages
past a sequence's live prefix (including the conventional scratch page)
are masked to -inf before the softmax, so their garbage contributes an
exact 0.0. The int8 variant dequantises pages in VMEM via scale pages
``(L, P, block_size, Hkv)``, read a whole page at a time with head ``h``'s
column picked out in VMEM, with the same kernel body as the fp one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_row(scales: jax.Array, h) -> jax.Array:
    """Head ``h``'s column of a ``(bs, Hkv)`` scale page as a ``(1, bs)``
    row: a masked lane sum (exact — every other term is 0.0) and a
    transpose, both in VMEM."""
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    col = jnp.sum(jnp.where(lane == h, scales, 0.0), axis=1, keepdims=True)
    return col.T


def _paged_kernel(table_ref, lengths_ref, layer_ref, q_ref, k_ref, v_ref,
                  *rest, block_size: int, scale: float, softcap: float,
                  quantized: bool):
    """One (batch, kv_head, logical-block) step. ``quantized`` adds two
    scale-page refs after ``v_ref``; they scale the score and probability
    columns exactly as in ``decode_attention._decode_kernel``."""
    del table_ref, layer_ref  # consumed by the BlockSpec index maps
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
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
        s = s * _head_row(ks_ref[0, 0, :, :].astype(jnp.float32), h)
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
        p = p * _head_row(vs_ref[0, 0, :, :].astype(jnp.float32), h)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_call(q, k_pages, v_pages, scale_pages, table, lengths, layer, *,
                softcap: float, interpret: bool) -> jax.Array:
    """Shared pallas_call over a stacked pool ``(L, P, bs, Hkv·K)``; one
    layer's ``(P, bs, Hkv·K)`` is a one-layer stack (the leading unit
    axis is a bitcast). Scale pages ``(L, P, bs, Hkv)`` ride a whole page
    per block: ``(bs, Hkv)`` is the array's own last two dims, which the
    TPU lowering accepts."""
    if k_pages.ndim == 3:
        k_pages, v_pages = k_pages[None], v_pages[None]
        scale_pages = tuple(sc[None] for sc in scale_pages)
    B, H, K = q.shape
    _, P, bs, D = k_pages.shape
    Hkv = D // K
    nblk = table.shape[1]
    G = H // Hkv
    grid = (B, Hkv, nblk)

    qg = q.reshape(B, Hkv, G, K)
    layer = jnp.asarray(layer, jnp.int32)
    if K % 128 == 0 or Hkv == 1:
        # head h's K lanes of the page, read where the pool lies
        kt, vt = k_pages, v_pages
        page = pl.BlockSpec(
            (1, 1, bs, K), lambda b, h, j, t, ln, l: (l[0], t[b, j], 0, h))
    else:
        # a K-lane block with K % 128 != 0 breaks the TPU's (8, 128)
        # block rule: read a head-major copy of this layer's pool
        def head_major(x):
            x = jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)
            return jnp.moveaxis(x.reshape(P, bs, Hkv, K), 2, 1)
        kt, vt = head_major(k_pages), head_major(v_pages)
        page = pl.BlockSpec(
            (1, 1, bs, K), lambda b, h, j, t, ln, l: (t[b, j], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, K), lambda b, h, j, t, ln, l: (b, h, 0, 0)),
        page, page,
    ]
    args = [qg, kt, vt]
    for sc in scale_pages:                         # (L, P, bs, Hkv) each
        in_specs.append(pl.BlockSpec(
            (1, 1, bs, Hkv), lambda b, h, j, t, ln, l: (l[0], t[b, j], 0, 0)))
        args.append(sc)

    kernel = functools.partial(_paged_kernel, block_size=bs,
                               scale=K ** -0.5, softcap=softcap,
                               quantized=bool(scale_pages))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # table, lengths, layer
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, K),
                               lambda b, h, j, t, ln, l: (b, h, 0, 0)),
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
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), layer.reshape(1),
      *args)
    return out.reshape(B, H, K)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           lengths: jax.Array, layer: jax.Array | int = 0,
                           *, softcap: float = 0.0,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, K); k_pages/v_pages: (L, P, bs, Hkv·K) or one layer's
    (P, bs, Hkv·K); table: (B, nblk) int32; lengths: (B,) int32; layer:
    int32 scalar, the stack index read -> (B, H, K)."""
    return _paged_call(q, k_pages, v_pages, (), table, lengths, layer,
                       softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention_int8(q: jax.Array, k_pages: jax.Array,
                                v_pages: jax.Array,
                                k_scale_pages: jax.Array,
                                v_scale_pages: jax.Array,
                                table: jax.Array, lengths: jax.Array,
                                layer: jax.Array | int = 0, *,
                                softcap: float = 0.0,
                                interpret: bool = False) -> jax.Array:
    """q: (B,H,K) fp; k/v pages: (L, P, bs, Hkv·K) int8; scale pages:
    (L, P, bs, Hkv) f32 (or each without its L axis); table: (B, nblk)
    int32; lengths: (B,) int32; layer: int32 scalar."""
    return _paged_call(q, k_pages, v_pages, (k_scale_pages, v_scale_pages),
                       table, lengths, layer, softcap=softcap,
                       interpret=interpret)
