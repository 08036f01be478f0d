"""Memory-honest flash attention in pure jnp (the roofline reference).

This is the CPU/dry-run execution path for full-sequence attention. Unlike
the quadratic oracle in ``ref.py`` it never materialises an (Sq, Skv)
score tensor: the forward is a two-level ``lax.scan`` over (q-block,
kv-block) tiles with an online softmax, and the backward is a ``custom_vjp``
implementing the flash-attention backward (recompute scores blockwise,
save only out + per-row logsumexp — O(S) residuals).

Why it exists: the multi-pod dry-run lowers the model on CPU and reads the
compiled HLO for the roofline. If the lowered attention materialised S²
tensors, the memory/bytes terms would describe an implementation we would
never run on TPU — this module makes the lowered graph structurally match
what the Pallas kernel (flash_attention.py) does on real hardware, tile for
tile. Like that kernel's grid, every (q, kv) tile is visited (no static
causal-block skipping) — the compute term reflects the full grid.

Numerics are validated against ``ref.flash_attention`` (values and grads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _blockify(x, n_blocks: int, block: int):
    """(..., S, K) -> (n_blocks, ..., block, K) for scan xs."""
    S = x.shape[-2]
    lead = x.shape[:-2]
    x = x.reshape(*lead, n_blocks, block, x.shape[-1])
    return jnp.moveaxis(x, -3, 0)


def _mask(q0, k0, bq, bk, *, sq, skv, causal, window):
    """(bq, bk) bool mask for tile at (q0, k0) with right-aligned queries."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (skv - sq)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = k_pos < skv  # guard for padded keys
    if causal:
        m &= k_pos <= q_pos
    if window and window > 0:
        m &= k_pos > q_pos - window
    return m


def _scores(qb, kb, q0, k0, *, scale, softcap, sq, skv, causal, window):
    """Raw+capped masked scores for one tile. qb: (B,Hkv,G,bq,K),
    kb: (B,Hkv,bk,K) -> (B,Hkv,G,bq,bk) f32."""
    s = jnp.einsum("bhgqk,bhsk->bhgqs", qb.astype(jnp.float32),
                   kb.astype(jnp.float32)) * scale
    if softcap and softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    bq, bk = qb.shape[-2], kb.shape[-2]
    m = _mask(q0, k0, bq, bk, sq=sq, skv=skv, causal=causal, window=window)
    return jnp.where(m[None, None, None], s, NEG_INF)


def _fwd_impl(q, k, v, *, causal, window, softcap, block_q, block_k,
              scale=None):
    """Returns (out, lse). q: (B,Sq,H,K); k/v: (B,Skv,Hkv,K); ``scale``
    multiplies the scores (default K**-0.5)."""
    B, Sq, H, K = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Kv = v.shape[3]
    G = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    scale = K ** -0.5 if scale is None else scale

    qg = jnp.moveaxis(q.reshape(B, Sq, Hkv, G, K), 1, 3)   # (B,Hkv,G,Sq,K)
    kg = jnp.moveaxis(k, 1, 2)                             # (B,Hkv,Skv,K)
    vg = jnp.moveaxis(v, 1, 2)                             # (B,Hkv,Skv,Kv)
    if pq:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pq), (0, 0)))
    if pk:
        kg = jnp.pad(kg, ((0, 0),) * 2 + ((0, pk), (0, 0)))
        vg = jnp.pad(vg, ((0, 0),) * 2 + ((0, pk), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Skv + pk) // bk

    q_blocks = _blockify(qg, nq, bq)                       # (nq,B,Hkv,G,bq,K)
    k_blocks = _blockify(kg, nk, bk)                       # (nk,B,Hkv,bk,K)
    v_blocks = _blockify(vg, nk, bk)

    def q_step(_, qb_i):
        qb, qi = qb_i
        q0 = qi * bq

        def kv_step(carry, kv_j):
            m_run, l_run, acc = carry
            kb, vb, kj = kv_j
            s = _scores(qb, kb, q0, kj * bk, scale=scale, softcap=softcap,
                        sq=Sq, skv=Skv, causal=causal, window=window)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_run, m_cur)
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_run + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhgqs,bhsk->bhgqk", p,
                                           vb.astype(jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, Hkv, G, bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, bq, 1), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, bq, Kv), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (k_blocks, v_blocks, jnp.arange(nk)))
        l_safe = jnp.maximum(l_f, 1e-30)
        out_b = acc / l_safe
        lse_b = (m_f + jnp.log(l_safe))[..., 0]            # (B,Hkv,G,bq)
        return None, (out_b, lse_b)

    _, (out_blocks, lse_blocks) = jax.lax.scan(
        q_step, None, (q_blocks, jnp.arange(nq)))
    # (nq,B,Hkv,G,bq,Kv) -> (B,Sq,H,Kv)
    out = jnp.moveaxis(out_blocks, 0, 3).reshape(B, Hkv, G, Sq + pq, Kv)
    lse = jnp.moveaxis(lse_blocks, 0, 3).reshape(B, Hkv, G, Sq + pq)
    out = jnp.moveaxis(out, 3, 1)[:, :Sq].reshape(B, Sq, H, Kv)
    return out.astype(q.dtype), lse[..., :Sq]


def _bwd_impl(q, k, v, out, lse, dout, *, causal, window, softcap,
              block_q, block_k, scale=None):
    B, Sq, H, K = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Kv = v.shape[3]
    G = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    scale = K ** -0.5 if scale is None else scale

    qg = jnp.moveaxis(q.reshape(B, Sq, Hkv, G, K), 1, 3)
    og = jnp.moveaxis(out.reshape(B, Sq, Hkv, G, Kv), 1, 3)
    dg = jnp.moveaxis(dout.reshape(B, Sq, Hkv, G, Kv), 1, 3).astype(jnp.float32)
    kg = jnp.moveaxis(k, 1, 2)
    vg = jnp.moveaxis(v, 1, 2)
    delta = jnp.sum(dg * og.astype(jnp.float32), axis=-1)  # (B,Hkv,G,Sq)
    if pq:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pq), (0, 0)))
        dg = jnp.pad(dg, ((0, 0),) * 3 + ((0, pq), (0, 0)))
        delta = jnp.pad(delta, ((0, 0),) * 3 + ((0, pq),))
        lse = jnp.pad(lse, ((0, 0),) * 3 + ((0, pq),))
    if pk:
        kg = jnp.pad(kg, ((0, 0),) * 2 + ((0, pk), (0, 0)))
        vg = jnp.pad(vg, ((0, 0),) * 2 + ((0, pk), (0, 0)))
    nq, nk = (Sq + pq) // bq, (Skv + pk) // bk

    q_blocks = _blockify(qg, nq, bq)
    d_blocks = _blockify(dg, nq, bq)
    l_blocks = jnp.moveaxis(lse.reshape(B, Hkv, G, nq, bq), 3, 0)
    e_blocks = jnp.moveaxis(delta.reshape(B, Hkv, G, nq, bq), 3, 0)
    k_blocks = _blockify(kg, nk, bk)
    v_blocks = _blockify(vg, nk, bk)

    def _p_and_dsr(qb, kb, q0, k0, lse_b, dov, delta_b):
        """Recompute tile probabilities + raw-score grads."""
        sr = jnp.einsum("bhgqk,bhsk->bhgqs", qb.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
        if softcap and softcap > 0.0:
            t = jnp.tanh(sr / softcap)
            sc = t * softcap
        else:
            sc = sr
        m = _mask(q0, k0, qb.shape[-2], kb.shape[-2],
                  sq=Sq, skv=Skv, causal=causal, window=window)
        sc = jnp.where(m[None, None, None], sc, NEG_INF)
        p = jnp.exp(sc - lse_b[..., None])                 # (B,Hkv,G,bq,bk)
        dsc = p * (dov - delta_b[..., None])
        if softcap and softcap > 0.0:
            dsc = dsc * (1.0 - t * t)
        return p, dsc

    # ---- pass 1: dq (scan q blocks; inner scan kv blocks)
    def q_step(_, xs):
        qb, db, lse_b, delta_b, qi = xs
        q0 = qi * bq

        def kv_step(dq_acc, kv_j):
            kb, vb, kj = kv_j
            dov = jnp.einsum("bhgqk,bhsk->bhgqs", db, vb.astype(jnp.float32))
            p, dsr = _p_and_dsr(qb, kb, q0, kj * bk, lse_b, dov, delta_b)
            dq_acc = dq_acc + jnp.einsum("bhgqs,bhsk->bhgqk", dsr,
                                         kb.astype(jnp.float32)) * scale
            return dq_acc, None

        dq0 = jnp.zeros((B, Hkv, G, bq, K), jnp.float32)
        dq_b, _ = jax.lax.scan(kv_step, dq0,
                               (k_blocks, v_blocks, jnp.arange(nk)))
        return None, dq_b

    _, dq_blocks = jax.lax.scan(
        q_step, None, (q_blocks, d_blocks, l_blocks, e_blocks,
                       jnp.arange(nq)))

    # ---- pass 2: dk, dv (scan kv blocks; inner scan q blocks)
    def kv_step2(_, xs):
        kb, vb, kj = xs
        k0 = kj * bk

        def q_step2(carry, q_j):
            dk_acc, dv_acc = carry
            qb, db, lse_b, delta_b, qi = q_j
            dov = jnp.einsum("bhgqk,bhsk->bhgqs", db, vb.astype(jnp.float32))
            p, dsr = _p_and_dsr(qb, kb, qi * bq, k0, lse_b, dov, delta_b)
            dv_acc = dv_acc + jnp.einsum("bhgqs,bhgqk->bhsk", p, db)
            dk_acc = dk_acc + jnp.einsum(
                "bhgqs,bhgqk->bhsk", dsr, qb.astype(jnp.float32)) * scale
            return (dk_acc, dv_acc), None

        dk0 = jnp.zeros((B, Hkv, bk, K), jnp.float32)
        dv0 = jnp.zeros((B, Hkv, bk, Kv), jnp.float32)
        (dk_b, dv_b), _ = jax.lax.scan(
            q_step2, (dk0, dv0),
            (q_blocks, d_blocks, l_blocks, e_blocks, jnp.arange(nq)))
        return None, (dk_b, dv_b)

    _, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_step2, None, (k_blocks, v_blocks, jnp.arange(nk)))

    dq = jnp.moveaxis(dq_blocks, 0, 3).reshape(B, Hkv, G, Sq + pq, K)
    dq = jnp.moveaxis(dq, 3, 1)[:, :Sq].reshape(B, Sq, H, K)
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(B, Hkv, Skv + pk, K)
    dk = jnp.moveaxis(dk, 2, 1)[:, :Skv]
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(B, Hkv, Skv + pk, Kv)
    dv = jnp.moveaxis(dv, 2, 1)[:, :Skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, softcap, block_q, block_k, scale):
    out, _ = _fwd_impl(q, k, v, causal=causal, window=window,
                       softcap=softcap, block_q=block_q, block_k=block_k,
                       scale=scale)
    return out


def _flash_fwd(q, k, v, causal, window, softcap, block_q, block_k, scale):
    out, lse = _fwd_impl(q, k, v, causal=causal, window=window,
                         softcap=softcap, block_q=block_q, block_k=block_k,
                         scale=scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, softcap, block_q, block_k, scale, res,
               dout):
    q, k, v, out, lse = res
    return _bwd_impl(q, k, v, out, lse, dout, causal=causal, window=window,
                     softcap=softcap, block_q=block_q, block_k=block_k,
                     scale=scale)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 512,
                    block_k: int = 512, scale: float | None = None):
    """Drop-in for ``ref.flash_attention`` with flash memory behaviour;
    ``scale`` multiplies the scores (default head_dim**-0.5)."""
    return _flash(q, k, v, causal, window, softcap, block_q, block_k,
                  scale)
