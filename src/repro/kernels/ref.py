"""Pure-jnp oracles for every Pallas kernel.

These are the numerical ground truth: each kernel test sweeps shapes/dtypes
and asserts allclose against these, and they are also the CPU execution path
(the models call ``kernels.ops`` which dispatches here off-TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(scores: jax.Array, softcap: float) -> jax.Array:
    if softcap and softcap > 0.0:
        return jnp.tanh(scores / softcap) * softcap
    return scores


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0) -> jax.Array:
    """Full attention oracle.

    q: (B, Sq, H, K); k/v: (B, Skv, Hkv, K) with H % Hkv == 0 (GQA).
    window > 0 masks keys further than ``window-1`` positions behind the
    query (sliding-window attention). Returns (B, Sq, H, K).
    """
    B, Sq, H, K = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Kv = v.shape[3]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, K)
    scores = jnp.einsum("bqhgk,bshk->bhgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (K ** -0.5)
    scores = _softcap(scores, softcap)
    q_pos = jnp.arange(Sq)[:, None] + (Skv - Sq)  # right-aligned queries
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window and window > 0:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqs,bshk->bqhgk", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Kv).astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *, softcap: float = 0.0) -> jax.Array:
    """Single-token decode oracle.

    q: (B, H, K); k/v: (B, W, Hkv, K); valid: (B, W) bool — which ring slots
    hold live entries for each sequence. Returns (B, H, K).
    """
    B, H, K = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, K)
    scores = jnp.einsum("bhgk,bshk->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (K ** -0.5)
    scores = _softcap(scores, softcap)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshk->bhgk", w, v.astype(jnp.float32))
    return out.reshape(B, H, K).astype(q.dtype)


def decode_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                             valid: jax.Array, *, softcap: float = 0.0,
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial flash-decode over a LOCAL slice of the KV cache: returns the
    unnormalised accumulator plus the (max, normaliser) statistics so a
    cross-shard merge can combine slices (sequence-parallel decode — see
    attention._seq_parallel_decode). Handles int8 caches via scales."""
    B, H, K = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None].astype(jnp.float32)
        vf = vf * v_scale[..., None].astype(jnp.float32)
    qg = q.reshape(B, Hkv, G, K).astype(jnp.float32)
    s = jnp.einsum("bhgk,bshk->bhgs", qg, kf) * (K ** -0.5)
    s = _softcap(s, softcap)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)           # exp(-inf-(-inf))
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgs,bshk->bhgk", p, vf)
    return (acc.reshape(B, H, vf.shape[-1]), m.reshape(B, H),
            l.reshape(B, H))


def decode_attention_blocked(q: jax.Array, k: jax.Array, v: jax.Array,
                             valid: jax.Array, *, softcap: float = 0.0,
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None,
                             block: int = 1024) -> jax.Array:
    """Flash-decode reference: ``lax.scan`` over KV blocks with an online
    softmax, so only one (B, Hkv, block, hd) tile is live at a time — the
    lowering/roofline counterpart of the Pallas decode kernel (the plain
    oracle above materialises (B, H, W) scores).

    Supports quantised caches: when ``k_scale``/``v_scale`` (B, W, Hkv) are
    given, k/v are int8 and dequantised per tile (in-kernel on TPU).
    """
    B, H, K = q.shape
    W, Hkv = k.shape[1], k.shape[2]
    Kv = v.shape[3]
    G = H // Hkv
    blk = min(block, W)
    pad = (-W) % blk
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    nb = (W + pad) // blk
    qg = q.reshape(B, Hkv, G, K).astype(jnp.float32)

    def to_blocks(a):
        return jnp.moveaxis(
            a.reshape(B, nb, blk, *a.shape[2:]), 1, 0)

    xs = [to_blocks(k), to_blocks(v), to_blocks(valid)]
    if k_scale is not None:
        xs += [to_blocks(k_scale), to_blocks(v_scale)]

    def step(carry, inp):
        m_run, l_run, acc = carry
        if k_scale is not None:
            kb, vb, vb_ok, ksb, vsb = inp
            kb = kb.astype(jnp.float32) * ksb[..., None].astype(jnp.float32)
            vb = vb.astype(jnp.float32) * vsb[..., None].astype(jnp.float32)
        else:
            kb, vb, vb_ok = inp
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
        s = jnp.einsum("bhgk,bshk->bhgs", qg, kb) * (K ** -0.5)
        s = _softcap(s, softcap)
        s = jnp.where(vb_ok[:, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_run, m_cur)
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgs,bshk->bhgk", p, vb)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, 1), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Kv), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0), tuple(xs))
    out = acc / jnp.maximum(l_f, 1e-30)
    return out.reshape(B, H, Kv).astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           lengths: jax.Array, layer: jax.Array | int = 0,
                           *, softcap: float = 0.0,
                           k_scale_pages: jax.Array | None = None,
                           v_scale_pages: jax.Array | None = None
                           ) -> jax.Array:
    """Paged flash-decode oracle: gather K/V through a per-sequence block
    table, then run the SAME blocked online softmax as the dense path.

    q: (B, H, K); k_pages/v_pages: (L, P, bs, Hkv·K) — a stack of shared
    physical page pools, token-major with the kv heads in the lanes (the
    last page is conventionally scratch) — or one layer's (P, bs, Hkv·K);
    ``layer`` picks the stack entry; table: (B, nblk) int32 page indices
    per logical block; lengths: (B,) — tokens [0, len) are live; scale
    pages (int8 K/V): (L, P, bs, Hkv) or (P, bs, Hkv). Masked positions
    (scratch garbage included) contribute an exact 0.0 to the
    accumulator, so for equal live prefixes the output is bitwise
    identical to ``decode_attention_blocked`` over a dense (B, nblk*bs)
    cache — the bit-parity contract the paged serving engine tests pin
    down.
    """
    def pool(pages, ndim):
        return pages if pages.ndim == ndim else pages[layer]
    B, _, K = q.shape
    kp, vp = pool(k_pages, 3), pool(v_pages, 3)
    nblk = table.shape[1]
    bs, D = kp.shape[1], kp.shape[2]
    W = nblk * bs
    k = kp[table].reshape(B, W, D // K, K)
    v = vp[table].reshape(B, W, D // K, K)
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    ks = vs = None
    if k_scale_pages is not None:
        ks = pool(k_scale_pages, 3)[table].reshape(B, W, D // K)
        vs = pool(v_scale_pages, 3)[table].reshape(B, W, D // K)
    return decode_attention_blocked(q, k, v, valid, softcap=softcap,
                                    k_scale=ks, v_scale=vs)


def mla_decode_ctx(q_lat: jax.Array, q_rope: jax.Array, ckv: jax.Array,
                   k_rope: jax.Array, valid: jax.Array, *,
                   scale: float) -> jax.Array:
    """Absorbed-MLA decode oracle: attention in the latent space.

    q_lat: (B, H, r); q_rope: (B, H, dr); ckv: (B, S, r);
    k_rope: (B, S, dr); valid: (B, S). Returns ctx (B, H, r) — the gated
    latent context (the caller applies W_uv and W_o).
    """
    scores = jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32),
                        ckv.astype(jnp.float32))
    scores += jnp.einsum("bhk,bsk->bhs", q_rope.astype(jnp.float32),
                         k_rope.astype(jnp.float32))
    scores *= scale
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", w,
                      ckv.astype(jnp.float32)).astype(q_lat.dtype)


def moe_expert_gmm(xs: jax.Array, tile_expert: jax.Array,
                   n_tiles: jax.Array, layer, w_gate: jax.Array,
                   w_up: jax.Array, w_down: jax.Array, *,
                   act: str = "silu") -> jax.Array:
    """Grouped SwiGLU oracle, shapes as in moe_gmm.moe_expert_gmm: each
    tile's rows through its expert; tiles past ``n_tiles`` read 0."""
    M, d = xs.shape
    n_tile = tile_expert.shape[0]
    x = xs.reshape(n_tile, M // n_tile, d)
    def pick(w):      # each tile's expert's weights, at ``layer``
        return jax.lax.dynamic_index_in_dim(w, layer,
                                            keepdims=False)[tile_expert]
    actfn = jax.nn.silu if act == "silu" else jax.nn.gelu
    g = jnp.einsum("tmd,tdf->tmf", x, pick(w_gate),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("tmd,tdf->tmf", x, pick(w_up),
                   preferred_element_type=jnp.float32)
    h = (actfn(g) * u).astype(xs.dtype)
    y = jnp.einsum("tmf,tfd->tmd", h, pick(w_down),
                   preferred_element_type=jnp.float32)
    y = jnp.where((jnp.arange(n_tile) < n_tiles)[:, None, None], y, 0.0)
    return y.reshape(M, d).astype(xs.dtype)


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < l <= i} x[..., l]."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
             C_: jax.Array, D: jax.Array, *, chunk: int = 64,
             init_state: jax.Array | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Mamba2 SSD (state-space duality) chunked scan oracle.

    x: (B, S, nh, hd); dt: (B, S, nh) (post-softplus, >=0); A: (nh,) (<0);
    B_/C_: (B, S, ng, ds); D: (nh,). Returns (y, final_state) with
    y: (B, S, nh, hd), state: (B, nh, hd, ds).

    Implements eq. (SSD) of arXiv:2405.21060: within-chunk quadratic form +
    across-chunk linear recurrence.
    """
    Bb, S, nh, hd = x.shape
    ng, ds = B_.shape[2], B_.shape[3]
    rep = nh // ng
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    f32 = jnp.float32

    xc = x.reshape(Bb, nc, chunk, nh, hd).astype(f32)
    dtc = dt.reshape(Bb, nc, chunk, nh).astype(f32)
    Bc = B_.reshape(Bb, nc, chunk, ng, ds).astype(f32)
    Cc = C_.reshape(Bb, nc, chunk, ng, ds).astype(f32)
    Bh = jnp.repeat(Bc, rep, axis=3)  # (B, nc, Q, nh, ds)
    Ch = jnp.repeat(Cc, rep, axis=3)

    dA = dtc * A.astype(f32)[None, None, None, :]        # (B, nc, Q, nh)
    dA_cum = jnp.cumsum(dA, axis=2)                      # within-chunk
    # ---- intra-chunk (quadratic) term
    L = jnp.exp(_segsum(jnp.moveaxis(dA, 3, 2)))         # (B, nc, nh, Q, Q)
    G = jnp.einsum("bcqhd,bckhd->bchqk", Ch, Bh)         # (B, nc, nh, Q, Q)
    M = G * L
    y_diag = jnp.einsum("bchqk,bckh,bckhp->bcqhp", M, dtc, xc)
    # ---- chunk states
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)  # (B, nc, Q, nh)
    states = jnp.einsum("bcqhd,bcqh,bcqh,bcqhp->bchpd",
                        Bh, dtc, decay_to_end, xc)          # (B, nc, nh, hd, ds)
    # ---- inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])              # (B, nc, nh)
    s0 = (jnp.zeros((Bb, nh, hd, ds), f32) if init_state is None
          else init_state.astype(f32))

    def step(carry, inp):
        st, dec = inp           # st: (B, nh, hd, ds), dec: (B, nh)
        new = carry * dec[:, :, None, None] + st
        return new, carry       # emit state *entering* this chunk

    final_state, prev_states = jax.lax.scan(
        step, s0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (B, nc, nh, hd, ds)
    # ---- contribution of carried-in state
    decay_from_start = jnp.exp(dA_cum)                      # (B, nc, Q, nh)
    y_off = jnp.einsum("bcqhd,bcqh,bchpd->bcqhp",
                       Ch, decay_from_start, prev_states)
    y = (y_diag + y_off).reshape(Bb, S, nh, hd)
    y = y + x.astype(f32) * D.astype(f32)[None, None, :, None]
    return y.astype(x.dtype), final_state.astype(x.dtype)


def ssd_scan_seq(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
                 C_: jax.Array, D: jax.Array, *, chunk: int = 64,
                 init_state: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Memory-honest SSD: ``lax.scan`` over chunks so only ONE chunk's
    quadratic form (nh, Q, Q) is live at a time — the lowering/roofline
    counterpart of the Pallas kernel's sequential-chunk grid (the vectorised
    oracle above materialises all (B, nc, nh, Q, Q) decay tiles at once).
    Numerically identical to ``ssd_scan`` (tested)."""
    Bb, S, nh, hd = x.shape
    ng, ds = B_.shape[2], B_.shape[3]
    rep = nh // ng
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    f32 = jnp.float32

    def to_chunks(a):
        return jnp.moveaxis(a.reshape(Bb, nc, chunk, *a.shape[2:]), 1, 0)

    xs = (to_chunks(x), to_chunks(dt), to_chunks(B_), to_chunks(C_))
    s0 = (jnp.zeros((Bb, nh, hd, ds), f32) if init_state is None
          else init_state.astype(f32))
    Af = A.astype(f32)
    Df = D.astype(f32)

    def step(state, inp):
        xc, dtc, Bc, Cc = inp
        xc = xc.astype(f32)                       # (B, Q, nh, hd)
        dtc = dtc.astype(f32)                     # (B, Q, nh)
        Bh = jnp.repeat(Bc.astype(f32), rep, axis=2)   # (B, Q, nh, ds)
        Ch = jnp.repeat(Cc.astype(f32), rep, axis=2)
        dA = dtc * Af[None, None, :]
        dA_cum = jnp.cumsum(dA, axis=1)
        L = jnp.exp(_segsum(jnp.moveaxis(dA, 2, 1)))       # (B, nh, Q, Q)
        G = jnp.einsum("bqhd,bkhd->bhqk", Ch, Bh)
        y_diag = jnp.einsum("bhqk,bkh,bkhp->bqhp", G * L, dtc, xc)
        y_off = jnp.einsum("bqhd,bqh,bhpd->bqhp",
                           Ch, jnp.exp(dA_cum), state)
        decay_to_end = jnp.exp(dA_cum[:, -1:, :] - dA_cum)
        new_contrib = jnp.einsum("bqhd,bqh,bqh,bqhp->bhpd",
                                 Bh, dtc, decay_to_end, xc)
        chunk_decay = jnp.exp(dA_cum[:, -1, :])
        new_state = state * chunk_decay[:, :, None, None] + new_contrib
        y = y_diag + y_off + xc * Df[None, None, :, None]
        return new_state, y.astype(x.dtype)

    final_state, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, S, nh, hd)
    return y, final_state.astype(x.dtype)


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    A: jax.Array, B_: jax.Array, C_: jax.Array,
                    D: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One-token SSD recurrence. state: (B, nh, hd, ds); x: (B, nh, hd);
    dt: (B, nh); B_/C_: (B, ng, ds)."""
    nh, ng = x.shape[1], B_.shape[1]
    rep = nh // ng
    f32 = jnp.float32
    Bh = jnp.repeat(B_.astype(f32), rep, axis=1)  # (B, nh, ds)
    Ch = jnp.repeat(C_.astype(f32), rep, axis=1)
    dA = jnp.exp(dt.astype(f32) * A.astype(f32)[None, :])   # (B, nh)
    upd = jnp.einsum("bh,bhp,bhd->bhpd", dt.astype(f32), x.astype(f32), Bh)
    new_state = state.astype(f32) * dA[:, :, None, None] + upd
    y = jnp.einsum("bhpd,bhd->bhp", new_state, Ch)
    y = y + x.astype(f32) * D.astype(f32)[None, :, None]
    return y.astype(x.dtype), new_state.astype(state.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)
