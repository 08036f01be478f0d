"""Attention: GQA (+qk_norm, sliding-window, cross) and DeepSeek MLA.

Two execution paths per variant:
  * ``*_prefill`` — full-sequence attention (causal / windowed / cross), used
    for training forward passes and serving prefill. Dispatches to the
    flash-attention op (Pallas on TPU, jnp oracle elsewhere).
  * ``*_decode`` — one new token against a ring-buffer KV cache.

Cache layout (per layer):
  ``{"k": (B, W, Hkv, hd), "v": (B, W, Hkv, hd)}`` with ``W`` the cache
  window (= sliding window for local layers, = max_len for global ones).
  Keys are stored post-RoPE at their absolute positions; slot ``s`` holds
  absolute position ``p_s = pos - ((pos - s) mod W)`` which the decode mask
  reconstructs, so no position tensor needs to be cached.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.layers import (apply_rope, init_rmsnorm, mesh_axis_sizes,
                                 rmsnorm_fwd, truncated_normal, yarn_mscale)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_attn(key, cfg: ArchConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": truncated_normal(k1, (d, h, hd), dtype, s),
        "wk": truncated_normal(k2, (d, kv, hd), dtype, s),
        "wv": truncated_normal(k3, (d, kv, hd), dtype, s),
        "wo": truncated_normal(k4, (h, hd, d), dtype, (h * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def init_mla(key, cfg: ArchConfig, dtype) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    r, dr, dn, dv = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                     cfg.qk_nope_head_dim, cfg.v_head_dim)
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    return {
        "wq": truncated_normal(ks[0], (d, h, dn + dr), dtype, s),
        "w_dkv": truncated_normal(ks[1], (d, r + dr), dtype, s),  # latent + shared rope key
        "w_uk": truncated_normal(ks[2], (r, h, dn), dtype, r ** -0.5),
        "w_uv": truncated_normal(ks[3], (r, h, dv), dtype, r ** -0.5),
        "wo": truncated_normal(ks[4], (h, dv, d), dtype, (h * dv) ** -0.5),
        "kv_norm": init_rmsnorm(r, dtype),
    }


# ---------------------------------------------------------------------------
# GQA prefill / full forward
# ---------------------------------------------------------------------------
def _qkv(p, cfg: ArchConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm_fwd(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_fwd(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    return q, k, v


def attn_prefill(p: dict, cfg: ArchConfig, x: jax.Array, *,
                 window: int = 0, positions: jax.Array | None = None,
                 causal: bool = True) -> jax.Array:
    """x: (B, S, d) -> (B, S, d). window>0 enables sliding-window masking."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _qkv(p, cfg, x, positions)
    out = kops.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attn_prefill(p: dict, cfg: ArchConfig, x: jax.Array,
                       memory_kv: tuple[jax.Array, jax.Array]) -> jax.Array:
    """Decoder cross-attention; memory k/v precomputed from encoder output.
    Softcap is applied here AND in cross_attn_decode — the two paths must
    stay numerically symmetric (decode == teacher-forced forward)."""
    k, v = memory_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    out = kops.flash_attention(q, k, v, causal=False, window=0,
                               softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attn_decode(p: dict, cfg: ArchConfig, x: jax.Array,
                      memory_kv: tuple[jax.Array, jax.Array]) -> jax.Array:
    """Decode-time cross-attention: x is (B, 1, d) — one query token — so
    dispatch to the flash-decode kernel (memory streamed once) instead of
    the prefill kernel's square tiling."""
    k, v = memory_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])[:, 0]
    out = kops.decode_cross_attention(q, k, v,
                                      softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]


def cross_attn_memory(p: dict, cfg: ArchConfig, memory: jax.Array):
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
    return k, v


# ---------------------------------------------------------------------------
# GQA decode with ring-buffer cache
# ---------------------------------------------------------------------------
def init_attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                    window: int = 0) -> dict:
    W = min(window, max_len) if window else max_len
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, W, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, W, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, W, kv), jnp.float32),
            "v_scale": jnp.zeros((batch, W, kv), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, W, kv, hd), dtype),
        "v": jnp.zeros((batch, W, kv, hd), dtype),
    }


def _quant_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) absmax int8 quantisation. x: (..., hd)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _seq_parallel_decode(cfg: ArchConfig, q, k, v, valid,
                         k_scale=None, v_scale=None):
    """Decode attention against a cache whose SEQUENCE dim is sharded over
    "model" (the rule when kv-heads don't divide the model axis). GSPMD
    cannot block-slice a seq-sharded cache, so the locality is asserted
    with shard_map: each model shard runs a partial flash-decode over its
    local KV slice and the (max, normaliser, accumulator) statistics are
    merged with one tiny all-gather — distributed flash-decoding, the
    TPU-native layout of the paper's "split the work" idea at decode time.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ref as kref

    sizes = mesh_axis_sizes()
    msize = sizes.get("model", 1)
    dax = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    dsize = 1
    for a in dax:
        dsize *= sizes[a]
    B, W = valid.shape
    kv = k.shape[2]
    dentry = dax if len(dax) > 1 else (dax[0] if dax else None)
    b_ok = dsize > 1 and B % dsize == 0

    # which axis shards the cache SEQUENCE dim (mirrors the cache rules in
    # launch/sharding.py): "model" when kv-heads don't divide it; the data
    # axes when the batch is idle (long-context, B=1)
    kv_div = msize > 1 and kv % msize == 0
    if msize > 1 and not kv_div and W % msize == 0 and (b_ok or dsize == 1):
        seq_axes: tuple | str = "model"
        bentry, hentry = (dentry if b_ok else None), None
    elif dsize > 1 and not b_ok and W % dsize == 0:
        seq_axes = dentry
        bentry, hentry = None, ("model" if kv_div else None)
    else:
        return kops.decode_attention(q, k, v, valid,
                                     softcap=cfg.attn_logit_softcap,
                                     k_scale=k_scale, v_scale=v_scale)

    use_scales = k_scale is not None

    def kernel(q_l, k_l, v_l, valid_l, ks_l, vs_l):
        acc, m, l = kref.decode_attention_partial(
            q_l, k_l, v_l, valid_l, softcap=cfg.attn_logit_softcap,
            k_scale=ks_l if use_scales else None,
            v_scale=vs_l if use_scales else None)
        # flash-decoding merge: one pmax + two psums of (B, H)-sized stats
        m_tot = jax.lax.pmax(m, seq_axes)
        w = jnp.exp(m - m_tot)
        num = jax.lax.psum(w[..., None] * acc, seq_axes)
        den = jnp.maximum(jax.lax.psum(w * l, seq_axes), 1e-30)
        return (num / den[..., None]).astype(q_l.dtype)

    qspec = P(bentry, hentry)                      # (B, H, K)
    cspec = P(bentry, seq_axes, hentry)            # (B, W, kv, hd)
    vspec = P(bentry, seq_axes)                    # (B, W)
    sspec = P(bentry, seq_axes, hentry)            # (B, W, kv)
    scale_args = ((k_scale, v_scale) if use_scales
                  else (jnp.zeros((B, W, kv), jnp.float32),) * 2)
    return jax.shard_map(
        kernel,
        in_specs=(qspec, cspec, cspec, vspec, sspec, sspec),
        out_specs=qspec)(q, k, v, valid, *scale_args)


def _ring_positions(W: int, pos: jax.Array) -> jax.Array:
    """Absolute position stored in each ring slot after writing at ``pos``.

    pos: (B,) -> (B, W); negative entries were never written.
    """
    slots = jnp.arange(W)[None, :]
    pos = pos[:, None]
    return pos - jnp.mod(pos - slots, W)


def attn_decode(p: dict, cfg: ArchConfig, x: jax.Array, cache: dict,
                pos: jax.Array) -> tuple[jax.Array, dict]:
    """x: (B, 1, d); pos: (B,) int32 — per-sequence position of the new
    token (continuous batching decodes slots at different depths)."""
    B = x.shape[0]
    positions = pos[:, None].astype(jnp.int32)
    q, k, v = _qkv(p, cfg, x, positions)
    bidx = jnp.arange(B)
    if "k_pages" in cache:
        # paged layout: the group's page pools are the whole layer stack
        # (L, P+1, bs, Hkv·hd), carried by the model's layer scan, with
        # this layer's index under "layer" (model._layer_scan). The new
        # token's row is written in place at [layer, page, off] (idle
        # rows point at the scratch page) and the kernel reads the same
        # stack. Only full-horizon layers are paged, so slot == position
        # and validity is simply position < pos+1 — the same mask the
        # dense ring produces when W == max_len.
        layer, table = cache["layer"], cache["table"]
        bs = cache["k_pages"].shape[-2]
        pidx = table[bidx, pos // bs]                        # (B,)
        off = jnp.mod(pos, bs)
        lengths = (pos + 1).astype(jnp.int32)
        if cfg.kv_cache_dtype == "int8":
            kq, ks = _quant_kv(k[:, 0])
            vq, vs = _quant_kv(v[:, 0])
            rows = {"k_pages": kq, "v_pages": vq,
                    "k_scale_pages": ks, "v_scale_pages": vs}
        else:
            rows = {"k_pages": k[:, 0], "v_pages": v[:, 0]}
        new = dict(cache)
        for name, row in rows.items():
            pages = cache[name]
            new[name] = pages.at[layer, pidx, off].set(
                row.reshape((B,) + pages.shape[3:]))
        out = kops.paged_decode_attention(
            q[:, 0], new["k_pages"], new["v_pages"], table, lengths, layer,
            softcap=cfg.attn_logit_softcap,
            k_scale_pages=new.get("k_scale_pages"),
            v_scale_pages=new.get("v_scale_pages"))
        y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
        return y, new
    W = cache["k"].shape[1]
    slot = jnp.mod(pos, W)                                   # (B,)
    valid = _ring_positions(W, pos) >= 0                     # (B, W)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_kv(k[:, 0])
        vq, vs = _quant_kv(v[:, 0])
        ck = cache["k"].at[bidx, slot].set(kq)
        cv = cache["v"].at[bidx, slot].set(vq)
        cks = cache["k_scale"].at[bidx, slot].set(ks)
        cvs = cache["v_scale"].at[bidx, slot].set(vs)
        out = _seq_parallel_decode(cfg, q[:, 0], ck, cv, valid,
                                   k_scale=cks, v_scale=cvs)
        y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
        return y, {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    ck = cache["k"].at[bidx, slot].set(k[:, 0])
    cv = cache["v"].at[bidx, slot].set(v[:, 0])
    out = _seq_parallel_decode(cfg, q[:, 0], ck, cv, valid)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return y, {"k": ck, "v": cv}


def attn_prefill_into_cache(p: dict, cfg: ArchConfig, x: jax.Array,
                            cache: dict, *, window: int = 0) -> tuple[jax.Array, dict]:
    """Run prefill and leave the (last W) keys/values in the ring cache."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _qkv(p, cfg, x, positions)
    out = kops.flash_attention(q, k, v, causal=True, window=window,
                               softcap=cfg.attn_logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    W = cache["k"].shape[1]
    # write positions [max(0, S-W), S) into slots (p % W)
    take = min(W, S)
    src_k, src_v = k[:, S - take:], v[:, S - take:]
    slots = jnp.mod(jnp.arange(S - take, S), W)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_kv(src_k)
        vq, vs = _quant_kv(src_v)
        return y, {"k": cache["k"].at[:, slots].set(kq),
                   "v": cache["v"].at[:, slots].set(vq),
                   "k_scale": cache["k_scale"].at[:, slots].set(ks),
                   "v_scale": cache["v_scale"].at[:, slots].set(vs)}
    ck = cache["k"].at[:, slots].set(src_k)
    cv = cache["v"].at[:, slots].set(src_v)
    return y, {"k": ck, "v": cv}


def attn_suffix_prefill_into_cache(p: dict, cfg: ArchConfig, x: jax.Array,
                                   cache: dict, ctx_k: jax.Array,
                                   ctx_v: jax.Array,
                                   offset: int) -> tuple[jax.Array, dict]:
    """Prefill only the residual suffix behind ``offset`` already-cached
    positions (prefix sharing): queries are the suffix tokens at their
    absolute rope positions, keys/values are [cached prefix, suffix].
    Causal masking right-aligns queries against the key axis, so the
    context width must equal ``offset`` EXACTLY — padding belongs on the
    suffix side only. ``ctx_k``/``ctx_v`` are (B, offset, Hkv·hd) as
    read from the pages. Returns the suffix K/V as the mini-cache (width
    == S: the whole ring is the suffix). Full-horizon rope attention only —
    the engine's sharing gate excludes windows, MLA and int8 caches."""
    B, S, _ = x.shape
    positions = offset + jnp.arange(S)[None, :]
    q, k, v = _qkv(p, cfg, x, positions)
    ck = jnp.concatenate([ctx_k.reshape(k.shape[:1] + (-1,) + k.shape[2:])
                          .astype(k.dtype), k], axis=1)
    cv = jnp.concatenate([ctx_v.reshape(v.shape[:1] + (-1,) + v.shape[2:])
                          .astype(v.dtype), v], axis=1)
    out = kops.flash_attention(q, ck, cv, causal=True, window=0,
                               softcap=cfg.attn_logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": k.astype(cache["k"].dtype),
               "v": v.astype(cache["v"].dtype)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): latent KV cache, absorbed decode
# ---------------------------------------------------------------------------
def mla_softmax_scale(cfg: ArchConfig) -> float:
    """The scores' scale: (nope + rope dims)**-0.5, times the square of
    YaRN's ``mscale_all_dim`` factor where the rope is scaled
    (DeepSeek-V2's ``softmax_scale``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = cfg.rope_scaling
    if s is not None and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def mla_rope(cfg: ArchConfig, x: jax.Array, positions: jax.Array
             ) -> jax.Array:
    """The rotary embedding of MLA's rope dims (YaRN-scaled where the
    config says so). x: (..., seq, heads, qk_rope_head_dim)."""
    return apply_rope(x, positions, cfg.rope_theta,
                      scaling=cfg.rope_scaling)


def mla_latents(p: dict, cfg: ArchConfig, x: jax.Array,
                positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """What the latent cache holds for x: (B, S, d): the normalised
    latent (B, S, kv_lora_rank) and the shared rope key (B, S, dr)."""
    r = cfg.kv_lora_rank
    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv = rmsnorm_fwd(p["kv_norm"], dkv[..., :r], cfg.norm_eps)
    k_rope = mla_rope(cfg, dkv[..., None, r:], positions)[:, :, 0]
    return ckv, k_rope


def mla_prefill(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    B, S, _ = x.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    positions = jnp.arange(S)[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = mla_rope(cfg, q_rope, positions)
    ckv, k_rope = mla_latents(p, cfg, x, positions)

    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, cfg.n_heads, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = kops.flash_attention(q_full, k, v, causal=True, window=0,
                               scale=mla_softmax_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_decode(p: dict, cfg: ArchConfig, x: jax.Array, cache: dict,
               pos: jax.Array) -> tuple[jax.Array, dict]:
    """Absorbed MLA decode: attention runs in the latent space. pos: (B,)."""
    B = x.shape[0]
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    positions = pos[:, None].astype(jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = mla_rope(cfg, q_rope, positions)
    ckv_new, k_rope_new = mla_latents(p, cfg, x, positions)
    # absorb W_uk into q: attention runs in the latent space (the Pallas
    # kernel reads each ckv tile once for score AND context — kernels/
    # mla_decode.py; jnp oracle on CPU)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])[:, 0]  # (B,H,r)
    scale = mla_softmax_scale(cfg)

    bidx = jnp.arange(B)
    if "ckv_pages" in cache:
        # paged latent cache: the group's pools are the whole layer stack
        # (L, P+1, bs, ·), carried by the model's layer scan with this
        # layer's index under "layer" (model._layer_scan). The new latent
        # and rope key are written in place at [layer, page, off]; the
        # rows' pages of this layer are then gathered into a dense
        # (B, nblk·bs, ·) view for the dense MLA context kernel (every
        # row's whole table: the one copy this path makes; masked
        # positions contribute an exact 0.0, so it bit-matches the dense
        # path)
        layer, table = cache["layer"], cache["table"]
        bs = cache["ckv_pages"].shape[-2]
        pidx = table[bidx, pos // bs]
        off = jnp.mod(pos, bs)
        ckv_pages = cache["ckv_pages"].at[layer, pidx, off].set(
            ckv_new[:, 0].astype(cache["ckv_pages"].dtype))
        kr_pages = cache["k_rope_pages"].at[layer, pidx, off].set(
            k_rope_new[:, 0].astype(cache["k_rope_pages"].dtype))
        S = table.shape[1] * bs
        ckv = ckv_pages[layer, table].reshape(B, S, r)
        k_rope = kr_pages[layer, table].reshape(B, S, -1)
        new = {**cache, "ckv_pages": ckv_pages, "k_rope_pages": kr_pages}
    else:
        ckv = cache["ckv"].at[bidx, pos].set(ckv_new[:, 0])
        k_rope = cache["k_rope"].at[bidx, pos].set(k_rope_new[:, 0])
        new = {"ckv": ckv, "k_rope": k_rope}
    S = ckv.shape[1]
    valid = jnp.arange(S)[None, :] <= pos[:, None]           # (B, S)
    ctx_lat = kops.mla_decode_ctx(q_lat, q_rope[:, 0], ckv, k_rope, valid,
                                  scale=scale).astype(ckv.dtype)
    out = jnp.einsum("bhr,rhk->bhk", ctx_lat, p["w_uv"])
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return y, new
