"""Transformer/SSM block definitions and their init / forward / decode paths.

A "block" = one residual layer. Block kinds:
  * ``attn_mlp``  — pre-norm attention + MLP (dense archs, optional window)
  * ``attn_moe``  — pre-norm attention (GQA or MLA) + MoE
  * ``ssm``       — pre-norm Mamba2
  * ``cross``     — decoder layer with self-attn + cross-attn + MLP (Whisper)
  * ``encoder``   — non-causal attention + MLP (Whisper encoder)

Each kind has matching ``init_*``, ``*_fwd`` (full sequence), ``*_decode``
(one token + cache) and cache-init functions, so model.py can scan stacks of
them uniformly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import cache as cache_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (constrain_batch, init_mlp, init_norm,
                                 mlp_fwd, norm_fwd)


# ---------------------------------------------------------------------------
# attn + mlp (dense)
# ---------------------------------------------------------------------------
def init_attn_mlp(key, cfg: ArchConfig, dtype, use_mla: bool | None = None) -> dict:
    k1, k2 = jax.random.split(key)
    use_mla = cfg.mla if use_mla is None else use_mla
    a = attn.init_mla(k1, cfg, dtype) if use_mla else attn.init_attn(k1, cfg, dtype)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "attn": a,
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype, cfg.mlp_gated),
    }


def _attn_fwd(p, cfg: ArchConfig, xin, *, window: int, causal: bool = True):
    if "w_dkv" in p:  # MLA params
        return attn.mla_prefill(p, cfg, xin)
    return attn.attn_prefill(p, cfg, xin, window=window, causal=causal)


def attn_mlp_fwd(p, cfg: ArchConfig, x, *, window: int = 0,
                 causal: bool = True):
    h = _attn_fwd(p["attn"], cfg, norm_fwd(cfg, p["ln1"], x),
                  window=window, causal=causal)
    x = x + checkpoint_name(h, "attn_out")
    x = x + checkpoint_name(
        mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln2"], x), cfg.act), "mlp_out")
    return constrain_batch(x)


def attn_mlp_prefill(p, cfg: ArchConfig, x, cache, *, window: int = 0):
    xin = norm_fwd(cfg, p["ln1"], x)
    if "w_dkv" in p["attn"]:
        h = attn.mla_prefill(p["attn"], cfg, xin)
        cache = _mla_fill_cache(p["attn"], cfg, xin, cache)
    else:
        h, cache = attn.attn_prefill_into_cache(
            p["attn"], cfg, xin, cache, window=window)
    x = x + h
    x = x + mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln2"], x), cfg.act)
    return constrain_batch(x), cache


def attn_mlp_suffix_prefill(p, cfg: ArchConfig, x, cache, ctx_k, ctx_v,
                            offset: int):
    """Residual-suffix prefill (prefix sharing): attention runs against
    [cached prefix K/V, suffix K/V]. GQA only — the engine's sharing
    gate never routes MLA here."""
    xin = norm_fwd(cfg, p["ln1"], x)
    h, cache = attn.attn_suffix_prefill_into_cache(
        p["attn"], cfg, xin, cache, ctx_k, ctx_v, offset)
    x = x + h
    x = x + mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln2"], x), cfg.act)
    return constrain_batch(x), cache


def attn_mlp_decode(p, cfg: ArchConfig, x, cache, pos):
    xin = norm_fwd(cfg, p["ln1"], x)
    if "w_dkv" in p["attn"]:
        h, cache = attn.mla_decode(p["attn"], cfg, xin, cache, pos)
    else:
        h, cache = attn.attn_decode(p["attn"], cfg, xin, cache, pos)
    x = x + h
    x = x + mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln2"], x), cfg.act)
    return constrain_batch(x), cache


# ---------------------------------------------------------------------------
# attn + moe (Mixtral / DeepSeek)
# ---------------------------------------------------------------------------
def init_attn_moe(key, cfg: ArchConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    a = attn.init_mla(k1, cfg, dtype) if cfg.mla else attn.init_attn(k1, cfg, dtype)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "attn": a,
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "moe": moe_lib.init_moe(k2, cfg, dtype),
    }


def attn_moe_fwd(p, cfg: ArchConfig, x, *, window: int = 0,
                 train: bool = False):
    xin = norm_fwd(cfg, p["ln1"], x)
    if cfg.mla:
        h = attn.mla_prefill(p["attn"], cfg, xin)
    else:
        h = attn.attn_prefill(p["attn"], cfg, xin, window=window)
    x = x + checkpoint_name(h, "attn_out")
    mo, aux = moe_lib.moe_fwd(p["moe"], cfg, norm_fwd(cfg, p["ln2"], x),
                              cfg.act, train=train)
    return constrain_batch(x + checkpoint_name(mo, "moe_out")), aux


def attn_moe_prefill(p, cfg: ArchConfig, x, cache, *, window: int = 0):
    xin = norm_fwd(cfg, p["ln1"], x)
    if cfg.mla:
        # MLA prefill + cache fill: recompute latents for the cache
        h = attn.mla_prefill(p["attn"], cfg, xin)
        cache = _mla_fill_cache(p["attn"], cfg, xin, cache)
    else:
        h, cache = attn.attn_prefill_into_cache(p["attn"], cfg, xin, cache,
                                                window=window)
    x = x + h
    mo, _ = moe_lib.moe_serve(p["moe"], cfg, norm_fwd(cfg, p["ln2"], x),
                              cfg.act)
    return constrain_batch(x + mo), cache


def attn_moe_suffix_prefill(p, cfg: ArchConfig, x, cache, ctx_k, ctx_v,
                            offset: int):
    """Residual-suffix prefill for MoE blocks (non-MLA only — the
    engine's sharing gate excludes latent caches)."""
    xin = norm_fwd(cfg, p["ln1"], x)
    h, cache = attn.attn_suffix_prefill_into_cache(
        p["attn"], cfg, xin, cache, ctx_k, ctx_v, offset)
    x = x + h
    mo, _ = moe_lib.moe_serve(p["moe"], cfg, norm_fwd(cfg, p["ln2"], x),
                              cfg.act)
    return constrain_batch(x + mo), cache


def _mla_fill_cache(pa, cfg: ArchConfig, xin, cache):
    S = xin.shape[1]
    ckv, k_rope = attn.mla_latents(pa, cfg, xin, jnp.arange(S)[None, :])
    L = cache["ckv"].shape[1]
    take = min(L, S)
    new_ckv = cache["ckv"].at[:, :take].set(ckv[:, :take])
    new_kr = cache["k_rope"].at[:, :take].set(k_rope[:, :take])
    return {"ckv": new_ckv, "k_rope": new_kr}


def attn_moe_decode(p, cfg: ArchConfig, x, cache, pos):
    """Returns (x, cache, counts): counts (B, 2) are each row's
    assignments to held experts and those not computed (moe_serve)."""
    xin = norm_fwd(cfg, p["ln1"], x)
    if cfg.mla:
        h, cache = attn.mla_decode(p["attn"], cfg, xin, cache, pos)
    else:
        h, cache = attn.attn_decode(p["attn"], cfg, xin, cache, pos)
    x = x + h
    mo, counts = moe_lib.moe_serve(p["moe"], cfg,
                                   norm_fwd(cfg, p["ln2"], x), cfg.act)
    return constrain_batch(x + mo), cache, counts


# ---------------------------------------------------------------------------
# ssm (Mamba2)
# ---------------------------------------------------------------------------
def init_ssm_block(key, cfg: ArchConfig, dtype) -> dict:
    return {
        "ln": init_norm(cfg, cfg.d_model, dtype),
        "mamba": ssm_lib.init_mamba2(key, cfg, dtype),
    }


def ssm_fwd(p, cfg: ArchConfig, x):
    return constrain_batch(
        x + checkpoint_name(
            ssm_lib.mamba2_fwd(p["mamba"], cfg, norm_fwd(cfg, p["ln"], x)),
            "ssm_out"))


def ssm_prefill(p, cfg: ArchConfig, x):
    """SSM prefill builds its cache from scratch (conv tail + final state)."""
    h, cache = ssm_lib.mamba2_fwd(p["mamba"], cfg, norm_fwd(cfg, p["ln"], x),
                                  return_cache=True)
    return constrain_batch(x + h), cache


def ssm_decode(p, cfg: ArchConfig, x, cache, pos):
    del pos  # SSM state is position-free
    h, cache = ssm_lib.mamba2_decode(p["mamba"], cfg,
                                     norm_fwd(cfg, p["ln"], x), cache)
    return constrain_batch(x + h), cache


# ---------------------------------------------------------------------------
# whisper encoder / decoder layers
# ---------------------------------------------------------------------------
def init_encoder_block(key, cfg: ArchConfig, dtype) -> dict:
    return init_attn_mlp(key, cfg, dtype)


def encoder_fwd(p, cfg: ArchConfig, x):
    return attn_mlp_fwd(p, cfg, x, causal=False)


def init_cross_block(key, cfg: ArchConfig, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "self_attn": attn.init_attn(k1, cfg, dtype),
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "cross_attn": attn.init_attn(k2, cfg, dtype),
        "ln3": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dtype, cfg.mlp_gated),
    }


def cross_fwd(p, cfg: ArchConfig, x, memory):
    x = x + attn.attn_prefill(p["self_attn"], cfg,
                              norm_fwd(cfg, p["ln1"], x))
    mem_kv = attn.cross_attn_memory(p["cross_attn"], cfg, memory)
    x = x + attn.cross_attn_prefill(p["cross_attn"], cfg,
                                    norm_fwd(cfg, p["ln2"], x), mem_kv)
    x = x + mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln3"], x), cfg.act)
    return constrain_batch(x)


def cross_prefill(p, cfg: ArchConfig, x, memory, cache):
    h, self_cache = attn.attn_prefill_into_cache(
        p["self_attn"], cfg, norm_fwd(cfg, p["ln1"], x), cache["self"])
    x = x + h
    mem_kv = attn.cross_attn_memory(p["cross_attn"], cfg, memory)
    x = x + attn.cross_attn_prefill(p["cross_attn"], cfg,
                                    norm_fwd(cfg, p["ln2"], x), mem_kv)
    x = x + mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln3"], x), cfg.act)
    return constrain_batch(x), {"self": self_cache, "mem_k": mem_kv[0],
                                "mem_v": mem_kv[1]}


def cross_decode(p, cfg: ArchConfig, x, cache, pos):
    h, self_cache = attn.attn_decode(p["self_attn"], cfg,
                                     norm_fwd(cfg, p["ln1"], x),
                                     cache["self"], pos)
    x = x + h
    mem_kv = (cache["mem_k"], cache["mem_v"])
    x = x + attn.cross_attn_decode(p["cross_attn"], cfg,
                                   norm_fwd(cfg, p["ln2"], x), mem_kv)
    x = x + mlp_fwd(p["mlp"], norm_fwd(cfg, p["ln3"], x), cfg.act)
    return constrain_batch(x), {"self": self_cache, "mem_k": cache["mem_k"],
                                "mem_v": cache["mem_v"]}


# ---------------------------------------------------------------------------
# cache constructors
# ---------------------------------------------------------------------------
def init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                     dtype, *, window: int = 0,
                     layout: cache_lib.PagedLayout | None = None):
    """``layout`` switches pageable groups to the block/paged cache.
    SSM states, genuinely sliding windows (W < max_len) and cross-attn
    encoder memories have no block-table equivalent and stay dense."""
    if kind == "ssm":
        return ssm_lib.init_mamba2_cache(cfg, batch, dtype)
    if kind == "mla":
        if layout is not None:
            return cache_lib.init_paged_mla_cache(cfg, batch, max_len,
                                                  dtype, layout)
        return attn.init_mla_cache(cfg, batch, max_len, dtype)
    if kind == "cross":
        self_cache = (cache_lib.init_paged_attn_cache(cfg, batch, max_len,
                                                      dtype, layout)
                      if layout is not None
                      else attn.init_attn_cache(cfg, batch, max_len, dtype))
        return {
            "self": self_cache,
            "mem_k": jnp.zeros((batch, cfg.encoder_seq, cfg.n_kv_heads,
                                cfg.head_dim), dtype),
            "mem_v": jnp.zeros((batch, cfg.encoder_seq, cfg.n_kv_heads,
                                cfg.head_dim), dtype),
        }
    if layout is not None and cache_lib.pageable(window, max_len):
        return cache_lib.init_paged_attn_cache(cfg, batch, max_len, dtype,
                                               layout)
    return attn.init_attn_cache(cfg, batch, max_len, dtype, window=window)
