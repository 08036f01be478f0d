"""Paged KV-cache layout: fixed-size blocks + per-sequence block tables.

The dense decode cache gives every sequence a private ``(max_len, ...)``
ring row, so a container's concurrency is hard-capped at ``n_slots`` no
matter how short the requests are. The paged layout (vLLM-style) breaks
the cache into ``block_size``-token physical pages shared by all
sequences; each sequence holds a row of page indices (the block table)
and only pays for the blocks its live prefix actually covers.

Per-layer group shapes (the model stacks layers on top, exactly like the
dense constructors in attention.py):

  attention:  ``{"table": (B, nblk) int32,
                 "k_pages"/"v_pages": (P+1, block_size, Hkv·hd)}``
              (+ ``k_scale_pages``/``v_scale_pages`` (P+1, bs, Hkv) f32
              for an int8 cache)
  MLA:        ``{"table": (B, nblk) int32,
                 "ckv_pages": (P+1, bs, kv_lora_rank),
                 "k_rope_pages": (P+1, bs, qk_rope_head_dim)}``

with ``nblk = max_len // block_size`` and ``P = max_blocks``. Attention
pages are token-major with the kv heads flattened into the lanes: one
kv head's ``hd`` values are a lane block of a page, so the paged decode
kernel reads a stacked ``(L, P+1, bs, Hkv·hd)`` pool where it lies and
the decode step writes a token's row in place (a 5-D ``(…, Hkv, hd)``
page would need a relayout copy of the pool on the TPU's (8, 128)
tiling). Programs that move pages to and from the dense per-request
``(B, W, Hkv, hd)`` side reshape that side, never the pool. Page index
``P`` (the last page) is SCRATCH: unreserved table entries point at it,
so lockstep decode writes for idle/finished rows land there instead of
corrupting live sequences. Attention never reads garbage — validity is
``position < length`` and masked lanes contribute an exact 0.0 (see
kernels/ref.paged_decode_attention), which is what makes paged greedy
decode bit-identical to the dense baseline.

Only caches whose window covers the whole horizon page cleanly: a ring
with ``W < max_len`` wraps, and wrap-eviction has no block-table
equivalent. ``pageable(window, max_len)`` encodes that rule; the model
keeps short-window rings, SSM states and cross-attention memories dense
and pages everything else (see model.init_cache).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static description of a paged cache: ``max_blocks`` physical pages
    of ``block_size`` tokens, shared by every pageable layer group (one
    logical block allocation spans all layers)."""
    block_size: int = 16
    max_blocks: int = 64

    def __post_init__(self):
        if self.block_size < 1 or self.max_blocks < 1:
            raise ValueError("block_size and max_blocks must be >= 1")

    @property
    def scratch_page(self) -> int:
        """Index of the write-sink page for unreserved table entries."""
        return self.max_blocks

    def n_blocks(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache positions."""
        return -(-n_tokens // self.block_size)


def pageable(window: int, max_len: int) -> bool:
    """True when a cache window covers the whole horizon, i.e. the ring
    never wraps (slot == position) and the layer pages bit-exactly. A
    genuinely sliding window (W < max_len) stays on the dense ring."""
    return window == 0 or window >= max_len


def init_paged_attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                          layout: PagedLayout) -> dict:
    """Paged counterpart of attention.init_attn_cache (full-window only)."""
    if max_len % layout.block_size:
        raise ValueError(f"max_len={max_len} must be a multiple of "
                         f"block_size={layout.block_size}")
    bs, P = layout.block_size, layout.max_blocks
    nblk = max_len // bs
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    table = jnp.full((batch, nblk), layout.scratch_page, jnp.int32)
    if cfg.kv_cache_dtype == "int8":
        return {
            "table": table,
            "k_pages": jnp.zeros((P + 1, bs, kv * hd), jnp.int8),
            "v_pages": jnp.zeros((P + 1, bs, kv * hd), jnp.int8),
            "k_scale_pages": jnp.zeros((P + 1, bs, kv), jnp.float32),
            "v_scale_pages": jnp.zeros((P + 1, bs, kv), jnp.float32),
        }
    return {
        "table": table,
        "k_pages": jnp.zeros((P + 1, bs, kv * hd), dtype),
        "v_pages": jnp.zeros((P + 1, bs, kv * hd), dtype),
    }


def init_paged_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                         layout: PagedLayout) -> dict:
    """Paged counterpart of attention.init_mla_cache (latent + rope-key
    pages; the decode path gathers pages and reuses kops.mla_decode_ctx)."""
    if max_len % layout.block_size:
        raise ValueError(f"max_len={max_len} must be a multiple of "
                         f"block_size={layout.block_size}")
    bs, P = layout.block_size, layout.max_blocks
    nblk = max_len // bs
    return {
        "table": jnp.full((batch, nblk), layout.scratch_page, jnp.int32),
        "ckv_pages": jnp.zeros((P + 1, bs, cfg.kv_lora_rank), dtype),
        "k_rope_pages": jnp.zeros((P + 1, bs, cfg.qk_rope_head_dim), dtype),
    }


def is_paged_group(cache: dict) -> bool:
    """A per-layer cache dict produced by one of the paged constructors."""
    return "k_pages" in cache or "ckv_pages" in cache
