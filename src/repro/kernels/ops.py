"""Jit'd dispatch wrappers: Pallas on TPU, jnp oracle elsewhere.

The models call these — never the kernels or oracles directly — so the same
model code runs the Pallas path on real TPU hardware and the numerically
identical jnp path on CPU (tests, dry-run lowering). On the CPU,
``REPRO_FORCE_PALLAS=interpret`` exercises the Pallas kernels in interpret
mode from the model layer (slow; used by a couple of integration tests).
On a TPU neither the oracle nor interpret mode is ever taken.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_ref, ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _mode() -> str:
    if jax.default_backend() == "tpu":
        return "tpu"
    if os.environ.get("REPRO_FORCE_PALLAS", "") == "interpret":
        return "interpret"
    return "ref"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0):
    mode = _mode()
    if mode == "ref":
        # flash-structured jnp path: same tiles/memory behaviour as the
        # Pallas kernel (flash_ref docstring) — this is what the dry-run
        # lowers, so the roofline describes the kernel we'd actually run.
        return flash_ref.flash_attention(q, k, v, causal=causal,
                                         window=window, softcap=softcap)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap,
                               interpret=(mode == "interpret"))


def decode_attention(q, k, v, valid, *, softcap: float = 0.0,
                     k_scale=None, v_scale=None):
    """Optional k/v scales mean an int8-quantised cache (dequant per tile —
    the blocked paths keep the dequantised tiles in VMEM/registers)."""
    mode = _mode()
    if mode == "ref":
        return ref.decode_attention_blocked(q, k, v, valid, softcap=softcap,
                                            k_scale=k_scale, v_scale=v_scale)
    if k_scale is not None:  # Pallas int8 kernel: dequant in VMEM
        return _da.decode_attention_int8(q, k, v, valid, k_scale, v_scale,
                                         softcap=softcap,
                                         interpret=(mode == "interpret"))
    return _da.decode_attention(q, k, v, valid, softcap=softcap,
                                interpret=(mode == "interpret"))


def paged_decode_attention(q, k_pages, v_pages, table, lengths, *,
                           softcap: float = 0.0, k_scale_pages=None,
                           v_scale_pages=None):
    """Paged flash-decode: K/V gathered through a per-sequence block table
    over a shared physical page pool. q: (B, H, hd); pages:
    (P, block_size, Hkv, hd); table: (B, nblk) int32; lengths: (B,).
    Optional scale pages mean int8 pages (dequant in VMEM)."""
    mode = _mode()
    if mode == "ref":
        return ref.paged_decode_attention(q, k_pages, v_pages, table,
                                          lengths, softcap=softcap,
                                          k_scale_pages=k_scale_pages,
                                          v_scale_pages=v_scale_pages)
    from repro.kernels import paged_attention as _pa
    if k_scale_pages is not None:
        return _pa.paged_decode_attention_int8(
            q, k_pages, v_pages, k_scale_pages, v_scale_pages, table,
            lengths, softcap=softcap, interpret=(mode == "interpret"))
    return _pa.paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                      softcap=softcap,
                                      interpret=(mode == "interpret"))


def decode_cross_attention(q, k, v, *, softcap: float = 0.0):
    """Single-token cross-attention against a fixed (fully valid) memory,
    routed through the flash-*decode* kernel path: during chunked decode
    the query is one token, so the prefill flash kernel's S×S tiling is
    the wrong shape — the decode kernel streams the memory K/V once per
    query instead. q: (B, H, hd); k/v: (B, S_mem, Hkv, hd)."""
    valid = jnp.ones(k.shape[:2], bool)
    return decode_attention(q, k, v, valid, softcap=softcap)


def ssd_scan(x, dt, A, B_, C_, D, *, chunk: int = 64):
    mode = _mode()
    if mode == "ref":
        return ref.ssd_scan_seq(x, dt, A, B_, C_, D, chunk=chunk)
    return _ssd.ssd_scan(x, dt, A, B_, C_, D, chunk=chunk,
                         interpret=(mode == "interpret"))


def mla_decode_ctx(q_lat, q_rope, ckv, k_rope, valid, *, scale: float):
    mode = _mode()
    if mode == "ref":
        return ref.mla_decode_ctx(q_lat, q_rope, ckv, k_rope, valid,
                                  scale=scale)
    from repro.kernels import mla_decode as _mla
    return _mla.mla_decode_ctx(q_lat, q_rope, ckv, k_rope, valid,
                               scale=scale, interpret=(mode == "interpret"))


def rmsnorm(x, scale, *, eps: float = 1e-6):
    mode = _mode()
    if mode == "ref":
        return ref.rmsnorm(x, scale, eps=eps)
    return _rn.rmsnorm(x, scale, eps=eps, interpret=(mode == "interpret"))
