"""Pallas kernel checkers: spec-level invariants, no kernel execution.

Each TPU kernel in ``src/repro/kernels`` is invoked under
``jax.eval_shape`` with ``pl.pallas_call`` monkeypatched to CAPTURE the
grid / BlockSpecs / scratch shapes / operand avals instead of building
the kernel — nothing compiles, nothing runs, and the real jit wrappers
are bypassed (``fn.__wrapped__``) so no fake executable can pollute the
shared jit cache. The captured spec is then checked:

* block-shape divisibility — every BlockSpec dim must divide its
  operand dim (our kernels tile exactly; a non-dividing block means
  silent padding or a runtime error on the accelerator);
* TPU tiling — the last two block dims must be multiples of (8, 128)
  or equal the operand's own dims, the rule the TPU lowering enforces
  (a ``(1, block_k)`` mask block over a ``(B, W)`` mask is refused);
* index-map bounds — each index map is evaluated at every grid corner
  with worst-case scalar-prefetch values (block tables filled with the
  LAST physical page) and must keep ``(idx+1)·block ≤ shape``;
* VMEM budget — double-buffered block tiles plus scratch must fit the
  per-core VMEM (~16 MiB on current TPUs; the guide's figure);
* dtype consistency — scratch accumulators must be f32, and int8 page
  operands must travel with f32 scale operands (the dequant contract).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.report import Finding

VMEM_BYTES = 16 * 1024 * 1024       # per-core VMEM (pallas guide)


@dataclasses.dataclass
class KernelSpec:
    """One captured ``pl.pallas_call`` invocation."""
    name: str
    grid: tuple
    in_specs: list                   # BlockSpec per (non-prefetch) operand
    out_specs: list
    scratch_shapes: list
    num_scalar_prefetch: int
    prefetch_args: list              # avals of the scalar-prefetch operands
    operands: list                   # avals of the blocked operands
    out_shapes: list                 # ShapeDtypeStructs


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


@contextlib.contextmanager
def capture_pallas(sink: list, name: str):
    """Patch ``pl.pallas_call`` to record specs and return zeros of
    ``out_shape`` — valid under ``jax.eval_shape`` tracing."""
    real = pl.pallas_call

    def fake(kernel, out_shape=None, *, grid_spec=None, grid=None,
             in_specs=None, out_specs=None, scratch_shapes=None,
             **kw):
        if grid_spec is not None:
            grid = grid_spec.grid
            in_specs = grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = grid_spec.scratch_shapes
            npf = getattr(grid_spec, "num_scalar_prefetch", 0)
        else:
            npf = 0
        spec = KernelSpec(
            name=name, grid=tuple(grid) if grid else (),
            in_specs=_as_list(in_specs), out_specs=_as_list(out_specs),
            scratch_shapes=_as_list(scratch_shapes),
            num_scalar_prefetch=npf, prefetch_args=[], operands=[],
            out_shapes=jax.tree.leaves(
                out_shape, is_leaf=lambda x: hasattr(x, "shape")))

        def runner(*args):
            avals = [jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))
                     for a in args]
            spec.prefetch_args = avals[:npf]
            spec.operands = avals[npf:]
            sink.append(spec)
            outs = [jnp.zeros(s.shape, s.dtype) for s in spec.out_shapes]
            if isinstance(out_shape, (list, tuple)):
                return outs
            return outs[0]
        return runner

    pl.pallas_call = fake
    try:
        yield
    finally:
        pl.pallas_call = real


def _grid_corners(grid: tuple):
    axes = [(0,) if g <= 1 else (0, g - 1) for g in grid]
    return itertools.product(*axes)


def _worst_case_prefetch(spec: KernelSpec, table_fill: dict[int, int]):
    """Concrete numpy stand-ins for the scalar-prefetch operands, filled
    with the caller-declared worst-case value (e.g. the highest physical
    page index a block table may hold)."""
    out = []
    for i, aval in enumerate(spec.prefetch_args):
        fill = table_fill.get(i, 0)
        out.append(np.full(aval.shape, fill,
                           dtype=aval.dtype if np.issubdtype(
                               np.dtype(aval.dtype), np.integer)
                           else np.int32))
    return out


def check_spec(spec: KernelSpec,
               table_fill: dict[int, int] | None = None,
               int8_scales_expected: bool = False) -> list[Finding]:
    findings: list[Finding] = []
    table_fill = table_fill or {}
    site = f"kernels/{spec.name}"

    pairs = (list(zip(spec.in_specs, spec.operands, itertools.repeat("in")))
             + list(zip(spec.out_specs, spec.out_shapes,
                        itertools.repeat("out"))))
    if len(spec.in_specs) != len(spec.operands):
        findings.append(Finding(
            "kernels", "KRN000", site,
            f"{len(spec.in_specs)} in_specs for {len(spec.operands)} "
            "blocked operands — spec/operand mismatch"))

    # -- divisibility + index-map bounds
    prefetch = _worst_case_prefetch(spec, table_fill)
    for k, (bspec, aval, way) in enumerate(pairs):
        block = tuple(bspec.block_shape)
        shape = tuple(aval.shape)
        if len(block) != len(shape):
            findings.append(Finding(
                "kernels", "KRN001", f"{site}/{way}{k}",
                f"block rank {len(block)} != operand rank {len(shape)} "
                f"({block} vs {shape})"))
            continue
        for d, (b, s) in enumerate(zip(block, shape)):
            if b is None:
                continue
            if b > s or s % b:
                findings.append(Finding(
                    "kernels", "KRN002", f"{site}/{way}{k}",
                    f"block dim {d} = {b} does not tile operand dim "
                    f"{s} exactly ({block} vs {shape})"))
        for d, align in zip(range(len(block) - 2, len(block)), (8, 128)):
            if d < 0:
                continue
            b = shape[d] if block[d] is None else block[d]
            if b != shape[d] and b % align:
                findings.append(Finding(
                    "kernels", "KRN011", f"{site}/{way}{k}",
                    f"block dim {d} = {b} is neither a multiple of {align} "
                    f"nor the operand dim {shape[d]} ({block} vs {shape}) "
                    "— the TPU lowering refuses it"))
        for corner in _grid_corners(spec.grid):
            try:
                idx = bspec.index_map(*corner, *prefetch)
            except Exception as e:   # index map must be total on the grid
                findings.append(Finding(
                    "kernels", "KRN003", f"{site}/{way}{k}",
                    f"index map raised at grid point {corner}: {e!r}"))
                break
            idx = tuple(np.asarray(i).max() for i in jnp.asarray(idx)
                        ) if not isinstance(idx, tuple) else tuple(
                        int(np.asarray(i).max()) for i in idx)
            for d, (i, b, s) in enumerate(zip(idx, block, shape)):
                if b is None:
                    b = 1
                if i < 0 or (i + 1) * b > s:
                    findings.append(Finding(
                        "kernels", "KRN004", f"{site}/{way}{k}",
                        f"index map at grid {corner} selects block {i} "
                        f"on dim {d}: ({i}+1)×{b} > {s} — out of "
                        "bounds under worst-case prefetch values"))
            if len(idx) != len(block):
                findings.append(Finding(
                    "kernels", "KRN005", f"{site}/{way}{k}",
                    f"index map returns {len(idx)} indices for rank-"
                    f"{len(block)} blocks"))

    # -- VMEM budget: double-buffered tiles + scratch
    def block_bytes(bspec, aval):
        n = 1
        for b, s in zip(bspec.block_shape, aval.shape):
            n *= s if b is None else b
        return n * np.dtype(aval.dtype).itemsize

    tile = sum(block_bytes(bs_, av) for bs_, av, _ in pairs
               if len(bs_.block_shape) == len(av.shape))
    scratch = 0
    for sc in spec.scratch_shapes:
        n = 1
        for d in sc.shape:
            n *= d
        scratch += n * np.dtype(sc.dtype).itemsize
        if np.dtype(sc.dtype) != np.float32:
            findings.append(Finding(
                "kernels", "KRN006", site,
                f"scratch accumulator dtype {np.dtype(sc.dtype).name} — "
                "online-softmax / state carries must accumulate in f32"))
    total = 2 * tile + scratch
    if total > VMEM_BYTES:
        findings.append(Finding(
            "kernels", "KRN007", site,
            f"estimated VMEM {total / 2**20:.1f} MiB (2×{tile} tile + "
            f"{scratch} scratch) exceeds the {VMEM_BYTES // 2**20} MiB "
            "per-core budget"))

    # -- int8 dequant contract
    int8_ops = [i for i, av in enumerate(spec.operands)
                if np.dtype(av.dtype) == np.int8]
    if int8_ops:
        # one f32 scale per int8 row: the int8 operand's size over its
        # last (head) dim, in whatever layout the kernel reads it
        q8 = spec.operands[int8_ops[0]].shape
        rows = int(np.prod(q8[:-1]))
        scales = [av for av in spec.operands
                  if np.dtype(av.dtype) == np.float32
                  and int(np.prod(av.shape)) == rows]
        if int8_scales_expected and not scales:
            findings.append(Finding(
                "kernels", "KRN008", site,
                "int8 page operands without matching f32 scale "
                "operands — dequantisation cannot be exact"))
    return findings


# ---------------------------------------------------------------------------
# registry: how to invoke each kernel wrapper with representative shapes
# ---------------------------------------------------------------------------
def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _invoke(name: str, fn: Callable, args: tuple,
            static: dict) -> tuple[list[KernelSpec], list[Finding]]:
    """Trace ``fn`` (unwrapped from jax.jit) under eval_shape with
    pallas_call captured."""
    sink: list[KernelSpec] = []
    inner = getattr(fn, "__wrapped__", fn)
    try:
        with capture_pallas(sink, name):
            jax.eval_shape(functools.partial(inner, **static), *args)
    except Exception as e:
        return sink, [Finding(
            "kernels", "KRN009", f"kernels/{name}",
            f"kernel wrapper failed to trace abstractly: {e!r}")]
    if not sink:
        return sink, [Finding(
            "kernels", "KRN010", f"kernels/{name}",
            "no pallas_call reached — wrapper short-circuited, the "
            "kernel is dead code for these shapes")]
    return sink, []


def run() -> list[Finding]:
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_int8)
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mla_decode import mla_decode_ctx
    from repro.kernels.paged_attention import (paged_decode_attention,
                                               paged_decode_attention_int8)
    from repro.kernels.ssd_scan import ssd_scan

    findings: list[Finding] = []
    P, bs, nblk = 9, 16, 4          # 8 live pages + scratch

    cases: list[tuple[str, Any, tuple, dict, dict, bool]] = [
        # (name, fn, args, static kwargs, table_fill, int8)
        ("flash_attention", flash_attention,
         (_f32(1, 256, 4, 128), _f32(1, 256, 2, 128), _f32(1, 256, 2, 128)),
         dict(causal=True, window=0, softcap=0.0,
              block_q=128, block_k=128, interpret=False), {}, False),
        ("decode_attention", decode_attention,
         (_f32(2, 4, 128), _f32(2, 512, 2, 128), _f32(2, 512, 2, 128),
          jax.ShapeDtypeStruct((2, 512), jnp.bool_)),
         dict(softcap=0.0, block_k=256, interpret=False), {}, False),
        ("decode_attention_int8", decode_attention_int8,
         (_f32(2, 4, 128),
          jax.ShapeDtypeStruct((2, 512, 2, 128), jnp.int8),
          jax.ShapeDtypeStruct((2, 512, 2, 128), jnp.int8),
          jax.ShapeDtypeStruct((2, 512), jnp.bool_),
          _f32(2, 512, 2), _f32(2, 512, 2)),
         dict(softcap=0.0, block_k=256, interpret=False), {}, True),
        ("paged_decode_attention", paged_decode_attention,
         (_f32(2, 4, 128),
          _f32(P, bs, 2, 128), _f32(P, bs, 2, 128),
          _i32(2, nblk), _i32(2)),
         dict(softcap=0.0, interpret=False), {0: P - 1}, False),
        ("paged_decode_attention_int8", paged_decode_attention_int8,
         (_f32(2, 4, 128),
          jax.ShapeDtypeStruct((P, bs, 2, 128), jnp.int8),
          jax.ShapeDtypeStruct((P, bs, 2, 128), jnp.int8),
          _f32(P, bs, 2), _f32(P, bs, 2),
          _i32(2, nblk), _i32(2)),
         dict(softcap=0.0, interpret=False), {0: P - 1}, True),
        ("mla_decode_ctx", mla_decode_ctx,
         (_f32(2, 4, 256), _f32(2, 4, 64), _f32(2, 1024, 256),
          _f32(2, 1024, 64),
          jax.ShapeDtypeStruct((2, 1024), jnp.bool_)),
         dict(scale=0.0625, block_s=512, interpret=False), {}, False),
        ("ssd_scan", ssd_scan,
         (_f32(1, 128, 4, 64), _f32(1, 128, 4), _f32(4),
          _f32(1, 128, 2, 64), _f32(1, 128, 2, 64), _f32(4)),
         dict(chunk=64, interpret=False), {}, False),
    ]
    for name, fn, args, static, fill, int8 in cases:
        specs, errs = _invoke(name, fn, args, static)
        findings += errs
        for spec in specs:
            findings += check_spec(spec, table_fill=fill,
                                   int8_scales_expected=int8)
    return findings
