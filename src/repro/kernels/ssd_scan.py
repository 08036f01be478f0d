"""Pallas TPU kernel for the Mamba2 SSD (state-space duality) chunked scan.

Grid (batch, head, S/Q): the chunk axis is TPU-sequential, so the head's
inter-chunk recurrent state (hd, ds) lives in VMEM scratch and is carried
across chunk iterations — the HBM→VMEM traffic per chunk is exactly one
tile of x/dt/B/C and one tile of y, the minimum possible for this op.

Each grid step is plain 2-D math on one head: three MXU matmuls (G = C·Bᵀ,
y = (G∘L)·(dt·x), the carried-state term) plus the state update. The
wrapper lays every operand out head-major, so each block's last two dims
are a (Q, feature) tile, and dt·A arrives as a (Q, 1) column. The TPU
lowering has no cumsum, so the within-chunk cumulative sum is a masked
reduction over a (Q, Q) tile; a second one, over the diagonal, turns that
row into the column the decay needs, exactly. The skip term D·x is added
by the wrapper.

All decay math runs in fp32; the recurrence is the oracle's in ref.py
(same segsum formulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    f32 = jnp.float32
    x = x_ref[0, 0].astype(f32)               # (Q, hd)
    dt = dt_ref[0, 0].astype(f32)             # (Q, 1)
    da = da_ref[0, 0]                         # (Q, 1) dt·A, f32
    Bm = b_ref[0, 0].astype(f32)              # (Q, ds)
    Cm = c_ref[0, 0].astype(f32)              # (Q, ds)

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ki <= qi
    # inclusive within-chunk cumsum of dt·A as a row, then as a column
    cum_row = jnp.sum(jnp.where(qi <= ki, da, 0.0), axis=0,
                      keepdims=True)                          # (1, Q)
    cum_col = jnp.sum(jnp.where(qi == ki, cum_row, 0.0), axis=1,
                      keepdims=True)                          # (Q, 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    total = jnp.sum(jnp.where(last, cum_row, 0.0), axis=1,
                    keepdims=True)                            # (1, 1)
    # decay L[q, k] = exp(cum[q] - cum[k]) for k <= q
    L = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)

    # intra-chunk quadratic term
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    dtx = x * dt                                              # (Q, hd)
    y_diag = jax.lax.dot_general(G * L, dtx, (((1,), (0,)), ((), ())))

    # carried-in state contribution: y_off[q] = exp(cum[q]) * C_q · state
    state = state_scr[...]                                    # (hd, ds)
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())))
    y_ref[0, 0] = (y_diag + y_off * jnp.exp(cum_col)).astype(y_ref.dtype)

    # state update: decay the whole chunk + within-chunk contributions
    wx = dtx * jnp.exp(total - cum_col)                       # (Q, hd)
    new_contrib = jax.lax.dot_general(wx, Bm, (((0,), (0,)), ((), ())))
    state_scr[...] = state * jnp.exp(total) + new_contrib

    @pl.when(c_idx == pl.num_programs(2) - 1)
    def _emit_state():
        st_ref[0, 0] = state_scr[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
             C_: jax.Array, D: jax.Array, *, chunk: int = 64,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Shapes as in ref.ssd_scan. Returns (y, final_state)."""
    Bb, S, nh, hd = x.shape
    ng, ds = B_.shape[2], B_.shape[3]
    rep = nh // ng
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    grid = (Bb, nh, S // chunk)
    f32 = jnp.float32

    head_major = functools.partial(jnp.moveaxis, source=2, destination=1)
    dA = head_major(dt.astype(f32) * A.astype(f32))          # (Bb, nh, S)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda b, h, c: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda b, h, c: (b, h // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, nh, S, hd), f32),
            jax.ShapeDtypeStruct((Bb, nh, hd, ds), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), f32)],
        interpret=interpret,
    )(head_major(x), head_major(dt)[..., None], dA[..., None],
      head_major(B_), head_major(C_))
    y = jnp.moveaxis(y, 1, 2) + x.astype(f32) * D.astype(f32)[:, None]
    return y.astype(x.dtype), st
