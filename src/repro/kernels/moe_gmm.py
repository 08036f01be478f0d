"""Pallas TPU grouped matmul: the SwiGLU experts a chip holds, over
their assignments sorted into row tiles.

``xs`` holds the assignments to the held experts, sorted by expert, each
expert's rows padded to a whole number of ``tm``-row tiles; tile ``t``
belongs to expert ``tile_expert[t]`` and only the first ``n_tiles`` tiles
are in use. Grid ``(tiles, d_ff / tf)``: each step reads one tile's rows
and a ``tf``-wide slice of its expert's gate, up and down weights, and
adds ``silu(x·Wg)·(x·Wu)·Wd`` for that slice into a float32 accumulator,
so a tile's output is finished at its last ``d_ff`` slice. The weights
are the whole layer stack ``(L, E, …)``, read at ``layer`` where they
lie: a layer's slab sliced out of the stack would be copied before the
call. Tiles past ``n_tiles`` repeat the last tile in use's block indices,
so they fetch nothing, and compute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(te_ref, nt_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref,
                o_ref, acc_scr, *, nf: int, act: str):
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < nt_ref[0])
    def _tile():
        @pl.when(f == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        actfn = jax.nn.silu if act == "silu" else jax.nn.gelu
        h = (actfn(g) * u).astype(x.dtype)
        acc_scr[...] += jnp.dot(h, wd_ref[0, 0],
                                preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _finish():
            o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def moe_expert_gmm(xs: jax.Array, tile_expert: jax.Array,
                   n_tiles: jax.Array, layer: jax.Array | int,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   *, act: str = "silu",
                   interpret: bool = False) -> jax.Array:
    """xs: (tiles·tm, d); tile_expert: (tiles,) int32; n_tiles: int32
    scalar; layer: int32 scalar; w_gate/w_up: (L, E, d, ff); w_down:
    (L, E, ff, d) -> (tiles·tm, d). Rows of tiles past ``n_tiles`` are
    left unwritten."""
    M, d = xs.shape
    n_tile = tile_expert.shape[0]
    tm = M // n_tile
    ff = w_gate.shape[-1]
    tf = 128 if ff % 128 == 0 else ff
    nf = ff // tf

    def tile(t, nt):
        return jnp.maximum(jnp.minimum(t, nt[0] - 1), 0)

    def ff_block(t, f, nt):
        return jnp.where(t < nt[0], f, nf - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # tile_expert, n_tiles, layer
        grid=(n_tile, nf),
        in_specs=[
            pl.BlockSpec((tm, d), lambda t, f, te, nt, ln: (tile(t, nt), 0)),
            pl.BlockSpec((1, 1, d, tf), lambda t, f, te, nt, ln: (
                ln[0], te[tile(t, nt)], 0, ff_block(t, f, nt))),
            pl.BlockSpec((1, 1, d, tf), lambda t, f, te, nt, ln: (
                ln[0], te[tile(t, nt)], 0, ff_block(t, f, nt))),
            pl.BlockSpec((1, 1, tf, d), lambda t, f, te, nt, ln: (
                ln[0], te[tile(t, nt)], ff_block(t, f, nt), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d),
                               lambda t, f, te, nt, ln: (tile(t, nt), 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nf=nf, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), jnp.asarray(n_tiles, jnp.int32)
      .reshape(1), jnp.asarray(layer, jnp.int32).reshape(1), xs, w_gate,
      w_up, w_down)
