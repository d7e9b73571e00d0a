"""Grouped squared-ReLU feed-forward over row tiles: the product of a
sparse expert dispatch (``nn/conf/moe.sparse_expert_ffn``).

The (token, expert) pairs routed to the experts a layer holds are sorted by
expert and cut into TILES of ``R`` rows, one expert a tile; tile ``i`` is
``x_tiles[i]`` (``[R, L]``, rows past the tile's count zero) and belongs to
expert ``tile_e[i]``. For each of the first ``n_tiles`` tiles

    out[i] = relu(x_tiles[i] @ w1[tile_e[i]])^2 @ w2[tile_e[i]]

with ``w1 [E, L, F]`` and ``w2 [E, F, L]`` the held experts' stacked
matrices; the tiles after ``n_tiles`` (the arrays are sized for the worst
routing) are not computed and read as zeros. Work and weight traffic grow
with the tiles the routing needs, not with the experts held: an expert
nobody chose is never read.

Two implementations of the same numbers, chosen by
``util.xla.kernel_mode()`` where the program is traced:

- **the Pallas kernel** (on the TPU; interpreted where a test asks for
  it): a grid of (tiles, chunks of ``F``) with ``tile_e`` and ``n_tiles``
  as scalar prefetch, so that a tile's blocks of ``w1`` and ``w2`` are
  DMA'd straight from the stacks by their expert's index; both products
  accumulate in float32, the second over the chunks of ``F``. A tile past
  ``n_tiles`` names the block the last live tile ended on, so nothing is
  fetched for it. Why a kernel: XLA materialises ``w1[e]`` and ``w2[e]``
  (a dynamic slice cannot be fused into the product's operand), which
  writes and reads again every expert matrix a tile touches: three times
  the weight traffic of a step that is bound by it (TPU compiler, PR 33).
- **plain XLA** (off the TPU): a ``while`` over the live tiles with the
  dynamic slices; the reference of the kernel's tests.

The kernel's name in a device trace is ``moe_grouped_ffn``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..util import xla as _xla

__all__ = ["tile_ffn", "tile_ffn_xla", "tile_ffn_pallas", "f_chunk"]

KERNEL_NAME = "moe_grouped_ffn"


def f_chunk(f: int) -> int:
    """Columns of ``w1`` (rows of ``w2``) one grid step holds: the largest
    divisor of ``f`` that is a multiple of 128 and at most 1024, so that
    the double-buffered blocks of both matrices fit the kernel's on-chip
    memory at a latent width of 1024; all of ``f`` where none divides."""
    best = 0
    for c in range(128, min(f, 1024) + 1, 128):
        if f % c == 0:
            best = c
    return best or f


def tile_ffn_xla(x_tiles, tile_e, n_tiles, w1, w2):
    """The same product as a ``while`` over the live tiles."""
    def body(i, out):
        e = tile_e[i]
        x = jax.lax.dynamic_index_in_dim(x_tiles, i, keepdims=False)
        w1_e = jax.lax.dynamic_index_in_dim(w1, e, keepdims=False)
        w2_e = jax.lax.dynamic_index_in_dim(w2, e, keepdims=False)
        h = jnp.matmul(x, w1_e.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        h = jnp.square(jnp.maximum(h, 0.0)).astype(x.dtype)
        y = jnp.matmul(h, w2_e.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_index_in_dim(out, y, i, axis=0)

    return jax.lax.fori_loop(
        jnp.int32(0), n_tiles.astype(jnp.int32), body,
        jnp.zeros(x_tiles.shape, jnp.float32))


def _kernel(tile_e_ref, n_tiles_ref, x_ref, w1_ref, w2_ref, o_ref):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_tiles_ref[0])
    def _():
        x = x_ref[0]
        h = jnp.dot(x, w1_ref[0].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        h = jnp.square(jnp.maximum(h, 0.0)).astype(x.dtype)
        o_ref[0] += jnp.dot(h, w2_ref[0].astype(x.dtype),
                            preferred_element_type=jnp.float32)


def tile_ffn_pallas(x_tiles, tile_e, n_tiles, w1, w2, *, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tiles, rows, l = x_tiles.shape
    f = w1.shape[2]
    fc = f_chunk(f)
    n_f = f // fc
    # a tile past the live ones keeps the expert AND the chunk the last
    # live tile ended on: its blocks' indices do not change, so the
    # pipeline fetches nothing for it
    last = jnp.maximum(n_tiles - 1, 0)
    tile_e = jnp.where(jnp.arange(tiles) < n_tiles, tile_e,
                       tile_e[last]).astype(jnp.int32)
    n_tiles = jnp.reshape(n_tiles, (1,)).astype(jnp.int32)

    def chunk(i, j, n):
        return jnp.where(i < n[0], j, n_f - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n_f),
        in_specs=[
            pl.BlockSpec((1, rows, l), lambda i, j, te, n: (i, 0, 0)),
            pl.BlockSpec((1, l, fc),
                         lambda i, j, te, n: (te[i], 0, chunk(i, j, n))),
            pl.BlockSpec((1, fc, l),
                         lambda i, j, te, n: (te[i], chunk(i, j, n), 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, l), lambda i, j, te, n: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, rows, l), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tile_e, n_tiles, x_tiles, w1, w2)


def tile_ffn(x_tiles, tile_e, n_tiles, w1, w2):
    """``[tiles, R, L]`` float32: see the module docstring. ``x_tiles``
    ``[tiles, R, L]`` in the compute dtype, ``tile_e [tiles]`` int32,
    ``n_tiles`` an int32 scalar."""
    mode = _xla.kernel_mode()
    if mode is None:
        return tile_ffn_xla(x_tiles, tile_e, n_tiles, w1, w2)
    return tile_ffn_pallas(x_tiles, tile_e, n_tiles, w1, w2,
                           interpret=mode == "interpret")
