"""Grouped feed-forward over row tiles: the product of a sparse expert
dispatch (``nn/conf/moe.sparse_expert_ffn``), squared-ReLU experts (two
stacks) or gated ones (three).

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

**Gated experts** (three stacks ``wg``, ``wu`` ``[E, D, F]`` and ``wd`` ``[E,
F, D]``, in the hidden width ``D``, no latent):

    out[i] = (silu(x_tiles[i] @ wg[e]) * (x_tiles[i] @ wu[e])) @ wd[e]

The same two implementations. An expert's matrices do not fit the kernel's
on-chip memory whole at a hidden width of thousands (3 x 7680 x 2048 in
bf16 is 94 MB), so the gated kernel (``moe_gated_ffn`` in a device trace)
blocks BOTH axes: a grid of (tiles, chunks of ``F``, 2 x chunks of ``D``).
For one chunk of ``F`` the first ``D`` steps accumulate ``x @ wg`` and ``x @
wu`` over the chunks of ``D`` in float32 scratch, the last of them forms
``silu(g) * u``, and the second ``D`` steps add that chunk's ``h @ wd`` into
the tile's output row, chunk of ``D`` by chunk of ``D``; the tile's input row
and its float32 output row stay resident for the whole tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..util import xla as _xla

__all__ = ["tile_ffn", "tile_ffn_xla", "tile_ffn_pallas", "f_chunk",
           "gated_chunks"]

KERNEL_NAME = "moe_grouped_ffn"
GATED_KERNEL_NAME = "moe_gated_ffn"


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


def gated_chunks(d: int, f: int) -> tuple:
    """``(columns of D, columns of F)`` one grid step of the gated kernel
    holds: the largest divisors that are multiples of 128 and at most 2048
    and 512, so that the three matrices' double-buffered blocks take a
    dozen MB at any width (1920 x 512 at 7680 x 2048); the whole axis
    where none divides."""
    def largest(n, cap):
        best = 0
        for c in range(128, min(n, cap) + 1, 128):
            if n % c == 0:
                best = c
        return best or n
    return largest(d, 2048), largest(f, 512)


def tile_ffn_xla(x_tiles, tile_e, n_tiles, *stacks):
    """The same product as a ``while`` over the live tiles."""
    if len(stacks) == 3:
        return _gated_xla(x_tiles, tile_e, n_tiles, *stacks)
    w1, w2 = stacks

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


def _gated_xla(x_tiles, tile_e, n_tiles, wg, wu, wd):
    def body(i, out):
        e = tile_e[i]
        x = jax.lax.dynamic_index_in_dim(x_tiles, i, keepdims=False)
        g, u, d = (jax.lax.dynamic_index_in_dim(w, e, keepdims=False)
                   .astype(x.dtype) for w in (wg, wu, wd))
        h = (jax.nn.silu(jnp.matmul(x, g, preferred_element_type=jnp.float32))
             * jnp.matmul(x, u, preferred_element_type=jnp.float32))
        y = jnp.matmul(h.astype(x.dtype), d,
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


def _gated_kernel(n_d, dc, tile_e_ref, n_tiles_ref, x_ref, wg_ref, wu_ref,
                  wd_ref, o_ref, g_acc, u_acc, h_buf):
    from jax.experimental import pallas as pl
    i, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (s == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    live = i < n_tiles_ref[0]

    @pl.when(live & (s < n_d))
    def _():                       # x @ wg, x @ wu over this chunk of D
        @pl.when(s == 0)
        def _():
            g_acc[...] = jnp.zeros_like(g_acc)
            u_acc[...] = jnp.zeros_like(u_acc)
        x = x_ref[0, :, pl.ds(pl.multiple_of(s * dc, 128), dc)]
        g_acc[...] += jnp.dot(x, wg_ref[0].astype(x.dtype),
                              preferred_element_type=jnp.float32)
        u_acc[...] += jnp.dot(x, wu_ref[0].astype(x.dtype),
                              preferred_element_type=jnp.float32)

        @pl.when(s == n_d - 1)
        def _():
            h_buf[...] = (jax.nn.silu(g_acc[...]) * u_acc[...]).astype(
                h_buf.dtype)

    @pl.when(live & (s >= n_d))
    def _():                       # this chunk of F into a chunk of the row
        at = pl.ds(pl.multiple_of((s - n_d) * dc, 128), dc)
        o_ref[0, :, at] += jnp.dot(h_buf[...],
                                   wd_ref[0].astype(h_buf.dtype),
                                   preferred_element_type=jnp.float32)


def _gated_pallas(x_tiles, tile_e, n_tiles, wg, wu, wd, *, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tiles, rows, d = x_tiles.shape
    f = wg.shape[2]
    dc, fc = gated_chunks(d, f)
    n_d, n_f = d // dc, f // fc
    # as in the kernel below: a tile past the live ones keeps the blocks
    # the last live tile ended on, so nothing is fetched for it
    last = jnp.maximum(n_tiles - 1, 0)
    tile_e = jnp.where(jnp.arange(tiles) < n_tiles, tile_e,
                       tile_e[last]).astype(jnp.int32)
    n_tiles = jnp.reshape(n_tiles, (1,)).astype(jnp.int32)

    def up_block(i, j, s, te, n):        # while the row goes down: held
        live = i < n[0]
        return (te[i], jnp.where(live, jnp.minimum(s, n_d - 1), n_d - 1),
                jnp.where(live, j, n_f - 1))

    def down_block(i, j, s, te, n):      # while the row goes up: the first
        live = i < n[0]
        return (te[i], jnp.where(live, j, n_f - 1),
                jnp.where(live, jnp.maximum(s - n_d, 0), n_d - 1))

    row = pl.BlockSpec((1, rows, d), lambda i, j, s, te, n: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n_f, 2 * n_d),
        in_specs=[row,
                  pl.BlockSpec((1, dc, fc), up_block),
                  pl.BlockSpec((1, dc, fc), up_block),
                  pl.BlockSpec((1, fc, dc), down_block)],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((rows, fc), jnp.float32),
                        pltpu.VMEM((rows, fc), jnp.float32),
                        pltpu.VMEM((rows, fc), x_tiles.dtype)])
    return pl.pallas_call(
        functools.partial(_gated_kernel, n_d, dc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name=GATED_KERNEL_NAME,
    )(tile_e, n_tiles, x_tiles, wg, wu, wd)


def tile_ffn_pallas(x_tiles, tile_e, n_tiles, *stacks, interpret=False):
    if len(stacks) == 3:
        return _gated_pallas(x_tiles, tile_e, n_tiles, *stacks,
                             interpret=interpret)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    w1, w2 = stacks
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


def tile_ffn(x_tiles, tile_e, n_tiles, *stacks):
    """``[tiles, R, L]`` float32: see the module docstring. ``x_tiles``
    ``[tiles, R, L]`` in the compute dtype, ``tile_e [tiles]`` int32,
    ``n_tiles`` an int32 scalar; ``stacks``: ``(w1, w2)`` of squared-ReLU
    experts or ``(wg, wu, wd)`` of gated ones."""
    mode = _xla.kernel_mode()
    if mode is None:
        return tile_ffn_xla(x_tiles, tile_e, n_tiles, *stacks)
    return tile_ffn_pallas(x_tiles, tile_e, n_tiles, *stacks,
                           interpret=mode == "interpret")
