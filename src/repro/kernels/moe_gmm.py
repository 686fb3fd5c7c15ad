"""MoE grouped matmul Pallas TPU kernel over ragged groups.

Computes ``y[r] = x[r] @ w[g(r)]`` for rows ``x (M, D)`` sorted by group
(expert) and padded per group to the row tile ``block_m``: group ``g``
owns ``group_sizes[g]`` consecutive rows, a multiple of ``block_m``, so
each row tile belongs to one group.  ``w`` is ``(G, D, F)``.

Grid ``(F / bf, M / block_m)``.  A tile→group table and the number of
live tiles are scalar-prefetched: each tile's weight block is the block
of its group, so consecutive tiles of one group reuse the resident
weights and each group's weights stream from HBM once per F block.
Tiles past the live ones name the last live tile's blocks, so Pallas
copies nothing for them, and ``pl.when`` skips their MXU work; their
output rows are left unwritten.  No ``(E, C, D)`` buffer and no padded
copy of the weights is built: the F block is all of F, or a multiple of
128 dividing it.

Operands go to the MXU in their dtype (bf16 in serving) with f32
accumulation over the whole of D in one product.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat

#: scoped VMEM the kernel asks for (double-buffered x, w and out blocks
#: and the f32 product at a 512-row tile and a 4 MiB weight block)
VMEM_LIMIT_BYTES = 48 << 20
#: bytes of one weight block, above which F is split
_W_BLOCK_BYTES = 8 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def row_tile(rows_per_group: float) -> int:
    """Row tile for an expected ``rows_per_group``: a multiple of 16 (the
    bf16 sublane tile) from 16 to 512."""
    return min(512, max(16, _round_up(math.ceil(rows_per_group), 16)))


def padded_rows(n_rows: int, groups: int, block_m: int) -> int:
    """Rows of the padded layout that holds ``n_rows`` sorted rows in any
    split over ``groups`` groups: each group adds at most ``block_m - 1``
    padding rows."""
    return _round_up(n_rows + groups * (block_m - 1), block_m)


def f_block(D: int, F: int, itemsize: int) -> int:
    """All of F while a ``(D, F)`` weight block fits ``_W_BLOCK_BYTES``,
    else the widest multiple of 128 dividing F that does."""
    if D * F * itemsize <= _W_BLOCK_BYTES or F % 128:
        return F
    bf = F
    while bf > 128 and (F % bf or D * bf * itemsize > _W_BLOCK_BYTES):
        bf -= 128
    return bf


def tile_groups(group_sizes: jax.Array, n_tiles: int, block_m: int):
    """(group of each of ``n_tiles`` row tiles, live tiles (1,)) for
    padded ``group_sizes``; tiles past the live ones take the last
    group."""
    ends = jnp.cumsum(group_sizes)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_m
    group = jnp.searchsorted(ends, starts, side="right").astype(jnp.int32)
    group = jnp.minimum(group, group_sizes.shape[0] - 1)
    live = (ends[-1] // block_m).astype(jnp.int32)
    return group, live[None]


def _kernel(group_ref, live_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < live_ref[0])
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def gmm(
    x: jax.Array,                 # (M, D) rows sorted and padded by group
    w: jax.Array,                 # (G, D, F)
    group_sizes: jax.Array,       # (G,) int32, multiples of block_m
    *,
    block_m: int,
    interpret: bool = False,
) -> jax.Array:
    M, D = x.shape
    F = w.shape[2]
    if M % block_m:
        raise ValueError(f"{M} rows are not whole tiles of {block_m}")
    n_tiles = M // block_m
    bf = f_block(D, F, w.dtype.itemsize)
    group, live = tile_groups(group_sizes.astype(jnp.int32), n_tiles,
                              block_m)

    def tile(t, live_ref):
        return jnp.minimum(t, jnp.maximum(live_ref[0] - 1, 0))

    def x_map(f, t, group_ref, live_ref):
        return tile(t, live_ref), 0

    def w_map(f, t, group_ref, live_ref):
        return group_ref[tile(t, live_ref)], 0, f

    def o_map(f, t, group_ref, live_ref):
        return tile(t, live_ref), f

    return pl.pallas_call(
        _kernel,
        grid_spec=compat.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(F // bf, n_tiles),
            in_specs=[
                pl.BlockSpec((block_m, D), x_map),
                pl.BlockSpec((1, D, bf), w_map),
            ],
            out_specs=pl.BlockSpec((block_m, bf), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((M, F), x.dtype),
        compiler_params=compat.compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(group, live, x, w)
