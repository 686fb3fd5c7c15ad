"""Selective-scan Pallas TPU kernel (Mamba-1 within-chunk recurrence).

Computes h_t = a_t * h_{t-1} + b_t for a chunk, returning every h_t.

Layout: a/b (B, Q, N, C), h0 (B, N, C), out (B, Q, N, C) where C is the
``d_inner`` channel axis and N the SSM state size.  Channels sit on the
128-wide lane axis and the state on the sublanes: N is small (16 for
falcon-mamba), and as the minor axis it would be padded to 128 lanes,
inflating every VMEM buffer eightfold.

Grid: (B, n_channel_blocks, n_time_blocks).  The time axis is the
sequential one: the running state ``h`` lives in VMEM scratch across time
blocks, and each kernel instance walks its block with ``fori_loop`` — the
recurrence is sequential in time but the (N, C) plane is vector-parallel,
which is the TPU-native shape of this computation (the GPU version's
warp-parallel scan over time does not transfer).  Blocking time as well
as channels bounds each VMEM buffer independently of the chunk length."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_scr, *, block_q: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, _):
        a_t = a_ref[0, t].astype(jnp.float32)      # (N, bc)
        b_t = b_ref[0, t].astype(jnp.float32)
        h = a_t * h_scr[...] + b_t
        h_scr[...] = h
        o_ref[0, t] = h.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, block_q, step, 0)


def selective_scan_bqnc(
    a: jax.Array,                 # (B, Q, N, C)
    b: jax.Array,                 # (B, Q, N, C)
    h0: jax.Array,                # (B, N, C)
    *,
    block_c: int = 512,
    block_q: int = 32,
    interpret: bool = False,
) -> jax.Array:
    B, Q, N, C = a.shape
    block_c = min(block_c, C)
    block_q = min(block_q, Q)
    if C % block_c or Q % block_q:
        raise ValueError(
            f"blocks ({block_q}, {block_c}) must divide (Q, C) = ({Q}, {C})"
        )

    kernel = functools.partial(_kernel, block_q=block_q)
    blk = pl.BlockSpec((1, block_q, N, block_c),
                       lambda b_, c, t: (b_, t, 0, c))
    return pl.pallas_call(
        kernel,
        grid=(B, C // block_c, Q // block_q),
        in_specs=[
            blk,
            blk,
            pl.BlockSpec((1, N, block_c), lambda b_, c, t: (b_, 0, c)),
        ],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((B, Q, N, C), jnp.float32),
        scratch_shapes=[compat.VMEM((N, block_c), jnp.float32)],
        compiler_params=compat.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0)
