"""Flash decode Pallas TPU kernel: one query token vs. a stacked KV cache.

Layout: q (B, Kv, G, D) — the G = H / Kv query heads that share one KV
head —, k/v (L, B, Kv, S, D): the whole head-major cache of a layer
stack, read at ``layer``, a scalar prefetched to SMEM; valid (B, 1, S)
int32, out (B, Kv, G, D).

Grid: (B, Kv, nKV) — the KV axis is the sequential reduction with running
max / denominator in VMEM scratch (split-K style flash decoding).  The
block index maps pick ``(layer, b, h, j, 0)`` of the stack, so a layer's
cache is read where it lies: no caller slices a layer out of the stack or
transposes it.  One kernel instance serves a whole GQA group, so each KV
block is read once per group rather than once per query head, and the
q/out blocks are the full (G, D) plane — aligned to the TPU tiling for
every G.  The validity mask (cache occupancy, ring-buffer slots) rides
along as a blocked input, so arbitrary cache lengths need no recompile.

Decode attention is HBM-bandwidth-bound (read the whole KV cache once per
token); the kernel's job is to keep the reads streaming with zero
intermediate HBM traffic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat

NEG_INF = -1e30


def _kernel(
    layer_ref, q_ref, k_ref, v_ref, valid_ref,
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    n_kv: int,
):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # (G, D)
    k = k_ref[0, 0, 0].astype(jnp.float32)          # (bkv, D)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    valid = valid_ref[0] != 0                        # (1, bkv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                        # (G, bkv)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                              # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kj == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def kv_block(S: int, block_kv: int) -> int:
    """Rows of one KV block: all S when they fit ``block_kv``, else the
    largest multiple of 128 up to ``block_kv`` that divides S (the mask's
    block must be lane-aligned), else all S in one block — padding the
    stack instead would copy every layer's cache."""
    if S <= block_kv:
        return S
    return next((b for b in range(block_kv - block_kv % 128, 0, -128)
                 if S % b == 0), S)


def flash_decode_bhd(
    q: jax.Array,                 # (B, H, D)
    k: jax.Array,                 # (L, B, Kv, S, D)
    v: jax.Array,
    valid: jax.Array,             # (B, S) int8/bool
    layer: jax.Array,             # () int32: the layer of the stack read
    *,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    Kv, S = k.shape[2], k.shape[3]
    G = H // Kv
    scale = 1.0 / math.sqrt(D)
    bkv = kv_block(S, block_kv)
    nkv = S // bkv
    valid = valid.astype(jnp.int32)[:, None, :]

    def kv_map(b, h, j, layer_ref):
        return layer_ref[0], b, h, j, 0

    kernel = functools.partial(_kernel, scale=scale, n_kv=nkv)
    out = pl.pallas_call(
        kernel,
        grid_spec=compat.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Kv, nkv),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, j, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, bkv, D), kv_map),
                pl.BlockSpec((1, 1, 1, bkv, D), kv_map),
                pl.BlockSpec((1, 1, bkv), lambda b, h, j, _: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, _: (b, h, 0, 0)),
            scratch_shapes=[
                compat.VMEM((G, 1), jnp.float32),
                compat.VMEM((G, 1), jnp.float32),
                compat.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, D), q.dtype),
        compiler_params=compat.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.reshape(B, Kv, G, D), k, v, valid)
    return out.reshape(B, H, D)
