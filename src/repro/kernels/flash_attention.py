"""Flash attention (prefill) Pallas TPU kernel.

Layout: q (B, H, Sq, D), k/v (B, Kv, Skv, D), out (B, H, Sq, D).

Grid: (B, Kv, nQ, nKV) with dimension semantics (parallel, parallel,
parallel, arbitrary) — the trailing KV axis is the sequential reduction:
running max ``m``, denominator ``l`` and the fp32 accumulator live in VMEM
scratch across KV iterations; the output block is written on the last one.

One program serves a whole GQA group: the q and out blocks are
``(1, G, bq, D)``, the G = H / Kv query heads that share one KV head,
folded in VMEM into ``G·bq`` rows, so each K/V tile is fetched once per
group rather than once per query head.  Row ``r`` is query position
``q_start + r % bq`` of head ``r // bq``.  The tiles are chosen from the
shapes by :func:`attention_tiles`.

Causal / sliding-window block skipping happens at *block* granularity:
the KV ``index_map`` clamps each step into the q block's live range
(:func:`live_kv_blocks`), so a step above the diagonal or below the window
names the tile already resident and Pallas copies nothing, and ``pl.when``
skips its MXU work.  Only blocks that straddle the diagonal, the window
edge or the KV padding build the element mask.

QKᵀ and PV take their operands in the input dtype (bf16 products are
exact in f32) and accumulate in f32; ``p`` is cast to v's dtype before PV.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat

NEG_INF = -1e30

#: lanes of the running max / denominator scratch (lane-dense rows)
_LANES = 128
#: the folded q rows (G·bq) one program aims for
_ROWS = 2048
#: scoped VMEM the kernel asks for: the v5e's default 16 MiB is too little
#: for a 2048 x 1024 f32 score tile and its temporaries
VMEM_LIMIT_BYTES = 48 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def attention_tiles(Sq: int, Skv: int, G: int, D: int) -> Tuple[int, int]:
    """(bq, bkv) for a call: KV tiles of up to 1024 positions; q tiles of
    up to 256 positions, a multiple of 16, halved while the G·bq folded
    rows exceed ``_ROWS`` (MQA and other wide groups get short q tiles) or
    the tiles overflow ``VMEM_LIMIT_BYTES``."""
    bkv = min(1024, _round_up(Skv, 128))
    bq = 256
    while bq > 16 and (G * bq > _ROWS
                       or vmem_bytes(G * bq, bkv, D, 4) > VMEM_LIMIT_BYTES):
        bq //= 2
    return min(bq, _round_up(Sq, 16)), bkv


def vmem_bytes(rows: int, bkv: int, D: int, itemsize: int) -> int:
    """VMEM one program needs: double-buffered q, out, k and v blocks; the
    m, l and accumulator scratch; the f32 score tile, its exponentials
    and their cast for PV."""
    blocks = 2 * (2 * rows * D + 2 * bkv * D) * itemsize
    scratch = (2 * rows * _LANES + rows * D) * 4
    temps = rows * bkv * (4 + 4 + itemsize)
    return blocks + scratch + temps


def live_kv_blocks(
    i, *, block_q: int, block_kv: int, n_kv: int, causal: bool,
    window: Optional[int], prefix_len: int,
):
    """First and last KV block that q block ``i`` sees, inclusive.

    Above the last: past the diagonal and past the prefix-LM zone, which
    the causal mask leaves open.  Below the first: every key further back
    than the sliding window from every row (the window masks the prefix
    zone too).  Works on ints and on traced grid indices alike."""
    q_first = i * block_q
    lo = 0
    if window is not None:
        lo = jnp.maximum(q_first - window + 1, 0) // block_kv
    hi = n_kv - 1
    if causal:
        top = (q_first + block_q - 1) // block_kv
        if prefix_len > 0:
            top = jnp.maximum(top, -(-prefix_len // block_kv) - 1)
        hi = jnp.minimum(top, hi)
    return lo, hi


def kv_block_index(i, j, **tiling):
    """The KV tile step ``(i, j)`` reads: ``j`` clamped into q block ``i``'s
    live range, so a dead step names the tile of its live neighbour."""
    lo, hi = live_kv_blocks(i, **tiling)
    return jnp.minimum(jnp.maximum(j, lo), hi)


def block_needs_mask(
    i, j, *, block_q: int, block_kv: int, causal: bool,
    window: Optional[int], prefix_len: int, seq_kv: int,
):
    """Whether any element of block ``(i, j)`` is masked: the block
    straddles the diagonal (outside the prefix-LM zone), the window's
    far edge, or the KV padding.  Every other live block is whole."""
    q_first, k_first = i * block_q, j * block_kv
    k_last = k_first + block_kv - 1
    edge = k_last >= seq_kv
    if causal:
        diag = k_last > q_first
        if prefix_len > 0:
            diag = jnp.logical_and(diag, k_last >= prefix_len)
        edge = jnp.logical_or(edge, diag)
    if window is not None:
        edge = jnp.logical_or(
            edge, q_first + block_q - 1 - k_first >= window
        )
    return edge


def _kernel(
    q_ref, k_ref, v_ref,             # VMEM blocks
    o_ref,                            # output block
    m_scr, l_scr, acc_scr,            # scratch (VMEM)
    *,
    scale: float,
    groups: int,
    block_q: int,
    block_kv: int,
    n_kv: int,
    causal: bool,
    window: Optional[int],
    prefix_len: int,
    seq_kv: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    rows = groups * block_q

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_kv
    tiling = dict(block_q=block_q, block_kv=block_kv, causal=causal,
                  window=window, prefix_len=prefix_len)
    lo, hi = live_kv_blocks(qi, n_kv=n_kv, **tiling)
    live = jnp.logical_and(kj >= lo, kj <= hi)
    edge = block_needs_mask(qi, kj, seq_kv=seq_kv, **tiling)

    def step(masked: bool):
        q = q_ref[0].reshape(rows, q_ref.shape[-1])    # (G·bq, d)
        k = k_ref[0, 0]                                # (bkv, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # (G·bq, bkv)
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            mask = k_pos < seq_kv
            if causal:
                c = q_pos >= k_pos
                if prefix_len > 0:
                    c = jnp.logical_or(c, k_pos < prefix_len)
                mask = jnp.logical_and(mask, c)
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
            s = jnp.where(
                mask[None], s.reshape(groups, block_q, block_kv), NEG_INF
            ).reshape(rows, block_kv)

        m_prev = m_scr[...]                            # (G·bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(live, edge))
    def _edge():
        step(masked=True)

    @pl.when(jnp.logical_and(live, jnp.logical_not(edge)))
    def _interior():
        step(masked=False)

    @pl.when(kj == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, :1], 1e-20)
        o_ref[0] = (acc_scr[...] / l).reshape(o_ref.shape[1:]).astype(
            o_ref.dtype
        )


def flash_attention_bhsd(
    q: jax.Array,                     # (B, H, Sq, D)
    k: jax.Array,                     # (B, Kv, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """``block_q`` / ``block_kv`` override :func:`attention_tiles`."""
    B, H, Sq, D = q.shape
    Kv, Skv = k.shape[1], k.shape[2]
    G = H // Kv
    scale = 1.0 / math.sqrt(D)

    bq, bkv = attention_tiles(Sq, Skv, G, D)
    block_q = min(block_q, _round_up(Sq, 16)) if block_q else bq
    block_kv = min(block_kv, Skv) if block_kv else bkv
    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nkv = (Skv + pad_kv) // block_kv

    tiling = dict(block_q=block_q, block_kv=block_kv, n_kv=nkv,
                  causal=causal, window=window, prefix_len=prefix_len)
    kernel = functools.partial(
        _kernel, scale=scale, groups=G, seq_kv=Skv, **tiling
    )

    def kv_map(b, h, i, j):
        return b, h, kv_block_index(i, j, **tiling), 0

    out = pl.pallas_call(
        kernel,
        grid=(B, Kv, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, G, block_q, D), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            compat.VMEM((G * block_q, _LANES), jnp.float32),
            compat.VMEM((G * block_q, _LANES), jnp.float32),
            compat.VMEM((G * block_q, D), jnp.float32),
        ],
        compiler_params=compat.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
