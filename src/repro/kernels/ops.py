"""jit'd wrappers over the Pallas kernels, in model layouts.

Every wrapper runs the compiled kernel unless the caller passes
``interpret=True`` (the kernel body then executes in Python, which is how
the CPU tests validate the kernels).  Nothing here looks at the backend:
a compiled kernel off-TPU fails loudly instead of silently running the
interpreter, and the choice never depends on what a trace happened to
see.  Model code calls these through ``impl="pallas"``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.flash_decode import flash_decode_bhd
from repro.kernels import moe_gmm as _gmm
from repro.kernels.selective_scan import selective_scan_bqnc


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix_len", "interpret"),
)
def flash_attention(
    q: jax.Array,                 # model layout (B, S, H, D)
    k: jax.Array,                 # (B, S, Kv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Tiles are chosen from the shapes (``attention_tiles``)."""
    out = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        prefix_len=prefix_len,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("block_kv", "interpret")
)
def flash_decode(
    q: jax.Array,                 # (B, 1, H, D) model layout
    k_cache: jax.Array,           # (L, B, Kv, S, D) a stack's whole cache
    v_cache: jax.Array,
    layer: jax.Array,             # () int32: the stack's layer to read
    *,
    kv_valid: jax.Array,          # (B, S)
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """One query token of ``layer`` against that layer's cache, read in
    place from the head-major stack the model keeps."""
    out = flash_decode_bhd(
        q[:, 0], k_cache, v_cache, kv_valid, layer,
        block_kv=block_kv, interpret=interpret,
    )
    return out[:, None]


#: bytes of one selective-scan VMEM block: three blocked arrays, double
#: buffered, stay far inside the 16 MiB of scoped VMEM a v5e kernel gets
_SCAN_BLOCK_BYTES = 1 << 20


def _scan_blocks(Q: int, N: int, C: int) -> tuple:
    """(block_q, block_c) for the selective scan: the channel block is the
    largest multiple of 128 up to 512 dividing C (else all of C, which the
    tiling also accepts); the time block is the longest divisor of Q whose
    f32 block fits ``_SCAN_BLOCK_BYTES``."""
    bc = next((c for c in (512, 384, 256, 128) if C % c == 0), C)
    bq = next(
        q for q in range(Q, 0, -1)
        if Q % q == 0 and (q * N * bc * 4 <= _SCAN_BLOCK_BYTES or q == 1)
    )
    return bq, bc


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(
    a: jax.Array,                 # (B, Q, C, N) model layout
    b: jax.Array,
    h0: jax.Array,                # (B, C, N)
    *,
    interpret: bool = False,
) -> jax.Array:
    B, Q, C, N = a.shape
    block_q, block_c = _scan_blocks(Q, N, C)
    out = selective_scan_bqnc(
        a.transpose(0, 1, 3, 2),
        b.transpose(0, 1, 3, 2),
        h0.transpose(0, 2, 1),
        block_c=block_c,
        block_q=block_q,
        interpret=interpret,
    )
    return out.transpose(0, 1, 3, 2)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def moe_gmm(
    x: jax.Array,                 # (M, D) rows sorted, padded per group
    w: jax.Array,                 # (G, D, F)
    group_sizes: jax.Array,       # (G,) int32, multiples of block_m
    *,
    block_m: int,
    interpret: bool = False,
) -> jax.Array:
    """Ragged grouped matmul (``kernels/moe_gmm.py``); rows past the
    groups' are left unwritten."""
    return _gmm.gmm(x, w, group_sizes, block_m=block_m, interpret=interpret)


def moe_ffn(
    xe: jax.Array,                # (E, C, D)
    wi: jax.Array,                # (E, D, F)
    wg: Optional[jax.Array],
    wo: jax.Array,                # (E, F, D)
    *,
    act: str = "silu",
    interpret: bool = False,
) -> jax.Array:
    """Full expert FFN of the capacity layout via the grouped-matmul
    kernel: each expert's C rows are one group, padded to the row tile."""
    E, C, D = xe.shape
    bm = _gmm.row_tile(C)
    Cp = -(-C // bm) * bm
    x = jnp.pad(xe, ((0, 0), (0, Cp - C), (0, 0))).reshape(E * Cp, D)
    sizes = jnp.full((E,), Cp, jnp.int32)

    def mm(a, w):
        return moe_gmm(a, w, sizes, block_m=bm, interpret=interpret)

    h = mm(x, wi)
    a = jax.nn.silu if act == "silu" else jax.nn.gelu
    if wg is not None:
        h = a(mm(x, wg)) * h
    else:
        h = a(h)
    return mm(h, wo).reshape(E, Cp, -1)[:, :C]
