"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jax.Array,                 # (B, H, Sq, D)
    k: jax.Array,                 # (B, Kv, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Kv, Skv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Kv, G, Sq, D).astype(jnp.float32)
    s = jnp.einsum(
        "bkgqd,bkmd->bkgqm", qg, k.astype(jnp.float32)
    ) / math.sqrt(D)
    q_pos = jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        c = q_pos[:, None] >= k_pos[None, :]
        if prefix_len:
            c = c | (k_pos[None, :] < prefix_len)
        mask &= c
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqm,bkmd->bkgqd", w, v.astype(jnp.float32))
    return out.reshape(B, H, Sq, D).astype(q.dtype)


def flash_decode_ref(
    q: jax.Array,                 # (B, H, D)
    k: jax.Array,                 # (B, Kv, S, D)
    v: jax.Array,
    valid: jax.Array,             # (B, S)
) -> jax.Array:
    B, H, D = q.shape
    Kv = k.shape[1]
    G = H // Kv
    qg = q.reshape(B, Kv, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bkmd->bkgm", qg, k.astype(jnp.float32)) \
        / math.sqrt(D)
    s = jnp.where(valid[:, None, None, :].astype(bool), s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgm,bkmd->bkgd", w, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def selective_scan_ref(
    a: jax.Array,                 # (B, Q, C, N)
    b: jax.Array,
    h0: jax.Array,                # (B, C, N)
) -> jax.Array:
    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    a_t = a.transpose(1, 0, 2, 3).astype(jnp.float32)
    b_t = b.transpose(1, 0, 2, 3).astype(jnp.float32)
    _, hs = jax.lax.scan(step, h0.astype(jnp.float32), (a_t, b_t))
    return hs.transpose(1, 0, 2, 3)


def moe_gmm_ref(
    x: jax.Array,                 # (M, D) rows sorted by group
    w: jax.Array,                 # (G, D, F)
    group_sizes: jax.Array,       # (G,)
) -> jax.Array:
    """Row r of group g (groups own consecutive rows, ``group_sizes[g]``
    each) times ``w[g]``, in f32; rows past the groups' are zero."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(x.shape[0])
    y = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g in range(w.shape[0]):
        mine = (rows >= starts[g]) & (rows < ends[g])
        y = y + jnp.where(mine[:, None],
                          x.astype(jnp.float32) @ w[g].astype(jnp.float32),
                          0.0)
    return y.astype(x.dtype)
