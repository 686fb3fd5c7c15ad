"""Mixture-of-Experts layer (phi3.5-moe 16e/top-2, qwen3-moe 128e/top-8,
mellum2 64e/top-8).

Two layers share the router and the expert weights:

* ``moe_apply`` (training and the full-sequence forward): GShard/Switch-
  style capacity-based dispatch, static shapes, shardable with EP (experts
  over the 'model' mesh axis).  Per expert capacity
  ``C = ceil(tokens · top_k / E · capacity_factor)``; overflow tokens drop
  their contribution from the overflowing expert (their other experts
  still fire).
* ``moe_serve`` (the serving steps): dropless, over the experts this
  device holds (``cfg.expert_offset`` on, ``cfg.held_experts`` of them).
  It routes over all ``cfg.num_experts``, keeps the assignments to its
  own experts, sorts them by expert and runs a grouped matmul over the
  ragged groups — the ``repro.kernels.moe_gmm`` Pallas kernel with
  ``impl="pallas"``, ``jax.lax.ragged_dot`` otherwise.  A model's layer
  scan hands it the whole expert stacks and the layer's index, which the
  kernel reads where they lie.  Its result is this device's part of the
  layer's output; the parts of all devices sum to the whole layer's.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.base import ParamSpec, dense_spec
from repro.models.config import ModelConfig


def moe_blueprint(cfg: ModelConfig) -> Dict[str, Any]:
    """The router over all ``num_experts``; the held experts' weights."""
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.held_experts
    bp: Dict[str, Any] = {
        "router": dense_spec(d, cfg.num_experts, "embed", None),
        "wi": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp")),
        "wo": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.mlp_gated:
        bp["wg"] = ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"))
    return bp


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(
        n_tokens * cfg.experts_per_token / cfg.num_experts
        * cfg.capacity_factor
    )
    return max(int(c), 1)


def route_topk(
    router_logits: jax.Array,   # (N, E) fp32
    top_k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing with softmax-renormalized combine weights."""
    gates = jax.nn.softmax(router_logits, axis=-1)
    weights, idx = jax.lax.top_k(gates, top_k)          # (N, k)
    weights = weights / jnp.maximum(
        weights.sum(axis=-1, keepdims=True), 1e-9
    )
    return weights, idx


def moe_apply(
    p: Dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,                    # (B, S, d)
    *,
    impl: str = "einsum",            # "einsum" | "pallas"
    return_aux: bool = False,
    chunk_tokens: int = 16_384,
):
    """Capacity-based top-k MoE, chunked over tokens.

    Expert capacity is proportional to the CHUNK token count, so the
    dispatch buffer is O(chunk x d) regardless of sequence length (a 1M-
    token prefill would otherwise materialize a multi-GiB (E, C, d)
    scatter target).  Chunks run under ``lax.scan``.
    Returns (y, aux_loss?) — aux is the Switch load-balancing loss."""
    if cfg.held_experts != cfg.num_experts:
        raise ValueError(
            f"{cfg.name}: the capacity layer computes every expert; this "
            f"device holds {cfg.held_experts} of {cfg.num_experts}"
        )
    B, S, d = x.shape
    N = B * S
    if N > chunk_tokens and N % chunk_tokens == 0:
        xf = x.reshape(N // chunk_tokens, 1, chunk_tokens, d)

        def step(aux_acc, xc):
            y, aux = moe_apply(
                p, cfg, xc, impl=impl, return_aux=return_aux,
                chunk_tokens=chunk_tokens,
            )
            if aux is None:
                aux = jnp.zeros((), jnp.float32)
            return aux_acc + aux, y

        aux_sum, ys = jax.lax.scan(
            step, jnp.zeros((), jnp.float32), xf
        )
        y = ys.reshape(B, S, d)
        return (y, aux_sum / (N // chunk_tokens)) if return_aux \
            else (y, None)
    E, k = cfg.num_experts, cfg.experts_per_token
    C = _capacity(cfg, N)
    dt = x.dtype

    xf = x.reshape(N, d)
    router_logits = (
        xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    )
    weights, expert_idx = route_topk(router_logits, k)   # (N,k)

    # ---- capacity assignment -------------------------------------------
    # position of each (token, k) within its expert's queue
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (N,k,E)
    flat_onehot = onehot.reshape(N * k, E)
    pos_in_expert = (
        jnp.cumsum(flat_onehot, axis=0) * flat_onehot
    ).sum(axis=-1) - 1                                    # (N*k,)
    expert_flat = expert_idx.reshape(N * k)
    keep = pos_in_expert < C
    slot = jnp.where(keep, pos_in_expert, C)              # C = overflow bin

    # dispatch: scatter tokens into (E, C+1, d), drop the overflow bin.
    # Each (token, k) owns a unique slot, so scatter-add == scatter-set and
    # the transport dtype may be quantized: with moe_dispatch_dtype =
    # "float8_e4m3fn" the cross-shard token movement (the EP all-to-all —
    # the dominant collective of high-top-k MoE) halves (§Perf 5).
    wire_dt = (
        jnp.dtype(cfg.moe_dispatch_dtype) if cfg.moe_dispatch_dtype else dt
    )
    dispatch_idx = expert_flat * (C + 1) + slot           # (N*k,)
    token_idx = jnp.repeat(jnp.arange(N), k)
    buf = jnp.zeros((E * (C + 1), d), wire_dt)
    buf = buf.at[dispatch_idx].add(
        (xf[token_idx] * keep[:, None]).astype(wire_dt)
    )
    xe = buf.reshape(E, C + 1, d)[:, :C].astype(dt)       # (E, C, d)

    # ---- expert FFN -------------------------------------------------------
    if impl == "pallas":
        from repro.kernels import ops as kops

        ye = kops.moe_ffn(
            xe, p["wi"].astype(dt),
            p["wg"].astype(dt) if "wg" in p else None,
            p["wo"].astype(dt), act=cfg.act,
        )
    else:
        h = jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(dt))
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        if "wg" in p:
            g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(dt))
            h = act(g) * h
        else:
            h = act(h)
        ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))

    # ---- combine (same quantized wire format on the way back) -----------
    ye_flat = jnp.concatenate(
        [ye, jnp.zeros((E, 1, d), ye.dtype)], axis=1
    ).reshape(E * (C + 1), d).astype(wire_dt)
    gathered = ye_flat[dispatch_idx].astype(dt)           # (N*k, d)
    w = (weights.reshape(N * k) * keep).astype(dt)
    y = jnp.zeros((N, d), dt).at[token_idx].add(gathered * w[:, None])
    y = y.reshape(B, S, d)

    if not return_aux:
        return y, None
    # Switch aux loss: E * sum_e f_e * P_e
    probs = jax.nn.softmax(router_logits, axis=-1)        # (N,E)
    f = (onehot.sum(axis=1) > 0).astype(jnp.float32).mean(axis=0)  # (E,)
    pbar = probs.mean(axis=0)
    aux = cfg.num_experts * jnp.sum(f * pbar) * cfg.router_aux_coef
    return y, aux


# ---------------------------------------------------------------------------
# Dropless expert share (serving)
# ---------------------------------------------------------------------------

#: tokens of one pass of the serving layer; a longer input runs chunk by
#: chunk under ``lax.scan`` (the last one padded with tokens routed to no
#: expert), so the sorted rows stay O(chunk · top_k · d)
SERVE_CHUNK_TOKENS = 16_384
#: the expert matrices of a layer's parameters, each ``(held, ...)``
EXPERT_MATRICES = ("wi", "wg", "wo")


def moe_serve(
    p: Dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,                    # (B, S, d)
    *,
    impl: str = "einsum",            # "pallas": the moe_gmm kernel
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """This device's part of the MoE layer, dropping nothing.  ``p``'s
    expert matrices (``wi``, ``wg``, ``wo``) are one layer's ``(held, d,
    f)``, or with ``layer`` (a () int32) stacks ``(L, held, d, f)`` read at
    that index.  Returns (y (B, S, d), load (held,) int32: the
    assignments each held expert received)."""
    B, S, d = x.shape
    N = B * S
    if layer is None:
        p = dict(p, **{k: p[k][None] for k in EXPERT_MATRICES if k in p})
        layer = 0
    with jax.named_scope("moe"):
        xf = x.reshape(N, d)
        if N <= SERVE_CHUNK_TOKENS:
            y, load = _serve_tokens(p, cfg, xf, jnp.ones((N,), bool),
                                    layer, impl)
            return y.reshape(B, S, d), load
        n = -(-N // SERVE_CHUNK_TOKENS)
        pad = n * SERVE_CHUNK_TOKENS - N
        xc = jnp.pad(xf, ((0, pad), (0, 0))).reshape(
            n, SERVE_CHUNK_TOKENS, d)
        real = (jnp.arange(n * SERVE_CHUNK_TOKENS) < N).reshape(
            n, SERVE_CHUNK_TOKENS)

        def step(load, chunk):
            y, c = _serve_tokens(p, cfg, *chunk, layer, impl)
            return load + c, y

        load, ys = jax.lax.scan(
            step, jnp.zeros((cfg.held_experts,), jnp.int32), (xc, real))
        return ys.reshape(n * SERVE_CHUNK_TOKENS, d)[:N].reshape(
            B, S, d), load


def _serve_tokens(p, cfg: ModelConfig, x: jax.Array, real: jax.Array,
                  layer, impl: str):
    """The layer on ``x (N, d)`` with ``layer``'s experts of the stacks in
    ``p``; tokens not ``real`` go to no expert."""
    from repro.kernels import moe_gmm as gmm_mod

    N, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    H, off = cfg.held_experts, cfg.expert_offset
    A = N * k
    dt = x.dtype
    with jax.named_scope("route"):
        logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        weights, idx = route_topk(logits, k)             # (N, k)
        local = idx.reshape(A) - off
        held = (local >= 0) & (local < H) & jnp.repeat(real, k)
        group = jnp.where(held, local, H)                # H: not held here
    with jax.named_scope("sort"):
        # rows sorted by expert, each expert's run padded to the row tile
        bm = gmm_mod.row_tile(A / E)
        M = gmm_mod.padded_rows(A, H, bm)
        order = jnp.argsort(group, stable=True)          # (A,)
        load = jnp.zeros((H + 1,), jnp.int32).at[group].add(1)[:H]
        padded = -(-load // bm) * bm
        start = jnp.cumsum(load) - load
        pstart = jnp.cumsum(padded) - padded
        g_sorted = group[order]
        rank = jnp.arange(A, dtype=jnp.int32) - jnp.take(
            start, g_sorted, mode="clip")
        dest = jnp.where(g_sorted < H,
                         jnp.take(pstart, g_sorted, mode="clip") + rank, M)
        row_token = jnp.full((M,), N, jnp.int32).at[dest].set(
            (order // k).astype(jnp.int32), mode="drop")
        xs = jnp.take(x, row_token, axis=0, mode="fill", fill_value=0)
    with jax.named_scope("experts"):
        if impl == "pallas":
            from repro.kernels import ops as kops

            # the whole stack, read at the layer by the kernel; the cast
            # is a no-op on weights served in the step's dtype
            def mm(a, w):
                return kops.moe_gmm(a, w.astype(dt), padded, layer,
                                    block_m=bm)
        else:
            def mm(a, w):
                return jax.lax.ragged_dot(a, w[layer].astype(dt), padded)

        h = mm(xs, p["wi"])
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(mm(xs, p["wg"])) * h if "wg" in p else act(h)
        ys = mm(h.astype(dt), p["wo"])                   # (M, d)
    with jax.named_scope("combine"):
        # each assignment's row, in (token, k) order; not held: none
        row = jnp.zeros((A,), jnp.int32).at[order].set(dest)
        ya = jnp.take(ys, row, axis=0, mode="fill", fill_value=0)
        w = jnp.where(held, weights.reshape(A), 0.0)
        y = (ya.reshape(N, k, d).astype(jnp.float32)
             * w.reshape(N, k, 1)).sum(axis=1)
    return y.astype(dt), load
