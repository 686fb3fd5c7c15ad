"""Shared layers: norms, RoPE, activations, MLP blocks."""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.base import ParamSpec, dense_spec
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(dim: int, axis: str = "embed") -> ParamSpec:
    return ParamSpec((dim,), (axis,), "ones")


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layernorm_spec(dim: int, axis: str = "embed") -> dict:
    return {
        "scale": ParamSpec((dim,), (axis,), "ones"),
        "bias": ParamSpec((dim,), (axis,), "zeros"),
    }


def layer_norm(x: jax.Array, p: dict, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(d: int, theta: float, yarn=None):
    """(inverse frequencies (d/2,), scale of cos and sin) of a rotary
    embedding over head size ``d``; with ``yarn`` (a ``config.YaRN``),
    the frequencies as transformers' ``_compute_yarn_parameters`` sets
    them, with truncation."""
    half = d // 2
    j = jnp.arange(0, half, dtype=jnp.float32)
    inv = 1.0 / (theta ** (j / half))
    if yarn is None:
        return inv, 1.0

    def correction_dim(rotations):
        return (d * math.log(yarn.original_max_position
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    inv = inv / yarn.factor * ramp + inv * (1.0 - ramp)
    return inv, yarn.attention_factor


def apply_rope(
    x: jax.Array,            # (B, S, H, D)
    positions: jax.Array,    # (S,) or (B, S)
    theta: float,
    yarn=None,
) -> jax.Array:
    """Rotary position embedding on the two halves of the trailing
    head_dim (``yarn``: see :func:`rope_frequencies`)."""
    assert x.ndim == 4, f"apply_rope expects (B,S,H,D), got {x.shape}"
    d = x.shape[-1]
    half = d // 2
    freq, scale = rope_frequencies(d, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freq  # (S,half)/(B,S,half)
    if ang.ndim == 2:
        ang = ang[None]                        # (1, S, half)
    cos = jnp.cos(ang)[:, :, None, :]           # (B|1, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [
            x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin,
            x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin,
        ],
        axis=-1,
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations & MLP
# ---------------------------------------------------------------------------


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(f"unknown activation {name!r}")


def mlp_blueprint(cfg: ModelConfig, d_ff: Optional[int] = None,
                  hidden_axis: str = "mlp") -> dict:
    """SwiGLU (silu) or plain 2-matrix MLP (gelu)."""
    d, f = cfg.d_model, d_ff if d_ff is not None else cfg.d_ff
    bp = {
        "wi": dense_spec(d, f, "embed", hidden_axis),
        "wo": dense_spec(f, d, hidden_axis, "embed"),
    }
    if cfg.mlp_gated:
        bp["wg"] = dense_spec(d, f, "embed", hidden_axis)
    return bp


def mlp_apply(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    act = activation(cfg.act)
    h = x @ p["wi"].astype(x.dtype)
    if "wg" in p:                       # gated (SwiGLU / GeGLU)
        h = act(x @ p["wg"].astype(x.dtype)) * h
    else:
        h = act(h)
    return h @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_spec(cfg: ModelConfig) -> ParamSpec:
    # normal(0.02): with tied unembedding, unit-normal embeddings would put
    # init logits at std ~ sqrt(d) (CE in the hundreds); 0.02 gives the
    # standard ln(V) init loss.
    return ParamSpec(
        (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), "embed",
        scale=0.02,
    )


def unembed_spec(cfg: ModelConfig) -> ParamSpec:
    return ParamSpec(
        (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), "normal"
    )


def embed_tokens(embedding: jax.Array, tokens: jax.Array,
                 dtype: Any) -> jax.Array:
    return embedding.astype(dtype)[tokens]


def logits_from_hidden(
    x: jax.Array,
    cfg: ModelConfig,
    *,
    embedding: Optional[jax.Array] = None,
    unembed: Optional[jax.Array] = None,
) -> jax.Array:
    """Project hidden states to (padded) vocab logits; padding masked."""
    if cfg.tie_embeddings:
        assert embedding is not None
        logits = x @ embedding.astype(x.dtype).T
    else:
        assert unembed is not None
        logits = x @ unembed.astype(x.dtype)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    if cfg.padded_vocab != cfg.vocab_size:
        pad = cfg.padded_vocab - cfg.vocab_size
        mask = jnp.concatenate(
            [
                jnp.zeros((cfg.vocab_size,), logits.dtype),
                jnp.full((pad,), jnp.finfo(logits.dtype).min, logits.dtype),
            ]
        )
        logits = logits + mask
    return logits
