"""EncDecLM — Whisper-style encoder-decoder backbone.

Per the assignment the conv/audio frontend is a STUB: ``input_specs()``
feeds precomputed frame embeddings ``(B, S_enc, d_model)``.  The backbone is
faithful otherwise: LayerNorm (not RMSNorm), GELU MLPs, absolute sinusoidal
positions (no RoPE), bidirectional encoder self-attention, causal decoder
self-attention with a KV cache, and per-layer cross-attention whose K/V are
computed once at prefill and cached read-only.

Deviation (documented in DESIGN.md): Whisper biases K projections are zero
in the original; we carry full qkv biases — a no-op at init and irrelevant
to systems behaviour.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.base import (
    ParamSpec,
    abstract_params,
    init_params,
    stack_blueprint,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    embed_spec,
    embed_tokens,
    layer_norm,
    layernorm_spec,
    logits_from_hidden,
    mlp_apply,
    mlp_blueprint,
)
from repro.models.lm import chunked_ce


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _xattn_blueprint(cfg: ModelConfig) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }


class EncDecLM:
    """Whisper-medium-style encoder-decoder."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        impl: str = "blockwise",
        q_block: int = 512,
        kv_block: int = 1024,
        remat: bool = False,
    ) -> None:
        assert cfg.is_encdec
        self.cfg = cfg
        self.impl = impl
        self.q_block = q_block
        self.kv_block = kv_block
        self.remat = remat

    # ------------------------------------------------------------------
    def _enc_layer(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "ln1": layernorm_spec(cfg.d_model),
            "attn": attn.attention_blueprint(cfg),
            "ln2": layernorm_spec(cfg.d_model),
            "mlp": mlp_blueprint(cfg),
        }

    def _dec_layer(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "ln1": layernorm_spec(cfg.d_model),
            "self_attn": attn.attention_blueprint(cfg),
            "ln_x": layernorm_spec(cfg.d_model),
            "cross_attn": _xattn_blueprint(cfg),
            "ln2": layernorm_spec(cfg.d_model),
            "mlp": mlp_blueprint(cfg),
        }

    def blueprint(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": embed_spec(cfg),
            "encoder": stack_blueprint(self._enc_layer(),
                                       cfg.encoder_layers),
            "enc_norm": layernorm_spec(cfg.d_model),
            "decoder": stack_blueprint(self._dec_layer(), cfg.num_layers),
            "dec_norm": layernorm_spec(cfg.d_model),
        }

    def init(self, key: jax.Array) -> Any:
        return init_params(self.blueprint(), key)

    def abstract(self, dtype=jnp.bfloat16) -> Any:
        return abstract_params(self.blueprint(), dtype)

    # ------------------------------------------------------------------
    # Encoder
    # ------------------------------------------------------------------
    def encode(self, params, frames: jax.Array) -> jax.Array:
        """frames: (B, S_enc, d) precomputed frontend embeddings (stub)."""
        cfg = self.cfg
        dt = frames.dtype
        x = frames + sinusoidal_positions(
            frames.shape[1], cfg.d_model
        ).astype(dt)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)

        def body(xc, lp):
            h = layer_norm(xc, lp["ln1"], cfg.norm_eps)
            a, _ = attn.attention_apply(
                lp["attn"], cfg, h, positions=positions, mode="full",
                causal=False, impl=self.impl, q_block=self.q_block,
                kv_block=self.kv_block,
            )
            xc = xc + a
            h2 = layer_norm(xc, lp["ln2"], cfg.norm_eps)
            return xc + mlp_apply(lp["mlp"], cfg, h2), None

        if self.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["encoder"])
        return layer_norm(x, params["enc_norm"], cfg.norm_eps)

    # ------------------------------------------------------------------
    # Cross attention
    # ------------------------------------------------------------------
    def _cross_kv(self, lp, enc_out: jax.Array):
        """Cross K/V, head-major (B, Kv, S_enc, D) as the caches are."""
        dt = enc_out.dtype
        k = jnp.einsum("bsd,dhk->bhsk", enc_out, lp["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bhsk", enc_out, lp["wv"].astype(dt))
        return k, v

    def _cross_attend(self, lp, cfg, x, ck, cv):
        dt = x.dtype
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dt))
        S_enc = ck.shape[2]
        valid = jnp.ones((x.shape[0], S_enc), bool)
        if x.shape[1] == 1:
            out = attn.decode_attention(q, ck, cv, kv_valid=valid)
        else:
            pos_q = jnp.arange(x.shape[1], dtype=jnp.int32)
            pos_k = jnp.arange(S_enc, dtype=jnp.int32)
            out = attn.blockwise_attention(
                q, ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3),
                q_pos=pos_q, kv_pos=pos_k, causal=False,
                q_block=self.q_block, kv_block=self.kv_block,
            )
        return jnp.einsum("bshk,hkd->bsd", out, lp["wo"].astype(dt))

    # ------------------------------------------------------------------
    # Decoder
    # ------------------------------------------------------------------
    def _dec_block(self, lp, x, *, positions, mode, kv, layer, cross_k,
                   cross_v, cache_len):
        cfg = self.cfg
        h = layer_norm(x, lp["ln1"], cfg.norm_eps)
        a, new_kv = attn.attention_apply(
            lp["self_attn"], cfg, h, positions=positions, mode=mode,
            kv=kv, layer=layer, cache_len=cache_len, impl=self.impl,
            q_block=self.q_block, kv_block=self.kv_block,
        )
        x = x + a
        hx = layer_norm(x, lp["ln_x"], cfg.norm_eps)
        x = x + self._cross_attend(lp["cross_attn"], cfg, hx, cross_k,
                                   cross_v)
        h2 = layer_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], cfg, h2)
        return x, new_kv

    def _decoder_stack(self, params, x, *, positions, mode, cache, enc_out):
        cfg = self.cfg
        cache_len = None if cache is None else cache["len"]

        if cache is None:
            # training path: cross-KV recomputed per layer inside the scan
            def body(xc, lp):
                ck, cv = self._cross_kv(lp["cross_attn"], enc_out)
                y, _ = self._dec_block(
                    lp, xc, positions=positions, mode=mode, kv=None,
                    layer=None, cross_k=ck, cross_v=cv, cache_len=None,
                )
                return y, None

            if self.remat:
                body = jax.checkpoint(body)
            x, _ = jax.lax.scan(body, x, params["decoder"])
            return x, None

        # the self-attention cache stack rides in the carry, read and
        # written at each layer's index
        def body(carry, per_layer):
            xc, kv = carry
            layer, lp, ck, cv = per_layer
            y, kv = self._dec_block(
                lp, xc, positions=positions, mode=mode, kv=kv, layer=layer,
                cross_k=ck, cross_v=cv, cache_len=cache_len,
            )
            return (y, kv), None

        (x, new_kv), _ = jax.lax.scan(
            body,
            (x, cache["kv"]),
            (jnp.arange(cfg.num_layers), params["decoder"], cache["cross_k"],
             cache["cross_v"]),
        )
        new_cache = dict(cache)
        new_cache["kv"] = new_kv
        return x, new_cache

    # ------------------------------------------------------------------
    # Cache
    # ------------------------------------------------------------------
    def _cache_template(self, batch, max_len, enc_len, dtype, abstract):
        cfg = self.cfg
        mk = (
            (lambda s, d: jax.ShapeDtypeStruct(s, d))
            if abstract
            else (lambda s, d: jnp.zeros(s, d))
        )
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        L = cfg.num_layers
        return {
            "len": mk((), jnp.int32),
            "kv": {
                "k": mk((L, batch, kv, max_len, hd), dtype),
                "v": mk((L, batch, kv, max_len, hd), dtype),
            },
            "cross_k": mk((L, batch, kv, enc_len, hd), dtype),
            "cross_v": mk((L, batch, kv, enc_len, hd), dtype),
        }

    def init_cache(self, batch, max_len, dtype=jnp.bfloat16,
                   enc_len: Optional[int] = None):
        return self._cache_template(
            batch, max_len, enc_len or self.cfg.frontend_seq, dtype, False
        )

    def abstract_cache(self, batch, max_len, dtype=jnp.bfloat16,
                       enc_len: Optional[int] = None):
        return self._cache_template(
            batch, max_len, enc_len or self.cfg.frontend_seq, dtype, True
        )

    # ------------------------------------------------------------------
    # Entry points
    # ------------------------------------------------------------------
    def _embed_dec(self, params, tokens, dtype, offset):
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, dtype)
        pos = sinusoidal_positions(
            offset + tokens.shape[1], cfg.d_model
        )[offset:].astype(dtype)
        return x + pos[None]

    def loss(self, params, frames, tokens, labels, *, dtype=jnp.bfloat16,
             ce_chunk: int = 512) -> jax.Array:
        """Teacher-forced seq2seq CE."""
        cfg = self.cfg
        enc_out = self.encode(params, frames.astype(dtype))
        x = self._embed_dec(params, tokens, dtype, 0)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x, _ = self._decoder_stack(
            params, x, positions=positions, mode="full", cache=None,
            enc_out=enc_out,
        )
        x = layer_norm(x, params["dec_norm"], cfg.norm_eps)
        return chunked_ce(
            x, labels, cfg, embedding=params["embed"], unembed=None,
            chunk=ce_chunk,
        )

    def prefill(self, params, frames, tokens, cache, *,
                dtype=jnp.bfloat16):
        """Encode audio, fill cross-KV + self-KV, return last logits."""
        cfg = self.cfg
        enc_out = self.encode(params, frames.astype(dtype))

        # compute per-layer cross KV once (scan over layers)
        def xkv(_, lp):
            k, v = self._cross_kv(lp["cross_attn"], enc_out)
            return None, (k, v)

        _, (cross_k, cross_v) = jax.lax.scan(xkv, None, params["decoder"])
        cache = dict(cache)
        cache["cross_k"] = cross_k.astype(cache["cross_k"].dtype)
        cache["cross_v"] = cross_v.astype(cache["cross_v"].dtype)

        x = self._embed_dec(params, tokens, dtype, 0)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x, new_cache = self._decoder_stack(
            params, x, positions=positions, mode="full", cache=cache,
            enc_out=enc_out,
        )
        x = layer_norm(x[:, -1:], params["dec_norm"], cfg.norm_eps)
        logits = logits_from_hidden(x, cfg, embedding=params["embed"])
        new_cache["len"] = jnp.asarray(tokens.shape[1], jnp.int32)
        return logits, new_cache

    def decode_step(self, params, tokens, cache, *, dtype=jnp.bfloat16):
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, dtype)
        # absolute sinusoidal position for the current slot (closed form —
        # no table lookup needed at a traced position)
        posf = cache["len"].astype(jnp.float32)
        d = cfg.d_model
        dim = jnp.arange(d // 2, dtype=jnp.float32)
        ang = posf / jnp.power(10_000.0, 2 * dim / d)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None]
        x = x + pe.astype(dtype)
        positions = cache["len"][None].astype(jnp.int32)
        x, new_cache = self._decoder_stack(
            params, x, positions=positions, mode="decode", cache=cache,
            enc_out=None,
        )
        x = layer_norm(x, params["dec_norm"], cfg.norm_eps)
        logits = logits_from_hidden(x, cfg, embedding=params["embed"])
        new_cache["len"] = cache["len"] + 1
        return logits, new_cache
