"""TransformerLM — the unified decoder-only model.

One class covers the dense / GQA / SWA / MoE / SSM / hybrid families of the
assignment (everything except Whisper's encoder-decoder, see ``whisper.py``):

* per-layer parameters are stacked and the layer stack is a single
  ``lax.scan`` (compile time is O(1) in depth — an 81-layer zamba2 compiles
  one block),
* zamba2's *shared* attention block is closed over by the scan body: its
  weights appear once in the pytree but are applied every
  ``hybrid_attn_every``-th step, each application with its own KV-cache
  slice (weight sharing ≠ cache sharing),
* the loss head is a *chunked* cross-entropy: logits are never materialized
  for the full sequence (vocab 257k × seq 4k would be hundreds of GB),
* PaliGemma's vision frontend is a stub per the assignment:
  ``prefix_embed`` (precomputed patch embeddings) is concatenated in front
  of the token embeddings with a bidirectional prefix-LM mask,
* the serving step names its parts with ``jax.named_scope`` (``embed``,
  ``layers``, ``logits``; in an attention block ``norm``, ``attention``
  with its ``kv_write``, ``ffn``), which a profile reads from each
  operation's metadata; what lies under ``layers`` in no block scope is
  the scan's own slicing and stacking of per-layer weights and cache.

Modes
-----
``forward``      full-sequence logits/hidden (training, scoring)
``prefill``      full-sequence + KV/SSM cache write (serving prompt phase)
``decode_step``  one token per replica step with carried cache (serving)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
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
    logits_from_hidden,
    mlp_apply,
    mlp_blueprint,
    rms_norm,
    rmsnorm_spec,
    unembed_spec,
)


class TransformerLM:
    """Decoder-only LM over a ModelConfig."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        impl: str = "blockwise",       # attention impl: blockwise|naive|pallas
        q_block: int = 512,
        kv_block: int = 1024,
        ssm_chunk: int = 256,
        remat: bool = False,           # checkpoint each scanned block
    ) -> None:
        self.cfg = cfg
        self.impl = impl
        self.q_block = q_block
        self.kv_block = kv_block
        self.ssm_chunk = ssm_chunk
        self.remat = remat

    # ==================================================================
    # Blueprint
    # ==================================================================
    def _layer_blueprint(self) -> Dict[str, Any]:
        cfg = self.cfg
        bp: Dict[str, Any] = {"ln1": rmsnorm_spec(cfg.d_model)}
        if cfg.family == "ssm":
            bp["mixer"] = ssm_mod.mamba1_blueprint(cfg)
            return bp
        bp["attn"] = attn.attention_blueprint(cfg)
        if not cfg.parallel_block:
            bp["ln2"] = rmsnorm_spec(cfg.d_model)
        if cfg.is_moe:
            bp["moe"] = moe_mod.moe_blueprint(cfg)
        else:
            bp["mlp"] = mlp_blueprint(cfg)
        return bp

    def _hybrid_blueprints(self) -> Dict[str, Any]:
        """zamba2: stacked mamba2 layers + ONE shared attention block."""
        cfg = self.cfg
        m_bp = {
            "ln1": rmsnorm_spec(cfg.d_model),
            "mixer": ssm_mod.mamba2_blueprint(cfg),
        }
        shared = {
            "ln1": rmsnorm_spec(cfg.d_model),
            "attn": attn.attention_blueprint(cfg),
            "ln2": rmsnorm_spec(cfg.d_model),
            "mlp": mlp_blueprint(cfg),
        }
        n_pre = cfg.hybrid_prelude
        per_blk = cfg.hybrid_attn_every - 1
        return {
            "prelude": stack_blueprint(m_bp, n_pre) if n_pre else {},
            "blocks": stack_blueprint(
                stack_blueprint(m_bp, per_blk), cfg.hybrid_blocks
            ),
            "shared_attn": shared,
        }

    def blueprint(self) -> Dict[str, Any]:
        cfg = self.cfg
        bp: Dict[str, Any] = {"embed": embed_spec(cfg)}
        if not cfg.tie_embeddings:
            bp["unembed"] = unembed_spec(cfg)
        bp["final_norm"] = rmsnorm_spec(cfg.d_model)
        if cfg.family == "hybrid":
            bp["decoder"] = self._hybrid_blueprints()
        else:
            bp["decoder"] = stack_blueprint(
                self._layer_blueprint(), cfg.num_layers
            )
        return bp

    def init(self, key: jax.Array, dtype=None) -> Any:
        return init_params(self.blueprint(), key, dtype)

    def abstract(self, dtype=jnp.bfloat16) -> Any:
        return abstract_params(self.blueprint(), dtype)

    # ==================================================================
    # Cache
    # ==================================================================
    def _cache_template(
        self, batch: int, max_len: int, dtype, abstract: bool
    ) -> Dict[str, Any]:
        cfg = self.cfg
        mk = (
            (lambda s, d: jax.ShapeDtypeStruct(s, d))
            if abstract
            else (lambda s, d: jnp.zeros(s, d))
        )
        cache: Dict[str, Any] = {
            "len": mk((), jnp.int32),
        }
        if cfg.family == "ssm":
            shapes = ssm_mod.mamba1_state_shapes(cfg, batch)
            L = cfg.num_layers
            cache["ssm_state"] = {
                k: mk((L,) + s, jnp.float32) for k, s in shapes.items()
            }
        elif cfg.family == "hybrid":
            shapes = ssm_mod.mamba2_state_shapes(cfg, batch)
            n_pre, n_blk = cfg.hybrid_prelude, cfg.hybrid_blocks
            per_blk = cfg.hybrid_attn_every - 1
            if n_pre:
                cache["prelude_state"] = {
                    k: mk((n_pre,) + s, jnp.float32)
                    for k, s in shapes.items()
                }
            cache["block_state"] = {
                k: mk((n_blk, per_blk) + s, jnp.float32)
                for k, s in shapes.items()
            }
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            slots = max_len
            cache["attn_kv"] = {
                "k": mk((n_blk, batch, slots, kv, hd), dtype),
                "v": mk((n_blk, batch, slots, kv, hd), dtype),
            }
        else:
            slots = (
                min(max_len, cfg.sliding_window)
                if cfg.sliding_window is not None
                else max_len
            )
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            L = cfg.num_layers
            cache["kv"] = {
                "k": mk((L, batch, slots, kv, hd), dtype),
                "v": mk((L, batch, slots, kv, hd), dtype),
            }
        return cache

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._cache_template(batch, max_len, dtype, abstract=False)

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._cache_template(batch, max_len, dtype, abstract=True)

    # ==================================================================
    # Blocks
    # ==================================================================
    def _attn_block(
        self, lp, x, *, positions, mode, layer_kv, cache_len, prefix_len
    ):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        with jax.named_scope("norm"):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        with jax.named_scope("attention"):
            a, new_kv = attn.attention_apply(
                lp["attn"], cfg, h,
                positions=positions, mode=mode, layer_cache=layer_kv,
                cache_len=cache_len, prefix_len=prefix_len, impl=self.impl,
                q_block=self.q_block, kv_block=self.kv_block,
            )
        if cfg.parallel_block:
            # command-r: attn and FFN read the SAME normed input, summed
            with jax.named_scope("ffn"):
                if cfg.is_moe:
                    f, aux_l = moe_mod.moe_apply(
                        lp["moe"], cfg, h, return_aux=True
                    )
                    aux = aux + aux_l
                else:
                    f = mlp_apply(lp["mlp"], cfg, h)
            x = x + a + f
        else:
            x = x + a
            with jax.named_scope("norm"):
                h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            with jax.named_scope("ffn"):
                if cfg.is_moe:
                    f, aux_l = moe_mod.moe_apply(
                        lp["moe"], cfg, h2, return_aux=True
                    )
                    aux = aux + (aux_l if aux_l is not None else 0.0)
                    x = x + f
                else:
                    x = x + mlp_apply(lp["mlp"], cfg, h2)
        return x, new_kv, aux

    def _mamba_block(self, lp, x, *, mode, state, version):
        cfg = self.cfg
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if version == 1:
            fn_full, fn_dec = ssm_mod.mamba1_full, ssm_mod.mamba1_decode
        else:
            fn_full, fn_dec = ssm_mod.mamba2_full, ssm_mod.mamba2_decode
        if mode == "decode":
            y, new_state = fn_dec(lp["mixer"], cfg, h, state)
        else:
            kwargs = {"chunk": self.ssm_chunk, "state": state}
            if version == 1:
                kwargs["impl"] = "pallas" if self.impl == "pallas" else "jnp"
            y, new_state = fn_full(lp["mixer"], cfg, h, **kwargs)
        return x + y, new_state

    # ==================================================================
    # Stacks
    # ==================================================================
    def _run_uniform_stack(
        self, params, x, *, positions, mode, cache, prefix_len
    ):
        """Dense / MoE / SSM: one scanned stack."""
        cfg = self.cfg
        cache_len = None if cache is None else cache["len"]

        if cfg.family == "ssm":
            def body(carry, per_layer):
                xc = carry
                lp, st = per_layer
                y, new_st = self._mamba_block(
                    lp, xc, mode=mode, state=st, version=1
                )
                return y, new_st

            if self.remat:
                body = jax.checkpoint(body)
            states = None
            if cache is not None:
                states = cache["ssm_state"]
            else:
                states = {
                    k: jnp.zeros((cfg.num_layers,) + s, jnp.float32)
                    for k, s in ssm_mod.mamba1_state_shapes(
                        cfg, x.shape[0]
                    ).items()
                }
            x, new_states = jax.lax.scan(
                body, x, (params["decoder"], states)
            )
            new_cache = None
            if cache is not None:
                new_cache = dict(cache)
                new_cache["ssm_state"] = new_states
            return x, new_cache, jnp.zeros((), jnp.float32)

        # attention families
        def body(carry, per_layer):
            xc, aux_acc = carry
            lp, kv_slice = per_layer
            y, new_kv, aux = self._attn_block(
                lp, xc, positions=positions, mode=mode,
                layer_kv=kv_slice, cache_len=cache_len,
                prefix_len=prefix_len,
            )
            return (y, aux_acc + aux), new_kv

        if self.remat:
            body = jax.checkpoint(body)
        kv = cache["kv"] if cache is not None else None
        if kv is None:
            # no-cache forward still scans a dummy so the body is uniform
            (x, aux), _ = jax.lax.scan(
                lambda c, lp: (
                    body(c, (lp, None))[0],
                    0.0,
                ),
                (x, jnp.zeros((), jnp.float32)),
                params["decoder"],
            )
            return x, None, aux
        (x, aux), new_kv = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["decoder"], kv)
        )
        new_cache = dict(cache)
        new_cache["kv"] = new_kv
        return x, new_cache, aux

    def _run_hybrid_stack(
        self, params, x, *, positions, mode, cache, prefix_len
    ):
        """zamba2: prelude mamba2 layers, then (shared-attn + mamba2 group)
        super-blocks."""
        cfg = self.cfg
        dec = params["decoder"]
        cache_len = None if cache is None else cache["len"]
        shared = dec["shared_attn"]

        def mamba_body(carry, per_layer):
            xc = carry
            lp, st = per_layer
            y, new_st = self._mamba_block(
                lp, xc, mode=mode, state=st, version=2
            )
            return y, new_st

        if self.remat:
            mamba_body = jax.checkpoint(mamba_body)

        def zero_states(n_shape):
            return {
                k: jnp.zeros(n_shape + s, jnp.float32)
                for k, s in ssm_mod.mamba2_state_shapes(
                    cfg, x.shape[0]
                ).items()
            }

        # ---- prelude -----------------------------------------------------
        new_prelude_state = None
        if cfg.hybrid_prelude:
            st = (
                cache["prelude_state"]
                if cache is not None
                else zero_states((cfg.hybrid_prelude,))
            )
            x, new_prelude_state = jax.lax.scan(
                mamba_body, x, (dec["prelude"], st)
            )

        # ---- super-blocks ---------------------------------------------------
        blk_state = (
            cache["block_state"]
            if cache is not None
            else zero_states((cfg.hybrid_blocks, cfg.hybrid_attn_every - 1))
        )

        if cache is not None:
            def block_body(carry, per_block):
                xc = carry
                blk_params, st, blk_kv = per_block
                # shared attention (weights shared; per-block cache slice)
                y, new_kv, _ = self._attn_block(
                    shared, xc, positions=positions, mode=mode,
                    layer_kv=blk_kv, cache_len=cache_len,
                    prefix_len=prefix_len,
                )
                y, new_state = jax.lax.scan(mamba_body, y, (blk_params, st))
                return y, (new_state, new_kv)

            x, (new_blk_state, new_blk_kv) = jax.lax.scan(
                block_body, x, (dec["blocks"], blk_state, cache["attn_kv"])
            )
            new_cache = dict(cache)
            if new_prelude_state is not None:
                new_cache["prelude_state"] = new_prelude_state
            new_cache["block_state"] = new_blk_state
            new_cache["attn_kv"] = new_blk_kv
            return x, new_cache, jnp.zeros((), jnp.float32)

        def block_body_nc(carry, per_block):
            xc = carry
            blk_params, st = per_block
            y, _, _ = self._attn_block(
                shared, xc, positions=positions, mode=mode,
                layer_kv=None, cache_len=cache_len, prefix_len=prefix_len,
            )
            y, new_state = jax.lax.scan(mamba_body, y, (blk_params, st))
            return y, new_state

        if self.remat:
            block_body_nc = jax.checkpoint(block_body_nc)
        x, _ = jax.lax.scan(block_body_nc, x, (dec["blocks"], blk_state))
        return x, None, jnp.zeros((), jnp.float32)

    def _run_stack(self, params, x, *, positions, mode, cache, prefix_len):
        run = (self._run_hybrid_stack if self.cfg.family == "hybrid"
               else self._run_uniform_stack)
        with jax.named_scope("layers"):
            return run(
                params, x, positions=positions, mode=mode, cache=cache,
                prefix_len=prefix_len,
            )

    # ==================================================================
    # Public entry points
    # ==================================================================
    def _embed_inputs(
        self, params, tokens, prefix_embed, dtype
    ) -> Tuple[jax.Array, int]:
        with jax.named_scope("embed"):
            x = embed_tokens(params["embed"], tokens, dtype)
            prefix_len = 0
            if prefix_embed is not None:
                x = jnp.concatenate([prefix_embed.astype(dtype), x], axis=1)
                prefix_len = prefix_embed.shape[1]
        return x, prefix_len

    def forward(
        self,
        params,
        tokens: jax.Array,               # (B, S)
        *,
        prefix_embed: Optional[jax.Array] = None,
        dtype=jnp.bfloat16,
    ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence hidden states; returns (hidden (B,S',d), aux)."""
        x, prefix_len = self._embed_inputs(params, tokens, prefix_embed,
                                           dtype)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, _, aux = self._run_stack(
            params, x, positions=positions, mode="full", cache=None,
            prefix_len=prefix_len if self.cfg.prefix_lm else 0,
        )
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return x, aux

    def logits(self, params, hidden: jax.Array) -> jax.Array:
        return logits_from_hidden(
            hidden, self.cfg,
            embedding=params.get("embed"),
            unembed=params.get("unembed"),
        )

    def loss(
        self,
        params,
        tokens: jax.Array,               # (B, S)
        labels: jax.Array,               # (B, S) — next-token targets
        *,
        prefix_embed: Optional[jax.Array] = None,
        dtype=jnp.bfloat16,
        ce_chunk: int = 512,
    ) -> jax.Array:
        """Mean next-token CE + MoE aux loss; logits chunked over sequence."""
        hidden, aux = self.forward(
            params, tokens, prefix_embed=prefix_embed, dtype=dtype
        )
        if prefix_embed is not None:
            hidden = hidden[:, prefix_embed.shape[1]:]
        ce = chunked_ce(
            hidden, labels, self.cfg,
            embedding=params.get("embed"),
            unembed=params.get("unembed"),
            chunk=ce_chunk,
        )
        return ce + aux

    def prefill(
        self,
        params,
        tokens: jax.Array,               # (B, S)
        cache: Dict[str, Any],
        *,
        prefix_embed: Optional[jax.Array] = None,
        dtype=jnp.bfloat16,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Process the prompt, fill the cache, return last-position logits."""
        x, prefix_len = self._embed_inputs(params, tokens, prefix_embed,
                                           dtype)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, new_cache, _ = self._run_stack(
            params, x, positions=positions, mode="full", cache=cache,
            prefix_len=prefix_len if self.cfg.prefix_lm else 0,
        )
        with jax.named_scope("logits"):
            x = rms_norm(x[:, -1:], params["final_norm"], self.cfg.norm_eps)
            logits = self.logits(params, x)
        new_cache["len"] = jnp.asarray(positions.shape[0], jnp.int32)
        return logits, new_cache

    def decode_step(
        self,
        params,
        tokens: jax.Array,               # (B, 1)
        cache: Dict[str, Any],
        *,
        dtype=jnp.bfloat16,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One decode step: next-token logits + updated cache."""
        with jax.named_scope("embed"):
            x = embed_tokens(params["embed"], tokens, dtype)
        positions = cache["len"][None].astype(jnp.int32)
        x, new_cache, _ = self._run_stack(
            params, x, positions=positions, mode="decode", cache=cache,
            prefix_len=0,
        )
        with jax.named_scope("logits"):
            x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
            logits = self.logits(params, x)
        new_cache["len"] = cache["len"] + 1
        return logits, new_cache


# ---------------------------------------------------------------------------
# Chunked cross-entropy (vocab-sharding-friendly)
# ---------------------------------------------------------------------------


def chunked_ce(
    hidden: jax.Array,        # (B, S, d)
    labels: jax.Array,        # (B, S)
    cfg: ModelConfig,
    *,
    embedding: Optional[jax.Array],
    unembed: Optional[jax.Array],
    chunk: int = 512,
) -> jax.Array:
    """Next-token CE without materializing (B,S,V): scan over S chunks.

    The label logit is extracted with a one-hot einsum (not a gather) so a
    vocab-sharded unembedding keeps the computation local + one all-reduce.
    """
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    n = (S + pad) // chunk
    hc = hidden.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    valid_count = jnp.asarray(B * S, jnp.float32)

    def step(acc, inp):
        h, lab = inp
        logits = logits_from_hidden(
            h, cfg, embedding=embedding, unembed=unembed
        ).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)           # (B, chunk)
        onehot = jax.nn.one_hot(lab, cfg.padded_vocab, dtype=logits.dtype)
        lab_logit = jnp.einsum("bcv,bcv->bc", logits, onehot)
        ce = lse - lab_logit
        return acc + ce.sum(), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hc, lc))
    return total / valid_count
