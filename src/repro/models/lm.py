"""TransformerLM — the unified decoder-only model.

One class covers the dense / GQA / SWA / MoE / SSM / hybrid families of the
assignment (everything except Whisper's encoder-decoder, see ``whisper.py``):

* per-layer parameters are stacked and the layer stack is a single
  ``lax.scan`` (compile time is O(1) in depth — an 81-layer zamba2 compiles
  one block),
* every attention cache is one head-major stack per kind of layer,
  ``(layers, B, Kv, slots, D)``, carried by the layer scan, whose xs are
  (layer index, layer params): each layer writes its new K/V in place and
  ``flash_decode`` reads its slots where they lie, so no layer's cache is
  sliced out, transposed or stacked again,
* zamba2's *shared* attention block is closed over by the scan body: its
  weights appear once in the pytree but are applied every
  ``hybrid_attn_every``-th step, each application with its own layer of
  the KV-cache stack (weight sharing ≠ cache sharing),
* the loss head is a *chunked* cross-entropy: logits are never materialized
  for the full sequence (vocab 257k × seq 4k would be hundreds of GB),
* PaliGemma's vision frontend is a stub per the assignment:
  ``prefix_embed`` (precomputed patch embeddings) is concatenated in front
  of the token embeddings with a bidirectional prefix-LM mask,
* window and full layers mixed (mellum2, ``cfg.full_attn_every``): the
  stack scans periods of ``full_attn_every - 1`` window blocks and one
  full block, with two caches side by side — the window layers' rings of
  ``sliding_window`` slots and the full layers' whole-context cache,
* MoE models serve (``prefill``, ``decode_step``) through the dropless
  expert share ``moe.moe_serve`` and count each held expert's
  assignments in the cache's ``moe_load``; a serving scan closes over
  the expert stacks whole and ``moe_gmm`` reads each layer's experts
  where they lie, at the layer's index (the cache's), so no layer's
  experts are sliced out; the full-sequence forward (training) keeps
  the capacity layer and its aux loss,
* the serving step names its parts with ``jax.named_scope`` (``embed``,
  ``layers``, ``logits``; in an attention block ``norm``, ``attention``
  with its ``kv_write``, ``ffn`` holding the expert layer's ``moe``;
  ``window`` and ``full`` around each kind of block), which a profile
  reads from each operation's metadata; what lies under ``layers`` in no
  block scope is the scan's own slicing of per-layer weights.

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
        elif cfg.full_attn_every:
            # one period: full_attn_every - 1 window layers, then a full one
            layer = self._layer_blueprint()
            bp["decoder"] = {
                "window": stack_blueprint(
                    stack_blueprint(layer, cfg.full_attn_every - 1),
                    cfg.periods,
                ),
                "full": stack_blueprint(layer, cfg.periods),
            }
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
                "k": mk((n_blk, batch, kv, slots, hd), dtype),
                "v": mk((n_blk, batch, kv, slots, hd), dtype),
            }
        elif cfg.full_attn_every:
            # two caches side by side: window layers' rings (window layer
            # j of period p at p * n + j), full layers'
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            P, n = cfg.periods, cfg.full_attn_every - 1
            ring = min(max_len, cfg.sliding_window)
            cache["kv_window"] = {
                "k": mk((P * n, batch, kv, ring, hd), dtype),
                "v": mk((P * n, batch, kv, ring, hd), dtype),
            }
            cache["kv_full"] = {
                "k": mk((P, batch, kv, max_len, hd), dtype),
                "v": mk((P, batch, kv, max_len, hd), dtype),
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
                "k": mk((L, batch, kv, slots, hd), dtype),
                "v": mk((L, batch, kv, slots, hd), dtype),
            }
        if cfg.is_moe:
            # assignments each held expert of each layer received, summed
            # over every serving step on this cache
            cache["moe_load"] = mk((cfg.num_layers, cfg.held_experts),
                                   jnp.int32)
        return cache

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._cache_template(batch, max_len, dtype, abstract=False)

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self._cache_template(batch, max_len, dtype, abstract=True)

    # ==================================================================
    # Blocks
    # ==================================================================
    def _attn_block(
        self, lp, x, *, positions, mode, kv, layer, cache_len, prefix_len,
        full=False, experts=None,
    ):
        """One attention block, layer ``layer`` of the stack whose cache is
        ``kv`` and whose expert stacks are ``experts`` (``_split_experts``;
        empty: ``lp`` holds the layer's own).  Returns (x, the updated
        cache stacks, aux loss, load): a serving step (``kv`` given) of an
        MoE model runs the dropless expert share, whose per-expert
        ``load`` it returns (else None); the full-sequence forward runs the
        capacity layer and its aux loss."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        load = None
        with jax.named_scope("norm"):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        with jax.named_scope("attention"):
            a, new_kv = attn.attention_apply(
                lp["attn"], cfg, h,
                positions=positions, mode=mode, kv=kv, layer=layer,
                cache_len=cache_len, prefix_len=prefix_len, impl=self.impl,
                q_block=self.q_block, kv_block=self.kv_block, full=full,
            )

        def ffn(hn):
            nonlocal aux, load
            with jax.named_scope("ffn"):
                if not cfg.is_moe:
                    return mlp_apply(lp["mlp"], cfg, hn)
                if kv is not None:
                    f, load = moe_mod.moe_serve(
                        dict(lp["moe"], **(experts or {})), cfg, hn,
                        impl=self.impl, layer=layer if experts else None)
                    return f
                f, aux_l = moe_mod.moe_apply(lp["moe"], cfg, hn,
                                             return_aux=True)
                aux = aux + aux_l
                return f

        if cfg.parallel_block:
            # command-r: attn and FFN read the SAME normed input, summed
            x = x + a + ffn(h)
        else:
            x = x + a
            with jax.named_scope("norm"):
                h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + ffn(h2)
        return x, new_kv, aux, load

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
    def _split_experts(self, stack, serving: bool):
        """(the expert matrices of a layer stack's parameters, the rest).
        A serving scan closes over the expert stacks whole, a loop
        invariant that ``moe_gmm`` reads at the layer's index, and takes
        the rest as its xs.  A dense stack, and the full-sequence forward
        (whose capacity layer takes one layer's experts), split off
        nothing."""
        if not (serving and self.cfg.is_moe):
            return {}, stack
        moe = dict(stack["moe"])
        experts = {k: moe.pop(k) for k in moe_mod.EXPERT_MATRICES
                   if k in moe}
        return experts, dict(stack, moe=moe)

    def _run_uniform_stack(
        self, params, x, *, positions, mode, cache, prefix_len
    ):
        """Dense / MoE / SSM: one scanned stack.  An attention stack's
        head-major cache ``(L, B, Kv, slots, D)`` rides in the scan's carry;
        each layer writes and reads its own slots at its index."""
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

        # attention families: the cache stacks ride in the carry, the
        # scan's xs are (layer index, layer params); a serving step's
        # expert stacks are closed over, read at the layer index
        experts, layers = self._split_experts(params["decoder"],
                                              cache is not None)

        def body(carry, per_layer):
            xc, aux_acc, kv = carry
            layer, lp = per_layer
            y, kv, aux, load = self._attn_block(
                lp, xc, positions=positions, mode=mode, kv=kv, layer=layer,
                cache_len=cache_len, prefix_len=prefix_len, experts=experts,
            )
            return (y, aux_acc + aux, kv), load

        if self.remat:
            body = jax.checkpoint(body)
        kv = cache["kv"] if cache is not None else None
        (x, aux, kv), load = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), kv),
            (jnp.arange(cfg.num_layers), layers),
        )
        if cache is None:
            return x, None, aux
        new_cache = dict(cache, kv=kv)
        if load is not None:
            new_cache["moe_load"] = cache["moe_load"] + load
        return x, new_cache, aux

    def _run_patterned_stack(
        self, params, x, *, positions, mode, cache, prefix_len
    ):
        """Window and full layers mixed (``cfg.full_attn_every``): one
        ``lax.scan`` over the periods, whose body scans the period's window
        blocks (scope ``window``) and then runs its full block (``full``),
        each kind on its own head-major cache stack: the window rings
        ``(P·n, B, Kv, ring, D)``, window layer j of period p at
        ``p·n + j``, and the full layers' ``(P, B, Kv, slots, D)``.  Both
        stacks ride in the scans' carries; each layer reads and writes its
        own slots where they lie, at its index.  A serving step's expert
        stacks are indexed alike, ``(P·n, E, d, f)`` and ``(P, E, d, f)``,
        and closed over whole."""
        cfg = self.cfg
        dec = params["decoder"]
        cache_len = None if cache is None else cache["len"]
        n = cfg.full_attn_every - 1
        zero = jnp.zeros((), jnp.float32)
        serving = cache is not None
        experts_w, window_layers = self._split_experts(dec["window"],
                                                       serving)
        experts_f, full_layers = self._split_experts(dec["full"], serving)
        experts_w = {k: w.reshape((-1,) + w.shape[2:])
                     for k, w in experts_w.items()}

        def block(full, experts):
            def body(xc, lp, kv, layer):
                return self._attn_block(
                    lp, xc, positions=positions, mode=mode, kv=kv,
                    layer=layer, cache_len=cache_len, prefix_len=prefix_len,
                    full=full, experts=experts,
                )

            return jax.checkpoint(body) if self.remat else body

        window_block = block(False, experts_w)
        full_block = block(True, experts_f)
        kv_w = kv_f = load = None
        if cache is not None:
            kv_w, kv_f = cache["kv_window"], cache["kv_full"]
            load = cache.get("moe_load")

        def period(carry, per_period):
            xc, aux_acc, kv_w, kv_f, load = carry
            p, wp, fp = per_period

            def inner(c, per_layer):
                xi, aux_i, kv_w, load = c
                j, lp = per_layer
                y, kv_w, aux, n_load = window_block(xi, lp, kv_w, p * n + j)
                if n_load is not None:
                    # the counter's rows are the model's layers; the
                    # window caches and experts stack window layers only
                    load = load.at[p * cfg.full_attn_every + j].add(n_load)
                return (y, aux_i + aux, kv_w, load), None

            with jax.named_scope("window"):
                (xc, aux_acc, kv_w, load), _ = jax.lax.scan(
                    inner, (xc, aux_acc, kv_w, load),
                    (jnp.arange(n), wp))
            with jax.named_scope("full"):
                xc, kv_f, aux, n_load = full_block(xc, fp, kv_f, p)
                if n_load is not None:
                    load = load.at[p * cfg.full_attn_every + n].add(n_load)
            return (xc, aux_acc + aux, kv_w, kv_f, load), None

        (x, aux, kv_w, kv_f, load), _ = jax.lax.scan(
            period, (x, zero, kv_w, kv_f, load),
            (jnp.arange(cfg.periods), window_layers, full_layers),
        )
        if cache is None:
            return x, None, aux
        new_cache = dict(cache, kv_window=kv_w, kv_full=kv_f)
        if load is not None:
            new_cache["moe_load"] = load
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

        def block_body(carry, per_block):
            xc, kv = carry
            blk, blk_params, st = per_block
            # shared attention (weights shared; block blk's own cache)
            y, kv, _, _ = self._attn_block(
                shared, xc, positions=positions, mode=mode, kv=kv,
                layer=blk, cache_len=cache_len, prefix_len=prefix_len,
            )
            y, new_state = jax.lax.scan(mamba_body, y, (blk_params, st))
            return (y, kv), new_state

        if self.remat:
            block_body = jax.checkpoint(block_body)
        kv = cache["attn_kv"] if cache is not None else None
        (x, kv), new_blk_state = jax.lax.scan(
            block_body, (x, kv),
            (jnp.arange(cfg.hybrid_blocks), dec["blocks"], blk_state),
        )
        if cache is None:
            return x, None, jnp.zeros((), jnp.float32)
        new_cache = dict(cache, block_state=new_blk_state, attn_kv=kv)
        if new_prelude_state is not None:
            new_cache["prelude_state"] = new_prelude_state
        return x, new_cache, jnp.zeros((), jnp.float32)

    def _run_stack(self, params, x, *, positions, mode, cache, prefix_len):
        if self.cfg.family == "hybrid":
            run = self._run_hybrid_stack
        elif self.cfg.full_attn_every:
            run = self._run_patterned_stack
        else:
            run = self._run_uniform_stack
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
