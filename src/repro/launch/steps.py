"""Step builders: (arch × shape × mesh) -> a pjit-able function + abstract
inputs + in/out shardings.

This is the seam between the model zoo and the distribution layer, used by
the multi-pod dry-run, the roofline benchmark and the real drivers:

* ``train_4k``     lowers ``train_step``   (loss + grads + AdamW/ZeRO-1)
* ``prefill_32k``  lowers ``prefill_step`` (prompt -> cache + last logits)
* ``decode_32k`` / ``long_500k`` lower ``serve_step`` (1 new token against
  a KV cache of seq_len; SSM archs carry O(1) state instead)

Sharding policy (single pod 16x16 = ("data","model"); multi-pod adds
"pod"):

* weights: Megatron TP over "model" (heads/mlp/vocab/experts/ssm_inner);
  non-dividing dims fall back to replication per-tensor.
* train: batch over ("pod","data"); optimizer state ZeRO-1 over "data".
* prefill: batch over ("pod","data"); cache written out in the *decode*
  layout so serving needs no resharding step between phases.
* decode: context parallelism — KV-cache seq over "model", batch over
  ("pod","data"); works for every kv_heads count (paligemma kv=1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, ShapeSpec, get_config
from repro.distributed.sharding import (
    logical_to_pspec,
    make_rules,
    shard_pytree_specs,
)
from repro.models import abstract_params, build_model, logical_axes
from repro.models.config import ModelConfig
from repro.training.data import abstract_batch
from repro.training.optimizer import AdamWConfig, zero1_logical_tree
from repro.training.train_loop import make_train_step


@dataclasses.dataclass
class BuiltStep:
    """Everything needed to lower / run one cell."""

    fn: Callable                  # jit-able python callable
    abstract_args: Tuple[Any, ...]
    in_shardings: Any
    out_shardings: Any
    static_desc: str
    donate: Tuple[int, ...] = ()  # donated args (cache / params+opt): the
                                  # output reuses the input buffer — decode
                                  # would otherwise hold 2x the KV cache

    def jitted(self):
        return jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate,
        )

    def lower(self):
        return self.jitted().lower(*self.abstract_args)


def _named(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _batch_spec(mesh: Mesh, batch: int = 0) -> P:
    axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if batch:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if batch % n != 0:
            # shrink to the largest prefix that divides (batch=1 cells)
            if batch % mesh.shape.get("data", 1) == 0 and batch > 1:
                return P("data")
            return P()
    return P(axes if len(axes) > 1 else axes[0])


def _data_axis_size(mesh: Mesh) -> int:
    n = mesh.shape.get("data", 1)
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


# ---------------------------------------------------------------------------
# Cache sharding (decode layout; prefill writes this layout out)
# ---------------------------------------------------------------------------

_CACHE_LOGICAL_AXES = {
    # kv caches, head-major: (layers/blocks, batch, kv_heads, seq, head_dim)
    "kv": ("layers", "batch", "kv_heads", "kv_seq", "head_dim"),
    # whisper cross kv: seq is the (short) encoder length
    "cross": ("layers", "batch", "kv_heads", None, "head_dim"),
}


def _cache_pspec_tree(cache_abs: Any, mesh: Mesh, rules) -> Any:
    """PartitionSpec tree matching a cache pytree (keyed heuristically)."""
    def leaf_spec(path: Tuple, ab) -> P:
        keys = [getattr(p, "key", str(p)) for p in path]
        name = keys[0] if keys else ""
        if name == "len":
            return P()
        if name in ("kv", "attn_kv"):
            return logical_to_pspec(
                _CACHE_LOGICAL_AXES["kv"], ab.shape, mesh, rules
            )
        if name in ("cross_k", "cross_v"):
            return logical_to_pspec(
                _CACHE_LOGICAL_AXES["cross"], ab.shape, mesh, rules
            )
        if name in ("ssm_state", "prelude_state", "block_state"):
            # (stack..., batch, channels...) — shard batch; channels over
            # model where divisible
            nd = len(ab.shape)
            if keys[-1] == "conv":
                logical = (None,) * (nd - 3) + ("batch", None, "ssm_inner")
            elif nd >= 4 and keys[-1] == "ssm":
                # mamba1: (L,B,di,N); mamba2: (stack..,B,H,P,N)
                if nd == 4:
                    logical = (None, "batch", "ssm_inner", None)
                else:
                    logical = (None,) * (nd - 4) + (
                        "batch", "heads", None, None
                    )
            else:
                logical = (None,) * (nd - 1) + ("batch",)
            return logical_to_pspec(logical, ab.shape, mesh, rules)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abs)
    specs = [leaf_spec(path, ab) for path, ab in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    shape: ShapeSpec,
    *,
    microbatches: int = 16,
    rules_name: str = "tp",
    param_dtype=jnp.bfloat16,   # mixed precision: bf16 params, fp32 m/v
    compress_grads: bool = False,
    impl: str = "blockwise",
    remat: bool = True,
    grad_accum: str = "f32_sharded",
    opt_cfg: Optional[AdamWConfig] = None,
) -> BuiltStep:
    model = build_model(cfg, impl=impl, remat=remat)
    rules = make_rules(rules_name)
    bp = model.blueprint()
    abs_p = abstract_params(bp, param_dtype)
    logical = logical_axes(bp)
    p_specs = shard_pytree_specs(logical, abs_p, mesh, rules)

    # optimizer state: ZeRO-1 over data
    z_logical = zero1_logical_tree(logical, abs_p, _data_axis_size(mesh))
    z_specs = shard_pytree_specs(z_logical, abs_p, mesh, rules)
    abs_opt = {
        "m": jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abs_p
        ),
        "v": jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abs_p
        ),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    opt_specs = {"m": z_specs, "v": z_specs, "step": P()}

    abs_b = abstract_batch(cfg, shape.global_batch, shape.seq_len)
    bspec = _batch_spec(mesh, shape.global_batch)
    b_specs = {k: P(*bspec) for k in abs_b}

    step = make_train_step(
        model, cfg, opt_cfg or AdamWConfig(),
        microbatches=microbatches, compress_grads=compress_grads,
        grad_specs=z_specs, batch_spec=bspec, grad_accum=grad_accum,
    )
    if compress_grads:
        abs_opt["ef_error"] = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abs_p
        )
        opt_specs["ef_error"] = z_specs

    metrics_specs = {"loss": P(), "grad_norm": P(), "step": P()}
    return BuiltStep(
        fn=step,
        abstract_args=(abs_p, abs_opt, abs_b),
        in_shardings=_named((p_specs, opt_specs, b_specs), mesh),
        out_shardings=_named((p_specs, opt_specs, metrics_specs), mesh),
        static_desc=(
            f"train {cfg.name} seq={shape.seq_len} gb={shape.global_batch} "
            f"mb={microbatches}"
        ),
        donate=(0, 1),        # params + opt_state update in place
    )


def build_prefill_step(
    cfg: ModelConfig,
    mesh: Mesh,
    shape: ShapeSpec,
    *,
    rules_name: str = "tp",
    cache_rules_name: str = "decode_cp",
    dtype=jnp.bfloat16,
    impl: str = "blockwise",
) -> BuiltStep:
    model = build_model(cfg, impl=impl)
    rules = make_rules(rules_name)
    cache_rules = make_rules(cache_rules_name)
    bp = model.blueprint()
    abs_p = abstract_params(bp, dtype)
    p_specs = shard_pytree_specs(logical_axes(bp), abs_p, mesh, rules)

    B, S = shape.global_batch, shape.seq_len
    cache_len = S + (cfg.frontend_seq if (cfg.frontend and not
                                          cfg.is_encdec) else 0)
    abs_cache = model.abstract_cache(B, cache_len, dtype)
    cache_specs = _cache_pspec_tree(abs_cache, mesh, cache_rules)
    bspec = _batch_spec(mesh, B)
    abs_tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)

    extra_abs = ()
    extra_specs = ()
    if cfg.is_encdec:
        extra_abs = (
            jax.ShapeDtypeStruct((B, cfg.frontend_seq, cfg.d_model), dtype),
        )
        extra_specs = (P(*bspec),)

        def fn(params, tokens, frames, cache):
            return model.prefill(params, frames, tokens, cache, dtype=dtype)
    elif cfg.frontend:
        extra_abs = (
            jax.ShapeDtypeStruct((B, cfg.frontend_seq, cfg.d_model), dtype),
        )
        extra_specs = (P(*bspec),)

        def fn(params, tokens, patches, cache):
            return model.prefill(
                params, tokens, cache, prefix_embed=patches, dtype=dtype
            )
    else:

        def fn(params, tokens, cache):
            return model.prefill(params, tokens, cache, dtype=dtype)

    logits_spec = P(*bspec)
    return BuiltStep(
        fn=fn,
        abstract_args=(abs_p, abs_tokens) + extra_abs + (abs_cache,),
        in_shardings=_named(
            (p_specs, P(*bspec)) + extra_specs + (cache_specs,), mesh
        ),
        out_shardings=_named((logits_spec, cache_specs), mesh),
        static_desc=f"prefill {cfg.name} seq={S} gb={B}",
        donate=(len((abs_p, abs_tokens) + extra_abs),),   # the cache
    )


def build_serve_step(
    cfg: ModelConfig,
    mesh: Mesh,
    shape: ShapeSpec,
    *,
    rules_name: str = "tp",
    cache_rules_name: str = "decode_cp",
    dtype=jnp.bfloat16,
    kv_dtype=None,            # e.g. jnp.float8_e4m3fn: halves KV traffic
    impl: str = "blockwise",
) -> BuiltStep:
    """One-token decode step against a cache of shape.seq_len tokens."""
    model = build_model(cfg, impl=impl)
    rules = make_rules(rules_name)
    cache_rules = make_rules(cache_rules_name)
    bp = model.blueprint()
    abs_p = abstract_params(bp, dtype)
    p_specs = shard_pytree_specs(logical_axes(bp), abs_p, mesh, rules)

    B, S = shape.global_batch, shape.seq_len
    cache_len = S + (cfg.frontend_seq if (cfg.frontend and not
                                          cfg.is_encdec) else 0)
    abs_cache = model.abstract_cache(B, cache_len, kv_dtype or dtype)
    cache_specs = _cache_pspec_tree(abs_cache, mesh, cache_rules)
    bspec = _batch_spec(mesh, B)
    abs_tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    def fn(params, tokens, cache):
        logits, new_cache = model.decode_step(
            params, tokens, cache, dtype=dtype
        )
        # greedy next token (serving returns tokens, not logit tensors)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    return BuiltStep(
        fn=fn,
        abstract_args=(abs_p, abs_tokens, abs_cache),
        in_shardings=_named((p_specs, P(*bspec), cache_specs), mesh),
        out_shardings=_named((P(*bspec), cache_specs), mesh),
        static_desc=f"decode {cfg.name} ctx={S} gb={B}",
        donate=(2,),          # the cache
    )


def build_step(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    **kwargs,
) -> BuiltStep:
    """Dispatch on the shape's kind."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        return build_train_step(cfg, mesh, shape, **kwargs)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, mesh, shape, **kwargs)
    if shape.kind == "decode":
        return build_serve_step(cfg, mesh, shape, **kwargs)
    raise ValueError(f"unknown shape kind {shape.kind}")


def input_specs(arch: str, shape_name: str, mesh: Mesh, **kwargs):
    """ShapeDtypeStruct stand-ins for every model input of a cell (the
    dry-run contract: weak-type-correct, shardable, no allocation)."""
    return build_step(arch, shape_name, mesh, **kwargs).abstract_args
