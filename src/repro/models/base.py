"""Parameter blueprints: one definition, three views.

A model definition builds a *blueprint* — a pytree (nested dict) of
:class:`ParamSpec` leaves.  From it we derive:

* ``init_params(bp, key)``     materialized parameters,
* ``abstract_params(bp)``      ``jax.ShapeDtypeStruct`` stand-ins — the
                               multi-pod dry-run lowers full-size models
                               (35B+) without allocating anything,
* ``logical_axes(bp)``         logical sharding axes per leaf, consumed by
                               ``repro.distributed.sharding`` rule tables,
* ``param_count(bp)``          exact parameter count (roofline §MODEL_FLOPS).

Logical axis names used throughout the zoo:

    "embed"     residual/model dimension
    "heads"     query heads            "kv_heads"  key/value heads
    "head_dim"  per-head dim           "mlp"       feed-forward hidden
    "vocab"     vocabulary             "layers"    stacked (scanned) layers
    "experts"   MoE experts            "expert_mlp" per-expert hidden
    "ssm_inner" SSM inner dim          "ssm_state" SSM state dim
    "conv"      conv kernel taps        None        never sharded
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declares one parameter tensor."""

    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | embed
    scale: float = 1.0          # stddev multiplier for "normal"
    dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.logical):
            raise ValueError(
                f"shape {self.shape} and logical {self.logical} rank mismatch"
            )

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


Blueprint = Any  # nested dict with ParamSpec leaves


def _is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def _fan_in(spec: ParamSpec) -> int:
    """Fan-in for variance scaling: all dims but the last."""
    if len(spec.shape) <= 1:
        return max(spec.shape[0] if spec.shape else 1, 1)
    return max(int(np.prod(spec.shape[:-1])), 1)


def _init_leaf(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        # embedding init: unit normal scaled down
        std = spec.scale
        return (
            jax.random.normal(key, spec.shape, jnp.float32) * std
        ).astype(spec.dtype)
    if spec.init == "normal":
        # truncated-normal variance scaling (fan-in), like flax defaults
        std = spec.scale / math.sqrt(_fan_in(spec))
        x = jax.random.truncated_normal(key, -2.0, 2.0, spec.shape,
                                        jnp.float32)
        return (x * std).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


# one program per leaf: the f32 draw fuses into the cast, so a bf16 leaf
# never holds an f32 copy of itself on the device
_init_leaf_jit = jax.jit(_init_leaf, static_argnums=0)


def init_params(bp: Blueprint, key: jax.Array, dtype: Any = None) -> Any:
    """Materialize parameters (smoke tests / examples / checkpoints).

    ``dtype`` overrides every leaf's dtype (serving weights in bf16)."""
    leaves, treedef = jax.tree_util.tree_flatten(bp, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    specs = [dataclasses.replace(s, dtype=dtype or s.dtype) for s in leaves]
    out = [_init_leaf_jit(s, k) for s, k in zip(specs, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(bp: Blueprint, dtype: Any = None) -> Any:
    """ShapeDtypeStruct view — zero allocation (dry-run input)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype),
        bp,
        is_leaf=_is_spec,
    )


def logical_axes(bp: Blueprint) -> Any:
    """Logical-axis pytree, mirroring the parameter structure."""
    return jax.tree_util.tree_map(lambda s: s.logical, bp, is_leaf=_is_spec)


def param_count(bp: Blueprint) -> int:
    return sum(
        s.size for s in jax.tree_util.tree_leaves(bp, is_leaf=_is_spec)
    )


def cast_params(params: Any, dtype: Any) -> Any:
    """Cast float leaves (weights) to ``dtype`` — serving runs bf16."""
    def _cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, params)


# ---------------------------------------------------------------------------
# Spec construction helpers (used by the model definitions)
# ---------------------------------------------------------------------------


def dense_spec(
    in_dim: int,
    out_dim: int,
    in_axis: Optional[str],
    out_axis: Optional[str],
    *,
    scale: float = 1.0,
    dtype: Any = jnp.float32,
) -> ParamSpec:
    return ParamSpec((in_dim, out_dim), (in_axis, out_axis), "normal",
                     scale, dtype)


def stacked(spec: ParamSpec, layers: int) -> ParamSpec:
    """Stack a per-layer spec along a leading scanned 'layers' axis."""
    return ParamSpec(
        (layers,) + spec.shape,
        ("layers",) + spec.logical,
        spec.init,
        spec.scale,
        spec.dtype,
    )


def stack_blueprint(bp: Blueprint, layers: int) -> Blueprint:
    """Stack every leaf of a per-layer blueprint for ``lax.scan``."""
    return jax.tree_util.tree_map(
        lambda s: stacked(s, layers), bp, is_leaf=_is_spec
    )
