"""Pallas TPU names, resolved in one place.

All four kernels take the compiler-options class, the VMEM scratch
handle and the scalar-prefetch grid spec from this module, so a JAX
upgrade that moves one is a one-file fix.  Only the spellings of the JAX pinned in ``pyproject.toml`` are
accepted; an install without them fails at import with an error naming
the pin.  The ``resolve_*`` helpers take the module as an argument so
tests can check the failure without touching the installed JAX.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax.experimental.pallas.tpu as _pltpu

__all__ = [
    "CompilerParams",
    "PrefetchScalarGridSpec",
    "VMEM",
    "compiler_params",
    "resolve_compiler_params_cls",
    "resolve_vmem",
]


def resolve_compiler_params_cls(module: Any = _pltpu) -> Any:
    """The TPU compiler-options class, ``CompilerParams``."""
    cls = getattr(module, "CompilerParams", None)
    if cls is None:
        raise ImportError(
            "jax.experimental.pallas.tpu has no CompilerParams; this JAX "
            "version is outside the range pinned in pyproject.toml"
        )
    return cls


def resolve_vmem(module: Any = _pltpu) -> Any:
    """The VMEM memory-space handle used for scratch shapes."""
    vmem = getattr(module, "VMEM", None)
    if vmem is None:
        raise ImportError(
            "jax.experimental.pallas.tpu has no VMEM handle; this JAX "
            "version is outside the range pinned in pyproject.toml"
        )
    return vmem


CompilerParams = resolve_compiler_params_cls()
VMEM = resolve_vmem()
#: grid spec whose leading operands are scalars prefetched to SMEM
PrefetchScalarGridSpec = _pltpu.PrefetchScalarGridSpec


def compiler_params(
    *, dimension_semantics: Sequence[str], **kwargs: Any
) -> Any:
    """TPU compiler params; further keywords pass through verbatim."""
    return CompilerParams(
        dimension_semantics=tuple(dimension_semantics), **kwargs
    )
