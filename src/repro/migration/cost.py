"""Migration cost model: KV transfer pricing + elastic re-shard pricing.

Two cost surfaces, both pure arithmetic:

* **KV transfer** — a migrated sequence ships ``resident_tokens ×
  kv_bytes_per_token`` over the inter-zone link; int8 KV compression
  (the symmetric per-tensor scheme of ``distributed/compression.py``,
  bf16 → int8) halves the bytes.  Transfer time is
  ``link_latency + bytes / bandwidth``.

* **Elastic re-shard** — SpotServe-style re-parallelization: instead of
  dying when chips are lost, shrink one mesh axis (power-of-two steps,
  the policy of ``distributed/elastic.plan_remesh``) and price the state
  movement.  Shrinking the ``data`` axis relocates the dropped replicas'
  KV; shrinking a model axis additionally re-partitions the weights.
  This is a pricing API for the planner and reports — replicas in the
  serving engines are single-instance, so the engines consume only
  the KV-transfer surface.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

__all__ = [
    "INT8_KV_FACTOR",
    "compression_factor",
    "kv_transfer_bytes",
    "kv_transfer_s",
    "ReshardCost",
    "plan_reshard",
]

# bf16 KV quantized to int8 with a per-tensor scale: 2 bytes -> 1 byte
INT8_KV_FACTOR = 0.5


def compression_factor(compression: str) -> float:
    """Bytes-on-the-wire multiplier for a KV compression mode."""
    if compression == "int8":
        return INT8_KV_FACTOR
    if compression == "none":
        return 1.0
    raise ValueError(f"unknown KV compression mode {compression!r}")


def kv_transfer_bytes(
    resident_tokens: int,
    kv_bytes_per_token: float,
    compression: str = "none",
) -> float:
    """Bytes a migration must move for one sequence's resident KV."""
    return (
        float(resident_tokens)
        * float(kv_bytes_per_token)
        * compression_factor(compression)
    )


def kv_transfer_s(
    nbytes: float,
    bandwidth_bytes_per_s: float,
    link_latency_s: float = 0.0,
) -> float:
    """Wall-clock seconds to move ``nbytes`` over one link."""
    if nbytes <= 0.0:
        return float(link_latency_s)
    if bandwidth_bytes_per_s <= 0.0:
        return float("inf")
    return float(link_latency_s) + float(nbytes) / float(
        bandwidth_bytes_per_s
    )


# ---------------------------------------------------------------------------
# Elastic re-shard pricing (SpotServe §4.2 analogue)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReshardCost:
    """Priced plan for continuing on fewer chips instead of dying."""

    old_shape: Tuple[int, ...]
    new_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    dropped_chips: int
    moved_bytes: float              # state that crosses the network
    transfer_s: float               # moved_bytes over the link
    relower_s: float                # recompile/re-lower the step fn

    @property
    def new_chip_count(self) -> int:
        n = 1
        for s in self.new_shape:
            n *= s
        return n

    @property
    def total_s(self) -> float:
        return self.transfer_s + self.relower_s

    def to_remesh_plan(self):
        """The equivalent ``distributed.elastic.RemeshPlan`` (lazy import:
        ``distributed/`` pulls in jax, which this package must not require
        at import time)."""
        try:
            from repro.distributed.elastic import RemeshPlan
        except Exception:  # jax unavailable: duck-typed stand-in
            @dataclasses.dataclass(frozen=True)
            class RemeshPlan:  # type: ignore[no-redef]
                old_shape: Tuple[int, ...]
                new_shape: Tuple[int, ...]
                axis_names: Tuple[str, ...]
                dropped_chips: int
        return RemeshPlan(
            old_shape=self.old_shape,
            new_shape=self.new_shape,
            axis_names=self.axis_names,
            dropped_chips=self.dropped_chips,
        )


def plan_reshard(
    mesh_shape: Sequence[int],
    axis_names: Sequence[str],
    surviving_chips: int,
    *,
    kv_resident_bytes: float = 0.0,
    weight_bytes: float = 0.0,
    bandwidth_bytes_per_s: float,
    link_latency_s: float = 0.0,
    relower_s: float = 2.0,
    shrink_axis: str = "data",
) -> Optional[ReshardCost]:
    """Price a SpotServe-style degree change onto ``surviving_chips``.

    Mirrors ``distributed.elastic.plan_remesh``'s policy — shrink only
    ``shrink_axis``, power-of-two steps — in pure arithmetic.  Returns
    ``None`` when no shrink of that axis fits the survivors (the caller
    falls back to kill-and-restart).

    Cost model: the dropped chips' share of resident KV always moves
    (``kv_resident_bytes × dropped/old``); weights move only when a
    *model* axis changes degree (data-parallel survivors already hold
    full weight shards).
    """
    names = tuple(axis_names)
    shape = tuple(int(s) for s in mesh_shape)
    if len(names) != len(shape):
        raise ValueError(
            f"mesh_shape {shape} and axis_names {names} length mismatch"
        )
    if shrink_axis not in names:
        raise ValueError(f"mesh has no axis {shrink_axis!r}")
    idx = names.index(shrink_axis)
    other = 1
    for i, s in enumerate(shape):
        if i != idx:
            other *= s
    old_chips = other * shape[idx]
    new_dim = shape[idx]
    while new_dim > 1 and other * new_dim > surviving_chips:
        new_dim //= 2
    if other * new_dim > surviving_chips:
        return None
    new_shape = tuple(
        new_dim if i == idx else s for i, s in enumerate(shape)
    )
    dropped = old_chips - other * new_dim
    frac = dropped / old_chips
    moved = kv_resident_bytes * frac
    if shrink_axis != "data":
        moved += weight_bytes * frac
    transfer = kv_transfer_s(moved, bandwidth_bytes_per_s, link_latency_s)
    return ReshardCost(
        old_shape=shape,
        new_shape=new_shape,
        axis_names=names,
        dropped_chips=dropped,
        moved_bytes=moved,
        transfer_s=transfer,
        relower_s=relower_s,
    )
