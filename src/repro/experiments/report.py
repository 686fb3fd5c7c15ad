"""Scenario reports: per-cell metrics, aggregation, JSON artifacts.

A :class:`ScenarioReport` is the output of ``ScenarioSuite.run``: one
:class:`CellResult` per scenario (P50/P90/P99 latency, failure rate,
cost-vs-OD, availability, preemption counts, wall-clock), plus suite-level
metadata.  ``save()`` writes the JSON artifact under ``artifacts/bench/``.

Artifact schema (``schema: 1``)::

    {
      "schema": 1,
      "suite": "latency-sweep",
      "engine": "vector",
      "workers": 1,
      "wall_s": 12.3,
      "n_cells": 27,
      "cells": [
        {
          "policy": "spothedge", "trace": "aws-1",
          "workload": "poisson", "seed": 5,
          "n_requests": 25902, "n_completed": 25721, "n_failed": 181,
          "failure_rate": 0.007, "mean_s": 3.1,
          "p50_s": 2.9, "p90_s": 4.9, "p99_s": 9.4,
          "total_cost": 101.2, "cost_vs_ondemand": 0.41,
          "availability": 0.97, "n_preemptions": 11,
          "n_launch_failures": 3, "wall_s": 0.41
        }, ...
      ]
    }
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

from repro.serving.result import ServingResult

__all__ = ["CellResult", "ScenarioReport", "SCHEMA_VERSION"]

SCHEMA_VERSION = 1


def _finite(v: float) -> Optional[float]:
    return float(v) if np.isfinite(v) else None


@dataclasses.dataclass
class CellResult:
    """One scenario's labels + headline metrics."""

    labels: Dict[str, Any]           # axis -> value (policy, trace, ...)
    n_requests: int
    n_completed: int
    n_failed: int
    failure_rate: float
    mean_s: float
    p50_s: float
    p90_s: float
    p99_s: float
    total_cost: float
    cost_vs_ondemand: float
    availability: float
    n_preemptions: int
    n_launch_failures: int
    wall_s: float
    # token-level metrics — populated only for replica_model="token"
    # cells and omitted from to_dict() when None, so request-level
    # artifacts keep their historical shape
    ttft_p50_s: Optional[float] = None
    ttft_p99_s: Optional[float] = None
    tpot_p50_s: Optional[float] = None
    tpot_p99_s: Optional[float] = None
    goodput_rps: Optional[float] = None
    slo_attainment: Optional[float] = None
    # grace-period migration counters (repro.migration) — token cells
    # only; zero when the cell ran with migration disabled
    n_drained_seqs: Optional[int] = None
    n_migrated_seqs: Optional[int] = None
    migrated_kv_tokens: Optional[int] = None
    saved_prefill_tokens: Optional[int] = None
    n_retried_requests: Optional[int] = None
    lost_kv_tokens: Optional[int] = None
    # observability (repro.obs) — picklable snapshots so process-parallel
    # sweep workers carry them back to the parent; omitted when the cell
    # ran with detail "off" (or recorded nothing)
    metrics: Optional[Dict[str, Any]] = None
    obs_event_counts: Optional[Dict[str, int]] = None
    obs_windows: Optional[List[Dict[str, Any]]] = None
    # SLO burn-rate summary + sampled-span count (repro.obs.slo /
    # repro.obs.spans); present only at detail "full"
    slo_burn: Optional[Dict[str, Any]] = None
    n_spans: Optional[int] = None

    @staticmethod
    def from_result(
        labels: Mapping[str, Any], res: ServingResult, wall_s: float
    ) -> "CellResult":
        lat = res.latencies_s
        tok = res.token
        return CellResult(
            labels=dict(labels),
            n_requests=res.n_requests,
            n_completed=res.n_completed,
            n_failed=res.n_failed,
            failure_rate=res.failure_rate,
            mean_s=float(lat.mean()) if len(lat) else float("nan"),
            p50_s=res.pct(50),
            p90_s=res.pct(90),
            p99_s=res.pct(99),
            total_cost=res.total_cost,
            cost_vs_ondemand=res.cost_vs_ondemand,
            availability=res.availability,
            n_preemptions=res.n_preemptions,
            n_launch_failures=res.n_launch_failures,
            wall_s=wall_s,
            # NaN percentiles (a token cell with zero completions) become
            # None so the JSON artifact stays strictly parseable
            ttft_p50_s=_finite(tok.ttft_pct(50)) if tok else None,
            ttft_p99_s=_finite(tok.ttft_pct(99)) if tok else None,
            tpot_p50_s=_finite(tok.tpot_pct(50)) if tok else None,
            tpot_p99_s=_finite(tok.tpot_pct(99)) if tok else None,
            goodput_rps=tok.goodput_rps if tok else None,
            slo_attainment=tok.slo_attainment if tok else None,
            n_drained_seqs=tok.n_drained_seqs if tok else None,
            n_migrated_seqs=tok.n_migrated_seqs if tok else None,
            migrated_kv_tokens=tok.migrated_kv_tokens if tok else None,
            saved_prefill_tokens=tok.saved_prefill_tokens if tok else None,
            n_retried_requests=res.n_retried_requests if tok else None,
            lost_kv_tokens=res.lost_kv_tokens if tok else None,
            metrics=res.metrics,
            obs_event_counts=(
                res.obs.event_counts() if res.obs is not None else None
            ),
            obs_windows=(
                res.obs.window_records() or None
                if res.obs is not None else None
            ),
            slo_burn=(
                res.obs.slo_burn_summary()
                if res.obs is not None else None
            ),
            n_spans=(
                len(res.obs.span_records()) or None
                if res.obs is not None else None
            ),
        )

    @property
    def cell_id(self) -> str:
        return "/".join(str(v) for v in self.labels.values())

    def to_dict(self, round_to: Optional[int] = 6) -> Dict[str, Any]:
        out: Dict[str, Any] = dict(self.labels)
        for f in dataclasses.fields(self):
            if f.name == "labels":
                continue
            v = getattr(self, f.name)
            if v is None:
                continue
            if round_to is not None and isinstance(v, float) \
                    and np.isfinite(v):
                v = round(v, round_to)
            out[f.name] = v
        return out


@dataclasses.dataclass
class ScenarioReport:
    """All cell results of one suite run, JSON-serializable."""

    suite: str
    engine: str
    workers: int
    cells: List[CellResult]
    wall_s: float
    # suite-level metrics: every cell's registry snapshot merged
    # (repro.obs.MetricsRegistry.merge_snapshots); None when no cell
    # recorded any
    metrics: Optional[Dict[str, Any]] = None

    # -- access ----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.cells)

    def select(self, **labels: Any) -> List[CellResult]:
        """Cells whose labels match every given ``axis=value``."""
        return [
            c for c in self.cells
            if all(c.labels.get(k) == v for k, v in labels.items())
        ]

    def burn_ranking(self) -> List[CellResult]:
        """Cells with a burn summary, worst error-budget burn first.

        Ranks by time spent alerting, then by alert-window count — the
        cell a paging SLO would flag first.  Cells that ran below detail
        ``full`` (no burn windows) are omitted.
        """
        burned = [c for c in self.cells if c.slo_burn]
        return sorted(
            burned,
            key=lambda c: (
                -float(c.slo_burn.get("alert_minutes", 0.0)),
                -int(c.slo_burn.get("alert_windows", 0)),
            ),
        )

    # -- serialization ---------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        out = {
            "schema": SCHEMA_VERSION,
            "suite": self.suite,
            "engine": self.engine,
            "workers": self.workers,
            "wall_s": round(self.wall_s, 3),
            "n_cells": len(self.cells),
            "cells": [c.to_dict() for c in self.cells],
        }
        if self.metrics is not None:
            out["metrics"] = self.metrics
        return out

    def save(self, directory: str = os.path.join("artifacts", "bench"),
             stem: Optional[str] = None) -> str:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(
            directory, f"{stem or 'scenario_' + self.suite}.json"
        )
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, default=str)
        return path

    # -- display ---------------------------------------------------------
    def summary(self) -> str:
        lines = [
            f"suite {self.suite}: {len(self.cells)} cells, "
            f"engine={self.engine}, workers={self.workers}, "
            f"wall={self.wall_s:.1f}s"
        ]
        for c in self.cells:
            lines.append(
                f"  {c.cell_id:<44s} p50={c.p50_s:7.2f}s "
                f"p99={c.p99_s:8.2f}s fail={c.failure_rate:7.2%} "
                f"cost={c.cost_vs_ondemand:6.2%} "
                f"avail={c.availability:.2%} [{c.wall_s:.2f}s]"
            )
        burned = self.burn_ranking()
        if burned:
            lines.append("  SLO burn (worst first):")
            for c in burned:
                b = c.slo_burn
                lines.append(
                    f"    {c.cell_id:<42s} "
                    f"alert={b['alert_minutes']:6.1f}min "
                    f"({b['alert_windows']}/{b['windows']} windows)"
                )
        return "\n".join(lines)
