"""What a serving run returns, and the engine's window sampler.

* :class:`ServingResult` — the §5.1 headline metrics of one run
  (P50/P90/P99 end-to-end latency, failure rate, cost, availability),
  with ``TokenStats`` attached in token mode and the run's observability
  snapshot;
* :class:`WindowSampler` — the windowed data-plane sampler (observability
  detail ``full``) and the SLO burn-rate monitor behind it;
* ``REPLICA_MODELS`` — the replica models an engine accepts:
  ``"request"`` (M/G/c slots with frozen service times) and ``"token"``
  (continuous batching, ``repro.serving.token``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import numpy as np

from repro.cluster.instance import Instance, InstanceKind, InstanceState
from repro.obs.events import WindowSampleEvent
from repro.obs.recorder import ObsRecorder
from repro.obs.slo import SLOBurnMonitor
from repro.serving.token.metrics import TokenRecord, TokenStats

__all__ = ["REPLICA_MODELS", "ServingResult", "WindowSampler"]

REPLICA_MODELS = ("request", "token")


class WindowSampler:
    """Windowed data-plane sampling (observability detail ``full``).

    The engine feeds it order-independent inputs only (cumulative
    counters + instantaneous cluster state at the control-tick
    boundary), so a window sample cannot depend on the order in which
    requests resolved inside the window.  The SLO burn-rate monitor
    hangs off the same choke point: every sample window also folds its
    error counts into the trailing fast/slow burn windows and emits one
    :class:`~repro.obs.events.SLOBurnEvent`.
    """

    def __init__(
        self,
        obs: ObsRecorder,
        slo_ttft_s: Optional[float] = None,
        slo_tpot_s: Optional[float] = None,
    ) -> None:
        self.obs = obs
        self._next_t = 0.0
        self._last_t = 0.0
        self._last_completed = 0
        self._last_failed = 0
        self._records_seen = 0
        self._burn = SLOBurnMonitor(
            obs.slo_burn, slo_ttft_s=slo_ttft_s, slo_tpot_s=slo_tpot_s
        )

    def maybe_emit(
        self,
        now: float,
        *,
        delivered: int,
        completed: int,
        failed: int,
        instances: Sequence[Instance],
        token_records: Optional[Sequence[TokenRecord]] = None,
    ) -> None:
        if not self.obs.wants_windows or now < self._next_t:
            return
        n_ready = n_spot = n_od = 0
        cost_per_h = 0.0
        for inst in instances:
            cost_per_h += inst.hourly_price
            if inst.state is InstanceState.READY:
                n_ready += 1
                if inst.kind is InstanceKind.SPOT:
                    n_spot += 1
                else:
                    n_od += 1
        elapsed = now - self._last_t
        delta = completed - self._last_completed
        goodput = delta / elapsed if elapsed > 0 else 0.0
        ttft_p50: Optional[float] = None
        new: Optional[Sequence[TokenRecord]] = None
        if token_records is not None:
            new = token_records[self._records_seen:]
            self._records_seen = len(token_records)
            if new:
                # median over the window's completion multiset: order-
                # independent, so engine-internal completion order
                # differences cannot leak into the sample
                ttft_p50 = float(np.median(sorted(
                    r.ttft_s for r in new
                )))
        self.obs.emit_window(WindowSampleEvent(
            t=now,
            queue_depth=delivered - completed - failed,
            n_ready=n_ready,
            n_spot=n_spot,
            n_od=n_od,
            cost_per_h=cost_per_h,
            n_completed=completed,
            n_failed=failed,
            goodput_rps=goodput,
            ttft_p50_s=ttft_p50,
        ))
        # burn rates from the same order-independent window deltas
        self.obs.emit_window(self._burn.observe(
            now,
            d_completed=delta,
            d_failed=failed - self._last_failed,
            new_records=new,
        ))
        self._last_t = now
        self._last_completed = completed
        self._last_failed = failed
        self._next_t = now + self.obs.window_s


@dataclasses.dataclass
class ServingResult:
    policy: str
    trace: str
    workload: str
    n_requests: int
    n_completed: int
    n_failed: int
    latencies_s: np.ndarray
    total_cost: float
    spot_cost: float
    od_cost: float
    cost_vs_ondemand: float
    availability: float
    n_preemptions: int = 0
    n_launch_failures: int = 0
    # token-level metrics (replica_model="token" runs only)
    token: Optional[TokenStats] = None
    # uniform kill accounting across replica models:
    # requests pushed back to the client for retry after a replica died,
    # and KV tokens destroyed doing so (always 0 in request mode)
    n_retried_requests: int = 0
    lost_kv_tokens: int = 0
    # observability (repro.obs): the run's metrics-registry snapshot and
    # the recorder holding the typed event stream (None when detail=off)
    metrics: Optional[Dict[str, Any]] = None
    obs: Optional[ObsRecorder] = None

    @property
    def failure_rate(self) -> float:
        return self.n_failed / max(self.n_requests, 1)

    def pct(self, q: float) -> float:
        if len(self.latencies_s) == 0:
            return float("nan")
        return float(np.percentile(self.latencies_s, q))

    def summary(self) -> str:
        out = (
            f"{self.policy:>16s} @ {self.trace}/{self.workload} "
            f"p50={self.pct(50):6.2f}s p90={self.pct(90):6.2f}s "
            f"p99={self.pct(99):7.2f}s fail={self.failure_rate:6.2%} "
            f"cost={self.cost_vs_ondemand:6.2%} avail={self.availability:.2%}"
        )
        if self.token is not None:
            out += (
                f" ttft_p50={self.token.ttft_pct(50):5.2f}s "
                f"goodput={self.token.goodput_rps:.3f}req/s "
                f"slo={self.token.slo_attainment:.2%}"
            )
        return out
