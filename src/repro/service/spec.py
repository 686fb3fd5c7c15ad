"""Declarative service specification — the paper's Listing 1 as typed data.

A :class:`ServiceSpec` is the single front door to this repro: it names the
model, the spot trace, the ``any_of`` resource filter, the replica policy
(SpotHedge or a baseline) with its knobs, the autoscaler, the request
workload and the simulation horizon.  ``repro.service.builder`` compiles a
spec into the resolved Catalog/SpotTrace/Policy/Autoscaler/serving-engine
stack; ``repro.service.Service`` runs it.

All specs are frozen dataclasses with ``to_dict`` round-trips, so a spec is
equally a Python literal, a JSON object, or a YAML file:

    service:
      name: chat
      model: command-r-35b
      trace: aws-3
      resources:
        instance_type: g5.48xlarge
        any_of:
          - region: us-west-2
          - region: us-east-1
      replica_policy:
        name: spothedge
        overprovision: 2
      autoscaler:
        kind: load
        target: 4
        qps_per_replica: 0.8

Local shape/positivity validation lives in ``__post_init__``; cross-registry
checks (is the policy registered? does the trace exist?) live in
``ServiceSpec.validate`` so module import stays cheap.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Tuple, Union

from repro.migration.config import MigrationSpec

__all__ = [
    "SpecError",
    "PlacementFilter",
    "ResourceSpec",
    "ReplicaPolicySpec",
    "AutoscalerSpec",
    "WorkloadSpec",
    "LatencySpec",
    "ForecastSpec",
    "SLOSpec",
    "ServingSpec",
    "SLOBurnSpec",
    "ObservabilitySpec",
    "MigrationSpec",
    "SimSpec",
    "SweepSpec",
    "ServiceSpec",
]


class SpecError(ValueError):
    """A service spec is malformed; the message says which field and why."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SpecError(msg)


def _clean(d: Dict[str, Any]) -> Dict[str, Any]:
    """Drop ``None`` values so to_dict output stays minimal and re-loadable."""
    return {k: v for k, v in d.items() if v is not None}


# ---------------------------------------------------------------------------
# Resources (Listing 1: resources + any_of)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlacementFilter:
    """One ``any_of`` entry: a zone matches if every set field matches.

    An entry with no fields set matches everything (Listing 1 uses bare
    ``cloud: aws`` entries; ``{}`` would mean "anywhere").
    """

    cloud: Optional[str] = None
    region: Optional[str] = None
    zone: Optional[str] = None

    def matches(self, cloud: str, region: str, zone: str) -> bool:
        return (
            (self.cloud is None or self.cloud == cloud)
            and (self.region is None or self.region == region)
            and (self.zone is None or self.zone == zone)
        )

    def to_dict(self) -> Dict[str, Any]:
        return _clean(dataclasses.asdict(self))

    @staticmethod
    def from_dict(d: Mapping[str, Any]) -> "PlacementFilter":
        unknown = set(d) - {"cloud", "region", "zone"}
        _require(
            not unknown,
            f"any_of entry has unknown keys {sorted(unknown)}; "
            "allowed: cloud, region, zone",
        )
        return PlacementFilter(
            cloud=d.get("cloud"), region=d.get("region"), zone=d.get("zone")
        )


@dataclasses.dataclass(frozen=True)
class ResourceSpec:
    """What to run on, and where placement is allowed.

    ``any_of=None`` (the default) leaves every zone of the trace enabled;
    an explicit empty tuple is rejected — it would match nothing.
    """

    instance_type: str = "p3.2xlarge"
    any_of: Optional[Tuple[PlacementFilter, ...]] = None
    exclude_zones: Tuple[str, ...] = ()

    def __post_init__(self) -> None:
        _require(
            bool(self.instance_type),
            "resources.instance_type must be a non-empty string",
        )
        if self.any_of is not None:
            _require(
                len(self.any_of) > 0,
                "resources.any_of is empty — it would match no zones; "
                "omit the field to allow every zone of the trace, or add "
                "at least one {cloud|region|zone} filter",
            )

    def allows(self, cloud: str, region: str, zone: str) -> bool:
        if zone in self.exclude_zones:
            return False
        if self.any_of is None:
            return True
        return any(f.matches(cloud, region, zone) for f in self.any_of)

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"instance_type": self.instance_type}
        if self.any_of is not None:
            out["any_of"] = [f.to_dict() for f in self.any_of]
        if self.exclude_zones:
            out["exclude_zones"] = list(self.exclude_zones)
        return out


# ---------------------------------------------------------------------------
# Replica policy (SpotHedge + baselines)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReplicaPolicySpec:
    """Which placement policy manages the fleet, and its knobs.

    ``overprovision`` / ``dynamic_fallback`` / ``min_ondemand`` are the
    paper's §3 knobs (``N_Extra``, Dynamic Fallback, the §4 custom-policy
    on-demand floor); they map onto SpotHedge-family constructor args.
    ``args`` passes any further keyword verbatim to the policy constructor
    (e.g. ``od_fraction`` for ``static_mixture``).
    """

    name: str = "spothedge"
    overprovision: Optional[int] = None
    dynamic_fallback: Optional[bool] = None
    min_ondemand: Optional[int] = None
    args: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        _require(bool(self.name), "replica_policy.name must be set")
        if self.overprovision is not None:
            _require(
                self.overprovision >= 0,
                f"replica_policy.overprovision must be >= 0, "
                f"got {self.overprovision}",
            )
        if self.min_ondemand is not None:
            _require(
                self.min_ondemand >= 0,
                f"replica_policy.min_ondemand must be >= 0, "
                f"got {self.min_ondemand}",
            )

    def policy_kwargs(self) -> Dict[str, Any]:
        """Constructor kwargs for ``make_policy`` (set fields only)."""
        kw: Dict[str, Any] = dict(self.args)
        if self.overprovision is not None:
            kw["num_overprovision"] = self.overprovision
        if self.dynamic_fallback is not None:
            kw["dynamic_ondemand_fallback"] = self.dynamic_fallback
        if self.min_ondemand is not None:
            kw["min_ondemand"] = self.min_ondemand
        return kw

    def to_dict(self) -> Dict[str, Any]:
        out = _clean(
            {
                "name": self.name,
                "overprovision": self.overprovision,
                "dynamic_fallback": self.dynamic_fallback,
                "min_ondemand": self.min_ondemand,
            }
        )
        if self.args:
            out["args"] = dict(self.args)
        return out


# ---------------------------------------------------------------------------
# Autoscaler
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AutoscalerSpec:
    """``kind="constant"`` pins N_Tar to ``target``; ``kind="load"`` is the
    paper's QPS autoscaler with hysteresis, with ``target`` as the initial
    N_Tar."""

    kind: str = "constant"
    target: int = 4
    qps_per_replica: float = 0.8
    min_replicas: int = 1
    max_replicas: int = 12
    window_s: float = 60.0
    upscale_delay_s: float = 300.0
    downscale_delay_s: float = 1200.0

    def __post_init__(self) -> None:
        _require(
            self.kind in ("constant", "load"),
            f"autoscaler.kind must be 'constant' or 'load', "
            f"got {self.kind!r}",
        )
        _require(
            self.target >= 0,
            f"autoscaler.target must be >= 0, got {self.target}",
        )
        _require(
            self.qps_per_replica > 0,
            f"autoscaler.qps_per_replica must be positive, "
            f"got {self.qps_per_replica}",
        )
        _require(
            0 < self.min_replicas <= self.max_replicas,
            f"autoscaler replica bounds invalid: need "
            f"0 < min_replicas <= max_replicas, got "
            f"[{self.min_replicas}, {self.max_replicas}]",
        )
        if self.kind == "load":
            _require(
                self.min_replicas <= self.target <= self.max_replicas,
                f"autoscaler.target (initial N_Tar) must lie within "
                f"[min_replicas, max_replicas] = "
                f"[{self.min_replicas}, {self.max_replicas}] for "
                f"kind='load', got {self.target}",
            )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Workload
# ---------------------------------------------------------------------------


WORKLOAD_KINDS = ("poisson", "arena", "maf", "none")


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """Request arrival process.  ``kind="none"`` runs the control plane
    against the trace with no request path (availability/cost only — the
    Fig. 14 setting)."""

    kind: str = "poisson"
    rate_per_s: float = 0.5
    seed: int = 0
    args: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        _require(
            self.kind in WORKLOAD_KINDS,
            f"workload.kind must be one of {list(WORKLOAD_KINDS)}, "
            f"got {self.kind!r}",
        )
        _require(
            self.rate_per_s > 0,
            f"workload.rate_per_s must be positive, got {self.rate_per_s}",
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "kind": self.kind,
            "rate_per_s": self.rate_per_s,
            "seed": self.seed,
        }
        if self.args:
            out["args"] = dict(self.args)
        return out


# ---------------------------------------------------------------------------
# Latency source (roofline vs. measured kernel profiles)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LatencySpec:
    """Where replica service times come from.

    ``source="roofline"`` (default) prices requests with the analytic
    hardware model — the historical behaviour, byte-identical golden
    metrics.  ``source="profile"`` loads a ``repro.profiles`` step-time
    table and uses the kernel-measured MFU/MBU for this (model,
    accelerator) pair; when no matching profile entry exists the run
    warns and falls back to the roofline, so specs stay portable.
    ``profile`` points at a table JSON or a directory of them
    (default: ``artifacts/profiles/``).
    """

    source: str = "roofline"
    profile: Optional[str] = None

    def __post_init__(self) -> None:
        # single source of truth for valid sources is the serving layer
        # (deferred import keeps spec module import cheap)
        from repro.serving.latency import LATENCY_SOURCES

        _require(
            self.source in LATENCY_SOURCES,
            f"latency.source must be one of {list(LATENCY_SOURCES)}, "
            f"got {self.source!r}",
        )
        if self.profile is not None:
            _require(
                bool(self.profile),
                "latency.profile must be a non-empty path when set",
            )

    def to_dict(self) -> Dict[str, Any]:
        return _clean({"source": self.source, "profile": self.profile})


# ---------------------------------------------------------------------------
# Forecasting (spot-availability predictors, repro.forecast)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ForecastSpec:
    """Which spot-availability forecaster a risk-aware policy consults.

    The section configures forecast-consuming policies (those declaring
    ``uses_forecast``, e.g. ``risk_spothedge``); other policies ignore it,
    so a sweep can mix risk-aware and vanilla cells under one spec.

    ``name`` picks the estimator (``persistence`` / ``ewma`` /
    ``markov``); ``horizon_s`` is the look-ahead the policy prices risk
    over; ``risk_threshold`` / ``calm_threshold`` bound the surge and
    trim regimes of :class:`repro.core.risk_aware.RiskAwareSpotHedgePolicy`;
    ``args`` passes further keywords verbatim to the forecaster
    constructor (e.g. ``smoothing`` for ``markov``).
    """

    name: str = "markov"
    horizon_s: Optional[float] = None
    risk_threshold: Optional[float] = None
    calm_threshold: Optional[float] = None
    args: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        _require(bool(self.name), "forecast.name must be set")
        if self.horizon_s is not None:
            _require(
                self.horizon_s > 0,
                f"forecast.horizon_s must be positive, got {self.horizon_s}",
            )
        for field in ("risk_threshold", "calm_threshold"):
            v = getattr(self, field)
            if v is not None:
                _require(
                    0.0 <= v <= 1.0,
                    f"forecast.{field} must be a probability, got {v}",
                )

    def policy_kwargs(self) -> Dict[str, Any]:
        """Constructor kwargs for a forecast-consuming policy."""
        kw: Dict[str, Any] = {"forecaster": self.name}
        if self.args:
            kw["forecaster_args"] = dict(self.args)
        if self.horizon_s is not None:
            kw["horizon_s"] = self.horizon_s
        if self.risk_threshold is not None:
            kw["risk_threshold"] = self.risk_threshold
        if self.calm_threshold is not None:
            kw["calm_threshold"] = self.calm_threshold
        return kw

    def to_dict(self) -> Dict[str, Any]:
        out = _clean(
            {
                "name": self.name,
                "horizon_s": self.horizon_s,
                "risk_threshold": self.risk_threshold,
                "calm_threshold": self.calm_threshold,
            }
        )
        if self.args:
            out["args"] = dict(self.args)
        return out


# ---------------------------------------------------------------------------
# Serving data plane (token-level continuous batching, SLOs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SLOSpec:
    """Token-level service-level objectives: TTFT and TPOT targets.

    A request attains the SLO when its time-to-first-token and its mean
    time-per-output-token are both within target; goodput is the
    throughput of attaining requests (``repro.serving.token.metrics``).
    """

    ttft_s: float = 10.0
    tpot_s: float = 0.2

    def __post_init__(self) -> None:
        _require(
            self.ttft_s > 0,
            f"serving.slo.ttft_s must be positive, got {self.ttft_s}",
        )
        _require(
            self.tpot_s > 0,
            f"serving.slo.tpot_s must be positive, got {self.tpot_s}",
        )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class ServingSpec:
    """Replica data-plane knobs (shared by both serving engines).

    ``concurrency_cap`` bounds the *request-level* model's model-derived
    concurrency default (``min(max_concurrency(), cap)`` when
    ``sim.concurrency`` is null) — historically a hardcoded 16.  The
    remaining fields configure the *token-level* engine selected by
    ``sim.replica_model: token``: the SLO targets, the per-iteration
    chunked-prefill budget, optional batch-size / KV-budget caps (the KV
    budget otherwise derives from the latency model's HBM arithmetic),
    a per-iteration scheduler overhead, and the goodput window.  In YAML
    the section also accepts ``replica_model`` as sugar for
    ``sim.replica_model``.
    """

    slo: SLOSpec = dataclasses.field(default_factory=SLOSpec)
    concurrency_cap: int = 16
    prefill_chunk_tokens: int = 512
    max_batch: Optional[int] = None
    kv_budget_tokens: Optional[int] = None
    iter_overhead_s: float = 0.0
    goodput_window_s: float = 60.0

    def __post_init__(self) -> None:
        _require(
            self.concurrency_cap >= 1,
            f"serving.concurrency_cap must be >= 1, "
            f"got {self.concurrency_cap}",
        )
        _require(
            self.prefill_chunk_tokens >= 1,
            f"serving.prefill_chunk_tokens must be >= 1, "
            f"got {self.prefill_chunk_tokens}",
        )
        if self.max_batch is not None:
            _require(
                self.max_batch >= 1,
                f"serving.max_batch must be >= 1, got {self.max_batch}",
            )
        if self.kv_budget_tokens is not None:
            _require(
                self.kv_budget_tokens >= 1,
                f"serving.kv_budget_tokens must be >= 1, "
                f"got {self.kv_budget_tokens}",
            )
        _require(
            self.iter_overhead_s >= 0,
            f"serving.iter_overhead_s must be >= 0, "
            f"got {self.iter_overhead_s}",
        )
        _require(
            self.goodput_window_s > 0,
            f"serving.goodput_window_s must be positive, "
            f"got {self.goodput_window_s}",
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "slo": self.slo.to_dict(),
            "concurrency_cap": self.concurrency_cap,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "iter_overhead_s": self.iter_overhead_s,
            "goodput_window_s": self.goodput_window_s,
        }
        if self.max_batch is not None:
            out["max_batch"] = self.max_batch
        if self.kv_budget_tokens is not None:
            out["kv_budget_tokens"] = self.kv_budget_tokens
        return out


# ---------------------------------------------------------------------------
# Observability (repro.obs: event tracing, metrics, artifact export)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SLOBurnSpec:
    """Burn-rate alerting knobs (``observability.slo_burn``).

    ``target`` is the SLO attainment target whose error budget the
    burn rates are measured against; ``fast_window_s``/``slow_window_s``
    are the trailing horizons and ``fast_threshold``/``slow_threshold``
    the multi-window alert thresholds (SRE-workbook defaults: 5 min at
    14.4× plus 1 h at 6×).
    """

    target: float = 0.99
    fast_window_s: float = 300.0
    slow_window_s: float = 3600.0
    fast_threshold: float = 14.4
    slow_threshold: float = 6.0

    def __post_init__(self) -> None:
        _require(
            0.0 < self.target < 1.0,
            f"observability.slo_burn.target must be in (0, 1), "
            f"got {self.target}",
        )
        _require(
            0 < self.fast_window_s <= self.slow_window_s,
            "observability.slo_burn windows must be positive with "
            "fast_window_s <= slow_window_s",
        )
        _require(
            self.fast_threshold > 0 and self.slow_threshold > 0,
            "observability.slo_burn thresholds must be positive",
        )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class ObservabilitySpec:
    """What the run records and exports (``repro.obs``).

    ``detail`` gates recording cost: ``off`` records nothing,
    ``decisions`` (default) records control-plane events (policy
    decisions with reasons, replica lifecycle, preemption warnings,
    migration plans) plus registry metrics and sampled request spans,
    and ``full`` adds windowed data-plane samples and SLO burn-rate
    events every ``window_s`` seconds and enables artifact export.  At
    detail ``full`` the :class:`repro.service.Service` facade writes a
    schema-v1 event log (``jsonl``), a span log
    (``<name>.spans.jsonl``) and a Perfetto-loadable timeline
    (``chrome_trace``) under ``out_dir``.  ``trace_sample`` is the
    deterministic per-request span sampling rate (keyed on the request
    run ordinal — no RNG, identical sampled sets in every engine);
    ``slo_burn`` configures the burn-rate monitor.  Recording never
    changes metrics — golden results are byte-identical at every
    detail level.
    """

    detail: str = "decisions"
    out_dir: str = "artifacts/obs"
    jsonl: bool = True
    chrome_trace: bool = True
    window_s: float = 60.0
    trace_sample: float = 0.01
    slo_burn: SLOBurnSpec = dataclasses.field(default_factory=SLOBurnSpec)

    def __post_init__(self) -> None:
        # single source of truth for valid levels is the obs layer
        # (deferred import keeps spec module import cheap)
        from repro.obs.recorder import DETAIL_LEVELS

        _require(
            self.detail in DETAIL_LEVELS,
            f"observability.detail must be one of {list(DETAIL_LEVELS)}, "
            f"got {self.detail!r}",
        )
        _require(
            bool(self.out_dir),
            "observability.out_dir must be a non-empty path",
        )
        _require(
            self.window_s > 0,
            f"observability.window_s must be positive, got {self.window_s}",
        )
        _require(
            0.0 <= self.trace_sample <= 1.0,
            f"observability.trace_sample must be in [0, 1], "
            f"got {self.trace_sample}",
        )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Simulation horizon / fabric knobs
# ---------------------------------------------------------------------------


ENGINE_NAMES = ("vector", "jax")


@dataclasses.dataclass(frozen=True)
class SimSpec:
    """Simulation fabric: horizon, cold start, control cadence, SLO.

    ``engine`` picks the serving hot path: ``"vector"`` (default) is the
    NumPy array reference engine in ``repro.serving.engine``; ``"jax"``
    is the two-phase jit/vmap engine in ``repro.serving.jaxengine`` that
    compiles the request-model data plane with ``lax.scan`` and batches
    whole scenario matrices with ``vmap`` (token-model cells fall back
    to the vector data plane).  The two are decision-for-decision
    equivalent (``tests/test_jax_engine.py``; the vector engine's
    answers are pinned by ``tests/test_differential.py``); they differ
    only in throughput.

    ``replica_model`` picks how a replica prices work: ``"request"``
    (default) is the M/G/c model with frozen per-request service times;
    ``"token"`` is the iteration-level continuous-batching model in
    ``repro.serving.token`` (KV-budget admission, chunked prefill,
    batch-dependent decode steps, TTFT/TPOT/goodput metrics).  Both
    engines accept both models; token-mode knobs live in the
    ``serving:`` section.
    """

    duration_hours: float = 4.0
    cold_start_s: float = 183.0
    control_interval_s: float = 15.0
    timeout_s: float = 100.0
    sub_step_s: float = 1.0
    concurrency: Optional[int] = 4
    drain_s: float = 600.0        # stop generating arrivals this long
    # before the horizon so in-flight work can finish
    warning_enabled: bool = True
    # override the cloud's advance-warning lead time (s) for this run's
    # trace (None -> the catalog per-cloud default: 120 s AWS, 30 s GCP)
    preemption_warning_s: Optional[float] = None
    seed: int = 0
    record_series: bool = True
    engine: str = "vector"
    replica_model: str = "request"

    def __post_init__(self) -> None:
        _require(
            self.engine in ENGINE_NAMES,
            f"sim.engine must be one of {list(ENGINE_NAMES)}, "
            f"got {self.engine!r}",
        )
        # single source of truth for valid models is the serving layer
        # (deferred import keeps spec module import cheap)
        from repro.serving.result import REPLICA_MODELS

        _require(
            self.replica_model in REPLICA_MODELS,
            f"sim.replica_model must be one of "
            f"{list(REPLICA_MODELS)}, got {self.replica_model!r}",
        )
        _require(
            self.duration_hours > 0,
            f"sim.duration_hours must be positive, got {self.duration_hours}",
        )
        _require(
            self.cold_start_s >= 0,
            f"sim.cold_start_s must be >= 0, got {self.cold_start_s}",
        )
        _require(
            self.control_interval_s > 0,
            f"sim.control_interval_s must be positive, "
            f"got {self.control_interval_s}",
        )
        _require(
            self.timeout_s > 0,
            f"sim.timeout_s must be positive, got {self.timeout_s}",
        )
        _require(
            self.sub_step_s > 0,
            f"sim.sub_step_s must be positive, got {self.sub_step_s}",
        )
        _require(
            self.drain_s >= 0,
            f"sim.drain_s must be >= 0, got {self.drain_s}",
        )
        if self.concurrency is not None:
            _require(
                self.concurrency > 0,
                f"sim.concurrency must be positive, got {self.concurrency}",
            )
        if self.preemption_warning_s is not None:
            _require(
                self.preemption_warning_s >= 0,
                f"sim.preemption_warning_s must be >= 0, "
                f"got {self.preemption_warning_s}",
            )

    @property
    def duration_s(self) -> float:
        return self.duration_hours * 3600.0

    def to_dict(self) -> Dict[str, Any]:
        # keep explicit None (concurrency: null == model-derived) so the
        # dict round-trips exactly
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Sweep (scenario grid) — consumed by repro.experiments.ScenarioSuite
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A scenario grid: ``policies × traces × workloads × seeds``
    (× ``forecasters`` / ``replica_models`` when those axes are set).

    Every axis left empty falls back to the base spec's single value, so a
    spec with ``sweep: {}`` expands to exactly one scenario.  Seeds
    override ``workload.seed`` per cell — the standard way to get
    replicated measurements of one configuration.  Forecaster entries
    override ``forecast.name`` per cell (vanilla policies in the same
    grid ignore the section, so predictor × policy backtests compose).
    Replica-model entries override ``sim.replica_model`` per cell, so a
    request-level vs token-level comparison replays one request tape.

        sweep:
          policies: [spothedge, risk_spothedge, ondemand_only]
          traces: [aws-1, gcp-1]
          workloads: [poisson, arena]
          seeds: [0, 1, 2]
          forecasters: [persistence, markov]
          replica_models: [request, token]
    """

    policies: Tuple[ReplicaPolicySpec, ...] = ()
    traces: Tuple[str, ...] = ()
    workloads: Tuple["WorkloadSpec", ...] = ()
    seeds: Tuple[int, ...] = ()
    forecasters: Tuple[str, ...] = ()
    replica_models: Tuple[str, ...] = ()
    # migration axis: each entry is a bool (toggle the base spec's
    # migration section on/off) or a full MigrationSpec override — the
    # A/B axis behind benchmarks/migration.py
    migration: Tuple[Union[bool, MigrationSpec], ...] = ()

    def __post_init__(self) -> None:
        for m in self.migration:
            _require(
                isinstance(m, (bool, MigrationSpec)),
                "sweep.migration entries must be booleans or migration "
                f"mappings, got {m!r}",
            )
        for tr in self.traces:
            _require(
                bool(tr), "sweep.traces entries must be non-empty strings"
            )
        for s in self.seeds:
            _require(
                isinstance(s, int) and not isinstance(s, bool),
                f"sweep.seeds entries must be ints, got {s!r}",
            )
        for fc in self.forecasters:
            _require(
                bool(fc),
                "sweep.forecasters entries must be non-empty strings",
            )
        if self.replica_models:
            from repro.serving.result import REPLICA_MODELS

            for rm in self.replica_models:
                _require(
                    rm in REPLICA_MODELS,
                    f"sweep.replica_models entries must be one of "
                    f"{list(REPLICA_MODELS)}, got {rm!r}",
                )

    @property
    def size(self) -> int:
        """Number of scenarios the grid expands to (axes default to 1)."""
        return (
            max(len(self.policies), 1)
            * max(len(self.traces), 1)
            * max(len(self.workloads), 1)
            * max(len(self.seeds), 1)
            * max(len(self.forecasters), 1)
            * max(len(self.replica_models), 1)
            * max(len(self.migration), 1)
        )

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        if self.policies:
            out["policies"] = [p.to_dict() for p in self.policies]
        if self.traces:
            out["traces"] = list(self.traces)
        if self.workloads:
            out["workloads"] = [w.to_dict() for w in self.workloads]
        if self.seeds:
            out["seeds"] = list(self.seeds)
        if self.forecasters:
            out["forecasters"] = list(self.forecasters)
        if self.replica_models:
            out["replica_models"] = list(self.replica_models)
        if self.migration:
            out["migration"] = [
                m if isinstance(m, bool) else m.to_dict()
                for m in self.migration
            ]
        return out


# ---------------------------------------------------------------------------
# The service spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServiceSpec:
    """The complete declarative description of one service run."""

    name: str = "service"
    model: str = "llama3.2-1b"
    trace: str = "aws-3"
    resources: ResourceSpec = dataclasses.field(default_factory=ResourceSpec)
    replica_policy: ReplicaPolicySpec = dataclasses.field(
        default_factory=ReplicaPolicySpec
    )
    autoscaler: AutoscalerSpec = dataclasses.field(
        default_factory=AutoscalerSpec
    )
    workload: WorkloadSpec = dataclasses.field(default_factory=WorkloadSpec)
    latency: LatencySpec = dataclasses.field(default_factory=LatencySpec)
    forecast: Optional[ForecastSpec] = None
    serving: ServingSpec = dataclasses.field(default_factory=ServingSpec)
    observability: ObservabilitySpec = dataclasses.field(
        default_factory=ObservabilitySpec
    )
    migration: Optional[MigrationSpec] = None
    sim: SimSpec = dataclasses.field(default_factory=SimSpec)
    load_balancer: str = "least_loaded"
    sweep: Optional[SweepSpec] = None

    def __post_init__(self) -> None:
        _require(bool(self.name), "service.name must be set")
        _require(bool(self.model), "service.model must be set")
        _require(bool(self.trace), "service.trace must be set")
        # the engine owns the dispatch rules, so it names the balancers
        from repro.serving.engine import LB_KINDS

        _require(
            self.load_balancer in LB_KINDS,
            f"service.load_balancer must be one of {list(LB_KINDS)}, "
            f"got {self.load_balancer!r}",
        )
        if self.migration is not None and self.migration.enabled:
            token_ok = self.sim.replica_model == "token" or (
                self.sweep is not None
                and "token" in self.sweep.replica_models
            )
            _require(
                token_ok,
                "migration.enabled requires the token-level engine: set "
                "sim.replica_model: token (or sweep over replica_models "
                "including 'token') — the request-level model has no KV "
                "state to migrate",
            )

    # -- cross-registry validation (deferred imports keep this cheap) -----
    def validate(self) -> "ServiceSpec":
        """Check fields against the live registries (policies, archs,
        instance types, named traces).  Returns self for chaining."""
        from repro.cluster.catalog import default_catalog
        from repro.cluster.traces import TraceLibrary
        from repro.configs import ARCH_IDS
        from repro.core.policy import registered_policies
        from repro.forecast.base import registered_forecasters

        policies = registered_policies()
        _require(
            self.replica_policy.name in policies,
            f"unknown replica_policy.name {self.replica_policy.name!r}; "
            f"registered policies: {policies}",
        )
        forecasters = registered_forecasters()
        if self.forecast is not None:
            _require(
                self.forecast.name in forecasters,
                f"unknown forecast.name {self.forecast.name!r}; "
                f"registered forecasters: {forecasters}",
            )
        if self.sweep is not None:
            for p in self.sweep.policies:
                _require(
                    p.name in policies,
                    f"unknown sweep policy {p.name!r}; "
                    f"registered policies: {policies}",
                )
            for fc in self.sweep.forecasters:
                _require(
                    fc in forecasters,
                    f"unknown sweep forecaster {fc!r}; "
                    f"registered forecasters: {forecasters}",
                )
            names = TraceLibrary().names()
            for tr in self.sweep.traces:
                _require(
                    tr in names or tr.endswith((".json", ".npz")),
                    f"unknown sweep trace {tr!r}; named datasets: {names} "
                    "(or pass a .json/.npz trace file path)",
                )
        _require(
            self.model in ARCH_IDS,
            f"unknown model {self.model!r}; available: {ARCH_IDS}",
        )
        catalog = default_catalog()
        try:
            catalog.instance_type(self.resources.instance_type)
        except KeyError:
            known = sorted(t.name for t in catalog.instance_types)
            raise SpecError(
                f"unknown resources.instance_type "
                f"{self.resources.instance_type!r}; catalog has {known}"
            ) from None
        is_file = self.trace.endswith((".json", ".npz"))
        if not is_file:
            names = TraceLibrary().names()
            _require(
                self.trace in names,
                f"unknown trace {self.trace!r}; named datasets: {names} "
                "(or pass a .json/.npz trace file path)",
            )
        return self

    # -- (de)serialization ------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        out = {
            "name": self.name,
            "model": self.model,
            "trace": self.trace,
            "resources": self.resources.to_dict(),
            "replica_policy": self.replica_policy.to_dict(),
            "autoscaler": self.autoscaler.to_dict(),
            "workload": self.workload.to_dict(),
            "latency": self.latency.to_dict(),
            "serving": self.serving.to_dict(),
            "observability": self.observability.to_dict(),
            "sim": self.sim.to_dict(),
            "load_balancer": self.load_balancer,
        }
        if self.forecast is not None:
            out["forecast"] = self.forecast.to_dict()
        if self.migration is not None:
            out["migration"] = self.migration.to_dict()
        if self.sweep is not None:
            out["sweep"] = self.sweep.to_dict()
        return out
