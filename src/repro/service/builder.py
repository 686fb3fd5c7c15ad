"""Compile a :class:`ServiceSpec` into the runnable simulator stack.

``build_service`` is the only place in the repo that assembles the
trace × catalog × policy × autoscaler × LB × serving-engine
pipeline; every driver (launch/serve, examples, benchmarks) goes through
it, so a new scenario is a spec file, not a new driver.

Overrides exist for the pieces an experiment may precompute: a trace
window sliced by hand (``trace=``), a shared request tape (``requests=``),
or a custom catalog.  Everything else is derived from the spec.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from repro.cluster.catalog import Catalog, default_catalog
from repro.cluster.simulator import SimConfig
from repro.cluster.traces import SpotTrace, load_trace
from repro.configs import get_config
from repro.core.autoscaler import Autoscaler, ConstantTarget, LoadAutoscaler
from repro.core.policy import Policy, make_policy, policy_class
from repro.models.config import ModelConfig
from repro.obs.recorder import ObsRecorder
from repro.obs.registry import use_registry
from repro.obs.slo import SLOBurnConfig
from repro.serving.latency import make_latency_model
from repro.serving.token.config import TokenSchedulerConfig
from repro.serving.engine import VectorizedServingEngine
from repro.service.spec import ResourceSpec, ServiceSpec, SpecError
from repro.workloads import Request, make_workload

__all__ = [
    "ResolvedService",
    "build_requests",
    "build_service",
    "resolve_zones",
]


def resolve_zones(
    resources: ResourceSpec, trace: SpotTrace, catalog: Catalog
) -> List[str]:
    """Zones of ``trace`` that pass the ``any_of``/``exclude`` filter.

    Zones the catalog does not know are skipped (a trace file may carry
    zones outside the default universe); an empty result is a spec error.
    """
    out: List[str] = []
    for name in trace.zones:
        try:
            z = catalog.zone(name)
        except KeyError:
            continue
        if resources.allows(z.cloud, z.region, z.name):
            out.append(name)
    if not out:
        raise SpecError(
            f"resources filter matches no zone of trace "
            f"{trace.name!r} (trace zones: {list(trace.zones)}); "
            "loosen any_of / exclude_zones"
        )
    return out


def _build_policy(spec: ServiceSpec, trace: SpotTrace,
                  catalog: Catalog) -> Policy:
    name = spec.replica_policy.name
    kwargs = spec.replica_policy.policy_kwargs()
    # the forecast: section only applies to forecast-consuming policies
    # (uses_forecast flag); vanilla cells of a mixed sweep ignore it
    if spec.forecast is not None and getattr(
        policy_class(name), "uses_forecast", False
    ):
        kwargs.update(spec.forecast.policy_kwargs())
    try:
        policy = make_policy(name, **kwargs)
    except (TypeError, ValueError) as e:
        raise SpecError(
            f"replica_policy {name!r} rejected its knobs {kwargs}: {e}"
        ) from e
    if name == "omniscient":
        # the oracle needs the full trace ahead of time (offline ILP)
        from repro.core.omniscient import solve_omniscient

        itype = spec.resources.instance_type
        k = (
            catalog.od_price(itype, trace.zones[0])
            / catalog.spot_price(itype, trace.zones[0])
        )
        policy.attach_schedule(
            solve_omniscient(
                trace,
                n_target=spec.autoscaler.target,
                cold_start_s=spec.sim.cold_start_s,
                k_ratio=k,
                avail_target=0.99,
            )
        )
    return policy


def _build_autoscaler(spec: ServiceSpec) -> Autoscaler:
    a = spec.autoscaler
    if a.kind == "constant":
        return ConstantTarget(a.target)
    return LoadAutoscaler(
        a.qps_per_replica,
        window_s=a.window_s,
        upscale_delay_s=a.upscale_delay_s,
        downscale_delay_s=a.downscale_delay_s,
        min_replicas=a.min_replicas,
        max_replicas=a.max_replicas,
        initial_target=a.target,
    )


def build_requests(spec: ServiceSpec) -> List[Request]:
    """Generate the spec's request tape (empty for ``workload: none``).

    Exposed so experiment sweeps can generate one tape and replay it
    across several service variants (``Service(..., requests=tape)``)."""
    w = spec.workload
    if w.kind == "none":
        return []
    kw = dict(w.args)
    kw["seed"] = w.seed
    rate_key = "rate_per_s" if w.kind == "poisson" else "base_rate_per_s"
    kw.setdefault(rate_key, w.rate_per_s)
    horizon = spec.sim.duration_s - spec.sim.drain_s
    if horizon <= 0:
        raise SpecError(
            f"sim.duration_hours ({spec.sim.duration_hours:g}h = "
            f"{spec.sim.duration_s:g}s) must exceed sim.drain_s "
            f"({spec.sim.drain_s:g}s) to leave room for arrivals; "
            "lengthen the run or shrink drain_s"
        )
    return make_workload(w.kind, **kw).generate(horizon)


@dataclasses.dataclass
class ResolvedService:
    """Everything ``build_service`` wired together, inspectable."""

    spec: ServiceSpec
    trace: SpotTrace
    catalog: Catalog
    model_config: ModelConfig
    zones: List[str]
    policy: Policy
    autoscaler: Autoscaler
    requests: List[Request]
    # VectorizedServingEngine or its subclass JaxServingEngine, per
    # spec.sim.engine
    simulator: VectorizedServingEngine
    # the run's shared event recorder + metrics registry, built from the
    # spec's observability: section (detail / window_s)
    obs: Optional[ObsRecorder] = None


def build_service(
    spec: ServiceSpec,
    *,
    trace: Optional[SpotTrace] = None,
    catalog: Optional[Catalog] = None,
    requests: Optional[Sequence[Request]] = None,
) -> ResolvedService:
    """Spec -> resolved, runnable service (fresh simulator each call)."""
    catalog = catalog or default_catalog()
    trace = trace if trace is not None else load_trace(spec.trace)
    zones = resolve_zones(spec.resources, trace, catalog)
    if tuple(zones) != tuple(trace.zones):
        trace = trace.slice_zones(zones)
    if spec.sim.preemption_warning_s is not None:
        # copy — named traces are process-global cached and must never
        # be mutated in place
        trace = dataclasses.replace(
            trace, preemption_warning_s=spec.sim.preemption_warning_s
        )

    policy = _build_policy(spec, trace, catalog)
    autoscaler = _build_autoscaler(spec)
    reqs = list(requests) if requests is not None else build_requests(spec)

    sim_spec = spec.sim
    # with no request path there is nothing to do between control ticks —
    # step the request loop at the control cadence instead of 1 Hz
    sub_step = (
        max(sim_spec.sub_step_s, sim_spec.control_interval_s)
        if spec.workload.kind == "none" and requests is None
        else sim_spec.sub_step_s
    )
    if sim_spec.engine == "jax":
        # lazy: only sim.engine: "jax" runs pay the jax import
        from repro.serving.jaxengine import JaxServingEngine

        engine_cls = JaxServingEngine
    else:
        engine_cls = VectorizedServingEngine
    burn = spec.observability.slo_burn
    obs = ObsRecorder(
        detail=spec.observability.detail,
        window_s=spec.observability.window_s,
        trace_sample=spec.observability.trace_sample,
        slo_burn=SLOBurnConfig(
            target=burn.target,
            fast_window_s=burn.fast_window_s,
            slow_window_s=burn.slow_window_s,
            fast_threshold=burn.fast_threshold,
            slow_threshold=burn.slow_threshold,
        ),
    )
    model_cfg = get_config(spec.model)
    # run-scope the registry so factory-level counters (e.g. the
    # profile-fallback) land on this run's obs, not a process global
    with use_registry(obs.registry):
        latency_model = make_latency_model(
            model_cfg,
            catalog.instance_type(spec.resources.instance_type),
            model_id=spec.model,
            source=spec.latency.source,
            profile=spec.latency.profile,
        )
    serving = spec.serving
    # migration only exists at token granularity; request-model cells of
    # a mixed replica_models sweep run without it (the status quo)
    migration = (
        spec.migration if sim_spec.replica_model == "token" else None
    )
    token_knobs = None
    if sim_spec.replica_model == "token":
        token_knobs = TokenSchedulerConfig(
            slo_ttft_s=serving.slo.ttft_s,
            slo_tpot_s=serving.slo.tpot_s,
            prefill_chunk_tokens=serving.prefill_chunk_tokens,
            max_batch=serving.max_batch,
            kv_budget_tokens=serving.kv_budget_tokens,
            iter_overhead_s=serving.iter_overhead_s,
            goodput_window_s=serving.goodput_window_s,
        )
    simulator = engine_cls(
        trace,
        policy,
        reqs,
        model_cfg,
        itype=spec.resources.instance_type,
        catalog=catalog,
        autoscaler=autoscaler,
        lb=spec.load_balancer,
        sim_config=SimConfig(
            itype=spec.resources.instance_type,
            cold_start_s=sim_spec.cold_start_s,
            control_interval_s=sim_spec.control_interval_s,
            warning_enabled=sim_spec.warning_enabled,
            seed=sim_spec.seed,
            record_series=sim_spec.record_series,
        ),
        timeout_s=sim_spec.timeout_s,
        sub_step_s=sub_step,
        workload_name=spec.workload.kind,
        concurrency=sim_spec.concurrency,
        concurrency_cap=serving.concurrency_cap,
        latency_model=latency_model,
        replica_model=sim_spec.replica_model,
        token_scheduler=token_knobs,
        migration=migration,
        obs=obs,
    )
    return ResolvedService(
        spec=spec,
        trace=trace,
        catalog=catalog,
        model_config=simulator.cfg,
        zones=zones,
        policy=policy,
        autoscaler=autoscaler,
        requests=reqs,
        simulator=simulator,
        obs=obs,
    )
