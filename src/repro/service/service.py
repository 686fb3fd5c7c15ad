"""The user-facing facade: a declared service you can run and inspect.

    from repro.service import Service

    svc = Service.from_yaml("service.yaml")
    result = svc.run()                  # ServingResult
    print(result.summary())
    print(svc.status())

A :class:`Service` owns a validated spec plus optional resolved overrides
(a hand-sliced trace window, a shared request tape).  ``run()`` compiles
the spec through ``build_service`` — a fresh simulator per run, so the
same Service can be run repeatedly (simulators are single-shot).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence

from repro.cluster.catalog import Catalog
from repro.cluster.traces import SpotTrace
from repro.serving.result import ServingResult
from repro.service.builder import ResolvedService, build_service
from repro.service.loader import load_spec
from repro.service.spec import ServiceSpec
from repro.workloads import Request

__all__ = ["Service"]


class Service:
    """One declared service: spec in, :class:`ServingResult` out."""

    def __init__(
        self,
        spec: "ServiceSpec | Mapping[str, Any] | str",
        *,
        trace: Optional[SpotTrace] = None,
        catalog: Optional[Catalog] = None,
        requests: Optional[Sequence[Request]] = None,
    ) -> None:
        self.spec = load_spec(spec)
        self._trace_override = trace
        self._catalog_override = catalog
        self._requests_override = requests
        self._resolved: Optional[ResolvedService] = None
        self._resolved_unused = False   # resolved but not yet run
        self.result: Optional[ServingResult] = None
        # artifact paths written by the last run (detail "full" only)
        self.artifacts: Dict[str, str] = {}

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_dict(cls, d: Mapping[str, Any], **overrides: Any) -> "Service":
        return cls(dict(d), **overrides)

    @classmethod
    def from_yaml(cls, path_or_text: str, **overrides: Any) -> "Service":
        from repro.service.loader import spec_from_yaml

        return cls(spec_from_yaml(path_or_text), **overrides)

    @classmethod
    def from_json(cls, path_or_text: str, **overrides: Any) -> "Service":
        from repro.service.loader import spec_from_json

        return cls(spec_from_json(path_or_text), **overrides)

    # -- execution ---------------------------------------------------------
    def resolve(self) -> ResolvedService:
        """Compile the spec (fresh policy/autoscaler/simulator)."""
        self._resolved = build_service(
            self.spec,
            trace=self._trace_override,
            catalog=self._catalog_override,
            requests=self._requests_override,
        )
        self._resolved_unused = True
        return self._resolved

    def run(self, duration_s: Optional[float] = None) -> ServingResult:
        """Run the service over its horizon; returns the ServingResult.

        Reuses a freshly ``resolve()``-d stack if one is pending;
        otherwise compiles a new one (simulators are single-shot)."""
        if self._resolved is not None and self._resolved_unused:
            resolved = self._resolved
        else:
            resolved = self.resolve()
        self._resolved_unused = False
        self.result = resolved.simulator.run(
            duration_s if duration_s is not None else self.spec.sim.duration_s
        )
        self._export_obs(resolved)
        return self.result

    def _export_obs(self, resolved: ResolvedService) -> None:
        """At observability detail ``full``, write the run's artifacts
        (event JSONL and/or Chrome trace) under ``out_dir``."""
        spec = self.spec.observability
        obs = resolved.obs
        if obs is None or obs.detail != "full":
            return
        if not (spec.jsonl or spec.chrome_trace):
            return
        import os

        from repro.obs.export import write_chrome_trace, write_jsonl

        os.makedirs(spec.out_dir, exist_ok=True)
        stem = os.path.join(spec.out_dir, self.spec.name)
        records = obs.records()
        spans = obs.span_records()
        tok = self.result.token if self.result is not None else None
        self.artifacts = {}
        if spec.jsonl:
            self.artifacts["events"] = write_jsonl(
                records, stem + ".events.jsonl"
            )
            if spans:
                self.artifacts["spans"] = write_jsonl(
                    spans, stem + ".spans.jsonl"
                )
        if spec.chrome_trace:
            self.artifacts["trace"] = write_chrome_trace(
                records, stem + ".trace.json",
                spans=spans or None,
                token_windows=tok.windows if tok is not None else None,
            )

    # -- introspection -----------------------------------------------------
    def status(self) -> Dict[str, Any]:
        """Resolved state (and metrics after a run), JSON-friendly."""
        resolved = self._resolved
        out: Dict[str, Any] = {
            "name": self.spec.name,
            "model": self.spec.model,
            "trace": self.spec.trace,
            "policy": self.spec.replica_policy.name,
            "instance_type": self.spec.resources.instance_type,
            "state": "declared",
        }
        if resolved is not None:
            cluster = resolved.simulator.cluster
            out.update(
                state="resolved",
                zones=list(resolved.zones),
                n_requests=len(resolved.requests),
                duration_hours=self.spec.sim.duration_hours,
                n_events=len(cluster.events),
                n_preemptions=cluster.n_preemptions,
                n_launch_failures=cluster.n_launch_failures,
            )
        if self.result is not None:
            r = self.result
            out.update(
                state="finished",
                availability=r.availability,
                cost_vs_ondemand=r.cost_vs_ondemand,
                total_cost=r.total_cost,
                failure_rate=r.failure_rate,
                n_completed=r.n_completed,
                p50_s=r.pct(50),
                p99_s=r.pct(99),
            )
            if r.obs is not None:
                out["obs_event_counts"] = r.obs.event_counts()
            if self.artifacts:
                out["obs_artifacts"] = dict(self.artifacts)
        return out
