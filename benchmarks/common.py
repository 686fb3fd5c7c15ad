"""Shared helpers for the benchmark modules.

Every serving benchmark constructs its runs through the declarative
service API: build a base :class:`ServiceSpec` dict, derive variants with
``variant()``, and execute through the scenario-matrix engine
(:func:`run_suite` / :class:`repro.experiments.ScenarioSuite`) so all
drivers share one execution path.  Scenarios of one sweep replay
identical request tapes via ``Scenario.tape_key``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

from repro.cluster.traces import SpotTrace
from repro.experiments import ScenarioReport, ScenarioSuite
from repro.serving.result import ServingResult
from repro.service import Service, ServiceSpec
from repro.workloads import Request

ART = os.path.join("artifacts", "bench")


def variant(spec: ServiceSpec, **field_replacements: Any) -> ServiceSpec:
    """A spec with top-level fields swapped (frozen dataclass replace)."""
    return dataclasses.replace(spec, **field_replacements)


def run_service(
    spec: ServiceSpec | Dict[str, Any],
    *,
    trace: Optional[SpotTrace] = None,
    requests: Optional[Sequence[Request]] = None,
    duration_s: Optional[float] = None,
) -> ServingResult:
    """Compile + run one declared service; returns its ServingResult."""
    return Service(spec, trace=trace, requests=requests).run(duration_s)


def run_suite(
    suite: ScenarioSuite,
    *,
    engine: Optional[str] = None,
    workers: "int | str | None" = "auto",
    save: bool = True,
) -> ScenarioReport:
    """Run a scenario suite with the bench defaults and save its report."""
    return suite.run(
        engine=engine,
        workers=workers,
        save_to=ART if save else None,
    )


def save(name: str, rows: List[Dict[str, Any]]) -> str:
    os.makedirs(ART, exist_ok=True)
    path = os.path.join(ART, f"{name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, default=str)
    return path


def emit_csv(name: str, rows: List[Dict[str, Any]]) -> None:
    """Print ``name,key=value,...`` lines (the bench_output.txt format)."""
    for r in rows:
        kv = ",".join(f"{k}={v}" for k, v in r.items())
        print(f"{name},{kv}")


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.s = time.time() - self.t0
