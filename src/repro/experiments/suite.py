"""ScenarioSuite: expand a ServiceSpec grid and run every cell.

The suite is the one execution path for every multi-run experiment in the
repo (``benchmarks/e2e_compare.py``, ``latency.py``, ``sensitivity.py``,
``launch/serve.py --sweep``).  Two ways to build one:

* **declaratively** — a spec with a ``sweep:`` section expands to the
  ``policies × traces × workloads × seeds`` grid::

      suite = ScenarioSuite.from_spec("sweep.yaml")
      report = suite.run(workers="auto")
      print(report.summary())

* **programmatically** — hand the suite explicit :class:`Scenario`
  variants (custom axes like trace windows or cold-start sweeps)::

      suite = ScenarioSuite([Scenario(labels={...}, spec=variant), ...])

Request tapes are shared: scenarios with equal ``tape_key`` replay
identical arrivals (the grid keys tapes by workload × seed × horizon, so
every policy/trace cell of one workload sees the same request stream —
the §5.1 fair-comparison methodology).  Tapes are regenerated from the
spec inside worker processes instead of being pickled across; workload
generation is seed-deterministic, so every worker sees the same stream.

Cells are independent, so ``run(workers=N)`` fans them out over worker
processes; results are deterministic and identical for any worker count.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import (
    Any, Dict, Hashable, List, Mapping, Optional, Sequence, Tuple,
)

from repro.cluster.traces import SpotTrace
from repro.core.policy import policy_class
from repro.experiments.report import CellResult, ScenarioReport
from repro.obs import hostspan
from repro.service.builder import build_requests, build_service
from repro.service.loader import load_spec
from repro.service.spec import (
    ForecastSpec,
    MigrationSpec,
    ServiceSpec,
    SpecError,
    SweepSpec,
)
from repro.workloads import Request

__all__ = ["Scenario", "ScenarioSuite"]


# label axes may not shadow metric fields — CellResult.to_dict flattens
# labels and metrics into one record
_RESERVED_LABELS = frozenset(
    f.name for f in dataclasses.fields(CellResult) if f.name != "labels"
)


@dataclasses.dataclass
class Scenario:
    """One cell of a scenario matrix: labels + a single-run spec.

    ``trace`` optionally overrides the spec's named trace with a
    pre-sliced window (the e2e benchmark's available/volatile windows).
    Scenarios sharing a ``tape_key`` replay one request tape.
    """

    labels: Dict[str, Any]
    spec: ServiceSpec
    trace: Optional[SpotTrace] = None
    tape_key: Optional[Hashable] = None

    def __post_init__(self) -> None:
        if self.spec.sweep is not None:
            raise SpecError(
                "a Scenario wraps a single-run spec; expand the sweep "
                "with ScenarioSuite.from_spec first"
            )
        clash = set(self.labels) & _RESERVED_LABELS
        if clash:
            raise SpecError(
                f"scenario label axes {sorted(clash)} collide with "
                "CellResult metric fields; pick different axis names"
            )

    @property
    def cell_id(self) -> str:
        return "/".join(str(v) for v in self.labels.values())


def _canonical_args(value: Any, path: str = "workload.args") -> Hashable:
    """Canonicalize a workload-args value into a hashable, order-insensitive
    structure for the tape key.

    Strict by design: only JSON-ish primitives and containers are
    accepted.  The previous ``json.dumps(..., default=repr)`` fallback
    silently stringified arbitrary objects, and a ``repr`` that embeds a
    memory address (the default ``object.__repr__``) yields a key that
    differs across processes/runs — spawn-started workers then regenerate
    tapes and logically identical cells stop sharing one, breaking the
    §5.1 same-tape methodology.  Anything un-canonicalizable now raises
    ``SpecError`` at key-construction time instead.
    """
    if value is None or isinstance(value, str):
        return value
    if isinstance(value, bool):
        # tag bools: True == 1 under dict/tuple equality, but workload
        # args {"flag": True} and {"flag": 1} must not share a tape key
        return ("__bool__", value)
    if isinstance(value, (int, float)):
        return value
    if isinstance(value, (list, tuple)):
        return tuple(
            _canonical_args(v, f"{path}[{k}]") for k, v in enumerate(value)
        )
    if isinstance(value, Mapping):
        items = []
        for k in sorted(value, key=str):
            if not isinstance(k, str):
                raise SpecError(
                    f"{path}: mapping key {k!r} is not a string; tape "
                    "keys require string-keyed mappings"
                )
            items.append((k, _canonical_args(value[k], f"{path}.{k}")))
        return tuple(items)
    raise SpecError(
        f"{path}: cannot canonicalize {type(value).__name__} value "
        f"{value!r} for the shared-tape key; workload args must be "
        "JSON-like (None/bool/int/float/str and lists/dicts thereof) so "
        "the key is stable across processes"
    )


def _workload_tape_key(spec: ServiceSpec) -> Tuple:
    """Tapes are equal iff workload spec and arrival horizon are equal."""
    w = spec.workload
    # args may hold unhashable values (e.g. a client_regions mapping) —
    # the canonical tuple form keeps the key hashable, order-insensitive
    # and — unlike repr-based fallbacks — stable across processes
    args_key = _canonical_args(dict(w.args))
    return (
        w.kind, w.rate_per_s, w.seed,
        args_key,
        spec.sim.duration_s - spec.sim.drain_s,
    )


def _effective_tape_key(scenario: Scenario) -> Optional[Tuple]:
    """Cache key for a scenario's shared tape.

    The user's ``tape_key`` groups cells; composing it with the workload
    fingerprint guarantees two suites that happen to reuse a key with
    *different* workloads can never share a stale tape (the worker-side
    cache outlives a single ``run()``).
    """
    if scenario.tape_key is None:
        return None
    return (scenario.tape_key, _workload_tape_key(scenario.spec))


def _run_scenario(
    scenario: Scenario,
    tape_cache: Dict[Hashable, List[Request]],
    engine: Optional[str],
) -> CellResult:
    """Build and run one cell; tapes are cached per process."""
    spec = scenario.spec
    if engine is not None and spec.sim.engine != engine:
        spec = dataclasses.replace(
            spec, sim=dataclasses.replace(spec.sim, engine=engine)
        )
    requests: Optional[List[Request]] = None
    key = _effective_tape_key(scenario)
    if key is not None:
        requests = tape_cache.get(key)
        if requests is None:
            requests = tape_cache[key] = build_requests(spec)
    t0 = time.perf_counter()
    resolved = build_service(
        spec, trace=scenario.trace, requests=requests
    )
    result = resolved.simulator.run(spec.sim.duration_s)
    wall = time.perf_counter() - t0
    return CellResult.from_result(scenario.labels, result, wall)


def _disambiguate(
    names: List[str], knobs: List[List[Tuple[str, Any]]]
) -> List[str]:
    """Axis labels: the bare name when unique, name[knob=...] or name#k
    when several grid entries share it (e.g. two spothedge variants)."""
    counts: Dict[str, int] = {}
    for n in names:
        counts[n] = counts.get(n, 0) + 1
    seen: Dict[str, int] = {}
    out: List[str] = []
    for n, kv in zip(names, knobs):
        if counts[n] == 1:
            out.append(n)
            continue
        k = seen[n] = seen.get(n, 0) + 1
        detail = ",".join(f"{key}={v}" for key, v in kv)
        out.append(f"{n}[{detail}]" if detail else f"{n}#{k}")
    # identical knob sets would still collide — fall back to indexing
    if len(set(out)) != len(out):
        out = [
            lab if out.count(lab) == 1 else f"{lab}#{i}"
            for i, lab in enumerate(out)
        ]
    return out


# module-level worker state so ProcessPoolExecutor workers reuse tapes
_worker_tapes: Dict[Hashable, List[Request]] = {}


def _run_scenario_worker(
    payload: Tuple[Scenario, Optional[str]]
) -> CellResult:
    scenario, engine = payload
    return _run_scenario(scenario, _worker_tapes, engine)


class ScenarioSuite:
    """A batch of scenarios sharing one execution path."""

    def __init__(self, scenarios: Sequence[Scenario],
                 name: str = "suite") -> None:
        self.scenarios: List[Scenario] = list(scenarios)
        self.name = name
        if not self.scenarios:
            raise SpecError("ScenarioSuite needs at least one scenario")

    def __len__(self) -> int:
        return len(self.scenarios)

    # ------------------------------------------------------------------
    @classmethod
    def from_spec(
        cls,
        spec: "ServiceSpec | Mapping[str, Any] | str",
        name: Optional[str] = None,
    ) -> "ScenarioSuite":
        """Expand a spec's ``sweep`` grid (missing axes fall back to the
        base spec's single value)."""
        base = load_spec(spec)
        sweep = base.sweep or SweepSpec()
        policies = sweep.policies or (base.replica_policy,)
        traces = sweep.traces or (base.trace,)
        workloads = sweep.workloads or (base.workload,)
        # no seeds axis: every workload keeps its own declared seed
        seeds: Tuple[Optional[int], ...] = sweep.seeds or (None,)
        # no forecasters axis: the base forecast section (if any) applies
        # to every cell and no "forecaster" label column is emitted
        forecasters: Tuple[Optional[str], ...] = sweep.forecasters or (None,)
        # no replica_models axis: every cell keeps sim.replica_model and
        # no "replica_model" label column is emitted
        replica_models: Tuple[Optional[str], ...] = (
            sweep.replica_models or (None,)
        )
        # no migration axis: the base migration section (if any) applies
        # to every cell and no "migration" label column is emitted
        migrations: "Tuple[bool | MigrationSpec | None, ...]" = (
            sweep.migration or (None,)
        )

        policy_labels = _disambiguate(
            [p.name for p in policies],
            [sorted(p.policy_kwargs().items()) for p in policies],
        )
        workload_labels = _disambiguate(
            [w.kind for w in workloads],
            [
                [("rate_per_s", w.rate_per_s), ("seed", w.seed),
                 *sorted(w.args.items())]
                for w in workloads
            ],
        )

        scenarios: List[Scenario] = []
        for (pol, plabel), tr, (wl, wlabel), seed, fc, rm, mg in (
            itertools.product(
                zip(policies, policy_labels),
                traces,
                zip(workloads, workload_labels),
                seeds,
                forecasters,
                replica_models,
                migrations,
            )
        ):
            if fc is not None and not getattr(
                policy_class(pol.name), "uses_forecast", False
            ):
                # a forecaster axis is meaningless for policies that
                # ignore the forecast section — expanding it would re-run
                # byte-identical cells once per predictor.  Keep exactly
                # one (unlabeled-forecaster) cell for such policies.
                if fc != forecasters[0]:
                    continue
                fc = None
            cell_rm = rm if rm is not None else base.sim.replica_model
            if mg is not None and cell_rm != "token":
                # migration only exists at token granularity; keep one
                # (unlabeled-migration) cell for request-model variants
                if mg != migrations[0]:
                    continue
                mg = None
            wl_seeded = (
                wl if seed is None else dataclasses.replace(wl, seed=seed)
            )
            forecast = base.forecast
            if fc is not None:
                forecast = dataclasses.replace(
                    base.forecast or ForecastSpec(), name=fc
                )
            sim = base.sim
            if rm is not None and sim.replica_model != rm:
                sim = dataclasses.replace(sim, replica_model=rm)
            migration = base.migration
            mig_label: Optional[str] = None
            if mg is not None:
                if isinstance(mg, bool):
                    migration = dataclasses.replace(
                        base.migration or MigrationSpec(), enabled=mg
                    )
                else:
                    migration = mg
                mig_label = "on" if migration.enabled else "off"
            if (
                migration is not None
                and migration.enabled
                and cell_rm != "token"
            ):
                # an enabled base section on a request-model cell of a
                # mixed replica_models sweep: the cell has no KV state,
                # drop the section (the token cells keep it)
                migration = None
            cell_spec = dataclasses.replace(
                base,
                name=(f"{base.name}-{plabel}-{tr}-{wlabel}"
                      f"-s{wl_seeded.seed}"
                      + (f"-{fc}" if fc is not None else "")
                      + (f"-{rm}" if rm is not None else "")
                      + (f"-mig_{mig_label}" if mig_label is not None
                         else "")),
                replica_policy=pol,
                trace=tr,
                workload=wl_seeded,
                forecast=forecast,
                migration=migration,
                sim=sim,
                sweep=None,
            )
            labels = {
                "policy": plabel,
                "trace": tr,
                "workload": wlabel,
                "seed": wl_seeded.seed,
            }
            if fc is not None:
                labels["forecaster"] = fc
            if rm is not None:
                labels["replica_model"] = rm
            if mig_label is not None:
                labels["migration"] = mig_label
            scenarios.append(
                Scenario(
                    labels=labels,
                    spec=cell_spec,
                    tape_key=_workload_tape_key(cell_spec),
                )
            )
        return cls(scenarios, name=name or base.name)

    # ------------------------------------------------------------------
    def run(
        self,
        *,
        engine: Optional[str] = None,
        workers: "int | str | None" = None,
        save_to: Optional[str] = None,
        progress: bool = False,
    ) -> ScenarioReport:
        """Run every scenario; returns the aggregated report.

        ``engine`` overrides ``spec.sim.engine`` for every cell
        ("vector" / "jax").  ``workers`` fans independent
        cells out over processes ("auto" = one per CPU); results are
        identical for any worker count.  ``save_to`` writes the JSON
        artifact into the given directory (e.g. ``artifacts/bench``).

        ``engine="jax"`` takes the matrix-batched path: the control
        plane of every cell is replayed in-process (phase A), then all
        request-model data planes run as one vmapped XLA program per
        shape group (phase B) — ``workers`` is ignored, the batching
        *is* the parallelism.  Results are identical to the per-cell
        engines (tests/test_jax_engine.py).
        """
        n_workers = self._resolve_workers(workers)
        t0 = time.perf_counter()
        # serial and parallel share the process-level tape cache, so
        # repeated runs of one suite (e.g. benchmark trials) pay tape
        # generation once regardless of worker count
        self._prime_tape_cache()
        use_jax = engine == "jax" or (
            engine is None
            and bool(self.scenarios)
            and all(sc.spec.sim.engine == "jax" for sc in self.scenarios)
        )
        if use_jax:
            n_workers = 1
            cells = self._run_jax_matrix(progress)
        elif n_workers <= 1 or len(self.scenarios) <= 1:
            n_workers = 1
            cells = []
            for sc in self.scenarios:
                cells.append(_run_scenario(sc, _worker_tapes, engine))
                if progress:
                    print(f"[suite {self.name}] {cells[-1].cell_id} done "
                          f"({len(cells)}/{len(self.scenarios)})",
                          flush=True)
        else:
            cells = self._run_parallel(n_workers, engine, progress)
        wall = time.perf_counter() - t0
        # merge every cell's registry snapshot (cells from parallel
        # workers carry theirs back through the picklable CellResult)
        from repro.obs.registry import MetricsRegistry

        snaps = [c.metrics for c in cells if c.metrics]
        report = ScenarioReport(
            suite=self.name,
            engine=engine or self._engine_label(),
            workers=n_workers,
            cells=cells,
            wall_s=wall,
            metrics=MetricsRegistry.merge_snapshots(snaps) or None
            if snaps else None,
        )
        if progress:
            # surface paging-worthy cells (detail "full" only) as they
            # would reach an operator: worst error-budget burn first
            for c in report.burn_ranking():
                b = c.slo_burn
                if b["alert_windows"]:
                    print(f"[suite {self.name}] SLO burn alert: "
                          f"{c.cell_id} {b['alert_minutes']:.1f}min "
                          f"over {b['alert_windows']} windows", flush=True)
        if save_to is not None:
            report.save(save_to)
        return report

    # ------------------------------------------------------------------
    def _run_jax_matrix(self, progress: bool) -> List[CellResult]:
        """The jit/vmap path: build every cell, replay control planes,
        then run all request-model data planes as one batched program.

        Token-model cells and queue-overflow lanes fall back to the
        NumPy oracle inside :func:`repro.serving.jaxengine.run_cells`,
        so a mixed matrix still returns a complete, exact report.
        """
        from repro.serving.jaxengine import run_cells

        builds = []
        for sc in self.scenarios:
            with hostspan.host_span(hostspan.BUILD):
                spec = sc.spec
                if spec.sim.engine != "jax":
                    spec = dataclasses.replace(
                        spec, sim=dataclasses.replace(spec.sim, engine="jax")
                    )
                requests: Optional[List[Request]] = None
                key = _effective_tape_key(sc)
                if key is not None:
                    requests = _worker_tapes.get(key)
                    if requests is None:
                        requests = _worker_tapes[key] = build_requests(spec)
                t0 = time.perf_counter()
                resolved = build_service(
                    spec, trace=sc.trace, requests=requests
                )
                builds.append((sc, spec, resolved,
                               time.perf_counter() - t0))
        t0 = time.perf_counter()
        results = run_cells(
            [b[2].simulator for b in builds],
            [b[1].sim.duration_s for b in builds],
        )
        # the batch is one program: attribute its wall clock evenly
        share = (time.perf_counter() - t0) / max(len(builds), 1)
        cells: List[CellResult] = []
        with hostspan.host_span(hostspan.ASSEMBLE):
            for (sc, _spec, _res, build_s), result in zip(builds, results):
                cells.append(
                    CellResult.from_result(sc.labels, result,
                                           build_s + share)
                )
                if progress:
                    print(f"[suite {self.name}] {cells[-1].cell_id} done "
                          f"({len(cells)}/{len(builds)})", flush=True)
        return cells

    def _engine_label(self) -> str:
        engines = {sc.spec.sim.engine for sc in self.scenarios}
        return engines.pop() if len(engines) == 1 else "mixed"

    @staticmethod
    def _resolve_workers(workers: "int | str | None") -> int:
        if workers is None:
            return 1
        if workers == "auto":
            return os.cpu_count() or 1
        try:
            n = int(workers)
        except (TypeError, ValueError):
            raise SpecError(
                f"workers must be an int >= 1 or 'auto', got {workers!r}"
            ) from None
        if n < 1:
            raise SpecError(
                f"workers must be an int >= 1 or 'auto', got {n}"
            )
        return n

    def _prime_tape_cache(self) -> None:
        """Generate this suite's shared tapes into the process cache.

        Runs in the parent BEFORE any pool forks, so fork-started workers
        inherit the tapes copy-on-write (spawn-started workers fall back
        to deterministic regeneration).  Keys other suites left behind
        are evicted so the process-global cache stays bounded by the
        current suite.
        """
        needed = {
            _effective_tape_key(sc): sc for sc in self.scenarios
            if sc.tape_key is not None
        }
        for stale in sorted(set(_worker_tapes) - set(needed)):
            del _worker_tapes[stale]
        for key, sc in needed.items():
            if key not in _worker_tapes:
                _worker_tapes[key] = build_requests(sc.spec)

    def _run_parallel(
        self, n_workers: int, engine: Optional[str], progress: bool
    ) -> List[CellResult]:
        import concurrent.futures as cf

        payloads = [(sc, engine) for sc in self.scenarios]
        cells: List[Optional[CellResult]] = [None] * len(payloads)
        with cf.ProcessPoolExecutor(max_workers=n_workers) as pool:
            futures = {
                pool.submit(_run_scenario_worker, p): i
                for i, p in enumerate(payloads)
            }
            n_done = 0
            for fut in cf.as_completed(futures):
                i = futures[fut]
                cells[i] = fut.result()
                n_done += 1
                if progress:
                    print(f"[suite {self.name}] {cells[i].cell_id} done "
                          f"({n_done}/{len(payloads)})", flush=True)
        # completeness: a lost future must be a loud failure, not a
        # silently shorter report (the old `[c for c in cells if c]`
        # filter dropped unfilled cells without a trace)
        missing = [
            self.scenarios[i].cell_id
            for i, c in enumerate(cells) if c is None
        ]
        if missing:
            raise RuntimeError(
                f"scenario suite {self.name!r}: {len(missing)} of "
                f"{len(cells)} cells never returned a result "
                f"(lost futures): {missing}"
            )
        return [c for c in cells if c is not None]
