"""Driver of ``kind: matrix`` configurations: a deployment-cell matrix
through the JAX scenario engine.

Every evaluation in the window is one ``ScenarioSuite.run(engine="jax")``
over the whole matrix: phase A (the control plane, replayed per cell on
the host), phase B (the vmapped data plane on the chip) and assembly.
Only compiled programs and the suite's primed request tapes carry over
from one evaluation to the next.  The window runs whole evaluations
until ``seconds`` have passed, and the rate is taken over all of them.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from reference import dataplane

# the data-plane fields of a deployment cell's result
COUNTS = ("n_requests", "n_completed", "n_failed")
FLOATS = ("failure_rate", "mean_s", "p50_s", "p90_s", "p99_s")


def workload_seeds(seed: int, seeds: List[int]) -> List[int]:
    """The traffic's workload seeds in an order drawn from the run's seed.

    Every run plays the same set of tapes, so phase B's program (whose
    shapes follow the longest tape and the busiest sub-step) is the same
    for every run seed and compiles once per checkout; the run seed
    decides which lane each cell takes."""
    order = np.random.default_rng(seed).permutation(len(seeds))
    return [int(seeds[i]) for i in order]


def suite_spec(config: Dict, traffic: Dict, seed: int) -> Dict:
    """The matrix as a sweep spec: the deployment of ``config`` under the
    policies, traces, seeds and horizon of ``traffic``."""
    return {
        "name": config["name"],
        "model": config["model"],
        "trace": traffic["traces"][0],
        "resources": {"instance_type": config["instance_type"]},
        "replica_policy": {"name": traffic["policies"][0]},
        "autoscaler": dict(config["autoscaler"]),
        "workload": {"kind": "poisson",
                     "rate_per_s": traffic["rate_per_s"], "seed": 0},
        "sim": {
            "duration_hours": traffic["hours"],
            "timeout_s": config["timeout_s"],
            "concurrency": config["concurrency"],
            "drain_s": config["drain_s"],
        },
        "sweep": {
            "policies": list(traffic["policies"]),
            "traces": list(traffic["traces"]),
            "seeds": workload_seeds(seed, traffic["seeds"]),
        },
        # the jax engine records spans of single-attempt requests only,
        # so span sampling stays off
        "observability": {"trace_sample": 0.0},
    }


def gaps(got: List[Dict], want: List[Dict]) -> Dict[str, float]:
    """``count_diffs``: counts that differ, cell by cell; ``rel_gap``: the
    widest relative gap of a request's latency or of a float field (NaN
    against NaN is no gap, a latency missing on one side is an infinite
    one)."""
    diffs, worst = 0, 0.0
    for a, b in zip(got, want, strict=True):
        diffs += sum(a[k] != b[k] for k in COUNTS)
        pairs = [(a[k], b[k]) for k in FLOATS]
        if len(a["latencies"]) == len(b["latencies"]):
            pairs += zip(a["latencies"], b["latencies"])
        else:
            worst = math.inf
        for x, y in pairs:
            if math.isnan(x) and math.isnan(y):
                continue
            gap = abs(x - y) / max(abs(y), 1e-12)
            worst = max(worst, gap if not math.isnan(gap) else math.inf)
    return {"count_diffs": float(diffs), "rel_gap": float(worst)}


def cell_arrays(sched) -> Dict:
    """A phase-B schedule as the plain arrays the reference reads."""
    g = sched.grid
    return {
        "arr": sched.arr, "svc": sched.svc, "rcode": sched.rcode,
        "ts": g.ts, "win_of": g.win_of, "win_first": g.win_first,
        "ready": sched.ready_mask, "rtt": sched.rtt,
        "kill_slot": sched.kill_slot, "kill_g": sched.kill_g,
        "timeout_s": sched.timeout_s, "concurrency": sched.concurrency,
        "lb": sched.lb_kind,
    }


class Driver:
    """Matrix evaluations of one deployment under one traffic file."""

    # host annotations the trace keeps, to name idle gaps
    ANNOTATIONS = ("evaluation", "phase_a")
    # a traced run measures one evaluation: phase B's scan puts ~4
    # million operation events on the device trace per 64-lane hour, and
    # the profiler drops events past about one and a half evaluations
    TRACED_SECONDS = 0.0

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 limits: Dict) -> None:
        from repro.experiments import ScenarioSuite
        from repro.serving import jaxengine
        from repro.serving.jaxengine.engine import (
            FALLBACK_COUNTER,
            JaxServingEngine,
        )

        self.config, self.traffic, self.limits = config, traffic, limits
        self.suite = ScenarioSuite.from_spec(
            suite_spec(config, traffic, seed))
        self.fallback_counter = FALLBACK_COUNTER
        # host span around each cell's phase A, and the schedules the
        # last evaluation handed to phase B (for the reference)
        self.phase_a_s = 0.0
        self.schedules: List = []
        original = JaxServingEngine.record_schedule
        driver = self

        def record_schedule(eng, *args, **kwargs):
            t0 = time.perf_counter()
            with _annotate("phase_a"):
                sched = original(eng, *args, **kwargs)
            driver.phase_a_s += time.perf_counter() - t0
            driver.schedules.append(sched)
            return sched

        # and the results of each evaluation's run_cells, per request
        cells_original = jaxengine.run_cells

        def run_cells(*args, **kwargs):
            results = cells_original(*args, **kwargs)
            driver.results.append(results)
            return results

        JaxServingEngine.record_schedule = record_schedule
        jaxengine.run_cells = run_cells

        def unwrap():
            JaxServingEngine.record_schedule = original
            jaxengine.run_cells = cells_original

        self._unwrap = unwrap
        self.results: List = []
        self.reports: List = []
        self.readings: Dict = {}

    def _evaluate(self):
        self.schedules = []
        return self.suite.run(engine="jax")

    def setup(self) -> None:
        """Primes the tapes and compiles phase B: one whole evaluation."""
        self._evaluate()

    def window(self, seconds: float) -> Dict:
        self.phase_a_s = 0.0
        self.results = []
        reports = []
        t0 = time.perf_counter()
        while True:
            with _annotate("evaluation"):
                reports.append(self._evaluate())
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.reports = reports
        n_cells = sum(len(r.cells) for r in reports)
        failed = 0
        for r in reports:
            counters = (r.metrics or {}).get("counters", {})
            failed += int(sum(v for k, v in counters.items()
                              if k.startswith(self.fallback_counter)))
        self.readings = {
            "window_s": elapsed, "evaluations": len(reports),
            "cells": n_cells, "phase_a_s": self.phase_a_s,
        }
        return {"attempted": n_cells, "failed": failed,
                "metrics": {"cells_per_s": n_cells / elapsed}}

    def free(self) -> None:
        """Takes the phase-A span off; nothing of the program's stays on
        the device between evaluations."""
        self._unwrap()

    def _reference(self, dtype=float) -> List[Dict]:
        out = []
        for s in self.schedules:
            sim = dataplane.simulate(cell_arrays(s), dtype)
            out.append(dict(dataplane.summary(sim),
                            latencies=sim["latencies"]))
        return out

    def check(self) -> Dict[str, Dict]:
        """Every evaluation's cells against the reference data plane,
        played in double precision on the schedules phase B was given."""
        want = self._reference()
        worst = {"count_diffs": 0.0, "rel_gap": 0.0}
        for rep, results in zip(self.reports, self.results, strict=True):
            got = [dict({k: c.to_dict(round_to=None)[k]
                         for k in COUNTS + FLOATS}, latencies=r.latencies_s)
                   for c, r in zip(rep.cells, results, strict=True)]
            for k, v in gaps(got, want).items():
                worst[k] = max(worst[k], v)
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in worst.items()}

    def control(self) -> Dict[str, float]:
        """The numbers ``check`` compares, with the reference played in
        single precision in the program's place."""
        return gaps(self._reference(np.float32), self._reference())


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
