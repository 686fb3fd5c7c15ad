"""JAX scenario-engine throughput: one vmapped XLA program per matrix.

Runs a wide policy×seed matrix (many same-shape cells — the workload the
jit/vmap engine exists for) through ``ScenarioSuite.run(engine="jax")``
and records wall-clock to ``artifacts/bench/jax_engine.json``:

* ``jax`` — phase A (per-cell control-plane replay) + phase B (all
  request-model data planes batched into one ``lax.scan`` program per
  shape group), cold (includes XLA compile) and warm;
* ``vector`` — the per-cell NumPy engine on the same matrix, serial.

These are host timings of whatever backend JAX runs on; the chip's
numbers come from the on-chip benchmark (``bench/run.py``).

Jax and vector metrics are asserted identical cell-for-cell (the
differential guarantee of tests/test_jax_engine.py, re-checked here
end-to-end), so the timing comparison is apples-to-apples.
"""

from __future__ import annotations

import math
from typing import Dict, List

from benchmarks.common import emit_csv, save
from repro.experiments import ScenarioSuite


def _spec(n_seeds: int, hours: float) -> Dict:
    return {
        "name": "jaxeng",
        "model": "llama3.2-1b",
        "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "replica_policy": {"name": "spothedge"},
        "autoscaler": {"kind": "constant", "target": 3},
        "workload": {"kind": "poisson", "rate_per_s": 1.0, "seed": 0},
        "sim": {
            "duration_hours": hours,
            "timeout_s": 60.0,
            "concurrency": 4,
            "drain_s": 300.0,
        },
        "sweep": {
            "policies": ["spothedge", "even_spread"],
            "seeds": list(range(n_seeds)),
        },
    }


def build_suite(n_seeds: int = 48, hours: float = 1.0) -> ScenarioSuite:
    return ScenarioSuite.from_spec(_spec(n_seeds, hours), name="jax_engine")


def _strip_wall(cells) -> List[Dict]:
    return [
        {k: v for k, v in c.to_dict(round_to=None).items()
         if k != "wall_s"}
        for c in cells
    ]


def _cells_match(a: List[Dict], b: List[Dict]) -> bool:
    """Cell-for-cell equality, floats to 1e-9 relative.

    Counts must match exactly; derived aggregates (mean/percentiles) may
    differ in the last ulp because the engines sum latencies in a
    different order (np.mean is pairwise, hence order-sensitive).
    """
    if len(a) != len(b):
        return False
    for ca, cb in zip(a, b):
        if ca.keys() != cb.keys():
            return False
        for k, va in ca.items():
            vb = cb[k]
            if isinstance(va, float) and isinstance(vb, float):
                if not math.isclose(va, vb, rel_tol=1e-9, abs_tol=1e-12):
                    return False
            elif va != vb:
                return False
    return True


def run(quick: bool = False) -> List[Dict]:
    n_seeds = 4 if quick else 48
    hours = 1.0
    suite = build_suite(n_seeds, hours)
    n_cells = len(suite)

    # first jax run pays tracing + XLA compile; the kernel cache is
    # process-global, so the second run isolates steady-state throughput
    jax_cold = suite.run(engine="jax")
    jax_warm = suite.run(engine="jax")
    vector = suite.run(engine="vector")

    if not _cells_match(_strip_wall(jax_warm.cells),
                        _strip_wall(vector.cells)):
        raise AssertionError(
            "jax engine diverged from the vector engine on the wide "
            "matrix — differential guarantee violated"
        )

    thpt = n_cells / jax_warm.wall_s

    spec = _spec(n_seeds, hours)
    rate = spec["workload"]["rate_per_s"]
    horizon = hours * 3600.0 - spec["sim"]["drain_s"]

    rows: List[Dict] = [
        {
            "metric": "jax_matrix_throughput",
            "n_cells": n_cells,
            "hours": hours,
            "rate_per_s": rate,
            "requests_per_cell": int(rate * horizon),
            "jax_cold_s": round(jax_cold.wall_s, 2),
            "jax_warm_s": round(jax_warm.wall_s, 2),
            "compile_s": round(jax_cold.wall_s - jax_warm.wall_s, 2),
            "throughput_cells_per_s": round(thpt, 2),
            "vector_serial_s": round(vector.wall_s, 2),
            "same_matrix_vs_vector_x": round(
                vector.wall_s / jax_warm.wall_s, 2
            ),
            "metrics_identical": True,
        }
    ]
    save("jax_engine", rows)
    emit_csv("jax_engine", rows)
    return rows


if __name__ == "__main__":
    import sys

    run(quick="--quick" in sys.argv)
