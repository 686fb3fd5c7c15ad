"""ScenarioSuite: grid expansion, sweep loading, reports, execution."""

import json

import pytest

from repro.experiments import Scenario, ScenarioSuite
from repro.service import (
    ReplicaPolicySpec,
    SpecError,
    spec_from_dict,
)

BASE = {
    "name": "exp",
    "model": "llama3.2-1b",
    "trace": "aws-1",
    "resources": {"instance_type": "g5.48xlarge"},
    "autoscaler": {"kind": "constant", "target": 2},
    "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 3},
    "sim": {"duration_hours": 0.5, "timeout_s": 60.0,
            "concurrency": 2, "drain_s": 300.0},
}


def _spec(**over):
    d = {**BASE, **over}
    return spec_from_dict(d)


# ---------------------------------------------------------------------------
# sweep spec + loader
# ---------------------------------------------------------------------------


def test_sweep_grid_size_and_expansion():
    spec = _spec(sweep={
        "policies": ["spothedge", "even_spread"],
        "traces": ["aws-1", "gcp-1"],
        "workloads": ["poisson", "arena"],
        "seeds": [0, 1, 2],
    })
    assert spec.sweep.size == 24
    suite = ScenarioSuite.from_spec(spec)
    assert len(suite) == 24
    labels = {sc.cell_id for sc in suite.scenarios}
    assert len(labels) == 24                      # all cells distinct
    assert "spothedge/aws-1/poisson/0" in labels
    # expanded cells are single-run specs
    assert all(sc.spec.sweep is None for sc in suite.scenarios)


def test_sweep_axes_default_to_base_values():
    spec = _spec(sweep={"policies": ["spothedge", "even_spread"]})
    suite = ScenarioSuite.from_spec(spec)
    assert len(suite) == 2
    for sc in suite.scenarios:
        assert sc.spec.trace == "aws-1"
        assert sc.spec.workload.kind == "poisson"
        assert sc.spec.workload.seed == 3


def test_sweep_policy_entries_accept_mappings():
    spec = _spec(sweep={
        "policies": ["spothedge", {"name": "spothedge",
                                   "overprovision": 3}],
    })
    pols = [sc.spec.replica_policy for sc in
            ScenarioSuite.from_spec(spec).scenarios]
    assert pols[0] == ReplicaPolicySpec(name="spothedge")
    assert pols[1].overprovision == 3


def test_sweep_duplicate_policy_names_get_distinct_labels():
    spec = _spec(sweep={
        "policies": [
            {"name": "spothedge", "overprovision": 0},
            {"name": "spothedge", "overprovision": 2},
        ],
    })
    suite = ScenarioSuite.from_spec(spec)
    labels = [sc.labels["policy"] for sc in suite.scenarios]
    assert len(set(labels)) == 2
    assert all("spothedge" in lab for lab in labels)


def test_sweep_seeds_override_workload_seed():
    spec = _spec(sweep={"seeds": [7, 8]})
    seeds = [sc.spec.workload.seed for sc in
             ScenarioSuite.from_spec(spec).scenarios]
    assert seeds == [7, 8]


def test_sweep_without_seeds_axis_keeps_workload_seeds():
    spec = _spec(sweep={"workloads": [
        {"kind": "poisson", "rate_per_s": 1.0, "seed": 7},
        {"kind": "poisson", "rate_per_s": 2.0, "seed": 9},
    ]})
    cells = ScenarioSuite.from_spec(spec).scenarios
    assert [sc.spec.workload.seed for sc in cells] == [7, 9]
    assert [sc.labels["seed"] for sc in cells] == [7, 9]
    # same kind, different knobs -> labels must stay distinguishable
    labels = [sc.labels["workload"] for sc in cells]
    assert len(set(labels)) == 2


def test_scenario_rejects_metric_shadowing_labels():
    with pytest.raises(SpecError, match="collide"):
        Scenario(labels={"n_requests": "small"}, spec=_spec())


def test_sweep_rejects_unknown_policy_and_trace():
    with pytest.raises(SpecError, match="sweep policy"):
        _spec(sweep={"policies": ["not-a-policy"]})
    with pytest.raises(SpecError, match="sweep trace"):
        _spec(sweep={"traces": ["not-a-trace"]})


def test_sweep_rejects_malformed_sections():
    with pytest.raises(SpecError, match="sweep"):
        _spec(sweep={"policies": "spothedge"})       # not a list
    with pytest.raises(SpecError, match="unknown keys"):
        _spec(sweep={"polices": ["spothedge"]})      # typo'd key


def test_sweep_round_trips_through_dict():
    spec = _spec(sweep={"policies": ["spothedge"], "seeds": [1, 2]})
    assert spec_from_dict(spec.to_dict()) == spec


def test_engine_field_validated():
    with pytest.raises(SpecError, match="sim.engine"):
        _spec(sim={**BASE["sim"], "engine": "warp-drive"})


def test_scenario_rejects_unexpanded_sweep():
    spec = _spec(sweep={"seeds": [1, 2]})
    with pytest.raises(SpecError, match="expand the sweep"):
        Scenario(labels={"x": 1}, spec=spec)


# ---------------------------------------------------------------------------
# suite execution + report
# ---------------------------------------------------------------------------


def _small_suite():
    return ScenarioSuite.from_spec(_spec(sweep={
        "policies": ["spothedge", "even_spread"],
    }))


def test_suite_run_produces_cells_in_order():
    report = _small_suite().run()
    assert [c.labels["policy"] for c in report.cells] == [
        "spothedge", "even_spread"
    ]
    for c in report.cells:
        assert c.n_requests > 0
        assert c.n_completed + c.n_failed <= c.n_requests * 2
        assert 0.0 <= c.availability <= 1.0
        assert c.wall_s > 0


def test_suite_shares_request_tapes_across_cells():
    suite = _small_suite()
    keys = {sc.tape_key for sc in suite.scenarios}
    assert len(keys) == 1          # same workload -> one tape
    report = suite.run()
    assert (report.cells[0].n_requests ==
            report.cells[1].n_requests)


def test_suite_engine_override_matches_default():
    suite = _small_suite()
    vec = suite.run()
    jx = suite.run(engine="jax")
    for a, b in zip(vec.cells, jx.cells):
        assert a.n_completed == b.n_completed
        assert a.n_failed == b.n_failed
        assert a.p50_s == pytest.approx(b.p50_s, abs=1e-9)


def test_suite_parallel_equals_serial():
    suite = _small_suite()
    serial = suite.run()
    parallel = suite.run(workers=2)
    assert parallel.workers == 2
    for a, b in zip(serial.cells, parallel.cells):
        da = {**a.to_dict(round_to=None), "wall_s": None}
        db = {**b.to_dict(round_to=None), "wall_s": None}
        assert da == db


def test_jax_matrix_never_reaches_worker_processes(monkeypatch):
    """engine="jax" runs in the calling process whatever ``workers``
    says: a chip belongs to one process, so a pool worker that needed
    it would fail or hang while the parent holds it."""
    def no_pool(*args, **kwargs):
        raise AssertionError("device cells reached the process pool")

    monkeypatch.setattr(ScenarioSuite, "_run_parallel", no_pool)
    report = _small_suite().run(engine="jax", workers=2)
    assert report.workers == 1 and len(report.cells) == 2


def test_worker_cells_start_no_jax_backend():
    """What a pool worker runs (request and token cells on the host
    engines) never initialises a JAX backend, so workers forked beside a
    process that holds the chip never contend for it."""
    import os
    import subprocess
    import sys

    code = (
        "from jax._src import xla_bridge\n"
        "from repro.experiments.suite import _run_scenario_worker\n"
        "from tests.test_experiments import _small_suite, _spec\n"
        "from repro.experiments import ScenarioSuite\n"
        "token = ScenarioSuite.from_spec(_spec(sweep={'policies': "
        "['spothedge'], 'replica_models': ['token']}))\n"
        "for sc in _small_suite().scenarios + token.scenarios:\n"
        "    _run_scenario_worker((sc, None))\n"
        "print(xla_bridge.backends_are_initialized())\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "src"), root]))
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_report_select_and_json_artifact(tmp_path):
    report = _small_suite().run(save_to=str(tmp_path))
    assert len(report.select(policy="spothedge")) == 1
    assert report.select(policy="nope") == []

    path = tmp_path / "scenario_exp.json"
    assert path.exists()
    doc = json.loads(path.read_text())
    assert doc["schema"] == 1
    assert doc["suite"] == "exp"
    assert doc["n_cells"] == 2
    cell = doc["cells"][0]
    for key in ("policy", "trace", "workload", "seed", "n_requests",
                "n_completed", "n_failed", "failure_rate", "p50_s",
                "p90_s", "p99_s", "total_cost", "cost_vs_ondemand",
                "availability", "n_preemptions", "wall_s"):
        assert key in cell, f"artifact cell missing {key}"


def test_suite_requires_scenarios():
    with pytest.raises(SpecError, match="at least one"):
        ScenarioSuite([])


def test_suite_rejects_bad_worker_counts():
    suite = _small_suite()
    with pytest.raises(SpecError, match="workers"):
        suite.run(workers="two")
    with pytest.raises(SpecError, match="workers"):
        suite.run(workers=0)


def test_worker_tape_cache_keyed_by_workload():
    """Reusing a tape_key with a different workload must not replay the
    first workload's arrivals (the worker cache outlives one run)."""
    spec_a = _spec()
    spec_b = _spec(workload={"kind": "poisson", "rate_per_s": 2.0,
                             "seed": 9})
    suite_a = ScenarioSuite(
        [Scenario(labels={"case": "a"}, spec=spec_a, tape_key="shared")],
        name="tapes-a",
    )
    suite_b = ScenarioSuite(
        [Scenario(labels={"case": "b"}, spec=spec_b, tape_key="shared")],
        name="tapes-b",
    )
    ra = suite_a.run(workers=2)
    rb = suite_b.run(workers=2)
    # 4x the rate -> far more requests; a stale shared tape would make
    # the two runs identical
    assert rb.cells[0].n_requests > 2 * ra.cells[0].n_requests


def test_suite_custom_scenarios_with_trace_override():
    from repro.cluster.traces import TraceLibrary

    tr = TraceLibrary().get("aws-1")
    base = _spec()
    sliced = tr.slice_zones(list(tr.zones[:2]))
    suite = ScenarioSuite(
        [Scenario(labels={"case": "sliced"}, spec=base, trace=sliced)],
        name="custom",
    )
    report = suite.run()
    assert len(report.cells) == 1
    assert report.cells[0].labels == {"case": "sliced"}
