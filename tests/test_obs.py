"""Lockdown for repro.obs: events, registry, exporters, engine parity.

The heavy fixtures run one fixed scenario (the differential-test mini
trace) through both engines at observability detail ``full`` and pin:

* **byte identity** — ``VectorizedServingEngine`` serializes to the
  JSONL bytes recorded from the per-request object engine it replaced
  (SHA-256 in tests/data/engine_record.json; request mode *and*
  token+migration mode);
* **JAX parity** — ``JaxServingEngine``'s phase-A replay reproduces the
  control-plane stream exactly (the vector stream minus data-plane
  records);
* **golden counts** — per-kind event totals for the fixed seed, so an
  emit-site regression (dropped or doubled events) fails loudly;
* **zero observation cost** — detail ``off`` vs ``full`` leaves every
  ``ServingResult`` metric identical (recording is pure observation).

Plus unit coverage for the run-scoped ``MetricsRegistry`` (the
``FALLBACK_COUNTS`` replacement), the exporters (JSONL round-trip,
Perfetto-loadable Chrome trace), the attribution report, the
``observability:`` spec section, and the ``python -m repro.obs`` CLI.
"""

import json

import numpy as np
import pytest

from repro.cluster.traces import synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import make_policy
from repro.experiments.report import CellResult
from repro.migration.config import MigrationSpec
from repro.obs import (
    MetricsRegistry,
    ObsRecorder,
    attribution_report,
    chrome_trace,
    control_plane_records,
    diff_summaries,
    dumps_jsonl,
    get_registry,
    read_jsonl,
    summarize,
    use_registry,
    write_chrome_trace,
    write_jsonl,
)
from engine_record import scenario, sha256
from repro.obs.__main__ import main as obs_main
from repro.serving.engine import VectorizedServingEngine
from repro.serving.jaxengine import JaxServingEngine
from repro.service import Service, SpecError, spec_from_dict
from repro.workloads import make_workload

CFG = get_config("llama3.2-1b")
HOURS = 2.0

# per-kind event totals for the fixed fixture below (detail "full");
# a changed emit site shows up here before it reaches the goldens
GOLDEN_COUNTS = {
    "autoscaler_target": 1,
    "decision": 498,
    "launch_failure": 478,
    "lifecycle": 40,
    "slo_burn": 130,
    "warning": 14,
    "window": 130,
}


def _mini_trace(steps=int(HOURS * 60) + 60, seed=3):
    zones = ["us-west-2a", "us-west-2b", "us-east-2a"]
    zmap = {z: z[:-1] for z in zones}
    return synth_correlated_trace(zones, zmap, steps=steps, dt=60.0,
                                  seed=seed, max_capacity=4, name="mini")


def _run(cls, *, detail="full", replica_model="request", migration=None,
         hours=HOURS):
    trace = _mini_trace(steps=int(hours * 60) + 60)
    reqs = make_workload("poisson", rate_per_s=0.8, seed=3).generate(
        hours * 3600.0
    )
    sim = cls(
        trace, make_policy("spothedge"), reqs, CFG,
        itype="g5.48xlarge", autoscaler=ConstantTarget(3),
        timeout_s=60.0, concurrency=2, workload_name="poisson",
        replica_model=replica_model, migration=migration,
        obs=ObsRecorder(detail=detail),
    )
    return sim.run(hours * 3600.0 + 600.0)


@pytest.fixture(scope="module")
def engine_runs():
    """(vector, jax) results for the fixed request-mode scenario."""
    return (
        _run(VectorizedServingEngine),
        _run(JaxServingEngine),
    )


# ---------------------------------------------------------------------------
# metrics registry


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    assert not reg
    reg.inc("launches", zone="us-west-2a")
    reg.inc("launches", 2, zone="us-west-2a")
    reg.gauge("target", 3)
    reg.observe("latency_s", 1.0)
    reg.observe("latency_s", 3.0)
    assert reg
    assert reg.counter("launches", zone="us-west-2a") == 3
    assert reg.counter("launches", zone="nowhere") == 0
    snap = reg.snapshot()
    assert snap["counters"] == {"launches{zone=us-west-2a}": 3}
    assert snap["gauges"] == {"target": 3}
    h = snap["histograms"]["latency_s"]
    assert h == {"count": 2, "sum": 4.0, "min": 1.0, "max": 3.0}


def test_registry_label_order_is_canonical():
    reg = MetricsRegistry()
    reg.inc("x", a=1, b=2)
    reg.inc("x", b=2, a=1)
    assert reg.snapshot()["counters"] == {"x{a=1,b=2}": 2}


def test_merge_snapshots_adds_counters_and_histograms():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("n", 2)
    b.inc("n", 3)
    a.observe("h", 1.0)
    b.observe("h", 5.0)
    a.gauge("g", 1)
    b.gauge("g", 9)
    merged = MetricsRegistry.merge_snapshots(
        [a.snapshot(), None, {}, b.snapshot()]
    )
    assert merged["counters"] == {"n": 5}
    assert merged["gauges"] == {"g": 9}           # last write wins
    assert merged["histograms"]["h"] == {
        "count": 2, "sum": 6.0, "min": 1.0, "max": 5.0,
    }
    assert MetricsRegistry.merge_snapshots([]) == {}


def test_use_registry_scoping_and_nesting():
    outer, inner = MetricsRegistry(), MetricsRegistry()
    default = get_registry()
    with use_registry(outer):
        get_registry().inc("k")
        with use_registry(inner):
            get_registry().inc("k")
        get_registry().inc("k")
    assert get_registry() is default
    assert outer.counter("k") == 2
    assert inner.counter("k") == 1


def test_latency_profile_fallback_is_run_scoped():
    """The old FALLBACK_COUNTS module global bled across runs; the
    registry counter lands on whichever run is active."""
    from repro.cluster.catalog import default_catalog
    from repro.serving.latency import make_latency_model

    itype = default_catalog().instance_type("g5.48xlarge")
    runs = [MetricsRegistry(), MetricsRegistry()]
    for reg in runs:
        with use_registry(reg), pytest.warns(UserWarning):
            make_latency_model(
                CFG, itype, model_id="no-such-model", source="profile",
                profile="does/not/exist.json",
            )
    for reg in runs:
        assert reg.counter(
            "latency_profile_fallback",
            model="no-such-model", accelerator=itype.accelerator,
        ) == 1


# ---------------------------------------------------------------------------
# recorder


def test_recorder_rejects_bad_config():
    with pytest.raises(ValueError):
        ObsRecorder(detail="verbose")
    with pytest.raises(ValueError):
        ObsRecorder(window_s=0.0)


def test_recorder_replica_ordinals_are_dense_and_stable():
    obs = ObsRecorder()
    assert obs.replica_ordinal(1234) == 0
    assert obs.replica_ordinal(99) == 1
    assert obs.replica_ordinal(1234) == 0
    fresh = obs.fresh()
    assert fresh.detail == obs.detail
    assert fresh.window_s == obs.window_s
    assert fresh.events == []
    assert fresh.replica_ordinal(99) == 0       # fresh map too


# ---------------------------------------------------------------------------
# engine parity (the tentpole contract)


def test_legacy_vector_jsonl_byte_identical(engine_runs):
    vector, _ = engine_runs
    want = scenario("obs_request_events")
    b = dumps_jsonl(vector.obs.events)
    assert len(b.splitlines()) == want["n_lines"]
    assert sha256(b) == want["sha256"]
    assert want["n_lines"] == sum(GOLDEN_COUNTS.values())
    assert want["event_counts"] == GOLDEN_COUNTS


def test_golden_event_counts(engine_runs):
    vector, jx = engine_runs
    assert vector.obs.event_counts() == GOLDEN_COUNTS
    # jax phase-A replays the control plane; no data-plane windows and
    # hence no per-window burn-rate events either
    assert jx.obs.event_counts() == {
        k: v for k, v in GOLDEN_COUNTS.items()
        if k not in ("window", "slo_burn")
    }


def test_jax_matches_vector_control_plane(engine_runs):
    vector, jx = engine_runs
    want = control_plane_records(vector.obs.records())
    assert dumps_jsonl(jx.obs.records()) == dumps_jsonl(want)


def test_decisions_carry_reasons_and_replica_links(engine_runs):
    vector, _ = engine_runs
    decisions = [r for r in vector.obs.records() if r["event"] == "decision"]
    launches = [d for d in decisions if d["action"].startswith("launch")]
    assert launches
    assert any(d.get("reason") for d in decisions)
    # every successful launch links the replica it produced, and that
    # replica's provision event precedes the decision record
    provisioned = {
        r["instance_id"] for r in vector.obs.records()
        if r["event"] == "lifecycle" and r["phase"] == "provision"
    }
    linked = [d["instance_id"] for d in launches if "instance_id" in d]
    assert linked and set(linked) <= provisioned


def test_detail_off_and_full_are_metric_identical():
    off = _run(VectorizedServingEngine, detail="off", hours=1.0)
    full = _run(VectorizedServingEngine, detail="full", hours=1.0)
    assert off.obs is None and off.metrics is None
    assert full.obs is not None and full.obs.events
    assert off.n_requests == full.n_requests
    assert off.n_completed == full.n_completed
    assert off.n_failed == full.n_failed
    assert off.n_preemptions == full.n_preemptions
    assert off.total_cost == full.total_cost
    np.testing.assert_array_equal(
        np.sort(off.latencies_s), np.sort(full.latencies_s)
    )


def test_token_migration_byte_identical():
    spec = MigrationSpec(enabled=True, drain_threshold_s=2.0)
    vector = _run(VectorizedServingEngine, replica_model="token",
                  migration=spec, hours=1.0)
    want = scenario("obs_token_migration_events")
    b = dumps_jsonl(vector.obs.events)
    assert len(b.splitlines()) == want["n_lines"]
    assert sha256(b) == want["sha256"]
    counts = vector.obs.event_counts()
    assert counts.get("migration_plan", 0) > 0


# ---------------------------------------------------------------------------
# exporters


def test_jsonl_roundtrip(tmp_path, engine_runs):
    vector, _ = engine_runs
    path = write_jsonl(vector.obs.events, str(tmp_path / "run.jsonl"))
    records = read_jsonl(path)
    # compare serialized: JSON turns reason tuples into lists
    assert dumps_jsonl(records) == dumps_jsonl(vector.obs.events)
    assert all(r["schema"] == 1 for r in records)


def test_chrome_trace_roundtrip(tmp_path, engine_runs):
    vector, _ = engine_runs
    path = write_chrome_trace(
        vector.obs.events, str(tmp_path / "run.trace.json")
    )
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert trace["otherData"]["schema"] == 1
    phases = {e["ph"] for e in events}
    assert {"M", "X", "i", "C"} <= phases        # spans, markers, counters
    # every complete span is well-formed
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0
    assert json.dumps(
        chrome_trace(vector.obs.records()), sort_keys=True
    ) == json.dumps(trace, sort_keys=True)


def test_summarize_and_diff(engine_runs):
    vector, jx = engine_runs
    s = summarize(vector.obs.records())
    assert s["n_events"] == sum(GOLDEN_COUNTS.values())
    assert s["event_counts"] == GOLDEN_COUNTS
    assert s["decisions"]                        # per-action breakdown
    same = diff_summaries(vector.obs.records(), vector.obs.records())
    assert same["identical"]
    diff = diff_summaries(vector.obs.records(), jx.obs.records())
    assert not diff["identical"]
    assert diff["event_counts"]["window"]["delta"] == -GOLDEN_COUNTS["window"]


def test_attribution_report(engine_runs):
    vector, _ = engine_runs
    rep = attribution_report(vector.obs.records(), top=5)
    assert rep["n_decisions"] == GOLDEN_COUNTS["decision"]
    assert rep["n_replicas"] > 0
    assert rep["total_cost_usd"] == pytest.approx(
        sum(a["cost_usd"] for a in rep["cost_by_action"].values())
    )
    assert len(rep["top_decisions"]) == 5
    tops = [d["cost_usd"] for d in rep["top_decisions"]]
    assert tops == sorted(tops, reverse=True)


def test_cli_smoke(tmp_path, engine_runs, capsys):
    vector, jx = engine_runs
    a = write_jsonl(vector.obs.events, str(tmp_path / "a.jsonl"))
    b = write_jsonl(jx.obs.records(), str(tmp_path / "b.jsonl"))
    assert obs_main(["summarize", a]) == 0
    capsys.readouterr()                         # drop the text output
    assert obs_main(["summarize", a, "--json"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out)["n_events"] == sum(GOLDEN_COUNTS.values())
    assert obs_main(["diff", a, a]) == 0        # identical → exit 0
    assert obs_main(["diff", a, b]) == 1        # different → exit 1
    assert obs_main(["attribute", a, "--top", "3"]) == 0
    trace_out = str(tmp_path / "a.trace.json")
    assert obs_main(["trace", a, "-o", trace_out]) == 0
    with open(trace_out) as f:
        assert json.load(f)["traceEvents"]


# ---------------------------------------------------------------------------
# spec / service / report plumbing


def _spec_dict(**obs):
    d = {
        "name": "obs-smoke",
        "model": "llama3.2-1b",
        "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "autoscaler": {"kind": "constant", "target": 2},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 7},
        "sim": {"duration_hours": 0.5, "timeout_s": 60.0,
                "concurrency": 2},
    }
    if obs:
        d["observability"] = obs
    return d


def test_observability_spec_defaults_and_validation():
    spec = spec_from_dict(_spec_dict())
    assert spec.observability.detail == "decisions"
    assert spec.observability.window_s == 60.0
    spec = spec_from_dict(_spec_dict(detail="full", window_s=30.0))
    assert spec.observability.detail == "full"
    assert spec.to_dict()["observability"]["window_s"] == 30.0
    with pytest.raises(SpecError):
        spec_from_dict(_spec_dict(detail="everything"))
    with pytest.raises(SpecError):
        spec_from_dict(_spec_dict(window_s=0))
    with pytest.raises(SpecError):
        spec_from_dict(_spec_dict(verbosity=3))   # unknown key


def test_service_exports_artifacts_at_full_detail(tmp_path):
    svc = Service(_spec_dict(detail="full", out_dir=str(tmp_path)))
    res = svc.run()
    assert res.obs is not None
    assert set(svc.artifacts) == {"events", "spans", "trace"}
    assert dumps_jsonl(read_jsonl(svc.artifacts["events"])) \
        == dumps_jsonl(res.obs.records())
    assert dumps_jsonl(read_jsonl(svc.artifacts["spans"])) \
        == dumps_jsonl(res.obs.span_records())
    with open(svc.artifacts["trace"]) as f:
        assert json.load(f)["traceEvents"]
    status = svc.status()
    assert status["obs_event_counts"] == res.obs.event_counts()
    assert status["obs_artifacts"] == svc.artifacts


def test_service_default_detail_writes_nothing(tmp_path):
    svc = Service(_spec_dict(out_dir=str(tmp_path)))
    res = svc.run()
    assert res.obs is not None                  # decisions recorded…
    assert svc.artifacts == {}                  # …but no artifacts
    assert list(tmp_path.iterdir()) == []


def test_cell_result_carries_obs_snapshots(engine_runs):
    vector, _ = engine_runs
    cell = CellResult.from_result({"policy": "spothedge"}, vector, 0.1)
    assert cell.obs_event_counts == GOLDEN_COUNTS
    assert cell.obs_windows is not None
    assert len(cell.obs_windows) == GOLDEN_COUNTS["window"]
    d = cell.to_dict()
    assert d["obs_event_counts"] == GOLDEN_COUNTS
