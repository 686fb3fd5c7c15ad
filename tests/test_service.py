"""Tests for the declarative service API (spec / loader / builder /
Service) and the typed controller contract."""

import dataclasses
import typing

import numpy as np
import pytest

from repro.cluster.simulator import SimConfig
from repro.cluster.traces import load_trace, synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import (
    Action,
    ControllerEvent,
    EventKind,
    LaunchOnDemand,
    LaunchSpot,
    Policy,
    Terminate,
    make_policy,
)
from repro.serving.engine import VectorizedServingEngine
from repro.service import (
    PlacementFilter,
    ReplicaPolicySpec,
    ResourceSpec,
    Service,
    ServiceSpec,
    SpecError,
    build_service,
    resolve_zones,
    spec_from_dict,
    spec_from_json,
)
from repro.workloads import make_workload


# ---------------------------------------------------------------------------
# spec round-trips
# ---------------------------------------------------------------------------


def test_default_spec_roundtrip():
    spec = ServiceSpec()
    assert spec_from_dict(spec.to_dict()) == spec


def test_full_spec_roundtrip():
    spec = spec_from_dict({
        "name": "svc",
        "model": "command-r-35b",
        "trace": "aws-3",
        "resources": {
            "instance_type": "g5.48xlarge",
            "any_of": [{"region": "us-west-2"}, {"cloud": "gcp"}],
            "exclude_zones": ["us-west-2c"],
        },
        "replica_policy": {
            "name": "spothedge",
            "overprovision": 3,
            "dynamic_fallback": False,
            "args": {"warning_ttl_s": 60.0},
        },
        "autoscaler": {"kind": "load", "target": 6,
                       "qps_per_replica": 1.5},
        "workload": {"kind": "arena", "rate_per_s": 2.0, "seed": 9},
        "sim": {"duration_hours": 1.5, "cold_start_s": 90.0},
        "load_balancer": "round_robin",
    })
    again = spec_from_dict(spec.to_dict())
    assert again == spec
    assert again.resources.any_of[0].region == "us-west-2"
    assert again.replica_policy.policy_kwargs() == {
        "num_overprovision": 3,
        "dynamic_ondemand_fallback": False,
        "warning_ttl_s": 60.0,
    }


def test_spec_from_json_text_and_listing_wrapper():
    spec = spec_from_json(
        '{"service": {"name": "j", "model": "llama3.2-1b",'
        ' "trace": "gcp-1"}}'
    )
    assert spec.name == "j"
    assert spec.trace == "gcp-1"


def test_spec_from_yaml_text():
    yaml = pytest.importorskip("yaml")  # noqa: F841
    from repro.service import spec_from_yaml

    spec = spec_from_yaml(
        "service:\n"
        "  name: y\n"
        "  model: llama3.2-1b\n"
        "  trace: aws-1\n"
        "  resources:\n"
        "    instance_type: p3.2xlarge\n"
        "    any_of:\n"
        "      - region: us-west-2\n"
    )
    assert spec.name == "y"
    assert spec.resources.any_of == (PlacementFilter(region="us-west-2"),)


# ---------------------------------------------------------------------------
# validation errors are actionable
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "overrides, fragment",
    [
        ({"replica_policy": {"name": "not_a_policy"}}, "registered policies"),
        ({"resources": {"any_of": []}}, "any_of is empty"),
        ({"workload": {"rate_per_s": -0.5}}, "must be positive"),
        ({"workload": {"kind": "bogus"}}, "workload.kind"),
        ({"autoscaler": {"kind": "magic"}}, "autoscaler.kind"),
        ({"autoscaler": {"min_replicas": 5, "max_replicas": 2}},
         "min_replicas <= max_replicas"),
        ({"model": "gpt-17"}, "unknown model"),
        ({"trace": "azure-9"}, "unknown trace"),
        ({"resources": {"instance_type": "q9.mega"}}, "instance_type"),
        ({"sim": {"duration_hours": -1}}, "duration_hours"),
        ({"sim": {"drain_s": -600.0}}, "drain_s"),
        ({"sim": {"engine": "legacy"}},
         r"sim.engine must be one of \['vector', 'jax'\]"),
        ({"load_balancer": "random"}, "load_balancer"),
        ({"typo_key": 1}, "unknown keys"),
        ({"replica_policy": {"overprovision": -1}}, "overprovision"),
    ],
)
def test_validation_errors(overrides, fragment):
    with pytest.raises(SpecError, match=fragment):
        spec_from_dict(overrides)


def test_duration_shorter_than_drain_is_spec_error():
    spec = spec_from_dict({
        "workload": {"kind": "poisson", "rate_per_s": 1.0},
        "sim": {"duration_hours": 0.1},     # 360 s < default drain 600 s
    })
    with pytest.raises(SpecError, match="drain_s"):
        build_service(spec, trace=_tiny_trace())


def test_policy_kwarg_mismatch_is_spec_error():
    # round_robin takes no knobs; overprovision must fail loudly at build
    spec = spec_from_dict({
        "replica_policy": {"name": "round_robin", "overprovision": 2},
        "workload": {"kind": "none"},
    })
    with pytest.raises(SpecError, match="rejected its knobs"):
        build_service(spec)


# ---------------------------------------------------------------------------
# zone resolution (any_of)
# ---------------------------------------------------------------------------


def test_resolve_zones_filters_trace():
    from repro.cluster.catalog import default_catalog

    trace = load_trace("aws-3")
    cat = default_catalog()
    res = ResourceSpec(
        any_of=(PlacementFilter(region="us-west-2"),),
        exclude_zones=("us-west-2c",),
    )
    assert resolve_zones(res, trace, cat) == ["us-west-2a", "us-west-2b"]
    with pytest.raises(SpecError, match="matches no zone"):
        resolve_zones(
            ResourceSpec(any_of=(PlacementFilter(cloud="azure"),)),
            trace, cat,
        )


# ---------------------------------------------------------------------------
# builder smoke: Service reproduces a hand-assembled simulator exactly
# ---------------------------------------------------------------------------


def _tiny_trace():
    zones = ["us-west-2a", "us-west-2b", "us-east-1a"]
    return synth_correlated_trace(
        zones, {z: z[:-1] for z in zones},
        steps=120, dt=60.0, max_capacity=3, seed=9, name="tiny",
    )


def test_service_run_matches_hand_assembled_defaults():
    trace = _tiny_trace()
    duration = 1800.0
    spec = spec_from_dict({
        "name": "smoke",
        "model": "llama3.2-1b",
        "trace": "aws-1",            # overridden by the tiny trace below
        "resources": {"instance_type": "p3.2xlarge"},
        "replica_policy": {"name": "spothedge"},
        "autoscaler": {"kind": "constant", "target": 2},
        "workload": {"kind": "poisson", "rate_per_s": 0.4, "seed": 2},
        "sim": {"duration_hours": duration / 3600.0,
                "control_interval_s": 15.0, "timeout_s": 100.0,
                "concurrency": 4},
    })
    got = Service(spec, trace=trace).run()

    # the same run, hand-wired the way launch/serve.py used to do it
    reqs = make_workload("poisson", rate_per_s=0.4, seed=2).generate(
        duration - 600.0
    )
    sim = VectorizedServingEngine(
        trace, make_policy("spothedge"), reqs, get_config("llama3.2-1b"),
        itype="p3.2xlarge",
        autoscaler=ConstantTarget(2),
        lb="least_loaded",
        sim_config=SimConfig(itype="p3.2xlarge", cold_start_s=183.0,
                             control_interval_s=15.0, seed=0),
        timeout_s=100.0, sub_step_s=1.0, workload_name="poisson",
        concurrency=4,
    )
    want = sim.run(duration)

    assert got.n_requests == want.n_requests
    assert got.n_completed == want.n_completed
    assert got.n_failed == want.n_failed
    assert got.availability == want.availability
    assert got.n_preemptions == want.n_preemptions
    np.testing.assert_allclose(got.total_cost, want.total_cost)
    np.testing.assert_allclose(
        np.sort(got.latencies_s), np.sort(want.latencies_s)
    )


def test_service_rerun_is_deterministic():
    spec = spec_from_dict({
        "workload": {"kind": "none"},
        "autoscaler": {"kind": "constant", "target": 2},
        "sim": {"duration_hours": 0.5, "control_interval_s": 30.0},
    })
    trace = _tiny_trace()
    svc = Service(spec, trace=trace)
    a, b = svc.run(), svc.run()          # fresh simulator per run
    assert a.availability == b.availability
    assert a.total_cost == b.total_cost


def test_status_progression():
    spec = spec_from_dict({
        "workload": {"kind": "none"},
        "sim": {"duration_hours": 0.25, "control_interval_s": 30.0},
    })
    svc = Service(spec, trace=_tiny_trace())
    assert svc.status()["state"] == "declared"
    svc.resolve()
    st = svc.status()
    assert st["state"] == "resolved"
    assert st["zones"] == ["us-west-2a", "us-west-2b", "us-east-1a"]
    svc.run()
    st = svc.status()
    assert st["state"] == "finished"
    assert 0.0 <= st["availability"] <= 1.0
    assert st["n_events"] >= 0


# ---------------------------------------------------------------------------
# typed controller contract
# ---------------------------------------------------------------------------


def test_action_is_a_real_union():
    assert set(typing.get_args(Action)) == {
        LaunchSpot, LaunchOnDemand, Terminate
    }


def test_on_event_dispatches_to_hooks():
    seen = []

    class Probe(Policy):
        name = "probe"

        def on_preemption(self, zone, now):
            seen.append(("preempt", zone, now))

        def on_warning(self, zone, now):
            seen.append(("warn", zone, now))

        def decide(self, obs):
            return []

    p = Probe()
    p.on_event(ControllerEvent(EventKind.PREEMPTION, "us-west-2a", 30.0,
                               instance_id=7))
    p.on_event(ControllerEvent(EventKind.WARNING, "us-east-1a", 60.0))
    p.on_event(ControllerEvent(EventKind.LAUNCH_FAILURE, "us-west-2b", 90.0))
    assert seen == [("preempt", "us-west-2a", 30.0),
                    ("warn", "us-east-1a", 60.0)]
    # the base LAUNCH_FAILURE hook records the cooldown
    assert not p._cooled("us-west-2b", 100.0)


def test_cluster_simulator_logs_events():
    spec = spec_from_dict({
        "workload": {"kind": "none"},
        "autoscaler": {"kind": "constant", "target": 3},
        "sim": {"duration_hours": 1.0, "control_interval_s": 30.0},
    })
    resolved = build_service(spec, trace=_tiny_trace())
    resolved.simulator.run(3600.0)
    events = resolved.simulator.cluster.events
    assert events, "an hour against a volatile trace must produce events"
    assert all(isinstance(e, ControllerEvent) for e in events)
    assert any(e.kind is EventKind.READY for e in events)
    ready = next(e for e in events if e.kind is EventKind.READY)
    assert ready.instance_id is not None


# ---------------------------------------------------------------------------
# SimConfig sharing regression (satellite fix)
# ---------------------------------------------------------------------------


def test_serving_sim_does_not_mutate_shared_sim_config():
    shared = SimConfig(itype="p3.2xlarge", control_interval_s=30.0)
    trace = _tiny_trace()
    reqs = make_workload("poisson", rate_per_s=0.2, seed=1).generate(300.0)
    VectorizedServingEngine(
        trace, make_policy("spothedge"), reqs, get_config("llama3.2-1b"),
        itype="g5.48xlarge", sim_config=shared,
    )
    assert shared.itype == "p3.2xlarge"
