"""Differential tests: the vectorized engine gives the answers recorded
from the per-request object engine it replaced.

Each scenario runs a fixed trace / policy / request tape / autoscaler /
LB through ``VectorizedServingEngine`` and holds it to the record in
``tests/data/engine_record.json`` (see ``engine_record.py``): identical
completion, failure, preemption and retry counts, cost to 1e-9 relative,
and the sorted latency sample (size, sum, extremes, percentiles 1..99)
to 1e-6.  Scenarios are chosen to cross the behavioral regimes:
multi-zone spot churn, round-robin vs least-loaded balancing, load
autoscaling with terminations, saturation with queue expiry, and an
on-demand-only fleet.  The same scenarios run through
``JaxServingEngine`` too, which must answer on its device path.
"""

import pytest

from engine_record import assert_matches
from repro.cluster.traces import synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget, LoadAutoscaler
from repro.core.policy import make_policy
from repro.serving.engine import VectorizedServingEngine
from repro.serving.jaxengine import JaxServingEngine
from repro.serving.jaxengine.engine import FALLBACK_COUNTER
from repro.workloads import make_workload

CFG = get_config("llama3.2-1b")


def _mini_trace(steps, seed):
    zones = ["us-west-2a", "us-west-2b", "us-east-2a"]
    zmap = {z: z[:-1] for z in zones}
    return synth_correlated_trace(zones, zmap, steps=steps, dt=60.0,
                                  seed=seed, max_capacity=4, name="mini")


def _load_autoscaler():
    return LoadAutoscaler(
        0.8, min_replicas=1, max_replicas=6, initial_target=2,
        upscale_delay_s=60.0, downscale_delay_s=300.0,
    )


#: request-mode scenarios: record name -> (policy, workload, overrides)
SCENARIOS = {
    # spot churn + retries through the least-loaded balancer
    "spothedge_poisson_least_loaded": ("spothedge", "poisson", {}),
    # bursty arrivals through the round-robin balancer
    "even_spread_arena_round_robin": (
        "even_spread", "arena", {"lb": "round_robin"}
    ),
    # diurnal load + autoscaler-driven launches AND terminations
    "aws_spot_maf_load_autoscaler": (
        "aws_spot", "maf", {"autoscaler": _load_autoscaler}
    ),
    # no preemptions; the steady immediate-start fast path
    "ondemand_only_stable_fleet": ("ondemand_only", "poisson", {}),
    # overload: deep queues, client-timeout expiry, request failures
    "saturated_queues_and_expiry": (
        "spothedge", "poisson",
        {"rate": 6.0, "concurrency": 1, "timeout_s": 30.0, "hours": 1.0},
    ),
}


def _run(name, engine_cls=VectorizedServingEngine):
    policy, workload, over = SCENARIOS[name]
    hours = over.get("hours", 2.0)
    seed, rate = 3, over.get("rate", 0.8)
    trace = _mini_trace(steps=int(hours * 60) + 60, seed=seed)
    rate_key = "rate_per_s" if workload == "poisson" else "base_rate_per_s"
    reqs = make_workload(workload, **{rate_key: rate}, seed=seed).generate(
        hours * 3600.0
    )
    autoscaler = over.get("autoscaler")
    sim = engine_cls(
        trace, make_policy(policy), reqs, CFG,
        itype="g5.48xlarge",
        autoscaler=autoscaler() if autoscaler else ConstantTarget(3),
        timeout_s=over.get("timeout_s", 60.0),
        concurrency=over.get("concurrency", 2),
        workload_name=workload,
        lb=over.get("lb", "least_loaded"),
    )
    return sim.run(hours * 3600.0 + 600.0)


def test_spothedge_poisson_least_loaded():
    res = _run("spothedge_poisson_least_loaded")
    assert res.n_completed > 0
    assert_matches(res, "spothedge_poisson_least_loaded")


def test_even_spread_arena_round_robin():
    res = _run("even_spread_arena_round_robin")
    assert res.n_completed > 0
    assert_matches(res, "even_spread_arena_round_robin")


def test_aws_spot_maf_load_autoscaler():
    res = _run("aws_spot_maf_load_autoscaler")
    assert res.n_completed > 0
    assert_matches(res, "aws_spot_maf_load_autoscaler")


def test_ondemand_only_stable_fleet():
    res = _run("ondemand_only_stable_fleet")
    assert res.n_preemptions == 0
    assert_matches(res, "ondemand_only_stable_fleet")


def test_saturated_queues_and_expiry():
    res = _run("saturated_queues_and_expiry")
    assert res.n_failed > 0          # saturation must actually occur
    assert_matches(res, "saturated_queues_and_expiry")


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_jax_engine_gives_the_recorded_answers(name):
    """The fast engine, on the device path (no NumPy fallback), gives
    the same recorded answers."""
    res = _run(name, JaxServingEngine)
    counters = (res.metrics or {}).get("counters", {})
    assert not any(k.startswith(FALLBACK_COUNTER) for k in counters)
    assert_matches(res, name)


def test_engine_via_service_spec_matches_legacy():
    """A spec run through Service gives the recorded answers."""
    from repro.service import Service, spec_from_dict

    spec = spec_from_dict({
        "name": "diff", "model": "llama3.2-1b", "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "autoscaler": {"kind": "constant", "target": 2},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 11},
        "sim": {"duration_hours": 1.0, "timeout_s": 60.0,
                "concurrency": 2, "drain_s": 300.0},
    })
    assert spec.sim.engine == "vector"
    assert_matches(Service(spec).run(), "engine_via_service_spec")
