"""Differential lockdown for the jit/vmap JAX scenario engine.

Every scenario runs the same trace / policy / tape / autoscaler / LB
through the NumPy oracle (``VectorizedServingEngine``) and the two-phase
JAX engine (``JaxServingEngine``) and asserts identical decisions:
request / completion / failure / retry counts, cost, and latency arrays
equal to 1e-6.  Scenarios cross the behavioral regimes — spot churn
with retries, round-robin vs least-loaded, autoscaler terminations,
saturation with queue expiry, cross-region RTT timeout boundaries,
token-model delegation, and the batched suite path.

Also pins the ``_workload_tape_key`` canonicalizer: stable across
process boundaries (the bug: ``json.dumps(default=repr)`` embedded
memory addresses), order-insensitive, type-strict.
"""

import dataclasses
import subprocess
import sys

import numpy as np
import pytest

from repro.cluster.traces import synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget, LoadAutoscaler
from repro.core.policy import make_policy
from repro.experiments.suite import (
    ScenarioSuite,
    _canonical_args,
    _workload_tape_key,
)
from repro.serving.engine import VectorizedServingEngine
from repro.serving.jaxengine import JaxServingEngine, run_cells
from repro.serving.jaxengine.engine import FALLBACK_COUNTER
from repro.service import Service, SpecError, spec_from_dict
from repro.workloads import make_workload

CFG = get_config("llama3.2-1b")


def _mini_trace(steps, seed):
    zones = ["us-west-2a", "us-west-2b", "us-east-2a"]
    zmap = {z: z[:-1] for z in zones}
    return synth_correlated_trace(zones, zmap, steps=steps, dt=60.0,
                                  seed=seed, max_capacity=4, name="mini")


def _run_both(policy, workload, *, hours=1.0, seed=3, rate=0.8,
              autoscaler=None, lb=None, timeout_s=60.0,
              concurrency=2, client_regions=None, replica_model="request"):
    """Run (vector, jax) on one scenario; identical inputs for both."""
    trace = _mini_trace(steps=int(hours * 60) + 60, seed=seed)
    rate_key = "rate_per_s" if workload == "poisson" else "base_rate_per_s"
    wargs = {rate_key: rate, "seed": seed}
    if client_regions is not None:
        wargs["client_regions"] = client_regions
    reqs = make_workload(workload, **wargs).generate(hours * 3600.0)
    out = []
    for cls in (VectorizedServingEngine, JaxServingEngine):
        kwargs = dict(
            itype="g5.48xlarge",
            autoscaler=autoscaler() if autoscaler else ConstantTarget(3),
            timeout_s=timeout_s,
            concurrency=concurrency,
            workload_name=workload,
            replica_model=replica_model,
        )
        if lb is not None:
            kwargs["lb"] = lb
        sim = cls(trace, make_policy(policy), reqs, CFG, **kwargs)
        out.append(sim.run(hours * 3600.0 + 600.0))
    return out


def _assert_equivalent(vector, jx):
    assert jx.n_requests == vector.n_requests
    assert jx.n_completed == vector.n_completed
    assert jx.n_failed == vector.n_failed
    assert jx.n_preemptions == vector.n_preemptions
    assert jx.n_launch_failures == vector.n_launch_failures
    assert jx.n_retried_requests == vector.n_retried_requests
    assert jx.total_cost == pytest.approx(vector.total_cost, abs=1e-9)
    assert jx.availability == pytest.approx(vector.availability, abs=1e-12)
    lat_v = np.sort(vector.latencies_s)
    lat_j = np.sort(jx.latencies_s)
    assert len(lat_v) == len(lat_j)
    if len(lat_v):
        np.testing.assert_allclose(lat_j, lat_v, atol=1e-6, rtol=0)


# ---------------------------------------------------------------------------
# engine-level differentials
# ---------------------------------------------------------------------------


def test_spothedge_poisson_least_loaded():
    """Spot churn + preemption re-pends through the LL balancer."""
    vector, jx = _run_both("spothedge", "poisson", hours=2.0)
    assert vector.n_completed > 0
    _assert_equivalent(vector, jx)


def test_even_spread_arena_round_robin():
    """Bursty arrivals through the round-robin cursor."""
    vector, jx = _run_both(
        "even_spread", "arena", hours=2.0, lb="round_robin"
    )
    assert vector.n_completed > 0
    _assert_equivalent(vector, jx)


def test_aws_spot_maf_load_autoscaler():
    """Diurnal load: autoscaler-driven launches AND terminations (kill
    events on both the preempt and the policy-terminate window edge)."""
    vector, jx = _run_both(
        "aws_spot", "maf", hours=2.0,
        autoscaler=lambda: LoadAutoscaler(
            0.8, min_replicas=1, max_replicas=6, initial_target=2,
            upscale_delay_s=60.0, downscale_delay_s=300.0,
        ),
    )
    assert vector.n_completed > 0
    _assert_equivalent(vector, jx)


def test_saturated_queues_and_expiry():
    """Overload: deep queues, RTT-inclusive expiry, mid-queue stragglers
    from re-pended requests with original arrival times."""
    vector, jx = _run_both(
        "spothedge", "poisson", rate=6.0, concurrency=1,
        timeout_s=30.0, hours=1.0,
    )
    assert vector.n_failed > 0
    _assert_equivalent(vector, jx)


def test_cross_region_rtt_timeout_boundary():
    """Clients split across regions: the RTT term in the unified timeout
    (queue expiry AND completion deadline) must move the same requests
    across the boundary in both engines.  Sub-second timeout with ~70 ms
    cross-country RTTs makes the boundary load-bearing."""
    vector, jx = _run_both(
        "spothedge", "poisson", rate=2.0, hours=1.0, timeout_s=2.5,
        client_regions={"us-west-2": 0.5, "us-east-2": 0.3,
                        "eu-west-1": 0.2},
    )
    assert vector.n_failed > 0      # the boundary must actually bite
    _assert_equivalent(vector, jx)


def test_token_model_delegates_to_oracle():
    """``replica_model: token`` on the jax engine runs the oracle's
    continuous-batching data plane — exact equality, token stats intact."""
    vector, jx = _run_both(
        "spothedge", "poisson", hours=1.0, replica_model="token"
    )
    assert vector.n_completed > 0
    _assert_equivalent(vector, jx)
    assert jx.token is not None
    assert jx.token.n_recorded == vector.token.n_recorded
    assert jx.token.goodput_rps == pytest.approx(
        vector.token.goodput_rps, abs=1e-9
    )


def test_queue_overflow_falls_back_to_oracle():
    """A cell whose queue pool is too small must rerun on the oracle
    (exactness over speed), never drop work."""
    trace = _mini_trace(steps=120, seed=3)
    reqs = make_workload("poisson", rate_per_s=6.0, seed=3).generate(3600.0)
    vec = VectorizedServingEngine(
        trace, make_policy("spothedge"), reqs, CFG,
        itype="g5.48xlarge", autoscaler=ConstantTarget(3),
        timeout_s=30.0, concurrency=1,
    )
    jx = JaxServingEngine(
        trace, make_policy("spothedge"), reqs, CFG,
        itype="g5.48xlarge", autoscaler=ConstantTarget(3),
        timeout_s=30.0, concurrency=1,
    )
    jx.queue_capacity = 2           # force overflow under saturation
    _assert_equivalent(vec.run(4200.0), jx.run(4200.0))


def _fallbacks(res, reason):
    counters = (res.metrics or {}).get("counters", {})
    return counters.get(f"{FALLBACK_COUNTER}{{reason={reason}}}", 0)


@pytest.mark.parametrize("reason", ["token", "overflow"])
def test_numpy_fallbacks_are_counted(reason):
    """Every cell the jax engine hands to NumPy counts once, by reason,
    on that cell's registry; a cell that ran on the device counts none."""
    trace = _mini_trace(steps=120, seed=3)
    reqs = make_workload("poisson", rate_per_s=6.0, seed=3).generate(3600.0)

    def engine(replica_model="request"):
        return JaxServingEngine(
            trace, make_policy("spothedge"), reqs, CFG,
            itype="g5.48xlarge", autoscaler=ConstantTarget(3),
            timeout_s=30.0, concurrency=1, replica_model=replica_model,
        )

    if reason == "token":
        fell_back = engine(replica_model="token")
    else:
        fell_back = engine()
        fell_back.queue_capacity = 2
    # one batch sizes its queue pools for its largest capacity, so the
    # undersized cell runs in a batch of its own
    (fell,) = run_cells([fell_back], [4200.0])
    kept = engine().run(4200.0)
    assert _fallbacks(fell, reason) == 1
    assert _fallbacks(kept, "token") == _fallbacks(kept, "overflow") == 0


# ---------------------------------------------------------------------------
# spec / suite plumbing
# ---------------------------------------------------------------------------


def _spec_dict(policy="spothedge", seed=0, engine="vector"):
    return {
        "name": f"jaxdiff-{policy}-{seed}",
        "model": "llama3.2-1b",
        "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "replica_policy": {"name": policy},
        "autoscaler": {"kind": "constant", "target": 3},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 17},
        "sim": {"duration_hours": 1.0, "timeout_s": 60.0,
                "concurrency": 2, "drain_s": 300.0, "seed": seed,
                "engine": engine},
    }


def test_engine_jax_via_service_spec():
    """``sim.engine: "jax"`` end to end through Service.run()."""
    res_v = Service(spec_from_dict(_spec_dict(engine="vector"))).run()
    res_j = Service(spec_from_dict(_spec_dict(engine="jax"))).run()
    _assert_equivalent(res_v, res_j)


def test_suite_matrix_batched_path_matches_vector():
    """``ScenarioSuite.run(engine="jax")`` batches the whole matrix into
    vmapped programs; every cell metric must match the vector path."""
    spec = spec_from_dict({
        **_spec_dict(),
        "sweep": {"policies": ["spothedge", "even_spread"],
                  "seeds": [0, 1]},
    })
    suite = ScenarioSuite.from_spec(spec)
    rep_v = suite.run(engine="vector")
    rep_j = suite.run(engine="jax")
    assert rep_j.engine == "jax"
    assert len(rep_j.cells) == len(rep_v.cells) == 4
    for cv, cj in zip(rep_v.cells, rep_j.cells):
        assert cj.labels == cv.labels
        assert cj.n_requests == cv.n_requests
        assert cj.n_completed == cv.n_completed
        assert cj.n_failed == cv.n_failed
        assert cj.n_preemptions == cv.n_preemptions
        assert cj.total_cost == pytest.approx(cv.total_cost, abs=1e-9)
        assert cj.p50_s == pytest.approx(cv.p50_s, abs=1e-6)
        assert cj.p99_s == pytest.approx(cv.p99_s, abs=1e-6)


# ---------------------------------------------------------------------------
# tape-key canonicalizer regressions
# ---------------------------------------------------------------------------


def test_tape_key_order_insensitive_and_type_strict():
    a = _canonical_args({"regions": {"us-west-2": 0.5, "us-east-2": 0.5},
                         "burst": [1, 2, 3]})
    b = _canonical_args({"burst": [1, 2, 3],
                         "regions": {"us-east-2": 0.5, "us-west-2": 0.5}})
    assert a == b and hash(a) == hash(b)
    # True == 1 under tuple equality; tape keys must distinguish them
    assert _canonical_args({"flag": True}) != _canonical_args({"flag": 1})


def test_tape_key_rejects_unstable_values():
    class Opaque:
        pass

    with pytest.raises(SpecError, match="cannot canonicalize"):
        _canonical_args({"x": Opaque()})
    with pytest.raises(SpecError, match="not a string"):
        _canonical_args({1: "a"})
    # the old default=repr fallback would have happily embedded the
    # object's memory address here — different key every process


_KEY_SCRIPT = """
import sys
from repro.experiments.suite import _workload_tape_key
from repro.service import spec_from_dict

spec = spec_from_dict({
    "name": "stab", "model": "llama3.2-1b", "trace": "aws-1",
    "resources": {"instance_type": "g5.48xlarge"},
    "autoscaler": {"kind": "constant", "target": 2},
    "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 7,
                 "args": {"client_regions": {"us-west-2": 0.6,
                                             "us-east-2": 0.4}}},
    "sim": {"duration_hours": 1.0, "drain_s": 300.0},
})
sys.stdout.write(repr(_workload_tape_key(spec)))
"""


def test_tape_key_stable_across_process_boundaries():
    """The regression the canonicalizer fixes: keys computed in freshly
    spawned interpreters (different hash seeds, different heap layouts)
    must be identical, or spawn-started suite workers stop sharing
    tapes.  The old repr-based key embedded ``object.__repr__`` memory
    addresses and failed exactly this check."""
    keys = set()
    for hashseed in ("0", "1", "31337"):
        proc = subprocess.run(
            [sys.executable, "-c", _KEY_SCRIPT],
            capture_output=True, text=True, timeout=120,
            env={
                "PYTHONPATH": "src",
                "PYTHONHASHSEED": hashseed,
                "PATH": "/usr/bin:/bin:/usr/local/bin",
            },
            cwd="/root/repo" if sys.path else None,
        )
        assert proc.returncode == 0, proc.stderr
        keys.add(proc.stdout)
    assert len(keys) == 1, f"tape key unstable across processes: {keys}"


def test_tape_key_matches_in_process_value():
    """Subprocess keys equal the parent's (not just each other)."""
    spec = spec_from_dict({
        "name": "stab", "model": "llama3.2-1b", "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "autoscaler": {"kind": "constant", "target": 2},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 7,
                     "args": {"client_regions": {"us-west-2": 0.6,
                                                 "us-east-2": 0.4}}},
        "sim": {"duration_hours": 1.0, "drain_s": 300.0},
    })
    proc = subprocess.run(
        [sys.executable, "-c", _KEY_SCRIPT],
        capture_output=True, text=True, timeout=120,
        env={
            "PYTHONPATH": "src",
            "PYTHONHASHSEED": "1729",
            "PATH": "/usr/bin:/bin:/usr/local/bin",
        },
        cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout == repr(_workload_tape_key(spec))
