"""Golden regression tests: exact fixed-seed end-to-end metrics.

These pin the complete serving pipeline — synthetic aws-1 trace, policy,
cluster FSM, autoscaler, LB, vectorized engine, billing — to the exact
numbers produced at the time this file was written.  Every stage is
seed-deterministic and uses plain IEEE-754 double arithmetic, so any
diff here means a semantic change to the pipeline, not noise.  If a
change is *intended*, rerun the scenario and update the constants in the
same commit (the diff then documents the metric shift).

The spec runs the default engine ("vector"); tests/test_jax_engine.py
holds the JAX engine to the same numbers, and tests/test_differential.py
holds the vector engine to the answers recorded from the per-request
object engine it replaced.

Timeout-semantics note: queue expiry became RTT-inclusive
(``t - arrival + rtt > timeout``, matching the deadline long applied to
completed responses).  The constants below were re-verified after that
change and are *unchanged*: these cells serve same-geo clients whose RTT
is 2 ms, and no queued request sits within 2 ms of the 60 s timeout
boundary at any expiry check.  Cross-region scenarios (where the unified
deadline does shift counts) are covered in tests/test_jax_engine.py.
"""

import dataclasses

import pytest

from repro.service import Service, spec_from_dict


@dataclasses.dataclass(frozen=True)
class GoldenMetrics:
    n_requests: int
    n_completed: int
    n_failed: int
    n_preemptions: int
    n_launch_failures: int
    total_cost: float
    p50_s: float
    p99_s: float
    availability: float


# aws-1 @ 2h, poisson(0.5/s, seed 17), constant N_Tar=3, g5.48xlarge,
# concurrency 2, timeout 60s, drain 300s, sim seed 0
GOLDEN = {
    "spothedge": GoldenMetrics(
        n_requests=3571, n_completed=3501,
        n_failed=70, n_preemptions=1,
        n_launch_failures=0,
        total_cost=50.733135, p50_s=0.703607,
        p99_s=1.692754, availability=0.972917,
    ),
    "even_spread": GoldenMetrics(
        n_requests=3571, n_completed=3501,
        n_failed=70, n_preemptions=1,
        n_launch_failures=12,
        total_cost=28.109217, p50_s=0.703671,
        p99_s=1.692754, availability=0.920833,
    ),
    "ondemand_only": GoldenMetrics(
        n_requests=3571, n_completed=3501,
        n_failed=70, n_preemptions=0,
        n_launch_failures=0,
        total_cost=92.910000, p50_s=0.703671,
        p99_s=1.692754, availability=0.972917,
    ),
    # risk-aware SpotHedge (markov forecaster in the loop): identical
    # serving quality to vanilla spothedge on this calm aws-1 window at
    # ~15% lower cost — the forecast-calm buffer trim at work
    "risk_spothedge": GoldenMetrics(
        n_requests=3571, n_completed=3501,
        n_failed=70, n_preemptions=1,
        n_launch_failures=0,
        total_cost=43.052385, p50_s=0.703607,
        p99_s=1.692754, availability=0.972917,
    ),
}


def _spec(policy: str):
    d = {
        "name": f"golden-{policy}",
        "model": "llama3.2-1b",
        "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "replica_policy": {"name": policy},
        "autoscaler": {"kind": "constant", "target": 3},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 17},
        "sim": {"duration_hours": 2.0, "timeout_s": 60.0,
                "concurrency": 2, "drain_s": 300.0, "seed": 0},
    }
    if policy == "risk_spothedge":
        d["forecast"] = {"name": "markov"}
    return spec_from_dict(d)


@pytest.mark.parametrize("policy", sorted(GOLDEN))
def test_golden_end_to_end_metrics(policy):
    want = GOLDEN[policy]
    res = Service(_spec(policy)).run()
    assert res.n_requests == want.n_requests
    assert res.n_completed == want.n_completed
    assert res.n_failed == want.n_failed
    assert res.n_preemptions == want.n_preemptions
    assert res.n_launch_failures == want.n_launch_failures
    assert res.total_cost == pytest.approx(want.total_cost, abs=1e-6)
    assert res.pct(50) == pytest.approx(want.p50_s, abs=1e-6)
    assert res.pct(99) == pytest.approx(want.p99_s, abs=1e-6)
    assert res.availability == pytest.approx(want.availability, abs=1e-6)


def test_golden_byte_identical_with_explicit_roofline_source():
    """`latency: {source: roofline}` (the default, spelled out) must
    reproduce the golden metrics byte-for-byte — the profile subsystem
    must not perturb default-priced runs in any way."""
    want = GOLDEN["spothedge"]
    d = _spec("spothedge").to_dict()
    assert d["latency"] == {"source": "roofline"}
    res = Service(spec_from_dict(d)).run()        # explicit roofline
    base = Service(_spec("spothedge")).run()      # implicit default
    # bit-identical to the defaulted run...
    assert res.n_requests == base.n_requests
    assert res.n_completed == base.n_completed
    assert res.n_failed == base.n_failed
    assert res.total_cost == base.total_cost
    assert float(res.pct(50)) == float(base.pct(50))
    assert float(res.pct(99)) == float(base.pct(99))
    # ...and on the pinned golden numbers
    assert res.n_requests == want.n_requests
    assert res.total_cost == pytest.approx(want.total_cost, abs=1e-6)
    assert res.pct(50) == pytest.approx(want.p50_s, abs=1e-6)
    assert res.pct(99) == pytest.approx(want.p99_s, abs=1e-6)


def test_golden_token_mode_end_to_end_metrics():
    """Token-mode golden: the same aws-1 scenario priced by the
    continuous-batching engine (sim.replica_model: token).  Pins both the
    classic metrics and the token-level TTFT/TPOT/goodput surface; the
    request-level goldens above prove the opt-in changes nothing else."""
    d = _spec("spothedge").to_dict()
    d["serving"]["slo"] = {"ttft_s": 2.0, "tpot_s": 0.002}
    d["sim"]["replica_model"] = "token"
    res = Service(spec_from_dict(d)).run()
    assert res.n_requests == 3571
    assert res.n_completed == 3501
    assert res.n_failed == 70
    assert res.n_preemptions == 1
    assert res.total_cost == pytest.approx(50.733135, abs=1e-6)
    assert res.pct(50) == pytest.approx(0.704981, abs=1e-6)
    assert res.pct(99) == pytest.approx(1.701918, abs=1e-6)
    tok = res.token
    assert tok is not None and tok.n_recorded == 3501
    assert tok.ttft_pct(50) == pytest.approx(0.562341, abs=1e-6)
    assert tok.ttft_pct(99) == pytest.approx(1.052005, abs=1e-6)
    assert tok.tpot_pct(50) == pytest.approx(0.000739, abs=1e-6)
    assert tok.n_slo_ok == 3477
    assert tok.slo_attainment == pytest.approx(0.973677, abs=1e-6)
    assert tok.goodput_rps == pytest.approx(0.482917, abs=1e-6)


def test_golden_is_reproducible_within_process():
    """Two runs of the same spec are bit-identical (no hidden state)."""
    a = Service(_spec("spothedge")).run()
    b = Service(_spec("spothedge")).run()
    assert a.n_completed == b.n_completed
    assert a.n_failed == b.n_failed
    assert a.total_cost == b.total_cost
    assert a.pct(50) == b.pct(50) and a.pct(99) == b.pct(99)
