"""Serving data plane: latency model, replica queueing, LB, e2e sim."""

from collections import Counter

import numpy as np
import pytest

import fleet
from fleet import ready_times, run_fleet, segments
from repro.cluster.catalog import default_catalog
from repro.cluster.traces import synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import make_policy
from repro.serving.engine import VectorizedServingEngine
from repro.serving.latency import LatencyModel
from repro.workloads import make_workload
from repro.workloads.arrivals import Request

CAT = default_catalog()
CFG = get_config("llama3.2-1b")
LM = LatencyModel.for_model(CFG, CAT.instance_type("g5.48xlarge"))


# ---------------------------------------------------------------------------
# Latency model
# ---------------------------------------------------------------------------


def test_latency_monotone_in_tokens():
    lm = LatencyModel.for_model(CFG, CAT.instance_type("g5.48xlarge"))
    assert lm.service_s(100, 100) < lm.service_s(1000, 100)
    assert lm.service_s(100, 100) < lm.service_s(100, 1000)


def test_decode_dominates_prefill_for_short_prompts():
    """Fig. 6a structure: decoding dominates request time."""
    lm = LatencyModel.for_model(CFG, CAT.instance_type("g5.48xlarge"))
    assert 44 * lm.decode_s_per_token() > lm.prefill_s(20)


def test_processing_dominates_rtt():
    """§3.1: request processing >> inter-region network latency."""
    from repro.cluster.catalog import region_rtt_ms

    big = get_config("command-r-35b")
    lm = LatencyModel.for_model(big, CAT.instance_type("g5.48xlarge"))
    service = lm.service_s(200, 150)
    rtt = region_rtt_ms("us-west-2", "eu-central-1") / 1e3
    assert service > 10 * rtt


# ---------------------------------------------------------------------------
# Replica behaviour, on the engine (one- to three-replica fleets)
# ---------------------------------------------------------------------------


def _req(t, p=50, o=50, **kw):
    return Request(arrival_s=t, prompt_tokens=p, output_tokens=o, **kw)


def test_replica_readiness_follows_instance():
    """No request starts service before the replica's cold start ends."""
    reqs = [_req(t) for t in (10.0, 60.0, 120.0)]
    res, spans = run_fleet(reqs)
    (ready,) = ready_times(res)
    assert ready >= 183.0               # SimConfig's default cold start
    assert res.n_completed == 3
    for sp in spans:
        assert segments(sp, "queue")[0]["t1_s"] >= ready
        assert segments(sp, "service")[0]["t0_s"] >= ready


def test_replica_completes_requests():
    """A request on an idle ready replica completes after its service
    time plus the client RTT."""
    res, (sp,) = run_fleet([_req(300.0)])
    assert res.n_completed == 1 and res.n_failed == 0
    svc = LM.service_s(50, 50)
    (service,) = segments(sp, "service")
    assert service["t0_s"] == 300.0
    assert service["t1_s"] == pytest.approx(300.0 + svc, abs=1e-9)
    assert sp["outcome"] == "ok"
    assert res.latencies_s[0] == pytest.approx(svc + sp["rtt_s"], abs=1e-9)


def test_replica_concurrency_queueing():
    """Concurrency 1 serialises starts: each request waits in the
    replica queue until the one before it has finished."""
    res, spans = run_fleet([_req(300.0, o=500) for _ in range(3)],
                           concurrency=1)
    assert res.n_completed == 3
    service = [segments(sp, "service")[0] for sp in spans]
    for prev, nxt in zip(service, service[1:]):
        assert nxt["t0_s"] >= prev["t1_s"]
    # one slot, so no interference: every service span is the bare time
    for seg in service:
        assert seg["t1_s"] - seg["t0_s"] == pytest.approx(
            LM.service_s(50, 500), rel=1e-9
        )
    assert [len(segments(sp, "rqueue")) for sp in spans] == [1, 1, 1]
    assert segments(spans[2], "rqueue")[0]["t1_s"] > 300.0


def test_replica_kill_returns_inflight():
    """A preemption re-pends the in-flight requests: each is retried on
    the next replica, and the retries are counted."""
    cap = np.ones(fleet.STEPS, np.int32)
    cap[10:15] = 0                      # preempted at t=600 s
    reqs = [_req(595.0 + i, o=20000) for i in range(3)]
    res, spans = run_fleet(reqs, spot_cap=cap, concurrency=4)
    assert res.n_preemptions == 1
    assert res.n_retried_requests == 3
    assert res.n_completed == 3
    second_ready = ready_times(res)[1]
    for sp in spans:
        assert sp["attempts"] == 2
        cut = [s for s in sp["segments"] if s.get("cut") == "preempt"]
        assert [s["t1_s"] for s in cut] == [600.0]
        assert segments(sp, "service")[-1]["t0_s"] >= second_ready
        assert sp["outcome"] == "ok"


def test_replica_queue_expiry():
    """A request queued behind a long one past ``timeout_s`` fails."""
    reqs = [_req(300.0, o=20000), _req(300.0)]
    res, spans = run_fleet(reqs, concurrency=1, timeout_s=10.0)
    assert LM.service_s(50, 20000) > 10.0
    assert res.n_failed >= 1
    sp = spans[1]
    assert sp["outcome"] == "timeout"
    (rq,) = segments(sp, "rqueue")
    assert rq["cut"] == "timeout"
    assert not segments(sp, "service")
    # expiry is RTT-inclusive: t - arrival + rtt > timeout
    assert rq["t1_s"] - 300.0 + 0.002 > 10.0


# ---------------------------------------------------------------------------
# Load balancers, on the engine
# ---------------------------------------------------------------------------


def test_round_robin_cycles():
    """Round-robin spreads requests evenly over the ready replicas."""
    reqs = [_req(300.0 + 0.5 * i) for i in range(30)]
    res, spans = run_fleet(reqs, n_replicas=3, lb="round_robin")
    assert res.n_completed == 30
    picks = [segments(sp, "rqueue")[0]["replica"] for sp in spans]
    assert sorted(Counter(picks).values()) == [10, 10, 10]
    assert picks[:3] == sorted(set(picks))
    assert picks[3:6] == picks[:3]


def test_least_loaded_prefers_idle():
    """Least-loaded sends new work past a busy replica to an idle one."""
    # 2 s apart: each short request's completion is booked (at the next
    # sub-tick) before the following one is routed
    reqs = [_req(300.0, o=20000)] + [_req(301.0 + 2 * i) for i in range(6)]
    res, spans = run_fleet(reqs, n_replicas=2, concurrency=1)
    assert res.n_completed == 7
    assert segments(spans[0], "service")[0]["t1_s"] > reqs[-1].arrival_s
    busy = segments(spans[0], "rqueue")[0]["replica"]
    others = {segments(sp, "rqueue")[0]["replica"] for sp in spans[1:]}
    assert others == {1 - busy}
    with pytest.raises(ValueError, match="lb must be one of"):
        run_fleet(reqs, lb="random")


def test_lb_returns_none_when_nothing_ready():
    """With no ready replica, requests wait in the pending pool; those
    whose wait passes ``timeout_s`` fail there, the rest are served."""
    reqs = [_req(10.0), _req(150.0)]
    res, (early, late) = run_fleet(reqs, timeout_s=100.0)
    (ready,) = ready_times(res)
    assert early["outcome"] == "timeout"
    assert [s["name"] for s in early["segments"]] == ["queue"]
    assert early["segments"][0]["cut"] == "timeout"
    assert early["finish_s"] - 10.0 > 100.0
    assert late["outcome"] == "ok"
    assert segments(late, "queue")[0]["t1_s"] == ready
    assert res.n_completed == 1 and res.n_failed == 1


# ---------------------------------------------------------------------------
# End-to-end serving sim
# ---------------------------------------------------------------------------


def _mini_trace(steps=240):
    zones = ["us-west-2a", "us-west-2b", "us-east-2a"]
    zmap = {z: z[:-1] for z in zones}
    return synth_correlated_trace(zones, zmap, steps=steps, dt=60.0,
                                  seed=21, max_capacity=4, name="mini")


def test_serving_sim_completes_requests():
    tr = _mini_trace()
    reqs = make_workload("poisson", rate_per_s=0.5, seed=1).generate(
        3 * 3600.0
    )
    sim = VectorizedServingEngine(
        tr, make_policy("spothedge"), reqs, CFG, itype="g5.48xlarge",
        autoscaler=ConstantTarget(2), timeout_s=60.0,
        workload_name="poisson",
    )
    res = sim.run(3 * 3600.0 + 600.0)
    assert res.n_requests == len(reqs)
    assert res.n_completed > 0.9 * len(reqs)
    assert res.failure_rate < 0.1
    assert res.pct(50) < 60.0


def test_spothedge_beats_singleregion_spot_on_failures():
    tr = _mini_trace(steps=480)
    reqs = make_workload("poisson", rate_per_s=1.0, seed=2).generate(
        6 * 3600.0
    )

    def run(policy, zones=None):
        sim = VectorizedServingEngine(
            tr, make_policy(policy), reqs, CFG, itype="g5.48xlarge",
            autoscaler=ConstantTarget(3), timeout_s=60.0, concurrency=2,
        )
        return sim.run(6 * 3600.0 + 600.0)

    hedge = run("spothedge")
    spread = run("even_spread")
    assert hedge.failure_rate <= spread.failure_rate
    assert hedge.availability > spread.availability
