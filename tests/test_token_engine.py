"""Token-level continuous-batching engine (repro.serving.token).

Covers the ISSUE-5 acceptance surface: the batch-1 reduction to the
request-level latency model, KV-budget admission invariants (hypothesis),
preemption-loses-KV re-prefill accounting, TTFT/TPOT/goodput metric
units, engine integration (token mode pinned to the recorded answers
in tests/data/engine_record.json), and the
spec/suite plumbing (serving: section, sweep.replica_models axis,
concurrency_cap satellite).
"""

import dataclasses

import numpy as np
import pytest

from engine_record import assert_matches
from repro.cluster.catalog import default_catalog
from repro.cluster.traces import synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import make_policy
from repro.serving.engine import VectorizedServingEngine
from repro.serving.latency import LatencyModel
from repro.serving.token import (
    ContinuousBatch,
    TokenEngineConfig,
    TokenSchedulerConfig,
    TokenStats,
    UNBOUNDED_KV_TOKENS,
)
from repro.service import Service, spec_from_dict
from repro.workloads import make_workload
from repro.workloads.arrivals import Request

CAT = default_catalog()
CFG = get_config("llama3.2-1b")
ITYPE = CAT.instance_type("g5.48xlarge")
LM = LatencyModel.for_model(CFG, ITYPE)
ECFG = TokenEngineConfig.from_latency(LM)


def mk_batch(**knob_overrides) -> ContinuousBatch:
    knobs = TokenSchedulerConfig(**knob_overrides)
    return ContinuousBatch(TokenEngineConfig.from_latency(LM, knobs))


def _mini_trace(steps=180, seed=3):
    zones = ["us-west-2a", "us-west-2b", "us-east-2a"]
    zmap = {z: z[:-1] for z in zones}
    return synth_correlated_trace(zones, zmap, steps=steps, dt=60.0,
                                  seed=seed, max_capacity=4, name="mini")


# ---------------------------------------------------------------------------
# physics: config derivation
# ---------------------------------------------------------------------------


def test_engine_config_matches_latency_model():
    """Decode floor == decode_s_per_token; prefill slope == prefill_s/P;
    KV budget shares max_concurrency's HBM arithmetic."""
    assert ECFG.weight_read_s == LM.decode_s_per_token()
    assert ECFG.prefill_s_per_token * 1000 == pytest.approx(
        LM.prefill_s(1000), rel=1e-12
    )
    # budget_tokens // context slots ~ max_concurrency (same free HBM)
    slots = 4096
    assert abs(ECFG.kv_budget_tokens // slots - LM.max_concurrency()) <= 1


def test_attention_free_arch_unbounded_kv():
    mamba = get_config("falcon-mamba-7b")
    lm = LatencyModel.for_model(mamba, ITYPE)
    ec = TokenEngineConfig.from_latency(lm)
    assert ec.kv_budget_tokens == UNBOUNDED_KV_TOKENS
    assert ec.kv_read_s_per_token == 0.0


# ---------------------------------------------------------------------------
# reduction property: batch 1 + unbounded KV == request-level service_s
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p,o", [(1, 1), (200, 150), (2048, 512), (7, 900)])
def test_batch1_reduces_to_request_level_service_time(p, o):
    b = mk_batch()
    assert b.enqueue(0, p, o, arrival_s=0.0, enqueued_s=0.0)
    done = b.advance(1e9)
    assert len(done) == 1
    e2e = done[0].finish_s - done[0].arrival_s
    svc = LM.service_s(p, o)
    # the only extra over service_s is the (physically real) per-token KV
    # re-read; bound it by o iterations reading at most (p+o) tokens each
    kv_extra = o * ECFG.kv_read_s_per_token * (p + o)
    assert svc - 1e-9 <= e2e <= svc + kv_extra + 1e-9
    assert e2e == pytest.approx(svc, rel=0.05)


def test_batch1_component_equality():
    """Prefill and weight-read components match the roofline exactly."""
    p, o = 300, 100
    b = mk_batch()
    b.enqueue(0, p, o, 0.0, 0.0)
    c = b.advance(1e9)[0]
    ttft = c.first_token_s - c.arrival_s
    # TTFT = overhead + full prefill + one decode iteration (+ first KV read)
    expect = LM.overhead_s + LM.prefill_s(p) + LM.decode_s_per_token()
    assert ttft == pytest.approx(
        expect + ECFG.kv_read_s_per_token * p, abs=1e-12
    )
    # decode phase = (o-1) iterations at weight_read + growing KV reads
    decode = c.finish_s - c.first_token_s
    kv_sum = ECFG.kv_read_s_per_token * sum(p + i for i in range(1, o))
    assert decode == pytest.approx(
        (o - 1) * LM.decode_s_per_token() + kv_sum, rel=1e-9
    )


# ---------------------------------------------------------------------------
# batching physics
# ---------------------------------------------------------------------------


def test_weight_reads_amortize_across_batch():
    """A batch of n finishes in far less than n serial service times —
    the roofline replaces the old 1+0.15·running interference factor."""
    n, p, o = 8, 200, 150
    b = mk_batch()
    for i in range(n):
        b.enqueue(i, p, o, 0.0, 0.0)
    done = b.advance(1e9)
    assert len(done) == n
    makespan = max(c.finish_s for c in done)
    serial = n * LM.service_s(p, o)
    assert makespan < 0.25 * serial


def test_tpot_grows_with_resident_kv():
    """More resident KV tokens -> slower decode steps (per-seq KV reads)."""
    def tpot(n):
        b = mk_batch()
        for i in range(n):
            b.enqueue(i, 1024, 256, 0.0, 0.0)
        done = b.advance(1e9)
        return float(np.mean([
            (c.finish_s - c.first_token_s) / max(c.output_tokens - 1, 1)
            for c in done
        ]))
    assert tpot(32) > tpot(4) > tpot(1) >= ECFG.weight_read_s


def test_chunked_prefill_bounds_decode_stall():
    """A huge prompt joining mid-decode delays other sequences by at most
    ~chunk-sized prefill slices per iteration, not the whole prompt."""
    chunk = 256
    b = mk_batch(prefill_chunk_tokens=chunk)
    b.enqueue(0, 16, 400, 0.0, 0.0)
    b.advance(0.12)                      # seq 0 is decoding by now
    assert b._dec[0] > 0
    d0 = int(b._dec[0])
    t0 = b.now
    b.enqueue(1, 2048, 64, 0.1, 0.1)
    b.advance(t0 + 0.1)
    # seq 0 kept decoding while seq 1 prefilled in chunks
    gap = (b.now - t0) / max(int(b._dec[0]) - d0, 1)
    max_iter = (
        ECFG.iter_overhead_s + chunk * ECFG.prefill_s_per_token
        + ECFG.weight_read_s + ECFG.kv_read_s_per_token * 3000
    )
    assert gap <= max_iter + 1e-9
    assert int(b._dec[0]) - d0 >= 5


# ---------------------------------------------------------------------------
# KV admission invariants (seeded-random; hypothesis variants live in
# tests/test_token_property.py and run where hypothesis is installed)
# ---------------------------------------------------------------------------


def check_kv_admission_invariants(reqs, budget, max_batch):
    b = mk_batch(kv_budget_tokens=budget, max_batch=max_batch)
    t = 0.0
    n_accepted = 0
    completions = []
    for key, (p, o, gap) in enumerate(reqs):
        t += gap
        if b.enqueue(key, p, o, t, t):
            n_accepted += 1
        else:
            assert p + o > budget       # only oversize is refused
        completions += b.advance(t)
        # invariants after every scheduling step
        assert b.n_active <= max_batch
        assert b.reserved_tokens <= budget
        assert b.reserved_tokens == int(
            (b._prompt + b._out).sum()
        )
        assert b.kv_tokens <= b.reserved_tokens
    completions += b.advance(t + 1e7)
    # conservation: everything accepted either completed or is still held
    assert len(completions) + b.load == n_accepted
    assert b.load == 0                  # nothing can be stuck forever
    seen = {c.key for c in completions}
    assert len(seen) == len(completions)


@pytest.mark.parametrize("seed", range(12))
def test_kv_admission_invariants_random(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 30))
    reqs = [
        (int(rng.integers(1, 600)), int(rng.integers(1, 400)),
         float(rng.uniform(0.0, 50.0)))
        for _ in range(n)
    ]
    budget = int(rng.integers(800, 4000))
    max_batch = int(rng.integers(1, 7))
    check_kv_admission_invariants(reqs, budget, max_batch)


def check_clock_monotone(gaps):
    """advance(t) never runs an iteration past t, and time never reverses."""
    b = mk_batch()
    t = 0.0
    last = 0.0
    for k, gap in enumerate(gaps):
        t += gap
        b.enqueue(k, 50, 40, t, t)
        b.advance(t)
        assert b.now <= t + 1e-12
        assert b.now >= last - 1e-12
        last = b.now


@pytest.mark.parametrize("seed", range(6))
def test_clock_monotone_and_bounded_random(seed):
    rng = np.random.default_rng(100 + seed)
    check_clock_monotone(
        [float(g) for g in rng.uniform(0.0, 10.0, int(rng.integers(2, 20)))]
    )


# ---------------------------------------------------------------------------
# preemption: KV state is lost, retries re-prefill
# ---------------------------------------------------------------------------


def test_kill_reports_lost_kv_work():
    b = mk_batch()
    b.enqueue(0, 400, 300, 0.0, 0.0)
    b.enqueue(1, 100, 500, 0.0, 0.0)
    b.advance(0.1)                      # mid-decode
    assert b.n_active == 2              # nothing finished yet
    pref_done = int(b._pref.sum())
    dec_done = int(b._dec.sum())
    assert pref_done == 500 and dec_done > 0
    report = b.kill()
    assert set(report.keys) == {0, 1}
    assert report.n_batch == 2 and report.n_queued == 0
    assert report.lost_prefill_tokens == pref_done
    assert report.lost_decode_tokens == dec_done
    assert b.load == 0 and b.reserved_tokens == 0


def test_retry_pays_full_reprefill():
    """A request killed mid-decode re-prefills from token zero on the
    replica it retries on: its completion reflects both attempts."""
    p, o = 600, 2000
    b1 = mk_batch()
    b1.enqueue(0, p, o, 0.0, 0.0)
    b1.advance(0.4)
    assert int(b1._dec[0]) > 0          # decode underway, work to lose
    b1.kill()
    # retry on a fresh replica at t=0.4 (original arrival rides along)
    b2 = mk_batch()
    b2.enqueue(0, p, o, 0.0, 0.4)
    done = b2.advance(1e9)
    assert len(done) == 1
    e2e = done[0].finish_s - done[0].arrival_s
    # e2e >= wasted first attempt (0.4s) + one full service time
    assert e2e >= 0.4 + LM.service_s(p, o) - 1e-9


def test_simulator_aggregates_preemption_accounting():
    """End-to-end: preemptions on a churny trace surface as KV-loss
    counters in TokenStats, and retried requests complete."""
    tr = _mini_trace(steps=180, seed=3)
    reqs = make_workload("poisson", rate_per_s=0.8, seed=3).generate(
        2 * 3600.0
    )
    sim = VectorizedServingEngine(
        tr, make_policy("spothedge"), reqs, CFG, itype="g5.48xlarge",
        autoscaler=ConstantTarget(3), timeout_s=60.0,
        replica_model="token",
    )
    res = sim.run(2 * 3600.0 + 600.0)
    assert res.n_preemptions > 0
    tok = res.token
    assert tok is not None
    assert tok.n_kv_preempted_seqs + tok.n_killed_queued > 0
    assert res.n_completed > 0.9 * len(reqs)


# ---------------------------------------------------------------------------
# metric units: TTFT / TPOT / goodput
# ---------------------------------------------------------------------------


def _token_run(slo_ttft=10.0, slo_tpot=0.2):
    tr = _mini_trace(steps=120, seed=21)
    reqs = make_workload("poisson", rate_per_s=0.5, seed=1).generate(3600.0)
    sim = VectorizedServingEngine(
        tr, make_policy("spothedge"), reqs, CFG, itype="g5.48xlarge",
        autoscaler=ConstantTarget(2), timeout_s=60.0,
        replica_model="token",
        token_scheduler=TokenSchedulerConfig(
            slo_ttft_s=slo_ttft, slo_tpot_s=slo_tpot
        ),
    )
    return sim.run(3600.0 + 600.0)


def test_metric_units_and_bounds():
    res = _token_run()
    tok = res.token
    assert tok.n_recorded == res.n_completed == len(res.latencies_s)
    # TTFT: at least overhead + one decode step; at most the e2e latency
    assert float(tok.ttft_s.min()) >= LM.overhead_s + ECFG.weight_read_s
    assert (tok.ttft_s <= res.latencies_s.max() + 1e-9).all()
    # TPOT: bounded below by the amortized weight read; sane above
    assert float(tok.tpot_s.min()) >= ECFG.weight_read_s - 1e-12
    assert float(tok.tpot_s.max()) < 1.0
    # goodput accounting is internally consistent
    assert 0 <= tok.n_slo_ok <= tok.n_recorded
    assert tok.slo_attainment == pytest.approx(
        tok.n_slo_ok / tok.n_requests
    )
    assert tok.goodput_rps == pytest.approx(tok.n_slo_ok / 4200.0)
    assert sum(w["n_slo_ok"] for w in tok.windows) == tok.n_slo_ok
    assert sum(w["n_completed"] for w in tok.windows) == tok.n_recorded


def test_slo_targets_gate_goodput():
    lax = _token_run(slo_ttft=50.0, slo_tpot=1.0)
    strict = _token_run(slo_ttft=0.2, slo_tpot=0.0008)
    assert lax.token.n_slo_ok >= strict.token.n_slo_ok
    assert lax.token.slo_attainment > 0.9
    assert strict.token.slo_attainment < lax.token.slo_attainment


def test_stats_to_dict_parses():
    tok = _token_run().token
    d = tok.to_dict()
    assert d["n_recorded"] == tok.n_recorded
    assert d["ttft_p50_s"] is not None
    assert isinstance(d["windows"], list) and d["windows"]
    import json
    json.loads(json.dumps(d))           # JSON-safe


def test_empty_run_stats():
    stats = TokenStats.from_records(
        [], slo_ttft_s=1.0, slo_tpot_s=0.1, horizon_s=10.0,
        window_s=60.0, n_requests=0,
    )
    assert stats.n_recorded == 0 and stats.goodput_rps == 0.0
    assert np.isnan(stats.ttft_pct(50))


# ---------------------------------------------------------------------------
# engine integration: token mode against the recorded answers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy,lb", [
    ("spothedge", None),
    ("even_spread", "rr"),
])
def test_token_mode_differential(policy, lb):
    tr = _mini_trace(steps=150, seed=7)
    reqs = make_workload("poisson", rate_per_s=0.8, seed=7).generate(
        2 * 3600.0
    )
    kwargs = dict(
        itype="g5.48xlarge", autoscaler=ConstantTarget(3),
        timeout_s=60.0, replica_model="token",
    )
    if lb == "rr":
        kwargs["lb"] = "round_robin"
    sim = VectorizedServingEngine(tr, make_policy(policy), reqs, CFG,
                                  **kwargs)
    res = sim.run(2 * 3600.0 + 600.0)
    assert_matches(res, f"token_mode_differential[{policy}-{lb}]",
                   atol=1e-9)


# ---------------------------------------------------------------------------
# spec / suite plumbing
# ---------------------------------------------------------------------------


def _spec_dict(**over):
    d = {
        "name": "tok", "model": "llama3.2-1b", "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "replica_policy": {"name": "spothedge"},
        "autoscaler": {"kind": "constant", "target": 3},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 17},
        "sim": {"duration_hours": 1.0, "timeout_s": 60.0,
                "drain_s": 300.0},
    }
    d.update(over)
    return d


def test_serving_section_round_trip():
    d = _spec_dict(serving={
        "replica_model": "token",
        "slo": {"ttft_s": 2.5, "tpot_s": 0.01},
        "max_batch": 12, "prefill_chunk_tokens": 128,
    })
    spec = spec_from_dict(d)
    assert spec.sim.replica_model == "token"
    assert spec.serving.slo.ttft_s == 2.5
    assert spec.serving.max_batch == 12
    assert spec_from_dict(spec.to_dict()) == spec


def test_serving_replica_model_conflict_rejected():
    from repro.service import SpecError

    d = _spec_dict(serving={"replica_model": "token"})
    d["sim"]["replica_model"] = "request"
    with pytest.raises(SpecError, match="conflicts"):
        spec_from_dict(d)


def test_invalid_replica_model_rejected():
    from repro.service import SpecError

    d = _spec_dict()
    d["sim"]["replica_model"] = "per-token"
    with pytest.raises(SpecError, match="replica_model"):
        spec_from_dict(d)


def test_token_spec_attaches_stats_and_report_fields():
    from repro.experiments.report import CellResult

    d = _spec_dict(serving={"replica_model": "token"})
    res = Service(spec_from_dict(d)).run()
    assert res.token is not None and res.token.n_recorded > 0
    cell = CellResult.from_result({"policy": "spothedge"}, res, 0.1)
    out = cell.to_dict()
    assert out["goodput_rps"] is not None
    assert out["ttft_p50_s"] > 0
    # request-level cells keep the historical artifact shape
    res_req = Service(spec_from_dict(_spec_dict())).run()
    out_req = CellResult.from_result({"policy": "x"}, res_req, 0.1).to_dict()
    assert "goodput_rps" not in out_req and "ttft_p50_s" not in out_req


def test_sweep_replica_models_axis():
    d = _spec_dict(sweep={
        "policies": ["spothedge", "ondemand_only"],
        "replica_models": ["request", "token"],
    })
    from repro.experiments import ScenarioSuite

    suite = ScenarioSuite.from_spec(d)
    assert len(suite) == 4
    models = sorted(
        sc.labels["replica_model"] for sc in suite.scenarios
    )
    assert models == ["request", "request", "token", "token"]
    # same tape across the axis (fair comparison)
    keys = {sc.tape_key for sc in suite.scenarios}
    assert len(keys) == 1


def test_sweep_rejects_unknown_replica_model():
    from repro.service import SpecError

    d = _spec_dict(sweep={"replica_models": ["tokenz"]})
    with pytest.raises(SpecError, match="replica_models"):
        spec_from_dict(d)


# ---------------------------------------------------------------------------
# satellites: concurrency cap + eta residual
# ---------------------------------------------------------------------------


def test_concurrency_cap_lifted_to_spec():
    d = _spec_dict(serving={"concurrency_cap": 3})
    d["sim"]["concurrency"] = None
    from repro.service.builder import build_service

    sim = build_service(spec_from_dict(d)).simulator
    assert sim.concurrency == min(LM.max_concurrency(), 3) == 3
    # default preserves the historical min(max_concurrency, 16)
    d2 = _spec_dict()
    d2["sim"]["concurrency"] = None
    sim2 = build_service(spec_from_dict(d2)).simulator
    assert sim2.concurrency == min(LM.max_concurrency(), 16)


def test_eta_includes_residual_running_time():
    """A request that must wait for a running sequence finishes that
    sequence's residual running time later than it would alone."""
    from fleet import run_fleet, segments

    long = Request(arrival_s=300.0, prompt_tokens=1000, output_tokens=20000)
    probe = Request(arrival_s=305.0, prompt_tokens=50, output_tokens=50)
    kw = dict(replica_model="token",
              token_scheduler=TokenSchedulerConfig(max_batch=1))
    alone, (p_alone,) = run_fleet([probe], **kw)
    busy, (p_long, p_busy) = run_fleet([long, probe], **kw)
    assert alone.n_completed == 1 and busy.n_completed == 2
    # the probe joins the one-sequence batch when the long one's last
    # decode iteration ends; that residual is added to its latency
    end_long = segments(p_long, "decode")[-1]["t1_s"]
    residual = end_long - probe.arrival_s
    assert residual > 0
    assert segments(p_busy, "prefill")[0]["t0_s"] == end_long
    assert p_busy["e2e_s"] == pytest.approx(
        p_alone["e2e_s"] + residual, rel=1e-9
    )
