"""Grace-period KV migration on preemption (repro.migration).

Covers the ISSUE-6 acceptance surface: drain/migrate/kill decision
boundaries (grace budget exhausted, target KV budget full, bandwidth
starvation, int8 rescue, NIC serialization), the transfer / elastic
re-shard cost model, ContinuousBatch KV injection, the runtime
executor, spec/loader plumbing (``migration:`` section, the
``sweep.migration`` axis, the ``preemption_warning_s`` trace override),
retried/lost-KV accounting against the answers recorded in
tests/data/engine_record.json with migration off and ON, and the
migration-off byte-identical golden property (hypothesis).
"""

import dataclasses
import json
import math

import numpy as np
import pytest

from engine_record import assert_matches
from repro.cluster.catalog import (
    INTER_CLOUD_GBPS,
    INTRA_ZONE_GBPS,
    default_catalog,
    link_bandwidth_gbps,
)
from repro.cluster.simulator import ClusterSimulator, SimConfig
from repro.cluster.traces import SpotTrace, load_trace, synth_correlated_trace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import make_policy
from repro.migration import (
    INT8_KV_FACTOR,
    MigratedSeq,
    MigrationRuntime,
    MigrationSpec,
    ReshardCost,
    SeqState,
    TargetInfo,
    compression_factor,
    kv_transfer_bytes,
    kv_transfer_s,
    plan_preemption,
    plan_reshard,
)
from repro.serving.engine import VectorizedServingEngine
from repro.serving.latency import LatencyModel
from repro.serving.jaxengine import JaxServingEngine
from repro.serving.token import (
    ContinuousBatch,
    TokenEngineConfig,
    TokenSchedulerConfig,
)
from repro.service import SpecError, spec_from_dict
from repro.service.builder import build_service
from repro.workloads import make_workload

CAT = default_catalog()
CFG = get_config("llama3.2-1b")
ITYPE = CAT.instance_type("g5.48xlarge")
LM = LatencyModel.for_model(CFG, ITYPE)

# a hand-sized engine config so planner byte/second math is exact:
# 1 MB per KV token, 20 ms/decode-token, 1 ms/prefill-token
PCFG = TokenEngineConfig(
    weight_read_s=0.02,
    kv_read_s_per_token=0.0,
    prefill_s_per_token=0.001,
    overhead_s=0.0,
    iter_overhead_s=0.0,
    kv_budget_tokens=100_000,
    prefill_chunk_tokens=512,
    max_batch=1 << 30,
    kv_bytes_per_token=1e6,
)


def _gbps(g: float) -> float:
    return g * 1e9 / 8.0


def _seq(key, prompt=100, out=200, pref=100, dec=0, arrival=0.0):
    return SeqState(key, prompt, out, pref, dec, arrival, arrival,
                    float("nan"))


def _tgt(rid=0, headroom=50_000, gbps=10.0):
    return TargetInfo(rid, headroom, _gbps(gbps))


def _mini_trace(steps=180, seed=3):
    zones = ["us-west-2a", "us-west-2b", "us-east-2a"]
    zmap = {z: z[:-1] for z in zones}
    return synth_correlated_trace(zones, zmap, steps=steps, dt=60.0,
                                  seed=seed, max_capacity=4, name="mini")


# ---------------------------------------------------------------------------
# planner: drain/migrate/kill boundaries
# ---------------------------------------------------------------------------


def test_drain_when_remaining_work_fits_threshold():
    spec = MigrationSpec(enabled=True, drain_threshold_s=2.0)
    # fully prefilled, 50 decode tokens left -> 1.0s remaining <= 2.0s
    s = _seq(1, prompt=100, out=200, pref=100, dec=150)
    tgt = _tgt()
    [d] = plan_preemption([s], [tgt], 120.0, PCFG, spec)
    assert d.action == "drain"
    assert d.transfer_s == 0.0 and d.target_rid is None
    assert tgt.headroom_tokens == 50_000   # drains ship nothing


def test_drain_cap_is_min_of_threshold_and_grace():
    # same sequence, but the grace window undercuts the drain threshold
    spec = MigrationSpec(enabled=True, drain_threshold_s=2.0,
                         link_latency_s=0.0)
    s = _seq(1, prompt=100, out=200, pref=100, dec=150)  # 1.0s remaining
    [d] = plan_preemption([s], [_tgt()], 0.5, PCFG, spec)
    assert d.action != "drain"
    # zero threshold: nothing ever drains, even trivially-finished seqs
    spec0 = dataclasses.replace(spec, drain_threshold_s=0.0)
    [d0] = plan_preemption([s], [_tgt()], 120.0, PCFG, spec0)
    assert d0.action == "migrate"


def test_kill_when_no_target_has_kv_headroom():
    spec = MigrationSpec(enabled=True, drain_threshold_s=0.0)
    s = _seq(1, prompt=100, out=200, pref=100, dec=0)   # needs 300 tokens
    [d] = plan_preemption([s], [_tgt(headroom=299)], 120.0, PCFG, spec)
    assert d.action == "kill"
    [d2] = plan_preemption([s], [_tgt(headroom=300)], 120.0, PCFG, spec)
    assert d2.action == "migrate"


def test_kill_when_bandwidth_starved_cross_cloud():
    """1000 resident tokens x 1 MB = 1 GB.  Over the 1 Gbps inter-cloud
    tier that is 8s of wire time — too slow for a 5s grace window; over
    the 25 Gbps intra-zone tier it fits easily."""
    spec = MigrationSpec(enabled=True, drain_threshold_s=0.0,
                         link_latency_s=0.0)
    s = _seq(1, prompt=1000, out=2000, pref=1000, dec=0)
    [slow] = plan_preemption(
        [s], [_tgt(gbps=INTER_CLOUD_GBPS)], 5.0, PCFG, spec
    )
    assert slow.action == "kill"
    [fast] = plan_preemption(
        [s], [_tgt(gbps=INTRA_ZONE_GBPS)], 5.0, PCFG, spec
    )
    assert fast.action == "migrate"
    assert fast.transfer_s == pytest.approx(1e9 / _gbps(INTRA_ZONE_GBPS))


def test_int8_compression_rescues_a_transfer():
    # 1 GB over 1 Gbps = 8s > 5s grace uncompressed; int8 halves the
    # payload to 4s, which fits
    s = _seq(1, prompt=1000, out=2000, pref=1000, dec=0)
    none = MigrationSpec(enabled=True, drain_threshold_s=0.0,
                         compression="none", link_latency_s=0.0)
    [d] = plan_preemption([s], [_tgt(gbps=1.0)], 5.0, PCFG, none)
    assert d.action == "kill"
    int8 = dataclasses.replace(none, compression="int8")
    [d8] = plan_preemption([s], [_tgt(gbps=1.0)], 5.0, PCFG, int8)
    assert d8.action == "migrate"
    assert d8.transfer_s == pytest.approx(
        INT8_KV_FACTOR * 1e9 / _gbps(1.0)
    )


def test_transfers_serialize_on_source_nic():
    """Two 3s transfers against a 5s grace: the first (largest resident)
    ships, the second would finish at 6s > grace and is killed."""
    spec = MigrationSpec(enabled=True, drain_threshold_s=0.0,
                         link_latency_s=0.0)
    big = _seq(1, prompt=3000, out=4000, pref=3000, dec=0)
    small = _seq(2, prompt=2999, out=4000, pref=2999, dec=0)
    # 3 GB / (8 Gbps = 1e9 B/s) = 3s each
    ds = plan_preemption([small, big], [_tgt(gbps=8.0)], 5.0, PCFG, spec)
    by_key = {d.state.key: d for d in ds}
    assert by_key[1].action == "migrate"       # larger resident goes first
    assert by_key[1].resume_offset_s == pytest.approx(3.0)
    assert by_key[2].action == "kill"


def test_target_ranking_prefers_bandwidth_then_headroom():
    spec = MigrationSpec(enabled=True, drain_threshold_s=0.0,
                         link_latency_s=0.0)
    slow_roomy = _tgt(rid=0, headroom=50_000, gbps=5.0)
    fast_tight = _tgt(rid=1, headroom=700, gbps=25.0)
    a = _seq(1, prompt=200, out=300, pref=200, dec=100)   # need 500
    b = _seq(2, prompt=150, out=300, pref=150, dec=100)   # need 450
    ds = plan_preemption([a, b], [slow_roomy, fast_tight], 120.0, PCFG,
                         spec)
    by_key = {d.state.key: d for d in ds}
    # a (larger resident) takes the fast NIC; its reservation leaves only
    # 200 tokens of headroom there, so b falls back to the roomy target
    assert by_key[1].target_rid == 1
    assert by_key[2].target_rid == 0
    assert fast_tight.headroom_tokens == 200
    assert slow_roomy.headroom_tokens == 50_000 - 450


def test_migrate_threshold_tokens_gates_small_caches():
    spec = MigrationSpec(enabled=True, drain_threshold_s=0.0,
                         migrate_threshold_tokens=10_000)
    s = _seq(1, prompt=1000, out=2000, pref=1000, dec=0)
    [d] = plan_preemption([s], [_tgt()], 120.0, PCFG, spec)
    assert d.action == "kill"


def test_queued_sequence_with_no_kv_never_migrates():
    # resident 0 < default migrate_threshold_tokens (1): nothing to ship
    spec = MigrationSpec(enabled=True, drain_threshold_s=0.0)
    s = _seq(1, prompt=1000, out=2000, pref=0, dec=0)
    [d] = plan_preemption([s], [_tgt()], 120.0, PCFG, spec)
    assert d.action == "kill"


# ---------------------------------------------------------------------------
# cost model: transfer + elastic re-shard
# ---------------------------------------------------------------------------


def test_compression_factor():
    assert compression_factor("none") == 1.0
    assert compression_factor("int8") == INT8_KV_FACTOR == 0.5
    with pytest.raises(ValueError, match="compression"):
        compression_factor("fp4")


def test_kv_transfer_math():
    assert kv_transfer_bytes(1000, 163840.0) == pytest.approx(1.6384e8)
    assert kv_transfer_bytes(1000, 163840.0, "int8") == pytest.approx(
        0.5 * 1.6384e8
    )
    # zero payload costs only the link latency
    assert kv_transfer_s(0.0, _gbps(10.0), link_latency_s=0.05) == 0.05
    # a dead link never completes
    assert kv_transfer_s(1e9, 0.0) == math.inf
    assert kv_transfer_s(1e9, _gbps(8.0), link_latency_s=0.05) == (
        pytest.approx(0.05 + 1.0)
    )


def test_plan_reshard_shrinks_data_axis():
    rc = plan_reshard(
        (4, 2), ("data", "model"), 6,
        kv_resident_bytes=8e9, weight_bytes=70e9,
        bandwidth_bytes_per_s=_gbps(25.0), link_latency_s=0.05,
        relower_s=2.0,
    )
    assert rc.new_shape == (2, 2) and rc.dropped_chips == 4
    assert rc.new_chip_count == 4
    # data-parallel shrink replays only KV (weights already replicated)
    assert rc.moved_bytes == pytest.approx(8e9 * 0.5)
    assert rc.transfer_s == pytest.approx(0.05 + 4e9 / _gbps(25.0))
    assert rc.total_s == pytest.approx(rc.transfer_s + 2.0)


def test_plan_reshard_model_axis_moves_weights_too():
    rc = plan_reshard(
        (2, 4), ("data", "model"), 6, shrink_axis="model",
        kv_resident_bytes=8e9, weight_bytes=70e9,
        bandwidth_bytes_per_s=_gbps(25.0),
    )
    assert rc.new_shape == (2, 2)
    assert rc.moved_bytes == pytest.approx((8e9 + 70e9) * 0.5)


def test_plan_reshard_none_when_nothing_fits():
    assert plan_reshard(
        (1, 2), ("data", "model"), 1, bandwidth_bytes_per_s=_gbps(10.0)
    ) is None


def test_plan_reshard_validates_inputs():
    with pytest.raises(ValueError):
        plan_reshard((4, 2), ("data",), 4,
                     bandwidth_bytes_per_s=_gbps(10.0))
    with pytest.raises(ValueError):
        plan_reshard((4, 2), ("data", "model"), 4, shrink_axis="expert",
                     bandwidth_bytes_per_s=_gbps(10.0))


def test_reshard_cost_exports_remesh_plan():
    rc = plan_reshard(
        (4, 2), ("data", "model"), 6,
        bandwidth_bytes_per_s=_gbps(10.0),
    )
    plan = rc.to_remesh_plan()
    assert tuple(plan.old_shape) == (4, 2)
    assert tuple(plan.new_shape) == (2, 2)
    assert tuple(plan.axis_names) == ("data", "model")
    assert plan.dropped_chips == 4
    assert plan.new_chip_count == 4


def test_int8_kv_roundtrip_error_bound_on_real_shapes():
    """Quantize a real model's KV block (layers x 2 x kv-heads x T x
    head-dim from configs/) and bound the round-trip error by half a
    quantization step; the payload shrink matches INT8_KV_FACTOR."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.distributed.compression import dequantize_int8, quantize_int8

    shape = (CFG.num_layers, 2, CFG.num_kv_heads, 64,
             CFG.resolved_head_dim)
    kv = 3.0 * jax.random.normal(jax.random.PRNGKey(0), shape,
                                 dtype=jnp.float32)
    q, scale = quantize_int8(kv)
    assert q.dtype == jnp.int8
    rt = dequantize_int8(q, scale)
    err = float(jnp.max(jnp.abs(rt - kv.astype(jnp.float32))))
    assert err <= float(scale) / 2 + 1e-6
    # int8 payload vs the fp16 KV cache the cost model assumes
    fp16_bytes = kv.size * 2
    assert q.size / fp16_bytes == pytest.approx(INT8_KV_FACTOR)


# ---------------------------------------------------------------------------
# ContinuousBatch: migrated-KV injection
# ---------------------------------------------------------------------------


def _small_batch(**over):
    kw = dict(
        weight_read_s=0.02, kv_read_s_per_token=0.0,
        prefill_s_per_token=0.001, overhead_s=0.0, iter_overhead_s=0.0,
        kv_budget_tokens=10_000, prefill_chunk_tokens=512,
        max_batch=1 << 30, kv_bytes_per_token=1e6,
    )
    kw.update(over)
    return ContinuousBatch(TokenEngineConfig(**kw))


def test_enqueue_migrated_resumes_without_reprefill():
    mig = _small_batch()
    assert mig.enqueue_migrated(7, 500, 100, 0.0, 10.0, 500, 40, 2.0)
    assert mig.committed_tokens == 600
    [done] = mig.advance(1e9)
    assert done.key == 7
    assert done.first_token_s == 2.0          # preserved across the move
    # only the 60 remaining decode steps run — no prefill
    assert done.finish_s == pytest.approx(10.0 + 60 * 0.02)
    # a cold retry of the same request pays prefill + full decode
    fresh = _small_batch()
    fresh.enqueue(7, 500, 100, 0.0, 10.0)
    [redo] = fresh.advance(1e9)
    assert redo.finish_s > done.finish_s + 0.5 - 1e-9


def test_enqueue_migrated_respects_kv_budget():
    b = _small_batch(kv_budget_tokens=500)
    assert not b.enqueue_migrated(1, 400, 200, 0.0, 0.0, 400, 10, 1.0)
    assert b.committed_tokens == 0 and len(b.queue) == 0


def test_kill_counts_pending_migrated_kv_as_lost():
    b = _small_batch()
    b.enqueue_migrated(7, 500, 100, 0.0, 10.0, 500, 40, 2.0)
    kr = b.kill()                              # dies before admission
    assert 7 in kr.keys
    assert kr.lost_prefill_tokens == 500
    assert kr.lost_decode_tokens == 40


def test_remove_frees_reservation_and_rows():
    b = _small_batch()
    b.enqueue(1, 100, 50, 0.0, 0.0)
    b.enqueue(2, 100, 50, 0.0, 0.0)
    b.advance(0.2)                             # admit both, work underway
    assert b.reserved_tokens == 300
    b.remove([1])
    assert b.reserved_tokens == 150
    assert [row[0] for row in b.iter_states()] == [2]
    [done] = b.advance(1e9)
    assert done.key == 2


# ---------------------------------------------------------------------------
# runtime executor
# ---------------------------------------------------------------------------


def _inst(zone):
    class _I:
        pass

    i = _I()
    i.zone = zone
    z = CAT.zone(zone)
    i.region = z.region
    i.cloud = z.cloud
    return i


def test_runtime_executes_plan_and_accounts_savings():
    spec = MigrationSpec(enabled=True, drain_threshold_s=2.0,
                         link_latency_s=0.0)
    rt = MigrationRuntime(spec, PCFG)
    src = ContinuousBatch(PCFG)
    # seed exact progress: seq 1 is 0.1s from done (drains), seq 2 has
    # ~38s of decode left (migrates)
    src.enqueue_migrated(1, 100, 200, 0.0, 0.0, 100, 195, 1.0)
    src.enqueue_migrated(2, 1000, 2000, 0.0, 0.0, 1000, 100, 1.0)
    src.advance(1e-9)                          # admit both
    rows = {r[0]: r for r in src.iter_states()}
    assert rows[1][3] == 100 and rows[2][3] == 1000
    assert (200 - rows[1][4]) * PCFG.weight_read_s <= 2.0
    assert (2000 - rows[2][4]) * PCFG.weight_read_s > 2.0
    tgt = ContinuousBatch(PCFG)
    out = rt.execute_preemption(
        src, _inst("us-west-2a"),
        [(42, tgt, _inst("us-west-2b"))], now=100.0, grace_s=120.0,
    )
    assert [s.key for s in out.drained] == [1]
    assert [m.state.key for m in out.migrated] == [2]
    assert out.migrated[0].target_rid == 42
    resident2 = rows[2][3] + rows[2][4]
    assert out.migrated_kv_tokens == resident2
    assert out.saved_prefill_tokens == 100 + 1000
    assert out.transfer_s_total == pytest.approx(
        resident2 * 1e6 / _gbps(link_bandwidth_gbps(
            "aws", "us-west-2", "us-west-2a",
            "aws", "us-west-2", "us-west-2b",
        ))
    )
    assert out.recompute_saved_s == pytest.approx(
        out.saved_prefill_tokens * PCFG.prefill_s_per_token
        + out.saved_decode_tokens * PCFG.weight_read_s
    )
    # the migrated sequence is queued on the target with KV intact
    assert tgt.committed_tokens == 3000
    assert out.kill_report.n_batch == 0        # nothing was abandoned
    # the source batch is dead either way
    assert len(src.iter_states()) == 0


def test_runtime_requires_enabled_spec():
    with pytest.raises(ValueError, match="enabled"):
        MigrationRuntime(MigrationSpec(), PCFG)


def test_runtime_bandwidth_override_beats_locality():
    rt = MigrationRuntime(
        MigrationSpec(enabled=True, bandwidth_gbps=2.5), PCFG
    )
    bw = rt.bandwidth_bytes_per_s(_inst("us-west-2a"), _inst("us-east-2a"))
    assert bw == pytest.approx(_gbps(2.5))


# ---------------------------------------------------------------------------
# spec / loader / sweep plumbing
# ---------------------------------------------------------------------------


def _spec_dict(**over):
    d = {
        "name": "mig", "model": "llama3.2-1b", "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "replica_policy": {"name": "spothedge"},
        "autoscaler": {"kind": "constant", "target": 3},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 17},
        "sim": {"duration_hours": 1.0, "timeout_s": 60.0,
                "drain_s": 300.0},
    }
    d.update(over)
    return d


def test_migration_spec_validation():
    with pytest.raises(ValueError, match="compression"):
        MigrationSpec(compression="fp4")
    with pytest.raises(ValueError):
        MigrationSpec(bandwidth_gbps=0.0)
    with pytest.raises(ValueError):
        MigrationSpec(drain_threshold_s=-1.0)
    with pytest.raises(ValueError):
        MigrationSpec(migrate_threshold_tokens=-1)
    s = MigrationSpec(enabled=True, compression="int8",
                      bandwidth_gbps=5.0)
    assert MigrationSpec(**s.to_dict()) == s


def test_migration_section_round_trip():
    d = _spec_dict(
        serving={"replica_model": "token"},
        migration={"enabled": True, "compression": "int8",
                   "drain_threshold_s": 2.0},
    )
    spec = spec_from_dict(d)
    assert spec.migration.enabled
    assert spec.migration.compression == "int8"
    assert spec.migration.drain_threshold_s == 2.0
    assert spec_from_dict(spec.to_dict()) == spec


def test_migration_requires_token_engine():
    d = _spec_dict(migration={"enabled": True})
    with pytest.raises(SpecError, match="token"):
        spec_from_dict(d)
    # a token entry in sweep.replica_models satisfies the requirement
    d = _spec_dict(
        migration={"enabled": True},
        sweep={"replica_models": ["request", "token"]},
    )
    assert spec_from_dict(d).migration.enabled


def test_loader_rejects_bad_migration_knobs_as_spec_errors():
    d = _spec_dict(serving={"replica_model": "token"},
                   migration={"enabled": True, "compression": "fp4"})
    with pytest.raises(SpecError, match="migration"):
        spec_from_dict(d)
    d = _spec_dict(serving={"replica_model": "token"},
                   migration={"enabled": True, "unknown_knob": 1})
    with pytest.raises(SpecError, match="unknown"):
        spec_from_dict(d)
    d = _spec_dict(serving={"replica_model": "token"},
                   sweep={"migration": ["yes"]})
    with pytest.raises(SpecError, match="sweep.migration"):
        spec_from_dict(d)


def test_sweep_migration_axis_expands_cells():
    from repro.experiments import ScenarioSuite

    d = _spec_dict(
        serving={"replica_model": "token"},
        migration={"enabled": False, "drain_threshold_s": 2.0},
        sweep={"migration": [False, True]},
    )
    suite = ScenarioSuite.from_spec(d)
    assert len(suite) == 2
    labels = sorted(sc.labels["migration"] for sc in suite.scenarios)
    assert labels == ["off", "on"]
    # the toggle inherits the base section's knobs
    for sc in suite.scenarios:
        assert sc.spec.migration.drain_threshold_s == 2.0
    # same tape across the axis (fair comparison)
    assert len({sc.tape_key for sc in suite.scenarios}) == 1


def test_sweep_migration_axis_collapses_for_request_cells():
    from repro.experiments import ScenarioSuite

    d = _spec_dict(sweep={
        "replica_models": ["request", "token"],
        "migration": [False, True],
    })
    suite = ScenarioSuite.from_spec(d)
    # request cells have no KV: the migration axis applies to token
    # cells only, and the request model keeps exactly one unlabeled cell
    per_model = {"request": 0, "token": 0}
    for sc in suite.scenarios:
        per_model[sc.labels["replica_model"]] += 1
    assert per_model == {"request": 1, "token": 2}
    for sc in suite.scenarios:
        if sc.labels["replica_model"] == "request":
            assert "migration" not in sc.labels
            assert sc.spec.migration is None


# ---------------------------------------------------------------------------
# satellite: preemption_warning_s trace override
# ---------------------------------------------------------------------------


def test_trace_warning_override_roundtrip(tmp_path):
    tr = dataclasses.replace(_mini_trace(steps=10),
                             preemption_warning_s=77.0)
    assert tr.preemption_warning_s == 77.0
    p = str(tmp_path / "tr.npz")
    tr.save(p)
    assert load_trace(p).preemption_warning_s == 77.0
    # None round-trips too (nan-encoded in the npz)
    p2 = str(tmp_path / "tr2.npz")
    _mini_trace(steps=10).save(p2)
    assert load_trace(p2).preemption_warning_s is None
    # zone slicing preserves the override
    assert tr.slice_zones(["us-west-2a"]).preemption_warning_s == 77.0
    with pytest.raises(ValueError):
        dataclasses.replace(tr, preemption_warning_s=-1.0)


def test_trace_warning_override_from_json(tmp_path):
    tr = _mini_trace(steps=6)
    d = {
        "zones": list(tr.zones),
        "dt": tr.dt,
        "cap": tr.cap.tolist(),
        "preemption_warning_s": 45,
    }
    p = tmp_path / "tr.json"
    p.write_text(json.dumps(d))
    assert load_trace(str(p)).preemption_warning_s == 45.0


def test_simulator_warning_lead_honors_override():
    tr = _mini_trace(steps=30)
    cfg = SimConfig(itype="g5.48xlarge")

    def lead(trace):
        sim = ClusterSimulator(trace, make_policy("spothedge"),
                               config=cfg)
        sim._deliver_warnings()
        return sim._warn_info["us-west-2a"][0]

    assert lead(tr) == CAT.cloud("aws").preemption_warning_s == 120.0
    assert lead(dataclasses.replace(tr, preemption_warning_s=300.0)) \
        == 300.0
    # the lead can never undercut the trace resolution
    assert lead(dataclasses.replace(tr, preemption_warning_s=10.0)) \
        == tr.dt == 60.0


def test_sim_spec_warning_override_reaches_trace():
    d = _spec_dict()
    d["sim"]["preemption_warning_s"] = 45.0
    spec = spec_from_dict(d)
    assert spec_from_dict(spec.to_dict()) == spec
    svc = build_service(spec)
    assert svc.trace.preemption_warning_s == 45.0
    # the named trace's cached copy must stay pristine
    assert load_trace("aws-1").preemption_warning_s is None
    d["sim"]["preemption_warning_s"] = -5.0
    with pytest.raises((SpecError, ValueError)):
        spec_from_dict(d)


# ---------------------------------------------------------------------------
# engine integration: accounting symmetry + migration differential
# ---------------------------------------------------------------------------


CFG35 = get_config("command-r-35b")   # ~10s service times: preempted
                                      # replicas actually hold KV


def _run_engine(cls, migration, *, steps=180, seed=3, rate=0.8,
                target=3):
    tr = _mini_trace(steps=steps, seed=seed)
    reqs = make_workload("poisson", rate_per_s=rate, seed=seed).generate(
        2 * 3600.0
    )
    sim = cls(
        tr, make_policy("spothedge"), reqs, CFG35, itype="g5.48xlarge",
        autoscaler=ConstantTarget(target), timeout_s=60.0,
        replica_model="token",
        token_scheduler=TokenSchedulerConfig(),
        migration=migration,
    )
    return sim.run(2 * 3600.0 + 600.0)


def test_engines_reject_migration_without_token_mode():
    tr = _mini_trace(steps=10)
    for cls in (VectorizedServingEngine, JaxServingEngine):
        with pytest.raises(ValueError, match="token"):
            cls(tr, make_policy("spothedge"), [], CFG,
                itype="g5.48xlarge", autoscaler=ConstantTarget(2),
                migration=MigrationSpec(enabled=True))


def test_retried_and_lost_kv_accounting_symmetry():
    """Satellite 2: retried-request and lost-KV-token counts match the
    recorded answers with migration off, and lost KV is the sum of the
    lost prefill and decode tokens."""
    vector = _run_engine(VectorizedServingEngine, None)
    assert vector.n_preemptions > 0
    assert vector.lost_kv_tokens == (
        vector.token.lost_prefill_tokens + vector.token.lost_decode_tokens
    )
    tok = vector.token
    assert tok.n_drained_seqs == tok.n_migrated_seqs == 0
    assert tok.migrated_kv_tokens == tok.saved_prefill_tokens == 0
    assert_matches(vector, "retried_and_lost_kv_accounting_symmetry",
                   atol=1e-9)


def test_migration_differential_legacy_vs_vector():
    """Acceptance: with migration ON, the engine makes the recorded
    drain/migrate/kill decisions and gives the recorded accounting."""
    mig = MigrationSpec(enabled=True, compression="int8",
                        drain_threshold_s=0.0)
    vector = _run_engine(VectorizedServingEngine, mig)
    vtok = vector.token
    # the scenario actually exercises the migrate path
    assert vector.n_preemptions > 0
    assert vtok.n_drained_seqs + vtok.n_migrated_seqs > 0
    assert_matches(vector, "migration_differential", atol=1e-9)


def test_migration_saves_reprefill_work():
    """With migration on, strictly less KV is re-prefetched than the
    kill-everything baseline loses (same tape, same trace)."""
    mig = MigrationSpec(enabled=True, compression="int8",
                        drain_threshold_s=2.0)
    off = _run_engine(VectorizedServingEngine, None)
    on = _run_engine(VectorizedServingEngine, mig)
    assert on.token.saved_prefill_tokens + on.token.n_drained_seqs > 0
    assert on.lost_kv_tokens < off.lost_kv_tokens
    assert on.n_requests == off.n_requests


# ---------------------------------------------------------------------------
# golden property: migration off == no migration section, byte-identical
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - environment-dependent
    _HAS_HYPOTHESIS = False

_BASELINE = {}


def _golden_run(migration):
    tr = _mini_trace(steps=60, seed=5)
    reqs = make_workload("poisson", rate_per_s=0.3, seed=5).generate(
        1800.0
    )
    sim = VectorizedServingEngine(
        tr, make_policy("spothedge"), reqs, CFG, itype="g5.48xlarge",
        autoscaler=ConstantTarget(2), timeout_s=60.0,
        replica_model="token", migration=migration,
    )
    return sim.run(3600.0)


def test_migration_disabled_section_is_inert():
    """Deterministic twin of the hypothesis property below, for
    environments without hypothesis: a disabled migration section —
    whatever its knobs — must not perturb a single byte."""
    base = _golden_run(None)
    for spec in (
        MigrationSpec(enabled=False),
        MigrationSpec(enabled=False, compression="int8",
                      drain_threshold_s=0.0, bandwidth_gbps=0.5),
    ):
        res = _golden_run(spec)
        assert res.n_completed == base.n_completed
        assert res.n_failed == base.n_failed
        assert res.total_cost == base.total_cost
        assert np.array_equal(res.latencies_s, base.latencies_s)
        assert np.array_equal(res.token.ttft_s, base.token.ttft_s)
        assert res.token.n_drained_seqs == res.token.n_migrated_seqs == 0
        assert res.n_retried_requests == base.n_retried_requests
        assert res.lost_kv_tokens == base.lost_kv_tokens


if _HAS_HYPOTHESIS:
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(
        drain=st.floats(0.0, 300.0, allow_nan=False),
        compression=st.sampled_from(("none", "int8")),
        bandwidth=st.one_of(st.none(), st.floats(0.1, 100.0,
                                                 allow_nan=False)),
    )
    def test_migration_disabled_is_byte_identical(drain, compression,
                                                  bandwidth):
        if "res" not in _BASELINE:
            _BASELINE["res"] = _golden_run(None)
        base = _BASELINE["res"]
        res = _golden_run(MigrationSpec(
            enabled=False, drain_threshold_s=drain,
            compression=compression, bandwidth_gbps=bandwidth,
        ))
        assert res.n_completed == base.n_completed
        assert res.n_failed == base.n_failed
        assert res.total_cost == base.total_cost
        assert np.array_equal(res.latencies_s, base.latencies_s)
        assert np.array_equal(res.token.ttft_s, base.token.ttft_s)
        assert res.token.n_drained_seqs == 0
        assert res.token.n_migrated_seqs == 0
        assert res.n_retried_requests == base.n_retried_requests
        assert res.lost_kv_tokens == base.lost_kv_tokens
