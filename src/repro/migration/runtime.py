"""MigrationRuntime: the engine-facing executor of the preemption plan.

One instance is shared by a serving engine for the whole run.  On a
warned preemption the engine hands it the dying replica's
:class:`~repro.serving.token.batch.ContinuousBatch`, its
:class:`~repro.cluster.instance.Instance`, and the surviving candidate
replicas; the runtime snapshots the batch, runs the pure planner,
injects migrated sequences into the target batches (they join after the
transfer delay, KV intact, counting against the target's KV budget) and
kills the residue.  Both engines call this one code path with
identically-constructed inputs, so their migration decisions are
identical by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.cluster.catalog import link_bandwidth_gbps
from repro.migration.config import MigrationSpec
from repro.migration.planner import SeqState, TargetInfo, plan_preemption
from repro.obs.events import MigrationPlanEvent, ReplicaLifecycleEvent
from repro.obs.recorder import ObsRecorder

__all__ = ["MigratedSeq", "PreemptionOutcome", "MigrationRuntime"]


@dataclasses.dataclass(frozen=True)
class MigratedSeq:
    """One sequence shipped to a surviving replica."""

    state: SeqState
    target_rid: int                 # instance id of the receiving replica
    transfer_s: float               # this sequence's own wire time
    resume_s: float                 # absolute time it joins the target


@dataclasses.dataclass(frozen=True)
class PreemptionOutcome:
    """Everything a serving engine needs to account one preemption."""

    drained: Tuple[SeqState, ...]   # finish in place at the kill instant
    migrated: Tuple[MigratedSeq, ...]
    kill_report: Any                # KillReport for the residue
    migrated_kv_tokens: int
    saved_prefill_tokens: int       # prefill work NOT re-done elsewhere
    saved_decode_tokens: int
    transfer_s_total: float
    recompute_saved_s: float        # engine-seconds of recompute avoided

    @property
    def n_drained(self) -> int:
        return len(self.drained)

    @property
    def n_migrated(self) -> int:
        return len(self.migrated)


class MigrationRuntime:
    """Plans and executes grace-period KV migration for one engine run."""

    def __init__(
        self,
        spec: MigrationSpec,
        engine_cfg,
        obs: Optional[ObsRecorder] = None,
    ) -> None:
        if not spec.enabled:
            raise ValueError(
                "MigrationRuntime requires migration.enabled: true"
            )
        self.spec = spec
        self.engine_cfg = engine_cfg    # TokenEngineConfig (duck-typed)
        # events derive solely from inputs + the pure planner's outcome,
        # so the stream through here is deterministic
        self.obs = obs if obs is not None else ObsRecorder(detail="off")

    # ------------------------------------------------------------------
    def bandwidth_bytes_per_s(self, src_inst, dst_inst) -> float:
        """Link bandwidth from the dying to a surviving instance: the
        spec's flat override when set, else the catalog's locality tiers."""
        if self.spec.bandwidth_gbps is not None:
            gbps = self.spec.bandwidth_gbps
        else:
            gbps = link_bandwidth_gbps(
                src_inst.cloud, src_inst.region, src_inst.zone,
                dst_inst.cloud, dst_inst.region, dst_inst.zone,
            )
        return gbps * 1e9 / 8.0

    # ------------------------------------------------------------------
    def execute_preemption(
        self,
        src_batch,                  # ContinuousBatch of the dying replica
        src_inst,                   # its Instance
        candidates: Sequence[Tuple[int, Any, Any]],  # (rid, batch, inst)
        now: float,
        grace_s: float,
    ) -> PreemptionOutcome:
        states = [SeqState(*row) for row in src_batch.iter_states()]
        targets: List[TargetInfo] = []
        bmap: Dict[int, Any] = {}
        for rid, tb, inst in candidates:
            bmap[rid] = tb
            targets.append(TargetInfo(
                rid=rid,
                headroom_tokens=(
                    tb.cfg.kv_budget_tokens - tb.committed_tokens
                ),
                bandwidth_bytes_per_s=self.bandwidth_bytes_per_s(
                    src_inst, inst
                ),
            ))
        decisions = plan_preemption(
            states, targets, grace_s, self.engine_cfg, self.spec
        )
        drained: List[SeqState] = []
        migrated: List[MigratedSeq] = []
        removed: List[int] = []
        # span taps ride the source batch's sampled-key map; consult it
        # before remove()/kill() evict the entries below
        tord = getattr(src_batch, "_tord", None)
        for d in decisions:
            s = d.state
            if d.action == "drain":
                drained.append(s)
                removed.append(s.key)
            elif d.action == "migrate":
                resume = now + d.resume_offset_s
                ok = bmap[d.target_rid].enqueue_migrated(
                    s.key, s.prompt_tokens, s.output_tokens,
                    s.arrival_s, resume, s.prefilled, s.decoded,
                    s.first_s,
                )
                if ok:
                    migrated.append(MigratedSeq(
                        state=s, target_rid=d.target_rid,
                        transfer_s=d.transfer_s, resume_s=resume,
                    ))
                    removed.append(s.key)
                    if tord:
                        o = tord.get(s.key)
                        if o is not None:
                            to_ord = self.obs.replica_ordinal(
                                d.target_rid
                            )
                            src_batch.tap.migrate(
                                o, now, to_replica=to_ord,
                                transfer_s=d.transfer_s, plan_t=now,
                            )
                            src_batch.tap.migrate_arrive(
                                o, resume, replica=to_ord
                            )
                            bmap[d.target_rid].track(s.key, o)
                # else: planner headroom said yes but the target refused
                # (over-large request) — falls through to the kill path
        if removed:
            src_batch.remove(removed)
        kr = src_batch.kill()
        saved_p = sum(s.prefilled for s in drained) + sum(
            m.state.prefilled for m in migrated
        )
        saved_d = sum(s.decoded for s in drained) + sum(
            m.state.decoded for m in migrated
        )
        cfg = self.engine_cfg
        if self.obs.enabled:
            # lifecycle phases precede the cluster's "dead" event: the
            # engine runs inside the preempt listener, and the cluster
            # emits death only after all listeners return
            src_ord = self.obs.replica_ordinal(src_inst.id)
            if drained:
                self.obs.emit(ReplicaLifecycleEvent(
                    t=now, phase="draining",
                    instance_id=src_ord, zone=src_inst.zone,
                ))
            if migrated:
                self.obs.emit(ReplicaLifecycleEvent(
                    t=now, phase="migrating",
                    instance_id=src_ord, zone=src_inst.zone,
                ))
            self.obs.emit(MigrationPlanEvent(
                t=now,
                instance_id=src_ord,
                n_drained=len(drained),
                n_migrated=len(migrated),
                n_killed=kr.n_batch + kr.n_queued,
                migrated_kv_tokens=sum(
                    m.state.resident_tokens for m in migrated
                ),
                transfer_s=sum(m.transfer_s for m in migrated),
                grace_s=grace_s,
            ))
        return PreemptionOutcome(
            drained=tuple(drained),
            migrated=tuple(migrated),
            kill_report=kr,
            migrated_kv_tokens=sum(
                m.state.resident_tokens for m in migrated
            ),
            saved_prefill_tokens=saved_p,
            saved_decode_tokens=saved_d,
            transfer_s_total=sum(m.transfer_s for m in migrated),
            recompute_saved_s=(
                saved_p * cfg.prefill_s_per_token
                + saved_d * cfg.weight_read_s
            ),
        )
