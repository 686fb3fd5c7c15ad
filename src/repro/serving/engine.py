"""Vectorized serving engine — the reference serving engine.

``VectorizedServingEngine`` runs the §5.1 serving methodology: the
cluster simulator (policy × spot trace × instances) composed with the
request path (workload → LB → replica queues → latency model).  It
produces the paper's headline metrics: P50/P90/P99 end-to-end latency,
failure rate (timeouts from preemption + queueing), cost, and
ready-replica series (Fig. 9/10/13/15).

Mechanics:

* requests arrive continuously; the LB routes to ready replicas only,
  least-loaded over ``(load, rtt, id)`` or round-robin;
* a preemption kills a replica; its in-flight and queued requests are
  retried by the client — the wasted time counts into that request's
  e2e latency;
* a request that cannot complete within ``timeout_s`` of its arrival
  (client RTT included) is a failure (the paper's definition);
* replica service times come from the latency model; queueing is M/G/c
  per replica on a ``sub_step_s`` grid, with a mild interference factor
  for concurrent decodes.

State lives in NumPy arrays and plain lists, not one object per request
or replica:

* the request tape is compiled once into ``float64`` arrays (arrival
  times, roofline service times, client-region codes) — no
  ``LatencyModel`` call or ``Request`` attribute chase ever happens inside
  the sub-tick loop;
* arrivals are delivered in batches with ``np.searchsorted`` over the
  arrival array;
* timeout expiry over deep pending/queue backlogs is a vectorized mask
  over the arrival array instead of a per-entry Python scan;
* per-replica RTTs are precomputed per client-region code at replica
  creation, so the load balancer's ``(load, rtt, id)`` key needs no
  string parsing through ``region_rtt_ms``;
* completions are tracked in a global min-heap of finish times: a sub-tick
  only visits replicas that have a finish due or received new work, and
  sub-ticks where provably nothing can happen are skipped outright;
* dead replicas cost nothing: they leave the live list at the next
  control tick.

The engine's answers are pinned: ``tests/test_differential.py`` holds it
to the answers recorded from the per-request object engine it replaced
(``tests/data/engine_record.json``), ``tests/test_golden.py`` pins the
metrics, and the JAX engine (``repro.serving.jaxengine``) is held to it.

``replica_model="token"`` switches the request path to the
continuous-batching model (``repro.serving.token``): each replica slot
carries a :class:`ContinuousBatch` whose per-sequence state lives in
NumPy arrays, dispatch enqueues tape indices into batches instead of
pushing precomputed finish times, and a per-sub-tick batched step loop
advances every busy batch (closed-form decode blocks, so cost scales
with joins/leaves, not decode iterations).  Token mode is pinned to the
same record (``tests/test_token_engine.py``, ``tests/test_migration.py``).
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.cluster.catalog import Catalog, default_catalog, region_rtt_ms
from repro.cluster.instance import Instance, InstanceState
from repro.migration.config import MigrationSpec
from repro.migration.runtime import MigrationRuntime
from repro.cluster.simulator import ClusterSimulator, SimConfig
from repro.cluster.traces import SpotTrace
from repro.core.autoscaler import Autoscaler, ConstantTarget
from repro.core.policy import Policy
from repro.models.config import ModelConfig
from repro.obs.recorder import ObsRecorder
from repro.obs.registry import use_registry
from repro.serving.latency import LatencyModel
from repro.serving.result import REPLICA_MODELS, ServingResult, WindowSampler
from repro.serving.token.batch import ContinuousBatch
from repro.serving.token.config import (
    TokenEngineConfig,
    TokenSchedulerConfig,
)
from repro.serving.token.metrics import TokenRecord, TokenStats
from repro.workloads.arrivals import Request

__all__ = ["LB_KINDS", "VectorizedServingEngine"]

#: load-balancer name (``service.load_balancer``) -> dispatch rule:
#: least-loaded over ``(load, rtt, id)``, or a round-robin cursor
LB_KINDS = {"least_loaded": "ll", "round_robin": "rr"}

_INF = float("inf")
# below this size a plain Python scan beats numpy call overhead
_VEC_MIN = 24


class _Rep:
    """Array-era replica record: plain slots, no FSM object, no probes."""

    __slots__ = ("inst", "slot", "rid", "dead", "rtt",
                 "running", "queue", "qage", "qmin", "batch", "ord")

    def __init__(self, inst: Instance, slot: int,
                 rtt: List[float]) -> None:
        self.inst = inst
        self.slot = slot
        self.rid = inst.id
        self.ord = -1                        # dense obs ordinal (spans)
        self.dead = False
        self.rtt = rtt                       # client-region code -> seconds
        self.running: List[Tuple[float, int]] = []   # (finish_s, req index)
        self.queue: List[int] = []                   # req indices, FIFO
        # parallel *effective* ages: arrival − client RTT, so the shared
        # `t - age > timeout` expiry predicate is RTT-inclusive
        # (t - (arr - rtt) > to  ⇔  t - arr + rtt > to), matching the
        # deadline applied to completed responses
        self.qage: List[float] = []
        self.qmin = _INF                     # lower bound on queued eff. ages
        self.batch: Optional[ContinuousBatch] = None   # token mode only

    @property
    def load(self) -> int:
        if self.batch is not None:
            return self.batch.load
        return len(self.running) + len(self.queue)


class VectorizedServingEngine:
    """The reference serving engine: one run of one scenario."""

    def __init__(
        self,
        trace: SpotTrace,
        policy: Policy,
        requests: Sequence[Request],
        cfg: ModelConfig,
        *,
        itype: str = "p3.2xlarge",
        catalog: Optional[Catalog] = None,
        autoscaler: Optional[Autoscaler] = None,
        lb: str = "least_loaded",
        sim_config: Optional[SimConfig] = None,
        timeout_s: float = 100.0,
        sub_step_s: float = 1.0,
        workload_name: str = "workload",
        concurrency: Optional[int] = None,
        concurrency_cap: int = 16,
        latency_model: Optional[LatencyModel] = None,
        replica_model: str = "request",
        token_scheduler: Optional[TokenSchedulerConfig] = None,
        migration: Optional[MigrationSpec] = None,
        obs: Optional[ObsRecorder] = None,
    ) -> None:
        # shared event recorder (repro.obs): the cluster, migration
        # runtime and window sampler all emit into this one ordered sink
        self.obs = obs if obs is not None else ObsRecorder()
        self.catalog = catalog or default_catalog()
        self.cfg = cfg
        self.itype = self.catalog.instance_type(itype)
        # an injected model (e.g. ProfiledLatencyModel from the spec's
        # latency: section) replaces the default analytic roofline
        self.latency_model = (
            latency_model
            if latency_model is not None
            else LatencyModel.for_model(cfg, self.itype)
        )
        self.timeout_s = timeout_s
        self.sub_step_s = sub_step_s
        self.workload_name = workload_name
        self.concurrency = concurrency or min(
            self.latency_model.max_concurrency(), concurrency_cap
        )
        if replica_model not in REPLICA_MODELS:
            raise ValueError(
                f"replica_model must be one of {list(REPLICA_MODELS)}, "
                f"got {replica_model!r}"
            )
        self.replica_model = replica_model
        self._token_knobs = token_scheduler or TokenSchedulerConfig()
        self._token_cfg: Optional[TokenEngineConfig] = (
            TokenEngineConfig.from_latency(
                self.latency_model, self._token_knobs
            )
            if replica_model == "token" else None
        )
        # the SLO-burn monitor inside the sampler needs the token-mode
        # latency targets, so construction waits for the knobs above
        self._win = WindowSampler(
            self.obs,
            slo_ttft_s=(
                self._token_knobs.slo_ttft_s
                if self._token_cfg is not None else None
            ),
            slo_tpot_s=(
                self._token_knobs.slo_tpot_s
                if self._token_cfg is not None else None
            ),
        )
        self._token_records: List[TokenRecord] = []
        self._busy: Set[int] = set()         # slots with live batch work
        self._n_kv_preempted = 0
        self._n_killed_queued = 0
        self._lost_prefill_tokens = 0
        self._lost_decode_tokens = 0
        self._n_retried = 0
        if migration is not None and migration.enabled \
                and self._token_cfg is None:
            raise ValueError(
                "migration.enabled requires replica_model='token'"
            )
        self._mig_rt: Optional[MigrationRuntime] = (
            MigrationRuntime(migration, self._token_cfg, obs=self.obs)
            if migration is not None and migration.enabled
            and self._token_cfg is not None else None
        )
        self._n_drained = 0
        self._n_migrated = 0
        self._migrated_kv_tokens = 0
        self._saved_prefill_tokens = 0
        self._saved_decode_tokens = 0
        self._migration_transfer_s = 0.0
        self._recompute_saved_s = 0.0

        if lb not in LB_KINDS:
            raise ValueError(
                f"lb must be one of {list(LB_KINDS)}, got {lb!r}"
            )
        self._lb_kind = LB_KINDS[lb]
        self._rr_cursor = 0

        # ---- compile the request tape into arrays ---------------------
        reqs = sorted(requests, key=lambda r: r.arrival_s)
        self.requests = reqs
        # request-span collector (None when off / unsampled).  The tape
        # is the stable arrival-sort, so tape index == span ordinal and
        # the hot loops test want_l[i] directly — no id lookup.
        self._spans = self.obs.span_collector(reqs)
        n = len(reqs)
        self._n = n
        self._arr = np.fromiter(
            (r.arrival_s for r in reqs), dtype=np.float64, count=n
        )
        p_tok = np.fromiter(
            (r.prompt_tokens for r in reqs), dtype=np.float64, count=n
        )
        o_tok = np.fromiter(
            (r.output_tokens for r in reqs), dtype=np.float64, count=n
        )
        lm = self.latency_model
        # same operation order as LatencyModel.service_s so every value is
        # bit-identical to a per-request service_s() call
        prefill = (2.0 * lm._active_params) * p_tok / lm.flops_per_s
        self._svc = (lm.overhead_s + prefill) + o_tok * lm.decode_s_per_token()
        # Python-list mirrors for scalar access: list indexing and float
        # arithmetic are several times faster than numpy scalar indexing
        # in the per-request loops, and .tolist() round-trips exactly
        self._arr_l: List[float] = self._arr.tolist()
        self._svc_l: List[float] = self._svc.tolist()
        if self._token_cfg is not None:
            # token mode prices work in tokens, not frozen service times
            self._ptok_l: List[int] = [int(v) for v in p_tok]
            self._otok_l: List[int] = [int(v) for v in o_tok]

        # client regions as small int codes; each replica precomputes its
        # RTT per code on creation
        regions: List[str] = []
        region_code: Dict[str, int] = {}
        rcode = np.empty(n, dtype=np.int32)
        for i, r in enumerate(reqs):
            c = region_code.get(r.client_region)
            if c is None:
                c = region_code[r.client_region] = len(regions)
                regions.append(r.client_region)
            rcode[i] = c
        self._rcode = rcode
        self._rcode_l: List[int] = rcode.tolist()
        self._client_regions = regions

        # ---- mutable serving state ------------------------------------
        self._ptr = 0                        # next arrival index
        self._pending: List[int] = []        # request indices, FIFO
        self._pmin = _INF                    # min arrival over pending
        self._qn = 0                         # total queued entries
        self._qmin = _INF                    # min arrival over queued
        self._heap: List[Tuple[float, int]] = []   # (finish_s, slot)
        self._reps: List[_Rep] = []          # insertion order (mirrors dict)
        self._live: List[_Rep] = []          # non-dead, insertion order
        self._live_dirty = False
        self._by_id: Dict[int, _Rep] = {}
        self._obs: List[Tuple[float, int]] = []   # autoscaler batch
        self._touched: Set[int] = set()      # slots enqueued at this point
        self._due: Set[int] = set()          # slots with finishes due
        # per-control-window LB state (ready set is constant in a window)
        self._ready_slots: List[int] = []
        self._ready_reps: List[_Rep] = []
        self._pos: Dict[int, int] = {}       # slot -> index in ready lists
        self._loads: List[int] = []
        self._ids: List[int] = []
        self._cols: Dict[int, List[float]] = {}   # rcode -> rtt column

        self.latencies: List[float] = []
        self.failed = 0
        self.completed = 0

        if sim_config is None:
            cfg_sim = SimConfig(itype=itype, control_interval_s=15.0)
        else:
            cfg_sim = dataclasses.replace(sim_config, itype=itype)
        self.cluster = ClusterSimulator(
            trace,
            policy,
            catalog=self.catalog,
            autoscaler=autoscaler or ConstantTarget(4),
            config=cfg_sim,
            tick_hook=self._tick,
            obs=self.obs,
        )
        self.cluster.add_preempt_listener(self._on_dead)
        self.cluster.add_terminate_listener(self._on_dead)
        self._observe_batch = self.cluster.autoscaler.observe_batch
        self._searchsorted = self._arr.searchsorted

    # ------------------------------------------------------------------
    # replica lifecycle
    # ------------------------------------------------------------------
    def _new_rep(self, inst: Instance) -> _Rep:
        rtt = [
            region_rtt_ms(creg, inst.region) / 1e3
            for creg in self._client_regions
        ]
        rep = _Rep(inst, len(self._reps), rtt)
        if self._spans is not None:
            rep.ord = self.obs.replica_ordinal(inst.id)
        if self._token_cfg is not None:
            rep.batch = ContinuousBatch(self._token_cfg, tap=self._spans)
        self._reps.append(rep)
        self._live.append(rep)
        self._by_id[inst.id] = rep
        return rep

    def _kill(self, rep: _Rep, now: Optional[float] = None) -> None:
        """Preemption/termination: in-flight then queued back to pending."""
        if rep.dead:
            return
        if rep.batch is not None:
            # token mode: the whole batch loses its KV state; every
            # request (in-flight and queued) retries client-side —
            # unless migration is on and the preemption was warned
            rep.dead = True
            self._live_dirty = True
            inst = rep.inst
            if (
                self._mig_rt is not None
                and now is not None
                and inst.state is InstanceState.PREEMPTED
                and inst.warned_at is not None
            ):
                kr = self._kill_with_migration(rep, now)
            else:
                kr = rep.batch.kill()
            arr = self._arr_l
            pending = self._pending
            pmin = self._pmin
            spans = self._spans
            want = spans.want_l if spans is not None else None
            t_kill = now if now is not None else 0.0
            for i in kr.keys:
                pending.append(i)
                if arr[i] < pmin:
                    pmin = arr[i]
                if want is not None and want[i]:
                    spans.preempt(i, t_kill)
            self._pmin = pmin
            self._n_retried += len(kr.keys)
            self._busy.discard(rep.slot)
            self._n_kv_preempted += kr.n_batch
            self._n_killed_queued += kr.n_queued
            self._lost_prefill_tokens += kr.lost_prefill_tokens
            self._lost_decode_tokens += kr.lost_decode_tokens
            return
        rep.dead = True
        self._live_dirty = True
        arr = self._arr_l
        pending = self._pending
        pmin = self._pmin
        spans = self._spans
        want = spans.want_l if spans is not None else None
        t_kill = now if now is not None else 0.0
        for _, i in rep.running:
            pending.append(i)
            if arr[i] < pmin:
                pmin = arr[i]
            if want is not None and want[i]:
                spans.preempt(i, t_kill)
        for i in rep.queue:
            pending.append(i)
            if arr[i] < pmin:
                pmin = arr[i]
            if want is not None and want[i]:
                spans.preempt(i, t_kill)
        self._pmin = pmin
        self._n_retried += len(rep.running) + len(rep.queue)
        self._qn -= len(rep.queue)
        rep.running = []
        rep.queue = []
        rep.qage = []
        rep.qmin = _INF

    def _kill_with_migration(self, rep: _Rep, now: float):
        """Warned preemption with migration on: drain/migrate/kill the
        dying batch.  Returns the residual KillReport."""
        inst = rep.inst
        grace = now - inst.warned_at
        cands = sorted(
            (
                r for r in self._live
                if r is not rep and not r.dead
                and r.batch is not None and r.inst.is_ready()
            ),
            key=lambda r: r.rid,
        )
        outcome = self._mig_rt.execute_preemption(
            rep.batch, inst,
            [(r.rid, r.batch, r.inst) for r in cands],
            now, grace,
        )
        cfg = self._token_cfg
        finish = now + cfg.overhead_s
        rcode = self._rcode_l
        arr = self._arr_l
        records = self._token_records
        spans = self._spans
        want = spans.want_l if spans is not None else None
        for s in outcome.drained:
            # finished decoding inside the grace window: completes at
            # the kill instant, first token (if any) already emitted
            i = s.key
            rtt = rep.rtt[rcode[i]]
            e2e = finish - arr[i] + rtt
            outcome_ok = e2e <= self.timeout_s
            first = (
                s.first_s + cfg.overhead_s
                if math.isfinite(s.first_s) else finish
            )
            if not outcome_ok:
                self.failed += 1
            else:
                self.latencies.append(e2e)
                self.completed += 1
                records.append(TokenRecord(
                    req_id=i,
                    arrival_s=arr[i],
                    first_token_s=first,
                    finish_s=finish,
                    output_tokens=s.output_tokens,
                    rtt_s=rtt,
                ))
            if want is not None and want[i]:
                spans.finish_token(
                    i, first, finish, cfg.overhead_s,
                    "ok" if outcome_ok else "timeout", e2e,
                )
        by_rid = {r.rid: r for r in cands}
        for m in outcome.migrated:
            # the target batch has queued work now; make sure it steps
            self._busy.add(by_rid[m.target_rid].slot)
        self._n_drained += outcome.n_drained
        self._n_migrated += outcome.n_migrated
        self._migrated_kv_tokens += outcome.migrated_kv_tokens
        self._saved_prefill_tokens += outcome.saved_prefill_tokens
        self._saved_decode_tokens += outcome.saved_decode_tokens
        self._migration_transfer_s += outcome.transfer_s_total
        self._recompute_saved_s += outcome.recompute_saved_s
        return outcome.kill_report

    def _on_dead(self, inst: Instance, now: float) -> None:
        rep = self._by_id.get(inst.id)
        if rep is not None:
            self._kill(rep, now)

    def _sync(self, now: Optional[float] = None) -> None:
        """Reconcile the replica set with the cluster's active instances.

        Instance state only changes at control ticks, so one
        reconciliation per window is exact (no per-sub-tick probes).
        The window-constant LB state (ready order, loads, rtt columns) is
        rebuilt here.
        """
        for inst in self.cluster.instances:
            rep = self._by_id.get(inst.id)
            if rep is None:
                if inst.is_active():
                    self._new_rep(inst)
            elif not inst.is_active():
                self._kill(rep, now)
        if self._live_dirty:
            self._live = [r for r in self._live if not r.dead]
            self._live_dirty = False
        ready = [r for r in self._live if r.inst.is_ready()]
        self._ready_reps = ready
        self._ready_slots = [r.slot for r in ready]
        self._pos = {r.slot: j for j, r in enumerate(ready)}
        self._loads = [r.load for r in ready]
        self._ids = [r.rid for r in ready]
        self._cols = {}

    # ------------------------------------------------------------------
    # sub-tick loop
    # ------------------------------------------------------------------
    def _active(self, t: float) -> bool:
        """Could anything at all happen at grid point ``t``?

        Conservative: a false positive costs one no-op pass, never
        correctness.
        """
        if self._ptr < self._n and self._arr_l[self._ptr] <= t:
            return True
        if self._heap and self._heap[0][0] <= t:
            return True
        if self._pending:
            if self._ready_slots:
                return True
            if t - self._pmin > self.timeout_s:
                return True
        if self._qn and t - self._qmin > self.timeout_s:
            return True
        return False

    def _tick(self, now: float, cluster: ClusterSimulator) -> None:
        self._sync(now)
        dt = cluster.config.control_interval_s
        t = now
        end = now + dt
        token = self._token_cfg is not None
        # plain float accumulation from the tick start: the jax engine's
        # grid (jaxengine.schedule.build_grid) repeats it bit-for-bit
        while t < end:
            if token:
                if self._active_token(t):
                    self._process_token(t)
            elif self._active(t):
                self._process(t, cluster)
            t += self.sub_step_s
        # flush arrival observations before the cluster reads target():
        # batch-equivalent to per-sub-tick observe() calls (eviction is
        # idempotent), amortizing the call overhead per control window
        if self._obs:
            self._observe_batch(self._obs)
            self._obs.clear()
        self._win.maybe_emit(
            now,
            delivered=self._ptr,
            completed=self.completed,
            failed=self.failed,
            instances=cluster.instances,
            token_records=(
                self._token_records if self._token_cfg is not None
                else None
            ),
        )

    def _process(self, t: float, cluster: ClusterSimulator) -> None:
        # 1) arrivals
        ptr = self._ptr
        if ptr < self._n and self._arr_l[ptr] <= t:
            new_ptr = int(self._searchsorted(t, side="right"))
            self._pending.extend(range(ptr, new_ptr))
            m = self._arr_l[ptr]
            if m < self._pmin:
                self._pmin = m
            self._ptr = new_ptr
            self._obs.append((t, new_ptr - ptr))
        # 2) slots with completions due, from the finish-time heap.  Found
        #    BEFORE dispatch so the dispatch fast path knows which replicas
        #    may not start work until their completions are processed.
        due = self._due
        due.clear()
        heap = self._heap
        reps = self._reps
        while heap and heap[0][0] <= t:
            _, s = heapq.heappop(heap)
            if not reps[s].dead:
                due.add(s)
        # 3) dispatch (fills self._touched with slots that got new queued
        #    work; replicas with free capacity, an empty queue and no due
        #    completion start the request immediately — the same outcome
        #    as queue-then-start within the sub-tick, because the
        #    dispatch timeout filter already applied the expiry predicate)
        touched = self._touched
        touched.clear()
        if self._pending:
            self._dispatch(t, due)
        # 4) step the affected replicas.  Untouched slots cannot change:
        #    their running set shrinks only via a due finish (a heap pop)
        #    and their queue only drains into slots freed the same way —
        #    except queue expiry, which is wall-clock driven and handled
        #    by the guarded full pass (per-replica qmin bounds make it a
        #    skip for replicas that cannot hold an expired entry).
        if self._qn and self.timeout_s > 0 \
                and t - self._qmin > self.timeout_s:
            self._step(t, self._ready_slots, due, expire=True)
            qmin_g = _INF
            for r in self._ready_reps:
                if r.qmin < qmin_g:
                    qmin_g = r.qmin
            self._qmin = qmin_g
        elif due:
            slots = sorted(due | touched) if touched else sorted(due)
            self._step(t, slots, due, expire=False)
        elif touched:
            self._step(t, sorted(touched), due, expire=False)
        if self._qn == 0:
            self._qmin = _INF

    # ------------------------------------------------------------------
    def _dispatch(self, t: float, due: Set[int]) -> None:
        pending = self._pending
        arr = self._arr_l
        timeout = self.timeout_s
        ready = self._ready_slots
        spans = self._spans
        want = spans.want_l if spans is not None else None
        if not ready:
            # nothing to route to; age out requests past their timeout
            if len(pending) >= _VEC_MIN:
                arr_v = self._arr
                pa = np.fromiter(pending, dtype=np.int64,
                                 count=len(pending))
                keep = (t - arr_v[pa]) <= timeout
                n_keep = int(keep.sum())
                if n_keep != len(pending):
                    self.failed += len(pending) - n_keep
                    if want is not None:
                        for i in pa[~keep].tolist():
                            if want[i]:
                                spans.expire(i, t, arr[i])
                    pa = pa[keep]
                    self._pending = pa.tolist()
                    self._pmin = (
                        float(arr_v[pa].min()) if n_keep else _INF
                    )
            else:
                kept: List[int] = []
                pmin = _INF
                for i in pending:
                    if t - arr[i] > timeout:
                        self.failed += 1
                        if want is not None and want[i]:
                            spans.expire(i, t, arr[i])
                    else:
                        kept.append(i)
                        if arr[i] < pmin:
                            pmin = arr[i]
                self._pending = kept
                self._pmin = pmin
            return

        reps = self._reps
        touched = self._touched
        svc = self._svc_l
        rcode = self._rcode_l
        heap = self._heap
        conc = self.concurrency
        qn = 0
        qmin = self._qmin
        # pmin is a lower bound on every pending arrival, so when even the
        # oldest request is within the timeout the per-request check is skipped
        check_to = t - self._pmin > timeout
        if self._lb_kind == "rr":
            nready = len(ready)
            loads = self._loads
            cur = self._rr_cursor
            for i in pending:
                if check_to and t - arr[i] > timeout:
                    self.failed += 1
                    if want is not None and want[i]:
                        spans.expire(i, t, arr[i])
                    continue
                j = cur % nready
                s = ready[j]
                cur += 1
                # RR routing ignores loads, but _step's completion/expiry
                # bookkeeping decrements them, so keep the counts honest
                loads[j] += 1
                rep = reps[s]
                if want is not None and want[i]:
                    spans.dispatch(
                        i, t, rep.ord, rep.rtt[rcode[i]], arr[i]
                    )
                run = rep.running
                if not rep.queue and len(run) < conc and s not in due:
                    # immediate start == queue-then-start this sub-tick
                    finish = t + svc[i] * (1.0 + 0.15 * len(run))
                    run.append((finish, i))
                    heapq.heappush(heap, (finish, s))
                    if want is not None and want[i]:
                        spans.start(i, t)
                    continue
                a = arr[i] - rep.rtt[rcode[i]]
                rep.queue.append(i)
                rep.qage.append(a)
                touched.add(s)
                qn += 1
                if a < qmin:
                    qmin = a
                if a < rep.qmin:
                    rep.qmin = a
            self._rr_cursor = cur
        else:
            # least-loaded waterfill: sequentially assign each request to
            # argmin (load, rtt, id) — ties go to the lower-RTT region,
            # then the lower replica id
            ready_reps = self._ready_reps
            loads = self._loads
            ids = self._ids
            cols = self._cols
            rcode = self._rcode_l
            nready = len(ready)
            rng = range(1, nready)
            for i in pending:
                if check_to and t - arr[i] > timeout:
                    self.failed += 1
                    if want is not None and want[i]:
                        spans.expire(i, t, arr[i])
                    continue
                rc = rcode[i]
                col = cols.get(rc)
                if col is None:
                    col = cols[rc] = [r.rtt[rc] for r in ready_reps]
                best, bl, br, bi = 0, loads[0], col[0], ids[0]
                for j in rng:
                    lj = loads[j]
                    if lj > bl:
                        continue
                    if lj < bl or col[j] < br or (
                        col[j] == br and ids[j] < bi
                    ):
                        best, bl, br, bi = j, lj, col[j], ids[j]
                loads[best] += 1
                rep = ready_reps[best]
                if want is not None and want[i]:
                    spans.dispatch(i, t, rep.ord, col[best], arr[i])
                run = rep.running
                if not rep.queue and len(run) < conc \
                        and rep.slot not in due:
                    finish = t + svc[i] * (1.0 + 0.15 * len(run))
                    run.append((finish, i))
                    heapq.heappush(heap, (finish, rep.slot))
                    if want is not None and want[i]:
                        spans.start(i, t)
                    continue
                a = arr[i] - rep.rtt[rc]
                rep.queue.append(i)
                rep.qage.append(a)
                touched.add(rep.slot)
                qn += 1
                if a < qmin:
                    qmin = a
                if a < rep.qmin:
                    rep.qmin = a
        self._qn += qn
        self._qmin = qmin
        # with ready replicas, every non-expired request was routed
        self._pending = []
        self._pmin = _INF

    # ------------------------------------------------------------------
    def _step(self, t: float, slots: Sequence[int], due: Set[int],
              expire: bool) -> None:
        arr = self._arr_l
        svc = self._svc_l
        rcode = self._rcode_l
        timeout = self.timeout_s
        conc = self.concurrency
        heap = self._heap
        reps = self._reps
        loads = self._loads
        pos = self._pos
        spans = self._spans
        want = spans.want_l if spans is not None else None
        for s in slots:
            rep = reps[s]
            run = rep.running
            # completions, in start order
            if s in due:
                still: List[Tuple[float, int]] = []
                n_done = 0
                for f, i in run:
                    if f <= t:
                        e2e = (f - arr[i]) + rep.rtt[rcode[i]]
                        ok = e2e <= timeout
                        if not ok:
                            self.failed += 1
                        else:
                            self.latencies.append(e2e)
                            self.completed += 1
                        if want is not None and want[i]:
                            spans.finish(
                                i, f, "ok" if ok else "timeout", e2e
                            )
                        n_done += 1
                    else:
                        still.append((f, i))
                rep.running = run = still
                loads[pos[s]] -= n_done
            # queue expiry (client hung up past its timeout).  Expired
            # entries are almost always a FIFO prefix, so pop from the
            # front; the post-pop min detects the rare mid-queue stragglers
            # (retried requests carry their original arrival time).
            q = rep.queue
            if expire and q and t - rep.qmin > timeout:
                ages = rep.qage
                nq = len(q)
                k = 0
                while k < nq and t - ages[k] > timeout:
                    k += 1
                if k:
                    if want is not None:
                        for i in q[:k]:
                            if want[i]:
                                spans.expire(i, t, arr[i])
                    del q[:k]
                    del ages[:k]
                    self.failed += k
                    self._qn -= k
                    loads[pos[s]] -= k
                if ages:
                    qmin = min(ages)
                    if t - qmin > timeout:
                        kept: List[int] = []
                        kept_a: List[float] = []
                        n_exp = 0
                        for i, a in zip(q, ages):
                            if t - a > timeout:
                                n_exp += 1
                                if want is not None and want[i]:
                                    spans.expire(i, t, arr[i])
                            else:
                                kept.append(i)
                                kept_a.append(a)
                        rep.queue = q = kept
                        rep.qage = ages = kept_a
                        self.failed += n_exp
                        self._qn -= n_exp
                        loads[pos[s]] -= n_exp
                        qmin = min(ages) if ages else _INF
                    rep.qmin = qmin
                else:
                    rep.qmin = _INF
            # starts: pull queued work into free slots
            if q and len(run) < conc:
                j = 0
                nq = len(q)
                while j < nq and len(run) < conc:
                    i = q[j]
                    j += 1
                    finish = t + svc[i] * (1.0 + 0.15 * len(run))
                    run.append((finish, i))
                    heapq.heappush(heap, (finish, s))
                    if want is not None and want[i]:
                        spans.start(i, t)
                del q[:j]
                del rep.qage[:j]
                self._qn -= j

    # ------------------------------------------------------------------
    # token mode: continuous-batching hot path
    # ------------------------------------------------------------------
    def _active_token(self, t: float) -> bool:
        """Token-mode activity check: arrivals due, routable/expirable
        pending work, or any replica with live batch state."""
        if self._ptr < self._n and self._arr_l[self._ptr] <= t:
            return True
        if self._pending:
            if self._ready_slots:
                return True
            if t - self._pmin > self.timeout_s:
                return True
        if self._busy:
            return True
        return False

    def _process_token(self, t: float) -> None:
        # 1) arrivals (identical batching to the request-mode path)
        ptr = self._ptr
        if ptr < self._n and self._arr_l[ptr] <= t:
            new_ptr = int(self._searchsorted(t, side="right"))
            self._pending.extend(range(ptr, new_ptr))
            m = self._arr_l[ptr]
            if m < self._pmin:
                self._pmin = m
            self._ptr = new_ptr
            self._obs.append((t, new_ptr - ptr))
        # 2) route pending into replica batches
        if self._pending:
            self._dispatch_token(t)
        # 3) run every busy batch's iterations up to t
        if self._busy:
            self._advance_batches(t)

    def _dispatch_token(self, t: float) -> None:
        pending = self._pending
        arr = self._arr_l
        timeout = self.timeout_s
        ready = self._ready_slots
        spans = self._spans
        want = spans.want_l if spans is not None else None
        if not ready:
            # nothing to route to; age out requests past their timeout
            kept: List[int] = []
            pmin = _INF
            for i in pending:
                if t - arr[i] > timeout:
                    self.failed += 1
                    if want is not None and want[i]:
                        spans.expire(i, t, arr[i])
                else:
                    kept.append(i)
                    if arr[i] < pmin:
                        pmin = arr[i]
            self._pending = kept
            self._pmin = pmin
            return
        reps = self._reps
        busy = self._busy
        ptok = self._ptok_l
        otok = self._otok_l
        rcode = self._rcode_l
        check_to = t - self._pmin > timeout
        if self._lb_kind == "rr":
            nready = len(ready)
            loads = self._loads
            cur = self._rr_cursor
            for i in pending:
                if check_to and t - arr[i] > timeout:
                    self.failed += 1
                    if want is not None and want[i]:
                        spans.expire(i, t, arr[i])
                    continue
                j = cur % nready
                s = ready[j]
                cur += 1
                rep = reps[s]
                ok = rep.batch.enqueue(i, ptok[i], otok[i], arr[i], t,
                                       rtt_s=rep.rtt[rcode[i]])
                if ok:
                    loads[j] += 1
                    busy.add(s)
                else:
                    self.failed += 1     # can never fit the KV budget
                if want is not None and want[i]:
                    # tap order: dispatch, then track (admitted) or
                    # reject (unservable)
                    spans.dispatch(
                        i, t, rep.ord, rep.rtt[rcode[i]], arr[i],
                        token=True,
                    )
                    if ok:
                        rep.batch.track(i, i)
                    else:
                        spans.reject(i, t)
            self._rr_cursor = cur
        else:
            # least-loaded waterfill over (load, rtt, id), load = batch
            # occupancy + admission queue
            ready_reps = self._ready_reps
            loads = self._loads
            ids = self._ids
            cols = self._cols
            rcode = self._rcode_l
            nready = len(ready)
            rng = range(1, nready)
            for i in pending:
                if check_to and t - arr[i] > timeout:
                    self.failed += 1
                    if want is not None and want[i]:
                        spans.expire(i, t, arr[i])
                    continue
                rc = rcode[i]
                col = cols.get(rc)
                if col is None:
                    col = cols[rc] = [r.rtt[rc] for r in ready_reps]
                best, bl, br, bi = 0, loads[0], col[0], ids[0]
                for j in rng:
                    lj = loads[j]
                    if lj > bl:
                        continue
                    if lj < bl or col[j] < br or (
                        col[j] == br and ids[j] < bi
                    ):
                        best, bl, br, bi = j, lj, col[j], ids[j]
                rep = ready_reps[best]
                ok = rep.batch.enqueue(i, ptok[i], otok[i], arr[i], t,
                                       rtt_s=rep.rtt[rc])
                if ok:
                    loads[best] += 1
                    busy.add(rep.slot)
                else:
                    self.failed += 1
                if want is not None and want[i]:
                    spans.dispatch(
                        i, t, rep.ord, rep.rtt[rc], arr[i], token=True
                    )
                    if ok:
                        rep.batch.track(i, i)
                    else:
                        spans.reject(i, t)
        self._pending = []
        self._pmin = _INF

    def _advance_batches(self, t: float) -> None:
        timeout = self.timeout_s
        loads = self._loads
        pos = self._pos
        rcode = self._rcode_l
        records = self._token_records
        spans = self._spans
        want = spans.want_l if spans is not None else None
        overhead = self._token_cfg.overhead_s
        idle: List[int] = []
        for s in sorted(self._busy):
            rep = self._reps[s]
            batch = rep.batch
            n_removed = 0
            for c in batch.advance(t):
                i = c.key
                rtt = rep.rtt[rcode[i]]
                e2e = c.finish_s - c.arrival_s + rtt
                ok = e2e <= timeout
                if not ok:
                    self.failed += 1
                else:
                    self.latencies.append(e2e)
                    self.completed += 1
                    records.append(TokenRecord(
                        req_id=i,
                        arrival_s=c.arrival_s,
                        first_token_s=c.first_token_s,
                        finish_s=c.finish_s,
                        output_tokens=c.output_tokens,
                        rtt_s=rtt,
                    ))
                if want is not None and want[i]:
                    spans.finish_token(
                        i, c.first_token_s, c.finish_s, overhead,
                        "ok" if ok else "timeout", e2e,
                    )
                n_removed += 1
            if timeout > 0 and batch.n_queued:
                expired = batch.expire_queue(t, timeout)
                self.failed += len(expired)
                if want is not None:
                    arr = self._arr_l
                    for i in expired:
                        if want[i]:
                            spans.expire(i, t, arr[i])
                n_removed += len(expired)
            if n_removed:
                loads[pos[s]] -= n_removed
            if batch.load == 0:
                idle.append(s)
        for s in idle:
            self._busy.discard(s)

    # ------------------------------------------------------------------
    def run(self, duration_s: Optional[float] = None) -> ServingResult:
        # run-scope the metrics registry so library-level counters
        # (e.g. latency-model fallbacks) land on this run, not a global
        with use_registry(self.obs.registry):
            base = self.cluster.run(duration_s)
        # drain: anything still pending/in-flight past the horizon fails
        self.failed += len(self._pending)
        for rep in self._reps:
            self.failed += rep.load
        if self._spans is not None:
            self._spans.finalize(base.duration_s)
        token_stats = None
        if self._token_cfg is not None:
            knobs = self._token_knobs
            token_stats = TokenStats.from_records(
                self._token_records,
                slo_ttft_s=knobs.slo_ttft_s,
                slo_tpot_s=knobs.slo_tpot_s,
                horizon_s=base.duration_s,
                window_s=knobs.goodput_window_s,
                n_requests=self._ptr,
                n_kv_preempted_seqs=self._n_kv_preempted,
                n_killed_queued=self._n_killed_queued,
                lost_prefill_tokens=self._lost_prefill_tokens,
                lost_decode_tokens=self._lost_decode_tokens,
                n_drained_seqs=self._n_drained,
                n_migrated_seqs=self._n_migrated,
                migrated_kv_tokens=self._migrated_kv_tokens,
                saved_prefill_tokens=self._saved_prefill_tokens,
                saved_decode_tokens=self._saved_decode_tokens,
                migration_transfer_s=self._migration_transfer_s,
                recompute_saved_s=self._recompute_saved_s,
            )
        return ServingResult(
            policy=self.cluster.policy.name,
            trace=self.cluster.trace.name,
            workload=self.workload_name,
            n_requests=self._ptr,
            n_completed=self.completed,
            n_failed=self.failed,
            latencies_s=np.asarray(self.latencies),
            total_cost=base.total_cost,
            spot_cost=base.spot_cost,
            od_cost=base.od_cost,
            cost_vs_ondemand=base.cost_vs_ondemand,
            availability=base.availability,
            n_preemptions=base.n_preemptions,
            n_launch_failures=base.n_launch_failures,
            token=token_stats,
            n_retried_requests=self._n_retried,
            lost_kv_tokens=(
                self._lost_prefill_tokens + self._lost_decode_tokens
            ),
            metrics=self.obs.registry.snapshot() or None,
            obs=self.obs if self.obs.enabled else None,
        )
