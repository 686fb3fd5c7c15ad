"""``JaxServingEngine``: jit/vmap scenario engine facade.

Drop-in subclass of :class:`VectorizedServingEngine` selectable via
``sim.engine: "jax"``.  A single ``run()`` replays the control plane in
Python (phase A, exact by construction — it *is* the real cluster
simulator) and compiles the serving data plane as one ``lax.scan``
(phase B).  The real win is :func:`run_cells` /
:func:`run_schedules`: every cell of a (policies × traces × seeds)
matrix that shares a static shape signature runs as one ``vmap``-ed XLA
program, so matrix throughput scales with the batch instead of the
Python interpreter.

Scope and guarantees:

* request-model cells are decision-for-decision equivalent to the NumPy
  oracle (``tests/test_jax_engine.py`` locks this down to 1e-6 and
  mostly to the bit);
* ``replica_model: "token"`` cells delegate to the oracle's data plane
  unchanged — continuous batching carries per-sequence KV state whose
  shapes are data-dependent, so it stays on the NumPy path (documented
  limitation; the jax path still accepts such specs);
* a cell whose per-replica queue would exceed ``queue_capacity`` is
  re-run on the oracle (the kernel flags overflow instead of dropping
  work), so capacity tuning can never change results.

Each cell sent to NumPy counts once on its run's registry, as
``jax_numpy_fallback{reason=token|overflow}``: a "jax" result that did
not run on the device says so.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.obs import hostspan
from repro.obs.registry import use_registry
from repro.serving.engine import VectorizedServingEngine, _Rep
from repro.serving.jaxengine.schedule import (
    CellSchedule,
    ScheduleRecorder,
    build_grid,
)
from repro.serving.result import ServingResult

__all__ = [
    "JaxServingEngine",
    "run_cells",
    "run_schedules",
    "assemble_result",
]

#: per-replica queue pool size (static shape); overflow → oracle rerun
DEFAULT_QUEUE_CAPACITY = 256

#: registry counter of cells that ran on the NumPy oracle instead
FALLBACK_COUNTER = "jax_numpy_fallback"


class JaxServingEngine(VectorizedServingEngine):
    """Two-phase JAX engine behind the ``VectorizedServingEngine`` API."""

    queue_capacity = DEFAULT_QUEUE_CAPACITY

    def __init__(self, trace, policy, requests, cfg, **kw) -> None:
        # pristine control-plane state for the overflow fallback (phase A
        # consumes the policy/autoscaler rng and counters)
        self._pristine = {
            "trace": trace,
            "policy": copy.deepcopy(policy),
            "requests": requests,
            "cfg": cfg,
            "kw": {
                k: (copy.deepcopy(v) if k == "autoscaler" else v)
                for k, v in kw.items()
            },
        }
        super().__init__(trace, policy, requests, cfg, **kw)
        self._rec: Optional[ScheduleRecorder] = None
        self.schedule: Optional[CellSchedule] = None

    # -- phase-A hooks ------------------------------------------------
    def _tick(self, now, cluster) -> None:
        rec = self._rec
        if rec is None:
            super()._tick(now, cluster)
            return
        self._sync(now)
        k = rec.record_tick(self._ready_slots)
        obs = rec.obs_for(k)
        if obs:
            self._observe_batch(list(obs))

    def _kill(self, rep: _Rep, now=None) -> None:
        rec = self._rec
        if rec is None or rep.batch is not None:
            super()._kill(rep, now)
            return
        if rep.dead:
            return
        rep.dead = True
        self._live_dirty = True
        rec.record_kill(rep.slot)

    # -- phase A ------------------------------------------------------
    def record_schedule(
        self, duration_s: Optional[float] = None
    ) -> CellSchedule:
        """Run the control plane once; return the phase-B payload.

        Consumes this engine (the cluster has run); callable once.
        """
        with hostspan.host_span(hostspan.PHASE_A):
            if self._token_cfg is not None:
                raise RuntimeError(
                    "token-model cells run on the NumPy data plane; "
                    "call run() directly"
                )
            dt = self.cluster.config.control_interval_s
            dur = float(duration_s or self.cluster.trace.duration_s)
            grid = build_grid(dur, dt, self.sub_step_s)
            self._rec = ScheduleRecorder(grid, self._arr)
            # phase A is the real control plane: the cluster's obs taps emit
            # the same decision/lifecycle events as the other engines (no
            # window samples — this tick override never runs the sampler)
            with use_registry(self.obs.registry):
                base = self.cluster.run(duration_s)
            ready, rtt, kill_slot, kill_g, post = self._rec.control_arrays(
                len(self._reps),
                [r.rtt for r in self._reps],
                len(self._client_regions),
            )
            self._rec = None
            sched = CellSchedule(
                policy_name=self.cluster.policy.name,
                trace_name=self.cluster.trace.name,
                workload_name=self.workload_name,
                arr=self._arr,
                svc=self._svc,
                rcode=np.asarray(self._rcode, dtype=np.int64),
                n_regions=max(len(self._client_regions), 1),
                timeout_s=self.timeout_s,
                concurrency=self.concurrency,
                lb_kind=self._lb_kind,
                grid=grid,
                ready_mask=ready,
                rtt=rtt,
                kill_slot=kill_slot,
                kill_g=kill_g,
                post_slots=post,
                base=base,
                n_slots=len(self._reps),
                trace_on=self._spans is not None,
            )
            self.schedule = sched
            return sched

    def _fallback_run(
        self, duration_s: Optional[float]
    ) -> ServingResult:
        """Oracle rerun from pristine control-plane state (overflow)."""
        p = self._pristine
        kw = {
            k: (copy.deepcopy(v) if k == "autoscaler" else v)
            for k, v in p["kw"].items()
        }
        # fresh recorder: the rerun replays the whole control plane, and
        # sharing this engine's recorder would double-record phase A
        kw["obs"] = self.obs.fresh()
        kw["obs"].registry.inc(FALLBACK_COUNTER, reason="overflow")
        eng = VectorizedServingEngine(
            p["trace"],
            copy.deepcopy(p["policy"]),
            p["requests"],
            p["cfg"],
            **kw,
        )
        return eng.run(duration_s)

    # -- public API ---------------------------------------------------
    def run(self, duration_s: Optional[float] = None) -> ServingResult:
        return run_cells([self], [duration_s])[0]


def assemble_result(sched: CellSchedule, out: dict) -> ServingResult:
    """Build a :class:`ServingResult` from one lane's kernel outputs."""
    n = sched.n
    status = np.asarray(out["status"][:n])
    e2e = np.asarray(out["e2e"][:n])
    n_req = int(out["a_ptr"])
    comp = status == 1
    n_completed = int(comp.sum())
    # drain: arrived but unresolved (pending / in-flight / queued,
    # including work on post-horizon-killed slots) fails, like the oracle
    n_failed = int((status == 2).sum()) + int(
        (status[:n_req] == 0).sum()
    )
    n_retried = int(out["n_retried"])
    for s in sched.post_slots:
        # kills after the last tick hook: the oracle re-pends this work
        # before the drain; the scan never processes the event, so its
        # final per-slot occupancy is exactly what the oracle re-pended
        n_retried += int(out["run_n"][s]) + int(out["q_cnt"][s])
    base = sched.base
    return ServingResult(
        policy=sched.policy_name,
        trace=sched.trace_name,
        workload=sched.workload_name,
        n_requests=n_req,
        n_completed=n_completed,
        n_failed=n_failed,
        latencies_s=e2e[comp],
        total_cost=base.total_cost,
        spot_cost=base.spot_cost,
        od_cost=base.od_cost,
        cost_vs_ondemand=base.cost_vs_ondemand,
        availability=base.availability,
        n_preemptions=base.n_preemptions,
        n_launch_failures=base.n_launch_failures,
        token=None,
        n_retried_requests=n_retried,
        lost_kv_tokens=0,
    )


def _empty_result(sched: CellSchedule) -> ServingResult:
    """Degenerate horizon (no control ticks) or empty tape: nothing to
    scan — every metric is determined host-side."""
    n_req = (
        int(np.searchsorted(sched.arr, sched.grid.ts[-1], side="right"))
        if sched.grid.n_points and sched.n
        else 0
    )
    base = sched.base
    return ServingResult(
        policy=sched.policy_name,
        trace=sched.trace_name,
        workload=sched.workload_name,
        n_requests=n_req,
        n_completed=0,
        n_failed=n_req,
        latencies_s=np.empty(0),
        total_cost=base.total_cost,
        spot_cost=base.spot_cost,
        od_cost=base.od_cost,
        cost_vs_ondemand=base.cost_vs_ondemand,
        availability=base.availability,
        n_preemptions=base.n_preemptions,
        n_launch_failures=base.n_launch_failures,
        token=None,
        n_retried_requests=0,
        lost_kv_tokens=0,
    )


def run_schedules(
    scheds: Sequence[CellSchedule],
    *,
    queue_capacity: int = DEFAULT_QUEUE_CAPACITY,
    outputs: Optional[List[Optional[dict]]] = None,
) -> List[Optional[ServingResult]]:
    """Phase B over many cells: group by static shape signature, pad
    each group to a common shape, and run one vmapped program per group.

    Returns results aligned with ``scheds``; ``None`` marks a lane whose
    queue pool overflowed (caller must rerun that cell on the oracle).
    Pass a list as ``outputs`` to also receive each lane's raw kernel
    outputs (aligned with ``scheds``; ``None`` for overflow/empty lanes)
    — the span-reconstruction path in :func:`run_cells` consumes these.
    """
    from repro.serving.jaxengine import kernel as K

    results: List[Optional[ServingResult]] = [None] * len(scheds)
    if outputs is not None:
        del outputs[:]
        outputs.extend([None] * len(scheds))
    groups: dict = {}
    for idx, sc in enumerate(scheds):
        if sc.grid.n_points == 0 or sc.n == 0 or sc.n_slots == 0:
            # no grid → nothing ever runs; no replicas → nothing ever
            # dispatches and the drain fails every arrival (oracle-equal:
            # with zero ready slots dispatch is skipped and pending only
            # drains at the horizon)
            results[idx] = _empty_result(sc)
            continue
        key = (
            sc.grid.signature,
            sc.concurrency,
            sc.lb_kind,
            sc.timeout_s > 0,
            sc.trace_on,
        )
        groups.setdefault(key, []).append(idx)

    for (gsig, C, lb_kind, expire_on, trace_on), idxs in groups.items():
        with hostspan.host_span(hostspan.PACK):
            cells = [scheds[i] for i in idxs]
            g = cells[0].grid
            N = max(c.n for c in cells)
            R = max(c.n_slots for c in cells)
            E = max(c.n_events for c in cells)
            NREG = max(c.n_regions for c in cells)
            L = len(cells)
            lanes = {
                "arr": np.full((L, N), np.inf),
                "svc": np.ones((L, N)),
                "rcode": np.zeros((L, N), dtype=np.int64),
                "rtt": np.zeros((L, R, NREG)),
                "ready": np.zeros((L, g.ticks, R), dtype=bool),
                "kill_slot": np.zeros((L, max(E, 1)), dtype=np.int64),
                "kill_g": np.full(
                    (L, max(E, 1)), g.n_points, dtype=np.int64
                ),
                "timeout": np.zeros(L),
            }
            amax, atyp = 1, 1
            for li, c in enumerate(cells):
                lanes["arr"][li, : c.n] = c.arr
                lanes["svc"][li, : c.n] = c.svc
                lanes["rcode"][li, : c.n] = c.rcode
                lanes["rtt"][li, : c.n_slots, : c.n_regions] = c.rtt
                lanes["ready"][li, :, : c.n_slots] = c.ready_mask
                lanes["kill_slot"][li, : c.n_events] = c.kill_slot
                lanes["kill_g"][li, : c.n_events] = c.kill_g
                lanes["timeout"][li] = c.timeout_s
                # exact per-sub-step arrival bound: sizes the kernel's masked
                # dispatch/start scans (backlog spikes spill to the remainder
                # loop, so this is a performance knob, not a correctness one)
                counts = np.diff(
                    np.searchsorted(c.arr, g.ts, side="right"), prepend=0
                )
                if counts.size:
                    amax = max(amax, int(counts.max()))
                    atyp = max(atyp, int(np.percentile(counts, 99)))
            key = K.KernelKey(
                G=g.n_points,
                W=g.ticks,
                N=N,
                R=R,
                Q=queue_capacity,
                C=C,
                NREG=NREG,
                E=E,
                AMAX=amax,
                ATYP=atyp,
                lb_rr=(lb_kind == "rr"),
                expire_on=expire_on,
                trace_on=trace_on,
            )
        out = K.run_group(
            key,
            lanes,
            g.ts,
            np.arange(g.n_points, dtype=np.int64),
            g.win_of,
        )
        with hostspan.host_span(hostspan.ASSEMBLE):
            for li, i in enumerate(idxs):
                if bool(out["overflow"][li]):
                    continue     # caller falls back to the oracle
                lane_out = {k2: v[li] for k2, v in out.items()}
                results[i] = assemble_result(cells[li], lane_out)
                if outputs is not None:
                    outputs[i] = lane_out
    return results


def _reconstruct_spans(
    eng: JaxServingEngine, sched: CellSchedule, out: dict
) -> None:
    """Rebuild sampled request spans from the kernel's span timelines.

    The kernel resolves one (dispatch, start, finish, slot) quadruple per
    completion-scattered request — a killed-and-retried request records
    its final, completing attempt (``attempts`` stays 1; no preempt
    cuts), and drain-failed or queue-expired requests get no jax spans.
    For never-preempted requests the replayed taps are bit-identical to
    the oracle's (x64 kernel, same grid), so the span parity test can
    compare records byte-for-byte after filtering.
    """
    spans = eng._spans
    if spans is None or "disp_t" not in out:
        return
    n = sched.n
    status = np.asarray(out["status"][:n])
    e2e = np.asarray(out["e2e"][:n])
    disp = np.asarray(out["disp_t"][:n])
    start = np.asarray(out["start_t"][:n])
    rep_slot = np.asarray(out["rep"][:n])
    fin = np.asarray(out["fin_t"][:n])
    rtt, rcode, arr = sched.rtt, sched.rcode, sched.arr
    ords = [r.ord for r in eng._reps]
    want = spans.want_l
    for o in range(n):
        if not want[o] or status[o] == 0:
            continue
        slot = int(rep_slot[o])
        spans.dispatch(
            o, float(disp[o]), ords[slot],
            float(rtt[slot, rcode[o]]), float(arr[o]),
        )
        spans.start(o, float(start[o]))
        spans.finish(
            o, float(fin[o]),
            "ok" if status[o] == 1 else "timeout", float(e2e[o]),
        )


def run_cells(
    engines: Sequence[JaxServingEngine],
    durations: Optional[Sequence[Optional[float]]] = None,
) -> List[ServingResult]:
    """Run a batch of cells end to end: serial phase A per cell, one
    vmapped phase B per shape group, oracle fallback for token cells and
    queue-overflow lanes.  Results align with ``engines``."""
    if durations is None:
        durations = [None] * len(engines)
    results: List[Optional[ServingResult]] = [None] * len(engines)
    jax_idx: List[int] = []
    scheds: List[CellSchedule] = []
    for i, (eng, dur) in enumerate(zip(engines, durations)):
        if eng._token_cfg is not None:
            # continuous batching stays on the NumPy path
            eng.obs.registry.inc(FALLBACK_COUNTER, reason="token")
            with hostspan.host_span(hostspan.FALLBACK):
                results[i] = VectorizedServingEngine.run(eng, dur)
        else:
            scheds.append(eng.record_schedule(dur))
            jax_idx.append(i)
    if scheds:
        cap = max(
            getattr(e, "queue_capacity", DEFAULT_QUEUE_CAPACITY)
            for e in engines
        )
        outs: List[Optional[dict]] = []
        group = run_schedules(scheds, queue_capacity=cap, outputs=outs)
        with hostspan.host_span(hostspan.ASSEMBLE):
            for k, (i, res) in enumerate(zip(jax_idx, group)):
                if res is None:     # queue pool overflow → oracle rerun
                    # the rerun's own recorder rides on its result
                    with hostspan.host_span(hostspan.FALLBACK):
                        res = engines[i]._fallback_run(durations[i])
                else:
                    obs = engines[i].obs
                    if outs[k] is not None:
                        _reconstruct_spans(engines[i], scheds[k], outs[k])
                    res = dataclasses.replace(
                        res,
                        metrics=obs.registry.snapshot() or None,
                        obs=obs if obs.enabled else None,
                    )
                results[i] = res
    return results
