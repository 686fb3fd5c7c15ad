"""Phase A: replay the control plane, record the replica schedule.

The serving tick loop has a one-way dependency structure: the control
plane (policy, cluster FSM, autoscaler) never observes the data plane —
the autoscaler sees only *arrival* batches, which are a pure function of
the request tape and the sub-step grid.  So the control plane can run
once in ordinary Python with the real :class:`ClusterSimulator` (exact
costs, preemptions, launch failures, rng draws by construction) while
recording everything the data plane needs as dense arrays:

* the sub-step grid itself (the engines' own float accumulation,
  precomputed so grid points match the NumPy oracle bit-for-bit);
* per control window, the roster of ready replica slots;
* per slot, its RTT row (client-region code → seconds);
* kill events as ``(event order, slot, window)`` — a preemption at tick
  ``k`` lands *before* the tick hook (window ``k``), a policy
  termination lands *after* it (window ``k + 1``), and the recorder
  tracks that boundary so phase B re-pends work at the oracle's instant.

Phase B (:mod:`.kernel`) then replays only the serving data plane as one
``lax.scan`` over these arrays.  A :class:`CellSchedule` is a plain
numpy/dataclass payload — picklable, so phase A can fan out across
worker processes while phase B batches every cell in one program.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

from repro.cluster.simulator import SimResult

__all__ = ["CellSchedule", "SubStepGrid", "build_grid", "ScheduleRecorder"]


@dataclasses.dataclass(frozen=True)
class SubStepGrid:
    """The exact sub-step grid of a (duration, dt, sub_step) family."""

    ts: np.ndarray         # [G] float64 grid points
    win_of: np.ndarray     # [G] int64: control window of each point
    win_first: np.ndarray  # [W] int64: first grid index of each window
    ticks: int             # W
    dt: float
    sub_step_s: float

    @property
    def n_points(self) -> int:
        return int(self.ts.shape[0])

    @property
    def signature(self) -> Tuple[float, float, int]:
        """Two grids with equal signatures hold identical floats."""
        return (self.dt, self.sub_step_s, self.ticks)


def build_grid(duration_s: float, dt: float, sub_step_s: float) -> SubStepGrid:
    """Replicate the vector engine's per-window float accumulation exactly.

    The vectorized engine walks
    ``t = now; while t < now + dt: ...; t += sub_step_s`` inside each
    control tick, so the grid must be built with the *same* accumulation
    (not ``arange``) for timeout instants to match bit-for-bit.
    """
    ticks = int(float(duration_s) / dt)
    ts: List[float] = []
    win_of: List[int] = []
    win_first = np.empty(ticks, dtype=np.int64)
    for k in range(ticks):
        now = k * dt
        win_first[k] = len(ts)
        t = now
        end = now + dt
        while t < end:
            ts.append(t)
            win_of.append(k)
            t += sub_step_s
    return SubStepGrid(
        ts=np.asarray(ts, dtype=np.float64),
        win_of=np.asarray(win_of, dtype=np.int64),
        win_first=win_first,
        ticks=ticks,
        dt=float(dt),
        sub_step_s=float(sub_step_s),
    )


@dataclasses.dataclass
class CellSchedule:
    """One cell's complete phase-B input: tape + control-plane replay.

    Self-contained and picklable: the data plane needs nothing else, and
    the final :class:`~repro.serving.result.ServingResult` is assembled
    from this plus the kernel outputs (see ``engine.assemble_result``).
    """

    # identity / labels
    policy_name: str
    trace_name: str
    workload_name: str
    # request tape
    arr: np.ndarray              # [n] float64 arrivals, sorted
    svc: np.ndarray              # [n] float64 roofline service times
    rcode: np.ndarray            # [n] client-region codes
    n_regions: int
    # serving knobs
    timeout_s: float
    concurrency: int
    lb_kind: str                 # "rr" | "ll"
    # control-plane replay
    grid: SubStepGrid
    ready_mask: np.ndarray       # [W, R] bool: slot ready in window
    rtt: np.ndarray              # [R, NREG] float64
    kill_slot: np.ndarray        # [E] int64, chronological
    kill_g: np.ndarray           # [E] int64 grid index; G ⇒ post-horizon
    post_slots: np.ndarray       # slots of post-horizon kill events
    base: SimResult              # control-plane result (costs, churn, ...)
    n_slots: int
    trace_on: bool = False       # carry span timelines through the kernel

    @property
    def n(self) -> int:
        return int(self.arr.shape[0])

    @property
    def n_events(self) -> int:
        return int(self.kill_slot.shape[0])


class ScheduleRecorder:
    """Recording state driven by ``JaxServingEngine``'s tick/kill hooks."""

    def __init__(self, grid: SubStepGrid, arr: np.ndarray) -> None:
        self.grid = grid
        # arrival observations per window: the oracle appends one
        # ``(t, n_new)`` per sub-step that consumed new arrivals
        ends = np.searchsorted(arr, grid.ts, side="right")
        counts = np.diff(ends, prepend=0)
        self._obs_by_win: List[List[Tuple[float, int]]] = [
            [] for _ in range(grid.ticks)
        ]
        for j in np.flatnonzero(counts):
            self._obs_by_win[int(grid.win_of[j])].append(
                (float(grid.ts[j]), int(counts[j]))
            )
        self.ready_rows: List[List[int]] = []
        self.kills: List[Tuple[int, int]] = []   # (window, slot), in order
        self.win = 0          # next window index
        self.kill_win = 0     # window a kill occurring *now* belongs to

    def obs_for(self, k: int) -> Sequence[Tuple[float, int]]:
        return self._obs_by_win[k]

    def record_tick(self, ready_slots: Sequence[int]) -> int:
        """Called from the tick hook *after* sync; returns this window."""
        k = self.win
        self.win = k + 1
        self.ready_rows.append(list(ready_slots))
        # anything dying between this hook and the next (policy
        # terminations of this tick, preemptions of the next) is
        # processed by the data plane at the start of window k+1
        self.kill_win = k + 1
        return k

    def record_kill(self, slot: int) -> None:
        self.kills.append((self.kill_win, slot))

    def control_arrays(
        self, n_slots: int, rtt_rows: Sequence[Sequence[float]],
        n_regions: int,
    ):
        """Densify the recording into phase-B arrays."""
        g = self.grid
        ready = np.zeros((max(g.ticks, 1), max(n_slots, 1)), dtype=bool)
        for k, row in enumerate(self.ready_rows):
            for s in row:
                ready[k, s] = True
        rtt = np.zeros((max(n_slots, 1), max(n_regions, 1)))
        for s, row in enumerate(rtt_rows):
            rtt[s, : len(row)] = row
        kill_slot = np.asarray([s for _, s in self.kills], dtype=np.int64)
        kill_g = np.asarray(
            [
                int(g.win_first[w]) if w < g.ticks else g.n_points
                for w, _ in self.kills
            ],
            dtype=np.int64,
        )
        post = np.asarray(
            [s for w, s in self.kills if w >= g.ticks], dtype=np.int64
        )
        return ready, rtt, kill_slot, kill_g, post
