"""Plain reference of the scenario engine's serving data plane.

Given one cell's request tape and its control-plane replay (the replica
roster of every control window, each replica's client RTTs and its kill
events), play the data plane request by request on the sub-step grid:
arrivals join the pending list, pending work is routed (round robin or
least loaded), a replica runs at most ``concurrency`` requests and queues
the rest, a request's service time grows by 15% for every request already
running beside it, and the client's timeout fails work that waits or
answers too late.  Killed replicas hand their work back to pending.  At
the horizon whatever is unresolved fails.

It is written from the semantics, in ordinary Python over scalars, and
shares no code with the engines.  ``dtype`` sets the precision of every
time: ``float`` is IEEE double; ``numpy.float32`` rounds every stored time
and every arithmetic result to single precision (the control).
"""

from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

# contention slowdown per request already running on the replica
SLOWDOWN = 0.15


def simulate(cell: Dict, dtype: Callable = float) -> Dict:
    """Play one cell; returns its counts and the latency of each completed
    request, in request order.

    ``cell`` holds plain arrays: ``arr``, ``svc``, ``rcode`` (per request),
    ``ts``, ``win_of``, ``win_first`` (the sub-step grid), ``ready``
    ([windows, slots] bool), ``rtt`` ([slots, regions]), ``kill_slot`` and
    ``kill_g`` (kill events in order, by grid index), ``timeout_s``,
    ``concurrency`` and ``lb`` ("rr" or "ll").
    """
    f = dtype
    arr = [f(x) for x in cell["arr"]]
    svc = [f(x) for x in cell["svc"]]
    rcode = [int(x) for x in cell["rcode"]]
    rtt = [[f(x) for x in row] for row in cell["rtt"]]
    ts = [f(x) for x in cell["ts"]]
    win_of = [int(x) for x in cell["win_of"]]
    win_first = {int(g): w for w, g in enumerate(cell["win_first"])}
    ready_mask = np.asarray(cell["ready"], dtype=bool)
    timeout = f(cell["timeout_s"])
    conc = int(cell["concurrency"])
    least_loaded = cell["lb"] != "rr"
    n_slots = ready_mask.shape[1]
    kills: Dict[int, List[int]] = {}
    for s, g in zip(cell["kill_slot"], cell["kill_g"]):
        kills.setdefault(int(g), []).append(int(s))
    slow = f(SLOWDOWN)
    one = f(1.0)

    running: List[List] = [[] for _ in range(n_slots)]   # (finish, req)
    queue: List[List] = [[] for _ in range(n_slots)]     # (req, eff. age)
    pending: List[int] = []
    done: Dict[int, float] = {}                          # req -> e2e
    n = len(arr)
    ptr = completed = failed = 0
    cursor = 0
    ready: List[int] = []
    loads: List[int] = []

    def start(s: int, i: int, t) -> None:
        k = f(len(running[s]))
        running[s].append((f(t + f(svc[i] * f(one + f(slow * k)))), i))

    for g, t in enumerate(ts):
        w = win_of[g]
        if g in win_first:
            for s in kills.get(g, ()):
                pending.extend(i for _, i in running[s])
                pending.extend(i for i, _ in queue[s])
                running[s], queue[s] = [], []
            ready = [s for s in range(n_slots) if ready_mask[w, s]]
            loads = [len(running[s]) + len(queue[s]) for s in ready]
        while ptr < n and arr[ptr] <= t:
            pending.append(ptr)
            ptr += 1
        due = {s for s in ready if any(fin <= t for fin, _ in running[s])}

        if pending:
            kept = []
            for i in pending:
                if f(t - arr[i]) > timeout:
                    failed += 1
                    continue
                if not ready:
                    kept.append(i)
                    continue
                if least_loaded:
                    j = min(range(len(ready)),
                            key=lambda j: (loads[j], rtt[ready[j]][rcode[i]],
                                           ready[j]))
                else:
                    j = cursor % len(ready)
                    cursor += 1
                loads[j] += 1
                s = ready[j]
                if not queue[s] and len(running[s]) < conc and s not in due:
                    start(s, i, t)
                else:
                    queue[s].append((i, f(arr[i] - rtt[s][rcode[i]])))
            pending = kept

        for j, s in enumerate(ready):
            if s in due:
                still = []
                for fin, i in running[s]:
                    if fin <= t:
                        e2e = f(f(fin - arr[i]) + rtt[s][rcode[i]])
                        if e2e <= timeout:
                            completed += 1
                            done[i] = float(e2e)
                        else:
                            failed += 1
                        loads[j] -= 1
                    else:
                        still.append((fin, i))
                running[s] = still
            if timeout > 0 and queue[s]:
                left = [(i, a) for i, a in queue[s] if not f(t - a) > timeout]
                failed += len(queue[s]) - len(left)
                loads[j] -= len(queue[s]) - len(left)
                queue[s] = left
            while queue[s] and len(running[s]) < conc:
                i, _ = queue[s].pop(0)
                start(s, i, t)

    failed += len(pending) + sum(len(r) + len(q)
                                 for r, q in zip(running, queue))
    return {"n_requests": ptr, "n_completed": completed, "n_failed": failed,
            "latencies": np.asarray([done[i] for i in sorted(done)])}


def summary(out: Dict) -> Dict:
    """The data-plane fields of a deployment cell's result."""
    lat = out["latencies"]
    nan = float("nan")
    return {
        "n_requests": out["n_requests"],
        "n_completed": out["n_completed"],
        "n_failed": out["n_failed"],
        "failure_rate": out["n_failed"] / max(out["n_requests"], 1),
        "mean_s": float(lat.mean()) if len(lat) else nan,
        "p50_s": float(np.percentile(lat, 50)) if len(lat) else nan,
        "p90_s": float(np.percentile(lat, 90)) if len(lat) else nan,
        "p99_s": float(np.percentile(lat, 99)) if len(lat) else nan,
    }
