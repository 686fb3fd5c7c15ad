"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything (quick)
    PYTHONPATH=src python -m benchmarks.run --full     # full durations
    PYTHONPATH=src python -m benchmarks.run --only cost,latency

Perf-trajectory tracking: ``--record`` appends one schema-v1 entry per
benchmark (name, wall-clock seconds, git SHA, timestamp) to
``artifacts/bench/trajectory.jsonl``; ``--compare`` gates the run
against each benchmark's previous recorded wall time and fails when one
regresses by more than 20 %.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
import traceback
from typing import Dict, Optional

from repro.compile_cache import use_compile_cache
from benchmarks import (
    availability,
    correlation,
    cost,
    e2e_compare,
    engine_bench,
    engine_speedup,
    jax_engine,
    latency,
    migration,
    roofline,
    sensitivity,
    token_engine,
)

MODULES = {
    "correlation": correlation,      # Fig. 3 + Fig. 5
    "availability": availability,    # Fig. 14a
    "cost": cost,                    # Fig. 14b
    "e2e_compare": e2e_compare,      # Fig. 9/10/13
    "latency": latency,              # Fig. 15
    "sensitivity": sensitivity,      # Fig. 14c/d
    "engine_bench": engine_bench,    # Fig. 6
    "engine_speedup": engine_speedup,  # legacy vs vector matrix timing
    "jax_engine": jax_engine,        # jit/vmap batched matrix throughput
    "roofline": roofline,            # deliverable (g)
    "token_engine": token_engine,    # request- vs token-level replicas
    "migration": migration,          # grace-period KV migration off/on
}


TRAJECTORY_SCHEMA = 1
TRAJECTORY_PATH = os.path.join("artifacts", "bench", "trajectory.jsonl")
REGRESSION_PCT = 20.0


def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        return out.stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def load_baselines(path: str) -> Dict[str, float]:
    """Latest recorded wall time per benchmark name."""
    base: Dict[str, float] = {}
    if not os.path.exists(path):
        return base
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            r = json.loads(line)
            if r.get("metric") == "wall_s":
                base[str(r["benchmark"])] = float(r["value"])
    return base


def record_entry(path: str, name: str, wall_s: float,
                 sha: Optional[str]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    entry = {
        "schema": TRAJECTORY_SCHEMA,
        "benchmark": name,
        "metric": "wall_s",
        "value": round(wall_s, 3),
        "sha": sha,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
    }
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True,
                           separators=(",", ":")) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--full", action="store_true",
                    help="full trace durations (slow)")
    ap.add_argument("--record", action="store_true",
                    help="append wall times to the trajectory log")
    ap.add_argument("--compare", action="store_true",
                    help=f"fail when a benchmark regresses "
                         f">{REGRESSION_PCT:.0f}%% vs its last "
                         f"recorded wall time")
    ap.add_argument("--trajectory", type=str, default=TRAJECTORY_PATH,
                    help="trajectory JSONL path")
    args = ap.parse_args(argv)
    use_compile_cache()
    names = (
        [n.strip() for n in args.only.split(",") if n.strip()]
        if args.only
        else list(MODULES)
    )
    baselines = load_baselines(args.trajectory) if args.compare else {}
    sha = _git_sha() if args.record else None
    failures = []
    regressions = []
    for name in names:
        mod = MODULES[name]
        t0 = time.time()
        print(f"### bench {name} ###", flush=True)
        try:
            mod.run(quick=not args.full)
            wall = time.time() - t0
            print(f"### bench {name} done in {wall:.1f}s ###",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((name, repr(e)))
            continue
        if args.compare and name in baselines:
            base = baselines[name]
            limit = base * (1.0 + REGRESSION_PCT / 100.0)
            if wall > limit:
                regressions.append((name, base, wall))
                print(f"### bench {name} REGRESSED: {wall:.1f}s vs "
                      f"baseline {base:.1f}s "
                      f"(>{REGRESSION_PCT:.0f}%) ###", flush=True)
        if args.record:
            record_entry(args.trajectory, name, wall, sha)
    if failures:
        print("FAILURES:", failures)
        return 1
    if regressions:
        print("REGRESSIONS:",
              [(n, f"{b:.1f}s -> {w:.1f}s") for n, b, w in regressions])
        return 1
    print("all benchmarks OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
