"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything (quick)
    PYTHONPATH=src python -m benchmarks.run --full     # full durations
    PYTHONPATH=src python -m benchmarks.run --only cost,latency

These are host runs of the paper's figures; the accelerator's speed is
measured by the on-chip benchmark (``bench/run.py``).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.compile_cache import use_compile_cache
from benchmarks import (
    availability,
    correlation,
    cost,
    e2e_compare,
    engine_bench,
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
    "jax_engine": jax_engine,        # jit/vmap batched matrix throughput
    "roofline": roofline,            # deliverable (g)
    "token_engine": token_engine,    # request- vs token-level replicas
    "migration": migration,          # grace-period KV migration off/on
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--full", action="store_true",
                    help="full trace durations (slow)")
    args = ap.parse_args(argv)
    use_compile_cache()
    names = (
        [n.strip() for n in args.only.split(",") if n.strip()]
        if args.only
        else list(MODULES)
    )
    failures = []
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
    if failures:
        print("FAILURES:", failures)
        return 1
    print("all benchmarks OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
