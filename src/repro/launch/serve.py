"""Production serving driver: SpotHedge-managed fleet + request replay.

    PYTHONPATH=src python -m repro.launch.serve --arch command-r-35b \
        --trace aws-3 --policy spothedge --hours 4

    # or run a declarative service file (paper Listing 1):
    PYTHONPATH=src python -m repro.launch.serve --spec examples/service.yaml

    # or expand a spec's sweep: section into a scenario matrix and run
    # every cell (report JSON lands under artifacts/bench/):
    PYTHONPATH=src python -m repro.launch.serve --spec examples/sweep.yaml \
        --sweep --workers auto

Runs the full control plane (SpotHedge placement + dynamic fallback +
autoscaler + least-loaded LB) against a recorded spot trace with the
roofline-derived data-plane latency model — the §5.1 methodology.  Every
run is a :class:`repro.service.ServiceSpec`; the CLI flags are just a spec
built for you.  ``--engine jax`` with ``--sweep`` plays the whole matrix
as one vmapped program on the accelerator.  examples/serve_llm.py serves
real tokens from in-process JAX engines.
"""

import argparse
import json
import sys

from repro.compile_cache import use_compile_cache
from repro.configs import ARCH_IDS
from repro.core.policy import registered_policies
from repro.service import Service, load_spec
from repro.service.spec import ENGINE_NAMES


def spec_from_args(args: argparse.Namespace) -> dict:
    """The CLI's kwarg soup, expressed as the one true spec dict."""
    return {
        "name": f"serve-{args.arch}",
        "model": args.arch,
        "trace": args.trace,
        "resources": {"instance_type": args.itype},
        "replica_policy": {"name": args.policy},
        "autoscaler": {
            "kind": "load",
            "target": 4,
            "qps_per_replica": args.qps_per_replica,
            "min_replicas": 2,
            "max_replicas": 12,
            "upscale_delay_s": 60.0,
            "downscale_delay_s": 600.0,
        },
        "workload": {"kind": args.workload, "rate_per_s": args.rate,
                     "seed": 11},
        "sim": {
            "duration_hours": args.hours,
            "control_interval_s": 15.0,
            "timeout_s": args.timeout,
            "concurrency": 4,
        },
    }


def _run_sweep(spec, args: argparse.Namespace) -> int:
    """Expand spec.sweep into a ScenarioSuite, run it, save the report."""
    import os

    from repro.experiments import ScenarioSuite

    suite = ScenarioSuite.from_spec(spec)
    print(f"[serve] sweep {spec.name!r}: {len(suite)} scenarios "
          f"({spec.sweep.size if spec.sweep else 1} grid cells)")
    report = suite.run(
        workers=args.workers,
        save_to=os.path.join("artifacts", "bench"),
        progress=True,
    )
    print(report.summary())
    print(f"[serve] report: artifacts/bench/scenario_{suite.name}.json")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="run a service spec file (.yaml/.json); "
                    "other flags are ignored")
    ap.add_argument("--arch", choices=ARCH_IDS, default="command-r-35b")
    ap.add_argument("--trace", default="aws-3")
    ap.add_argument("--policy", default="spothedge",
                    choices=registered_policies())
    ap.add_argument("--workload", default="arena",
                    choices=["poisson", "arena", "maf"])
    ap.add_argument("--itype", default="g5.48xlarge")
    ap.add_argument("--hours", type=float, default=4.0)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--qps-per-replica", type=float, default=0.8)
    ap.add_argument("--timeout", type=float, default=100.0)
    ap.add_argument("--status", action="store_true",
                    help="print the resolved service status as JSON")
    ap.add_argument("--sweep", action="store_true",
                    help="expand the spec's sweep: grid into a scenario "
                    "suite and run every cell")
    ap.add_argument("--workers", default=None, metavar="N|auto",
                    help="run sweep cells in N worker processes "
                    "('auto' = one per CPU); default serial")
    ap.add_argument("--engine", default=None,
                    choices=list(ENGINE_NAMES),
                    help="override sim.engine for this run")
    ap.add_argument("--replica-model", default=None,
                    choices=["request", "token"],
                    help="override sim.replica_model for this run "
                    "(token = continuous batching + TTFT/TPOT/goodput)")
    args = ap.parse_args(argv)
    use_compile_cache()

    from repro.service import SpecError

    try:
        spec = load_spec(args.spec if args.spec else spec_from_args(args))
        if args.engine and spec.sim.engine != args.engine:
            import dataclasses

            spec = dataclasses.replace(
                spec, sim=dataclasses.replace(spec.sim, engine=args.engine)
            )
        if args.replica_model and \
                spec.sim.replica_model != args.replica_model:
            import dataclasses

            spec = dataclasses.replace(
                spec,
                sim=dataclasses.replace(
                    spec.sim, replica_model=args.replica_model
                ),
            )
        if args.sweep:
            return _run_sweep(spec, args)
        if args.workers is not None:
            print("error: --workers requires --sweep (a single service "
                  "run is one cell)", file=sys.stderr)
            return 2
        svc = Service(spec)
        resolved = svc.resolve()
        print(f"[serve] {spec.replica_policy.name} serving "
              f"{resolved.model_config.name} on "
              f"{spec.resources.instance_type}: {len(resolved.requests)} "
              f"requests / {spec.sim.duration_hours:g}h over trace "
              f"{resolved.trace.name} ({len(resolved.zones)} zones)")
        res = svc.run()
    except SpecError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(res.summary())
    if args.status:
        print(json.dumps(svc.status(), indent=1, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
