"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name in
``BENCHMARK.json`` at the root of the checkout; everything else that
belongs to them lives in files named after them:

* ``bench/configs/<config>.json`` — the configuration, with its ``kind``;
* ``bench/drivers/<kind>.py``     — the driver of that kind of configuration;
* ``bench/traffic/<traffic>.json`` — the traffic mix the driver reads;
* ``bench/limits/<cell>.json``    — the limit of each number compared
  with the reference;
* ``bench/metrics/<metric>.py``   — the reader of one per-layer metric.

The run sets up (weights or inputs from ``--seed``, every program the
window uses compiled and run once), measures for ``--seconds``, reads the
device's peak memory, frees the program's state, and compares what the
window produced with the plain reference.  With ``--trace 1`` the window
runs under the profiler (a driver may trace a shorter one, as its
``TRACED_SECONDS`` says) and the line carries the per-layer metrics, the
device's busy time and a breakdown; otherwise the end-to-end metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  The last line of standard output is the result as JSON;
the last lines of standard error give each compared number beside its
limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, so only a cell's first run there compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(SystemExit):
    def __init__(self, msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(3)


def use_cache() -> None:
    """The program's sources on the path, JAX's compilation cache at its
    fixed place in the checkout, libtpu's logs off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_cell(name: str) -> dict:
    """The cell's entry, its configuration and traffic, its limits and
    the metric entries of BENCHMARK.json that it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "limits", name + ".json")) as f:
        limits = json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def find_chips(n: int):
    """The first ``n`` TPU devices; none, or too few, ends the run."""
    import jax

    from core import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    peaks.of(devices[0].device_kind)   # a device with no peaks is an error
    return devices[:n]


def read_metric(name: str, ctx: dict):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def run(args, devices, c=None) -> dict:
    """Set up, measure, check; returns the result line's object.  ``c``
    stands in for the cell's files (``load_cell``'s form) where given."""
    import jax

    from core import trace as tr

    c = c or load_cell(args.workload)
    driver_mod = importlib.import_module(f"drivers.{c['config']['kind']}")
    driver = driver_mod.Driver(c["config"], c["traffic"], args.seed,
                               c["limits"])
    driver.setup()
    setup_s = time.perf_counter() - T0

    trace = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # host spans come from the harness's annotations; tracing every
        # Python call would slow the host path it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        seconds = (args.seconds if driver.TRACED_SECONDS is None
                   else driver.TRACED_SECONDS)
        with jax.profiler.trace(TRACE_DIR, profiler_options=options):
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                result = driver.window(seconds)
        t0 = time.perf_counter()
        trace = tr.load(TRACE_DIR, len(devices), driver.ANNOTATIONS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        print(f"bench: trace read in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
    else:
        result = driver.window(args.seconds)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    driver.free()
    checks = driver.check()
    correct = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in checks.values())

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    metrics = {}
    if trace is None:
        values = dict(result["metrics"], setup_s=setup_s)
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        window_s = driver.readings["window_s"]
        ctx = {"readings": driver.readings, "trace": trace,
               "config": c["config"], "traffic": c["traffic"],
               "kind": d0.device_kind, "window_s": window_s}
        for m in c["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        line["breakdown"] = trace.breakdown()
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_cache()
    cell = load_cell(args.workload)["cell"]
    devices = find_chips(cell["chips"])
    line = run(args, devices)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
