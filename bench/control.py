"""Readings that set a cell's limits: the program's numbers and the
control's, seed after seed, in one process on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed: set up as a run does, run a window of ``--seconds``, free
the program's state, then print one JSON line with the numbers ``check``
compares (``program``) and the same numbers with the reference computed
one precision below the configuration's in the program's place
(``control``).  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.use_cache()
    c = harness.load_cell(args.workload)
    harness.find_chips(c["cell"]["chips"])
    mod = importlib.import_module(f"drivers.{c['config']['kind']}")
    for seed in args.seeds:
        d = mod.Driver(c["config"], c["traffic"], seed, c["limits"])
        d.setup()
        d.window(args.seconds)
        d.free()
        program = {k: v["value"] for k, v in d.check().items()}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": d.control()}),
              flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
