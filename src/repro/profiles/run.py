"""CLI: generate step-time profile artifacts.

    PYTHONPATH=src python -m repro.profiles.run \
        --models llama3.2-1b --itype v5e-8 \
        --out artifacts/profiles/cpu-interpret.json

With ``--out`` pointing at an existing table the new entries merge in
(re-profiles supersede old rows; other rows survive), so one artifact can
accumulate the full model × accelerator matrix across runs.  The default
output name encodes provenance: ``artifacts/profiles/<backend>-<mode>.json``.
"""

from __future__ import annotations

import argparse
import os
import sys

import jax

from repro.cluster.catalog import default_catalog
from repro.compile_cache import use_compile_cache
from repro.configs import ARCH_IDS
from repro.profiles.profiler import profile_models
from repro.profiles.schema import (
    DEFAULT_PROFILE_DIR,
    ProfileSchemaError,
    ProfileTable,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.profiles.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--models", nargs="+", default=["llama3.2-1b"],
        help=f"arch ids to profile, or 'all' (available: {ARCH_IDS})",
    )
    ap.add_argument(
        "--itype", default="v5e-8",
        help="catalog instance type whose peaks normalize mfu/mbu",
    )
    ap.add_argument("--out", default=None,
                    help="output JSON path (merged if it exists); "
                    f"default {DEFAULT_PROFILE_DIR}/<backend>-<mode>.json")
    ap.add_argument("--prefill-tokens", type=int, default=256)
    ap.add_argument("--cache-tokens", type=int, default=512)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument(
        "--compiled", action="store_true",
        help="force compiled (non-interpret) kernels; default picks "
        "interpret off-TPU",
    )
    args = ap.parse_args(argv)
    use_compile_cache()

    models = list(args.models)
    if models == ["all"]:
        models = list(ARCH_IDS)
    unknown = [m for m in models if m not in ARCH_IDS]
    if unknown:
        ap.error(f"unknown models {unknown}; available: {ARCH_IDS}")

    catalog = default_catalog()
    try:
        itype = catalog.instance_type(args.itype)
    except KeyError:
        known = sorted(t.name for t in catalog.instance_types)
        ap.error(f"unknown --itype {args.itype!r}; catalog has {known}")

    interpret = False if args.compiled else None
    table = profile_models(
        models, itype,
        prefill_tokens=args.prefill_tokens,
        cache_tokens=args.cache_tokens,
        batch=args.batch,
        repeats=args.repeats,
        interpret=interpret,
    )

    out = args.out
    if out is None:
        out = os.path.join(
            DEFAULT_PROFILE_DIR, f"{table.backend}-{table.mode}.json"
        )
    if os.path.exists(out):
        try:
            prior = ProfileTable.load(out)
        except ProfileSchemaError as e:
            # never clobber rows we cannot read — measurements are not
            # reproducible for free on another machine
            print(
                f"error: existing table {out} cannot be merged ({e}); "
                "pass a fresh --out path or fix/remove the file",
                file=sys.stderr,
            )
            return 1
        prior.merge(table)
        table.entries = prior.entries
    table.jax_version = jax.__version__
    table.save(out)

    for key, e in sorted(table.entries.items()):
        print(
            f"{key:40s} prefill {e.prefill_flops_per_s:10.3e} FLOP/s "
            f"(mfu {e.mfu_prefill:8.2e})  decode "
            f"{e.decode_bytes_per_s:10.3e} B/s (mbu {e.mbu_decode:8.2e})"
        )
    print(f"wrote {out} ({len(table.entries)} entries, "
          f"{table.backend}/{table.mode})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
