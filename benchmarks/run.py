"""Benchmark harness: one module per paper table/figure + roofline readout.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only tableN]

Prints ``name,us_per_call,derived`` CSV (one row per measured cell).
"""
from __future__ import annotations

import argparse
import importlib


# name the CLI exposes → module under benchmarks/. THE registry: the
# --only choices/help derive from these keys, so adding a module here is
# the whole registration (the old hand-written help string had drifted to
# listing 8 of 14 modules).
MODULES = {
    "table1": "table1_taus",
    "table2": "table2_dense",
    "table3": "table3_sparse",
    "table4": "table4_ergo",
    "table5": "table5_vgg",
    "loadbalance": "loadbalance",
    "kernels": "kernels_micro",
    "kernel_blocks": "kernel_blocks",
    "plan_cache": "plan_cache",
    "pyramid_gating": "pyramid_gating",
    "sparse_exec": "sparse_exec",
    "frozen_prefill": "frozen_prefill",
    "mixed_precision": "mixed_precision",
    "autotune": "autotune",
    "scenario_sweep": "scenario_sweep",
    "roofline": "roofline",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU-friendly trimmed sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="alias of --quick (the CI fast lane's spelling)")
    ap.add_argument("--only", default=None, choices=sorted(MODULES),
                    help="run a single module (" + "|".join(MODULES) + ")")
    args = ap.parse_args()
    args.quick = args.quick or args.smoke

    from benchmarks.common import header

    header()
    for name, modname in MODULES.items():
        if args.only and args.only != name:
            continue
        print(f"# --- {name} ---", flush=True)
        importlib.import_module(f"benchmarks.{modname}").run(quick=args.quick)


if __name__ == '__main__':
    main()
