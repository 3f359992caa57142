"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig7,fleet]

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = {
    "api": "api_smoke",
    "fig6": "fig6_detection",
    "fig7": "fig7_compare",
    "fig8": "fig8_flip",
    "leakage": "leakage",
    "privacy": "privacy_tradeoff",
    "ablations": "ablations",
    "comm": "comm_efficiency",
    "net": "net_sweep",
    "fleet": "fleet_scale",
    "async": "async_scale",
    "roofline": "roofline_table",
    "obs": "obs_smoke",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset: "
                    + ",".join(MODULES))
    args = ap.parse_args()
    wanted = [w for w in args.only.split(",") if w] or list(MODULES)
    print("name,us_per_call,derived")
    failures = 0
    for key in wanted:
        mod_name = MODULES[key]
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            mod.run()
        except Exception:
            failures += 1
            print(f"{key},0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
