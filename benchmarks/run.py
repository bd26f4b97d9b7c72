"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--only NAME]`` — each module prints
``name,us_per_call,derived`` CSV rows.  Mapping to the paper (also in
DESIGN.md §6):

  bench_datapath_bounds   Fig. 3 + Table II (+ hardware constants)
  bench_membw             Figs. 2, 7, 8
  bench_copy              Figs. 5, 9, 10
  bench_latency           Figs. 11, 12
  bench_pingpong          Fig. 13
  bench_internode         Fig. 14
  bench_gemm              Figs. 15, 16 + Table III
  bench_llm_inference     Fig. 17
  bench_collectives       Figs. 18, 19
  bench_managed_vs_system Fig. 4
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "bench_datapath_bounds",
    "bench_membw",
    "bench_copy",
    "bench_latency",
    "bench_pingpong",
    "bench_internode",
    "bench_gemm",
    "bench_llm_inference",
    "bench_collectives",
    "bench_managed_vs_system",
]

#: modules centered on the datapath model — the CI smoke-check mode.
#: ``--analytic`` runs exactly these.  bench_datapath_bounds is pure
#: analysis on one device; when >= 2 devices are visible it additionally
#: times the measured donor column (peer/remote gather + stream).
ANALYTIC_MODULES = [
    "bench_datapath_bounds",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single module")
    ap.add_argument(
        "--analytic", action="store_true",
        help="datapath-model smoke modules only (adds the measured donor "
             "column when >= 2 devices are visible)",
    )
    ap.add_argument(
        "--calibration", default=None, metavar="PATH",
        help="activate a measurement-calibrated hardware model from this "
             "calibration.json (created by tools/calibrate.py) so every "
             "analytic row reports both spec and calibrated bounds",
    )
    args = ap.parse_args()

    cal_path = args.calibration
    if cal_path is not None:
        from repro.core.calibration import Calibration
        from repro.core.hardware import set_active_system

        cal = Calibration.load(cal_path)
        set_active_system(cal.apply())
        print(f"# calibration: {cal_path} (backend={cal.backend}, "
              f"{len(cal.terms)} measured terms)")

    if args.only:
        mods = [args.only]
    elif args.analytic:
        mods = ANALYTIC_MODULES
    else:
        mods = MODULES
    failures = 0
    for name in mods:
        print(f"# ==== {name} ====")
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},0.00,FAILED")
        print(f"# {name} done in {time.time()-t0:.1f}s")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
