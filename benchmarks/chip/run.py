"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload olmo-1b.offline-long \
        --seed 7 --seconds 30 --trace 0

Loads and warms up the cell named in ``BENCHMARK.json`` (weights from
``--seed``, the server built through the planner, its one prefill and
one decode shape run once), serves the cell's traffic for ``--seconds``
and checks a sample of what it served against the float32 reference.
With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the window runs under the profiler and the metrics are
the per-layer ones, read from the trace.  Earlier lines go to standard
error, ending with each compared number beside its limit; the last line
of standard output is one JSON object.  Exits non-zero, printing no
result, without a TPU or with fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if __package__ in (None, ""):
    # run as a script: import the harness as a package, and the program
    # from the checkout's src/, never the script's own directory
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.cell import load_cell, load_json  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    cell = load_cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       started=STARTED, keep_trace=args.keep_trace)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
