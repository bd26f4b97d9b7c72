"""The program's own spans and counters over one traced window of a cell.

    python3 benchmarks/chip/program_trace.py --workload olmo-1b.offline-long \
        --seeds 7,8,9 --seconds 51

The serve loop names its phases with ``serve.`` profiler spans and keeps
integer counters in ``Server.stats()`` (``docs/serving.md``); the
harness hands both to every per-layer reader (``Trace.spans``,
``Window.counters``).  For each seed, in one process: the cell built,
warmed and watched as a benchmark run builds it, and one window served
under the profiler and read as ``run.py --trace 1`` reads it.  One JSON
line per seed on standard output: the decode steps in the window; the
idle seconds by the innermost span that covers them, the harness's or
the program's; the host's turnaround between decode steps and the
padding of the prefill dispatches from the program's counters (the
functions below; ``metrics/host_turnaround_ms.py`` reads the first);
the counters' change; and the cell's per-layer metrics.  A program
without the spans or counters reads None for the turnaround and the
padding.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
if __package__ in (None, ""):
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import harness, tracefile, traffic  # noqa: E402
from benchmarks.chip.cell import load_cell, load_json, metric_reader  # noqa: E402
from benchmarks.chip.peaks import peaks_for  # noqa: E402

DISPATCH = "serve.executor.decode.dispatch"
FETCH = "serve.executor.decode.fetch"
PREFILL = "serve.executor.prefill"
COUNTERS = ("decode_steps", "decode_tokens", "prefill_dispatches",
            "prefill_slot_tokens", "prefill_tokens")


def host_turnaround(spans, window) -> list:
    """ns from the end of decode step k's fetch to the end of step k+1's
    dispatch, for each pair of consecutive steps inside ``window``: the
    host's work on the critical path while the chip has nothing queued.
    A pair with a prefill between its steps is left out (the prefill's
    device time is counted apart)."""
    lo, hi = window
    marks = sorted((s, e, n) for n, s, e in spans
                   if n in (DISPATCH, FETCH, PREFILL) and lo <= s and e <= hi)
    out, fetched = [], None
    for _, e, n in marks:
        if n == FETCH:
            fetched = e
        elif n == PREFILL:
            fetched = None
        elif fetched is not None:
            out.append(e - fetched)
            fetched = None
    return out


def host_turnaround_ms(spans, window) -> float | None:
    """Mean of ``host_turnaround``, in ms; None without decode spans."""
    gaps = host_turnaround(spans, window)
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None


def prefill_pad_share(counters: dict) -> float | None:
    """% of the token positions that prefill dispatched which held no
    prompt token; None when the counters lack the positions or none
    were dispatched."""
    slots = counters.get("prefill_slot_tokens")
    if not slots:
        return None
    return 100.0 * (1.0 - counters["prefill_tokens"] / slots)


def measure(cell, seed: int, seconds: float, peaks: dict) -> dict:
    params, server = harness.build(cell, seed)
    harness.warm(server, cell, seed)
    planned = traffic.generate(cell.traffic, seconds, seed,
                               cell.model["vocab_size"])
    harness.watch(server, cell, planned, seed)
    trace_dir = tempfile.mkdtemp(prefix="chip-trace-")
    jax.profiler.start_trace(trace_dir)
    try:
        win = harness.serve(server, planned, seconds)
    finally:
        jax.profiler.stop_trace()
    del server, params
    gc.collect()
    run = harness.traced(cell, win, trace_dir, peaks)
    shutil.rmtree(trace_dir, ignore_errors=True)
    tr = run.trace
    gaps = tracefile.idle_gaps(tr)
    turn = host_turnaround(tr.spans, tr.window)
    return {
        "seed": seed,
        "decode_steps": len(win.steps),
        "window_s": tr.window_s,
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "host_turnaround_ms": host_turnaround_ms(tr.spans, tr.window),
        "turnaround_pairs": len(turn),
        "turnaround_s": sum(turn) * 1e-9,
        "prefill_pad_share": prefill_pad_share(win.counters),
        "counters": {k: win.counters.get(k) for k in COUNTERS},
        "idle_gaps": tracefile.top(
            tracefile.label_gaps(gaps, tr.host + tr.spans), 16),
        "metrics": {m["name"]: metric_reader(m["name"])(run)
                    for m in cell.per_layer},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = harness.require_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = measure(cell, seed, args.seconds, peaks)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
