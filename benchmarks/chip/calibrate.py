"""Readings that set a cell's correctness limits and its slot count, and
an open-loop knee sweep.

    python3 benchmarks/chip/calibrate.py --workload olmo-1b.offline-long \
        --seeds 1,2,3 --seconds 20 \
        --control float8_e4m3fn:qkv,float8_e4m3fn:all [--slots 32]
    python3 benchmarks/chip/calibrate.py --workload <open-loop cell> \
        --seeds 5 --seconds 51 --rates 0.4,0.6,0.8

For each seed, in one process: weights from the seed, the server built
and warmed as a benchmark run builds it (with ``--slots``, at that many
slots), the cell's traffic served for ``--seconds`` through the same
window with the same logits tap, the watched requests finished, the
server freed, and the numbers of ``check.py`` against the float32
reference; with ``--control``, also those of each control (a lower
precision and the scope it rounds: ``qkv`` or ``all``) at the same
positions.  With ``--rates`` the first seed's server serves one window
per arrival rate instead, finishing what is in flight between them, and
each window's tails and backlog are printed.  One JSON line per reading
on standard output.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if __package__ in (None, ""):
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import check, harness, traffic  # noqa: E402
from benchmarks.chip.cell import load_cell, load_json  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def window(cell, server, seed, seconds, rate=None):
    planned = traffic.generate(cell.traffic, seconds, seed,
                               cell.model["vocab_size"], rate)
    win = harness.serve(server, planned, seconds)
    return win, harness.end_to_end(win)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--rates", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    cell = load_cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    if args.slots:
        cell.config["serve"]["batch_slots"] = args.slots
    devices = harness.require_devices(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.rates:
        params, server = harness.build(cell, seeds[0])
        harness.warm(server, cell, seeds[0])
        for rate in (float(r) for r in args.rates.split(",")):
            win, e2e = window(cell, server, seeds[0], args.seconds, rate)
            waiting = len(win.sent) - len(win.admitted)
            emit(rate=rate, sent=len(win.sent), admitted=len(win.admitted),
                 waiting_at_close=waiting,
                 finished=sum(r.done for r in win.sent), **e2e,
                 late_p95_ms=harness.percentile(win.late, 95) * 1e3)
            harness.drain(server, win, set(win.admitted),
                          harness.DRAIN_S)
        return
    controls = [c.split(":") for c in args.control.split(",")] \
        if args.control else []
    for seed in seeds:
        t0 = time.perf_counter()
        params, server = harness.build(cell, seed)
        harness.warm(server, cell, seed)
        planned = traffic.generate(cell.traffic, args.seconds, seed,
                                   cell.model["vocab_size"])
        tap = harness.watch(server, cell, planned, seed)
        setup = time.perf_counter() - t0
        win = harness.serve(server, planned, args.seconds)
        e2e = harness.end_to_end(win)
        t1 = time.perf_counter()
        harness.release(server, win, tap)
        drain_s = time.perf_counter() - t1
        peak = harness.memory_peak_bytes(devices[: cell.chips])
        del server
        gc.collect()
        watched = [r for r in win.sent if r.rid in tap.rids]
        args_ = (params, cell.family.forward, cell.model, watched, tap.got,
                 cell.serve["max_len"], cell.serve["prefill_chunk"])
        t1 = time.perf_counter()
        got = check.compare(*args_)
        check_s = time.perf_counter() - t1
        for low, scope in controls:
            c = check.compare(*args_, low=low, scope=scope)
            got[f"control_{low}_{scope}"] = {
                k[4:]: v for k, v in c.items() if k.startswith("low_")}
        emit(seed=seed, slots=cell.serve["batch_slots"], **got,
             finished=len(harness.finished(win)), setup_s=setup,
             drain_s=drain_s, check_s=check_s, memory_peak_bytes=peak,
             decode_steps=len(win.steps), admissions=len(win.prefills),
             **e2e)
        # the next seed's server needs this one's weights gone
        del params, tap, win, watched, args_
        gc.collect()


if __name__ == "__main__":
    main()
