"""One run of one cell: set-up, the measured window, the numbers.

The window drives the program's own serving entry, ``Server.add_request``
and ``Server.step``, with the placement the planner picks.  Requests are
sent when due (open loop); each is timed from its due time.  The
harness wraps the server's executor instance (``engine.prefill`` and
``engine.decode``) to stamp admissions and record the live cache fills
of every decode step, and brackets each layer's call in a host span
that the profiler's trace carries.  Its ``logits_tap`` keeps the logits
the window serves to a few requests drawn from the seed, which the
check holds to the float32 reference once the window has closed
(``check.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from . import check, counts, tracefile, traffic, weights
from .cell import Cell, arch_config, metric_reader
from .peaks import peaks_for

HOST_SPANS = ("Server.step", "Executor.prefill", "Executor.decode",
              "generator", "wait_arrival")
#: warm-up requests use rids the window never does
WARM_RID = 1 << 40
#: the longest a run waits after the close for requests in flight
DRAIN_S = 120.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: programs traced or compiled so far in this process; the window should
#: add none
_COMPILES = [0]


def _count_compiles(event: str, duration: float, **kw) -> None:
    if event in ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/backend_compile_duration"):
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


@dataclasses.dataclass
class Window:
    """What the host saw in the measured window (``perf_counter`` s)."""

    t0: float = 0.0           # the window opens
    t1: float = 0.0           # and closes, --seconds later
    t_exit: float = 0.0       # the step that straddles the close ends
    planned: list = dataclasses.field(default_factory=list)
    sent: list = dataclasses.field(default_factory=list)       # Requests
    stamps: dict = dataclasses.field(default_factory=dict)     # rid -> [s]
    admitted: dict = dataclasses.field(default_factory=dict)   # rid -> s
    prefills: list = dataclasses.field(default_factory=list)   # [(rid, n)]
    steps: list = dataclasses.field(default_factory=list)      # [fills]
    late: list = dataclasses.field(default_factory=list)
    failed: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def due_in_window(self):
        """(rid, due) of every planned request due before the close,
        sent or not."""
        return [(k, self.t0 + p.due_s) for k, p in enumerate(self.planned)
                if self.t0 + p.due_s < self.t1]

    def stamps_by_close(self, rid: int) -> list:
        """Times of the request's tokens received by the close."""
        return [t for t in self.stamps.get(rid, ()) if t <= self.t1]


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads."""

    cell: Cell
    model: counts.Model
    window: Window
    trace: tracefile.Trace | None
    peaks: dict

    def device_s(self, events, match) -> tuple[float, int]:
        ns, n = tracefile.time_of(events, match, self.trace.window)
        return ns * 1e-9 / self.trace.n_devices, n

    def kernel_s(self, executable: str, output: str) -> tuple[float, int]:
        """Device time of the Mosaic kernel calls whose result is
        ``output`` (an HLO shape such as ``bf16[20,16,1,128]``) inside
        runs of the executable named ``executable``."""
        runs = [(s, e) for n, s, e in self.trace.modules if executable in n]
        calls = [ev for ev in tracefile.inside(self.trace.ops, runs)
                 if "tpu_custom_call" in ev[0]
                 and f" = {output}" in ev[0]]
        return self.device_s(calls, lambda name: True)

    def needed_flops(self) -> int:
        """Prompt tokens prefilled plus tokens decoded in the window, at
        their positions; padding counts for nothing."""
        m, w = self.model, self.window
        flops = sum(m.prefill_flops(n) for _, n in w.prefills)
        flops += sum(m.decode_flops(f) for fills in w.steps for f in fills)
        return flops


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def require_devices(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    return devs


def build(cell: Cell, seed: int):
    """(params, server) with seeded weights, through the planner."""
    from repro.models.model_zoo import ModelBundle
    from repro.serve import ServeConfig, Server

    t0 = time.perf_counter()
    bundle = ModelBundle(arch_config(cell.config))
    shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    params = jax.block_until_ready(weights.make_params(shapes, seed))
    t1 = time.perf_counter()
    s = cell.serve
    server = Server(
        bundle,
        ServeConfig(batch_slots=s["batch_slots"], max_len=s["max_len"],
                    prefill_chunk=s["prefill_chunk"], policy=None),
        params,
    )
    log(f"planner picked {server.policy.name} for {cell.config['arch']} "
        f"({s['batch_slots']} slots x {s['max_len']} positions, prefill "
        f"chunk {s['prefill_chunk']})")
    log(f"set-up: weights {t1 - t0:.3f} s, server build "
        f"{time.perf_counter() - t1:.3f} s")
    return params, server


def warm(server, cell: Cell, seed: int) -> None:
    """Run the cell's one prefill shape and one decode shape once, with
    the logits tap on, so that its gather compiles here too."""
    from repro.serve import Request

    rng = np.random.default_rng([seed, 2])
    s = cell.serve
    server.engine.logits_tap = check.Tap(server.table, [WARM_RID],
                                         cell.config["correct"]["sample_rows"])
    server.add_requests([
        Request(rid=WARM_RID + i,
                prompt=rng.integers(0, cell.model["vocab_size"],
                                    s["prefill_chunk"] + 1).astype(np.int32),
                max_new_tokens=2)
        for i in range(s["batch_slots"])
    ])
    server.run_until_done()
    server.engine.logits_tap.flush()
    server.engine.logits_tap = None


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def serve(server, planned, seconds: float) -> Window:
    from repro.serve import QueueFullError, Request

    now, span = time.perf_counter, jax.profiler.TraceAnnotation
    win = Window(planned=planned)
    eng, table = server.engine, server.table
    prefill_call, decode_call = eng.prefill, eng.decode

    def prefill(new, tbl):
        t = now()
        rids = [tbl.slots[i] for i, _ in new]
        with span("Executor.prefill"):
            prefill_call(new, tbl)
        for rid, (_, prompt) in zip(rids, new):
            win.admitted.setdefault(rid, t)
            win.prefills.append((rid, len(prompt) - 1))

    def decode(state):
        win.steps.append([int(table.lengths[i])
                          for i in table.active_slots()])
        with span("Executor.decode"):
            return decode_call(state)

    def on_token(req, tok):
        if tok >= 0:
            win.stamps[req.rid].append(now())

    eng.prefill, eng.decode = prefill, decode
    try:
        k, n = 0, len(planned)
        with span(tracefile.WINDOW_SPAN):
            win.t0 = now()
            t_end = win.t0 + seconds
            while (t := now()) < t_end:
                if k < n and win.t0 + planned[k].due_s <= t:
                    with span("generator"):
                        while k < n and win.t0 + planned[k].due_s <= t:
                            p = planned[k]
                            req = Request(rid=k, prompt=p.prompt,
                                          max_new_tokens=p.max_new_tokens,
                                          on_token=on_token)
                            win.stamps[k] = []
                            try:
                                server.add_request(req)
                                win.sent.append(req)
                            except (QueueFullError, ValueError):
                                win.failed += 1
                            win.late.append(t - win.t0 - p.due_s)
                            k += 1
                if server.has_work():
                    with span("Server.step"):
                        server.step()
                else:
                    wake = win.t0 + planned[k].due_s if k < n else t_end
                    with span("wait_arrival"):
                        time.sleep(max(0.0, min(wake, t_end) - now()))
            win.t1, win.t_exit = t_end, now()
    finally:
        del eng.prefill, eng.decode
    return win


def finished(win: Window) -> list:
    return [r for r in win.sent if r.done and not r.cancelled]


def drain(server, win: Window, rids, limit_s: float) -> None:
    """After the close, finish the watched requests ``rids``, admitting
    no request that is not watched, so that the check compares whole
    requests however long one takes.  Nothing here is measured."""
    watched = [r for r in win.sent if r.rid in rids]
    if all(r.done for r in watched):
        return
    for req in win.sent:
        if req.rid not in win.admitted and req.rid not in rids:
            req.cancel()
    t_end = time.perf_counter() + limit_s
    while (not all(r.done for r in watched) and server.has_work()
           and time.perf_counter() < t_end):
        server.step()


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(win: Window) -> dict:
    """Over the window from its open to its close, --seconds later:
    tokens received per second, and percentiles of time to first token
    (every request due in the window, unserved ones at their time so
    far) and of the gaps between a request's tokens (a pending gap up to
    the close).  What the step that straddles the close delivers after
    it counts for nothing."""
    n_tok = sum(len(win.stamps_by_close(rid)) for rid in win.stamps)
    ttft = []
    for rid, due in win.due_in_window():
        s = win.stamps_by_close(rid)
        ttft.append((s[0] if s else win.t1) - due)
    itl = []
    for req in win.sent:
        s = win.stamps_by_close(req.rid)
        itl += list(np.diff(s))
        ended = req.done and req.finished_s <= win.t1
        if s and not ended:
            itl.append(win.t1 - s[-1])
    return {
        "tokens_per_s": n_tok / win.seconds,
        "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
        "ttft_p95_ms": percentile(ttft, 95) * 1e3 if ttft else None,
        "itl_p95_ms": percentile(itl, 95) * 1e3 if itl else None,
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def watch(server, cell: Cell, planned, seed: int) -> check.Tap:
    """Put the logits tap on the server for the requests the check will
    compare (``check.watch_set``)."""
    rows = cell.config["correct"]["sample_rows"]
    rids = check.watch_set(planned, seed, cell.serve["batch_slots"], rows)
    tap = check.Tap(server.table, rids, rows)
    server.engine.logits_tap = tap
    return tap


def release(server, win: Window, tap: check.Tap) -> None:
    """Finish the watched requests, then take the tap off the server."""
    drain(server, win, tap.rids, DRAIN_S)
    tap.flush()
    server.engine.logits_tap = tap.table = None


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compare(cell: Cell, params, win: Window, tap: check.Tap) -> dict:
    """The numbers compared, each with its limit."""
    lim = cell.config["correct"]
    done = finished(win)
    missing = sum(r.max_new_tokens - len(r.out_tokens) for r in done)
    watched = [r for r in win.sent if r.rid in tap.rids]
    got = check.compare(params, cell.model, watched, tap.got,
                        cell.serve["max_len"], cell.serve["prefill_chunk"])
    log(f"check: {len(watched)} watched requests ({sum(r.done for r in watched)}"
        f" finished), {got['tokens']} served tokens and {got['dispatches']} "
        f"dispatches' logits against the float32 reference; fewest slots "
        f"live at a compared dispatch: {got['live_min']}")
    out = {k: {"value": got[k], "limit": lim[k]} for k in check.NUMBERS}
    out["dispatch_mismatch"] = {"value": got["dispatch_mismatch"],
                                "limit": 0}
    out["missing_tokens"] = {"value": missing, "limit": 0}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        started: float, require_chip: bool = True,
        keep_trace: str | None = None) -> dict:
    """The result line of one run (see ``run.py``)."""
    from repro.launch.compile_cache import enable_compile_cache

    devices = (require_devices(cell.chips) if require_chip
               else jax.devices())
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if require_chip else None
    log(f"compile cache: {enable_compile_cache()}")
    # every program of the run, however quick to compile, is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    params, server = build(cell, seed)
    t = time.perf_counter()
    warm(server, cell, seed)
    log(f"set-up: warm-up {time.perf_counter() - t:.3f} s")
    planned = traffic.generate(cell.traffic, seconds, seed,
                               cell.model["vocab_size"])
    tap = watch(server, cell, planned, seed)
    setup_s = time.perf_counter() - started
    log(f"set-up {setup_s:.3f} s; {len(planned)} requests planned")

    trace_dir = keep_trace or (tempfile.mkdtemp(prefix="chip-trace-")
                               if trace else None)
    if trace:
        jax.profiler.start_trace(trace_dir)
    compiles = _COMPILES[0]
    try:
        win = serve(server, planned, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"traces and compiles inside the window: {_COMPILES[0] - compiles}")
    log(f"window {win.seconds:.3f} s (loop ended "
        f"{win.t_exit - win.t1:.3f} s after the close): {len(win.sent)} sent, "
        f"{sum(r.done for r in win.sent)} finished, "
        f"{sum(len(s) for s in win.stamps.values())} tokens, "
        f"{len(win.steps)} decode steps, {len(win.prefills)} admissions; "
        f"generator late p95 "
        f"{percentile(win.late, 95) * 1e3 if win.late else 0:.3f} ms")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": None}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    out = {}
    breakdown = None
    if trace:
        tr = tracefile.load(tracefile.find_xplane(trace_dir), HOST_SPANS,
                            cell.chips)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Run(cell, counts.Model.from_config(cell.model), win, tr,
                  peaks)
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                out[m["name"]] = value
        device["busy_s"] = tracefile.busy_ns(tr) * 1e-9
        device["window_s"] = tr.window_s
        breakdown = {
            "device_ops": tracefile.top(tracefile.op_totals(tr)),
            "idle_gaps": tracefile.top(
                tracefile.label_gaps(tracefile.idle_gaps(tr), tr.host)),
        }
    else:
        e2e = end_to_end(win)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                out[m["name"]] = e2e[m["name"]]

    t = time.perf_counter()
    release(server, win, tap)
    log(f"after the close: {time.perf_counter() - t:.1f} s finishing "
        f"the watched requests for the check")
    device["memory_peak_bytes"] = memory_peak_bytes(devices[: cell.chips])
    del server
    gc.collect()     # the server holds reference cycles; free its cache
    compared = compare(cell, params, win, tap)
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    line = {
        "correct": correct,
        "attempted": len(win.sent) + win.failed,
        "failed": win.failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in out.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line
