"""Reduction of a profiler trace to device busy time, executable and
kernel time, and idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes; the rest
are plain functions over ``(name, start_ns, end_ns)`` events, which the
CPU tests drive with synthetic traces.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

#: lines of a TPU device plane: one event per operation, and one per run
#: of a compiled executable
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host span that brackets the measured window
WINDOW_SPAN = "window"
#: the program's own host spans all start so (``docs/serving.md``)
PROGRAM_SPANS = "serve."


@dataclasses.dataclass
class Trace:
    ops: list          # [(name, start_ns, end_ns)] device operations
    modules: list      # [(name, start_ns, end_ns)] executable runs
    host: list         # [(name, start_ns, end_ns)] host spans
    window: tuple      # (start_ns, end_ns) of the measured window
    n_devices: int = 1
    #: [(name, start_ns, end_ns)] the program's ``serve.*`` host spans
    spans: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}"
        )
    return found[0]


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def device_events(data, devices: int = 1):
    """(ops, modules, lines seen) of the device planes ``/device:TPU:0
    ..`` (the first ``devices`` of them) of a ``ProfileData``."""
    ops, modules, seen = [], [], []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        if int(plane.name.rsplit(":", 1)[1]) >= devices:
            continue
        for line in plane.lines:
            seen.append(f"{plane.name}/{line.name}")
            if line.name == OPS_LINE:
                ops += _events(line)
            elif line.name == MODULES_LINE:
                modules += _events(line)
    return ops, modules, seen


def host_events(data, host_names):
    """(host, spans) of a ``ProfileData``: the host events whose names
    are in ``host_names``, and the program's (``PROGRAM_SPANS``)."""
    host, spans = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in _events(line):
                if e[0] in host_names:
                    host.append(e)
                elif e[0].startswith(PROGRAM_SPANS):
                    spans.append(e)
    return host, spans


def load(path: str, host_names, devices: int = 1) -> Trace:
    """Device planes ``/device:TPU:0 ..`` (the first ``devices`` of
    them), the host spans whose names are in ``host_names``, and the
    program's spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, seen = device_events(data, devices)
    host, spans = host_events(data, set(host_names) | {WINDOW_SPAN})
    windows = [e for e in host if e[0] == WINDOW_SPAN]
    if not ops or len(windows) != 1:
        raise ValueError(
            f"trace holds {len(ops)} device operations and {len(windows)} "
            f"'{WINDOW_SPAN}' spans; device lines seen: {seen}"
        )
    host = [e for e in host if e[0] != WINDOW_SPAN]
    return Trace(ops, modules, host, windows[0][1:], devices, spans)


def clip(events, lo: int, hi: int):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merge(intervals):
    """Union of ``[(start, end)]`` as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(trace: Trace) -> float:
    """Union of device operation intervals inside the window, averaged
    over the devices traced."""
    lo, hi = trace.window
    busy = sum(e - s for s, e in merge(
        (s, e) for _, s, e in clip(trace.ops, lo, hi)))
    return busy / trace.n_devices


def idle_gaps(trace: Trace):
    """[(start, end)] stretches of the window with no device operation."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in merge((s, e) for _, s, e in clip(trace.ops, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def time_of(events, match, window):
    """(total ns, count) of the events inside ``window`` whose name
    ``match`` accepts."""
    lo, hi = window
    hits = [(s, e) for n, s, e in clip(events, lo, hi) if match(n)]
    return sum(e - s for s, e in hits), len(hits)


def label_gaps(gaps, host):
    """Idle ns per host span: each gap goes to the innermost host span
    (the latest started) that covers its midpoint, or to ``"none"``."""
    by_name = collections.defaultdict(list)
    for name, s, e in host:
        by_name[name].append((s, e))
    spans = {n: sorted(v) for n, v in by_name.items()}
    starts = {n: [s for s, _ in v] for n, v in spans.items()}
    out = collections.Counter()
    for gs, ge in gaps:
        mid = (gs + ge) // 2
        best, best_start = "none", None
        for name, v in spans.items():
            k = bisect.bisect_right(starts[name], mid) - 1
            # a name's spans may nest only within other names; within
            # one name they follow each other, so the last that started
            # before the midpoint is the only one that can cover it
            if k >= 0 and v[k][1] >= mid:
                if best_start is None or v[k][0] > best_start:
                    best, best_start = name, v[k][0]
        out[best] += ge - gs
    return out


def top(counter, n: int = 10, scale: float = 1e-9):
    return [[k, v * scale] for k, v in counter.most_common(n)]


def leaves(events):
    """The events that hold no other: a loop's event spans its body's
    operations on the same line, and would count them twice."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    return [x for i, x in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= x[2]]


def _skip(text: str, i: int) -> int:
    """Index past one token of HLO text that may nest brackets."""
    depth = 0
    while i < len(text):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    return i


def short_name(op: str) -> str:
    """``"%copy.7 = bf16[8,128]{1,0} copy(...)"`` -> ``"copy.7 copy
    bf16[8,128]"``: the instruction, its kind and its result shape."""
    head, eq, rest = op.partition(" = ")
    if not eq:
        return op[:120]
    end = _skip(rest, 0)
    shape = rest[:end].split("{")[0]
    kind = rest[end + 1:].split("(", 1)[0]
    return f"{head.lstrip('%')} {kind} {shape[:80]}"


def op_totals(trace: Trace):
    """Device ns per operation inside the window, leaves only."""
    out = collections.Counter()
    for n, s, e in clip(leaves(trace.ops), *trace.window):
        out[short_name(n)] += e - s
    return out


def inside(events, runs):
    """The events that start inside one of ``runs`` (disjoint
    ``(start, end)`` intervals, e.g. the runs of one executable)."""
    runs = sorted(runs)
    starts = [s for s, _ in runs]
    out = []
    for ev in events:
        k = bisect.bisect_right(starts, ev[1]) - 1
        if k >= 0 and ev[1] < runs[k][1]:
            out.append(ev)
    return out
