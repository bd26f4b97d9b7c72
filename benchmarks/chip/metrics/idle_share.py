"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device operation intervals / window).  Moves
``tokens_per_s``."""

from benchmarks.chip import tracefile


def read(run):
    return 100.0 * (1.0 - tracefile.busy_ns(run.trace) * 1e-9
                    / run.trace.window_s)
