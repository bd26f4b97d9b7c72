"""Executor (``serve/engine.py``): device time of the compiled chunked
prefill step as a share of the traced window.  Every admission blocks
every live decode for this long, so it moves ``tokens_per_s``."""

PREFILL = "_prefill_fn"


def read(run):
    s, n = run.device_s(run.trace.modules, lambda name: PREFILL in name)
    return 100.0 * s / run.trace.window_s if n else None
