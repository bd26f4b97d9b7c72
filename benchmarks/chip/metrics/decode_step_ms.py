"""Executor (``serve/engine.py``): device time per run of the compiled
decode step, from the trace's executable events.  Moves
``tokens_per_s``."""

DECODE = "_step_fn"


def read(run):
    s, n = run.device_s(run.trace.modules, lambda name: DECODE in name)
    return s / n * 1e3 if n else None
