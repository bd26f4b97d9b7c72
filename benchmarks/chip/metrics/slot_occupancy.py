"""Scheduler (``serve/scheduler.py``): live slots per decode step over
``batch_slots``, averaged over the window's decode steps (a count the
harness reads from the slot table at each step).  Moves
``tokens_per_s``."""


def read(run):
    steps = run.window.steps
    if not steps:
        return None
    slots = run.cell.serve["batch_slots"]
    return 100.0 * sum(len(f) for f in steps) / (len(steps) * slots)
