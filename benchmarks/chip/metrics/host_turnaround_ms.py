"""Scheduler (``serve/scheduler.py``): the host's work on the critical
path between decode steps, from the program's spans: the mean time from
the end of step k's token fetch (``serve.executor.decode.fetch``) to the
end of step k+1's dispatch (``serve.executor.decode.dispatch``), over the
pairs of steps inside the window with no prefill between them
(``program_trace.host_turnaround``).  The chip has nothing queued for
that long.  Moves ``tokens_per_s``."""

from benchmarks.chip import program_trace


def read(run):
    return program_trace.host_turnaround_ms(run.trace.spans, run.trace.window)
