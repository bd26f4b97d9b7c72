"""Executor (``serve/engine.py``): share of the token positions that the
window's chunked prefill dispatched which held no prompt token.  Each
run of the compiled prefill step (``_prefill_fn`` in the trace's
executable events) is ``batch_slots x prefill_chunk`` positions wide;
the prompt tokens written are the admissions' (``Executor.prefill``
writes every prompt token but the last).  The program counts the same
(``prefill_tokens`` / ``prefill_slot_tokens`` in ``Server.stats()``).
Moves ``tokens_per_s``."""

PREFILL = "_prefill_fn"


def read(run):
    _, n = run.device_s(run.trace.modules, lambda name: PREFILL in name)
    runs = n / run.trace.n_devices
    if not runs:
        return None
    serve = run.cell.serve
    slots = runs * serve["batch_slots"] * serve["prefill_chunk"]
    written = sum(k for _, k in run.window.prefills)
    return 100.0 * (1.0 - written / slots)
