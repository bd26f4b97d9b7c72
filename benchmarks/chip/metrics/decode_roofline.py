"""Model step (``models/``): the least time the decode steps of the window
could take on the chip, over their device time.  The least time is the
larger of needed FLOPs over peak and needed bytes over HBM bandwidth;
needed bytes are every weight once, K/V of each live row's positions and
the new row written (``counts.Model.decode_step_bytes``).  Moves
``tokens_per_s``."""

DECODE = "_step_fn"


def read(run):
    s, n = run.device_s(run.trace.modules, lambda name: DECODE in name)
    steps = run.window.steps
    if not n or not steps:
        return None
    m, p = run.model, run.peaks
    flops = sum(m.decode_flops(f) for fills in steps for f in fills)
    nbytes = sum(m.decode_step_bytes(fills) for fills in steps)
    least = max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / s
