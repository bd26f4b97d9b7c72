"""Model step (``models/``): FLOPs of the work the window needed, over
the traced window times the chips' bf16 peak.  Needed work is every
prompt token prefilled and every token decoded in the window, at its
position (``counts.Model``); padded rows and idle slots count for
nothing.  Moves ``tokens_per_s``."""


def read(run):
    flops = run.needed_flops()
    if not flops:
        return None
    peak = run.peaks["flops_bf16"] * run.cell.chips
    return 100.0 * flops / (run.trace.window_s * peak)
