"""Kernels (``kernels/decode_attention.py``): the least time the decode
attention kernel's work could take, over its device time in the window.
Work is, per layer and live row, its query, K/V of its live positions
and its output (bytes), and its causal FLOPs; dead cache tiles and idle
slots count for nothing.  Moves ``tokens_per_s``."""

DTYPES = {"bfloat16": "bf16", "float32": "f32"}


def read(run):
    # the kernel's result: (slots, kv heads, q heads per kv head, head dim)
    m = run.model
    out = (f"{DTYPES[run.cell.model['dtype']]}[{run.cell.serve['batch_slots']},"
           f"{m.n_kv_heads},{m.n_heads // m.n_kv_heads},{m.d_head}]")
    s, n = run.kernel_s("_step_fn", out)
    steps = run.window.steps
    if not n or not steps:
        return None
    p = run.peaks
    fills = [f for step in steps for f in step]
    flops = sum(m.attn_flops(f, 1) for f in fills)
    nbytes = m.flash_decode_bytes(fills)
    least = max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / s
