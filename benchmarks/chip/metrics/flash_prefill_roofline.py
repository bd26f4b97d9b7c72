"""Kernels (``kernels/flash_attention.py``): the least time the chunked
prefill attention kernel's work could take, over its device time in the
window.  Work is the causal attention of the prompt rows actually
admitted, chunk by chunk; padded rows of the (slots x chunk) dispatch
count for nothing.  The bound that binds (FLOPs or bytes) is taken.
In the offline cells each wave of admissions fills every slot.  Moves
``tokens_per_s``."""

from benchmarks.chip.counts import prompt_chunks

DTYPES = {"bfloat16": "bf16", "float32": "f32"}


def read(run):
    # the kernel's result: (slots x q heads, chunk, head dim)
    serve, m = run.cell.serve, run.model
    chunk = serve["prefill_chunk"]
    out = (f"{DTYPES[run.cell.model['dtype']]}"
           f"[{serve['batch_slots'] * m.n_heads},{chunk},{m.d_head}]")
    s, n = run.kernel_s("_prefill_fn", out)
    if not n or not run.window.prefills:
        return None
    chunks = [c for _, k in run.window.prefills for c in prompt_chunks(k, chunk)]
    flops, nbytes = run.model.flash_prefill_work(chunks)
    p = run.peaks
    least = max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / s
