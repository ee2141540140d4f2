"""Roofline share of the flash offset-prefill kernel: the least time the
causal attention of every valid prefill token in the window needs (its
FLOPs or its bytes, whichever bounds), over the kernel's device time in
the prefill programs."""
from bench import flops, readers


def read(run):
    if run.trace is None:
        return None
    pk = readers.device_peak(run)
    c = run.cell.hf
    need = 0.0
    for start, n in readers.prefill_chunks(run):
        w = flops.prefill_chunk_work(c, start, n)
        need += flops.roofline_s(w["attn_flops"], w["attn_bytes"], pk)[0]
    t = run.trace.op_s(readers.is_kernel,
                       readers.program_is("prefill_fn"))
    return readers.share(need, t)
