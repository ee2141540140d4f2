"""Roofline share of the decode attention kernel: the least time the
attention of every slot's valid cache in each decode step of the window
needs, over the kernel's device time in the decode programs."""
from bench import flops, readers


def read(run):
    if run.trace is None:
        return None
    pk = readers.device_peak(run)
    need = sum(flops.roofline_s(w["attn_flops"], w["attn_bytes"], pk)[0]
               for w in readers.decode_work(run))
    t = run.trace.op_s(readers.is_kernel, readers.program_is("decode_fn"))
    return readers.share(need, t)
