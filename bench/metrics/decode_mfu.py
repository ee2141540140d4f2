"""Whole decode step's roofline share: for each decode step of the
window, the larger of its model FLOPs over the peak FLOP/s and its bytes
(every weight once, each slot's valid keys and values) over the peak
bandwidth, summed, over the decode programs' device time."""
from bench import flops, readers


def read(run):
    if run.trace is None:
        return None
    pk = readers.device_peak(run)
    need = sum(flops.roofline_s(w["flops"], w["bytes"], pk)[0]
               for w in readers.decode_work(run))
    t = run.trace.program_s(readers.program_is("decode_fn"))
    return readers.share(need, t)
