"""Arithmetic shared by the per-layer metric readers (bench/metrics).

A reader returns None where its run has nothing to read: no trace, no
device time in the program it reads, or no work of that kind in the
window.  It never returns 0 for a share of a roofline or a peak.
"""
from __future__ import annotations

from typing import Callable, Optional

from bench import flops
from bench.peaks import peak

def is_kernel(name: str) -> bool:
    """The Pallas calls carry no name of their own: in the trace a kernel
    is a custom call to ``tpu_custom_call``, told from the others by the
    jitted program that holds it (prefill_fn, decode_fn)."""
    return name.endswith(" tpu_custom_call")


def program_is(fn_name: str) -> Callable[[str], bool]:
    """Matches the trace's module events of the jitted ``fn_name``."""
    return lambda prog: prog == f"jit_{fn_name}" or prog.startswith(
        f"jit_{fn_name}.")


def device_peak(run):
    return peak(run.devices[0].device_kind)


def share(work_s: float, device_s: float) -> Optional[float]:
    """Percent of a peak: least possible time over the time taken."""
    if device_s <= 0 or work_s <= 0:
        return None
    return 100.0 * work_s / device_s


def traced(run, t: float) -> bool:
    """Whether a record stamped ``t`` seconds into the measured window
    falls in its traced part."""
    return run.records["trace_from_s"] <= t <= run.window_s


def prefill_chunks(run):
    """(start, n_valid) of every prefill chunk admitted in the traced
    part of the window."""
    c = run.cell.mix["prefill_chunk"]
    for t, plen in run.records["served"].admitted:
        if traced(run, t):
            for start in range(0, plen, c):
                yield start, min(c, plen - start)


def prompts_traced(run) -> int:
    return sum(1 for t, _ in run.records["served"].admitted
               if traced(run, t))


def decode_work(run):
    """decode_step_work of each decode step in the traced part of the
    window."""
    c = run.cell.hf
    for t, lengths in run.records["served"].decode_lengths:
        if traced(run, t):
            yield flops.decode_step_work(c, lengths)
