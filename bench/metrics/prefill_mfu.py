"""Prefill programs' share of the bf16 peak: the model FLOPs of every
valid prefill token in the window (layers, causal attention, and the LM
head once per prompt) over the prefill programs' device time."""
from bench import flops, readers


def read(run):
    if run.trace is None:
        return None
    pk = readers.device_peak(run)["bf16_flops"]
    c = run.cell.hf
    f = sum(flops.prefill_chunk_work(c, s, n)["flops"]
            for s, n in readers.prefill_chunks(run))
    f += readers.prompts_traced(run) * flops.prompt_head_flops(c)
    t = run.trace.program_s(readers.program_is("prefill_fn"))
    return readers.share(f / pk, t)
