"""Model FLOP utilization of the training step on the device: forward
and backward FLOPs per token (recomputation not counted) x the tokens of
the step executions in the traced window, over the window's length x the
chips' summed bf16 peak."""
from bench import flops, readers


def read(run):
    if run.trace is None:
        return None
    mix = run.cell.mix
    step = flops.train_flops_per_token(run.cell.hf, mix["seq_len"]) * (
        mix["batch"] * mix["seq_len"])
    calls = run.trace.program_calls(readers.program_is("step_fn"))
    pk = readers.device_peak(run)["bf16_flops"]
    return readers.share(calls * step / (run.cell.chips * pk),
                         run.trace.window_s)
