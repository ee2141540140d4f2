"""The training step's forward pass: device self time per step of the
ops in the step program under the engine's ``fwd_bwd`` scope whose
op_name holds no ``transpose(`` (bench/scopes.py)."""
from bench import scopes


def read(run):
    ms = scopes.train_phases(run)
    return None if ms is None else ms["forward"]
