"""The training step's optimizer: device self time per step of the ops
in the step program under the engine's ``optimizer`` scope (AdamW and
the cast-down to the compute weights; bench/scopes.py)."""
from bench import scopes


def read(run):
    ms = scopes.train_phases(run)
    return None if ms is None else ms["optimizer"]
