"""Paper §5.1 placement: the first (slowest-interconnect) cut carries the
highest Theorem-1 weight, so the solver should put the cheapest
conversion pattern — data parallelism over the batch — on the `pod` axis
of the multi-pod mesh, and reserve model-style cuts for the fast ICI
axes.  Validated on the dry-run's multi-pod plans."""
import pytest


def _plan(arch):
    """The dry-run's train_4k plan on the two-pod mesh (solved here when
    the plan cache does not hold it)."""
    from repro.configs.base import SHAPES, get_arch
    from repro.launch.compile import solve_plan
    return solve_plan(get_arch(arch), SHAPES["train_4k"], multi_pod=True)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "qwen2.5-32b",
                                  "zamba2-2.7b", "musicgen-large"])
def test_pod_axis_is_batch_cut_for_training(arch):
    rec = _plan(arch)
    x_cuts = rec["role_cuts"]["x"]
    assert x_cuts.get("pod") in ("batch", "seq"), x_cuts


@pytest.mark.parametrize("arch", ["llama3.2-3b", "qwen2.5-32b"])
def test_weights_not_cut_across_pods(arch):
    """Weight shards should not straddle the slow DCN tier."""
    rec = _plan(arch)
    for role in ("wq", "wo", "w_gate", "w_down"):
        cuts = rec["role_cuts"].get(role, {})
        assert cuts.get("pod") is None, (role, cuts)


def test_per_axis_costs_recorded():
    rec = _plan("llama3.2-3b")
    assert len(rec["per_axis_bytes"]) == 3      # pod, data, model
    assert rec["total_bytes"] >= 0
