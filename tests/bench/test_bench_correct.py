"""What decides ``correct``, at a size a test run holds: the program's
readings sit under each tiny cell's limits, the control (the reference
computed in float8, put in the program's place) and each planted fault
fail one of them, and a whole run with its timed path broken underneath
comes out not correct."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import run_cell

from bench import calibrate, harness

SEEDS = [1, 2, 3]


def _cell(root, workload, seconds=2.0):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    return harness.load_cell(spec, workload, SEEDS[0], seconds, False,
                             root=str(root))


def _fails(readings, limits):
    return any(v > limits[k] for k, v in readings.items())


@pytest.mark.parametrize("workload", ["train.tiny", "serve.tiny"])
def test_control_and_faults_fail_program_passes(tiny_root, workload):
    cell = _cell(tiny_root, workload)
    lines = []
    getattr(calibrate, cell.mix["kind"])(cell, jax.devices()[:1], SEEDS,
                                         lines.append, len(SEEDS))
    assert [r["seed"] for r in lines] == SEEDS
    for r in lines:
        assert not _fails(r["program"], cell.limits), r
        assert _fails(r["control"], cell.limits), r
        fault = "fault_half_batch" if workload == "train.tiny" \
            else "fault_token"
        assert _fails(r[fault], cell.limits), r


def _keep_state(runner):
    make = runner.make_engine

    def broken(cell):
        eng = make(cell)
        step = eng.step

        def unchanged(state, batch):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, m = step(state, batch)
            return keep, m

        eng.step = unchanged
        return eng

    runner.make_engine = broken


def _half_batch(runner):
    from repro.models.common import softmax_cross_entropy
    from repro.models.model import LM

    class HalfLM(LM):
        def loss(self, params, batch):
            logits, _ = self.forward(params, batch["tokens"])
            s = logits.shape[1] // 2
            return softmax_cross_entropy(logits[:, :s], batch["labels"][:, :s],
                                         self.cfg.vocab)

    make = runner.make_engine

    def broken(cell):
        eng = make(cell)
        eng.model = HalfLM(**{f.name: getattr(eng.model, f.name)
                              for f in dataclasses.fields(LM)})
        eng._jit = None
        return eng

    runner.make_engine = broken


def _alter_token(runner):
    make = runner.make_server

    def broken(cell, params):
        srv = make(cell, params)
        append = srv._append

        def altered(slot, tok):
            if srv.n_out[slot] == 2:
                tok = (tok + 1) % cell.hf["vocab_size"]
            return append(slot, tok)

        srv._append = altered
        return srv

    runner.make_server = broken


@pytest.mark.parametrize("workload,fault", [
    ("train.tiny", _keep_state), ("train.tiny", _half_batch),
    ("serve.tiny", _alter_token)])
def test_broken_timed_path_is_not_correct(tiny_root, capsys, workload,
                                          fault):
    out = run_cell(tiny_root, workload, seed=5, hook=fault, capsys=capsys)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("workload", ["train.tiny", "serve.tiny"])
def test_sound_run_is_correct(tiny_root, capsys, workload):
    out = run_cell(tiny_root, workload, seed=5, capsys=capsys)
    assert out["correct"] is True, out["checks"]
