"""Shared fixtures of the benchmark's CPU tests: a throwaway checkout that
holds the benchmark's code beside tiny configurations, so that a whole run
of a cell fits a test on the CPU."""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout with the benchmark's code, the program, and the tiny
    cells of tests/bench/data/tiny: train.tiny and serve.tiny."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    bench.mkdir(parents=True)
    for name in os.listdir(os.path.join(REPO, "bench")):
        src = os.path.join(REPO, "bench", name)
        if name in ("configs", "mixes", "limits", "__pycache__"):
            continue
        if os.path.isdir(src):
            shutil.copytree(src, bench / name,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, bench / name)
    shutil.copytree(os.path.join(DATA, "tiny"), bench, dirs_exist_ok=True)
    shutil.move(str(bench / "BENCHMARK.json"), str(root / "BENCHMARK.json"))
    os.symlink(os.path.join(REPO, "src"), root / "src")
    return root


def run_cell(root, workload, seed=3, seconds=2.0, trace=0, hook=None,
             capsys=None):
    """One run of a cell through the harness's own entry, on the CPU."""
    from bench import harness
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=str(root), require_tpu=False, runner_hook=hook)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
