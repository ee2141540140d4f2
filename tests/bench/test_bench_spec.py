"""BENCHMARK.json against the rules its harness relies on: every name is
well formed, every cell finds its configuration, mix and limits, every
per-layer metric its reader, and every metric is reported where it is
listed."""
import json
import os
import re

import pytest

from conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][1] == "bench/run.py"
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in spec["paths"])
    assert 1 <= spec["run_seconds"] <= 51


def test_cells_find_their_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        conf = configs[w["config"]]
        with open(os.path.join(REPO, conf["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == conf["reduced"]
        for sub in ("mixes/" + w["traffic"], "limits/" + w["name"]):
            with open(os.path.join(REPO, "bench", sub + ".json")) as f:
                json.load(f)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)


def test_metrics_are_well_formed_and_reported(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for cell in cells:
        reported = [m["name"] for m in spec["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py"))
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    assert all(any(cell in m["workloads"] for m in spec["per_layer"])
               for cell in cells)


def test_full_check_fits_its_time(spec):
    r, n = spec["run_seconds"], 24
    assert (2 + 14 * n) * (r + 60) + n * 2 * 90 + 1200 <= 43200
