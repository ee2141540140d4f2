"""The harness finds a cell, configuration, mix and per-layer metric that
are added only as new files and BENCHMARK.json entries, and runs them;
and a run without a TPU ends with no result."""
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO, run_cell

from bench import trace


class FakeRecorder(trace.Recorder):
    """The device layer, faked: no profiler; the window's trace is a
    hand-made one in which the device is busy half the time."""

    def start(self):
        self._win = True

    def close_window(self):
        pass

    def stop(self):
        if not self.on or not self._win:
            return None
        dev = "/device:TPU:0"
        ev = [("/host:CPU", "python", trace.WINDOW, 0, 1000)]
        ev += [(dev, trace.OPS_LINE, f"fusion.{i}", 100 * i, 50)
               for i in range(10)]
        return trace.Reduced(ev)


def test_new_cell_config_mix_and_metric_found_by_name(tiny_root, capsys):
    bench = tiny_root / "bench"
    conf = json.loads((bench / "configs" / "tiny.json").read_text())
    conf["config"]["num_hidden_layers"] = 3
    (bench / "configs" / "tiny-3l.json").write_text(json.dumps(conf))
    mix = json.loads((bench / "mixes" / "chat-tiny.json").read_text())
    mix["rate_per_s"] = 5.0
    (bench / "mixes" / "chat-slow.json").write_text(json.dumps(mix))
    (bench / "limits" / "serve.tiny-3l.slow.json").write_text(
        json.dumps({"served_gap": 0.1}))
    (bench / "metrics" / "decode_steps_seen.py").write_text(
        "def read(run):\n"
        "    return run.records['served'].n_decode_in_window\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="tiny-3l",
                                file="bench/configs/tiny-3l.json"))
    spec["workloads"].append({"name": "serve.tiny-3l.slow",
                              "config": "tiny-3l", "traffic": "chat-slow",
                              "chips": 1, "why": "CPU tests only."})
    spec["per_layer"].append({
        "name": "decode_steps_seen", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "runtime/serve scheduler",
        "moves": "itl_p95_ms", "workloads": ["serve.tiny-3l.slow"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "serve.tiny" in m["workloads"]:
            m["workloads"].append("serve.tiny-3l.slow")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    def fake_device(runner):
        runner.Recorder = FakeRecorder

    out = run_cell(tiny_root, "serve.tiny-3l.slow", trace=1,
                   hook=fake_device, capsys=capsys)
    # the per-layer metrics that list this cell, and only those
    assert set(out["metrics"]) == {"decode_steps_seen"}
    assert out["metrics"]["decode_steps_seen"]["value"] > 0
    assert out["device"]["busy_s"] == pytest.approx(500e-9)
    assert out["device"]["window_s"] == pytest.approx(1000e-9)
    assert out["correct"] is True and out["attempted"] == 10
    assert list(out)[-1] == "checks"


def test_end_to_end_line_has_the_contract_keys(tiny_root, capsys):
    out = run_cell(tiny_root, "train.tiny", capsys=capsys)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert set(out["metrics"]) == {"train_tok_s_per_chip", "setup_s"}
    assert out["correct"] is True


def test_no_tpu_no_result(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.tiny",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_no_program_beside_it_no_result(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in ("run.py", "harness.py", "__init__.py"):
        (tmp_path / "bench" / f).write_text(
            open(os.path.join(REPO, "bench", f)).read())
    (tmp_path / "BENCHMARK.json").write_text(
        open(os.path.join(REPO, "BENCHMARK.json")).read())
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "serve.qwen2-1.5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
