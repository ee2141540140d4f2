"""The benchmark's dispatch: one cell of ``BENCHMARK.json``, run once.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

  bench/configs/<config>.json   sizes as run (``file`` in BENCHMARK.json)
  bench/mixes/<traffic>.json    traffic parameters; ``kind`` names the
                                runner bench/kinds/<kind>.py
  bench/limits/<cell>.json      the limit of each number compared
  bench/metrics/<metric>.py     ``read(run) -> float | None``

A runner takes a ``Cell`` and returns a ``Run``: its end-to-end metrics,
its records for the per-layer readers, and the numbers compared with the
plain reference, each beside its limit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".cache", "bench", "trace")

# program config field <- key of the published config.json
_ARCH_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_LOADED: Dict[str, Any] = {}


def load_module(path: str, name: str):
    """A benchmark file found by name, loaded once per process."""
    path = os.path.abspath(path)
    if path not in _LOADED:
        if not os.path.exists(path):
            raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def read_json(path: str) -> Any:
    if not os.path.exists(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]        # the configuration file, whole
    mix: Dict[str, Any]
    limits: Dict[str, float]
    seed: int
    seconds: float
    trace: bool
    root: str = ROOT

    @property
    def hf(self) -> Dict[str, Any]:
        """The model's published config.json keys, as run."""
        return self.config["config"]

    def arch(self):
        """The program's ArchConfig for this configuration: its
        registered architecture with every size taken from the file."""
        import dataclasses as dc

        from repro.configs.base import get_arch
        base = get_arch(self.config["program_arch"])
        upd = {f: self.hf[k] for k, f in _ARCH_KEYS.items()}
        upd["head_dim"] = self.hf.get("head_dim") or (
            self.hf["hidden_size"] // self.hf["num_attention_heads"])
        upd["qkv_bias"] = self.config["qkv_bias"]
        upd["dtype"] = self.config["dtype"]
        return dc.replace(base, **upd)

    def reference(self):
        return load_module(os.path.join(
            self.root, "bench", "references",
            self.config["reference"] + ".py"),
            f"bench_ref_{self.config['reference']}")


@dataclasses.dataclass
class Run:
    """What one run of a cell measured, for the result line and the
    per-layer readers."""
    cell: Cell
    attempted: int
    failed: int
    metrics: Dict[str, float]                 # end-to-end, by name
    checks: List[tuple]                       # (name, value, limit)
    records: Dict[str, Any]                   # runner-specific, for readers
    window_s: float
    devices: List[Any] = dataclasses.field(default_factory=list)
    memory_peak_bytes: Optional[int] = None
    trace: Any = None                         # bench.trace.Reduced

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim
                   for _, v, lim in self.checks) and bool(self.checks)


def find_devices(chips: int, require_tpu: bool = True) -> List[Any]:
    """The cell's devices; no TPU, or fewer chips than the cell asks for,
    ends the run with no result (never a CPU fallback)."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"{len(devs)} device(s); the cell asks for "
                         f"{chips}")
    return devs[:chips]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache, at the fixed path
    <checkout>/.cache/bench/jax whatever the environment says, so that
    the checkouts of two commits share nothing and no other tool's
    entries sit beside the benchmark's; every program is kept, so a
    second run of a cell in a checkout compiles nothing."""
    import jax
    path = os.path.join(root, ".cache", "bench", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_COMPILES = [0]
_COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


def count_compiles() -> None:
    """Count every program handed to the compiler from now on (compiled
    or fetched from the persistent cache)."""
    import jax

    def on_event(event: str, **_) -> None:
        if event == _COMPILE_EVENT:
            _COMPILES[0] += 1

    jax.monitoring.register_event_listener(on_event)


def compiles() -> int:
    """Programs handed to the compiler since count_compiles; a runner
    reads it at both ends of its window, which should compile nothing."""
    return _COMPILES[0]


def load_cell(spec: Dict[str, Any], workload: str, seed: int,
              seconds: float, trace: bool, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=conf["name"],
        config=read_json(os.path.join(root, conf["file"])),
        mix=read_json(os.path.join(root, "bench", "mixes",
                                   w["traffic"] + ".json")),
        limits=read_json(os.path.join(root, "bench", "limits",
                                      workload + ".json")),
        seed=seed, seconds=seconds, trace=trace, root=root)


def metrics_for(spec: Dict[str, Any], cell: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The metric entries this cell reports: its end-to-end ones, or with
    a trace its per-layer ones (those listing the cell, or without a list
    those whose end-to-end metric the cell reports)."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_per_layer(run: Run, entries: List[Dict[str, Any]]
                   ) -> Dict[str, float]:
    out = {}
    for m in entries:
        mod = load_module(os.path.join(run.cell.root, "bench", "metrics",
                                       m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        val = mod.read(run)
        if val is not None:
            out[m["name"]] = float(val)
    return out


def device_info(run: Run) -> Dict[str, Any]:
    d = run.devices[0] if run.devices else None
    info = {"platform": getattr(d, "platform", None),
            "kind": getattr(d, "device_kind", None),
            "count": len(run.devices),
            "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace is not None:
        info["busy_s"] = run.trace.busy_s()
        info["window_s"] = run.trace.window_s
    return info


def peak_memory(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def result_line(run: Run, spec: Dict[str, Any]) -> Dict[str, Any]:
    entries = metrics_for(spec, run.cell.name, run.cell.trace)
    units = {m["name"]: m["unit"] for m in entries}
    if run.cell.trace:
        values = read_per_layer(run, entries)
    else:
        values = {k: v for k, v in run.metrics.items() if k in units}
        missing = set(units) - set(values)
        if missing:
            raise BenchError(f"cell reports no {sorted(missing)}")
    out: Dict[str, Any] = {
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": device_info(run),
    }
    if run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in run.checks}
    return out


def main(argv=None, t_start: Optional[float] = None, root: str = ROOT,
         require_tpu: bool = True,
         runner_hook: Optional[Callable] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise BenchError("--seed must be non-negative")

    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)

    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    cell = load_cell(spec, args.workload, args.seed, args.seconds,
                     bool(args.trace), root)
    cache = enable_compile_cache(root)
    count_compiles()
    devices = find_devices(cell.chips, require_tpu)
    log(f"bench: {cell.name} seed {cell.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache}")
    runner = load_module(os.path.join(root, "bench", "kinds",
                                      cell.mix["kind"] + ".py"),
                         "bench_kind_" + cell.mix["kind"])
    if runner_hook is not None:
        runner_hook(runner)
    run = runner.run(cell, devices, t_start)
    line = result_line(run, spec)
    for n, v, lim in run.checks:
        log(f"check {n}: {v!r} (limit {lim!r})"
            f"{'' if math.isfinite(v) and v <= lim else '  FAILS'}")
    print(json.dumps(line), flush=True)
    return 0
