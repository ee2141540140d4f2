"""Observability stack: tracing spans, the metrics registry, exact
percentile stats, drift gauges, artifact validation and the CLI runs'
end-to-end trace/metrics outputs.

The tracer is process-global, so every tracing test runs under the
``clean_tracer`` fixture (restore disabled + empty afterwards) — the
rest of the suite must never see tracing enabled.
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import drift, metrics, stats, tracing
from repro.obs.__main__ import (load_metrics, load_trace, main as obs_main,
                                render_timeline, validate_metrics,
                                validate_trace)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ------------------------------------------------------------------ stats --

class TestStats:
    def test_empty_is_none(self):
        assert stats.percentile([], 50.0) is None
        assert stats.mean([]) is None
        s = stats.summarize([])
        assert s["count"] == 0 and s["p50"] is None

    def test_single_sample_every_q(self):
        for q in (0.0, 37.5, 50.0, 100.0):
            assert stats.percentile([4.2], q) == 4.2

    def test_q_out_of_range(self):
        with pytest.raises(ValueError):
            stats.percentile([1.0], -1.0)
        with pytest.raises(ValueError):
            stats.percentile([1.0], 100.5)

    def test_numpy_parity(self):
        rng = np.random.default_rng(0)
        xs = rng.exponential(size=257).tolist()
        for q in (0.0, 10.0, 50.0, 90.0, 95.0, 99.0, 100.0):
            assert stats.percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12)

    def test_summarize(self):
        s = stats.summarize([3.0, 1.0, 2.0])
        assert s["count"] == 3 and s["min"] == 1.0 and s["max"] == 3.0
        assert s["mean"] == pytest.approx(2.0)
        assert s["p50"] == 2.0


# ---------------------------------------------------------------- tracing --

@pytest.fixture
def clean_tracer():
    t = tracing.get_tracer()
    t.clear()
    t.detach_ring()
    prev_out = t.out
    try:
        yield t
    finally:
        t.disable()
        t.detach_ring()
        t.clear()
        t.out = prev_out


class TestTracing:
    def test_disabled_records_nothing(self, clean_tracer):
        t = clean_tracer
        assert not t.enabled
        for _ in range(100):
            with tracing.span("solver.dp", n=3):
                pass
            tracing.instant("serve.preempt", slot=1)
        assert t.events == []

    def test_disabled_span_is_shared_null(self, clean_tracer):
        # the hot path must not allocate per call: every disabled span()
        # returns the one shared null context manager
        a = tracing.span("x")
        b = tracing.span("y", k=1)
        assert a is b is tracing.NULL_SPAN
        assert a.set(foo=1) is a     # set() is a no-op on the null span

    def test_span_nesting_and_attrs(self, clean_tracer):
        t = clean_tracer
        t.enable()
        with tracing.span("solver.dp", beam=8) as outer:
            outer.set(exact=True)
            with tracing.span("solver.dp.incumbent"):
                pass
        evs = t.events
        assert [e["name"] for e in evs] == ["solver.dp.incumbent",
                                            "solver.dp"]   # exit order
        inner, outer_ev = evs
        assert outer_ev["args"] == {"beam": 8, "exact": True}
        assert outer_ev["cat"] == "solver"
        # the inner span's interval nests inside the outer's
        assert outer_ev["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]
                <= outer_ev["ts"] + outer_ev["dur"] + 1e-6)

    def test_record_and_instant(self, clean_tracer):
        t = clean_tracer
        t.enable()
        import time
        t0 = time.perf_counter()
        tracing.record("compile.lower", t0, t0 + 0.25, arch="x")
        tracing.instant("serve.retire", rid=0, slot=2)
        x, i = t.events
        assert x["ph"] == "X" and x["dur"] == pytest.approx(0.25e6)
        assert i["ph"] == "i" and i["s"] == "t"
        assert i["args"] == {"rid": 0, "slot": 2}

    def test_export_is_valid_chrome_trace(self, clean_tracer, tmp_path):
        t = clean_tracer
        t.enable()
        with tracing.span("train.step", step=0):
            pass
        tracing.instant("serve.admitted", rid=1, slot=0)
        p = str(tmp_path / "t.trace.json")
        assert tracing.export(p) == p
        doc = load_trace(p)
        assert doc["displayTimeUnit"] == "ms"
        assert validate_trace(doc) == []

    def test_profiler_sink_puts_attrs_on_the_profiler_clock(
            self, clean_tracer, tmp_path):
        import jax
        from jax.profiler import ProfileData
        t = clean_tracer
        t.enable(annotate=True)
        jax.profiler.start_trace(str(tmp_path))
        with tracing.span("serve.admit", rid=7, queued_ms=3.5,
                          slots=[0, 4, 9]) as sp:
            sp.set(late=1)          # after opening: Chrome export only
            with tracing.span("serve.sample"):
                pass
        jax.profiler.stop_trace()
        t.disable()
        assert t.events == []       # the profiler sink keeps no list
        path = next(p for p in tmp_path.rglob("*.xplane.pb"))
        host = {ev.name: dict(ev.stats)
                for plane in ProfileData.from_file(str(path)).planes
                for line in plane.lines for ev in line.events
                if ev.name.startswith("serve.")}
        assert host["serve.admit"] == {"rid": 7, "queued_ms": 3.5,
                                       "n_slots": 3}
        assert host["serve.sample"] == {}
        assert tracing.span("serve.admit", rid=1) is tracing.NULL_SPAN

    def test_sinks_combine_and_disable_together(self, clean_tracer):
        t = clean_tracer
        t.enable(annotate=True)
        assert not t.enabled and t.annotation is not None
        t.enable("x.trace.json", annotate=True)
        assert t.enabled and t.annotation is not None
        with tracing.span("train.step", step=3):
            pass
        assert [e["args"] for e in t.events] == [{"step": 3}]
        t.disable()
        assert t.annotation is None and not t._active
        assert tracing.span("train.step") is tracing.NULL_SPAN


# ---------------------------------------------------------------- metrics --

class TestMetrics:
    def test_counter(self):
        r = metrics.Registry()
        c = r.counter("n")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_starts_nan(self):
        g = metrics.Registry().gauge("g")
        assert math.isnan(g.value)
        g.set(7)
        assert g.value == 7.0

    def test_get_or_create_and_type_clash(self):
        r = metrics.Registry()
        assert r.counter("x") is r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_histogram_bucket_boundaries_are_inclusive(self):
        h = metrics.Histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (1.0, 2.0, 4.0):       # v <= le lands IN the bucket
            h.observe(v)
        h.observe(4.0001)               # only this overflows to +inf
        assert h.counts == [1, 1, 1, 1]
        d = h.to_dict()
        assert d["buckets"][-1] == {"le": "inf", "count": 1}
        assert d["count"] == 4 and d["min"] == 1.0 and d["max"] == 4.0001

    def test_histogram_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            metrics.Histogram("h", buckets=(1.0, 1.0, 2.0))
        with pytest.raises(ValueError):
            metrics.Histogram("h", buckets=(2.0, 1.0))

    def test_histogram_percentile_bounded(self):
        # q is on [0, 100], matching obs.stats.percentile (PR 10)
        h = metrics.Histogram("h", buckets=(0.01, 0.1, 1.0))
        assert h.percentile(50.0) is None
        h.observe_many([0.05, 0.06, 0.07, 0.5])
        for q in (0.0, 50.0, 90.0, 100.0):
            p = h.percentile(q)
            assert 0.05 <= p <= 0.5
        with pytest.raises(ValueError):
            h.percentile(-1.0)
        with pytest.raises(ValueError):
            h.percentile(100.5)

    def test_histogram_percentile_fraction_shim(self):
        # legacy q in (0, 1) is interpreted as a fraction with a
        # DeprecationWarning — same answer as the new convention
        h = metrics.Histogram("h", buckets=(0.01, 0.1, 1.0))
        h.observe_many([0.05, 0.06, 0.07, 0.5])
        with pytest.warns(DeprecationWarning):
            old = h.percentile(0.5)
        assert old == h.percentile(50.0)

    def test_jsonl_round_trip_validates(self, tmp_path):
        r = metrics.Registry()
        r.counter("serve.tokens").inc(10)
        r.gauge("drift.predicted_vs_measured_bytes").set(1.2)
        r.histogram("serve.ttft_s").observe_many([0.01, 0.2])
        p = str(tmp_path / "m.jsonl")
        r.dump_jsonl(p)
        recs = load_metrics(p)
        assert validate_metrics(recs) == []
        by = {m["name"]: m for m in recs}
        assert by["serve.tokens"]["value"] == 10
        assert by["serve.ttft_s"]["count"] == 2

    def test_prometheus_text_cumulative(self):
        r = metrics.Registry()
        h = r.histogram("lat", buckets=(1.0, 2.0))
        h.observe_many([0.5, 1.5, 5.0])
        txt = r.prometheus_text()
        assert '# TYPE lat histogram' in txt
        assert 'lat_bucket{le="1.0"} 1' in txt
        assert 'lat_bucket{le="2.0"} 2' in txt
        assert 'lat_bucket{le="+Inf"} 3' in txt
        assert "lat_count 3" in txt

    def test_prometheus_sum_count_typed(self):
        # _sum/_count are cumulative counters in their own right and
        # need their own # TYPE lines for strict scrapers (PR 10)
        r = metrics.Registry()
        r.histogram("serve.itl_s", buckets=(0.1,)).observe_many([0.05, 0.5])
        txt = r.prometheus_text()
        assert "# TYPE serve_itl_s histogram" in txt
        assert "# TYPE serve_itl_s_sum counter" in txt
        assert "# TYPE serve_itl_s_count counter" in txt
        assert "serve_itl_s_count 2" in txt

    def test_prometheus_round_trip_with_labels(self):
        r = metrics.Registry()
        r.counter("req.total", labels={"mode": 'pre"fill\\x',
                                       "arch": "a\nb"}).inc(7)
        r.gauge("drift.ratio", labels={"mesh": "4x2"}).set(1.25)
        r.histogram("lat", buckets=(1.0,)).observe_many([0.5, 2.0])
        parsed = metrics.parse_prometheus_text(r.prometheus_text())
        samples = {(s, tuple(sorted(lab.items()))): v
                   for s, lab, v in parsed["samples"]}
        key = ("req_total", (("arch", "a\nb"), ("mode", 'pre"fill\\x')))
        assert samples[key] == 7.0
        assert samples[("drift_ratio", (("mesh", "4x2"),))] == 1.25
        assert samples[("lat_count", ())] == 2.0
        assert parsed["types"]["lat"] == "histogram"
        assert parsed["types"]["lat_sum"] == "counter"

    def test_null_registry_discards(self):
        n = metrics.NULL
        n.counter("a").inc(5)
        n.gauge("b").set(1)
        n.histogram("c").observe(2)
        assert n.collect() == []


# ------------------------------------------------------------------ drift --

class TestDrift:
    def test_ratio(self):
        assert drift.drift_ratio(1e6, 2e6) == 2.0
        # both sides under the absolute floor: declared in-band at 1.0
        assert drift.drift_ratio(10.0, 100.0, floor=256e3) == 1.0
        # a real measured volume against a zero prediction is the bad
        # case the CI finiteness gate must catch
        assert drift.drift_ratio(0.0, 1e9) == math.inf

    def test_record_drift_gauges(self):
        r = metrics.Registry()
        rec = drift.record_drift(r, 0.0, "HloModule m\n", 4)
        assert rec["measured_wire_bytes"] == 0.0
        assert rec["ratio"] == 1.0 and rec["in_band"]
        by = {m["name"]: m for m in r.collect()}
        assert by["drift.predicted_vs_measured_bytes"]["value"] == 1.0


# ----------------------------------------------------- CLI + artifacts ----

class TestObsCLI:
    def _write_artifacts(self, tmp_path):
        trace = {"displayTimeUnit": "ms", "traceEvents": [
            {"name": "serve.admitted", "cat": "serve", "ph": "i",
             "s": "t", "ts": 0.0, "pid": 1, "tid": 1,
             "args": {"rid": 0, "slot": 0}},
            {"name": "serve.prefill", "cat": "serve", "ph": "X",
             "ts": 10.0, "dur": 40.0, "pid": 1, "tid": 1,
             "args": {"slot": 0, "tokens": 8}},
            {"name": "serve.decode", "cat": "serve", "ph": "X",
             "ts": 60.0, "dur": 40.0, "pid": 1, "tid": 1,
             "args": {"slots": [0]}},
            {"name": "serve.retire", "cat": "serve", "ph": "i",
             "s": "t", "ts": 100.0, "pid": 1, "tid": 1,
             "args": {"rid": 0, "slot": 0, "reason": "done"}},
        ]}
        tp = str(tmp_path / "t.json")
        with open(tp, "w") as f:
            json.dump(trace, f)
        r = metrics.Registry()
        r.gauge("drift.predicted_vs_measured_bytes").set(1.0)
        mp = str(tmp_path / "m.jsonl")
        r.dump_jsonl(mp)
        return tp, mp

    def test_validate_ok(self, tmp_path, capsys):
        tp, mp = self._write_artifacts(tmp_path)
        rc = obs_main(["--trace", tp, "--metrics", mp, "--validate",
                       "--require-drift"])
        assert rc == 0
        assert "OK" in capsys.readouterr().out

    def test_validate_catches_corruption(self, tmp_path, capsys):
        tp, mp = self._write_artifacts(tmp_path)
        with open(mp, "a") as f:
            f.write(json.dumps({"type": "histogram", "name": "bad",
                                "count": 2, "sum": 1.0,
                                "buckets": [{"le": 1.0, "count": 1}]})
                    + "\n")
        rc = obs_main(["--trace", tp, "--metrics", mp, "--validate"])
        assert rc == 1
        assert "INVALID" in capsys.readouterr().err

    def test_validate_rejects_bad_ph(self, tmp_path):
        doc = {"traceEvents": [{"name": "a", "ph": "Z", "ts": 0,
                                "pid": 1, "tid": 1}]}
        errs = validate_trace(doc)
        assert errs and "ph" in errs[0]

    def test_timeline_lanes(self, tmp_path):
        tp, _ = self._write_artifacts(tmp_path)
        txt = render_timeline(load_trace(tp), width=40)
        lane = [ln for ln in txt.splitlines() if ln.startswith("slot")][0]
        assert "A" in lane and "P" in lane and "D" in lane
        assert lane.rstrip().endswith("|")   # retire instant at the end


# --------------------------------------------- end-to-end CLI artifacts ---

@pytest.mark.slow
class TestEndToEnd:
    def test_serve_trace_and_metrics(self, tmp_path):
        """A real (reduced, host-device) serve run must emit the
        admit -> prefill -> decode span sequence and a valid metrics
        registry with latency histograms."""
        tp = str(tmp_path / "serve.trace.json")
        mp = str(tmp_path / "serve.metrics.jsonl")
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve",
             "--arch", "qwen2-1.5b", "--reduced", "--slots", "2",
             "--gen", "4", "--prompt-len", "8", "--requests", "2",
             "--trace-out", tp, "--metrics-out", mp],
            capture_output=True, text=True, timeout=560,
            env=dict(os.environ, PYTHONPATH=SRC))
        assert out.returncode == 0, out.stderr[-4000:]
        doc = load_trace(tp)
        assert validate_trace(doc) == []
        names = [e["name"] for e in doc["traceEvents"]]
        for expected in ("serve.admit", "serve.prefill", "serve.decode",
                         "serve.retire"):
            assert expected in names, names
        # spans appear in scheduling order per request: admit precedes
        # the first decode tick
        assert names.index("serve.admit") < names.index("serve.decode")
        recs = load_metrics(mp)
        assert validate_metrics(recs) == []
        by = {m["name"]: m for m in recs}
        assert by["serve.ttft_s"]["type"] == "histogram"
        assert by["serve.ttft_s"]["count"] == 2
        assert by["serve.itl_s"]["count"] > 0
        assert by["serve.tokens"]["value"] == pytest.approx(
            by["serve.itl_s"]["count"] + 2)

    def test_train_loss_log_interval_invariant(self, tmp_path):
        """Satellite regression: buffering device losses between sync
        boundaries must not change any step's logged loss."""
        outs = {}
        for le in (1, 3):
            jp = str(tmp_path / f"train{le}.json")
            out = subprocess.run(
                [sys.executable, "-m", "repro.launch.train",
                 "--arch", "qwen2-1.5b", "--reduced", "--steps", "5",
                 "--batch", "2", "--seq", "16", "--warmup", "1",
                 "--log-every", str(le), "--json-out", jp],
                capture_output=True, text=True, timeout=560,
                env=dict(os.environ, PYTHONPATH=SRC))
            assert out.returncode == 0, out.stderr[-4000:]
            with open(jp) as f:
                outs[le] = json.load(f)
        assert outs[1]["losses"] == outs[3]["losses"]
        assert len(outs[1]["losses"]) == 5
