#!/usr/bin/env python3
"""The program's own spans on the profiler's clock, and what they show that
the benchmark's spans around whole calls cannot.

The program's tracer (``repro.obs.tracing``) has a profiler sink: with
``tracing.enable(annotate=True)`` each span (``serve.admit``,
``serve.sample``, ``serve.decode``, ``serve.decode.wait``,
``serve.tokens``, ``serve.flush``, ``serve.prefill``, ``train.step``,
``train.data_wait``) enters a ``jax.profiler.TraceAnnotation`` whose
stats are the attributes it opened with (``serve.admit`` carries ``rid``
and ``queued_ms``).  ``load`` reads them from a ``.xplane.pb`` beside the
events that ``bench.trace.load`` reads; the readings below take both.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>
        [--program-spans 0|1] [--out DIR]

runs one cell as ``bench/run.py --trace 1`` does, with the program's
spans in the profiler trace for the traced part of the window (unless
``--program-spans 0``), and writes ``DIR/<cell>.spans.json``: the
readings, the idle gaps named by the innermost program span, the device
time of each program by the model's named scopes, the host cost of a
span, and ``DIR/<cell>.spans_excerpt.json.gz``, a trimmed slice of the
trace for the CPU tests.  The benchmark's own runs never turn the
program's spans on.
"""
from __future__ import annotations

import collections
import gzip
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import readers, scopes, trace, traffic

PROGRAM = ("serve.", "train.")
MODEL_SCOPES = ("kv_write", "attn", "mlp", "lm_head")

# (name, start_ns, dur_ns, stats)
Span = Tuple[str, int, int, Dict[str, Any]]


def load(xplane_path: str) -> Tuple[List[trace.Event], List[Span]]:
    """The events ``bench.trace.load`` keeps, and the program's spans
    (host events named ``serve.*`` or ``train.*``) with their stats."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.duration_ns), dict(ev.stats)))
    return trace.load(xplane_path), spans


# -- readings ----------------------------------------------------------------

def _in_window(red, spans: List[Span], name: str) -> List[Span]:
    """Spans of ``name`` that start inside the window."""
    return [sp for sp in spans
            if sp[0] == name and red.lo <= sp[1] < red.hi]


def _clipped(red, spans: List[Span], pred) -> List[trace.Interval]:
    return trace.union((max(s, red.lo), min(s + d, red.hi))
                       for n, s, d, _ in spans
                       if pred(n) and s + d > red.lo and s < red.hi)


def _idle(red, plane: str) -> List[trace.Interval]:
    busy = trace.union((s, e) for _, s, e in red.ops[plane])
    return trace.subtract([(red.lo, red.hi)], busy)


def sched_wait_p90_ms(red, spans: List[Span]) -> Optional[float]:
    """p90 of ``queued_ms`` over the admissions in the window: the wait in
    the server's own queue, submit to admission, without the loop's
    lateness in reaching submit."""
    q = [st["queued_ms"] for _, _, _, st in
         _in_window(red, spans, "serve.admit") if "queued_ms" in st]
    return traffic.percentile(q, 90) if q else None


def admit_stalls_ms(red, spans: List[Span]) -> List[float]:
    """For each decode step in the window after the first, the host time
    spent in admissions since the previous decode step ended."""
    dec = sorted((s, s + d) for _, s, d, _ in
                 _in_window(red, spans, "serve.decode"))
    admits = trace.union((s, s + d) for n, s, d, _ in spans
                         if n == "serve.admit")
    return [trace.length(trace.intersect(admits, [(a[1], b[0])])) / 1e6
            for a, b in zip(dec, dec[1:]) if b[0] > a[1]]


def admit_stall_p95_ms(red, spans: List[Span]) -> Optional[float]:
    st = admit_stalls_ms(red, spans)
    return traffic.percentile(st, 95) if st else None


def server_idle_frac(red, spans: List[Span]) -> Optional[float]:
    """Percent of the window in which the device is idle while the host
    is inside a ``serve.*`` span, averaged over the devices."""
    serve = _clipped(red, spans, lambda n: n.startswith("serve."))
    if not serve:
        return None
    idle = sum(trace.length(trace.intersect(_idle(red, p), serve))
               for p in red.devices) / len(red.devices)
    return 100.0 * idle / (red.hi - red.lo)


def feed_wait_frac(red, spans: List[Span]) -> Optional[float]:
    """Percent of the window inside the program's ``train.data_wait``
    spans (BatchFeed.get)."""
    wait = _clipped(red, spans, lambda n: n == "train.data_wait")
    if not any(n == "train.step" for n, *_ in spans):
        return None
    return 100.0 * trace.length(wait) / (red.hi - red.lo)


def named_idle_share(red, spans: List[Span]) -> float:
    """Share of the first device's idle time that falls inside a named
    host span (the benchmark's or the program's)."""
    idle = _idle(red, red.devices[0])
    named = trace.union([(s, e) for _, s, e in red.host]
                        + _clipped(red, spans, lambda n: True))
    tot = trace.length(idle)
    return trace.length(trace.intersect(idle, named)) / tot if tot else 1.0


def idle_gaps(red, spans: List[Span], top: int = 10) -> List[list]:
    """The longest device gaps, each named as ``Reduced.breakdown`` names
    it, with ``>`` and the innermost program span that covers most of
    the gap where there is one (``bench.admit>serve.sample``)."""
    gaps = trace.subtract([(red.lo, red.hi)], trace.union(
        (s, e) for _, s, e in red.ops[red.devices[0]]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        best, cover = "no host span", 0
        for n, hs, he in red.host:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = n, c
        inner = [(d, n) for n, hs, d, _ in spans
                 if 2 * (min(e, hs + d) - max(s, hs)) > e - s]
        if inner:
            best += ">" + min(inner)[1]
        out.append([best, (e - s) / 1e9])
    return out


def decode_iteration_ms(red) -> Optional[Dict[str, float]]:
    """The serving loop's iteration, start to start of the benchmark's
    ``bench.decode`` spans in the window: mean and p50."""
    st = sorted(s for n, s, _ in red.host if n == "bench.decode")
    it = [(b - a) / 1e6 for a, b in zip(st, st[1:])]
    if not it:
        return None
    return {"mean": sum(it) / len(it), "p50": traffic.percentile(it, 50),
            "n": len(it)}


def model_scope(op_name: str) -> str:
    """The innermost of the model's scopes an op lies under; else its
    op_name's last part, as ``other:<part>``; ``compiler`` for an op the
    compiler put in without an op_name."""
    if not op_name:
        return "compiler"
    parts = scopes.scope_parts(op_name)
    for p in reversed(parts):
        if p in MODEL_SCOPES:
            return p
    return "other:" + parts[-1]


def scope_table(red, names: Dict[str, str], prog: str, calls: float,
                top: int = 12) -> Dict[str, Any]:
    """Device self time of the program ``prog`` per call, by model scope
    and by op_name (ms)."""
    in_prog = readers.program_is(prog)
    by_scope = scopes.split(red, names, in_prog, model_scope)
    by_name = scopes.split(red, names, in_prog, lambda n: n or "compiler")
    per = lambda d: sorted(([k, v * 1e3 / calls] for k, v in d.items()),
                           key=lambda kv: -kv[1])
    return {"calls": calls, "by_scope": per(by_scope)[:top],
            "by_op_name": per(by_name)[:top]}


# -- the run -----------------------------------------------------------------

def excerpt(events: List[trace.Event], spans: List[Span], lo: int, hi: int,
            texts: Dict[str, str]) -> Dict[str, Any]:
    """Events and spans that overlap [lo, hi), with the window span cut
    to it, and, for each program of ``texts`` (function name -> compiled
    HLO text), the op_names of the ops in it."""
    keep = [e for e in events
            if e[2] != trace.WINDOW and e[3] + e[4] > lo and e[3] < hi]
    keep.append(("/host:CPU", "python", trace.WINDOW, lo, hi - lo))
    ops = {e[2].partition(" ")[0] for e in keep
           if e[1] == trace.OPS_LINE}
    return {"events": keep,
            "spans": [sp for sp in spans if sp[1] + sp[2] > lo
                      and sp[1] < hi],
            "op_names": {f: {k: v for k, v in scopes.op_names(t).items()
                             if k in ops} for f, t in texts.items()}}


def serve_texts(srv, mix: Dict[str, Any]) -> Dict[str, str]:
    """Compiled HLO text of the server's decode step and prefill chunk,
    at the argument types its own calls use."""
    import jax.numpy as jnp

    n, c = mix["slots"], mix["prefill_chunk"]
    z = jnp.zeros((n,), jnp.int32)
    with srv._ctx():
        dec = srv._decode.lower(srv.params, srv.cache, z, z, z,
                                jnp.zeros((n,), bool))
        pre = srv._prefill.lower(srv.params, srv.cache,
                                 jnp.zeros((c,), jnp.int32), 0, c)
        return {"decode_fn": dec.compile().as_text(),
                "prefill_fn": pre.compile().as_text()}


def span_cost_us(n: int = 20000) -> Dict[str, float]:
    """Host microseconds of one span with attributes: tracing off, the
    profiler sink on without a profiler session, and with one."""
    import shutil

    import jax

    from bench.harness import TRACE_DIR
    from repro.obs import tracing

    def loop() -> float:
        t = time.perf_counter()
        for i in range(n):
            with tracing.span("serve.admit", rid=i, queued_ms=1.5):
                pass
        return (time.perf_counter() - t) / n * 1e6

    out = {"off": loop()}
    tracing.enable(annotate=True)
    out["sink_on_no_session"] = loop()
    d = os.path.join(TRACE_DIR, "span_cost")
    jax.profiler.start_trace(d)
    out["sink_on_recording"] = loop()
    jax.profiler.stop_trace()
    tracing.disable()
    shutil.rmtree(d, ignore_errors=True)
    return out


def main(argv=None) -> int:
    import argparse
    import shutil

    from bench import harness
    from repro.obs import tracing

    ap = argparse.ArgumentParser(prog="bench/spans.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--excerpt-ms", type=float, default=300.0)
    ap.add_argument("--excerpt-at", choices=("start", "end"), default="end",
                    help="cut the excerpt at the traced window's start or "
                         "end")
    ap.add_argument("--out", default=".cache/bench/spans")
    args = ap.parse_args(argv)
    got: Dict[str, Any] = {"texts": {}}

    class SpanRecorder(trace.Recorder):
        def start(self):
            if self.on and self._win is None:
                super().start()
                if args.program_spans:
                    tracing.enable(annotate=True)

        def stop(self):
            if not self.on or self._win is None:
                return None
            import jax
            tracing.disable()
            self.close_window()
            jax.profiler.stop_trace()
            path = trace.find_xplane(self.path)
            got["events"], got["spans"] = load(path)
            shutil.rmtree(self.path, ignore_errors=True)
            return trace.Reduced(got["events"])

    def hook(runner):
        runner.Recorder = SpanRecorder
        run, setup = runner.run, getattr(runner, "setup", None)

        def keep_run(*a, **k):
            got["run"] = run(*a, **k)
            return got["run"]
        runner.run = keep_run
        if setup is not None:
            def keep_texts(cell, devices):
                srv = setup(cell, devices)
                got["texts"].update(serve_texts(srv, cell.mix))
                return srv
            runner.setup = keep_texts

    harness.main(["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", "1"],
                 runner_hook=hook)
    run, red, spans = got["run"], got["run"].trace, got["spans"]
    if "step_fn" not in got["texts"] and run.cell.mix["kind"] == "train":
        got["texts"]["step_fn"] = scopes.step_hlo(run.cell)
    report: Dict[str, Any] = {
        "cell": run.cell.name, "seed": run.cell.seed,
        "program_spans": args.program_spans,
        "window_s": red.window_s, "idle_frac": red.idle_frac(),
        "readings": {
            "sched_wait_p90_ms": sched_wait_p90_ms(red, spans),
            "admit_stall_p95_ms": admit_stall_p95_ms(red, spans),
            "server_idle_frac": server_idle_frac(red, spans),
            "feed_wait_frac": feed_wait_frac(red, spans),
        },
        "train_phases_ms": scopes.train_phases(run),
        "named_idle_share": named_idle_share(red, spans),
        "idle_gaps": idle_gaps(red, spans),
        "decode_iteration_ms": decode_iteration_ms(red),
        "span_counts": collections.Counter(n for n, *_ in spans),
        "scopes": {},
    }
    for prog in ("step_fn", "decode_fn", "prefill_fn"):
        calls = red.program_calls(readers.program_is(prog))
        if calls and prog in got["texts"]:
            report["scopes"][prog] = scope_table(
                red, scopes.op_names(got["texts"][prog]), prog, calls)
    report["span_cost_us"] = span_cost_us()
    os.makedirs(args.out, exist_ok=True)
    base = os.path.join(args.out, f"{run.cell.name}.s{args.seed}"
                        f".p{args.program_spans}")
    with open(base + ".spans.json", "w") as f:
        json.dump(report, f, indent=1, default=str)
    cut = int(args.excerpt_ms * 1e6)
    lo, hi = ((red.lo, min(red.hi, red.lo + cut)) if args.excerpt_at ==
              "start" else (max(red.lo, red.hi - cut), red.hi))
    with gzip.open(base + ".spans_excerpt.json.gz", "wt") as f:
        json.dump(excerpt(got["events"], spans, lo, hi, got["texts"]), f)
    print(json.dumps({k: report[k] for k in (
        "readings", "train_phases_ms", "named_idle_share",
        "decode_iteration_ms", "span_cost_us")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
