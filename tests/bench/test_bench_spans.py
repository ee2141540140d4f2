"""The program's spans and named scopes, read from trimmed traces that
bench/spans.py recorded on a TPU v5e with the program's tracing in the
profiler (tests/bench/data/chat_spans_excerpt.json.gz: the last 600 ms
of the chat cell's traced window; train_scopes_excerpt.json.gz: the first
900 ms of the one-chip training window; each with the op_names of its
ops from the compiled programs), checked against brute-force counts on a
1 us grid."""
import gzip
import json
import os

import numpy as np
import pytest

from conftest import DATA, REPO

from bench import harness, readers, scopes, spans, trace

US = 1000


@pytest.fixture(scope="module")
def chat():
    return _load("chat_spans_excerpt.json.gz")


@pytest.fixture(scope="module")
def train():
    return _load("train_scopes_excerpt.json.gz")


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        d = json.load(f)
    red = trace.Reduced([tuple(e) for e in d["events"]])
    return red, [tuple(s) for s in d["spans"]], d["op_names"]


def _grid(red, intervals):
    """The 1 us cells of the window that the intervals cover."""
    g = np.zeros((red.hi - red.lo) // US + 1, bool)
    for s, e in intervals:
        s, e = max(s, red.lo), min(e, red.hi)
        if e > s:
            g[(s - red.lo) // US:(e - red.lo) // US] = True
    return g


def _spans(sp, pred):
    return [(s, s + d) for n, s, d, _ in sp if pred(n)]


def _busy(red):
    return _grid(red, [(s, e) for _, s, e in red.ops[red.devices[0]]])


def test_sched_wait_is_the_p90_of_queued_ms(chat):
    red, sp, _ = chat
    q = [st["queued_ms"] for n, s, _, st in sp
         if n == "serve.admit" and red.lo <= s < red.hi]
    assert q and all(v >= 0 for v in q)
    assert spans.sched_wait_p90_ms(red, sp) == pytest.approx(
        float(np.percentile(q, 90)))


def test_admit_stalls_match_a_brute_force_count(chat):
    red, sp, _ = chat
    dec = sorted(_spans(sp, lambda n: n == "serve.decode"))
    dec = [d for d in dec if red.lo <= d[0] < red.hi]
    admits = _grid(red, _spans(sp, lambda n: n == "serve.admit"))
    want = []
    for a, b in zip(dec, dec[1:]):
        lo, hi = (a[1] - red.lo) // US, (b[0] - red.lo) // US
        want.append(admits[lo:hi].sum() * US / 1e6)
    got = spans.admit_stalls_ms(red, sp)
    assert len(got) == len(want) >= 3
    assert got == pytest.approx(want, abs=0.01)
    # some decode step waited on an admission's prefill
    assert max(got) > 10
    assert spans.admit_stall_p95_ms(red, sp) == pytest.approx(
        float(np.percentile(got, 95)))


def test_server_idle_matches_a_brute_force_count(chat):
    red, sp, _ = chat
    serve = _grid(red, _spans(sp, lambda n: n.startswith("serve.")))
    cells = (~_busy(red) & serve)[:-1].sum()
    want = 100.0 * cells * US / (red.hi - red.lo)
    got = spans.server_idle_frac(red, sp)
    n = len(red.ops[red.devices[0]]) + len(sp)
    assert got == pytest.approx(want, abs=100.0 * 2 * n * US
                                / (red.hi - red.lo))
    assert 0 < got <= 100 * red.idle_frac() + 1e-9


def test_idle_time_sits_under_named_spans(chat):
    red, sp, _ = chat
    assert spans.named_idle_share(red, sp) >= 0.8
    gaps = spans.idle_gaps(red, sp)
    # the benchmark's naming of each gap is kept, with the program's
    # innermost span appended where one covers most of the gap
    base = red.breakdown()["idle_gaps"]
    assert [g[0].split(">")[0] for g in gaps] == [b[0] for b in base]
    assert [g[1] for g in gaps] == [b[1] for b in base]
    assert any(">serve." in g[0] for g in gaps)


def test_decode_attention_scope_holds_the_decode_kernel(chat):
    red, _, names = chat
    in_dec = readers.program_is("decode_fn")
    by = scopes.split(red, names["decode_fn"], in_dec, spans.model_scope)
    kernel = red.op_s(readers.is_kernel, in_dec)
    assert kernel > 0
    assert by["attn"] == pytest.approx(kernel, rel=0.02)
    assert sum(by.values()) == pytest.approx(
        red.program_s(in_dec), rel=0.02)


def _self_by(red, names, key):
    """Brute force: paint each op of the step programs on the grid in
    start order, longest first, so that a cell ends with the innermost op
    that covers it; then sum cells by key(op_name)."""
    plane = red.devices[0]
    step = _grid(red, [(s, e) for n, s, e in red.modules[plane]
                       if trace.program(n) == "jit_step_fn"])
    ops = sorted(red.ops[plane], key=lambda x: (x[1], -x[2]))
    owner = np.full(step.shape, -1)
    for i, (n, s, e) in enumerate(ops):
        if step[(s - red.lo) // US]:
            owner[(s - red.lo) // US:(e - red.lo) // US] = i
    out = {}
    for i, cells in zip(*np.unique(owner[owner >= 0], return_counts=True)):
        op = names.get(ops[i][0].partition(" ")[0])
        k = "unmatched" if op is None else key(op)
        out[k] = out.get(k, 0) + cells * US / 1e9
    return out


def test_train_phases_match_a_brute_force_self_time(train):
    red, _, names = train
    got = scopes.split(red, names["step_fn"], readers.program_is("step_fn"),
                       scopes.phase)
    want = _self_by(red, names["step_fn"], scopes.phase)
    n = len(red.ops[red.devices[0]])
    for k in set(got) | set(want):
        assert got.get(k, 0) == pytest.approx(want.get(k, 0),
                                              abs=2 * n * US / 1e9)
    # the three phases hold at least 90 % of the step's device time
    main = got["forward"] + got["backward"] + got["optimizer"]
    assert main >= 0.9 * sum(got.values())
    assert got["backward"] > 2 * got["forward"] > 0
    assert got.get("unmatched", 0) == 0


def test_feed_wait_matches_a_brute_force_count(train):
    red, sp, _ = train
    waits = _spans(sp, lambda n: n == "train.data_wait")
    assert waits and any(n == "train.step" for n, *_ in sp)
    want = 100.0 * _grid(red, waits)[:-1].sum() * US / (red.hi - red.lo)
    assert spans.feed_wait_frac(red, sp) == pytest.approx(
        want, abs=100.0 * 2 * len(waits) * US / (red.hi - red.lo))


def _hlo(names):
    """HLO text that names each instruction with its op_name."""
    return "\n".join(
        f'  %{k} = f32[] add(), metadata={{op_name="{v}"}}' if v
        else f"  %{k} = f32[] copy()" for k, v in names.items())


class _Run:
    def __init__(self, red, cell=None):
        self.trace, self.cell = red, cell


@pytest.mark.parametrize("metric,phase", [
    ("train_fwd_ms", "forward"), ("train_bwd_ms", "backward"),
    ("train_opt_ms", "optimizer")])
def test_train_phase_readers(train, monkeypatch, metric, phase):
    red, _, names = train
    mod = harness.load_module(
        os.path.join(REPO, "bench", "metrics", metric + ".py"),
        "bench_metric_" + metric)
    monkeypatch.setattr(scopes, "step_hlo",
                        lambda cell: _hlo(names["step_fn"]))
    calls = red.program_calls(readers.program_is("step_fn"))
    secs = scopes.split(red, names["step_fn"],
                        readers.program_is("step_fn"), scopes.phase)
    assert mod.read(_Run(red)) == pytest.approx(secs[phase] * 1e3 / calls)
    assert mod.read(_Run(None)) is None
    # a step without the phase scopes (the program before them) reads
    # nothing
    bare = {k: v.replace("/fwd_bwd", "").replace("/optimizer", "")
            for k, v in names["step_fn"].items()}
    monkeypatch.setattr(scopes, "step_hlo", lambda cell: _hlo(bare))
    assert mod.read(_Run(red)) is None
