"""The trace reduction of bench/trace.py, on trimmed excerpts of traces
recorded on a TPU v5e (tests/bench/data/*_trace_excerpt.json.gz: a
250 ms slice of the chat cell's window and a 900 ms slice of the
one-chip training window), checked against brute-force counts."""
import os

import numpy as np
import pytest

from conftest import DATA

from bench import readers, trace


def _excerpt(name):
    return trace.load_excerpt(os.path.join(DATA, name +
                                           "_trace_excerpt.json.gz"))


def _brute_union_ns(intervals, lo, hi):
    """Covered nanoseconds, by marking a 1 us grid."""
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // 1000:(e - lo) // 1000] = True
    return grid.sum() * 1000


def test_interval_arithmetic():
    a = trace.union([(0, 10), (5, 20), (30, 40)])
    assert a == [(0, 20), (30, 40)]
    assert trace.subtract(a, [(2, 3), (15, 35)]) == [(0, 2), (3, 15),
                                                     (35, 40)]
    assert trace.intersect(a, [(10, 32)]) == [(10, 20), (30, 32)]
    assert trace.length(a) == 30


def test_short_names_keep_the_kernel_target():
    hlo = ('%closed_call.10 = bf16[64,2,6,128]{3,2,1,0} custom-call(s32[64]'
           '{0} %a), custom_call_target="tpu_custom_call", operand_layout')
    assert trace.short_name(hlo) == "closed_call.10 tpu_custom_call"
    assert trace.short_name("%fusion.12 = f32[8]{0} fusion(%p)") == \
        "fusion.12"
    assert trace.op_kind("closed_call.10 tpu_custom_call") == \
        "closed_call tpu_custom_call"


@pytest.mark.parametrize("name", ["chat", "train"])
def test_busy_matches_a_brute_force_count(name):
    ev = _excerpt(name)
    r = trace.Reduced(ev)
    ops = [(s, s + d) for p, l, n, s, d in ev if l == trace.OPS_LINE]
    brute = _brute_union_ns(ops, r.lo, r.hi)
    assert r.busy_s() == pytest.approx(brute / 1e9, abs=2e-5 * len(ops))
    assert 0.0 < r.idle_frac() < 1.0
    # ops by self time add up to the busy time
    assert sum(r.self_times(r.devices[0]).values()) / 1e9 == \
        pytest.approx(r.busy_s())


def test_decode_kernel_time_is_the_custom_calls_in_decode_programs():
    ev = _excerpt("chat")
    r = trace.Reduced(ev)
    decode = [(s, s + d) for p, l, n, s, d in ev
              if l == trace.MODULES_LINE
              and trace.program(n) == "jit_decode_fn"]
    kern = 0
    for p, l, n, s, d in ev:
        if l == trace.OPS_LINE and n.endswith(" tpu_custom_call"):
            if any(a <= s and s + d <= b for a, b in decode):
                kern += min(s + d, r.hi) - max(s, r.lo)
    got = r.op_s(readers.is_kernel, readers.program_is("decode_fn"))
    assert got > 0
    assert got == pytest.approx(kern / 1e9, rel=1e-6)
    # prefill and decode programs share the device time, never more
    both = (r.program_s(readers.program_is("decode_fn"))
            + r.program_s(readers.program_is("prefill_fn")))
    assert both <= r.busy_s() + 1e-9


def test_training_idle_gap_is_attributed_to_the_host_span():
    r = trace.Reduced(_excerpt("train"))
    calls = r.program_calls(readers.program_is("step_fn"))
    # 900 ms of 332 ms steps, the last ended early in the slice
    assert 1.0 < calls < 3.0
    gaps = r.breakdown()["idle_gaps"]
    assert gaps[0][0] == "bench.sync" and gaps[0][1] > 0.3
    assert r.exposed_collective_s() == 0.0


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            jnp.ones((8, 8)).sum().block_until_ready()
    ev = trace.load(trace.find_xplane(str(tmp_path)))
    assert any(n == trace.WINDOW for p, l, n, s, d in ev)
    with pytest.raises(ValueError, match="no device operation"):
        trace.Reduced(ev)          # a CPU holds no TPU device plane
