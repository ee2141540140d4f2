"""From a profiler trace to device busy and idle time, per-operation and
per-program device time, and exposed collective time.

The runners record a window with ``jax.profiler.trace``; ``load`` reads
the ``.xplane.pb`` through ``jax.profiler.ProfileData`` into plain event
tuples; ``Reduced`` does the arithmetic on those tuples alone, so a trimmed
excerpt of a chip trace (tests/bench/data) checks it without a chip.

Device planes are those named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds one event per executed HLO operation and ``XLA Modules``
one per executed program (``jit_<function>(<id>)``).  Host spans that the
benchmark opens with ``jax.profiler.TraceAnnotation`` sit on the host
plane; the one named ``bench.window`` bounds the measured window.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv")
_SUFFIX = re.compile(r"[.\d]+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_MODULE_ID = re.compile(r"\(\d+\)$")

# (plane, line, name, start_ns, dur_ns)
Event = Tuple[str, str, str, int, int]
Interval = Tuple[int, int]


def load(xplane_path: str) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    out: List[Event] = []
    for plane in pd.planes:
        dev = _DEVICE.match(plane.name) is not None
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and ev.name != WINDOW and \
                        not ev.name.startswith("bench."):
                    continue
                name = ev.name
                if dev and line.name == OPS_LINE:
                    name = short_name(name)
                out.append((plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def short_name(hlo: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's name, and a custom call's target after it (the Pallas
    kernels are custom calls to ``tpu_custom_call``)."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    m = _TARGET.search(hlo)
    return f"{name} {m.group(1)}" if m else name


def find_xplane(path: str) -> str:
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def union(iv: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(iv: Iterable[Interval]) -> int:
    return sum(e - s for s, e in iv)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """a minus b, both unions (sorted, disjoint)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """a and b, both unions (sorted, disjoint)."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_kind(name: str) -> str:
    """An op's name without its instance number (fusion.123 -> fusion;
    closed_call.10 tpu_custom_call -> closed_call tpu_custom_call)."""
    head, _, target = name.partition(" ")
    head = _SUFFIX.sub("", head) or head
    return f"{head} {target}" if target else head


def program(name: str) -> str:
    """A module event's program name without its id (jit_f(12) -> jit_f)."""
    return _MODULE_ID.sub("", name)


class Reduced:
    """Device-side arithmetic over one traced window."""

    def __init__(self, events: List[Event]):
        wins = [(s, s + d) for p, l, n, s, d in events if n == WINDOW]
        if not wins:
            raise ValueError(f"trace holds no {WINDOW!r} span")
        self.lo, self.hi = wins[0]
        self.window_s = (self.hi - self.lo) / 1e9
        self.ops: Dict[str, List[Tuple[str, int, int]]] = \
            collections.defaultdict(list)
        self.modules: Dict[str, List[Tuple[str, int, int]]] = \
            collections.defaultdict(list)
        self.host: List[Tuple[str, int, int]] = []
        self.calls: Dict[str, List[Tuple[str, float]]] = \
            collections.defaultdict(list)
        for plane, line, name, s, d in events:
            e = s + d
            if e <= self.lo or s >= self.hi:
                continue
            cs, ce = max(s, self.lo), min(e, self.hi)
            if _DEVICE.match(plane):
                (self.ops if line == OPS_LINE else self.modules)[
                    plane].append((name, cs, ce))
                if line == MODULES_LINE and d > 0:
                    self.calls[plane].append((name, (ce - cs) / d))
            elif name != WINDOW:
                self.host.append((name, cs, ce))
        if not self.ops:
            raise ValueError("trace holds no device operation in the "
                             "window")
        self.devices = sorted(self.ops, key=lambda p: int(
            _DEVICE.match(p).group(1)))
        self._busy = {p: union((s, e) for _, s, e in self.ops[p])
                      for p in self.devices}

    # -- busy / idle ---------------------------------------------------------
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        return sum(length(b) for b in self._busy.values()) / 1e9 / len(
            self.devices)

    def idle_frac(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    # -- time by operation / program ------------------------------------------
    def op_s(self, pred: Callable[[str], bool],
             in_program: Optional[Callable[[str], bool]] = None) -> float:
        """Device seconds of operations whose name satisfies ``pred``
        (inside programs satisfying ``in_program``), averaged over the
        devices."""
        tot = 0
        for p in self.devices:
            iv = union((s, e) for n, s, e in self.ops[p] if pred(n))
            if in_program is not None:
                iv = intersect(iv, union((s, e) for n, s, e
                                         in self.modules[p]
                                         if in_program(program(n))))
            tot += length(iv)
        return tot / 1e9 / len(self.devices)

    def program_s(self, pred: Callable[[str], bool]) -> float:
        """Device seconds inside programs whose name satisfies ``pred``,
        counted where an operation runs, averaged over the devices."""
        tot = 0
        for p in self.devices:
            spans = union((s, e) for n, s, e in self.modules[p]
                          if pred(program(n)))
            tot += length(intersect(spans, self._busy[p]))
        return tot / 1e9 / len(self.devices)

    def program_calls(self, pred: Callable[[str], bool]) -> float:
        """Executions of programs satisfying ``pred`` in the window, a
        call cut by the window's edge counted by the share inside it,
        averaged over the devices."""
        return sum(f for p in self.devices for n, f in self.calls[p]
                   if pred(program(n))) / len(self.devices)

    def exposed_collective_s(self) -> float:
        """Seconds in collective operations during which no other
        operation runs on that device, averaged over the devices."""
        tot = 0
        for p in self.devices:
            coll = union((s, e) for n, s, e in self.ops[p]
                         if _COLLECTIVE.search(n))
            comp = union((s, e) for n, s, e in self.ops[p]
                         if not _COLLECTIVE.search(n))
            tot += length(subtract(coll, comp))
        return tot / 1e9 / len(self.devices)

    # -- what the next reader of the ledger sees ------------------------------
    def self_times(self, plane: str) -> Dict[str, int]:
        """Nanoseconds of each op kind on one device, without the time
        of the ops nested in it (a while loop holds its body's ops)."""
        out: Dict[str, int] = collections.Counter()
        stack: List[list] = []       # [name, end, nested ns]
        for n, s, e in sorted(self.ops[plane], key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][1] <= s:
                top = stack.pop()
                out[op_kind(top[0])] += top[3] - top[2]
            if stack:
                stack[-1][2] += e - s
            stack.append([n, e, 0, e - s])
        for top in stack:
            out[op_kind(top[0])] += top[3] - top[2]
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        by_op: Dict[str, int] = collections.Counter()
        for p in self.devices:
            by_op.update(self.self_times(p))
        nd = len(self.devices)
        ops = [[n, t / 1e9 / nd] for n, t in by_op.most_common(top)]
        dev0 = self.devices[0]
        gaps = subtract([(self.lo, self.hi)], self._busy[dev0])
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, cover = "no host span", 0
            for n, hs, he in self.host:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = n, c
            out.append([best, (e - s) / 1e9])
        return {"device_ops": ops, "idle_gaps": out}


def load_excerpt(path: str) -> List[Event]:
    """Events saved as gzipped JSON (the trimmed chip traces that the
    tests read)."""
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


class Recorder:
    """Profiler trace of the last part of a measured window, when ``on``.
    ``start`` begins the trace and opens the host span ``bench.window``;
    ``stop``, after the measured window, closes both and reduces the
    trace.  ``span`` opens further host spans for the gap attribution."""

    def __init__(self, on: bool, path: str):
        self.on = on
        self.path = path
        self._win = None

    def start(self) -> None:
        if self.on and self._win is None:
            import shutil

            import jax
            shutil.rmtree(self.path, ignore_errors=True)
            jax.profiler.start_trace(self.path)
            self._win = jax.profiler.TraceAnnotation(WINDOW)
            self._win.__enter__()

    def close_window(self) -> None:
        if self._win not in (None, False):
            self._win.__exit__(None, None, None)
            self._win = False

    def span(self, name: str):
        if self.on:
            import jax
            return jax.profiler.TraceAnnotation(name)
        import contextlib
        return contextlib.nullcontext()

    def stop(self) -> Optional["Reduced"]:
        """Ends the trace and reduces it; the trace files are removed."""
        if not self.on or self._win is None:
            return None
        import shutil

        import jax
        self.close_window()
        jax.profiler.stop_trace()
        try:
            return Reduced(load(find_xplane(self.path)))
        finally:
            shutil.rmtree(self.path, ignore_errors=True)
