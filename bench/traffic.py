"""Traffic from a mix file and a seed, and the open-loop serving loop.

A serving mix fixes the request count (rate x window) and draws one
schedule of gaps and sizes from the mix's own ``set_seed``; the run's
``--seed`` draws the token ids.  Every seed therefore offers the same
work at the same times (token ids do not change a dense model's work),
and two runs of one seed the same inputs.  A seed that reordered the
schedule moved the chat cell's TTFT p90 by 40 % between seeds, against
2-8 % between two runs of one seed.

``drive`` is the benchmark's copy of the program's workload loop
(``launch/serve.py::run_workload``), with one change: a request's time to
first token is counted from when it was due in the open-loop schedule,
not from when the loop got round to submitting it, so a stall is charged
to every request that queued behind it.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float
    prompt: List[int]
    max_new: int


def _lengths(spec: Dict[str, Any], n: int, rng) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    dist = spec["dist"]
    if dist == "lognormal":
        x = np.exp(np.log(spec["median"]) + spec["sigma"]
                   * rng.standard_normal(n))
    elif dist == "loguniform":
        x = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
    elif dist == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def schedule(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Request]:
    """Open-loop requests due in [0, seconds): Poisson arrivals at
    ``rate_per_s`` (exponential gaps scaled to fill the window exactly)
    and lengths from the mix's distributions, both from its
    ``set_seed``; token ids uniform from ``seed``."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    base = np.random.default_rng(mix["set_seed"])
    p_len = _lengths(mix["prompt"], n, base)
    o_len = _lengths(mix["output"], n, base)
    gaps = base.exponential(1.0, n)
    rng = np.random.default_rng(seed)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (
        seconds / gaps.sum())
    return [Request(float(due[i]),
                    rng.integers(0, vocab, int(p_len[i])).tolist(),
                    int(o_len[i]))
            for i in range(n)]


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int
                ) -> Dict[str, np.ndarray]:
    """The benchmark's copy of the program's ``data/pipeline.host_batch``:
    noisy successor sequences over a small alphabet, seeded by (seed,
    step).  Token values do not change a dense step's work."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    alpha = max(8, min(64, vocab // 4))
    start = rng.integers(0, alpha, size=(batch, 1))
    toks = (start + np.arange(seq + 1)[None, :]) % alpha
    noise = rng.random((batch, seq + 1)) < 0.02
    toks = np.where(noise, rng.integers(0, alpha, toks.shape), toks)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


@dataclasses.dataclass
class Served:
    """What the open-loop window measured."""
    window_s: float
    due: Dict[int, float]                  # rid -> due time (s)
    submitted: Dict[int, float]            # rid -> submit time
    admit_start: Dict[int, float]          # rid -> admission start
    tokens: Dict[int, List[float]]         # rid -> token times
    decode_lengths: List[tuple]            # (time, lengths), in window
    admitted: List[tuple]                  # (time, prompt length)
    n_decode_in_window: int
    rejected: int

    def ttfts(self) -> List[float]:
        return [self.tokens[r][0] - self.due[r] for r in self.due
                if self.tokens.get(r)]

    def itls(self) -> List[float]:
        out = []
        for ts in self.tokens.values():
            out += [b - a for a, b in zip(ts, ts[1:]) if b <= self.window_s]
        return out

    def queue_waits(self) -> List[float]:
        return [self.admit_start[r] - self.due[r] for r in self.due
                if r in self.admit_start]

    def lateness(self) -> List[float]:
        return [self.submitted[r] - self.due[r] for r in self.due]

    def missing(self) -> int:
        return sum(1 for r in self.due if not self.tokens.get(r))


def drive(srv, reqs: List[Request], seconds: float,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          span: Optional[Callable[[str], Any]] = None,
          marks: Optional[List[tuple]] = None,
          min_finished: int = 0, drain_s: float = 60.0) -> Served:
    """Offer ``reqs`` to ``srv`` open-loop for ``seconds``, then keep
    serving (with no new arrivals) until every request has its first
    token and ``min_finished`` of them have finished, or ``drain_s`` more
    seconds pass.

    ``srv`` needs ``submit(prompt, max_new) -> rid``, ``waiting``,
    ``active`` (bool array), ``pos`` (int array), ``admit_waiting()`` and
    ``decode_once()`` returning ``(kind, rid, value)`` events; an
    ``admit_t`` dict, when it has one, gives each admission's start.
    ``marks``: (time, function) pairs, each function called once when
    the window reaches its time."""
    import contextlib
    span = span or (lambda name: contextlib.nullcontext())
    pending = collections.deque(sorted(reqs, key=lambda r: r.due_s))
    out = Served(seconds, {}, {}, {}, {}, [], [], 0, 0)
    marks = sorted(marks or [], key=lambda m: m[0])
    finished = set()
    t0 = clock()
    admit_t = getattr(srv, "admit_t", None)
    if admit_t is not None:
        admit_t.clear()

    def now() -> float:
        return clock() - t0

    while True:
        t = now()
        while marks and t >= marks[0][0]:
            marks.pop(0)[1]()
        with span("bench.submit"):
            while pending and pending[0].due_s <= t:
                r = pending.popleft()
                rid = srv.submit(r.prompt, r.max_new)
                out.due[rid] = r.due_s
                out.submitted[rid] = now()
                out.tokens[rid] = []
        if t >= seconds and (t >= seconds + drain_s or (
                all(out.tokens[r] for r in out.due)
                and len(finished) >= min(min_finished, len(out.due)))):
            break
        if not (len(srv.waiting) or srv.active.any()):
            if not pending:
                if t >= seconds:
                    break
                sleep(min(0.001, seconds - t))
                continue
            with span("bench.idle"):
                sleep(min(0.001, max(0.0, pending[0].due_s - now())))
            continue
        with span("bench.admit"):
            evs = srv.admit_waiting()
        tb = now()
        lengths = [int(p) + 1 for p in srv.pos[srv.active]]
        with span("bench.decode"):
            dec = srv.decode_once()
        tc = now()
        if dec and tc <= seconds:
            out.decode_lengths.append((tc, lengths))
            out.n_decode_in_window += 1
        for batch, ts in ((evs, tb), (dec, tc)):
            for kind, rid, val in batch:
                if kind == "admit" and rid in out.due:
                    out.admitted.append((tb, int(srv.prompt_len[val])))
                elif kind == "token" and rid in out.tokens:
                    out.tokens[rid].append(ts)
                elif kind == "retire" and rid in out.due:
                    finished.add(rid)
                    out.rejected += val == "rejected"
    if admit_t is not None:
        out.admit_start = {r: admit_t[r] - t0 for r in out.due
                           if r in admit_t}
    return out


def percentile(xs: List[float], q: float) -> float:
    """The q-th percentile, linear between closest ranks (numpy's
    default); NaN for no samples."""
    return float(np.percentile(xs, q)) if xs else math.nan
