"""Serving cells: open-loop traffic through the program's continuous-
batching server (``runtime/serve.Server``), on the kernel path.

Set-up makes the weights from the seed on the device, builds the server,
and warms the programs this traffic uses: slot reset, one prefill chunk
shape, first-token sampling and the pool-wide decode step.  The window
offers the mix's requests open-loop; once it closes, the served tokens
of a seeded sample of finished requests (the longest among them) are
checked against the plain reference's full forward pass.
"""
from __future__ import annotations

import gc
import os
import time
from typing import Dict, List

import numpy as np

from bench import traffic
from bench.harness import (TRACE_DIR, BenchError, Cell, Run, compiles, log,
                           peak_memory)
from bench.trace import Recorder


def make_server(cell: Cell, params):
    from repro.models.model import LM
    from repro.runtime.serve import ServeConfig, Server

    class TimedServer(Server):
        """The program's server, keeping each request's prompt and
        stamping the start of each admission on the benchmark's clock."""

        def submit(self, prompt, max_new_tokens=None):
            rid = super().submit(prompt, max_new_tokens)
            self.prompts[rid] = prompt
            return rid

        def _admit(self, req, slot, method="chunked"):
            self.admit_t[req.rid] = time.perf_counter()
            return super()._admit(req, slot, method)

    mix = cell.mix
    srv = TimedServer(LM(cell.arch()), params, ServeConfig(
        slots=mix["slots"], max_len=mix["max_len"],
        prefill_chunk=mix["prefill_chunk"]))
    srv.admit_t, srv.prompts = {}, {}
    return srv


def check_layout(cell: Cell, params) -> None:
    """The weights the benchmark made have the program's own layout."""
    import jax

    from repro.models.model import LM
    want = jax.eval_shape(LM(cell.arch()).init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise BenchError("the reference's weight layout differs from the "
                         "program's")


def warm(srv, chunk: int) -> None:
    """Compile every program the window will call: a two-chunk prompt
    (reset, prefill, first-token sampling) and a few decode steps."""
    srv.submit(list(range(1, chunk + 2)), 3)
    while srv.waiting or srv.active.any():
        srv.admit_waiting()
        srv.decode_once()
    np.asarray(srv.cache["pos"])


def served_sample(cell: Cell, srv, served: traffic.Served) -> List[tuple]:
    """A seeded sample of finished requests, the longest among them:
    (prompt, served tokens) pairs."""
    done = [r for r in served.due
            if srv.finished.get(r) in ("length", "max_len")
            and srv.outputs.get(r)]
    if not done:
        return []
    prompts = srv.prompts
    size = lambda r: len(prompts[r]) + len(srv.outputs[r])
    longest = max(done, key=size)
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng(cell.seed)
    k = min(len(rest), cell.mix["check_requests"] - 1)
    pick = [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                     replace=False)]
    return [(prompts[r], list(srv.outputs[r])) for r in pick]


def reference_gaps(cell: Cell, sample: List[tuple], control: bool = False
                   ) -> Dict[str, float]:
    """Widest gap, over every served token of the sample, between the
    reference's best logit and its logit of the served token.  With
    ``control``, also the widest such gap of the token that the
    reference computed in float8 puts first, and of each served token
    altered to the next id."""
    import functools

    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    c = cell.hf
    params = ref.make_params(c, cell.seed)
    n = cell.mix["max_len"]
    m = cell.mix["output"]["max"]

    @functools.partial(jax.jit, static_argnums=(4,))
    def gaps(params, toks, pos, served, with_control):
        lg = ref.logits(c, params, toks, rows=pos)       # [m, V]
        best = lg.max(-1)
        out = {"served": best - jnp.take_along_axis(
            lg, served[:, None], -1)[:, 0]}
        if with_control:
            # a token altered where it is produced
            out["fault_token"] = best - jnp.take_along_axis(
                lg, (served[:, None] + 1) % lg.shape[-1], -1)[:, 0]
            lq = ref.logits(c, params, toks, ref.fp8_round, rows=pos)
            pick = jnp.argmax(lq, -1)
            out["control"] = best - jnp.take_along_axis(
                lg, pick[:, None], -1)[:, 0]
        return out

    worst: Dict[str, float] = {}
    for prompt, outs in sample:
        seq = prompt + outs[:-1]
        toks = np.zeros((n,), np.int32)
        toks[:len(seq)] = seq
        pos = np.full((m,), len(prompt) - 1, np.int32)
        pos[:len(outs)] = np.arange(len(prompt) - 1, len(seq))
        served = np.zeros((m,), np.int32)
        served[:len(outs)] = outs
        res = jax.device_get(gaps(params, toks, pos, served, control))
        for k, v in res.items():
            worst[k] = max(worst.get(k, 0.0), float(np.max(v[:len(outs)])))
    return worst


def setup(cell: Cell, devices):
    """The warmed server, with the weights drawn from the cell's seed."""
    from repro.kernels.ops import _default_interpret

    mix, c = cell.mix, cell.hf
    params = cell.reference().make_params(c, cell.seed)
    check_layout(cell, params)
    srv = make_server(cell, params)
    del params
    if devices[0].platform == "tpu" and (
            srv.model.attn_impl != "pallas" or _default_interpret()):
        raise BenchError("the server is not on the compiled kernel path")
    warm(srv, mix["prefill_chunk"])
    return srv


def finish(srv) -> None:
    """Serve what is left, with no new arrivals, until the pool is idle."""
    while len(srv.waiting) or srv.active.any():
        srv.admit_waiting()
        srv.decode_once()


def run(cell: Cell, devices, t_start: float) -> Run:
    c = cell.hf
    srv = setup(cell, devices)
    reqs = traffic.schedule(cell.mix, cell.seed, cell.seconds,
                            c["vocab_size"])
    srv.prompts.clear()
    rec = Recorder(cell.trace, os.path.join(TRACE_DIR, cell.name))
    trace_from = max(0.0, cell.seconds - cell.mix["trace_seconds"])
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")
    c0 = compiles()
    served = traffic.drive(
        srv, reqs, cell.seconds, span=rec.span,
        marks=[(trace_from, rec.start), (cell.seconds, rec.close_window)],
        min_finished=cell.mix["check_requests"])
    n_compiles = compiles() - c0
    reduced = rec.stop()
    peak = peak_memory(devices)
    late = served.lateness()
    log(f"generator: {len(served.due)} requests due, lateness p50 "
        f"{traffic.percentile(late, 50) * 1e3:.3f} ms, max "
        f"{max(late) * 1e3:.3f} ms; {served.n_decode_in_window} decode "
        f"steps in the window; {n_compiles} compiles")
    sample = served_sample(cell, srv, served)
    del srv
    gc.collect()
    checks = []
    if sample:
        t = time.perf_counter()
        gap = reference_gaps(cell, sample)["served"]
        log(f"reference over {sum(len(o) for _, o in sample)} served "
            f"tokens of {len(sample)} requests: "
            f"{time.perf_counter() - t:.3f} s")
        checks.append(("served_gap", gap, cell.limits["served_gap"]))
    ttft = [t * 1e3 for t in served.ttfts()]
    itl = [t * 1e3 for t in served.itls()]
    return Run(
        cell=cell, attempted=len(served.due),
        failed=served.rejected + served.missing(),
        metrics={"ttft_p90_ms": traffic.percentile(ttft, 90),
                 "itl_p95_ms": traffic.percentile(itl, 95),
                 "setup_s": setup_s},
        checks=checks, records={"served": served, "setup_s": setup_s,
                                "compiles": n_compiles,
                                "trace_from_s": trace_from},
        window_s=cell.seconds, devices=list(devices),
        memory_peak_bytes=peak, trace=reduced)
