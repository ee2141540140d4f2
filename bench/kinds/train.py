"""Training cells: the program's ``TrainEngine`` step, fed by its
``BatchFeed``, on one chip.

Set-up builds one engine and one state (weights from the seed, made on
the device in one jitted call, with the optimizer state and f32 master
copy), and drives that state through its first three steps by the
window's own call and feed; those steps compile the program.  The program's
own readings of those steps (each step's loss, each leaf's first gradient
as the optimizer holds it, each leaf's change after three steps) are
taken before step 4 consumes the state.  The window then steps the same
object for ``--seconds``; once it closes, the plain reference repeats the
three steps in float32 and the readings are compared.
"""
from __future__ import annotations

import collections
import gc
import os
import time
from typing import Any, Dict

import numpy as np

from bench import traffic
from bench.harness import (TRACE_DIR, BenchError, Cell, Run, compiles, log,
                           peak_memory)
from bench.trace import Recorder

CHECK_STEPS = 3
IN_FLIGHT = 2          # steps dispatched ahead of the one waited for


def make_feed(cell: Cell):
    """The program's BatchFeed, producing the benchmark's own batches."""
    from repro.data.pipeline import BatchFeed, DataConfig

    mix, c = cell.mix, cell.hf
    seed = cell.seed

    class Feed(BatchFeed):
        def _produce(self) -> None:
            step = self._step
            while not self._stop.is_set():
                try:
                    item = (step, self._place(traffic.train_batch(
                        seed, step, mix["batch"], mix["seq_len"],
                        c["vocab_size"])))
                except BaseException as e:   # noqa: BLE001 — raised in get
                    item = (step, e)
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except Exception:        # queue.Full
                        continue
                if isinstance(item[1], BaseException):
                    return
                step += 1

    return Feed(DataConfig(seed=seed % 2 ** 32, vocab=c["vocab_size"],
                           seq_len=mix["seq_len"],
                           global_batch=mix["batch"]))


def make_engine(cell: Cell):
    """The program's engine on one device, with the mix's optimizer."""
    from repro.models.model import LM
    from repro.optim.adamw import AdamWConfig
    from repro.train.engine import EngineConfig, TrainEngine

    return TrainEngine(LM(cell.arch()), EngineConfig(
        optim=AdamWConfig(**cell.mix["optimizer"])))


def make_state(cell: Cell, eng):
    """Weights from the seed, the optimizer state and the f32 master
    copy, in one jitted call placed as the engine places its state."""
    import jax
    import jax.numpy as jnp

    from repro.optim import adamw

    ref = cell.reference()
    c = cell.hf

    def build(key):
        params = ref.build_params(c, key)
        return {"params": params, "opt": adamw.init_state(params),
                "master": jax.tree_util.tree_map(
                    lambda p: jnp.array(p, jnp.float32, copy=True),
                    params)}

    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                  eng.state_struct())
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                 jax.eval_shape(build, jax.random.PRNGKey(0)))
    if got != want:
        raise BenchError("the reference's weight layout differs from the "
                         "program's")
    return jax.jit(build)(ref.seed_key(cell.seed))


def leaf_norms(ref, tree, names) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(ref.get_leaf(t, n).astype(
            jnp.float32)))) for n in names]))
    return np.asarray(fn(tree), np.float64)


def change_norms(cell: Cell, master, names) -> np.ndarray:
    """Each leaf's ||master - initial weight||, drawing each initial leaf
    again from the seed (one leaf at a time).  The key is an argument, so
    that a new seed compiles nothing."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    key = ref.seed_key(cell.seed)
    out = []
    for n in names:
        fn = jax.jit(lambda m, k, n=n: jnp.sqrt(jnp.sum(jnp.square(
            m - ref.leaf_value(cell.hf, k, n).astype(jnp.float32)))))
        out.append(float(fn(ref.get_leaf(master, n), key)))
    return np.asarray(out, np.float64)


def program_readings(cell: Cell, eng, state, feed) -> tuple:
    """Drive the state through the first CHECK_STEPS steps by the
    window's call and feed; returns (state, readings)."""
    ref = cell.reference()
    names = ref.flat_names(cell.hf)
    b1 = cell.mix["optimizer"]["beta1"]
    losses = []
    grads = None
    for t in range(CHECK_STEPS):
        state, m = eng.step(state, feed.get())
        losses.append(float(m["loss"]))
        if t == 0:
            grads = leaf_norms(ref, state["opt"]["m"], names) / (1 - b1)
    change = change_norms(cell, state["master"], names)
    return state, {"loss": losses, "grad": grads, "change": change}


def reference_readings(cell: Cell, quant=None, half: bool = False
                       ) -> Dict[str, Any]:
    """The plain reference's three steps on the same batches (``quant``
    and ``half``: the control precision and the half-batch fault put in
    the program's place)."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    c, mix = cell.hf, cell.mix
    names = ref.flat_names(c)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    p = jax.jit(lambda k: f32(ref.build_params(c, k)))(
        ref.seed_key(cell.seed))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    state = {"p": p, "m": zeros(p), "v": zeros(p)}
    losses, grads = [], None
    for t in range(CHECK_STEPS):
        b = traffic.train_batch(cell.seed, t, mix["batch"],
                                mix["seq_len"], c["vocab_size"])
        state, lval, gn = ref.train_step(
            ref.freeze(c), ref.freeze(mix["optimizer"]),
            np.int32(t + 1), quant,
            half, state, b["tokens"], b["labels"])
        losses.append(float(lval))
        if t == 0:
            grads = np.asarray([float(ref.get_leaf(gn, n)) for n in names])
    change = change_norms(cell, state["p"], names)
    return {"loss": losses, "grad": grads, "change": change}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared.  Norms are compared leaf by leaf: the gap of
    the two norms over the larger of the reference's norm of that leaf and
    of the median leaf.  Leaves whose reference gradient is under a
    thousandth of the median leaf's (nought to rounding, as a key bias's
    under softmax) are left out of the change."""
    def worst(a, b, keep):
        den = np.maximum(b, np.median(b))
        return float(np.max((np.abs(a - b) / den)[keep]))

    g_ref = ref["grad"]
    moved = g_ref >= 1e-3 * np.median(g_ref)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["loss"])
                                        - np.asarray(ref["loss"])))),
        "grad_gap": worst(prog["grad"], g_ref, np.ones_like(moved)),
        "change_gap": worst(prog["change"], ref["change"], moved),
    }


def run(cell: Cell, devices, t_start: float) -> Run:
    import jax

    mix = cell.mix
    eng = make_engine(cell)
    state = make_state(cell, eng)
    feed = make_feed(cell)
    state, prog = program_readings(cell, eng, state, feed)
    rec = Recorder(cell.trace, os.path.join(TRACE_DIR, cell.name))
    trace_from = max(0.0, cell.seconds - mix["trace_seconds"])
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")
    inflight: collections.deque = collections.deque()
    steps = 0
    wait = 0.0
    c0 = compiles()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < cell.seconds:
        if time.perf_counter() - t0 >= trace_from:
            rec.start()
        with rec.span("bench.feed"):
            ta = time.perf_counter()
            batch = feed.get()
            wait += time.perf_counter() - ta
        with rec.span("bench.step"):
            state, m = eng.step(state, batch)
        steps += 1
        inflight.append(m["loss"])
        if len(inflight) > IN_FLIGHT:
            with rec.span("bench.sync"):
                inflight.popleft().block_until_ready()
    jax.block_until_ready(state)
    window = time.perf_counter() - t0
    rec.close_window()
    n_compiles = compiles() - c0
    reduced = rec.stop()
    feed.close()
    peak = peak_memory(devices)
    tokens = steps * mix["batch"] * mix["seq_len"]
    log(f"train: {steps} steps of {mix['batch']} x {mix['seq_len']} in "
        f"{window:.3f} s; data wait {wait:.3f} s; {n_compiles} compiles; "
        f"losses {[round(x, 4) for x in prog['loss']]}")
    records = {"steps": steps, "tokens": tokens, "data_wait_s": wait,
               "compiles": n_compiles,
               "window_s": window, "setup_s": setup_s,
               "trace_from_s": trace_from}
    del state, eng, batch, m, inflight
    gc.collect()
    t = time.perf_counter()
    got = compare(prog, reference_readings(cell))
    log(f"reference, {CHECK_STEPS} steps: {time.perf_counter() - t:.3f} s")
    checks = [(k, v, cell.limits[k]) for k, v in got.items()]
    return Run(
        cell=cell, attempted=steps, failed=0,
        metrics={"train_tok_s_per_chip": tokens / window / cell.chips,
                 "setup_s": setup_s},
        checks=checks, records=records, window_s=window,
        devices=list(devices), memory_peak_bytes=peak, trace=reduced)
