"""Device time of a jitted program by the program's own ``jax.named_scope``.

A trace's ``XLA Ops`` events name each executed HLO instruction
(``fusion.123``); the compiled program's HLO text gives each instruction
its ``op_name`` metadata, the jax name stack it was traced under
(``jit(step_fn)/fwd_bwd/transpose(jvp(LM.loss))/while/body/...``).  The
join of the two on the instruction's name, within one program, gives the
device time of each scope.

The training step (``train/engine.py``) marks its phases with the scopes
``fwd_bwd``, ``grad_sync`` and ``optimizer``.  An op under ``optimizer``
is the optimizer's; one under ``fwd_bwd`` is the backward pass's where
its op_name holds ``transpose(`` (so is the recompute of a
rematerialised layer, traced under the transpose as
``.../transpose(jvp(...))/.../rematted_computation/...``), else the
forward pass's.  Ops are counted by self time (an op's time less that of
the ops nested in it), so a layer scan's ``while`` does not count its
body twice.
"""
from __future__ import annotations

import bisect
import collections
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

from bench import readers, trace

PHASES = ("forward", "backward", "optimizer", "grad_sync", "unscoped",
          "compiler", "unmatched")

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s')
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name, over every computation of an HLO
    module's text (instruction names are unique within a module); "" for
    an instruction the compiler put in without one (a layout ``copy``
    or ``reshape``, an async ``copy-start``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line, m.end())
            out[m.group(1)] = op.group(1) if op else ""
    return out


def scope_parts(op_name: str) -> List[str]:
    """An op_name's parts, each without the transformations wrapped round
    it: a scope entered under autodiff reads ``transpose(jvp(lm_head))``
    in the name stack, and is ``lm_head`` here."""
    out = []
    for p in op_name.split("/"):
        while p.endswith(")") and "(" in p and not p.startswith("jit("):
            p = p[p.index("(") + 1:-1]
        out.append(p)
    return out


def phase(op_name: str) -> str:
    """The training step's phase of an op, from its op_name ("" for an
    op the compiler put in: "compiler")."""
    if not op_name:
        return "compiler"
    parts = scope_parts(op_name)
    if "optimizer" in parts:
        return "optimizer"
    if "fwd_bwd" in parts:
        return "backward" if "transpose(" in op_name else "forward"
    if "grad_sync" in parts:
        return "grad_sync"
    return "unscoped"


def _inside(spans: List[Tuple[int, int]]) -> Callable[[int], bool]:
    starts = [s for s, _ in spans]

    def test(t: int) -> bool:
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]
    return test


def self_ns(red: "trace.Reduced", plane: str,
            in_program: Callable[[str], bool]) -> Dict[str, int]:
    """Nanoseconds of each op instance on one device, less the time of
    the ops nested in it, over the ops that start inside the programs
    satisfying ``in_program``."""
    inside = _inside(trace.union(
        (s, e) for n, s, e in red.modules[plane]
        if in_program(trace.program(n))))
    out: Dict[str, int] = collections.Counter()
    stack: List[list] = []       # [name, end, nested ns, own ns]
    ops = sorted(((n, s, e) for n, s, e in red.ops[plane] if inside(s)),
                 key=lambda x: (x[1], -x[2]))
    for n, s, e in ops:
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[0]] += top[3] - top[2]
        if stack:
            stack[-1][2] += e - s
        stack.append([n, e, 0, e - s])
    for top in stack:
        out[top[0]] += top[3] - top[2]
    return out


def split(red: "trace.Reduced", names: Dict[str, str],
          in_program: Callable[[str], bool],
          key: Callable[[str], str]) -> Dict[str, float]:
    """Device seconds of the ops in ``in_program`` by ``key(op_name)``,
    averaged over the devices; an op not in ``names`` counts under
    ``"unmatched"``.  An op's name in the trace may carry its custom
    call's target after a space (``flash_decode.1 tpu_custom_call``)."""
    out: Dict[str, float] = collections.Counter()
    for p in red.devices:
        for n, ns in self_ns(red, p, in_program).items():
            op = names.get(n.partition(" ")[0])
            out["unmatched" if op is None else key(op)] += ns
    return {k: v / 1e9 / len(red.devices) for k, v in out.items()}


def step_hlo(cell) -> str:
    """The compiled HLO text of the cell's training step, as the train
    runner builds it (its engine on a batch of the mix's shape).  The
    compile is found in the benchmark's persistent cache, where the run
    put it."""
    import jax
    import jax.numpy as jnp

    from bench import harness
    kind = harness.load_module(
        os.path.join(cell.root, "bench", "kinds", "train.py"),
        "bench_kind_train")
    b = jax.ShapeDtypeStruct((cell.mix["batch"], cell.mix["seq_len"]),
                             jnp.int32)
    eng = kind.make_engine(cell)
    return eng.lower_step({"labels": b, "tokens": b}).as_text()


# (run, its phases): the three phase readers of one run share one compile
_LAST: list = [None, None]


def train_phases(run) -> Optional[Dict[str, float]]:
    """Milliseconds of device self time per training step in each of
    PHASES ("unscoped": ops with an op_name outside the phase scopes;
    "compiler": ops the compiler put in without one; "unmatched": ops
    the compiled step does not name), over the traced window; None
    without a trace, without step calls in it, or when the step carries
    no phase scopes (a program without them)."""
    if run.trace is None:
        return None
    if _LAST[0] is not run:
        _LAST[:] = [run, _phases(run)]
    return _LAST[1]


def _phases(run) -> Optional[Dict[str, float]]:
    in_step = readers.program_is("step_fn")
    calls = run.trace.program_calls(in_step)
    if calls <= 0:
        return None
    names = op_names(step_hlo(run.cell))
    if not any("fwd_bwd" in v.split("/") for v in names.values()):
        return None
    secs = split(run.trace, names, in_step, phase)
    return {k: secs.get(k, 0.0) * 1e3 / calls for k in PHASES}
