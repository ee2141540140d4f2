"""The join of trace ops to the program's named scopes (bench/scopes.py):
the phases of a tiny engine step compiled on the CPU, and the parse of
HLO text."""
import collections

import jax
import jax.numpy as jnp
import pytest

from bench import scopes


@pytest.fixture(scope="module")
def tiny_engine():
    from repro.configs.base import get_arch
    from repro.models.model import LM
    from repro.train.engine import EngineConfig, TrainEngine
    return lambda n_micro: TrainEngine(
        LM(get_arch("qwen2-1.5b").reduced()),
        EngineConfig(microbatches=n_micro))


@pytest.mark.parametrize("n_micro", [1, 2])
def test_engine_step_phases(tiny_engine, n_micro):
    b = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    text = tiny_engine(n_micro).lower_step(
        {"labels": b, "tokens": b}).as_text()
    # a reducer's own instructions carry a partial name stack and never
    # run as ops of their own
    ops = [op for op in scopes.op_names(text).values()
           if op.startswith("jit(step_fn)/")]
    by = collections.defaultdict(list)
    for op in ops:
        by[scopes.phase(op)].append(op)
    assert {"forward", "backward", "optimizer"} <= set(by)
    assert all("/fwd_bwd/" in op and "transpose(" in op
               for op in by["backward"])
    assert all("/fwd_bwd/" in op and "transpose(" not in op
               for op in by["forward"])
    assert all("/optimizer/" in op for op in by["optimizer"])
    # the recompute of the rematerialised layers is the backward pass's
    remat = [op for op in ops if "rematted_computation" in op]
    assert remat and all(scopes.phase(op) == "backward" for op in remat)
    # the model's own scopes sit inside the step's phases
    assert any("mlp" in scopes.scope_parts(op) for op in by["forward"])
    assert any("lm_head" in scopes.scope_parts(op) for op in by["backward"])


def test_op_names_reads_every_instruction_form():
    text = "\n".join([
        "HloModule jit_step_fn, is_scheduled=true",
        '  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(step_fn)/'
        'optimizer/mul" source_file="x.py" source_line=3}',
        '  ROOT tuple.4 = (f32[8]{0}) tuple(%fusion.12), '
        'metadata={op_name="jit(step_fn)/fwd_bwd/jvp()/add"}',
        "  %param.1 = f32[8]{0} parameter(0)",
        '  %flash_decode.1 = bf16[8]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(decode_fn)/while/body/attn/pallas_call"}',
    ])
    assert scopes.op_names(text) == {
        "fusion.12": "jit(step_fn)/optimizer/mul",
        "tuple.4": "jit(step_fn)/fwd_bwd/jvp()/add",
        "param.1": "",          # put in without an op_name
        "flash_decode.1": "jit(decode_fn)/while/body/attn/pallas_call",
    }


@pytest.mark.parametrize("op,want", [
    ("jit(step_fn)/fwd_bwd/jvp(LM.loss)/while/body/mlp/dot_general",
     "forward"),
    ("jit(step_fn)/fwd_bwd/transpose(jvp(LM.loss))/while/body/mlp/dot",
     "backward"),
    ("jit(step_fn)/fwd_bwd/transpose(jvp(LM.loss))/while/body/checkpoint/"
     "rematted_computation/attn/dot_general", "backward"),
    ("jit(step_fn)/optimizer/sqrt", "optimizer"),
    ("jit(step_fn)/grad_sync/optimization_barrier", "grad_sync"),
    ("jit(step_fn)/convert_element_type", "unscoped"),
    ("", "compiler"),
])
def test_phase_of_an_op_name(op, want):
    assert scopes.phase(op) == want


def test_scope_parts_unwrap_autodiff():
    assert scopes.scope_parts(
        "jit(step_fn)/fwd_bwd/transpose(jvp(lm_head))/dot_general") == [
        "jit(step_fn)", "fwd_bwd", "lm_head", "dot_general"]
