"""Solver tests: DP optimality vs brute force, the paper's §2.2 numbers,
hybrid-beats-pure claims, Theorem-2 commutativity."""
import itertools
import random

import pytest
from _hypothesis_compat import given, settings, st

from repro.core.builders import mlp_graph
from repro.core.cost import graph_cost
from repro.core.graph import Graph
from repro.core.solver import (MeshAxis, assignment_cost_naive,
                               solve_mesh_many,
                               canonical_mp_assignment, composed_cost,
                               data_parallel_assignment, solve_mesh,
                               solve_one_cut, solve_one_cut_bruteforce)
from repro.core.tiling import Part, REPLICATE

AXES16 = [MeshAxis(f"c{i}", 2) for i in range(4)]


def random_chain_graph(rng: random.Random, n_layers: int) -> Graph:
    """Random einsum chain with a couple of ewise ops."""
    g = Graph("rand", allow_uneven=True)
    widths = [rng.choice([8, 16, 32]) for _ in range(n_layers + 1)]
    batch = rng.choice([8, 16])
    g.tensor("x0", ("batch", "h0"), (batch, widths[0]), 4.0, kind="input")
    for l in range(1, n_layers + 1):
        g.tensor(f"W{l}", (f"h{l-1}", f"h{l}"),
                 (widths[l - 1], widths[l]), 4.0, kind="weight")
        g.tensor(f"x{l}", ("batch", f"h{l}"), (batch, widths[l]), 4.0)
        g.einsum(f"mm{l}", f"x{l-1}", f"W{l}", f"x{l}")
        if rng.random() < 0.5:
            g.tensor(f"a{l}", ("batch", f"h{l}"), (batch, widths[l]), 4.0)
            g.ewise(f"act{l}", (f"x{l}",), f"a{l}")
    return g


class TestOptimality:
    @pytest.mark.parametrize("seed", range(6))
    def test_dp_matches_bruteforce(self, seed):
        rng = random.Random(seed)
        g = random_chain_graph(rng, rng.randint(1, 3))
        for arity in (2, 4):
            exact = solve_one_cut_bruteforce(g, arity, mem_scale=1.0)
            dp = solve_one_cut(g, arity, mem_scale=1.0)
            dp_total = graph_cost(g, dp.assignment, arity, mem_scale=1.0)
            assert dp_total == pytest.approx(exact.cost, rel=1e-9), (
                f"seed={seed} arity={arity}")

    def test_dp_cost_equals_assignment_cost(self):
        g = mlp_graph(batch=64, hidden=[32, 32, 32])
        sol = solve_one_cut(g, 2, mem_scale=1.0)
        assert sol.cost == pytest.approx(
            graph_cost(g, sol.assignment, 2, mem_scale=1.0), rel=1e-9)

    def test_fixed_pins_respected(self):
        g = mlp_graph(batch=64, hidden=[32, 32])
        fixed = {"W1": Part("h0")}
        sol = solve_one_cut(g, 2, fixed=fixed)
        assert sol.assignment["W1"] == Part("h0")


class TestPaperSection22:
    """The paper's §2.2 example: 5-layer MLP, 300 neurons, batch 400,
    16 GPUs => DP 57.6 MB, MP 76.8 MB, hybrid 33.6 MB."""

    def setup_method(self):
        self.g = mlp_graph(batch=400, hidden=[300] * 6)
        self.dp = data_parallel_assignment(self.g)
        self.mp = canonical_mp_assignment(self.g)

    def test_data_parallel_57_6(self):
        c = assignment_cost_naive(self.g, AXES16, [self.dp] * 4)
        assert c / 1e6 == pytest.approx(57.6)

    def test_model_parallel_76_8(self):
        c = assignment_cost_naive(self.g, AXES16, [self.mp] * 4)
        assert c / 1e6 == pytest.approx(76.8)

    def test_hybrid_33_6(self):
        per_axis = [self.dp, self.dp, self.mp, self.mp]
        c = assignment_cost_naive(self.g, AXES16, per_axis)
        assert c / 1e6 == pytest.approx(33.6)

    def test_solver_beats_hand_hybrid(self):
        sol = solve_mesh(self.g, AXES16, mem_scale=0.0)
        hybrid = composed_cost(self.g, AXES16,
                               [self.dp, self.dp, self.mp, self.mp])
        dp = composed_cost(self.g, AXES16, [self.dp] * 4)
        mp = composed_cost(self.g, AXES16, [self.mp] * 4)
        assert sol.total_bytes <= hybrid + 1e-6
        assert sol.total_bytes < min(dp, mp)

    def test_flipped_shapes_favor_mp(self):
        # §2.2: "if the batch size is 300 while the layer size is 400,
        # model parallelism becomes better"
        g2 = mlp_graph(batch=300, hidden=[400] * 6)
        dp = assignment_cost_naive(
            g2, AXES16, [data_parallel_assignment(g2)] * 4)
        mp = assignment_cost_naive(
            g2, AXES16, [canonical_mp_assignment(g2)] * 4)
        assert mp < dp


class TestCommutativity:
    """Theorem 2/3: composition of cuts is commutative — reordering the
    per-axis assignments of a composed tiling does not change its total
    cost (binary axes)."""

    @pytest.mark.parametrize("seed", range(4))
    def test_reorder_invariance(self, seed):
        rng = random.Random(100 + seed)
        g = random_chain_graph(rng, 2)
        axes = [MeshAxis("a", 2), MeshAxis("b", 2)]
        a1 = data_parallel_assignment(g)
        sol = solve_one_cut(g, 2, mem_scale=0.0)
        a2 = sol.assignment
        c12 = composed_cost(g, axes, [a1, a2])
        c21 = composed_cost(g, axes, [a2, a1])
        assert c12 == pytest.approx(c21, rel=1e-6)


class TestCostTableMemoization:
    def test_out_of_op_form_tensors_keep_distinct_signatures(self):
        """Custom forms may reference tensors outside the op; they are
        feasibility-checked (not priced), so two ops differing only in
        such a tensor's cuttability must not share one cached table."""
        from repro.core.cost import (cached_cost_table, op_cost,
                                     tensor_tiling_choices)
        g = Graph("t")
        g.tensor("h1", ("p",), (8,), 4.0)   # cuttable at arity 2
        g.tensor("h2", ("p",), (7,), 4.0)   # not cuttable
        for i, h in ((1, "h1"), (2, "h2")):
            g.tensor(f"x{i}", ("p",), (8,), 4.0)
            g.tensor(f"y{i}", ("p",), (8,), 4.0)
            g.custom(f"c{i}", (f"x{i}",), f"y{i}",
                     [({f"x{i}": Part("p"), f"y{i}": Part("p"),
                        h: Part("p")}, 0.0)])
        cache = {}
        choices = {t: tensor_tiling_choices(g, t, 2) for t in g.tensors}
        for op in g.ops:
            tbl = cached_cost_table(g, op, 2, choices, cache)
            tensors = g.op_tensors(op)
            for combo, base in tbl.items():
                assign = {t: choices[t][ci]
                          for t, ci in zip(tensors, combo)}
                assert base * op.repeat == pytest.approx(
                    op_cost(g, op, assign, 2)), (op.name, combo)
        assert len(cache) == 2


class TestParallelHelpers:
    """concurrent.futures fan-out must agree with the sequential paths."""

    def test_solve_mesh_many_matches_sequential(self):
        g = mlp_graph(batch=64, hidden=[32, 32, 32])
        jobs = [(g, [MeshAxis("a", 2), MeshAxis("b", 2)]),
                (g, [MeshAxis("a", 4)])]
        par = solve_mesh_many(jobs, workers=2, mem_scale=0.0)
        seq = [solve_mesh(gg, ax, mem_scale=0.0) for gg, ax in jobs]
        for p, s in zip(par, seq):
            assert p.total_bytes == pytest.approx(s.total_bytes)
            assert p.per_axis == s.per_axis

    def test_bruteforce_workers_match_serial(self):
        g = random_chain_graph(random.Random(7), 2)
        ser = solve_one_cut_bruteforce(g, 2, mem_scale=1.0, workers=0)
        par = solve_one_cut_bruteforce(g, 2, mem_scale=1.0, workers=2)
        assert par.cost == pytest.approx(ser.cost)

    def test_capacity_workers_match_sequential(self):
        from repro.core.solver import solve_mesh_capacity
        g = mlp_graph(batch=64, hidden=[64, 64, 64])
        axes = [MeshAxis("a", 2), MeshAxis("b", 2)]
        seq = solve_mesh_capacity(g, axes, beam=500)
        par = solve_mesh_capacity(g, axes, beam=500, workers=2)
        assert par.total_bytes == pytest.approx(seq.total_bytes)

    def test_workers_are_spawned_and_import_no_jax(self):
        """A worker must not inherit or open the parent's accelerator: the
        pool spawns (never forks), and what a worker imports to run a
        solver job pulls in no JAX."""
        import os
        import subprocess
        import sys

        from repro.core.solver import _process_pool
        with _process_pool(1) as ex:
            assert ex._mp_context.get_start_method() == "spawn"
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys; import repro.core.solver; "
             "print('jax' in sys.modules)"],
            env=dict(os.environ, PYTHONPATH=src), capture_output=True,
            text=True, check=True).stdout
        assert out.strip() == "False"


class TestMeshSolve:
    def test_monotone_axes(self):
        """More devices never decrease the solver's total bytes."""
        g = mlp_graph(batch=64, hidden=[64, 64, 64])
        c2 = solve_mesh(g, [MeshAxis("a", 2)]).total_bytes
        c4 = solve_mesh(g, [MeshAxis("a", 2), MeshAxis("b", 2)]).total_bytes
        assert c4 >= c2 - 1e-9

    def test_zero_cost_trivial_mesh(self):
        g = mlp_graph(batch=64, hidden=[64, 64])
        sol = solve_mesh(g, [MeshAxis("a", 1)])
        assert sol.total_bytes == 0.0


class TestComputeTerm:
    """Kernel-aware compute cost term (core/costterms.ComputeTerm)."""

    def _cc(self):
        from repro.core.costterms import ComputeConfig
        return ComputeConfig(peak_flops=1e12, calibration=1.3)

    def test_alignment_factor(self):
        from repro.core.costterms import alignment_factor
        assert alignment_factor(128, 128) == pytest.approx(1.0)
        assert alignment_factor(256, 128) == pytest.approx(1.0)
        assert alignment_factor(64, 128) == pytest.approx(2.0)
        assert alignment_factor(192, 128) == pytest.approx(256 / 192)
        assert alignment_factor(0, 128) == 1.0
        # misaligned shards always pay >= 1
        for n in (1, 3, 7, 100, 129, 1000):
            assert alignment_factor(n, 8) >= 1.0

    def test_penalties_nonnegative_and_einsum_only(self):
        g = mlp_graph(batch=64, hidden=[48, 64], with_backward=True)
        term = self._cc().term_for_axis(50e9, 4)
        pen = term.penalties(g, 4)
        assert pen   # einsum outputs got priced
        outs = {op.output for op in g.ops if op.kind == "einsum"}
        assert set(pen) <= outs
        from repro.core.costterms import alignment_factor
        from repro.core.tiling import Part
        for t, per in pen.items():
            assert all(v >= 0.0 for v in per.values())
            # replication computes everything: an *aligned* partition is
            # never costlier (a misaligned one may be — tiny shards pad)
            ts = g.tensors[t]
            sizes = dict(zip(ts.dims, ts.shape))
            repl = per[REPLICATE]
            for c, v in per.items():
                if not isinstance(c, Part):
                    continue
                unit = term.lane if c.dim == ts.dims[-1] else term.sublane
                if alignment_factor(sizes[c.dim] / 4, unit) == 1.0:
                    assert v <= repl + 1e-9

    @pytest.mark.parametrize("seed", range(4))
    def test_solve_reprice_oracle(self, seed):
        g = random_chain_graph(random.Random(seed), 2)
        term = self._cc().term_for_axis(50e9, 2)
        sol = solve_one_cut(g, 2, terms=[term])
        oracle = solve_one_cut_bruteforce(g, 2, workers=0, terms=[term])
        priced = graph_cost(g, sol.assignment, 2, terms=[term])
        assert sol.cost == pytest.approx(oracle.cost, rel=1e-9)
        assert sol.cost == pytest.approx(priced, rel=1e-6)
        # adding a >= 0 term never lowers the optimum
        base = solve_one_cut(g, 2)
        assert sol.cost >= base.cost - 1e-9

    def test_solve_mesh_matches_composed_and_breakdown(self):
        from repro.core.solver import solution_breakdown
        g = mlp_graph(batch=32, hidden=[48, 64, 40], with_backward=True)
        axes = [MeshAxis("x", 4, 50e9), MeshAxis("y", 2, 50e9)]
        cc = self._cc()
        sol = solve_mesh(g, axes, compute=cc)
        comp = composed_cost(g, axes, sol.per_axis, compute=cc)
        bd = solution_breakdown(g, axes, sol.per_axis, compute=cc)
        assert sol.total_bytes == pytest.approx(comp, rel=1e-6)
        assert bd["total"] == pytest.approx(comp, rel=1e-6)
        assert sum(bd["by_term"].values()) == pytest.approx(bd["total"])
        assert bd["by_term"]["compute"] > 0
        assert bd["by_term"]["conversion"] >= 0
        # default call shape unchanged: conversion-only breakdown
        bd0 = solution_breakdown(g, axes, sol.per_axis)
        assert bd0["total"] == pytest.approx(bd0["by_term"]["conversion"])

    def test_solution_compute_seconds(self):
        from repro.core.costterms import graph_compute_seconds
        from repro.core.solver import solution_compute_seconds
        g = mlp_graph(batch=32, hidden=[64, 64])
        axes = [MeshAxis("x", 4, 50e9)]
        cc = self._cc()
        sol = solve_mesh(g, axes, compute=cc)
        secs = solution_compute_seconds(g, axes, sol.per_axis, cc)
        assert secs > 0
        # partitioning along an aligned batch never increases per-device
        # compute beyond the unsharded whole graph
        whole = graph_compute_seconds(g, cc)
        assert secs <= whole + 1e-12

    def test_misaligned_partition_penalized(self):
        """A 4-way cut of a 4-element dim leaves 1-wide blocks: the
        alignment factor must make that strictly worse per-shard than
        the flops/arity ideal."""
        from repro.core.tiling import Part
        g = Graph("tiny", allow_uneven=True)
        g.tensor("x", ("b", "i"), (256, 64), 4.0, kind="input")
        g.tensor("W", ("i", "o"), (64, 4), 4.0, kind="weight")
        g.tensor("y", ("b", "o"), (256, 4), 4.0)
        g.einsum("mm", "x", "W", "y")
        term = self._cc().term_for_axis(50e9, 4)
        per = term.penalties(g, 4)["y"]
        flops = 2.0 * 256 * 64 * 4
        scale = 1.3 * (50e9 * 4) / 1e12
        # Part("o"): last dim, 1-wide shards on a 128 lane -> 128x pad
        assert per[Part("o")] == pytest.approx(
            flops / 4 * 128.0 * scale)
        # Part("b"): second-to-last, 64-wide shards aligned to 8 -> ideal
        assert per[Part("b")] == pytest.approx(flops / 4 * scale)
        assert per[REPLICATE] == pytest.approx(flops * scale)

    def test_plan_cache_key_distinct(self, tmp_path, monkeypatch):
        from repro.core.costterms import ComputeConfig
        from repro.launch import compile as C
        monkeypatch.setattr(C, "CACHE_DIR", str(tmp_path))
        key = C.plan_key("inputs")
        a = C.plan_cache_path("arch", "shape", "mesh", key)
        cc = ComputeConfig()
        b = C.plan_cache_path("arch", "shape",
                              f"mesh_{cc.token()}", key)
        cc2 = ComputeConfig(calibration=0.5)
        c = C.plan_cache_path("arch", "shape",
                              f"mesh_{cc2.token()}", key)
        d = C.plan_cache_path("arch", "shape", "mesh",
                              C.plan_key("other inputs"))
        assert len({a, b, c, d}) == 4

    def test_plan_cache_ignores_records_of_other_inputs_or_sources(
            self, tmp_path, monkeypatch):
        """A record solved for another config or by other solver sources
        shares the cell's name but never its path, so it is never read."""
        import dataclasses
        import json

        from repro.configs import get_arch
        from repro.configs.base import ShapeConfig
        from repro.launch import compile as C
        monkeypatch.setattr(C, "CACHE_DIR", str(tmp_path))
        cfg = get_arch("qwen2-1.5b").reduced()
        shape = ShapeConfig("t", 16, 4, "decode")
        axes = [MeshAxis("data", 2)]
        rec = C.solve_cell_plan(cfg, shape, axes, "m")
        (path,) = tmp_path.iterdir()
        stale = dict(rec, total_bytes=-1.0)
        path.write_text(json.dumps(stale))
        assert C.solve_cell_plan(cfg, shape, axes, "m") == stale
        wider = dataclasses.replace(cfg, d_ff=2 * cfg.d_ff)
        assert C.solve_cell_plan(wider, shape, axes,
                                 "m")["total_bytes"] >= 0
        monkeypatch.setattr(C, "_plan_sources_digest", lambda: "edited")
        assert C.solve_cell_plan(cfg, shape, axes, "m")["total_bytes"] >= 0
        assert len(list(tmp_path.iterdir())) == 3
