"""jaxpr capture: lower any jittable JAX function to the named-dims IR.

The paper's system "automatically transforms a serial dataflow graph
captured by an existing deep learning system frontend"; this module is
that frontend for JAX.  ``capture(fn, *example_args)`` traces ``fn``
with ``jax.make_jaxpr`` and walks the jaxpr, emitting one semantic op
(core/graph.py) per equation:

  dot_general / conv     -> einsum ops (dim classes from name identity)
  element-wise family    -> ewise ops (broadcasts included)
  reduce_sum/max/...     -> reduce ops (multi-axis reduces are chained)
  layout moves           -> zero-cost aliases (transpose, cast, squeeze,
                            1-axis reshapes) or custom tie ops (merged /
                            split dims, with granule ``units`` so a cut
                            never splits the folded constituent)
  scan                   -> the body is lowered ONCE with repeat=length
                            (the builders' layer-stack coarsening,
                            detected automatically), with zero-cost ties
                            for xs slices / ys stacking and an explicit
                            loop-back op pricing carry re-sharding
  jit / remat / custom_{jvp,vjp} -> inlined
  anything else          -> a conservative ewise fallback (recorded in
                            ``Traced.unknown_primitives``)

Dimension *names* are discovered by unification: every tensor axis gets
a fresh slot; primitives merge slots that must carry the same logical
dimension (einsum contraction/batch pairs, element-wise alignment,
broadcast mappings, scan carries).  A union-find over slots yields the
final named-dims graph, so e.g. every residual-stream activation in a
traced transformer ends up sharing one "d_model" name without any model
knowledge.  Sharding correctness never depends on capture fidelity: the
plan only *chooses* in/out shardings, GSPMD keeps execution correct.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.graph import Graph
from ..core.tiling import Part, REPLICATE


# ---------------------------------------------------------------------------
# dim-slot union-find
# ---------------------------------------------------------------------------

class DimTable:
    """Union-find over dimension slots; merging requires equal sizes."""

    def __init__(self):
        self._parent: List[int] = []
        self._size: List[int] = []

    def new(self, size: int) -> int:
        i = len(self._parent)
        self._parent.append(i)
        self._size.append(int(size))
        return i

    def find(self, i: int) -> int:
        while self._parent[i] != i:
            self._parent[i] = self._parent[self._parent[i]]
            i = self._parent[i]
        return i

    def size(self, i: int) -> int:
        return self._size[self.find(i)]

    def unify(self, a: int, b: int) -> bool:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return True
        if self._size[ra] != self._size[rb]:
            return False
        self._parent[rb] = ra
        return True


# ---------------------------------------------------------------------------
# intermediate records (dim names are only assigned at finalize)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Val:
    """A jaxpr var's value: the tensor holding it plus this var's view of
    the tensor's axes (aliases permute / subset the dim ids)."""
    tensor: Optional[str]          # None => scalar literal, no tensor
    dims: Tuple[int, ...]          # dim slot ids in this var's axis order
    shape: Tuple[int, ...]
    dtype: Any

    @property
    def ndim(self) -> int:
        return len(self.shape)


@dataclasses.dataclass
class _TRec:
    name: str
    dims: Tuple[int, ...]
    shape: Tuple[int, ...]
    bytes_per_elem: float
    kind: str
    units: Dict[int, int] = dataclasses.field(default_factory=dict)


# custom-op form spec: {tensor_name: ("axis", axis_index) | "r"}
_FormSpec = Tuple[Dict[str, object], float]


@dataclasses.dataclass
class _OpRec:
    kind: str                      # einsum | ewise | reduce | custom
    inputs: Tuple[str, ...]
    output: str
    repeat: float
    align: Optional[Tuple[int, ...]] = None    # ewise dim-id whitelist
    update: bool = False
    axis: Optional[int] = None                 # reduce: input axis INDEX
    forms: Optional[Tuple[_FormSpec, ...]] = None


# lax element-wise primitives (operands pre-broadcast to one shape)
_ELEMENTWISE = frozenset("""
add sub mul div max min pow atan2 rem nextafter and or xor not
shift_left shift_right_logical shift_right_arithmetic
neg exp exp2 log log1p expm1 tanh sin cos tan asin acos atan
sinh cosh asinh acosh atanh sqrt rsqrt cbrt square logistic
erf erfc erf_inv abs sign floor ceil round is_finite integer_pow
eq ne lt le gt ge le_to lt_to select_n clamp real imag conj complex
population_count clz nan_to_num
""".split())

# pure layout moves: output aliases the input tensor
_CAST_ALIAS = frozenset(
    "convert_element_type copy stop_gradient reduce_precision "
    "copy_start copy_done".split())

_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
}

_CUMULATIVE = {"cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"}


class _Capture:
    def __init__(self, name: str):
        self.name = name
        self.dt = DimTable()
        self.tensors: Dict[str, _TRec] = {}
        self.ops: List[_OpRec] = []
        self._n = 0
        self.unknown: List[str] = []

    # -- tensor helpers ------------------------------------------------
    def _fresh_name(self, prefix: str) -> str:
        self._n += 1
        prefix = "".join(c if (c.isalnum() or c == "_") else "_"
                         for c in prefix)
        return f"{prefix}.{self._n}"

    def new_dims(self, shape: Sequence[int]) -> Tuple[int, ...]:
        return tuple(self.dt.new(s) for s in shape)

    def tensor(self, prefix: str, dims: Sequence[int],
               shape: Sequence[int], dtype,
               kind: str = "activation",
               units: Optional[Dict[int, int]] = None) -> _Val:
        name = self._fresh_name(prefix)
        self.tensors[name] = _TRec(name, tuple(dims), tuple(shape),
                                   float(np.dtype(dtype).itemsize), kind,
                                   dict(units or {}))
        return _Val(name, tuple(dims), tuple(shape), dtype)

    def leaf(self, prefix: str, shape, dtype, kind: str = "input") -> _Val:
        return self.tensor(prefix, self.new_dims(shape), shape, dtype,
                           kind)

    # -- op emit -------------------------------------------------------
    def ewise(self, inputs: Sequence[_Val], out: _Val, repeat: float,
              align: Optional[Sequence[int]] = None,
              update: bool = False) -> None:
        ins = tuple(v.tensor for v in inputs if v.tensor is not None)
        if not ins:
            return                       # pure-literal compute: local
        self.ops.append(_OpRec("ewise", ins, out.tensor, repeat,
                               align=None if align is None
                               else tuple(align), update=update))

    def einsum(self, lhs: _Val, rhs: _Val, out: _Val,
               repeat: float) -> None:
        self.ops.append(_OpRec("einsum", (lhs.tensor, rhs.tensor),
                               out.tensor, repeat))

    def _tensor_axis(self, v: _Val, i: int) -> int:
        """Translate an axis of a var *view* (which may permute or
        subset its tensor's axes via aliasing) to the tensor's own
        axis index — op records always store tensor axes."""
        return self.tensors[v.tensor].dims.index(v.dims[i])

    def reduce(self, inp: _Val, out: _Val, axis_index: int,
               repeat: float) -> None:
        self.ops.append(_OpRec("reduce", (inp.tensor,), out.tensor,
                               repeat,
                               axis=self._tensor_axis(inp, axis_index)))

    def custom(self, inputs: Sequence[_Val], out: _Val,
               forms: Sequence[_FormSpec], repeat: float) -> None:
        self.ops.append(_OpRec(
            "custom", tuple(v.tensor for v in inputs), out.tensor,
            repeat, forms=tuple(forms)))

    def tie(self, src: _Val, dst: _Val,
            pairs: Sequence[Tuple[int, int]], repeat: float) -> None:
        """Zero-cost data-identity op: partitioning ``src`` axis i is the
        same physical layout as partitioning ``dst`` axis j for every
        (i, j) in ``pairs``; replication maps to replication for free."""
        forms: List[_FormSpec] = []
        for i, j in pairs:
            if src.shape[i] <= 1:      # size-1 axes are never cuttable
                continue
            try:
                forms.append(
                    ({src.tensor: ("axis", self._tensor_axis(src, i)),
                      dst.tensor: ("axis", self._tensor_axis(dst, j))},
                     0.0))
            except ValueError:
                # alias-view axis absent from the backing tensor
                # (inserted size-1 dim): no corresponding cut exists
                continue
        forms.append(({src.tensor: "r", dst.tensor: "r"}, 0.0))
        self.custom((src,), dst, forms, repeat)

    # -- jaxpr walking ---------------------------------------------------
    def read(self, v, env: Dict[Any, _Val]) -> _Val:
        from jax.extend.core import Literal
        if isinstance(v, Literal):
            val = np.asarray(v.val)
            if val.ndim == 0:
                return _Val(None, (), (), val.dtype)
            out = self.leaf("lit", val.shape, val.dtype,
                            kind="activation")
            return out
        return env[v]

    def bind(self, var, val: _Val, env: Dict[Any, _Val]) -> None:
        env[var] = val               # a dropped output binds harmlessly

    def lower_closed(self, closed, invals: Sequence[_Val],
                     repeat: float) -> List[_Val]:
        env: Dict[Any, _Val] = {}
        jaxpr = closed.jaxpr
        for cv, c in zip(jaxpr.constvars, closed.consts):
            arr = np.asarray(c) if not hasattr(c, "shape") else c
            self.bind(cv, self.leaf("const", tuple(arr.shape), arr.dtype),
                      env)
        for iv, v in zip(jaxpr.invars, invals):
            self.bind(iv, v, env)
        self.lower(jaxpr, env, repeat)
        return [self.read(v, env) for v in jaxpr.outvars]

    def lower(self, jaxpr, env: Dict[Any, _Val], repeat: float) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            invals = [self.read(v, env) for v in eqn.invars]
            handler = getattr(self, f"_p_{prim.replace('-', '_')}", None)
            if prim in _ELEMENTWISE:
                outs = self._elementwise(prim, eqn, invals, repeat)
            elif prim in _CAST_ALIAS:
                v = invals[0]
                outs = [_Val(v.tensor, v.dims, v.shape,
                             eqn.outvars[0].aval.dtype)]
            elif prim in _REDUCE_PRIMS:
                outs = self._reduce(prim, eqn, invals, repeat)
            elif prim in _CUMULATIVE:
                outs = self._cumulative(prim, eqn, invals, repeat)
            elif handler is not None:
                outs = handler(eqn, invals, repeat)
            else:
                outs = self._fallback(prim, eqn, invals, repeat)
            for ov, val in zip(eqn.outvars, outs):
                self.bind(ov, val, env)

    # -- element-wise / broadcast ---------------------------------------
    def _elementwise(self, prim, eqn, invals, repeat) -> List[_Val]:
        out_aval = eqn.outvars[0].aval
        out_shape = tuple(out_aval.shape)
        rank = len(out_shape)
        arrs = [v for v in invals if v.tensor is not None and v.ndim > 0]
        if not arrs:             # pure-scalar compute
            return self._fallback(prim, eqn, invals, repeat,
                                  record=False)
        # per-axis dim discovery + unification across rank-equal
        # operands (lax binary ops broadcast rank-equal size-1 axes)
        dims: List[int] = []
        for j, s in enumerate(out_shape):
            cands = [v for v in arrs
                     if v.ndim == rank and v.shape[j] == s]
            if cands:
                d = cands[0].dims[j]
                for v in cands[1:]:
                    self.dt.unify(d, v.dims[j])
                dims.append(d)
            else:
                dims.append(self.dt.new(s))
        full = [v for v in arrs if v.shape == out_shape]
        if len(full) == 1 and len(arrs) == 1 and rank > 0:
            # unary activation / scalar-operand op: alias (builders
            # model at block granularity too; keeping every tanh as an
            # op floods the DP with equal-cost states)
            ref = full[0]
            return [_Val(ref.tensor, ref.dims, ref.shape,
                         out_aval.dtype)]
        if len(full) == 1 and rank > 0:
            # one full operand + size-1-broadcast partners (keepdims
            # normalizations: x * rsqrt(mean)): alias the full operand.
            # The weak partners stay unified by dim name but get no op —
            # materializing every normalization multiply re-floods the
            # DP (observed: dense trace cost 0.4x -> 8x of the builder)
            ref = full[0]
            return [_Val(ref.tensor, ref.dims, ref.shape,
                         out_aval.dtype)]
        out = self.tensor(prim, dims, out_shape, out_aval.dtype)
        self.ewise(invals, out, repeat)
        return [out]

    def _p_broadcast_in_dim(self, eqn, invals, repeat) -> List[_Val]:
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        v = invals[0]
        if v.tensor is None:                  # scalar fill: local compute
            return [self.leaf("fill", shape, out_aval.dtype,
                              kind="activation")]
        bd = eqn.params["broadcast_dimensions"]
        dims = []
        mapped = {}
        expands = False
        for i, j in enumerate(bd):
            mapped[j] = v.dims[i] if v.shape[i] == shape[j] else None
        for j, s in enumerate(shape):
            d = mapped.get(j)
            if d is None and s > 1:
                expands = True
            dims.append(d if d is not None else self.dt.new(s))
        if not expands:
            # only size-1 axes inserted (keepdims patterns): pure alias
            return [_Val(v.tensor, tuple(dims), shape, out_aval.dtype)]
        out = self.tensor("bcast", dims, shape, out_aval.dtype)
        self.ewise([v], out, repeat, update=True)
        return [out]

    # -- reductions ------------------------------------------------------
    def _reduce(self, prim, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        if v.tensor is None:     # reduce of a scalar literal
            return self._fallback(prim, eqn, invals, repeat,
                                  record=False)
        axes = sorted(eqn.params["axes"], reverse=True)
        out_dtype = eqn.outvars[0].aval.dtype
        for n, ax in enumerate(axes):
            dims = v.dims[:ax] + v.dims[ax + 1:]
            shape = v.shape[:ax] + v.shape[ax + 1:]
            last = n == len(axes) - 1
            if v.shape[ax] <= 1:      # reducing a singleton: pure alias
                v = _Val(v.tensor, dims, shape,
                         out_dtype if last else v.dtype)
                continue
            out = self.tensor(prim, dims, shape,
                              out_dtype if last else v.dtype)
            self.reduce(v, out, ax, repeat)
            v = out
        return [v]

    def _cumulative(self, prim, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        out_aval = eqn.outvars[0].aval
        ax = eqn.params.get("axis", 0)
        out = self.tensor(prim, v.dims, out_aval.shape, out_aval.dtype)
        align = [d for i, d in enumerate(v.dims) if i != ax]
        self.ewise([v], out, repeat, align=align)
        return [out]

    # -- einsum-class ops ------------------------------------------------
    def _p_dot_general(self, eqn, invals, repeat) -> List[_Val]:
        lhs, rhs = invals
        out_aval = eqn.outvars[0].aval
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        for i, j in list(zip(lc, rc)) + list(zip(lb, rb)):
            self.dt.unify(lhs.dims[i], rhs.dims[j])
        lhs_roots = {self.dt.find(d) for d in lhs.dims}
        # fork rhs free axes whose dim collides with an lhs dim: without
        # a fork the classifier would see a spurious batch dim (q @ k^T
        # with both seq axes unified is the canonical case)
        rdims = list(rhs.dims)
        forked = False
        for k, d in enumerate(rhs.dims):
            if k in rc or k in rb:
                continue
            if self.dt.find(d) in lhs_roots:
                rdims[k] = self.dt.new(rhs.shape[k])
                forked = True
        if forked:
            fork = self.tensor("fork", rdims, rhs.shape, rhs.dtype)
            self.tie(rhs, fork, [(i, i) for i in range(len(rdims))],
                     repeat)
            rhs = fork
        lfree = [i for i in range(len(lhs.dims)) if i not in lc + lb]
        rfree = [i for i in range(len(rhs.dims)) if i not in rc + rb]
        out_dims = [lhs.dims[i] for i in lb] + \
                   [lhs.dims[i] for i in lfree] + \
                   [rhs.dims[i] for i in rfree]
        # de-duplicate within the output (duplicate names break the
        # einsum classifier; only degenerate graphs hit this)
        seen = set()
        for i, d in enumerate(out_dims):
            r = self.dt.find(d)
            if r in seen:
                out_dims[i] = self.dt.new(out_aval.shape[i])
            else:
                seen.add(r)
        out = self.tensor("mm", out_dims, out_aval.shape, out_aval.dtype)
        self.einsum(lhs, rhs, out, repeat)
        return [out]

    def _p_conv_general_dilated(self, eqn, invals, repeat) -> List[_Val]:
        lhs, rhs = invals
        out_aval = eqn.outvars[0].aval
        dn = eqn.params["dimension_numbers"]
        groups = eqn.params.get("feature_group_count", 1)
        lspec, rspec, ospec = dn
        shape = tuple(out_aval.shape)
        dims: List[Optional[int]] = [None] * len(shape)
        dims[ospec[0]] = lhs.dims[lspec[0]]              # batch
        for a, b in zip(lspec[2:], ospec[2:]):           # spatial
            if lhs.shape[a] == shape[b]:
                dims[b] = lhs.dims[a]
        if groups == 1:
            # dense conv: feature contraction lhs C x rhs Cin -> Cout
            self.dt.unify(lhs.dims[lspec[1]], rhs.dims[rspec[1]])
            dims[ospec[1]] = rhs.dims[rspec[0]]
            dims = [d if d is not None else self.dt.new(shape[i])
                    for i, d in enumerate(dims)]
            out = self.tensor("conv", dims, shape, out_aval.dtype)
            self.einsum(lhs, rhs, out, repeat)
            return [out]
        # grouped / depthwise (the mamba & xlstm causal conv1d): the
        # channel dim is batch-like; spatial cuts would need halos
        chan_shared = lhs.shape[lspec[1]] == shape[ospec[1]]
        if chan_shared:
            dims[ospec[1]] = lhs.dims[lspec[1]]
        dims = [d if d is not None else self.dt.new(shape[i])
                for i, d in enumerate(dims)]
        out = self.tensor("dwconv", dims, shape, out_aval.dtype)
        align = [dims[ospec[0]]]
        if chan_shared:     # channel-multiplier convs: out channels are
            align.append(dims[ospec[1]])   # output-only, not alignable
        self.ewise(invals, out, repeat, align=align)
        return [out]

    # -- layout ----------------------------------------------------------
    def _p_transpose(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        perm = eqn.params["permutation"]
        return [_Val(v.tensor, tuple(v.dims[i] for i in perm),
                     tuple(v.shape[i] for i in perm), v.dtype)]

    def _p_squeeze(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        drop = set(eqn.params["dimensions"])
        keep = [i for i in range(v.ndim) if i not in drop]
        return [_Val(v.tensor, tuple(v.dims[i] for i in keep),
                     tuple(v.shape[i] for i in keep), v.dtype)]

    def _p_reshape(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        if v.tensor is None or eqn.params.get("dimensions") is not None:
            return self._fallback("reshape", eqn, invals, repeat)
        groups = _reshape_groups(v.shape, shape)
        if groups is None:
            return self._fallback("reshape", eqn, invals, repeat)
        out_dims: List[int] = [0] * len(shape)
        pairs: List[Tuple[int, int]] = []    # (src_axis, dst_axis) ties
        units: Dict[int, int] = {}
        pure = True
        for src_axes, dst_axes in groups:
            if len(src_axes) == 1 and len(dst_axes) == 1:
                out_dims[dst_axes[0]] = v.dims[src_axes[0]]
                pairs.append((src_axes[0], dst_axes[0]))
                continue
            pure = False
            lead_src = next((a for a in src_axes if v.shape[a] > 1),
                            src_axes[0] if src_axes else None)
            lead_dst = next((a for a in dst_axes if shape[a] > 1),
                            dst_axes[0] if dst_axes else None)
            for a in dst_axes:
                out_dims[a] = self.dt.new(shape[a])
            if lead_src is None or lead_dst is None:
                continue
            if len(dst_axes) == 1:
                # merge: a cut of the folded dim must keep whole trailing
                # granules (trailing product after the lead axis)
                gran = 1
                past = False
                for a in src_axes:
                    if past:
                        gran *= v.shape[a]
                    if a == lead_src:
                        past = True
                units[out_dims[dst_axes[0]]] = gran
            pairs.append((lead_src, lead_dst))
        if pure:
            return [_Val(v.tensor, tuple(out_dims), shape, v.dtype)]
        out = self.tensor("rs", out_dims, shape, out_aval.dtype,
                          units=units)
        self.tie(v, out, pairs, repeat)
        return [out]

    def _p_rev(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        out = self.tensor("rev", v.dims, v.shape, v.dtype)
        rdims = set(eqn.params["dimensions"])
        self.ewise([v], out, repeat,
                   align=[d for i, d in enumerate(v.dims)
                          if i not in rdims])
        return [out]

    def _p_pad(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        cfg = eqn.params["padding_config"]
        dims = [v.dims[i] if (lo, hi, ii) == (0, 0, 0)
                else self.dt.new(shape[i])
                for i, (lo, hi, ii) in enumerate(cfg)]
        out = self.tensor("pad", dims, shape, out_aval.dtype)
        self.ewise([v], out, repeat,
                   align=[d for d, c in zip(dims, cfg)
                          if c == (0, 0, 0)])
        return [out]

    # -- indexing --------------------------------------------------------
    def _p_concatenate(self, eqn, invals, repeat) -> List[_Val]:
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        k = eqn.params["dimension"]
        arrs = [v for v in invals if v.tensor is not None]
        ref = arrs[0]
        dims = []
        for j, s in enumerate(shape):
            if j == k:
                dims.append(self.dt.new(s))
                continue
            for other in arrs[1:]:
                self.dt.unify(ref.dims[j], other.dims[j])
            dims.append(ref.dims[j])
        out = self.tensor("cat", dims, shape, out_aval.dtype)
        self.ewise(arrs, out, repeat)
        return [out]

    def _p_slice(self, eqn, invals, repeat) -> List[_Val]:
        return self._slice_like(eqn, invals[0], repeat)

    def _p_dynamic_slice(self, eqn, invals, repeat) -> List[_Val]:
        return self._slice_like(eqn, invals[0], repeat)

    def _slice_like(self, eqn, v: _Val, repeat) -> List[_Val]:
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        if v.tensor is None:
            return self._fallback("slice", eqn, [v], repeat)
        dims = [v.dims[i] if v.shape[i] == shape[i] else self.dt.new(s)
                for i, s in enumerate(shape)]
        out = self.tensor("slc", dims, shape, out_aval.dtype)
        self.ewise([v], out, repeat, update=True,
                   align=[d for i, d in enumerate(dims)
                          if v.shape[i] == shape[i]])
        return [out]

    def _p_dynamic_update_slice(self, eqn, invals, repeat) -> List[_Val]:
        v, upd = invals[0], invals[1]
        out_aval = eqn.outvars[0].aval
        out = self.tensor("dus", v.dims, tuple(out_aval.shape),
                          out_aval.dtype)
        ins = [v] + ([upd] if upd.tensor is not None else [])
        self.ewise(ins, out, repeat,
                   align=[d for i, d in enumerate(v.dims)
                          if upd.tensor is None
                          or upd.shape[i] == v.shape[i]])
        return [out]

    def _p_gather(self, eqn, invals, repeat) -> List[_Val]:
        operand, idx = invals
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        dn = eqn.params["dimension_numbers"]
        ss = eqn.params["slice_sizes"]
        offset = set(dn.offset_dims)
        collapsed = set(dn.collapsed_slice_dims) | \
            set(getattr(dn, "operand_batching_dims", ()) or ())
        op_axes = iter(a for a in range(operand.ndim)
                       if a not in collapsed)
        batch_axes = iter(range(max(0, idx.ndim - 1)))
        dims = []
        for j, s in enumerate(shape):
            d = None
            if j in offset:
                a = next(op_axes, None)
                if a is not None and ss[a] == operand.shape[a]:
                    d = operand.dims[a]
            else:
                a = next(batch_axes, None)
                if a is not None and idx.tensor is not None \
                        and idx.shape[a] == s:
                    d = idx.dims[a]
            dims.append(d if d is not None else self.dt.new(s))
        out = self.tensor("gth", dims, shape, out_aval.dtype)
        self.ewise([v for v in invals if v.tensor is not None], out,
                   repeat)
        return [out]

    def _scatter_like(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        out_aval = eqn.outvars[0].aval
        out = self.tensor("sct", v.dims, tuple(out_aval.shape),
                          out_aval.dtype)
        self.ewise([x for x in invals if x.tensor is not None], out,
                   repeat)
        return [out]

    _p_scatter = _scatter_like
    _p_scatter_add = _scatter_like
    _p_scatter_mul = _scatter_like
    _p_scatter_min = _scatter_like
    _p_scatter_max = _scatter_like

    def _p_iota(self, eqn, invals, repeat) -> List[_Val]:
        out_aval = eqn.outvars[0].aval
        return [self.leaf("iota", tuple(out_aval.shape), out_aval.dtype,
                          kind="activation")]

    def _p_sort(self, eqn, invals, repeat) -> List[_Val]:
        ax = eqn.params["dimension"]
        outs = []
        for v, ov in zip(invals, eqn.outvars):
            out = self.tensor("sort", v.dims, v.shape, ov.aval.dtype)
            self.ewise([x for x in invals if x.tensor is not None], out,
                       repeat,
                       align=[d for i, d in enumerate(v.dims) if i != ax])
            outs.append(out)
        return outs

    def _p_top_k(self, eqn, invals, repeat) -> List[_Val]:
        v = invals[0]
        outs = []
        for ov in eqn.outvars:
            shape = tuple(ov.aval.shape)
            dims = v.dims[:-1] + (self.dt.new(shape[-1]),)
            out = self.tensor("topk", dims, shape, ov.aval.dtype)
            self.ewise([v], out, repeat, align=v.dims[:-1])
            outs.append(out)
        return outs

    # -- structured control flow ----------------------------------------
    def _p_scan(self, eqn, invals, repeat) -> List[_Val]:
        p = eqn.params
        closed = p["jaxpr"]
        length = int(p["length"])
        nc, ncarry = p["num_consts"], p["num_carry"]
        consts = invals[:nc]
        carries = invals[nc:nc + ncarry]
        xs = invals[nc + ncarry:]
        body_rep = repeat * length

        body_in: List[_Val] = list(consts) + list(carries)
        for x in xs:
            if x.tensor is None or x.ndim == 0:
                body_in.append(x)
                continue
            sl = self.tensor("xslice", x.dims[1:], x.shape[1:], x.dtype)
            self.tie(x, sl, [(i + 1, i) for i in range(sl.ndim)],
                     body_rep)
            body_in.append(sl)
        body_out = self.lower_closed(closed, body_in, body_rep)
        carry_out, ys = body_out[:ncarry], body_out[ncarry:]

        outs: List[_Val] = []
        for cin, cout in zip(carries, carry_out):
            if cin.tensor is not None and cout.tensor is not None \
                    and cin.tensor != cout.tensor:
                for a, b in zip(cin.dims, cout.dims):
                    self.dt.unify(a, b)
                # price the loop-back re-shard (iteration i's carry-out
                # feeds iteration i+1's carry-in); update=True: a
                # replicated carry is the same buffer, not recompute
                self.ops.append(_OpRec("ewise", (cout.tensor,),
                                       cin.tensor, body_rep,
                                       update=True))
            outs.append(cout)
        for y, ov in zip(ys, eqn.outvars[ncarry:]):
            shape = tuple(ov.aval.shape)
            if y.tensor is None:
                outs.append(self.leaf("ys", shape, ov.aval.dtype,
                                      kind="activation"))
                continue
            st = self.tensor("ystack", (self.dt.new(shape[0]),) + y.dims,
                             shape, ov.aval.dtype)
            self.tie(y, st, [(i, i + 1) for i in range(y.ndim)],
                     body_rep)
            outs.append(st)
        return outs

    def _p_optimization_barrier(self, eqn, invals, repeat) -> List[_Val]:
        return list(invals)          # n-ary identity: alias everything

    def _p_while(self, eqn, invals, repeat) -> List[_Val]:
        # data-dependent trip count: no repeat factor exists; lower as a
        # conservative opaque op (recorded by _fallback)
        return self._fallback("while", eqn, invals, repeat)

    def _p_cond(self, eqn, invals, repeat) -> List[_Val]:
        # cost-model coarseness: only the first branch is priced —
        # record it so describe()/conformance flag the capture as coarse
        branches = eqn.params["branches"]
        if len(branches) > 1 and "cond" not in self.unknown:
            self.unknown.append("cond")
        return self.lower_closed(branches[0], invals[1:], repeat)

    def _p_jit(self, eqn, invals, repeat) -> List[_Val]:
        return self.lower_closed(eqn.params["jaxpr"], invals, repeat)

    def _p_closed_call(self, eqn, invals, repeat) -> List[_Val]:
        return self.lower_closed(eqn.params["call_jaxpr"], invals, repeat)

    def _p_custom_jvp_call(self, eqn, invals, repeat) -> List[_Val]:
        return self.lower_closed(eqn.params["call_jaxpr"], invals, repeat)

    def _p_custom_vjp_call(self, eqn, invals, repeat) -> List[_Val]:
        return self.lower_closed(eqn.params["call_jaxpr"], invals, repeat)

    def _p_remat2(self, eqn, invals, repeat) -> List[_Val]:
        jx = eqn.params["jaxpr"]           # open jaxpr, no consts
        env: Dict[Any, _Val] = {}
        for iv, v in zip(jx.invars, invals):
            self.bind(iv, v, env)
        self.lower(jx, env, repeat)
        return [self.read(v, env) for v in jx.outvars]

    _p_checkpoint = _p_remat2

    # -- fallback --------------------------------------------------------
    def _fallback(self, prim, eqn, invals, repeat,
                  record: bool = True) -> List[_Val]:
        """Conservative ewise lowering.  ``record=False``: the caller
        judged the bail-out harmless (pure-scalar compute) — every other
        coarse lowering is surfaced in ``unknown_primitives`` so
        describe()/conformance never report a coarse capture as exact."""
        if record and prim not in self.unknown:
            self.unknown.append(prim)
        outs = []
        arrs = [v for v in invals if v.tensor is not None]
        for ov in eqn.outvars:
            aval = ov.aval
            shape = tuple(getattr(aval, "shape", ()))
            dims = None
            for v in arrs:
                if v.shape == shape:
                    dims = v.dims
                    break
            if dims is None:
                dims = self.new_dims(shape)
            out = self.tensor(prim, dims, shape,
                              getattr(aval, "dtype", np.float32))
            if arrs:
                self.ewise(arrs, out, repeat)
            outs.append(out)
        return outs

    # -- finalize --------------------------------------------------------
    def val_axis_names(self, v: Optional[_Val]) -> Tuple[str, ...]:
        """Final dim names of a var view, aligned to ITS axis order (an
        alias view may permute / extend its tensor's axes).  Must be
        called after :meth:`finalize`."""
        if v is None or v.tensor is None:
            return ()
        tdims = self.tensors[v.tensor].dims
        fdims = self._final_dims[v.tensor]
        out = []
        for k, d in enumerate(v.dims):
            try:
                out.append(fdims[tdims.index(d)])
            except ValueError:    # inserted size-1 axis: never cuttable
                out.append(f"_one{k}")
        return tuple(out)

    def finalize(self) -> Graph:
        names: Dict[int, str] = {}

        def dim_name(d: int) -> str:
            r = self.dt.find(d)
            if r not in names:
                names[r] = f"d{len(names)}"
            return names[r]

        g = Graph(self.name)
        final_dims: Dict[str, Tuple[str, ...]] = {}
        self._final_dims = final_dims
        for t in self.tensors.values():
            dims: List[str] = []
            used: Dict[str, int] = {}
            for d in t.dims:
                nm = dim_name(d)
                k = used.get(nm, 0)
                used[nm] = k + 1
                dims.append(nm if k == 0 else f"{nm}x{k}")
            units = {}
            for d, u in t.units.items():
                nm = dim_name(d)
                if nm in dims and u > 1:
                    units[nm] = u
            final_dims[t.name] = tuple(dims)
            g.tensor(t.name, dims, t.shape, t.bytes_per_elem, t.kind,
                     role=None, units=units)

        for i, op in enumerate(self.ops):
            nm = f"{op.kind[:2]}{i}:{op.output}"
            if op.kind == "einsum":
                g.einsum(nm, op.inputs[0], op.inputs[1], op.output,
                         op.repeat)
            elif op.kind == "ewise":
                align = None
                if op.align is not None:
                    out_dims = set(final_dims[op.output])
                    align = tuple(d for d in
                                  dict.fromkeys(dim_name(a)
                                                for a in op.align)
                                  if d in out_dims)
                g.ewise(nm, op.inputs, op.output, op.repeat,
                        align_dims=align, update=op.update)
            elif op.kind == "reduce":
                axis = final_dims[op.inputs[0]][op.axis]
                g.reduce(nm, op.inputs[0], op.output, axis, op.repeat)
            else:
                forms = []
                for spec, pen in op.forms:
                    form = {}
                    for tname, s in spec.items():
                        if s == "r":
                            form[tname] = REPLICATE
                        else:
                            form[tname] = Part(final_dims[tname][s[1]])
                    forms.append((form, pen))
                g.custom(nm, op.inputs, op.output, forms, op.repeat)
        return g


def _reshape_groups(src: Tuple[int, ...], dst: Tuple[int, ...]):
    """Greedy factorization of a reshape into groups of axes whose size
    products match; None when the shapes cannot be grouped (should not
    happen for equal element counts, but stay safe)."""
    groups = []
    i = j = 0
    while i < len(src) or j < len(dst):
        si, sj = [i], [j]
        if i >= len(src) or j >= len(dst):
            # trailing size-1 axes on one side
            rest_i = list(range(i, len(src)))
            rest_j = list(range(j, len(dst)))
            if all(src[a] == 1 for a in rest_i) and \
                    all(dst[a] == 1 for a in rest_j):
                if groups and (rest_i or rest_j):
                    groups.append((rest_i, rest_j))
                break
            return None
        pi, pj = src[i], dst[j]
        i += 1
        j += 1
        while pi != pj:
            if pi < pj:
                if i >= len(src):
                    return None
                pi *= src[i]
                si.append(i)
                i += 1
            else:
                if j >= len(dst):
                    return None
                pj *= dst[j]
                sj.append(j)
                j += 1
        groups.append((si, sj))
    return groups


# ---------------------------------------------------------------------------
# public capture API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Traced:
    """A captured program: the semantic graph plus the mapping from the
    function's flattened inputs/outputs to graph tensor names (the
    generalized "roles" the sharding plan is keyed on).  ``in_dims`` /
    ``out_dims`` give each leaf's dim names in the LEAF's own axis order
    (an output may be an alias view that permutes its tensor's axes)."""

    graph: Graph
    in_tensors: List[Optional[str]]       # per flattened input leaf
    out_tensors: List[Optional[str]]      # per flattened output leaf
    in_dims: List[Tuple[str, ...]]
    out_dims: List[Tuple[str, ...]]
    in_tree: Any
    out_shape: Any                        # pytree of ShapeDtypeStruct
    unknown_primitives: List[str]

    def tensor_roles(self) -> Dict[str, str]:
        """Identity role map (tensor name -> itself) for
        ShardingPlan.from_solution — the plan is keyed by traced tensor
        ids, not hand-written role names."""
        return {t: t for t in self.graph.tensors}

    def dims_of(self, tensor: str):
        return self.graph.tensors[tensor].dims


def capture(fn: Callable, *example_args, name: Optional[str] = None,
            weight_argnums: Sequence[int] = (),
            **example_kwargs) -> Traced:
    """Trace ``fn`` on example arguments and lower its jaxpr to a
    semantic graph.  Array leaves of arguments listed in
    ``weight_argnums`` are marked kind="weight" (they then participate
    in the solver's capacity accounting like builder weights)."""
    import jax

    from ..obs.tracing import span as _span
    with _span("trace.capture",
               fn=name or getattr(fn, "__name__", "traced")):
        return _capture_impl(fn, example_args, example_kwargs, name,
                             weight_argnums)


def _capture_impl(fn, example_args, example_kwargs, name,
                  weight_argnums) -> Traced:
    import jax

    flat, in_tree = jax.tree_util.tree_flatten(
        (example_args, example_kwargs))
    weight_leaf: List[bool] = []
    for i, a in enumerate(example_args):
        n = len(jax.tree_util.tree_flatten(a)[0])
        weight_leaf.extend([i in set(weight_argnums)] * n)
    weight_leaf.extend(
        [False] * len(jax.tree_util.tree_flatten(example_kwargs)[0]))

    def flat_fn(*leaves):
        args, kwargs = jax.tree_util.tree_unflatten(in_tree, leaves)
        return fn(*args, **kwargs)

    closed, out_shape = jax.make_jaxpr(flat_fn, return_shape=True)(*flat)

    cap = _Capture(name or getattr(fn, "__name__", "traced"))
    env: Dict[Any, _Val] = {}
    jaxpr = closed.jaxpr
    for cv, c in zip(jaxpr.constvars, closed.consts):
        arr = np.asarray(c) if not hasattr(c, "shape") else c
        cap.bind(cv, cap.leaf("const", tuple(arr.shape), arr.dtype), env)
    in_tensors: List[Optional[str]] = []
    in_vals: List[_Val] = []
    for i, (iv, leaf) in enumerate(zip(jaxpr.invars, flat)):
        aval = iv.aval
        kind = "weight" if i < len(weight_leaf) and weight_leaf[i] \
            else "input"
        v = cap.leaf(f"arg{i}", tuple(aval.shape), aval.dtype, kind=kind)
        cap.bind(iv, v, env)
        in_tensors.append(v.tensor)
        in_vals.append(v)
    cap.lower(jaxpr, env, repeat=1.0)
    out_vals = [cap.read(v, env) for v in jaxpr.outvars]
    g = cap.finalize()
    return Traced(g, in_tensors, [v.tensor for v in out_vals],
                  [cap.val_axis_names(v) for v in in_vals],
                  [cap.val_axis_names(v) for v in out_vals],
                  in_tree, out_shape, cap.unknown)
