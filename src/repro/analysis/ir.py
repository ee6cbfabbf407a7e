"""Dataflow IR for traced Pallas kernels, lowered from the jaxpr.

A ``pallas_call`` equation carries the kernel body as a jaxpr whose
equations are the kernel's *schedule*: ``dma_start``/``dma_wait`` pairs
with full source/destination/semaphore descriptors, ``get``/``swap`` on
the scratch refs, and ``cond`` branches for every ``pl.when`` guard. This
module lowers that jaxpr into a small dataflow IR the verifier passes can
simulate:

  * :class:`Expr`    — symbolic scalars over ``program_id`` axes and
    constants (index arithmetic, bank selectors, ``pl.when`` predicates),
    evaluable at any concrete grid point;
  * :class:`Access`  — a ref plus a composed window: per original ref
    dimension an (offset ``Expr``, static size, point?) triple, with
    chained ``.at[]`` indexers (bank select, then slices) folded into one
    window;
  * op records       — :class:`DmaStart` / :class:`DmaWait` (descriptor +
    semaphore identity), :class:`RefRead` / :class:`RefWrite`,
    :class:`Convert` (dtype moves on ref-provenance data) — each tagged
    with the conjunction of the ``pl.when`` predicates it sits under;
  * :class:`KernelIR` — the grid (with axis roles from the
    :class:`~repro.kernels.filter2d.contract.KernelContract`), the ref
    table and the op list in program order, plus the traced VMEM
    working-set accounting.

``iter_eqns``/``pallas_calls`` are the shared jaxpr walkers (they replace
the ad-hoc traversal ``tests/test_halo_engine.py`` used to hand-roll):
they recurse through ``pjit``/``cond``/``scan`` sub-jaxprs generically.

The lowering is *static*: nothing is executed, no TPU is needed — the
same trace ``jax.make_jaxpr`` produces on any backend with
``interpret=False``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, FrozenSet, Iterator, List, Optional, Tuple

import numpy as np
import jax

from repro.kernels.filter2d.contract import KernelContract


class AnalysisError(Exception):
    """The trace cannot be lowered/analyzed (CLI exit code 2 territory)."""


# ---------------------------------------------------------------------------
# Symbolic scalars
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Expr:
    """A symbolic scalar: ``op`` over ``args`` (sub-``Expr`` operands).

    ``val`` carries the payload for leaf/annotated ops: the axis index for
    ``pid``, the Python value for ``const``, a target-kind tag for
    ``convert``, an opaque identity for ``opaque``. Evaluable at a
    concrete grid point via :func:`ev`; ``opaque`` leaves (values the
    lowering cannot model, e.g. data loaded from memory) raise — they
    must never reach an index or predicate position in a well-formed
    kernel."""

    op: str
    args: Tuple["Expr", ...] = ()
    val: Any = None


def const(v) -> Expr:
    return Expr("const", (), v)


_BIN = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "max": max,
    "min": min,
    "and": lambda a, b: bool(a) and bool(b),
    "or": lambda a, b: bool(a) or bool(b),
    "xor": lambda a, b: bool(a) ^ bool(b),
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
}

# scalar jax primitive name -> Expr op (shared shape: args become operands)
SCALAR_PRIMS = {
    "add": "add", "sub": "sub", "mul": "mul", "max": "max", "min": "min",
    "and": "and", "or": "or", "xor": "xor", "eq": "eq", "ne": "ne",
    "lt": "lt", "le": "le", "gt": "gt", "ge": "ge", "neg": "neg",
    "not": "not", "rem": "rem", "div": "div", "select_n": "select",
    "convert_element_type": "convert",
}


def ev(e: Expr, pids: Tuple[int, ...]):
    """Evaluate ``e`` at the concrete grid point ``pids``."""
    if e.op == "const":
        return e.val
    if e.op == "pid":
        return pids[e.val]
    if e.op == "opaque":
        raise AnalysisError(
            f"opaque value (from {e.val}) reached an index/predicate "
            "position; the lowering cannot model data-dependent control")
    a = [ev(x, pids) for x in e.args]
    if e.op in _BIN:
        return _BIN[e.op](a[0], a[1])
    if e.op == "neg":
        return -a[0]
    if e.op == "not":
        return not bool(a[0])
    if e.op in ("rem", "div"):
        x, y = int(a[0]), int(a[1])
        q = abs(x) // abs(y)
        if e.op == "div":
            return q if (x >= 0) == (y >= 0) else -q
        r = abs(x) - q * abs(y)
        return r if x >= 0 else -r
    if e.op == "select":
        return ev(e.args[1 + int(a[0])], pids)  # a[0] picks the case
    if e.op == "convert":
        if e.val == "bool":
            return bool(a[0])
        if e.val == "int":
            return int(a[0])
        return a[0]
    raise AnalysisError(f"cannot evaluate Expr op {e.op!r}")


def _conj(pred: Optional[Expr], cond: Expr) -> Expr:
    return cond if pred is None else Expr("and", (pred, cond))


# ---------------------------------------------------------------------------
# Refs, windows and op records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RefInfo:
    """One kernel operand/output/scratch ref, with its contract role."""

    index: int                  # position among the kernel jaxpr invars
    role: str                   # contract role: frame/coeffs/out/ext/...
    kind: str                   # 'input' | 'output' | 'scratch'
    shape: Tuple[int, ...]
    dtype: str
    itemsize: int
    space: str                  # 'vmem' | 'smem' | 'any' | 'sem'


# one window dim: (offset Expr, static size, point-indexed?)
Dim = Tuple[Expr, int, bool]


@dataclasses.dataclass(frozen=True)
class Access:
    """A ref plus its composed window, one dim triple per ref dim."""

    ref: int
    dims: Tuple[Dim, ...]

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(s for _, s, _ in self.dims)


@dataclasses.dataclass(frozen=True)
class DmaStart:
    pred: Optional[Expr]
    src: Access
    dst: Access
    sem: Access


@dataclasses.dataclass(frozen=True)
class DmaWait:
    pred: Optional[Expr]
    src: Access
    dst: Access
    sem: Access


@dataclasses.dataclass(frozen=True)
class RefRead:
    pred: Optional[Expr]
    acc: Access


@dataclasses.dataclass(frozen=True)
class RefWrite:
    pred: Optional[Expr]
    acc: Access
    const: Optional[float]            # known scalar fill value, if any
    prov: FrozenSet[int]              # refs the written data was read from


@dataclasses.dataclass(frozen=True)
class Convert:
    """A ``convert_element_type`` on array data with ref provenance."""

    pred: Optional[Expr]
    src_dtype: str
    dst_dtype: str
    prov: FrozenSet[int]


@dataclasses.dataclass(frozen=True)
class KernelIR:
    """One lowered pallas_call: grid, refs and the op list in program
    order (``cond`` branches flattened under conjoined predicates)."""

    name: str
    grid: Tuple[int, ...]
    contract: KernelContract
    refs: Tuple[RefInfo, ...]
    ops: Tuple[Any, ...]
    # traced VMEM accounting: role -> bytes (ext/obuf scratch, blocked
    # operands at full size, blocked output blocks)
    vmem_parts: Tuple[Tuple[str, int], ...]

    @property
    def vmem_bytes(self) -> int:
        return sum(b for _, b in self.vmem_parts)

    def ref_by_role(self, role: str) -> Optional[RefInfo]:
        for r in self.refs:
            if r.role == role:
                return r
        return None

    def axis(self, role: str) -> Optional[int]:
        return self.contract.axis(role)


# ---------------------------------------------------------------------------
# Shared jaxpr walkers
# ---------------------------------------------------------------------------


def _as_jaxpr(jx):
    """Normalise Jaxpr | ClosedJaxpr | make_jaxpr result to a Jaxpr."""
    return jx.jaxpr if hasattr(jx, "jaxpr") else jx


def sub_jaxprs(eqn) -> Iterator:
    """The sub-jaxprs an equation carries (``pjit`` bodies, ``cond``
    branches, ``scan``/``while`` bodies, custom-call jaxprs) — NOT the
    pallas kernel body, which :func:`iter_eqns` treats separately."""
    for name, v in eqn.params.items():
        if name == "jaxpr" and eqn.primitive.name == "pallas_call":
            continue
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for u in vals:
            if hasattr(u, "eqns"):
                yield u
            elif hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                yield u.jaxpr


def iter_eqns(jx, into_pallas: bool = False) -> Iterator:
    """Yield every equation reachable from ``jx`` (a Jaxpr/ClosedJaxpr),
    recursing through sub-jaxprs. ``into_pallas=True`` additionally
    recurses into pallas_call kernel bodies."""
    jx = _as_jaxpr(jx)
    for eqn in jx.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, into_pallas=into_pallas)
        if into_pallas and eqn.primitive.name == "pallas_call":
            yield from iter_eqns(_as_jaxpr(eqn.params["jaxpr"]),
                                 into_pallas=into_pallas)


def pallas_calls(jx) -> List:
    """All pallas_call equations reachable from ``jx``."""
    return [e for e in iter_eqns(jx) if e.primitive.name == "pallas_call"]


# ---------------------------------------------------------------------------
# Lowering: pallas_call eqn -> KernelIR
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _ArrayVal:
    """Opaque array data: dtype + ref provenance + known scalar value."""

    dtype: str
    prov: FrozenSet[int] = frozenset()
    const: Optional[float] = None


def _space_of(aval) -> str:
    dt = str(getattr(aval, "dtype", "")).lower()
    if "semaphore" in dt or "dma_sem" in dt:
        return "sem"
    ms = getattr(aval, "memory_space", None)
    s = str(ms).lower() if ms is not None else ""
    if "any" in s:
        return "any"
    if "smem" in s:
        return "smem"
    return "vmem"


def _is_ref(aval) -> bool:
    # plain arrays carry a memory_space too (the device space), so the
    # aval type decides
    return type(aval).__name__ in ("AbstractMemoryRef", "AbstractRef")


def _dtype_name(aval) -> str:
    try:
        return np.dtype(aval.dtype).name
    except TypeError:
        return str(aval.dtype)


def _itemsize(aval) -> int:
    try:
        return int(np.dtype(aval.dtype).itemsize)
    except TypeError:
        return 0


class _Lowerer:
    """Walks one kernel jaxpr, building the op list."""

    def __init__(self, grid: Tuple[int, ...], refs: Tuple[RefInfo, ...],
                 ref_vars: Dict[int, int]):
        self.grid = grid
        self.refs = refs
        self.env: Dict[Any, Any] = {}     # Var -> Expr | _ArrayVal | ref idx
        self.ref_env: Dict[int, int] = ref_vars  # id(var) -> ref index
        self.ops: List[Any] = []
        self._opaque = 0

    # -- env helpers -------------------------------------------------------

    def val(self, atom):
        if hasattr(atom, "val"):                       # Literal
            v = atom.val
            if np.ndim(v) == 0:
                return const(v.item() if hasattr(v, "item") else v)
            return _ArrayVal(_dtype_name(atom.aval), frozenset(),
                             v.item() if v.size == 1 else None)
        if id(atom) in self.ref_env:
            return ("ref", self.ref_env[id(atom)])
        if atom in self.env:
            return self.env[atom]
        # unknown var (e.g. a const captured by a branch): opaque
        return self.opaque(f"var {atom}")

    def opaque(self, why: str):
        self._opaque += 1
        return Expr("opaque", (), f"{why}#{self._opaque}")

    def expr_of(self, v) -> Expr:
        if isinstance(v, Expr):
            return v
        if isinstance(v, int):
            return const(v)
        raise AnalysisError(
            f"expected a scalar index/predicate, got {type(v).__name__}")

    def prov_of(self, vals) -> FrozenSet[int]:
        out = set()
        for v in vals:
            if isinstance(v, _ArrayVal):
                out |= v.prov
            elif isinstance(v, tuple) and v and v[0] == "ref":
                out.add(v[1])
        return frozenset(out)

    # -- window composition ------------------------------------------------

    def compose(self, ref_idx: int, transforms) -> Access:
        """Fold a chain of NDIndexer transforms into one window over the
        ref's original dims."""
        shape = self.refs[ref_idx].shape
        dims: List[Dim] = [(const(0), s, False) for s in shape]
        view = list(range(len(shape)))       # current view dim -> orig dim
        for tr in transforms:
            idxs = getattr(tr, "indices", None)
            if idxs is None:
                raise AnalysisError(
                    f"unsupported ref transform {type(tr).__name__}")
            if len(idxs) != len(view):
                raise AnalysisError(
                    f"indexer rank {len(idxs)} != view rank {len(view)}")
            nxt = []
            for idx, d in zip(idxs, view):
                off, _, _ = dims[d]
                if hasattr(idx, "start"):            # Slice(start, size)
                    if getattr(idx, "stride", 1) not in (1, None):
                        raise AnalysisError("strided ref slices are not "
                                            "modelled")
                    start = idx.start
                    s_expr = (self.expr_of(self.val(start))
                              if hasattr(start, "aval") else
                              self.expr_of(start))
                    dims[d] = (Expr("add", (off, s_expr)), int(idx.size),
                               False)
                    nxt.append(d)
                else:                                # scalar index (point)
                    i_expr = (self.expr_of(self.val(idx))
                              if hasattr(idx, "aval") else
                              self.expr_of(int(idx)))
                    dims[d] = (Expr("add", (off, i_expr)), 1, True)
            view = nxt
        return Access(ref_idx, tuple(dims))

    # -- equation dispatch -------------------------------------------------

    def run(self, jaxpr, pred: Optional[Expr]) -> None:
        for eqn in jaxpr.eqns:
            self.eqn(eqn, pred)

    def eqn(self, eqn, pred: Optional[Expr]) -> None:
        name = eqn.primitive.name
        if name == "program_id":
            self.env[eqn.outvars[0]] = Expr("pid", (), eqn.params["axis"])
            return
        if name == "num_programs":
            self.env[eqn.outvars[0]] = const(self.grid[eqn.params["axis"]])
            return
        if name == "cond":
            self.cond(eqn, pred)
            return
        if name in ("dma_start", "dma_wait"):
            self.dma(eqn, pred, start=name == "dma_start")
            return
        if name == "get":
            tr = jax.tree_util.tree_unflatten(eqn.params["tree"],
                                              eqn.invars[1:])
            ref = self.ref_env[id(eqn.invars[0])]
            acc = self.compose(ref, tr)
            self.ops.append(RefRead(pred, acc))
            self.env[eqn.outvars[0]] = _ArrayVal(
                _dtype_name(eqn.outvars[0].aval), frozenset([ref]))
            return
        if name == "swap":
            tr = jax.tree_util.tree_unflatten(eqn.params["tree"],
                                              eqn.invars[2:])
            ref = self.ref_env[id(eqn.invars[0])]
            acc = self.compose(ref, tr)
            v = self.val(eqn.invars[1])
            cval = None
            if isinstance(v, _ArrayVal):
                cval = v.const
            elif isinstance(v, Expr) and v.op == "const":
                cval = v.val
            self.ops.append(RefWrite(pred, acc, cval, self.prov_of([v])))
            self.env[eqn.outvars[0]] = _ArrayVal(
                _dtype_name(eqn.outvars[0].aval), frozenset([ref]))
            return
        if name in ("while", "scan") and any(
                e.primitive.name in ("dma_start", "dma_wait", "get", "swap")
                for sub in sub_jaxprs(eqn) for e in iter_eqns(sub)):
            raise AnalysisError(
                f"effectful ops under {name!r} loops are not modelled")
        self.generic(eqn, pred)

    def cond(self, eqn, pred: Optional[Expr]) -> None:
        index = self.expr_of(self.val(eqn.invars[0]))
        branches = eqn.params["branches"]
        for k, closed in enumerate(branches):
            sub = _Lowerer(self.grid, self.refs, self.ref_env)
            sub.env = dict(self.env)
            sub._opaque = self._opaque
            jx = _as_jaxpr(closed)
            consts = list(getattr(closed, "consts", ()) or ())
            for cv, cval in zip(jx.constvars, consts):
                sub.env[cv] = _ArrayVal(
                    _dtype_name(cv.aval), frozenset(),
                    cval.item() if np.ndim(cval) == 0 else None)
            for bv, outer in zip(jx.invars, eqn.invars[1:]):
                sub.env[bv] = self.val(outer)
                if id(outer) in self.ref_env:
                    sub.ref_env = dict(sub.ref_env)
                    sub.ref_env[id(bv)] = self.ref_env[id(outer)]
            sub.ops = self.ops                # shared op list, in order
            sub.run(jx, _conj(pred, Expr("eq", (index, const(k)))))
            self._opaque = sub._opaque
        for ov in eqn.outvars:                # joins are opaque
            self.env[ov] = _ArrayVal(_dtype_name(ov.aval), frozenset())

    def dma(self, eqn, pred: Optional[Expr], start: bool) -> None:
        tree = jax.tree_util.tree_unflatten(eqn.params["tree"], eqn.invars)
        src_ref, src_tr, dst_ref, dst_tr, sem_ref, sem_tr = tree[:6]
        src = self.compose(self.ref_env[id(src_ref)], src_tr or ())
        dst = self.compose(self.ref_env[id(dst_ref)], dst_tr or ())
        sem = self.compose(self.ref_env[id(sem_ref)], sem_tr or ())
        cls = DmaStart if start else DmaWait
        self.ops.append(cls(pred, src, dst, sem))

    def generic(self, eqn, pred: Optional[Expr]) -> None:
        name = eqn.primitive.name
        vals = [self.val(v) for v in eqn.invars]
        out = eqn.outvars[0] if eqn.outvars else None
        scalar_out = (out is not None and out.aval.shape == ()
                      and not _is_ref(out.aval))
        if (scalar_out and name in SCALAR_PRIMS
                and all(isinstance(v, (Expr, int)) for v in vals)):
            op = SCALAR_PRIMS[name]
            args = tuple(self.expr_of(v) for v in vals)
            meta = None
            if op == "convert":
                kind = np.dtype(out.aval.dtype).kind
                meta = {"b": "bool", "f": "float"}.get(kind, "int")
            self.env[out] = Expr(op, args, meta)
            return
        # array-level (or unmodelled scalar) op: propagate provenance;
        # record dtype moves on ref-provenance data for the width lint
        prov = self.prov_of(vals)
        cval = None
        if name in ("broadcast_in_dim", "convert_element_type", "reshape",
                    "squeeze", "copy"):
            v0 = vals[0] if vals else None
            if isinstance(v0, _ArrayVal):
                cval = v0.const
            elif isinstance(v0, Expr) and v0.op == "const":
                cval = v0.val
        if name == "convert_element_type" and prov and out is not None:
            self.ops.append(Convert(
                pred, _dtype_name(eqn.invars[0].aval),
                _dtype_name(out.aval), prov))
        for ov in eqn.outvars:
            self.env[ov] = _ArrayVal(_dtype_name(ov.aval), prov, cval)


def lower_pallas_call(eqn, contract: KernelContract) -> KernelIR:
    """Lower one pallas_call equation into a :class:`KernelIR`, naming
    refs and grid axes by the kernel's declared ``contract``."""
    if eqn.primitive.name != "pallas_call":
        raise AnalysisError(f"not a pallas_call: {eqn.primitive.name}")
    p = eqn.params
    gm = p["grid_mapping"]
    kj = _as_jaxpr(p["jaxpr"])
    grid = tuple(int(g) for g in gm.grid)
    if len(grid) != len(contract.axes):
        raise AnalysisError(
            f"grid rank {len(grid)} != contract axes {contract.axes}")
    n_in = int(gm.num_inputs)
    n_out = int(gm.num_outputs)
    n_scr = int(getattr(gm, "num_scratch_operands", 0))
    invars = list(kj.invars)
    if len(invars) != n_in + n_out + n_scr:
        raise AnalysisError(
            f"kernel has {len(invars)} refs; grid_mapping declares "
            f"{n_in}+{n_out}+{n_scr}")
    roles = (tuple(contract.operands) + tuple(contract.outputs)
             + tuple(contract.scratch))
    if len(roles) != len(invars):
        raise AnalysisError(
            f"contract names {len(roles)} refs ({roles}) but the kernel "
            f"binds {len(invars)}")
    kinds = (("input",) * n_in + ("output",) * n_out + ("scratch",) * n_scr)
    refs, ref_vars = [], {}
    for k, (var, role, kind) in enumerate(zip(invars, roles, kinds)):
        av = var.aval
        refs.append(RefInfo(index=k, role=role, kind=kind,
                            shape=tuple(int(s) for s in av.shape),
                            dtype=_dtype_name(av), itemsize=_itemsize(av),
                            space=_space_of(av)))
        ref_vars[id(var)] = k
    refs = tuple(refs)

    # traced VMEM accounting (what vmem_budget compares against the plan):
    # VMEM scratch allocations + blocked VMEM operands at their FULL
    # operand size (the whole coefficient file cycles through VMEM) +
    # blocked output blocks. ANY/SMEM refs and semaphores cost no VMEM.
    parts: List[Tuple[str, int]] = []
    outer_in = list(eqn.invars)[-n_in:] if n_in else []
    for r in refs:
        if r.space != "vmem":
            continue
        if r.kind == "scratch":
            parts.append((f"scratch:{r.role}",
                          int(np.prod(r.shape, dtype=np.int64)) * r.itemsize))
        elif r.kind == "input":
            oav = outer_in[r.index].aval
            parts.append((f"operand:{r.role}",
                          int(np.prod(oav.shape, dtype=np.int64))
                          * _itemsize(oav)))
        else:
            parts.append((f"out_block:{r.role}",
                          int(np.prod(r.shape, dtype=np.int64)) * r.itemsize))

    lo = _Lowerer(grid, refs, ref_vars)
    for cv in kj.constvars:
        lo.env[cv] = _ArrayVal(_dtype_name(cv.aval), frozenset())
    lo.run(kj, None)
    name = getattr(getattr(p.get("name_and_src_info"), "name", None),
                   "__str__", lambda: "pallas_call")()
    return KernelIR(name=str(name), grid=grid, contract=contract,
                    refs=refs, ops=tuple(lo.ops),
                    vmem_parts=tuple(parts))
