"""Kernel contract analyzer: shape/dtype/domain/ring-mask checking.

The batched Raft kernel's whole correctness story is that per-shard
state is fixed-width i32/bool lanes advanced in lockstep — and JAX will
happily compile a silent f32 upcast, an implicit ``[G]``→``[G,P]``
broadcast, or an unmasked ring index, corrupting every shard at once.
This pass promotes the field comments of ``core/kstate.py`` into
machine-checked contracts (the ``CONTRACTS`` literals there and in
``core/kernel.py``; grammar documented at the kstate declaration) and
verifies them two ways:

**Statically** — an abstract interpreter over the AST of
``core/kernel.py`` (reachability reuses the tracer-safety walk: every
function reachable from a jit/vmap/scan seed is analyzed).  Each value
carries an abstract ``(axes, dtype)`` where axes are SYMBOLIC names
(G/P/CAP/K/E/B/RI) resolved from ``kp.<attr>`` uses, ``.shape`` reads
and ``jnp.arange`` extents — essential because the default geometry has
K = E = B = RI = 8, so a cross-axis mixup is shape-correct and
invisible to eval_shape.  ``jnp.where`` joins branches in the lattice;
named-axis conflicts, dtype drift and un-ring-masked dynamic indices
are findings:

- KC001  implicit broadcast aligning two DIFFERENT named axes
- KC002  silent dtype conversion (f32/i32 mix, u32/i32 mix, bool
         arithmetic, int/int true division)
- KC003  comparison mixing bool and i32 operands
- KC004  dynamic index into a ring-tagged array without the
         ``& (cap - 1)`` mask (or an equivalent in-range proof:
         argmax/arange over that axis, min/clip against ``cap - 1``)
- KC005  store of a known constant outside a field's declared domain
- KC006  store whose shape/dtype contradicts the field's contract
         (``_replace`` / ``mrep`` / struct constructors / ``_set1``)

**At runtime (shapes only)** — ``init_state`` / ``empty_inbox`` /
``empty_input`` are built for a geometry with all-distinct axis sizes
and ``kernel.step`` is ``jax.eval_shape``-traced (no compile); declared
vs. actual shape/dtype diffs are KC007.  This closes the loop: the
declarations the static pass trusts are themselves checked against the
arrays the kernel really builds.

Analyzing a custom file set (``run(root, files=[...])``, used by the
fixture tests) reads ``CONTRACTS`` and domain constants from those
files and skips the runtime diff.  Parameters are bound by annotation
(``s: ShardState``) or by the repo's conventional names (``s``, ``box``,
``m``, ``inp``, ``eff``, ``pre``, ``r``, ``out``); the leading [G] axis
(and [K] for the per-message ``m``) is stripped, mirroring vmap/scan.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, replace

from dragonboat_tpu.analysis import tracer_safety as ts
from dragonboat_tpu.analysis.common import (
    FieldContract,
    Finding,
    broadcast_axes,
    parse_contract,
    rel,
)

PASS = "contracts"

KERNEL_FILE = "dragonboat_tpu/core/kernel.py"
CONTRACT_FILES = (
    "dragonboat_tpu/core/kstate.py",
    "dragonboat_tpu/core/kernel.py",
    "dragonboat_tpu/core/fleet.py",
    "dragonboat_tpu/core/health.py",
    "dragonboat_tpu/core/invariants.py",
)
PARAMS_FILE = "dragonboat_tpu/core/params.py"

# modules whose donate_argnums decorations the KC008 cross-check scans:
# kernel.py (the default module of a DONATION entry) plus every module a
# DONATION ``module`` key may name.  scripts/lint.py folds these into
# the contracts pass's --changed-only scope.
DONATION_MODULES = (
    "dragonboat_tpu/core/kernel.py",
    "dragonboat_tpu/parallel/ici.py",
    "dragonboat_tpu/core/router.py",
    "dragonboat_tpu/core/round.py",
    "dragonboat_tpu/parallel/round.py",
)

# KernelParams attribute -> the symbolic axis it sizes
KP_AXIS_ATTRS = {
    "num_peers": "P",
    "log_cap": "CAP",
    "inbox_cap": "K",
    "msg_entries": "E",
    "proposal_cap": "B",
    "readindex_cap": "RI",
}

# Conventional parameter names -> (contract class, axes stripped by the
# enclosing vmap/scan).  Annotations take precedence when present.
NAME_BINDINGS = {
    "s": ("ShardState", ("G",)),
    "state": ("ShardState", ("G",)),
    "box": ("Inbox", ("G",)),
    "inbox": ("Inbox", ("G",)),
    "m": ("Inbox", ("G", "K")),      # one message: the scan strips K too
    "inp": ("StepInput", ("G",)),
    "eff": ("Effects", ()),
    "pre": ("_Pre", ()),
    "r": ("_Resp", ()),
    "out": ("StepOutput", ("G",)),
}

_INT_DTYPES = ("i32", "u32")
_DTYPE_NAMES = {
    "int32": "i32", "uint32": "u32", "float32": "f32", "bool": "bool",
    "bool_": "bool", "int64": "i32", "float64": "f32",
}


@dataclass(frozen=True)
class AVal:
    """Abstract value: symbolic shape + dtype + provenance facts."""

    axes: tuple[str, ...] | None = None  # None = unknown shape
    dtype: str | None = None             # 'i32'|'u32'|'f32'|'bool'|None
    weak: bool = False                   # python-scalar weak type
    const: int | None = None             # known int value (domain checks)
    bound: str | None = None             # values proven in-range of axis
    size_axis: str | None = None         # python int == size of this axis
    maskconst: str | None = None         # python int == size(axis) - 1
    ring: str | None = None              # ring-tagged array: masked axis
    cls: str | None = None               # contract struct this value is
    strip: tuple[str, ...] = ()          # axes stripped from cls's fields
    tup: tuple | None = None             # tuple value (AVal elements)
    dt_marker: str | None = None         # value IS a dtype (I32, jnp.bool_)
    part: str | None = None              # partition: 'G' | 'rep' | None
    bcast: bool = False                  # replicated value explicitly
    #                                      broadcast to a G-shaped operand


UNKNOWN = AVal()
_KP = AVal(cls="<kp>")


def _scalar(dtype, weak=False, const=None, bound=None):
    return AVal(axes=(), dtype=dtype, weak=weak, const=const, bound=bound)


def _is_intlike(v: AVal) -> bool:
    return v.dtype in _INT_DTYPES


def _strip(axes: tuple[str, ...], strip: tuple[str, ...]) -> tuple[str, ...]:
    out = list(axes)
    for ax in strip:
        if out and out[0] == ax:
            out.pop(0)
    return tuple(out)


def _join(a: AVal, b: AVal) -> AVal:
    """Lattice join for where/sel branches.  Optimistic on unknowns."""
    if a.tup is not None and b.tup is not None and len(a.tup) == len(b.tup):
        return AVal(tup=tuple(_join(x, y) for x, y in zip(a.tup, b.tup)))
    if a.cls is not None and a.cls == b.cls:
        return a
    axes, _ = broadcast_axes(a.axes, b.axes)
    if a.dtype is None or b.dtype is None:
        dtype = a.dtype or b.dtype
    elif a.dtype == b.dtype:
        dtype = a.dtype
    elif a.weak and not b.weak:
        dtype = b.dtype
    elif b.weak and not a.weak:
        dtype = a.dtype
    else:
        dtype = None
    const = a.const if a.const == b.const else None
    bound = a.bound if a.bound == b.bound else None
    ring = a.ring if a.ring == b.ring else None
    return AVal(axes=axes, dtype=dtype, weak=a.weak and b.weak,
                const=const, bound=bound, ring=ring)


def _attr_chain(node: ast.AST) -> list[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    parts.reverse()
    return parts


def _ann_name(node: ast.AST | None) -> str | None:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.split(".")[-1]
    return None


class _Ctx:
    """Shared analysis context: contracts, constants, functions."""

    def __init__(self) -> None:
        self.contracts: dict[str, dict[str, FieldContract]] = {}
        self.contract_lines: dict[tuple[str, str], tuple[str, int]] = {}
        self.consts: dict[str, int] = {}
        self.funcs: dict[str, tuple[ts._Module, ast.FunctionDef]] = {}
        self.summaries: dict[str, AVal] = {}
        self.findings: list[Finding] = []

    def field(self, cls: str | None, name: str) -> FieldContract | None:
        if cls is None:
            return None
        return self.contracts.get(cls, {}).get(name)

    def domain_range(self, fc: FieldContract) -> tuple[int, int] | None:
        if fc.domain is None:
            return None
        lo, hi = self.consts.get(fc.domain[0]), self.consts.get(fc.domain[1])
        if lo is None or hi is None:
            return None
        return lo, hi


def _field_aval(ctx: _Ctx, fc: FieldContract, strip: tuple[str, ...]) -> AVal:
    axes = _strip(fc.axes, strip)
    ring = axes[0] if (fc.ring and axes) else None
    return AVal(axes=axes, dtype=fc.dtype, ring=ring)


def _struct_aval(cls: str, strip: tuple[str, ...]) -> AVal:
    return AVal(cls=cls, strip=strip)


def _collect_contracts(ctx: _Ctx, tree: ast.Module, relpath: str) -> None:
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "CONTRACTS"):
            continue
        try:
            table = ast.literal_eval(node.value)
        except (ValueError, SyntaxError):
            ctx.findings.append(Finding(
                PASS, relpath, node.lineno, "KC007",
                "CONTRACTS must be a pure literal dict"))
            continue
        # remember source lines of each field key for finding anchors
        if isinstance(node.value, ast.Dict):
            for ck, cv in zip(node.value.keys, node.value.values):
                if not (isinstance(ck, ast.Constant)
                        and isinstance(cv, ast.Dict)):
                    continue
                for fk in cv.keys:
                    if isinstance(fk, ast.Constant):
                        ctx.contract_lines[(ck.value, fk.value)] = (
                            relpath, fk.lineno)
        for cls, fields in table.items():
            parsed = {}
            for fname, spec in fields.items():
                where = f"{relpath}:{cls}.{fname}"
                try:
                    parsed[fname] = parse_contract(spec, where)
                except ValueError as e:
                    path, line = ctx.contract_lines.get(
                        (cls, fname), (relpath, node.lineno))
                    ctx.findings.append(
                        Finding(PASS, path, line, "KC007", str(e)))
            ctx.contracts.setdefault(cls, {}).update(parsed)


def _collect_consts(ctx: _Ctx, tree: ast.Module) -> None:
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            try:
                v = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                continue
            if isinstance(v, int) and not isinstance(v, bool):
                ctx.consts[node.targets[0].id] = v


# ---------------------------------------------------------------------------
# the per-function abstract interpreter
# ---------------------------------------------------------------------------

# jnp reductions: result drops the reduced axis (or all, without axis=)
_REDUCTIONS = {"sum": None, "any": "bool", "all": "bool", "min": None,
               "max": None, "prod": None, "mean": "f32"}

_INDEX_FUNCS = {
    #  name: (array argpos, index argpos, value argpos or None, row)
    "_get1": (1, 2, None, False),
    "_get_row": (1, 2, None, True),
    "_set1": (0, 1, 2, False),
    "_set_row": (0, 1, 2, True),
}


class _Interp:
    def __init__(self, ctx: _Ctx, relpath: str) -> None:
        self.ctx = ctx
        self.relpath = relpath
        self.env: dict[str, AVal] = {}
        self.returns: list[AVal] = []
        self._flagged: set[tuple[int, str]] = set()

    # -- reporting -------------------------------------------------------
    def flag(self, node: ast.AST, rule: str, msg: str) -> None:
        key = (getattr(node, "lineno", 0), rule)
        if key in self._flagged:
            return
        self._flagged.add(key)
        self.ctx.findings.append(
            Finding(PASS, self.relpath, getattr(node, "lineno", 0),
                    rule, msg))

    # -- parameter binding ----------------------------------------------
    def bind_params(self, fn: ast.FunctionDef | ast.Lambda) -> None:
        args = fn.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            ann = _ann_name(getattr(a, "annotation", None))
            name = a.arg
            if name == "kp" or ann == "KernelParams":
                self.env[name] = _KP
            elif ann in self.ctx.contracts:
                strip = NAME_BINDINGS.get(name, (None, ("G",)))[1]
                self.env[name] = _struct_aval(ann, strip)
            elif name in NAME_BINDINGS:
                cls, strip = NAME_BINDINGS[name]
                if cls in self.ctx.contracts:
                    self.env[name] = _struct_aval(cls, strip)
                else:
                    self.env[name] = UNKNOWN
            else:
                self.env[name] = UNKNOWN
        for extra in (args.vararg, args.kwarg):
            if extra is not None:
                self.env[extra.arg] = UNKNOWN

    # -- statements ------------------------------------------------------
    def exec_body(self, body: list[ast.stmt]) -> None:
        for st in body:
            self.exec_stmt(st)

    def exec_stmt(self, st: ast.stmt) -> None:
        if isinstance(st, ast.Assign):
            v = self.eval(st.value)
            for tgt in st.targets:
                self.assign(tgt, v)
        elif isinstance(st, ast.AnnAssign):
            if st.value is not None:
                self.assign(st.target, self.eval(st.value))
        elif isinstance(st, ast.AugAssign):
            v = self.binop(st, self.eval(st.target), self.eval(st.value),
                           st.op)
            self.assign(st.target, v)
        elif isinstance(st, ast.Expr):
            self.eval(st.value)
        elif isinstance(st, ast.Return):
            if st.value is not None:
                self.returns.append(self.eval(st.value))
        elif isinstance(st, ast.If):
            # host-level branch (trace-time static): walk both arms with
            # a shared env — a sound over-approximation for lint purposes
            self.eval(st.test)
            self.exec_body(st.body)
            self.exec_body(st.orelse)
        elif isinstance(st, ast.For):
            it = self.eval(st.iter)
            self.assign(st.target, self._loop_var(st.iter, it))
            self.exec_body(st.body)
            self.exec_body(st.orelse)
        elif isinstance(st, ast.While):
            self.eval(st.test)
            self.exec_body(st.body)
        elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            sub = _Interp(self.ctx, self.relpath)
            sub.env.update(self.env)
            sub.bind_params(st)
            sub._flagged = self._flagged
            sub.exec_body(st.body)
        elif isinstance(st, ast.Assert):
            self.eval(st.test)
        elif isinstance(st, ast.With):
            self.exec_body(st.body)
        elif isinstance(st, ast.Try):
            self.exec_body(st.body)
            for h in st.handlers:
                self.exec_body(h.body)
            self.exec_body(st.orelse)
            self.exec_body(st.finalbody)
        # Raise / Pass / Import / Global / Delete: nothing to track

    def _loop_var(self, iter_node: ast.AST, it: AVal) -> AVal:
        # for j in range(RI): j is an in-range index of axis RI
        if isinstance(iter_node, ast.Call) \
                and isinstance(iter_node.func, ast.Name) \
                and iter_node.func.id == "range" \
                and len(iter_node.args) == 1:
            n = self.eval(iter_node.args[0])
            if n.size_axis is not None:
                return _scalar("i32", weak=True, bound=n.size_axis)
            return _scalar("i32", weak=True)
        return UNKNOWN

    def assign(self, tgt: ast.AST, v: AVal) -> None:
        if isinstance(tgt, ast.Name):
            self.env[tgt.id] = v
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            if v.tup is not None and len(v.tup) == len(tgt.elts):
                for el, sub in zip(tgt.elts, v.tup):
                    self.assign(el, sub)
            else:
                for el in tgt.elts:
                    self.assign(el, UNKNOWN)
        elif isinstance(tgt, ast.Starred):
            self.assign(tgt.value, UNKNOWN)
        # attribute/subscript stores: no local binding

    # -- expressions -----------------------------------------------------
    def eval(self, node: ast.AST | None) -> AVal:
        if node is None:
            return UNKNOWN
        meth = getattr(self, "eval_" + type(node).__name__, None)
        if meth is not None:
            return meth(node)
        return UNKNOWN

    def eval_Constant(self, node: ast.Constant) -> AVal:
        v = node.value
        if isinstance(v, bool):
            return _scalar("bool", weak=True, const=int(v))
        if isinstance(v, int):
            return _scalar("i32", weak=True, const=v)
        if isinstance(v, float):
            return _scalar("f32", weak=True)
        return UNKNOWN

    def eval_Name(self, node: ast.Name) -> AVal:
        if node.id in self.env:
            return self.env[node.id]
        if node.id == "I32":
            return AVal(dt_marker="i32")
        if node.id == "INT_MAX":
            return _scalar("i32", weak=True)
        if node.id in ("bool", "int"):
            return AVal(dt_marker="bool" if node.id == "bool" else "i32")
        if node.id in self.ctx.consts:
            return _scalar("i32", weak=True, const=self.ctx.consts[node.id])
        return UNKNOWN

    def eval_Tuple(self, node: ast.Tuple) -> AVal:
        return AVal(tup=tuple(self.eval(e) for e in node.elts))

    eval_List = eval_Tuple

    def eval_NamedExpr(self, node: ast.NamedExpr) -> AVal:
        v = self.eval(node.value)
        self.assign(node.target, v)
        return v

    def eval_IfExp(self, node: ast.IfExp) -> AVal:
        self.eval(node.test)
        return _join(self.eval(node.body), self.eval(node.orelse))

    def eval_BoolOp(self, node: ast.BoolOp) -> AVal:
        for v in node.values:
            self.eval(v)
        return UNKNOWN

    def eval_JoinedStr(self, node: ast.JoinedStr) -> AVal:
        for v in node.values:
            self.eval(v)
        return UNKNOWN

    def eval_FormattedValue(self, node: ast.FormattedValue) -> AVal:
        self.eval(node.value)
        return UNKNOWN

    def eval_Lambda(self, node: ast.Lambda) -> AVal:
        return UNKNOWN

    def eval_Starred(self, node: ast.Starred) -> AVal:
        return self.eval(node.value)

    def eval_Attribute(self, node: ast.Attribute) -> AVal:
        # jnp.iinfo(...).max / .min: a weak scalar bound constant
        if node.attr in ("max", "min") and isinstance(node.value, ast.Call):
            base = _attr_chain(node.value.func)
            if base and base[-1] in ("iinfo", "finfo"):
                return _scalar("f32" if base[-1] == "finfo" else "i32",
                               weak=True)
        v = self.eval(node.value)
        if v is _KP or v.cls == "<kp>":
            if node.attr in KP_AXIS_ATTRS:
                return AVal(axes=(), dtype="i32", weak=True,
                            size_axis=KP_AXIS_ATTRS[node.attr])
            return _scalar("i32", weak=True)  # host config scalar/flag
        if v.cls is not None:
            fc = self.ctx.field(v.cls, node.attr)
            if fc is not None:
                return _field_aval(self.ctx, fc, v.strip)
            return UNKNOWN
        if node.attr == "shape" and v.axes is not None:
            return AVal(tup=tuple(
                AVal(axes=(), dtype="i32", weak=True, size_axis=ax)
                if ax not in ("1", "?")
                else _scalar("i32", weak=True, const=1 if ax == "1" else None)
                for ax in v.axes))
        if node.attr == "dtype" and v.dtype is not None:
            return AVal(dt_marker=v.dtype)
        if node.attr == "T" and v.axes is not None:
            return replace(v, axes=tuple(reversed(v.axes)), ring=None)
        # jnp.int32 / jnp.uint32 / jnp.bool_ as dtype markers
        chain = _attr_chain(node)
        if len(chain) >= 2 and chain[0] in ("jnp", "np", "jax", "numpy") \
                and chain[-1] in _DTYPE_NAMES:
            return AVal(dt_marker=_DTYPE_NAMES[chain[-1]])
        # module constants via an alias (P.LEADER, params.K_VOTER, ...)
        if isinstance(node.value, ast.Name) and node.attr in self.ctx.consts \
                and node.value.id not in self.env:
            return _scalar("i32", weak=True, const=self.ctx.consts[node.attr])
        return UNKNOWN

    # -- operators -------------------------------------------------------
    def _broadcast(self, node: ast.AST, a: AVal, b: AVal,
                   what: str) -> tuple[str, ...] | None:
        axes, conflict = broadcast_axes(a.axes, b.axes)
        if conflict:
            self.flag(node, "KC001",
                      f"implicit broadcast aligns distinct named axes in "
                      f"{what}: {conflict} (shapes {list(a.axes)} vs "
                      f"{list(b.axes)} — equal extents would silently "
                      "cross-wire lanes)")
        return axes

    def _dtype_of_binop(self, node: ast.AST, a: AVal, b: AVal,
                        op: ast.operator) -> str | None:
        da, db = a.dtype, b.dtype
        if da is None or db is None:
            return da or db
        strong = not (a.weak or b.weak)
        kind = type(op).__name__
        if kind in ("BitAnd", "BitOr", "BitXor"):
            if da == "bool" and db == "bool":
                return "bool"
            if "f32" in (da, db):
                self.flag(node, "KC002",
                          f"bitwise {kind} on float operand ({da}/{db})")
                return None
            if strong and ("bool" in (da, db)) and (da != db):
                self.flag(node, "KC002",
                          f"bitwise {kind} mixes bool and "
                          f"{da if db == 'bool' else db} "
                          "(mask and integer cross-wired?)")
                return None
            if strong and da != db:
                self.flag(node, "KC002",
                          f"bitwise {kind} mixes {da} and {db}")
            return da if not a.weak else db
        if kind == "Div":
            if da in _INT_DTYPES and db in _INT_DTYPES:
                self.flag(node, "KC002",
                          "int/int true division silently produces float "
                          "(use // or an explicit astype)")
                return "f32"
            return "f32"
        # Add/Sub/Mult/FloorDiv/Mod/Pow/shifts
        if kind == "Mult" and "bool" in (da, db) and (
                db in _INT_DTYPES or da in _INT_DTYPES):
            # bool * int is the kernel's masking idiom — deliberate
            return da if da in _INT_DTYPES else db
        if strong and "bool" in (da, db) and da != db:
            self.flag(node, "KC002",
                      f"{kind} arithmetic on bool and {da if db == 'bool' else db} "
                      "operands (silent upcast)")
            return None
        if strong and ("f32" in (da, db)) and (da != db):
            self.flag(node, "KC002",
                      f"{kind} mixes {da} and {db}: silent float upcast")
            return "f32"
        if strong and da in _INT_DTYPES and db in _INT_DTYPES and da != db:
            self.flag(node, "KC002",
                      f"{kind} mixes {da} and {db} (signedness drift)")
            return None
        if a.weak and not b.weak:
            return db
        return da

    def binop(self, node: ast.AST, a: AVal, b: AVal,
              op: ast.operator) -> AVal:
        axes = self._broadcast(node, a, b, "arithmetic")
        dtype = self._dtype_of_binop(node, a, b, op)
        kind = type(op).__name__
        bound = None
        # x & (size - 1): the ring-mask idiom proves in-range
        if kind == "BitAnd":
            bound = a.maskconst or b.maskconst
        # size - 1 yields a mask constant
        maskconst = None
        if kind == "Sub" and a.size_axis is not None and b.const == 1:
            maskconst = a.size_axis
        weak = a.weak and b.weak
        const = None
        if a.const is not None and b.const is not None:
            try:
                const = {
                    "Add": a.const + b.const, "Sub": a.const - b.const,
                    "Mult": a.const * b.const,
                }.get(kind)
            except Exception:
                const = None
        return AVal(axes=axes, dtype=dtype, weak=weak, const=const,
                    bound=bound, maskconst=maskconst)

    def eval_BinOp(self, node: ast.BinOp) -> AVal:
        return self.binop(node, self.eval(node.left), self.eval(node.right),
                          node.op)

    def eval_UnaryOp(self, node: ast.UnaryOp) -> AVal:
        v = self.eval(node.operand)
        if isinstance(node.op, ast.Not):
            return _scalar("bool", weak=True)
        if isinstance(node.op, ast.Invert):
            return replace(v, const=None, bound=None, maskconst=None)
        if isinstance(node.op, ast.USub):
            c = -v.const if v.const is not None else None
            return replace(v, const=c, bound=None, size_axis=None,
                           maskconst=None)
        return v

    def eval_Compare(self, node: ast.Compare) -> AVal:
        vals = [self.eval(node.left)] + [self.eval(c)
                                         for c in node.comparators]
        axes: tuple[str, ...] | None = vals[0].axes
        cur = vals[0]
        for op, nxt in zip(node.ops, vals[1:]):
            if isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn)):
                cur = nxt
                continue
            axes = self._broadcast(node, replace(cur, axes=axes), nxt,
                                   "comparison")
            da, db = cur.dtype, nxt.dtype
            if da and db and not (cur.weak or nxt.weak) \
                    and ("bool" in (da, db)) and da != db:
                self.flag(node, "KC003",
                          f"comparison mixes bool and "
                          f"{da if db == 'bool' else db} operands")
            cur = nxt
        return AVal(axes=axes, dtype="bool")

    # -- subscripts ------------------------------------------------------
    def _check_ring_index(self, node: ast.AST, arr: AVal, idx: AVal,
                          via: str) -> None:
        if arr.ring is None:
            return
        if idx.dtype == "bool":
            return  # boolean masking, not positional indexing
        if idx.const is not None:
            return  # static index: in-range by construction/review
        if idx.bound == arr.ring:
            return
        self.flag(node, "KC004",
                  f"dynamic index into ring array (axis {arr.ring}) via "
                  f"{via} without the `& (cap - 1)` ring mask (or an "
                  "argmax/arange/min-against-cap-1 in-range proof) — an "
                  "unwrapped log position reads/writes the wrong slot "
                  "once the log exceeds the ring capacity")

    def _subscript_axes(self, node: ast.Subscript, base: AVal,
                        items: list[ast.AST]) -> AVal:
        if base.axes is None:
            # still ring-check a fully dynamic first index
            if items and not isinstance(items[0], ast.Slice):
                self._check_ring_index(node, base, self.eval(items[0]),
                                       "subscript")
            return UNKNOWN
        out: list[str] = []
        dim = 0
        for it in items:
            if isinstance(it, ast.Slice):
                self.eval(it.lower)
                self.eval(it.upper)
                if dim < len(base.axes):
                    out.append(base.axes[dim])
                dim += 1
            elif isinstance(it, ast.Constant) and it.value is None:
                out.append("1")
            else:
                iv = self.eval(it)
                if dim == 0:
                    self._check_ring_index(node, base, iv, "subscript")
                if iv.axes is not None and iv.axes != ():
                    out.extend(iv.axes)   # array index: its axes splice in
                dim += 1
        out.extend(base.axes[dim:])
        return AVal(axes=tuple(out), dtype=base.dtype,
                    bound=base.bound)

    def eval_Subscript(self, node: ast.Subscript) -> AVal:
        base = self.eval(node.value)
        sl = node.slice
        if base.tup is not None:
            if isinstance(sl, ast.Constant) and isinstance(sl.value, int):
                try:
                    return base.tup[sl.value]
                except IndexError:
                    return UNKNOWN
            return UNKNOWN
        if base.cls is not None or base.dt_marker is not None:
            return UNKNOWN
        items = list(sl.elts) if isinstance(sl, ast.Tuple) else [sl]
        return self._subscript_axes(node, base, items)

    # -- calls -----------------------------------------------------------
    def _dtype_from_arg(self, node: ast.AST | None) -> str | None:
        if node is None:
            return None
        v = self.eval(node)
        if v.dt_marker is not None:
            return v.dt_marker
        name = _ann_name(node)
        return _DTYPE_NAMES.get(name or "", None)

    def _kwdict(self, node: ast.Call) -> dict[str, ast.AST]:
        return {k.arg: k.value for k in node.keywords if k.arg is not None}

    def _check_store(self, node: ast.AST, cls: str, fname: str,
                     v: AVal, strip: tuple[str, ...]) -> None:
        fc = self.ctx.field(cls, fname)
        if fc is None:
            if fname in ("lv", "ent_val", "prop_val", "s_ent_val"):
                return
            self.flag(node, "KC006",
                      f"store to {cls}.{fname}: field has no declared "
                      "contract (add it to CONTRACTS)")
            return
        declared = _field_aval(self.ctx, fc, strip)
        # shape: the stored value must broadcast INTO the declared shape
        if v.axes is not None and declared.axes is not None:
            axes, conflict = broadcast_axes(declared.axes, v.axes)
            if conflict or (axes != declared.axes and "?" not in axes):
                self.flag(node, "KC006",
                          f"store to {cls}.{fname}: value shape "
                          f"{list(v.axes)} does not match declared "
                          f"{list(fc.axes)} (per-shard {list(declared.axes)})")
        # dtype: strong mismatches only; weak python scalars adapt
        if v.dtype is not None and not v.weak and v.dtype != fc.dtype:
            self.flag(node, "KC006",
                      f"store to {cls}.{fname}: value dtype {v.dtype} "
                      f"contradicts declared {fc.dtype}")
        dom = self.ctx.domain_range(fc)
        if dom is not None and v.const is not None \
                and not (dom[0] <= v.const <= dom[1]):
            self.flag(node, "KC005",
                      f"store of constant {v.const} to {cls}.{fname}: "
                      f"outside declared domain "
                      f"{fc.domain[0]}..{fc.domain[1]} [{dom[0]}, {dom[1]}]")

    def _call_replace(self, node: ast.Call, target: AVal,
                      kwargs: dict[str, ast.AST]) -> AVal:
        for fname, vnode in kwargs.items():
            v = self.eval(vnode)
            if target.cls is not None and target.cls in self.ctx.contracts:
                self._check_store(node, target.cls, fname, v, target.strip)
        return target

    def _call_ctor(self, node: ast.Call, cls: str) -> AVal:
        strip = ("G",) if any(
            fc.axes[:1] == ("G",) for fc in self.ctx.contracts[cls].values()
        ) else ()
        for a in node.args:
            self.eval(a)
        for fname, vnode in self._kwdict(node).items():
            self._check_store(node, cls, fname, self.eval(vnode), strip)
        return _struct_aval(cls, strip)

    def _call_index_func(self, node: ast.Call, name: str) -> AVal:
        arr_pos, idx_pos, val_pos, row = _INDEX_FUNCS[name]
        args = node.args
        if len(args) <= max(arr_pos, idx_pos):
            return UNKNOWN
        arr = self.eval(args[arr_pos])
        idx = self.eval(args[idx_pos])
        self._check_ring_index(node, arr, idx, name)
        if val_pos is not None and len(args) > val_pos:
            v = self.eval(args[val_pos])
            # domain/dtype checks when the array is a contract field read
            src = args[arr_pos]
            if isinstance(src, ast.Attribute):
                holder = self.eval(src.value)
                fc = self.ctx.field(holder.cls, src.attr)
                if fc is not None:
                    dom = self.ctx.domain_range(fc)
                    if dom is not None and v.const is not None \
                            and not (dom[0] <= v.const <= dom[1]):
                        self.flag(node, "KC005",
                                  f"{name} stores constant {v.const} into "
                                  f"{holder.cls}.{src.attr}: outside domain "
                                  f"{fc.domain[0]}..{fc.domain[1]} "
                                  f"[{dom[0]}, {dom[1]}]")
                    if v.dtype is not None and not v.weak \
                            and v.dtype != fc.dtype:
                        self.flag(node, "KC006",
                                  f"{name} stores {v.dtype} value into "
                                  f"{holder.cls}.{src.attr} declared "
                                  f"{fc.dtype}")
            for extra in args[val_pos + 1:]:
                self.eval(extra)
            return arr
        # read form: result takes the index's shape (+ trailing row axes)
        if name == "_get_row":
            tail = arr.axes[1:] if arr.axes else None
            return AVal(axes=tail, dtype=arr.dtype)
        return AVal(axes=idx.axes, dtype=arr.dtype, bound=arr.bound)

    def _call_jnp(self, node: ast.Call, fname: str) -> AVal | None:
        args = node.args
        kw = self._kwdict(node)

        def arg(i):
            return self.eval(args[i]) if len(args) > i else UNKNOWN

        if fname == "where":
            c, a, b = arg(0), arg(1), arg(2)
            j = _join(a, b)
            axes = self._broadcast(node, replace(c, dtype=None),
                                   replace(j, dtype=None), "jnp.where")
            if a.dtype and b.dtype and not (a.weak or b.weak) \
                    and a.dtype != b.dtype \
                    and not ({a.dtype, b.dtype} <= set(_INT_DTYPES)):
                self.flag(node, "KC002",
                          f"jnp.where joins {a.dtype} and {b.dtype} "
                          "branches: silent upcast")
            return replace(j, axes=axes)
        if fname == "arange":
            n = arg(0)
            dt = self._dtype_from_arg(kw.get("dtype")) or "i32"
            if len(args) == 1 and n.size_axis is not None:
                return AVal(axes=(n.size_axis,), dtype=dt,
                            bound=n.size_axis)
            return AVal(axes=("?",), dtype=dt)
        if fname in ("zeros", "ones", "full", "empty"):
            shape = args[0] if args else None
            dt_node = kw.get("dtype")
            if fname == "full":
                dt_node = dt_node or (args[2] if len(args) > 2 else None)
                fill = arg(1)
                dt = self._dtype_from_arg(dt_node) or fill.dtype
                return AVal(axes=self._shape_from(shape), dtype=dt,
                            const=fill.const)
            dt_node = dt_node or (args[1] if len(args) > 1 else None)
            dt = self._dtype_from_arg(dt_node) or "f32"
            return AVal(axes=self._shape_from(shape), dtype=dt)
        if fname in ("zeros_like", "ones_like", "full_like", "empty_like"):
            base = arg(0)
            dt_node = kw.get("dtype")
            if fname == "full_like":
                # full_like(a, fill_value, dtype=None)
                dt_node = dt_node or (args[2] if len(args) > 2 else None)
                fill = arg(1)
                dt = self._dtype_from_arg(dt_node) or base.dtype
                return AVal(axes=base.axes, dtype=dt, const=fill.const)
            # zeros_like(a, dtype=None)
            dt_node = dt_node or (args[1] if len(args) > 1 else None)
            dt = self._dtype_from_arg(dt_node) or base.dtype
            zc = 0 if fname == "zeros_like" else 1
            return AVal(axes=base.axes, dtype=dt,
                        const=zc if fname in ("zeros_like", "ones_like")
                        else None)
        if fname in ("asarray", "array"):
            v = arg(0)
            dt = self._dtype_from_arg(
                kw.get("dtype") or (args[1] if len(args) > 1 else None))
            if dt is not None:
                return replace(v, dtype=dt, weak=False) \
                    if v.axes is not None else AVal(axes=None, dtype=dt)
            return v
        if fname == "broadcast_to":
            v, shape = arg(0), args[1] if len(args) > 1 else None
            axes = self._shape_from(shape)
            if v.axes is not None and axes is not None:
                _, conflict = broadcast_axes(axes, v.axes)
                if conflict:
                    self.flag(node, "KC001",
                              f"jnp.broadcast_to aligns distinct named "
                              f"axes: {conflict}")
            return AVal(axes=axes, dtype=v.dtype)
        if fname in ("minimum", "maximum"):
            a, b = arg(0), arg(1)
            axes = self._broadcast(node, a, b, f"jnp.{fname}")
            dt = self._dtype_of_binop(node, a, b, ast.Add())
            bound = None
            if fname == "minimum":
                # min against (size - 1), or against an already-bounded
                # value, keeps the result in range of that axis
                bound = a.maskconst or b.maskconst or a.bound or b.bound
            return AVal(axes=axes, dtype=dt, bound=bound)
        if fname == "clip":
            v = arg(0)
            hi = self.eval(kw.get("max")) if "max" in kw else arg(2)
            bound = hi.maskconst
            return replace(v, bound=bound or v.bound, const=None,
                           size_axis=None, maskconst=None, ring=None)
        if fname in _REDUCTIONS:
            v = arg(0)
            for extra in args[1:]:
                self.eval(extra)
            dt = _REDUCTIONS[fname] or v.dtype
            axis_node = kw.get("axis")
            if axis_node is None and len(args) > 1:
                axis_node = args[1]
            return self._reduce(v, axis_node, dt)
        if fname in ("argmax", "argmin"):
            v = arg(0)
            bound = None
            if v.axes is not None and len(v.axes) == 1 \
                    and v.axes[0] not in ("1", "?"):
                bound = v.axes[0]
            return _scalar("i32", bound=bound)
        if fname in ("sort", "cumsum", "flip", "roll", "abs", "sign",
                     "square"):
            v = arg(0)
            for extra in args[1:]:
                self.eval(extra)
            return replace(v, bound=None, const=None, maskconst=None,
                           ring=None)
        if fname == "expand_dims":
            v, ax = arg(0), arg(1)
            if v.axes is not None and ax.const is not None:
                lst = list(v.axes)
                pos = ax.const if ax.const >= 0 else len(lst) + 1 + ax.const
                if 0 <= pos <= len(lst):
                    lst.insert(pos, "1")
                    return AVal(axes=tuple(lst), dtype=v.dtype)
            return AVal(axes=None, dtype=v.dtype)
        if fname in ("concatenate", "stack", "hstack", "vstack"):
            for a in args:
                self.eval(a)
            return UNKNOWN
        if fname in ("int32", "uint32", "float32", "bool_"):
            v = arg(0)
            return replace(v, dtype=_DTYPE_NAMES[fname], weak=False) \
                if v.axes is not None \
                else AVal(axes=None, dtype=_DTYPE_NAMES[fname])
        if fname in ("logical_and", "logical_or", "logical_xor"):
            a, b = arg(0), arg(1)
            axes = self._broadcast(node, a, b, f"jnp.{fname}")
            return AVal(axes=axes, dtype="bool")
        if fname == "logical_not":
            v = arg(0)
            return AVal(axes=v.axes, dtype="bool")
        return None

    def _shape_from(self, node: ast.AST | None) -> tuple[str, ...] | None:
        if node is None:
            return None
        v = self.eval(node)
        if v.tup is not None:
            out = []
            for e in v.tup:
                if e.size_axis is not None:
                    out.append(e.size_axis)
                elif e.const == 1:
                    out.append("1")
                else:
                    out.append("?")
            return tuple(out)
        if v.size_axis is not None:      # scalar int shape
            return (v.size_axis,)
        if v.axes is not None and v.axes == () and v.dtype in _INT_DTYPES:
            return ("?",)
        if v.tup is None and v.axes is None:
            return None
        return None

    def _reduce(self, v: AVal, axis_node: ast.AST | None,
                dt: str | None) -> AVal:
        if axis_node is None:
            return AVal(axes=(), dtype=dt)
        ax = self.eval(axis_node)
        if v.axes is not None and ax.const is not None:
            lst = list(v.axes)
            pos = ax.const if ax.const >= 0 else len(lst) + ax.const
            if 0 <= pos < len(lst):
                lst.pop(pos)
                return AVal(axes=tuple(lst), dtype=dt)
        return AVal(axes=None, dtype=dt)

    def eval_Call(self, node: ast.Call) -> AVal:
        func = node.func

        # ----- .at[idx].set(v) chains ---------------------------------
        if isinstance(func, ast.Attribute) \
                and func.attr in ("set", "add", "multiply", "max", "min") \
                and isinstance(func.value, ast.Subscript) \
                and isinstance(func.value.value, ast.Attribute) \
                and func.value.value.attr == "at":
            base = self.eval(func.value.value.value)
            sl = func.value.slice
            items = list(sl.elts) if isinstance(sl, ast.Tuple) else [sl]
            if items and not isinstance(items[0], ast.Slice):
                self._check_ring_index(node, base, self.eval(items[0]),
                                       ".at[]")
            for a in node.args:
                self.eval(a)
            return replace(base, const=None)

        # ----- method calls -------------------------------------------
        if isinstance(func, ast.Attribute):
            recv = func.value
            attr = func.attr
            chain = _attr_chain(func)
            root = chain[0] if chain else None
            if attr == "_replace":
                target = self.eval(recv)
                return self._call_replace(node, target, self._kwdict(node))
            if attr == "astype":
                v = self.eval(recv)
                dt = self._dtype_from_arg(node.args[0]) if node.args else None
                return AVal(axes=v.axes, dtype=dt or None, bound=v.bound)
            if root in ("jnp", "np", "numpy") or (
                    root == "jax" and len(chain) > 1
                    and chain[1] == "numpy"):
                res = self._call_jnp(node, attr)
                if res is not None:
                    return res
                for a in node.args:
                    self.eval(a)
                for k in node.keywords:
                    self.eval(k.value)
                return UNKNOWN
            if attr == "scan" and root in ("jax", "lax"):
                # (carry, stacked) = scan(f, init, xs): carry keeps init's
                # abstract value — the precision anchor for _shard_step
                init = self.eval(node.args[1]) if len(node.args) > 1 else \
                    self.eval(self._kwdict(node).get("init"))
                for a in node.args[2:]:
                    self.eval(a)
                return AVal(tup=(init, UNKNOWN))
            if attr in ("tree_map", "map") and root in ("jax", "tree",
                                                        "tree_util"):
                best = UNKNOWN
                for a in node.args[1:]:
                    v = self.eval(a)
                    if best is UNKNOWN and (v.cls is not None
                                            or v.axes is not None):
                        best = v
                return best
            if attr in ("fori_loop", "while_loop"):
                for a in node.args:
                    self.eval(a)
                init = self.eval(node.args[2]) if attr == "fori_loop" \
                    and len(node.args) > 2 else UNKNOWN
                return init
            if attr in _REDUCTIONS:     # x.sum(axis=..) method form
                v = self.eval(recv)
                kw = self._kwdict(node)
                axis_node = kw.get("axis") or (
                    node.args[0] if node.args else None)
                return self._reduce(v, axis_node,
                                    _REDUCTIONS[attr] or v.dtype)
            if attr == "reshape":
                self.eval(recv)
                for a in node.args:
                    self.eval(a)
                return UNKNOWN
            # unknown method: evaluate args for side-findings
            self.eval(recv)
            for a in node.args:
                self.eval(a)
            for k in node.keywords:
                self.eval(k.value)
            return UNKNOWN

        # ----- plain-name calls ---------------------------------------
        if isinstance(func, ast.Name):
            name = func.id
            if name == "sel":
                return self._call_jnp(node, "where") or UNKNOWN
            if name == "mrep":
                target = self.eval(node.args[0]) if node.args else UNKNOWN
                if len(node.args) > 1:
                    self.eval(node.args[1])
                return self._call_replace(node, target, self._kwdict(node))
            if name == "_slot" and len(node.args) == 2:
                idx = self.eval(node.args[1])
                return AVal(axes=idx.axes, dtype="i32", bound="CAP")
            if name in _INDEX_FUNCS:
                return self._call_index_func(node, name)
            if name == "onehot_select" and len(node.args) >= 3:
                oh = self.eval(node.args[0])
                arr = self.eval(node.args[1])
                return self._reduce(arr, node.args[2], arr.dtype)
            if name in self.ctx.contracts:
                return self._call_ctor(node, name)
            if name in ("range", "len", "sorted", "list", "tuple", "dict",
                        "set", "enumerate", "zip", "print", "isinstance",
                        "getattr", "hasattr", "repr", "str", "min", "max"):
                for a in node.args:
                    self.eval(a)
                return UNKNOWN
            if name in ("int", "float", "bool"):
                v = self.eval(node.args[0]) if node.args else UNKNOWN
                return _scalar({"int": "i32", "float": "f32",
                                "bool": "bool"}[name], weak=True,
                               const=v.const)
            if name in self.ctx.funcs:
                for a in node.args:
                    self.eval(a)
                for k in node.keywords:
                    self.eval(k.value)
                return self.ctx.summaries.get(name, UNKNOWN)
            for a in node.args:
                self.eval(a)
            for k in node.keywords:
                self.eval(k.value)
            return UNKNOWN

        # calling the result of a call: jax.vmap(f)(...) etc.
        self.eval(func)
        for a in node.args:
            self.eval(a)
        for k in node.keywords:
            self.eval(k.value)
        return UNKNOWN


# ---------------------------------------------------------------------------
# driving the interpreter over the jit-reachable function set
# ---------------------------------------------------------------------------


def _reachable(mods: list[ts._Module]) -> tuple[set[str], dict[str, set[str]]]:
    """Jit-reachable function names + the call graph (tracer-safety walk)."""
    global_funcs: dict[str, tuple[ts._Module, ast.FunctionDef]] = {}
    for m in mods:
        for name, fn in m.funcs.items():
            global_funcs.setdefault(name, (m, fn))
    traced: set[str] = set()
    all_calls: dict[str, set[str]] = {}
    for m in mods:
        seeds, calls = ts._seed_and_calls(m)
        traced |= seeds
        for name, callees in calls.items():
            all_calls.setdefault(name, set()).update(
                m.imports.get(c, c) for c in callees)
    frontier = list(traced)
    while frontier:
        name = frontier.pop()
        for callee in all_calls.get(name, ()):
            if callee in global_funcs and callee not in traced:
                traced.add(callee)
                frontier.append(callee)
    return traced & set(global_funcs), all_calls


def _topo_order(names: set[str], calls: dict[str, set[str]]) -> list[str]:
    """Callees before callers (cycles broken arbitrarily): summaries of
    helpers exist by the time their call sites are interpreted."""
    order: list[str] = []
    state: dict[str, int] = {}

    def visit(n: str) -> None:
        if state.get(n):            # 1 = in progress, 2 = done
            return
        state[n] = 1
        for c in sorted(calls.get(n, ())):
            if c in names and state.get(c) != 1:
                visit(c)
        state[n] = 2
        order.append(n)

    for n in sorted(names):
        visit(n)
    return order


def _summary_join(avals: list[AVal]) -> AVal:
    if not avals:
        return UNKNOWN
    out = avals[0]
    for v in avals[1:]:
        out = _join(out, v)
    return out


def _analyze(ctx: _Ctx, mods: list[ts._Module], root: str) -> None:
    reachable, calls = _reachable(mods)
    global_funcs: dict[str, tuple[ts._Module, ast.FunctionDef]] = {}
    for m in mods:
        for name, fn in m.funcs.items():
            global_funcs.setdefault(name, (m, fn))
    ctx.funcs = global_funcs
    for name in _topo_order(reachable, calls):
        mod, fn = global_funcs[name]
        interp = _Interp(ctx, rel(root, mod.path))
        interp.bind_params(fn)
        interp.exec_body(fn.body)
        ctx.summaries[name] = _summary_join(interp.returns)


# ---------------------------------------------------------------------------
# runtime cross-validation (KC007): declared vs eval-shaped reality
# ---------------------------------------------------------------------------

# all-distinct axis sizes: shape equality then implies axis-name equality
_CHECK_GEOMETRY = dict(num_peers=3, log_cap=32, inbox_cap=4, msg_entries=5,
                       proposal_cap=6, readindex_cap=16)
_CHECK_SHARDS = 7


def _dtype_name(dt) -> str:
    return _DTYPE_NAMES.get(str(dt), str(dt))


def runtime_check(kp=None, num_shards: int = _CHECK_SHARDS,
                  root: str | None = None,
                  eval_step: bool = True) -> list[Finding]:
    """Diff the declared CONTRACTS against the structures the kernel
    actually builds (init_state / empty_inbox / empty_input and the
    eval_shape of one step).  Shapes only — nothing is compiled."""
    import jax

    from dragonboat_tpu.core import kernel, kstate
    from dragonboat_tpu.core import params as kparams

    if root is None:
        root = os.getcwd()
    if kp is None:
        kp = kparams.KernelParams(**_CHECK_GEOMETRY)
    G = num_shards
    axis_env = {
        "G": G, "P": kp.num_peers, "CAP": kp.log_cap, "K": kp.inbox_cap,
        "E": kp.msg_entries, "B": kp.proposal_cap, "RI": kp.readindex_cap,
    }
    ctx = _Ctx()
    kpath = os.path.join(root, CONTRACT_FILES[0])
    for cf in CONTRACT_FILES:
        p = os.path.join(root, cf)
        if not os.path.exists(p):
            continue
        with open(p, encoding="utf-8") as f:
            _collect_contracts(ctx, ast.parse(f.read(), filename=p),
                               rel(root, p))
    findings = list(ctx.findings)

    def anchor(cls: str, fname: str) -> tuple[str, int]:
        return ctx.contract_lines.get((cls, fname), (rel(root, kpath), 1))

    def diff(cls: str, struct) -> None:
        decl = ctx.contracts.get(cls)
        if decl is None:
            findings.append(Finding(
                PASS, rel(root, kpath), 1, "KC007",
                f"no CONTRACTS entry for {cls}"))
            return
        actual_fields = set(getattr(struct, "_fields", ()))
        for fname in sorted(actual_fields - set(decl)):
            path, line = anchor(cls, next(iter(decl), fname))
            findings.append(Finding(
                PASS, path, line, "KC007",
                f"{cls}.{fname} exists on the struct but has no declared "
                "contract"))
        for fname, fc in decl.items():
            path, line = anchor(cls, fname)
            if fname not in actual_fields:
                findings.append(Finding(
                    PASS, path, line, "KC007",
                    f"{cls}.{fname} declared but absent from the struct"))
                continue
            val = getattr(struct, fname)
            if val is None:
                if not fc.optional:
                    findings.append(Finding(
                        PASS, path, line, "KC007",
                        f"{cls}.{fname} is None but not declared optional"))
                continue
            want = tuple(axis_env.get(a, -1) for a in fc.axes)
            got = tuple(val.shape)
            if got != want:
                findings.append(Finding(
                    PASS, path, line, "KC007",
                    f"{cls}.{fname}: declared {list(fc.axes)} -> {want} "
                    f"but actual shape is {got}"))
            actual_dt = _dtype_name(val.dtype)
            if actual_dt != fc.dtype:
                findings.append(Finding(
                    PASS, path, line, "KC007",
                    f"{cls}.{fname}: declared dtype {fc.dtype} but actual "
                    f"is {actual_dt}"))

    peer_ids = list(range(1, kp.num_peers + 1))
    state = kstate.init_state(kp, G, 1, peer_ids)
    box = kstate.empty_inbox(kp, G)
    inp = kstate.empty_input(kp, G)
    diff("ShardState", state)
    diff("Inbox", box)
    diff("StepInput", inp)
    if eval_step:
        new_state, out = jax.eval_shape(
            lambda st, bx, ip: kernel.step(kp, st, bx, ip), state, box, inp)
        diff("StepOutput", out)
        diff("ShardState", new_state)

    # health structures: C/TOPK/RW are host-side constants, and k clamps
    # to G on small fleets (core/health.py) — the env mirrors that
    from dragonboat_tpu.core import health as _health

    hk = min(_health.DEFAULT_TOP_K, G)
    axis_env.update({"C": _health.NUM_CLASSES, "TOPK": hk,
                     "RW": _health.ROW_WIDTH})
    digest = _health.empty_digest(G)
    report, new_digest = jax.eval_shape(
        lambda st, bx, dg: _health._fleet_health_impl(
            st, bx, dg, k=_health.DEFAULT_TOP_K),
        state, box.from_, digest)
    diff("HealthReport", report)
    diff("HealthDigest", new_digest)
    import jax.numpy as jnp

    row = jax.eval_shape(
        _health._shard_row_impl, state, box.from_, digest,
        jax.ShapeDtypeStruct((), jnp.int32))
    diff("ShardRow", row)

    # invariant-probe structures: NI is the declared invariant count
    from dragonboat_tpu.core import invariants as _invariants

    axis_env["NI"] = _invariants.NUM_INVARIANTS
    inv_digest = _invariants.empty_digest(G)
    inv_report, new_inv_digest = jax.eval_shape(
        _invariants._check_invariants_impl, state, inv_digest)
    diff("InvariantReport", inv_report)
    diff("InvariantDigest", new_inv_digest)

    # the collection (core/digest.py) packs the three reports into one
    # int32 vector and carries the two digests as the columns of one
    # [G, W] array: as long, and as wide, as the five classes declare
    from math import prod

    from dragonboat_tpu.core import digest as _digest
    from dragonboat_tpu.core import fleet as _fleet

    reports = ("FleetStats", "HealthReport", "InvariantReport")
    digests = ("HealthDigest", "InvariantDigest")
    if not all(cls in ctx.contracts for cls in reports + digests):
        return findings         # a fixture tree without these modules
    axis_env.update({"ROLES": _fleet.NUM_ROLES,
                     "LAGB": len(_fleet.LAG_BUCKETS) + 1,
                     "INBOXB": len(_fleet.INBOX_BUCKETS) + 1})
    vec, carry = jax.eval_shape(
        lambda st, bx, dg: _digest._fleet_digest_impl(
            st, bx, dg, k=_health.DEFAULT_TOP_K),
        state, box.from_, _digest.empty_carry(G))
    declared = {
        "packed reports": (vec, (sum(
            prod(axis_env.get(a, -1) for a in fc.axes)
            for cls in reports for fc in ctx.contracts[cls].values()),)),
        "carried digests": (carry, (G, sum(
            len(ctx.contracts[cls]) for cls in digests))),
    }
    for what, (got, shape) in declared.items():
        if tuple(got.shape) != shape or _dtype_name(got.dtype) != "i32":
            findings.append(Finding(
                PASS, "dragonboat_tpu/core/digest.py", 1, "KC007",
                f"the collection's {what}: the contracts declare i32 "
                f"{shape} but the program gives {got.dtype} "
                f"{tuple(got.shape)}"))
    return findings


# ---------------------------------------------------------------------------
# donation contract (KC008): kstate.DONATION vs kernel.py donate_argnums
# ---------------------------------------------------------------------------


def _donation_decl(tree: ast.Module) -> tuple[dict | None, int]:
    """The DONATION literal from a kstate-shaped module (+ its line)."""
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "DONATION":
            try:
                return ast.literal_eval(node.value), node.lineno
            except (ValueError, SyntaxError):
                return None, node.lineno
    return None, 1


def _donated_entries(tree: ast.Module) -> dict[str, tuple[tuple, list, int]]:
    """kernel.py functions carrying donate_argnums: name ->
    (argnums, positional param names, lineno)."""
    out: dict[str, tuple[tuple, list, int]] = {}
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        for dec in node.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            for k in dec.keywords:
                if k.arg != "donate_argnums":
                    continue
                try:
                    nums = ast.literal_eval(k.value)
                except (ValueError, SyntaxError):
                    nums = None
                if isinstance(nums, int):
                    nums = (nums,)
                params = [a.arg for a in (node.args.posonlyargs
                                          + node.args.args)]
                out[node.name] = (tuple(nums) if nums else (),
                                  params, node.lineno)
    return out


def donation_check(root: str, kstate_tree: ast.Module,
                   kernel_tree: ast.Module,
                   extra_trees: dict[str, ast.Module] | None = None,
                   ) -> list[Finding]:
    """Cross-check the declared donation contract against the actual
    ``donate_argnums`` decorations (both directions).

    Entries default to ``KERNEL_FILE``; an entry carrying a ``module``
    key is checked against that module instead (``extra_trees`` maps
    repo-relative module path -> parsed tree; every DONATION_MODULES
    member beyond kernel.py should be present).  An entry's ``function``
    key names the decorated function when it differs from the entry
    name."""
    findings: list[Finding] = []
    srel = rel(root, os.path.join(root, CONTRACT_FILES[0]))
    decl, decl_line = _donation_decl(kstate_tree)
    mod_trees = {KERNEL_FILE: kernel_tree}
    mod_trees.update(extra_trees or {})
    mod_entries = {m: _donated_entries(t) for m, t in mod_trees.items()}
    if decl is None:
        if any(mod_entries.values()):
            findings.append(Finding(
                PASS, srel, decl_line, "KC008",
                "jit entries donate buffers but kstate.py has no (or a "
                "non-literal) DONATION declaration"))
        return findings
    declared_fns: dict[str, set[str]] = {m: set() for m in mod_trees}
    for name, spec in decl.items():
        module = spec.get("module", KERNEL_FILE)
        fn_name = spec.get("function", name)
        mrel = rel(root, os.path.join(root, module))
        entries = mod_entries.get(module)
        if entries is None:
            findings.append(Finding(
                PASS, srel, decl_line, "KC008",
                f"DONATION entry {name} names module {module} which is "
                "not in DONATION_MODULES — the cross-check cannot see "
                "its decorators"))
            continue
        declared_fns.setdefault(module, set()).add(fn_name)
        if fn_name not in entries:
            findings.append(Finding(
                PASS, srel, decl_line, "KC008",
                f"DONATION declares {name} but {module} has no "
                f"donate_argnums-decorated function {fn_name}"))
            continue
        nums, params, line = entries[fn_name]
        want_nums = tuple(spec.get("argnums", ()))
        if nums != want_nums:
            findings.append(Finding(
                PASS, mrel, line, "KC008",
                f"{name}: donate_argnums {nums} != declared "
                f"DONATION argnums {want_nums}"))
            continue
        bound = tuple(params[i] for i in nums if i < len(params))
        want_params = tuple(spec.get("params", ()))
        if bound != want_params:
            findings.append(Finding(
                PASS, mrel, line, "KC008",
                f"{name}: donated parameters {bound} != declared "
                f"DONATION params {want_params}"))
    for module, entries in mod_entries.items():
        mrel = rel(root, os.path.join(root, module))
        for name, (_, _, line) in entries.items():
            if name not in declared_fns.get(module, set()):
                findings.append(Finding(
                    PASS, mrel, line, "KC008",
                    f"{name} donates buffers but is not declared in "
                    "kstate.DONATION — the host no-touch contract is "
                    "undocumented/unchecked"))
    return findings


# ---------------------------------------------------------------------------
# pass entry point
# ---------------------------------------------------------------------------


def run(root: str, files: list[str] | None = None) -> list[Finding]:
    default_mode = files is None
    if default_mode:
        paths = [os.path.join(root, KERNEL_FILE)]
        contract_paths = [os.path.join(root, cf) for cf in CONTRACT_FILES]
        const_paths = [os.path.join(root, PARAMS_FILE)]
    else:
        paths = list(files)
        contract_paths = list(files)
        const_paths = list(files)

    ctx = _Ctx()
    trees: dict[str, ast.Module] = {}

    def tree_of(p: str) -> ast.Module | None:
        if p not in trees:
            if not os.path.exists(p):
                return None
            with open(p, encoding="utf-8") as f:
                trees[p] = ast.parse(f.read(), filename=p)
        return trees[p]

    for p in contract_paths:
        t = tree_of(p)
        if t is not None:
            _collect_contracts(ctx, t, rel(root, p))
    for p in const_paths + paths:
        t = tree_of(p)
        if t is not None:
            _collect_consts(ctx, t)

    mods = [ts._Module(p, trees[p]) for p in paths if tree_of(p) is not None]
    _analyze(ctx, mods, root)
    findings = ctx.findings

    if default_mode:
        findings = findings + runtime_check(root=root)
        ktree = tree_of(os.path.join(root, CONTRACT_FILES[0]))
        ntree = tree_of(os.path.join(root, KERNEL_FILE))
        if ktree is not None and ntree is not None:
            extra: dict[str, ast.Module] = {}
            for m in DONATION_MODULES:
                if m == KERNEL_FILE:
                    continue
                mt = tree_of(os.path.join(root, m))
                if mt is not None:
                    extra[m] = mt
            findings = findings + donation_check(root, ktree, ntree, extra)
    return sorted(set(findings), key=lambda f: (f.path, f.line, f.rule))
