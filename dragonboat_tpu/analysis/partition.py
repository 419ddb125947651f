"""SPMD partition-safety analyzer: the G axis as a checked contract.

The mesh layout (``parallel/ici.py``) shards every kernel struct's
leading G axis flat over the ``('g', 'r')`` device mesh; the whole
scaling story rests on groups never talking to each other except through
the two declared seams (the in-mesh router exchange and the fleet-stats
reduction).  Nothing in JAX enforces that: a stray ``.sum()`` over the
batch axis, a shard_map spec that silently replicates a G-sharded
struct, or an ``int()`` on a device value in the engine step loop all
compile fine and only show up as wrong answers or a 10x serving
regression.  This pass promotes the layout to a machine-checked
discipline, driven by the ``part=``/``collective=`` tags on the
``CONTRACTS`` literals (``core/kstate.py`` grammar block):

- PS001  cross-G data flow outside a declared collective: a reduction
         whose reduced axes include G, not inside a ``jax.lax`` named
         collective over ``'g'`` and not in a function producing a
         ``collective=declared`` struct (fleet stats)
- PS002  shard_map ``in_specs``/``out_specs`` contradicting a value's
         declared partition (``part=G`` fed a replicated spec or vice
         versa, arity mismatches), plus the [dynamic] variant from the
         2-device cross-check below
- PS003  a replicated operand (named-collective result) combined with
         G-sharded data without an explicit broadcast annotation
         (``jnp.broadcast_to`` / ``jnp.expand_dims`` on the replicated
         side is the annotation)
- PS004  donation whose donor sharding differs from every result
         sharding (``kstate.DONATION`` ``donor_classes`` vs
         ``result_classes``; composes with the KC008 argnum check)
- PS005  ``pure_callback``/``io_callback``/``jax.debug.callback``
         reachable inside a shard_map body (host round-trip per device
         per step)
- PS006  implicit device→host syncs in engine hot paths: ``int()``/
         ``bool()``/``float()``/``.item()``/``.tolist()``/
         ``np.asarray`` on device values, ``block_until_ready``,
         ``jax.device_get`` inside the step_all/staging methods of
         ``kernel_engine.py``/``mesh_engine.py`` (the designated sync
         points — ``_process_outputs``, ``_device_pending``,
         ``_collect_digest`` — are exempt by design)

Static scope: the abstract interpreter (subclassing the contracts
pass's ``_Interp``) runs over ``core/fleet.py`` and ``parallel/ici.py``
— the two files that live at mesh level, where the G axis is real.
``core/kernel.py`` is deliberately NOT interpreted here: under the
engines it runs vmapped/shard_mapped with G stripped, so its per-shard
full reductions are legitimate; its structs still contribute their
``part=`` declarations.  The PS005 walk additionally descends through
kernel.py/router.py since shard_map bodies call into them.

Dynamic cross-check: the default-mode run builds a real 2-device
``('g','r')`` mesh (CPU works via
``XLA_FLAGS=--xla_force_host_platform_device_count=2``, which
scripts/lint.py sets), runs one ``ici_serve_step`` and diffs every
declared ``part=`` against the actual ``jax.sharding`` of the outputs.
Results are cached in ``.partition_cache.json`` keyed on
``jax.__version__`` + the source files, mirroring the hlo-budget pass.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
from dataclasses import replace

from dragonboat_tpu.analysis import contracts as ct
from dragonboat_tpu.analysis import tracer_safety as ts
from dragonboat_tpu.analysis.common import Finding, rel

PASS = "partition"

#: mesh axis name carrying the group dimension (parallel/ici.py layout)
G_MESH_AXIS = "g"

DEFAULT_CONTRACT_FILES = (
    "dragonboat_tpu/core/kstate.py",
    "dragonboat_tpu/core/kernel.py",
    "dragonboat_tpu/core/fleet.py",
    "dragonboat_tpu/core/health.py",
    "dragonboat_tpu/core/invariants.py",
)
#: files interpreted at mesh level (G axis real) — see module docstring
DEFAULT_ANALYSIS_FILES = (
    "dragonboat_tpu/core/fleet.py",
    "dragonboat_tpu/core/health.py",
    "dragonboat_tpu/core/invariants.py",
    "dragonboat_tpu/parallel/ici.py",
    # the elastic controller consumes the fleet-health digest at host
    # level and must STAY jax-free: any reduction/collective appearing
    # here is a cross-G flow outside the two declared seams
    "dragonboat_tpu/control.py",
)
DEFAULT_CONST_FILES = ("dragonboat_tpu/core/params.py",)
#: PS005 walks shard_map bodies through these
DEFAULT_WALK_FILES = (
    "dragonboat_tpu/parallel/ici.py",
    "dragonboat_tpu/core/kernel.py",
    "dragonboat_tpu/core/router.py",
    "dragonboat_tpu/core/kstate.py",
    "dragonboat_tpu/core/fleet.py",
    "dragonboat_tpu/core/health.py",
    "dragonboat_tpu/core/invariants.py",
)
DEFAULT_ENGINE_FILES = (
    "dragonboat_tpu/engine/kernel_engine.py",
    "dragonboat_tpu/engine/mesh_engine.py",
    "dragonboat_tpu/engine/dispatch.py",
    "dragonboat_tpu/capacity.py",
)

#: every file any sub-check reads — scripts/lint.py --changed-only scope
SCOPE = tuple(dict.fromkeys(
    DEFAULT_CONTRACT_FILES + DEFAULT_ANALYSIS_FILES + DEFAULT_CONST_FILES
    + DEFAULT_WALK_FILES + DEFAULT_ENGINE_FILES))

# Conventional parameter names at MESH level: no axes are stripped (the
# G axis is present), unlike the contracts pass's vmap-level bindings.
PART_BINDINGS = {
    "s": "ShardState",
    "st": "ShardState",
    "state": "ShardState",
    "box": "Inbox",
    "bx": "Inbox",
    "inbox": "Inbox",
    "inp": "StepInput",
    "out": "StepOutput",
    "digest": "HealthDigest",
    "inv_digest": "InvariantDigest",
}

#: jax.lax named collectives — using one IS declaring cross-device flow
_NAMED_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "psum_scatter", "all_gather",
    "all_to_all", "ppermute", "pshuffle", "pbroadcast", "axis_index",
})
#: collectives whose result is identical on every participating device
_REPLICATING = frozenset({"psum", "pmean", "pmax", "pmin"})

_CALLBACKS = frozenset({"pure_callback", "io_callback", "host_callback"})

# --- PS006 scope (engine hot paths) ----------------------------------------
# Methods on the engine step/staging path where a surprise sync stalls
# every lane.  The designated sync points are exempt by design:
# _process_outputs (the one fetch per step), _device_pending (the mesh
# drain probe), _collect_digest (decimated).
HOT_PATH_FUNCS = frozenset({
    "step_all", "mark_dirty", "_kernel_call", "_stage_lane",
    "_stage_props", "_prop_target", "dispatch",
})
#: self.<attr> values that live on device in both engines
_DEVICE_SELF_ATTRS = frozenset({
    "state", "_resident", "box", "_box", "_pending_dev", "_cut_dev"})
#: calls whose results are device values
_DEVICE_PRODUCERS = frozenset({
    "kernel_step", "kernel_step_donated", "step", "step_donated",
    "ici_serve_step", "ici_cluster_step", "fleet_stats",
    "fleet_health", "shard_row",
    "jit_serve_step", "jit_serve_step_donated",
    "cluster_step", "cluster_step_donated", "dispatch",
    "output_row_flags", "to_device", "shard", "device_put", "_kernel_call",
})

# --- dynamic-check cache ---------------------------------------------------
CACHE_FILE = "dragonboat_tpu/analysis/.partition_cache.json"
CACHE_SOURCES = (
    "dragonboat_tpu/core/kstate.py",
    "dragonboat_tpu/core/kernel.py",
    "dragonboat_tpu/core/router.py",
    "dragonboat_tpu/core/params.py",
    "dragonboat_tpu/core/fleet.py",
    "dragonboat_tpu/core/health.py",
    "dragonboat_tpu/core/invariants.py",
    "dragonboat_tpu/parallel/ici.py",
    "dragonboat_tpu/analysis/partition.py",
)


def class_partition(ctx: ct._Ctx, cls: str | None) -> str | None:
    """The uniform declared partition of a struct, or None if mixed or
    undeclared ('G' | 'replicated')."""
    fields = ctx.contracts.get(cls or "")
    if not fields:
        return None
    parts = {fc.part for fc in fields.values() if fc.part is not None}
    return next(iter(parts)) if len(parts) == 1 else None


def _declares_collective(ctx: ct._Ctx, fn: ast.AST) -> bool:
    """Does ``fn`` construct a struct whose fields are declared
    ``collective=declared``?  Such a producer's cross-G reductions are
    the licensed seam (fleet stats)."""
    for n in ast.walk(fn):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
            fields = ctx.contracts.get(n.func.id)
            if fields and any(fc.collective == "declared"
                              for fc in fields.values()):
                return True
    return False


def _relabel_collect_findings(ctx: ct._Ctx) -> None:
    """Contract-table parse errors surface from the shared collector as
    contracts/KC007; re-own them as partition/PS000 here."""
    ctx.findings = [
        f if f.pass_name == PASS
        else Finding(PASS, f.path, f.line, "PS000", f.message)
        for f in ctx.findings
    ]


# ---------------------------------------------------------------------------
# the partition-aware abstract interpreter (PS001 / PS003)
# ---------------------------------------------------------------------------


class _PartInterp(ct._Interp):
    """Contracts interpreter with partition tracking layered on.

    Only PS* rules are emitted — the KC* checks the parent runs on the
    way through are the contracts pass's job and are dropped here."""

    def __init__(self, ctx: ct._Ctx, relpath: str) -> None:
        super().__init__(ctx, relpath)
        self._collective_depth = 0   # >0: inside a cross-G collective's args
        self._declared = False       # fn produces a collective=declared struct
        self._call_stack: list[ast.Call] = []

    # -- reporting: PS-only --------------------------------------------
    def flag(self, node: ast.AST, rule: str, msg: str) -> None:
        if not rule.startswith("PS"):
            return
        key = (getattr(node, "lineno", 0), rule)
        if key in self._flagged:
            return
        self._flagged.add(key)
        self.ctx.findings.append(
            Finding(PASS, self.relpath, getattr(node, "lineno", 0),
                    rule, msg))

    # -- parameter binding: mesh level, nothing stripped ----------------
    def bind_params(self, fn: ast.FunctionDef | ast.Lambda) -> None:
        args = fn.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            ann = ct._ann_name(getattr(a, "annotation", None))
            name = a.arg
            if name == "kp" or ann == "KernelParams":
                self.env[name] = ct._KP
            elif ann in self.ctx.contracts:
                self.env[name] = ct._struct_aval(ann, ())
            elif name in PART_BINDINGS \
                    and PART_BINDINGS[name] in self.ctx.contracts:
                self.env[name] = ct._struct_aval(PART_BINDINGS[name], ())
            else:
                self.env[name] = ct.UNKNOWN
        for extra in (args.vararg, args.kwarg):
            if extra is not None:
                self.env[extra.arg] = ct.UNKNOWN

    # nested defs must spawn THIS interpreter class (the parent hardcodes
    # _Interp, which would re-enable KC findings and lose partition state)
    def exec_stmt(self, st: ast.stmt) -> None:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            sub = _PartInterp(self.ctx, self.relpath)
            sub.env.update(self.env)
            sub.bind_params(st)
            sub._flagged = self._flagged
            sub._call_stack = self._call_stack
            sub._declared = self._declared \
                or _declares_collective(self.ctx, st)
            sub.exec_body(st.body)
        else:
            super().exec_stmt(st)

    # -- collectives -----------------------------------------------------
    def _axis_names(self, node: ast.Call) -> set[str]:
        axis_node = None
        for k in node.keywords:
            if k.arg == "axis_name":
                axis_node = k.value
        if axis_node is None and len(node.args) > 1:
            axis_node = node.args[1]
        names: set[str] = set()
        if isinstance(axis_node, ast.Constant) \
                and isinstance(axis_node.value, str):
            names.add(axis_node.value)
        elif isinstance(axis_node, ast.Tuple):
            for e in axis_node.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add(e.value)
        return names

    def eval_Call(self, node: ast.Call) -> ct.AVal:
        func = node.func
        cname = None
        if isinstance(func, ast.Attribute):
            chain = ct._attr_chain(func)
            if chain and chain[-1] in _NAMED_COLLECTIVES \
                    and chain[0] in ("jax", "lax"):
                cname = chain[-1]
        elif isinstance(func, ast.Name) and func.id in _NAMED_COLLECTIVES:
            cname = func.id
        # the parent takes args[2] (the body lambda) as fori_loop's carry;
        # the real init operand is args[3]
        if isinstance(func, ast.Attribute) and func.attr == "fori_loop" \
                and len(node.args) > 3:
            for a in node.args[:3]:
                self.eval(a)
            return self.eval(node.args[3])
        axes = self._axis_names(node) if cname else set()
        # a named collective over 'g' (or with unresolvable axes —
        # optimistic) licenses cross-G reductions in its operands
        suppress = cname is not None and (not axes or G_MESH_AXIS in axes)
        self._call_stack.append(node)
        if suppress:
            self._collective_depth += 1
        try:
            res = super().eval_Call(node)
            if cname in _REPLICATING and (not axes or G_MESH_AXIS in axes) \
                    and node.args:
                v0 = self.eval(node.args[0])
                base = v0 if v0.axes is not None else res
                res = replace(base, part="rep", bcast=False, cls=None,
                              tup=None, const=None, size_axis=None,
                              maskconst=None)
            return res
        finally:
            if suppress:
                self._collective_depth -= 1
            self._call_stack.pop()

    # -- PS001: reductions that erase the G axis -------------------------
    def _reduce(self, v: ct.AVal, axis_node: ast.AST | None,
                dt: str | None) -> ct.AVal:
        out = super()._reduce(v, axis_node, dt)
        reduced_g = (v.axes is not None and "G" in v.axes
                     and out.axes is not None and "G" not in out.axes)
        if reduced_g and not self._declared \
                and self._collective_depth == 0 and self._call_stack:
            self.flag(
                self._call_stack[-1], "PS001",
                "reduction erases the G (group/batch) axis outside a "
                "declared collective — at mesh level this mixes data "
                "across independent raft groups (wrap it in a jax.lax "
                "collective over 'g', or produce a collective=declared "
                "struct like FleetStats)")
        if v.part == "G" and out.axes and "G" in out.axes:
            out = replace(out, part="G")
        return out

    # -- PS003: unannotated replicated×G-sharded combination -------------
    def _broadcast(self, node: ast.AST, a: ct.AVal, b: ct.AVal,
                   what: str) -> tuple[str, ...] | None:
        for r_, g_ in ((a, b), (b, a)):
            if (r_.part == "rep" and not r_.bcast
                    and r_.axes not in (None, ())
                    and (g_.part == "G"
                         or (g_.axes is not None and "G" in g_.axes))):
                self.flag(
                    node, "PS003",
                    f"replicated collective result combined with "
                    f"G-sharded data in {what} without an explicit "
                    "broadcast annotation (jnp.broadcast_to / "
                    "jnp.expand_dims on the replicated operand makes "
                    "the fan-out intentional)")
        return super()._broadcast(node, a, b, what)

    # -- partition propagation -------------------------------------------
    def binop(self, node: ast.AST, a: ct.AVal, b: ct.AVal,
              op: ast.operator) -> ct.AVal:
        r = super().binop(node, a, b, op)
        if a.part == "G" or b.part == "G":
            r = replace(r, part="G")
        elif a.part == "rep" and b.part == "rep":
            r = replace(r, part="rep")
        return r

    def eval_Attribute(self, node: ast.Attribute) -> ct.AVal:
        v = super().eval_Attribute(node)
        recv = self.eval(node.value)
        if recv.cls is not None:
            fc = self.ctx.field(recv.cls, node.attr)
            if fc is not None and fc.part is not None:
                v = replace(v, part="G" if fc.part == "G" else "rep")
        return v

    def eval_Subscript(self, node: ast.Subscript) -> ct.AVal:
        r = super().eval_Subscript(node)
        base = self.eval(node.value)
        if base.cls is None and base.tup is None:
            if base.part == "rep":
                r = replace(r, part="rep", bcast=base.bcast)
            elif base.part == "G" and r.axes is not None and "G" in r.axes:
                r = replace(r, part="G")
        return r

    def _call_jnp(self, node: ast.Call, fname: str) -> ct.AVal | None:
        res = super()._call_jnp(node, fname)
        # broadcast_to/expand_dims IS the PS003 annotation
        if fname in ("broadcast_to", "expand_dims") and res is not None \
                and node.args:
            v = self.eval(node.args[0])
            if v.part is not None:
                res = replace(res, part=v.part, bcast=(v.part == "rep"))
        return res

    def _call_ctor(self, node: ast.Call, cls: str) -> ct.AVal:
        # mesh level: constructed structs keep their G axis
        return replace(super()._call_ctor(node, cls), strip=())


def _interpret(ctx: ct._Ctx, mods: list[ts._Module], root: str
               ) -> dict[str, list[ct.AVal]]:
    """Interpret EVERY function of the analysis modules (host helpers
    included — a stray cross-G reduce in a utility is just as wrong) and
    record per-function return avals for the PS002 out_specs check."""
    global_funcs: dict[str, tuple[ts._Module, ast.FunctionDef]] = {}
    all_calls: dict[str, set[str]] = {}
    for m in mods:
        for name, fn in m.funcs.items():
            global_funcs.setdefault(name, (m, fn))
        _, calls = ts._seed_and_calls(m)
        for name, callees in calls.items():
            all_calls.setdefault(name, set()).update(
                m.imports.get(c, c) for c in callees)
    ctx.funcs = global_funcs
    part_returns: dict[str, list[ct.AVal]] = {}
    for name in ct._topo_order(set(global_funcs), all_calls):
        mod, fn = global_funcs[name]
        interp = _PartInterp(ctx, rel(root, mod.path))
        interp._declared = _declares_collective(ctx, fn)
        interp.bind_params(fn)
        interp.exec_body(fn.body)
        ctx.summaries[name] = ct._summary_join(interp.returns)
        part_returns[name] = list(interp.returns)
    return part_returns


# ---------------------------------------------------------------------------
# PS002: shard_map specs vs declared partitions (static side)
# ---------------------------------------------------------------------------

_PS_NAMES = ("PS", "P", "PartitionSpec")


def _resolve_body(arg: ast.AST, funcs: dict) -> tuple[str | None, int]:
    """shard_map body arg -> (function name, #params pre-bound by
    functools.partial)."""
    if isinstance(arg, ast.Name):
        return (arg.id if arg.id in funcs else None), 0
    if isinstance(arg, ast.Call):
        chain = ct._attr_chain(arg.func)
        if chain and chain[-1] == "partial" and arg.args:
            inner = arg.args[0]
            if isinstance(inner, ast.Name) and inner.id in funcs:
                return inner.id, len(arg.args) - 1
    return None, 0


def _spec_axes(entry: ast.AST) -> set[str] | None:
    """One ``PS(...)`` call -> the set of mesh axis names it shards
    over, or None when unresolvable."""
    if not (isinstance(entry, ast.Call)
            and (chain := ct._attr_chain(entry.func))
            and chain[-1] in _PS_NAMES):
        return None
    names: set[str] = set()
    for a in entry.args:
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            names.add(a.value)
        elif isinstance(a, ast.Constant) and a.value is None:
            pass
        elif isinstance(a, ast.Tuple):
            for e in a.elts:
                if isinstance(e, ast.Constant) \
                        and isinstance(e.value, str):
                    names.add(e.value)
        else:
            return None
    return names


def _spec_list(node: ast.AST) -> tuple[list[set[str]], bool] | None:
    """in_specs/out_specs value -> (per-element axis sets, was_tuple).
    Handles literal tuples, a single spec (jax broadcasts it over the
    pytree), and the ``(PS(...),) * 3`` idiom."""
    if isinstance(node, ast.Tuple):
        out = []
        for e in node.elts:
            ax = _spec_axes(e)
            if ax is None:
                return None
            out.append(ax)
        return out, True
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        tup, count = node.left, node.right
        if not isinstance(tup, ast.Tuple):
            tup, count = count, tup
        if isinstance(tup, ast.Tuple) and isinstance(count, ast.Constant) \
                and isinstance(count.value, int):
            inner = _spec_list(tup)
            if inner is not None:
                return inner[0] * count.value, True
        return None
    ax = _spec_axes(node)
    if ax is not None:
        return [ax], False
    return None


def _param_partition(ctx: ct._Ctx, fn: ast.FunctionDef,
                     pname: str) -> str | None:
    for a in (fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs):
        if a.arg != pname:
            continue
        ann = ct._ann_name(getattr(a, "annotation", None))
        if ann in ctx.contracts:
            return class_partition(ctx, ann)
    cls = PART_BINDINGS.get(pname)
    if cls in ctx.contracts:
        return class_partition(ctx, cls)
    return None


def _elem_partition(ctx: ct._Ctx, el: ct.AVal) -> str | None:
    if el.cls is not None:
        return class_partition(ctx, el.cls)
    if el.part == "rep":
        return "replicated"
    if el.part == "G":
        return "G"
    return None


def _check_spec(findings: list[Finding], relpath: str, node: ast.AST,
                what: str, decl: str | None, axes: set[str]) -> None:
    if decl is None:
        return
    g_sharded = G_MESH_AXIS in axes
    if decl == "G" and not g_sharded:
        findings.append(Finding(
            PASS, relpath, node.lineno, "PS002",
            f"shard_map spec for {what} does not shard over mesh axis "
            f"'{G_MESH_AXIS}' but the value is declared part=G — every "
            "device would hold (and step) ALL groups"))
    elif decl == "replicated" and g_sharded:
        findings.append(Finding(
            PASS, relpath, node.lineno, "PS002",
            f"shard_map spec for {what} shards over mesh axis "
            f"'{G_MESH_AXIS}' but the value is declared "
            "part=replicated — each device would see a different slice "
            "of supposedly-identical data"))


def _shard_map_spec_check(ctx: ct._Ctx, mods: list[ts._Module],
                          part_returns: dict[str, list[ct.AVal]],
                          root: str) -> list[Finding]:
    findings: list[Finding] = []
    for m in mods:
        relpath = rel(root, m.path)
        for call in ast.walk(m.tree):
            if not isinstance(call, ast.Call):
                continue
            chain = ct._attr_chain(call.func)
            if not chain or chain[-1] != "shard_map" or not call.args:
                continue
            kw = {k.arg: k.value for k in call.keywords if k.arg}
            fname, skip = _resolve_body(call.args[0], m.funcs)
            if fname is None:
                continue
            fn = m.funcs[fname]
            params = [a.arg for a in
                      (fn.args.posonlyargs + fn.args.args)][skip:]
            ins = _spec_list(kw["in_specs"]) if "in_specs" in kw else None
            if ins is not None:
                specs, was_tuple = ins
                if was_tuple and len(specs) != len(params):
                    findings.append(Finding(
                        PASS, relpath, call.lineno, "PS002",
                        f"shard_map in_specs has {len(specs)} entries but "
                        f"body {fname}() takes {len(params)} (after "
                        f"{skip} partial-bound)"))
                else:
                    if not was_tuple:
                        specs = specs * len(params)
                    for pname, axes in zip(params, specs):
                        _check_spec(findings, relpath, call,
                                    f"{fname}() param {pname!r}",
                                    _param_partition(ctx, fn, pname), axes)
            outs = _spec_list(kw["out_specs"]) if "out_specs" in kw else None
            if outs is not None:
                specs, was_tuple = outs
                for ret in part_returns.get(fname, ()):
                    elems = ret.tup if ret.tup is not None else (ret,)
                    if was_tuple and ret.tup is not None \
                            and len(specs) != len(elems):
                        findings.append(Finding(
                            PASS, relpath, call.lineno, "PS002",
                            f"shard_map out_specs has {len(specs)} entries "
                            f"but body {fname}() returns {len(elems)}"))
                        continue
                    if was_tuple and ret.tup is None and len(specs) != 1:
                        continue  # structure unknown — optimistic
                    use = specs if was_tuple else list(specs) * len(elems)
                    for i, (el, axes) in enumerate(zip(elems, use)):
                        _check_spec(findings, relpath, call,
                                    f"{fname}() result[{i}]",
                                    _elem_partition(ctx, el), axes)
    return findings


# ---------------------------------------------------------------------------
# PS004: donation must preserve sharding (kstate.DONATION)
# ---------------------------------------------------------------------------


def _donation_partition_check(ctx: ct._Ctx, tree: ast.Module,
                              relpath: str) -> list[Finding]:
    decl, line = ct._donation_decl(tree)
    if not decl:
        return []
    findings: list[Finding] = []
    for name, spec in decl.items():
        donors = spec.get("donor_classes")
        results = spec.get("result_classes")
        if donors is None or results is None:
            findings.append(Finding(
                PASS, relpath, line, "PS004",
                f"DONATION entry {name!r} lacks donor_classes/"
                "result_classes — the sharding identity of the donated "
                "buffers is undeclared (XLA aliases donor memory into "
                "results; that is only sound under identical sharding)"))
            continue
        result_parts = {p for rcls in results
                        if (p := class_partition(ctx, rcls)) is not None}
        for dcls in donors:
            p = class_partition(ctx, dcls)
            if p is None:
                findings.append(Finding(
                    PASS, relpath, line, "PS004",
                    f"DONATION {name!r}: donor class {dcls} has no "
                    "uniform declared partition (tag every field part=G "
                    "or part=replicated)"))
            elif result_parts and p not in result_parts:
                findings.append(Finding(
                    PASS, relpath, line, "PS004",
                    f"DONATION {name!r}: donor {dcls} is part={p} but "
                    f"result classes are {sorted(result_parts)} — XLA "
                    "would reuse a buffer under a different sharding"))
    return findings


# ---------------------------------------------------------------------------
# PS005: host callbacks reachable inside shard_map bodies
# ---------------------------------------------------------------------------


def _callback_check(mods: list[ts._Module], root: str) -> list[Finding]:
    funcs: dict[str, tuple[ts._Module, ast.FunctionDef]] = {}
    all_calls: dict[str, set[str]] = {}
    bodies: set[str] = set()
    for m in mods:
        for name, fn in m.funcs.items():
            funcs.setdefault(name, (m, fn))
        _, calls = ts._seed_and_calls(m)
        for name, callees in calls.items():
            all_calls.setdefault(name, set()).update(
                m.imports.get(c, c) for c in callees)
        for call in ast.walk(m.tree):
            if isinstance(call, ast.Call):
                chain = ct._attr_chain(call.func)
                if chain and chain[-1] == "shard_map" and call.args:
                    fname, _ = _resolve_body(call.args[0], m.funcs)
                    if fname is not None:
                        bodies.add(fname)
    reach: set[str] = set()
    frontier = [b for b in bodies if b in funcs]
    while frontier:
        n = frontier.pop()
        if n in reach:
            continue
        reach.add(n)
        frontier.extend(c for c in all_calls.get(n, ())
                        if c in funcs and c not in reach)
    findings: list[Finding] = []
    for name in sorted(reach):
        m, fn = funcs[name]
        for call in ast.walk(fn):
            if not isinstance(call, ast.Call):
                continue
            chain = ct._attr_chain(call.func)
            if not chain:
                continue
            if chain[-1] in _CALLBACKS or (
                    len(chain) >= 2 and chain[-1] == "callback"
                    and chain[-2] == "debug"):
                findings.append(Finding(
                    PASS, rel(root, m.path), call.lineno, "PS005",
                    f"host callback {'.'.join(chain)} reachable inside a "
                    f"shard_map body (via {name}) — one host round-trip "
                    "per device per step serializes the mesh"))
    return findings


# ---------------------------------------------------------------------------
# PS006: implicit device→host syncs in engine hot paths
# ---------------------------------------------------------------------------


def _host_sync_check(trees: list[tuple[str, ast.Module]],
                     root: str) -> list[Finding]:
    findings: list[Finding] = []
    for path, tree in trees:
        relpath = rel(root, path)
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef) \
                    or fn.name not in HOT_PATH_FUNCS:
                continue
            findings.extend(_scan_hot_fn(fn, relpath))
    return findings


def _scan_hot_fn(fn: ast.FunctionDef, relpath: str) -> list[Finding]:
    findings: list[Finding] = []
    tainted: set[str] = set()
    seen: set[tuple[int, str]] = set()

    def is_device(node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in tainted
        if isinstance(node, ast.Attribute):
            chain = ct._attr_chain(node)
            if len(chain) >= 2 and chain[0] == "self" \
                    and chain[1] in _DEVICE_SELF_ATTRS:
                return True
            return is_device(node.value)
        if isinstance(node, ast.Subscript):
            return is_device(node.value)
        if isinstance(node, ast.Call):
            c = ct._attr_chain(node.func)
            return bool(c) and c[-1] in _DEVICE_PRODUCERS
        return False

    def emit(node: ast.AST, msg: str) -> None:
        key = (getattr(node, "lineno", 0), msg[:40])
        if key not in seen:
            seen.add(key)
            findings.append(Finding(
                PASS, relpath, getattr(node, "lineno", 0), "PS006",
                msg + f" in engine hot path {fn.name}() — this blocks "
                "on the device and stalls every lane (move it to a "
                "designated sync point like _process_outputs)"))

    def check_call(call: ast.Call) -> None:
        func = call.func
        if isinstance(func, ast.Name) \
                and func.id in ("int", "bool", "float") \
                and call.args and is_device(call.args[0]):
            emit(call, f"{func.id}() on a device value")
            return
        if not isinstance(func, ast.Attribute):
            return
        chain = ct._attr_chain(func)
        attr = func.attr
        if attr in ("item", "tolist") and is_device(func.value):
            emit(call, f".{attr}() on a device value")
        elif attr in ("asarray", "array") and chain \
                and chain[0] in ("np", "numpy") \
                and call.args and is_device(call.args[0]):
            emit(call, f"np.{attr}() on a device value")
        elif attr == "block_until_ready":
            emit(call, ".block_until_ready()")
        elif attr == "device_get" and chain and chain[0] == "jax":
            emit(call, "jax.device_get()")

    def check_exprs(st: ast.AST) -> None:
        for node in ast.walk(st):
            if isinstance(node, ast.Call):
                check_call(node)

    def taint(tgt: ast.AST) -> None:
        if isinstance(tgt, ast.Name):
            tainted.add(tgt.id)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for el in tgt.elts:
                taint(el)
        elif isinstance(tgt, ast.Starred):
            taint(tgt.value)

    def visit(body: list[ast.stmt]) -> None:
        for st in body:
            if isinstance(st, (ast.If, ast.While)):
                check_exprs(st.test)
                if isinstance(st.test,
                              (ast.Name, ast.Attribute, ast.Subscript)) \
                        and is_device(st.test):
                    emit(st.test, "implicit bool() of a device value "
                                  "in a branch condition")
                visit(st.body)
                visit(st.orelse)
            elif isinstance(st, ast.For):
                check_exprs(st.iter)
                visit(st.body)
                visit(st.orelse)
            elif isinstance(st, ast.With):
                for it in st.items:
                    check_exprs(it.context_expr)
                visit(st.body)
            elif isinstance(st, ast.Try):
                visit(st.body)
                for h in st.handlers:
                    visit(h.body)
                visit(st.orelse)
                visit(st.finalbody)
            elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            else:
                check_exprs(st)
                if isinstance(st, ast.Assign) and is_device(st.value):
                    for t in st.targets:
                        taint(t)
                elif isinstance(st, ast.AnnAssign) and st.value is not None \
                        and is_device(st.value):
                    taint(st.target)

    visit(fn.body)
    return findings


# ---------------------------------------------------------------------------
# dynamic cross-check: declared part= vs actual jax.sharding (2 devices)
# ---------------------------------------------------------------------------


def _source_key(root: str) -> str:
    import jax

    h = hashlib.sha256()
    h.update(("jax:" + getattr(jax, "__version__", "unknown")).encode())
    for f in CACHE_SOURCES:
        p = os.path.join(root, f)
        h.update(f.encode())
        if os.path.exists(p):
            with open(p, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def _cache_load(path: str, key: str) -> list[Finding] | None:
    try:
        with open(path, encoding="utf-8") as f:
            cache = json.load(f)
    except (OSError, ValueError):
        return None
    if cache.get("source_hash") != key:
        return None
    try:
        return [Finding(*entry) for entry in cache.get("findings", [])]
    except TypeError:
        return None


def _cache_save(path: str, key: str, findings: list[Finding]) -> None:
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump({
                "source_hash": key,
                "findings": [[g.pass_name, g.path, g.line, g.rule,
                              g.message] for g in findings],
            }, f, indent=1)
    except OSError:
        pass  # cache is best-effort


def sharding_check(root: str, parts_override: dict | None = None,
                   use_cache: bool = True) -> list[Finding]:
    """Run one real ``ici_serve_step`` on a 2-device ``('g','r')`` mesh
    and diff every declared ``part=`` tag against the actual output
    shardings.  ``parts_override`` ({(cls, field): part}) lets tests
    tamper with declarations; overridden runs bypass the cache.

    Returns [] when fewer than 2 devices are visible (scripts/lint.py
    forces 2 via XLA_FLAGS before jax initializes)."""
    import jax

    if jax.device_count() < 2:
        return []
    cache_path = os.path.join(root, CACHE_FILE)
    cacheable = parts_override is None and use_cache
    key = _source_key(root)
    if cacheable:
        cached = _cache_load(cache_path, key)
        if cached is not None:
            return cached
    findings = _sharding_check_impl(root, parts_override)
    if cacheable:
        _cache_save(cache_path, key, findings)
    return findings


def _sharding_check_impl(root: str,
                         parts_override: dict | None) -> list[Finding]:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from dragonboat_tpu.core.params import KernelParams
    from dragonboat_tpu.parallel import ici

    ctx = ct._Ctx()
    for f in DEFAULT_CONTRACT_FILES:
        p = os.path.join(root, f)
        if os.path.exists(p):
            with open(p, encoding="utf-8") as fh:
                ct._collect_contracts(ctx, ast.parse(fh.read(), filename=p),
                                      rel(root, p))
    _relabel_collect_findings(ctx)
    if parts_override:
        for (cls, fname), part in parts_override.items():
            fc = ctx.contracts.get(cls, {}).get(fname)
            if fc is not None:
                ctx.contracts[cls][fname] = replace(fc, part=part)

    # small but legal: router.route needs inbox_cap >= 5 * (R - 1)
    kp = KernelParams(num_peers=2, log_cap=8, inbox_cap=8, msg_entries=2,
                      proposal_cap=2, readindex_cap=4)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("g", "r"))
    cluster, state, box = ici.make_ici_cluster(kp, mesh, num_groups=2)
    inp = cluster.shard(ici.self_driving_input(kp, state))
    cut = cluster.shard(
        np.zeros((cluster.total_rows, kp.num_peers), np.bool_))
    state2, box2, out = ici.ici_serve_step(
        cluster, state, box, inp, cut)

    findings = list(ctx.findings)

    def anchor(cls: str, fname: str) -> tuple[str, int]:
        return ctx.contract_lines.get(
            (cls, fname), (DEFAULT_CONTRACT_FILES[0], 1))

    for cls, struct in (("ShardState", state2), ("Inbox", box2),
                        ("StepOutput", out)):
        for fname, fc in ctx.contracts.get(cls, {}).items():
            if fc.part is None:
                continue
            val = getattr(struct, fname, None)
            if val is None:
                continue  # optional field absent under this geometry
            sh = getattr(val, "sharding", None)
            if sh is None:
                continue
            path, line = anchor(cls, fname)
            if fc.part == "G":
                split = (val.ndim > 0 and val.shape[0] > 0
                         and tuple(sh.shard_shape(val.shape))[0]
                         < val.shape[0])
                if sh.is_fully_replicated or not split:
                    findings.append(Finding(
                        PASS, path, line, "PS002",
                        f"[dynamic] {cls}.{fname} is declared part=G but "
                        "the 2-device mesh run left its leading axis "
                        "unsplit (actual sharding is "
                        f"{'replicated' if sh.is_fully_replicated else sh})"
                    ))
            elif not sh.is_fully_replicated:
                findings.append(Finding(
                    PASS, path, line, "PS002",
                    f"[dynamic] {cls}.{fname} is declared "
                    f"part=replicated but the mesh run sharded it: {sh}"))
    return findings


# ---------------------------------------------------------------------------
# pass entry point
# ---------------------------------------------------------------------------


def run(root: str, files: list[str] | None = None,
        dynamic: bool = True) -> list[Finding]:
    default_mode = files is None
    if default_mode:
        contract_paths = [os.path.join(root, f)
                          for f in DEFAULT_CONTRACT_FILES]
        const_paths = [os.path.join(root, f) for f in DEFAULT_CONST_FILES]
        analysis_paths = [os.path.join(root, f)
                          for f in DEFAULT_ANALYSIS_FILES]
        walk_paths = [os.path.join(root, f) for f in DEFAULT_WALK_FILES]
        engine_paths = [os.path.join(root, f)
                        for f in DEFAULT_ENGINE_FILES]
        donation_paths = [os.path.join(root, DEFAULT_CONTRACT_FILES[0])]
    else:
        contract_paths = const_paths = analysis_paths = walk_paths = \
            engine_paths = donation_paths = list(files)

    ctx = ct._Ctx()
    trees: dict[str, ast.Module] = {}

    def tree_of(p: str) -> ast.Module | None:
        if p not in trees:
            if not os.path.exists(p):
                return None
            with open(p, encoding="utf-8") as f:
                trees[p] = ast.parse(f.read(), filename=p)
        return trees.get(p)

    for p in contract_paths:
        t = tree_of(p)
        if t is not None:
            ct._collect_contracts(ctx, t, rel(root, p))
    _relabel_collect_findings(ctx)
    for p in const_paths + analysis_paths:
        t = tree_of(p)
        if t is not None:
            ct._collect_consts(ctx, t)

    analysis_mods = [ts._Module(p, trees[p]) for p in analysis_paths
                     if tree_of(p) is not None]
    part_returns = _interpret(ctx, analysis_mods, root)
    findings = list(ctx.findings)

    findings += _shard_map_spec_check(ctx, analysis_mods, part_returns,
                                      root)
    for p in donation_paths:
        t = tree_of(p)
        if t is not None:
            findings += _donation_partition_check(ctx, t, rel(root, p))
    walk_mods = [ts._Module(p, trees[p]) for p in walk_paths
                 if tree_of(p) is not None]
    findings += _callback_check(walk_mods, root)
    findings += _host_sync_check(
        [(p, trees[p]) for p in engine_paths if tree_of(p) is not None],
        root)
    if default_mode and dynamic:
        findings += sharding_check(root)
    return sorted(set(findings), key=lambda f: (f.path, f.line, f.rule))
