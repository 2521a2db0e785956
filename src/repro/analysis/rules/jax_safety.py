"""JAX device-safety rules: host syncs under jit, use-after-donate,
recompile hazards, and undeclared env gates.

These are the static twins of invariants the runtime only checks when a
test happens to drive the broken path: ``compile_count`` staying flat
(PR 8) detects a stray per-round ``jax.jit`` *after* it recompiled;
donation bugs surface as wrong numerics only when XLA actually reuses
the buffer; a ``float()`` inside a jitted body fails at trace time only
if that branch is traced.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import (FileContext, Finding, Project, Rule, call_name,
                    walk_scope)

# the env-gate registry module — the single place REPRO_* may be read
GATES_RELPATH = "analysis/gates.py"

# the mesh-axis vocabulary module — MESH_AXES is the declared set of
# axis names every mesh in the repo may use (JAX004 reads it by AST, so
# the lint engine never imports jax)
AXIS_RULES_RELPATH = "sharding/rules.py"

# wrapper entry points that donate caller buffers when donate=True;
# positions are the donated *positional* argument slots (mirrors
# donate_argnums on the jit twins in kernels/fed_agg.py)
DONATING_WRAPPERS: Dict[str, Tuple[int, ...]] = {
    "fed_agg": (0,),
    "fed_agg_apply": (0, 3, 4),
}

_HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "onp.asarray", "onp.array",
}


def _is_jax_jit(node: ast.AST) -> bool:
    """The expression refers to jax.jit (or a bare jit import)."""
    dotted = (call_name(node) if isinstance(node, ast.Call)
              else None)
    if dotted is None:
        name = None
        if isinstance(node, ast.Attribute):
            parts: List[str] = []
            cur: ast.AST = node
            while isinstance(cur, ast.Attribute):
                parts.append(cur.attr)
                cur = cur.value
            if isinstance(cur, ast.Name):
                parts.append(cur.id)
                name = ".".join(reversed(parts))
        elif isinstance(node, ast.Name):
            name = node.id
        return name in ("jax.jit", "jit")
    return False


def _jit_call(node: ast.Call) -> bool:
    return call_name(node) in ("jax.jit", "jit")


def _partial_jit_decorator(dec: ast.AST) -> bool:
    """@functools.partial(jax.jit, ...) / @partial(jax.jit, ...)."""
    if not isinstance(dec, ast.Call):
        return False
    if call_name(dec) not in ("functools.partial", "partial"):
        return False
    return bool(dec.args) and _is_jax_jit(dec.args[0])


def _jitted_function_names(tree: ast.Module) -> Set[str]:
    """Function names that end up traced under jax.jit in this file:
    decorated defs, defs assigned through ``X = jax.jit(f, ...)``, and
    defs referenced anywhere inside a jax.jit(...) argument expression
    (covers ``jax.jit(jax.vmap(f, ...))``)."""
    defs = {n.name for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    jitted: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jax_jit(dec) or _partial_jit_decorator(dec):
                    jitted.add(node.name)
        elif isinstance(node, ast.Call) and _jit_call(node):
            for arg in node.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and sub.id in defs:
                        jitted.add(sub.id)
    return jitted


class HostSyncInJitRule(Rule):
    """JAX001: host synchronization inside a jit-traced function.

    ``float(x)`` / ``x.item()`` / ``np.asarray(x)`` on a traced value
    either fails at trace time (if that branch traces) or silently
    constant-folds a runtime value into the compiled program.  Hot paths
    must keep values on device; sync once, outside the jit.
    """

    id = "JAX001"
    name = "host-sync-in-jit"
    description = "float()/.item()/np.asarray inside a jitted function"

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterator[Finding]:
        jitted = _jitted_function_names(ctx.tree)
        if not jitted:
            return
        for node in ast.walk(ctx.tree):
            if (not isinstance(node, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))
                    or node.name not in jitted):
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                dotted = call_name(sub)
                if dotted in _HOST_SYNC_CALLS:
                    yield self.finding(
                        ctx, sub.lineno,
                        f"{dotted}() inside jitted `{node.name}` pulls "
                        f"the value to host; keep it on device (jnp)")
                elif (isinstance(sub.func, ast.Name)
                      and sub.func.id == "float" and sub.args
                      and not isinstance(sub.args[0], ast.Constant)):
                    yield self.finding(
                        ctx, sub.lineno,
                        f"float() inside jitted `{node.name}` forces a "
                        f"host sync (or a trace error); use "
                        f"jnp.float32/astype")
                elif (isinstance(sub.func, ast.Attribute)
                      and sub.func.attr == "item" and not sub.args):
                    yield self.finding(
                        ctx, sub.lineno,
                        f".item() inside jitted `{node.name}` forces a "
                        f"host sync; return the array and read it "
                        f"outside the jit")


def _donate_kwarg_active(node: ast.Call) -> bool:
    """donate=... present and not a literal False."""
    for kw in node.keywords:
        if kw.arg == "donate":
            return not (isinstance(kw.value, ast.Constant)
                        and kw.value.value is False)
    return False


def _donated_positions(node: ast.Call) -> Optional[Tuple[int, ...]]:
    """For a jax.jit(...) call: the donate_argnums value, if literal."""
    for kw in node.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if (isinstance(e, ast.Constant)
                            and isinstance(e.value, int)):
                        out.append(e.value)
                return tuple(out)
    return None


class UseAfterDonateRule(Rule):
    """JAX002: reading a buffer after passing it at a donated position.

    Once a call donates an argument, XLA may have overwritten the buffer
    in place — any later read sees garbage *only on backends that honor
    donation*, so the bug passes every CPU test and corrupts results on
    TPU.  Covers twins created in-file via ``jax.jit(...,
    donate_argnums=...)`` and the exported kernels/fed_agg wrappers
    called with ``donate=True``.
    """

    id = "JAX002"
    name = "use-after-donate"
    description = "buffer read after being passed at a donated position"

    def _donating_callees(self, tree: ast.Module) -> Dict[str,
                                                          Tuple[int, ...]]:
        callees = dict(DONATING_WRAPPERS)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and _jit_call(node.value)):
                pos = _donated_positions(node.value)
                if pos:
                    callees[node.targets[0].id] = pos
        return callees

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterator[Finding]:
        callees = self._donating_callees(ctx.tree)
        scopes: List[ast.AST] = [ctx.tree]
        scopes += [n for n in ast.walk(ctx.tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._check_scope(ctx, scope, callees)

    def _check_scope(self, ctx: FileContext, scope: ast.AST,
                     callees: Dict[str, Tuple[int, ...]]
                     ) -> Iterator[Finding]:
        # this scope's own statements — nested defs are their own scopes
        nodes = list(walk_scope(scope))
        # (call start line, call end line, var name)
        donated: List[Tuple[int, int, str]] = []
        calls = [n for n in nodes if isinstance(n, ast.Call)]
        for node in calls:
            name = None
            if isinstance(node.func, ast.Name):
                name = node.func.id
            elif isinstance(node.func, ast.Attribute):
                name = node.func.attr
            if name not in callees:
                continue
            wrapper = name in DONATING_WRAPPERS
            if wrapper and not _donate_kwarg_active(node):
                continue
            for pos in callees[name]:
                if pos < len(node.args) and isinstance(node.args[pos],
                                                       ast.Name):
                    donated.append((node.lineno,
                                    node.end_lineno or node.lineno,
                                    node.args[pos].id))
        if not donated:
            return
        stores: List[Tuple[int, str]] = []
        loads: List[ast.Name] = []
        for node in nodes:
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    stores.append((node.lineno, node.id))
                elif isinstance(node.ctx, ast.Load):
                    loads.append(node)
        for call_line, call_end, var in donated:
            for load in loads:
                # reads inside the donating call's own span are the
                # donation itself, not a use-after
                if load.id != var or load.lineno <= call_end:
                    continue
                # a re-assignment between donation and read kills the
                # hazard — including `x = f(x)` reassigning on the
                # donating statement itself, the canonical pattern
                if any(call_line <= s_line <= load.lineno
                       for s_line, s_var in stores if s_var == var):
                    continue
                yield self.finding(
                    ctx, load.lineno,
                    f"`{var}` is read after being donated at line "
                    f"{call_line}; donated buffers may be overwritten "
                    f"in place on accelerator backends")
                break       # one finding per donated var is enough


class JitInRoundPathRule(Rule):
    """JAX003: ``jax.jit`` constructed inside a per-round call path.

    A fresh ``jax.jit`` object starts with an empty compile cache —
    building one per call retraces and recompiles every round, the exact
    hazard PR 8's ``compile_count`` counter only detects at runtime.
    Construction belongs at module scope or in ``__init__``; memoized
    builders need an explanatory pragma.
    """

    id = "JAX003"
    name = "jit-in-round-path"
    description = "jax.jit(...) constructed inside a function body"
    paths = ("core/", "fl/", "kernels/")

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterator[Finding]:
        funcs = [n for n in ast.walk(ctx.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in funcs:
            if fn.name == "__init__":       # construction-time is fine
                continue
            for node in walk_scope(fn):
                if isinstance(node, ast.Call) and _jit_call(node):
                    yield self.finding(
                        ctx, node.lineno,
                        f"jax.jit constructed inside `{fn.name}`; hoist "
                        f"to module scope / __init__, or memoize and "
                        f"pragma with the cache justification")


# collectives whose axis-name argument must come from the declared
# vocabulary; shard_map is handled separately (axis names live in its
# in_specs/out_specs PartitionSpecs)
_COLLECTIVE_CALLS = {
    "jax.lax.psum", "lax.psum", "psum",
    "jax.lax.pmean", "lax.pmean", "pmean",
    "jax.lax.pmax", "lax.pmax", "pmax",
    "jax.lax.pmin", "lax.pmin", "pmin",
    "jax.lax.all_gather", "lax.all_gather", "all_gather",
    "jax.lax.ppermute", "lax.ppermute", "ppermute",
    "jax.lax.axis_index", "lax.axis_index", "axis_index",
}

_SHARD_MAP_CALLS = {"shard_map", "jax.shard_map"}


class UndeclaredMeshAxisRule(Rule):
    """JAX004: a mesh-axis literal outside the declared vocabulary.

    Every mesh this repo builds (launch/mesh.py) names its axes from
    ``sharding/rules.MESH_AXES``.  A ``shard_map`` spec or a collective
    (``psum``/``all_gather``/...) naming an axis *not* in that tuple is
    either a typo or a mesh the sharing rules (merge_spec, cohort_spec,
    batch_specs) know nothing about — both fail only at run time, on a
    multi-device host the CI tier may never provision.  Axis names that
    arrive through variables are out of scope (they were resolved from
    the declared constants already).
    """

    id = "JAX004"
    name = "undeclared-mesh-axis"
    description = ("shard_map/psum axis literal not declared in "
                   "sharding/rules.py MESH_AXES")

    def _declared_axes(self, project: Project) -> Set[str]:
        """AST-parse MESH_AXES from the project's sharding/rules.py:
        string elements directly, Name elements resolved against the
        module's own string-constant assignments (CLIENT_AXIS)."""
        for f in project.files:
            if not f.relpath.endswith(AXIS_RULES_RELPATH):
                continue
            consts: Dict[str, str] = {}
            for node in ast.walk(f.tree):
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, str)):
                    consts[node.targets[0].id] = node.value.value
            axes: Set[str] = set()
            for node in ast.walk(f.tree):
                target, value = None, None
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    target, value = node.targets[0].id, node.value
                elif (isinstance(node, ast.AnnAssign)
                        and isinstance(node.target, ast.Name)):
                    target, value = node.target.id, node.value
                if target != "MESH_AXES" or not isinstance(
                        value, (ast.Tuple, ast.List)):
                    continue
                for e in value.elts:
                    if (isinstance(e, ast.Constant)
                            and isinstance(e.value, str)):
                        axes.add(e.value)
                    elif isinstance(e, ast.Name) and e.id in consts:
                        axes.add(consts[e.id])
            return axes
        return set()

    @staticmethod
    def _axis_literals(expr: ast.AST) -> Iterator[ast.Constant]:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                yield sub

    def _candidate_exprs(self, node: ast.Call) -> List[ast.AST]:
        """The expressions of this call that carry axis names."""
        dotted = call_name(node)
        if dotted in _SHARD_MAP_CALLS:
            exprs = [kw.value for kw in node.keywords
                     if kw.arg in ("in_specs", "out_specs")]
            # positional form: shard_map(f, mesh, in_specs, out_specs)
            exprs.extend(node.args[2:4])
            return exprs
        if dotted in _COLLECTIVE_CALLS:
            exprs = [kw.value for kw in node.keywords
                     if kw.arg == "axis_name"]
            pos = 0 if dotted.endswith("axis_index") else 1
            if len(node.args) > pos:
                exprs.append(node.args[pos])
            return exprs
        return []

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterator[Finding]:
        if ctx.relpath.endswith(AXIS_RULES_RELPATH):
            return              # the vocabulary itself
        declared = self._declared_axes(project)
        seen: Set[Tuple[int, str]] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            for expr in self._candidate_exprs(node):
                for lit in self._axis_literals(expr):
                    axis = lit.value
                    if axis in declared or (lit.lineno, axis) in seen:
                        continue
                    seen.add((lit.lineno, axis))
                    yield self.finding(
                        ctx, lit.lineno,
                        f"mesh axis {axis!r} is not declared in "
                        f"sharding/rules.py MESH_AXES; add it to the "
                        f"vocabulary (or use the declared constant)")


class EnvGateRegistryRule(Rule):
    """GATE001: ``REPRO_*`` env access outside ``analysis/gates.py``.

    Scattered ``os.environ.get("REPRO_...")`` reads are how two call
    sites end up disagreeing about a default (import-time vs call-time
    reads of the same gate).  All gates live in the
    :mod:`repro.analysis.gates` registry; everything else imports it.
    """

    id = "GATE001"
    name = "env-gate-registry"
    description = "REPRO_* env access outside the analysis/gates registry"

    def _gate_name(self, node: ast.AST) -> Optional[str]:
        """The REPRO_* string touched by this expression, if any."""
        if isinstance(node, ast.Subscript):
            target = node.value
            key = node.slice
            if (isinstance(target, ast.Attribute)
                    and target.attr == "environ"
                    and isinstance(key, ast.Constant)
                    and isinstance(key.value, str)
                    and key.value.startswith("REPRO_")):
                return key.value
        if isinstance(node, ast.Call):
            dotted = call_name(node)
            if dotted in ("os.environ.get", "os.getenv",
                          "os.environ.setdefault", "os.environ.pop"):
                if (node.args and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        and node.args[0].value.startswith("REPRO_")):
                    return node.args[0].value
        return None

    def check_file(self, ctx: FileContext,
                   project: Project) -> Iterator[Finding]:
        if ctx.relpath == GATES_RELPATH:
            return
        for node in ast.walk(ctx.tree):
            gate = self._gate_name(node)
            if gate:
                yield self.finding(
                    ctx, node.lineno,
                    f"direct env access to {gate}; read it through "
                    f"repro.analysis.gates (the documented registry)")


RULES = (HostSyncInJitRule(), UseAfterDonateRule(), JitInRoundPathRule(),
         UndeclaredMeshAxisRule(), EnvGateRegistryRule())
