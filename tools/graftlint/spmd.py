"""graftlint/spmd — distributed-correctness rules (GL06–GL10).

The SPMD/DMA dimension of graftlint: the bug classes that pass every
single-device CPU test and then deadlock or silently corrupt results on
a real mesh. Mirrors the reference stack's compute-sanitizer/racecheck
lane (COVERAGE.md) at lint time; the runtime complement is the
collective-schedule checker in :mod:`raft_tpu.obs.sanitize`.

GL06  collective scope/axis consistency — a ``Comms(...)`` construction
      or raw ``lax`` collective whose statically-resolvable axis name is
      not bound by any mesh/axis declaration in the module, or a
      collective issued from a function the module never wraps in
      ``shard_map`` (module-local reach analysis over shard_map targets,
      lexical nesting, and by-name calls).
GL07  statically-evaluable ``ppermute`` perms that are not permutations:
      duplicate sources, non-injective destinations, dropped
      destinations (``lax.ppermute`` silently ZERO-FILLS ranks nobody
      sends to), and ring-named perms that don't close a single cycle.
GL08  Pallas DMA lifetime — every ``make_async_copy`` /
      ``make_async_remote_copy`` ``.start()`` needs a matching
      ``.wait()`` on all control paths before kernel exit; a slot
      restarted while its previous copy is in flight, or two
      concurrently-live copies sharing one semaphore, is the
      double-buffering race class. Copy-factory calls with statically
      stable arguments resolve to concrete semaphore slots (actuals
      substituted into the factory's sem expression), so the overlap
      idiom — loop-carried slot reuse with two in-flight copies on
      DISTINCT semaphores — is checked too; dynamically-rotated slots
      (loop-varying arguments) stay with the whole-tree start/wait
      tally.
GL09  ``shard_map`` contract — ``in_specs`` arity vs. the wrapped
      function's positional signature, and ``P()`` axis names absent
      from the mesh / module axis declarations.
GL10  facade bypass — raw ``lax.psum``/``all_gather``/``ppermute``/...
      in ``raft_tpu/`` outside ``parallel/comms.py`` escapes the
      ``comms.ops``/``comms.bytes`` telemetry (docs/observability.md).

Analyses are module-local and conservative: a finding needs a
statically-resolvable axis/perm/spec; anything dynamic is skipped, so
axis-generic helpers (``jax.lax.axis_size``, the facade itself)
stay quiet by construction.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

from tools.graftlint import _Parents, _const_env, _const_int, _dotted, \
    cached_walk

# Traffic-bearing collective verbs on jax.lax (axis_index / axis_size
# carry no payload and are deliberately excluded).
_RAW_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "psum_scatter", "ppermute", "pshuffle",
}
# Collective verbs of the Comms facade (parallel/comms.py). get_rank /
# get_size are no-traffic topology queries, not collectives.
_FACADE_VERBS = {
    "allreduce", "reduce", "bcast", "allgather", "gather", "allgatherv",
    "gatherv", "reducescatter", "alltoall", "ppermute", "send_recv_ring",
}
_DMA_MAKERS = {"make_async_copy", "make_async_remote_copy"}
_AXIS_PARAM_NAMES = {"axis", "axis_name", "axis_names"}

_FnLike = Union[ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda]


def _last_seg(callee: str) -> str:
    return callee.split(".")[-1] if callee else ""


def _fn_like_nodes(tree: ast.Module) -> List[_FnLike]:
    return [n for n in cached_walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda))]


def _enclosing(node: ast.AST, parents: _Parents) -> List[_FnLike]:
    """Function-like ancestors of ``node``, innermost first."""
    out: List[_FnLike] = []
    cur = parents.parent.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            out.append(cur)
        cur = parents.parent.get(cur)
    return out


def _module_strs(tree: ast.Module) -> Dict[str, object]:
    """Module-level constants usable as axis names: bare strings and
    tuples/lists of strings (2-D mesh axis bundles like
    ``HIER_AXIS_NAMES = ("dcn", "ici")``)."""
    out: Dict[str, object] = {}
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        val = node.value
        if isinstance(val, ast.Constant) and isinstance(val.value, str):
            out[node.targets[0].id] = val.value
        elif isinstance(val, (ast.Tuple, ast.List)) and val.elts and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in val.elts):
            out[node.targets[0].id] = tuple(e.value for e in val.elts)
    return out


def _str_default(fn: _FnLike, name: str):
    """Resolve ``name`` within ``fn``: its string default if ``name`` is
    a parameter with one, ``None`` if bound but unresolvable (param
    without a string default, or ambiguous local assigns), ``False`` if
    ``fn`` does not bind it (keep looking outward)."""
    a = fn.args
    params = a.posonlyargs + a.args
    off = len(params) - len(a.defaults)
    for i, p in enumerate(params):
        if p.arg == name:
            if i >= off:
                d = a.defaults[i - off]
                if isinstance(d, ast.Constant) and isinstance(d.value, str):
                    return d.value
            return None
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if p.arg == name:
            if d is not None and isinstance(d, ast.Constant) \
                    and isinstance(d.value, str):
                return d.value
            return None
    if isinstance(fn, ast.Lambda):
        return False
    assigns = [s.value for s in ast.walk(fn)
               if isinstance(s, ast.Assign) and len(s.targets) == 1
               and isinstance(s.targets[0], ast.Name)
               and s.targets[0].id == name]
    if len(assigns) == 1 and isinstance(assigns[0], ast.Constant) \
            and isinstance(assigns[0].value, str):
        return assigns[0].value
    if assigns:
        return None
    return False


def _resolve_axis(expr: ast.AST, chain: Sequence[_FnLike],
                  mod_strs: Dict[str, object]):
    """Statically resolve an axis-name expression to a str, a tuple of
    strs (multi-axis), or None when dynamic."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.Name):
        for fn in chain:
            r = _str_default(fn, expr.id)
            if r is not False:
                return r
        return mod_strs.get(expr.id)
    if isinstance(expr, (ast.Tuple, ast.List)) and expr.elts:
        parts = [_resolve_axis(e, chain, mod_strs) for e in expr.elts]
        if all(isinstance(p, str) for p in parts):
            return tuple(parts)
        return None
    return None


def _axis_strs(resolved) -> List[str]:
    if isinstance(resolved, str):
        return [resolved]
    if isinstance(resolved, tuple):
        return list(resolved)
    return []


def _mesh_call_axes(call: ast.Call,
                    mod_strs: Optional[Dict[str, object]] = None
                    ) -> Optional[Set[str]]:
    """String axis names of a mesh-constructor call (``Mesh`` /
    ``make_mesh`` / ``make_hybrid_mesh`` / ``hier_mesh`` with an
    ``axis_names`` that is literal or a module string/tuple constant),
    or None when ``call`` is not a mesh construction / not static.
    Single source of truth for GL06's declaration set and GL09's mesh
    resolution."""
    seg = _last_seg(_dotted(call.func))
    if seg not in ("Mesh", "make_mesh", "make_hybrid_mesh", "hier_mesh"):
        return None
    cand = None
    for kw in call.keywords:
        if kw.arg == "axis_names":
            cand = kw.value
    if cand is None and seg == "Mesh" and len(call.args) >= 2:
        cand = call.args[1]
    if cand is None:
        return None
    if isinstance(cand, ast.Name) and mod_strs is not None:
        const = mod_strs.get(cand.id)
        if isinstance(const, str):
            return {const}
        if isinstance(const, tuple):
            return set(const)
    return {el.value for el in ast.walk(cand)
            if isinstance(el, ast.Constant) and isinstance(el.value, str)}


def _declared_axes(tree: ast.Module,
                   mod_strs: Dict[str, object]) -> Set[str]:
    """Axis names the module binds: mesh constructions with literal
    ``axis_names``, string defaults of parameters named axis/axis_name/
    axis_names, and axis-named module string/tuple constants."""
    axes: Set[str] = set()

    def strs_of(node: ast.AST) -> None:
        for el in ast.walk(node):
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                axes.add(el.value)

    for node in cached_walk(tree):
        if isinstance(node, ast.Call):
            mesh_axes = _mesh_call_axes(node, mod_strs)
            if mesh_axes:
                axes.update(mesh_axes)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            a = node.args
            params = a.posonlyargs + a.args
            off = len(params) - len(a.defaults)
            for i, p in enumerate(params):
                if i >= off and p.arg in _AXIS_PARAM_NAMES:
                    strs_of(a.defaults[i - off])
            for p, d in zip(a.kwonlyargs, a.kw_defaults):
                if p.arg in _AXIS_PARAM_NAMES and d is not None:
                    strs_of(d)
    for name, val in mod_strs.items():
        low = name.lower()
        if "axis" not in low and "axes" not in low:
            continue
        if isinstance(val, str):
            axes.add(val)
        else:
            axes.update(val)
    return axes


def _lax_imports(tree: ast.Module) -> Set[str]:
    names: Set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.ImportFrom) and node.module == "jax.lax":
            for al in node.names:
                names.add(al.asname or al.name)
    return names


def _raw_collective(call: ast.Call, lax_names: Set[str]) -> Optional[str]:
    callee = _dotted(call.func)
    if not callee:
        return None
    parts = callee.split(".")
    verb = parts[-1]
    if verb not in _RAW_COLLECTIVES:
        return None
    if len(parts) >= 2 and parts[-2] == "lax":
        return verb
    if len(parts) == 1 and verb in lax_names:
        return verb
    return None


# ---------------------------------------------------------------------------
# module info
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ModuleInfo:
    tree: ast.Module
    parents: _Parents
    path: str
    env: Dict[str, int]
    mod_strs: Dict[str, object]
    calls: List[ast.Call]
    lax_names: Set[str]
    declared_axes: Set[str]
    uses_shard_map: bool
    shard_map_calls: List[ast.Call]
    reach: Set[ast.AST]
    by_name: Dict[str, List[ast.AST]]
    comms_binds: Dict[str, List[ast.Call]]


def _shard_map_info(tree: ast.Module,
                    calls: Sequence[ast.Call]) -> Tuple[bool, List[ast.Call]]:
    uses = False
    sm_calls: List[ast.Call] = []
    for call in calls:
        if _last_seg(_dotted(call.func)) in ("shard_map", "_shard_map"):
            uses = True
            sm_calls.append(call)
    return uses, sm_calls


def _reach_set(tree: ast.Module, parents: _Parents,
               sm_calls: Sequence[ast.Call],
               by_name: Dict[str, List[ast.AST]]) -> Set[ast.AST]:
    """Functions that execute under shard_map: targets passed to
    shard_map, functions lexically nested in reaching functions, and
    functions called by name from reaching ones (fixpoint)."""
    fns = _fn_like_nodes(tree)
    reach: Set[ast.AST] = set()
    for call in sm_calls:
        if not call.args or isinstance(call.args[0], ast.Starred):
            continue
        t = call.args[0]
        if isinstance(t, ast.Lambda):
            reach.add(t)
        elif isinstance(t, ast.Name):
            reach.update(by_name.get(t.id, []))
        elif isinstance(t, ast.Call) and t.args \
                and isinstance(t.args[0], ast.Name):  # partial(fn, ...)
            reach.update(by_name.get(t.args[0].id, []))
    changed = True
    while changed:
        changed = False
        for f in fns:
            if f in reach:
                continue
            anc = parents.parent.get(f)
            while anc is not None:
                if anc in reach:
                    reach.add(f)
                    changed = True
                    break
                anc = parents.parent.get(anc)
        called: Set[str] = set()
        for rf in reach:
            for node in ast.walk(rf):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name):
                    called.add(node.func.id)
        for name in called:
            for f in by_name.get(name, []):
                if f not in reach:
                    reach.add(f)
                    changed = True
    return reach


def _build_info(tree: ast.Module, parents: _Parents,
                path: str) -> _ModuleInfo:
    calls = [n for n in cached_walk(tree) if isinstance(n, ast.Call)]
    by_name: Dict[str, List[ast.AST]] = {}
    for f in _fn_like_nodes(tree):
        if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(f.name, []).append(f)
    uses, sm_calls = _shard_map_info(tree, calls)
    mod_strs = _module_strs(tree)
    comms_binds: Dict[str, List[ast.Call]] = {}
    for node in cached_walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call):
            seg = _last_seg(_dotted(node.value.func))
            if seg in ("Comms", "comm_split") and node.value.args:
                comms_binds.setdefault(node.targets[0].id,
                                       []).append(node.value)
    return _ModuleInfo(
        tree=tree, parents=parents, path=path, env=_const_env(tree),
        mod_strs=mod_strs, calls=calls, lax_names=_lax_imports(tree),
        declared_axes=_declared_axes(tree, mod_strs),
        uses_shard_map=uses, shard_map_calls=sm_calls,
        reach=_reach_set(tree, parents, sm_calls, by_name),
        by_name=by_name, comms_binds=comms_binds)


# ---------------------------------------------------------------------------
# GL06 — collective scope / axis consistency
# ---------------------------------------------------------------------------

def _collective_axis_arg(call: ast.Call, raw_verb: Optional[str]):
    if raw_verb is not None:
        for kw in call.keywords:
            if kw.arg == "axis_name":
                return kw.value
        if len(call.args) >= 2:
            return call.args[1]
        return None
    return call.args[0] if call.args else None


def _is_method(fn: ast.AST) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    params = fn.args.posonlyargs + fn.args.args
    return bool(params) and params[0].arg in ("self", "cls")


def _check_gl06(info: _ModuleInfo, add) -> None:
    declared = info.declared_axes

    def check_declared(call: ast.Call, resolved, what: str) -> None:
        if not declared:
            return
        missing = [a for a in _axis_strs(resolved) if a not in declared]
        if missing:
            add(call, "GL06",
                f"{what} uses axis name(s) {missing} not bound by any "
                f"mesh/axis declaration in this module "
                f"(declared: {sorted(declared)})")

    def check_enclosure(call: ast.Call, resolved, verb: str) -> None:
        if not info.uses_shard_map or not _axis_strs(resolved):
            return
        chain = _enclosing(call, info.parents)
        if not chain:
            add(call, "GL06",
                f"{verb}() at module level runs eagerly with no "
                "shard_map binding its axis")
            return
        fn = chain[0]
        if fn in info.reach or _is_method(fn):
            return
        name = getattr(fn, "name", "<lambda>")
        add(call, "GL06",
            f"{verb}() over axis {_axis_strs(resolved)} inside {name}(), "
            "which is never wrapped in (or called from) shard_map in "
            "this module — the axis is unbound at this call site")

    # raw lax collectives
    for call in info.calls:
        verb = _raw_collective(call, info.lax_names)
        if verb is None:
            continue
        axis = _collective_axis_arg(call, verb)
        resolved = (None if axis is None else
                    _resolve_axis(axis, _enclosing(call, info.parents),
                                  info.mod_strs))
        check_declared(call, resolved, f"lax.{verb}()")
        check_enclosure(call, resolved, f"lax.{verb}")

    # Comms(...) constructions: axis checked once, at the binding
    cons_resolution: Dict[int, object] = {}
    for call in info.calls:
        if _last_seg(_dotted(call.func)) != "Comms" or not call.args:
            continue
        resolved = _resolve_axis(call.args[0],
                                 _enclosing(call, info.parents),
                                 info.mod_strs)
        cons_resolution[id(call)] = resolved
        check_declared(call, resolved, "Comms(...)")

    # facade collective calls on Comms-bound names (or inline Comms(...))
    for call in info.calls:
        if not isinstance(call.func, ast.Attribute) \
                or call.func.attr not in _FACADE_VERBS:
            continue
        recv = call.func.value
        resolved = None
        if isinstance(recv, ast.Name) and recv.id in info.comms_binds:
            res = {repr(_resolve_axis(
                c.args[0], _enclosing(c, info.parents), info.mod_strs))
                for c in info.comms_binds[recv.id]}
            if len(res) == 1:
                resolved = _resolve_axis(
                    info.comms_binds[recv.id][0].args[0],
                    _enclosing(info.comms_binds[recv.id][0], info.parents),
                    info.mod_strs)
        elif isinstance(recv, ast.Call) \
                and _last_seg(_dotted(recv.func)) == "Comms" and recv.args:
            resolved = cons_resolution.get(id(recv))
        check_enclosure(call, resolved, f"Comms.{call.func.attr}")


# ---------------------------------------------------------------------------
# GL07 — statically-evaluable ppermute perms
# ---------------------------------------------------------------------------

def _literal_perm(expr: ast.AST, chain: Sequence[_FnLike],
                  env: Dict[str, int]) -> Optional[List[Tuple[int, int]]]:
    if isinstance(expr, ast.Name):
        for fn in chain:
            if isinstance(fn, ast.Lambda):
                continue
            assigns = [s.value for s in ast.walk(fn)
                       if isinstance(s, ast.Assign) and len(s.targets) == 1
                       and isinstance(s.targets[0], ast.Name)
                       and s.targets[0].id == expr.id]
            if len(assigns) == 1:
                expr = assigns[0]
                break
            if assigns:
                return None
        else:
            return None
    if not isinstance(expr, (ast.List, ast.Tuple)):
        return None
    pairs: List[Tuple[int, int]] = []
    for el in expr.elts:
        if not isinstance(el, (ast.Tuple, ast.List)) or len(el.elts) != 2:
            return None
        s = _const_int(el.elts[0], env)
        d = _const_int(el.elts[1], env)
        if s is None or d is None:
            return None
        pairs.append((s, d))
    return pairs or None


def _cycle_count(pairs: Sequence[Tuple[int, int]]) -> int:
    nxt = dict(pairs)
    seen: Set[int] = set()
    cycles = 0
    for start in nxt:
        if start in seen:
            continue
        cycles += 1
        cur = start
        while cur not in seen:
            seen.add(cur)
            cur = nxt[cur]
    return cycles


def _check_gl07(info: _ModuleInfo, add) -> None:
    for call in info.calls:
        raw = _raw_collective(call, info.lax_names)
        perm_expr = None
        if raw == "ppermute":
            perm_expr = call.args[2] if len(call.args) >= 3 else None
        elif isinstance(call.func, ast.Attribute) \
                and call.func.attr == "ppermute":
            perm_expr = call.args[1] if len(call.args) >= 2 else None
        else:
            continue
        for kw in call.keywords:
            if kw.arg == "perm":
                perm_expr = kw.value
        if perm_expr is None:
            continue
        chain = _enclosing(call, info.parents)
        pairs = _literal_perm(perm_expr, chain, info.env)
        if not pairs:
            continue
        srcs = [s for s, _ in pairs]
        dsts = [d for _, d in pairs]
        dup_src = sorted({s for s in srcs if srcs.count(s) > 1})
        dup_dst = sorted({d for d in dsts if dsts.count(d) > 1})
        if dup_src:
            add(call, "GL07",
                f"ppermute perm has duplicate source(s) {dup_src} — "
                "each rank may appear as source at most once")
        if dup_dst:
            add(call, "GL07",
                f"ppermute perm is not injective: destination(s) "
                f"{dup_dst} receive from multiple sources")
        participants = range(max(max(srcs), max(dsts)) + 1)
        dropped = sorted(set(participants) - set(dsts))
        if dropped and not dup_dst:
            add(call, "GL07",
                f"ppermute perm drops destination(s) {dropped} — "
                "lax.ppermute silently ZERO-FILLS ranks nobody sends to")
        if not dup_src and not dup_dst and not dropped \
                and set(srcs) == set(dsts) == set(participants):
            ring_ctx = "ring" in _dotted(call.func).lower() or any(
                "ring" in getattr(fn, "name", "").lower() for fn in chain)
            cycles = _cycle_count(pairs)
            if ring_ctx and cycles > 1:
                add(call, "GL07",
                    f"ring perm does not close a single cycle "
                    f"({cycles} disjoint cycles over "
                    f"{len(pairs)} ranks)")


# ---------------------------------------------------------------------------
# GL08 — Pallas DMA start/wait lifetime
# ---------------------------------------------------------------------------

def _is_dma_make(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) \
        and _last_seg(_dotted(node.func)) in _DMA_MAKERS


def _sem_dump(make_call: ast.Call) -> str:
    sems = [ast.dump(kw.value) for kw in make_call.keywords
            if kw.arg in ("sem", "send_sem", "recv_sem")]
    if sems:
        return "|".join(sems)
    if len(make_call.args) >= 3:
        return ast.dump(make_call.args[2])
    return ""


def _is_pl_when(fn: ast.AST) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call) \
                and _last_seg(_dotted(dec.func)) == "when":
            return True
    return False


def _dma_roots(tree: ast.Module) -> List[ast.FunctionDef]:
    cands = [f for f in cached_walk(tree)
             if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
             and any(_is_dma_make(n) for n in ast.walk(f))]
    roots = []
    for f in cands:
        if not any(o is not f and f in ast.walk(o) for o in cands):
            roots.append(f)
    return roots


def _check_gl08(info: _ModuleInfo, add) -> None:
    for root in _dma_roots(info.tree):
        _dma_check_fn(root, add)


def _dma_check_fn(root: ast.FunctionDef, add) -> None:
    # copy factories: local defs returning a make_async_* call
    factories: Set[str] = set()
    for f in ast.walk(root):
        if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and f is not root:
            if any(isinstance(s, ast.Return) and _is_dma_make(s.value)
                   for s in ast.walk(f)):
                factories.add(f.name)
    # variables assigned from make_async_* anywhere in the kernel
    dma_vars: Set[str] = set()
    var_descr: Dict[str, str] = {}
    var_sem: Dict[str, str] = {}
    for node in ast.walk(root):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_dma_make(node.value):
            name = node.targets[0].id
            dma_vars.add(name)
            var_descr[name] = ast.dump(node.value)
            var_sem[name] = _sem_dump(node.value)

    def identity(recv: ast.AST):
        if isinstance(recv, ast.Name) and recv.id in dma_vars:
            return ("var", recv.id)
        if isinstance(recv, ast.Call):
            seg = _last_seg(_dotted(recv.func))
            if seg in factories:
                return ("factory", seg)
            if _is_dma_make(recv):
                return ("descr", ast.dump(recv))
        return None

    # whole-tree tally (includes nested defs — the queue idiom waits in
    # a fori_loop body function)
    starts: List[Tuple[Tuple[str, str], ast.Call]] = []
    waited: Set[Tuple[str, str]] = set()
    for node in ast.walk(root):
        if isinstance(node, ast.Call) and isinstance(node.func,
                                                     ast.Attribute):
            ident = identity(node.func.value)
            if ident is None:
                continue
            if node.func.attr == "start":
                starts.append((ident, node))
            elif node.func.attr.startswith("wait"):
                waited.add(ident)

    def is_waited(ident: Tuple[str, str]) -> bool:
        if ident in waited:
            return True
        if ident[0] == "var":
            return ("descr", var_descr.get(ident[1], "")) in waited
        if ident[0] == "descr":
            return any(w[0] == "var" and var_descr.get(w[1]) == ident[1]
                       for w in waited)
        return False

    flagged: Set[Tuple[str, str]] = set()
    for ident, node in starts:
        if not is_waited(ident) and ("nowait", ident[1]) not in flagged:
            flagged.add(("nowait", ident[1]))
            what = (f"factory {ident[1]}()" if ident[0] == "factory"
                    else f"DMA {ident[1]!r}")
            add(node, "GL08",
                f"{what} is started but never waited anywhere in "
                f"{root.name}() — in-flight DMA at kernel exit")

    # factory slot identity (the overlap idiom — ISSUE 11): a factory
    # call whose arguments are statically stable resolves to a concrete
    # semaphore slot by substituting the actuals into the factory's sem
    # expression, so loop-carried slot reuse across hops is checkable:
    # two in-flight copies on DISTINCT semaphores are the legitimate
    # pipelined schedule; a restart of the SAME slot without a wait is
    # the race. Calls carrying loop-varying names (slot = s % 2, the
    # gather-refine queue's t % NBUF) rotate dynamically and stay with
    # the whole-tree tally.
    fac_sems: Dict[str, Tuple[List[str], List[ast.AST]]] = {}
    for f in ast.walk(root):
        if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and f is not root and f.name in factories:
            for s in ast.walk(f):
                if isinstance(s, ast.Return) and _is_dma_make(s.value):
                    sems = [kw.value for kw in s.value.keywords
                            if kw.arg in ("sem", "send_sem", "recv_sem")]
                    if not sems and len(s.value.args) >= 3:
                        sems = [s.value.args[2]]
                    fac_sems[f.name] = ([a.arg for a in f.args.args],
                                        sems)
                    break
    varying: Set[str] = set()
    for node in ast.walk(root):
        if isinstance(node, ast.For) and isinstance(node.target,
                                                    ast.Name):
            varying.add(node.target.id)
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    varying.add(tgt.id)
        elif isinstance(node, ast.AugAssign) \
                and isinstance(node.target, ast.Name):
            varying.add(node.target.id)

    class _Subst(ast.NodeTransformer):
        def __init__(self, mapping):
            self.mapping = mapping

        def visit_Name(self, node):
            return self.mapping.get(node.id, node)

    # a factory with ANY dynamically-slotted wait (loop-varying
    # argument) makes per-slot liveness unsound — a rotated wait may
    # cover any static slot (the prologue-fill + drain-in-loop queue
    # idiom) — so its calls stay with the whole-tree tally entirely
    def _args_vary(recv: ast.Call) -> bool:
        return any(isinstance(n, ast.Name) and n.id in varying
                   for a in recv.args for n in ast.walk(a))

    dyn_wait_facs: Set[str] = set()
    for node in ast.walk(root):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr.startswith("wait") \
                and isinstance(node.func.value, ast.Call):
            fname = _last_seg(_dotted(node.func.value.func))
            if fname in fac_sems and _args_vary(node.func.value):
                dyn_wait_facs.add(fname)

    def fac_slot(recv: ast.Call):
        """(key, sem-dump, label) for a statically-slotted factory call,
        or ``None`` when the slot rotates dynamically."""
        import copy as _copy

        name = _last_seg(_dotted(recv.func))
        if name not in fac_sems or name in dyn_wait_facs \
                or recv.keywords:
            return None
        params, sems = fac_sems[name]
        if not sems or len(recv.args) > len(params):
            return None
        for a in recv.args:
            if any(isinstance(n, ast.Name) and n.id in varying
                   for n in ast.walk(a)):
                return None
        mapping = dict(zip(params, recv.args))
        subst = []
        for s_expr in sems:
            cp = _Subst(mapping).visit(_copy.deepcopy(s_expr))
            if any(isinstance(n, ast.Name) and n.id in varying
                   for n in ast.walk(cp)):
                return None
            subst.append(ast.dump(cp))
        sem = "|".join(subst)
        args = ",".join(ast.dump(a) for a in recv.args)
        return (("fslot", name, args), sem,
                f"{name}({', '.join(ast.unparse(a) for a in recv.args)})")

    # sequential abstract interpretation over the kernel body: per-slot
    # liveness, loop-carried reuse, semaphore sharing, all-paths waits
    def merge(l1: Dict[object, dict], l2: Dict[object, dict]
              ) -> Dict[object, dict]:
        out: Dict[object, dict] = {}
        for name in set(l1) | set(l2):
            a, b = l1.get(name), l2.get(name)
            ent = dict(a or b)
            ent["definite"] = bool(a and b and a["definite"]
                                   and b["definite"])
            out[name] = ent
        return out

    def handle_call(call: ast.Call, live: Dict[object, dict]) -> None:
        if not isinstance(call.func, ast.Attribute):
            return
        recv = call.func.value
        if isinstance(recv, ast.Name) and recv.id in dma_vars:
            key = recv.id
            sem = var_sem.get(recv.id, "")
            label = repr(recv.id)
        elif isinstance(recv, ast.Call):
            fs = fac_slot(recv)
            if fs is None:
                return
            key, sem, label = fs
            label = f"factory {label}"
        else:
            return
        if call.func.attr == "start":
            ent = live.get(key)
            if ent is not None and ent["definite"]:
                if ("restart", key) not in flagged:
                    flagged.add(("restart", key))
                    add(call, "GL08",
                        f"DMA slot {label} restarted while its previous "
                        "copy is still in flight — wait() the slot "
                        "before reuse (double-buffering race)")
            else:
                for other, oent in live.items():
                    if other != key and sem and oent.get("sem") == sem \
                            and ("sem", key) not in flagged:
                        flagged.add(("sem", key))
                        add(call, "GL08",
                            f"DMAs {oent.get('label', other)} and "
                            f"{label} are concurrently live on the SAME "
                            "semaphore — waits become ambiguous; give "
                            "each in-flight copy its own semaphore slot")
            live[key] = {"sem": sem, "node": call, "definite": True,
                         "label": label}
        elif call.func.attr.startswith("wait"):
            live.pop(key, None)

    def exit_check(live: Dict[object, dict]) -> None:
        for name, ent in live.items():
            fname = name[1] if isinstance(name, tuple) else name
            if ("nowait", fname) in flagged or ("exit", name) in flagged \
                    or ("restart", name) in flagged:
                continue
            flagged.add(("exit", name))
            add(ent["node"], "GL08",
                f"DMA {ent.get('label', repr(name))} is not waited on "
                f"all control paths before {root.name}() exits")

    def exec_block(stmts: Sequence[ast.stmt],
                   live: Dict[str, dict]) -> Dict[str, dict]:
        for st in stmts:
            if isinstance(st, ast.Expr) and isinstance(st.value, ast.Call):
                handle_call(st.value, live)
            elif isinstance(st, ast.Assign):
                pass  # descriptor (re)binding tracked via var_sem/descr
            elif isinstance(st, ast.If):
                l1 = exec_block(list(st.body), dict(live))
                l2 = exec_block(list(st.orelse), dict(live))
                live = merge(l1, l2)
            elif isinstance(st, (ast.For, ast.While)):
                l1 = exec_block(list(st.body), dict(live))
                exec_block(list(st.body), dict(l1))  # loop-carried pass
                live = merge(live, l1)
            elif isinstance(st, (ast.With, ast.Try)):
                live = exec_block(list(st.body), live)
            elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _is_pl_when(st):  # conditionally-executed inline body
                    live = merge(live, exec_block(list(st.body),
                                                  dict(live)))
            elif isinstance(st, ast.Return):
                exit_check(live)
        return live

    exit_check(exec_block(list(root.body), {}))


# ---------------------------------------------------------------------------
# GL09 — shard_map contract
# ---------------------------------------------------------------------------

def _mesh_axes(expr: ast.AST, info: _ModuleInfo) -> Set[str]:
    """Mesh axis names when statically resolvable (inline construction
    or module-level binding with literal axis_names); empty otherwise."""
    if isinstance(expr, ast.Call):
        return _mesh_call_axes(expr, info.mod_strs) or set()
    if isinstance(expr, ast.Name):
        for node in info.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == expr.id \
                    and isinstance(node.value, ast.Call):
                return _mesh_call_axes(node.value, info.mod_strs) or set()
    return set()


def _positional_arity(fn: ast.AST) -> Optional[int]:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
        return None
    if fn.args.vararg is not None:
        return None
    return len(fn.args.posonlyargs) + len(fn.args.args)


def _check_gl09(info: _ModuleInfo, add) -> None:
    for call in info.shard_map_calls:
        if not call.args or isinstance(call.args[0], ast.Starred):
            continue
        target = call.args[0]
        kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        in_specs = kwargs.get("in_specs")
        out_specs = kwargs.get("out_specs")

        # (a) in_specs arity vs the wrapped function's signature. Only
        # literal tuples/lists pin the arity: a bare P(...) in_specs is
        # a valid pytree PREFIX that broadcasts over every argument.
        arity = None
        if isinstance(in_specs, (ast.Tuple, ast.List)):
            arity = len(in_specs.elts)
        nparams = None
        fname = None
        if isinstance(target, ast.Lambda):
            nparams = _positional_arity(target)
            fname = "<lambda>"
        elif isinstance(target, ast.Name):
            defs = info.by_name.get(target.id, [])
            if len(defs) == 1:
                nparams = _positional_arity(defs[0])
                fname = target.id
        if arity is not None and nparams is not None and arity != nparams:
            add(call, "GL09",
                f"shard_map in_specs has {arity} entr"
                f"{'y' if arity == 1 else 'ies'} but {fname}() takes "
                f"{nparams} positional parameter"
                f"{'' if nparams == 1 else 's'}")

        # (b) P() axis names absent from the mesh / module declarations
        universe = _mesh_axes(kwargs.get("mesh"), info) \
            or info.declared_axes
        if not universe:
            continue
        chain = _enclosing(call, info.parents)
        for spec_root in (in_specs, out_specs):
            if spec_root is None:
                continue
            for node in ast.walk(spec_root):
                if not (isinstance(node, ast.Call)
                        and _last_seg(_dotted(node.func))
                        in ("P", "PartitionSpec")):
                    continue
                for arg in node.args:
                    resolved = _resolve_axis(arg, chain, info.mod_strs)
                    missing = [a for a in _axis_strs(resolved)
                               if a not in universe]
                    if missing:
                        add(node, "GL09",
                            f"P() names axis {missing} absent from the "
                            f"mesh axes {sorted(universe)}")


# ---------------------------------------------------------------------------
# GL10 — facade bypass
# ---------------------------------------------------------------------------

def _check_gl10(info: _ModuleInfo, add) -> None:
    norm = info.path.replace(os.sep, "/")
    if "raft_tpu/" not in norm or norm.endswith("parallel/comms.py"):
        return
    for call in info.calls:
        verb = _raw_collective(call, info.lax_names)
        if verb is not None:
            add(call, "GL10",
                f"raw lax.{verb}() outside parallel/comms.py bypasses "
                "the Comms facade — comms.ops/comms.bytes telemetry "
                "misses this collective; route it through Comms (scoped "
                "disable-fn=GL10 with a reason for true exceptions)")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def check(tree: ast.Module, parents: _Parents, path: str, add) -> None:
    """Run GL06–GL10 over one module (called from lint_source)."""
    info = _build_info(tree, parents, path)
    _check_gl06(info, add)
    _check_gl07(info, add)
    _check_gl08(info, add)
    _check_gl09(info, add)
    _check_gl10(info, add)
