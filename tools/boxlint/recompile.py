"""Pass — recompile hazards at jit entry call sites (BX911).

The static twin of the PR-15 recompile sentinel: ``InstrumentedJit``
counts executable cache misses at runtime and alarms after the warmup
budget; this pass pins the three hazard shapes that CAUSE those misses,
at the call site, before a chip run ever burns compile time on
them:

  * **python scalars / set displays at traced positions** — a weak-typed
    python scalar keys a different executable than the array the other
    call sites pass (and a set is not even a pytree); wrap the value in
    ``jnp.asarray`` at the boundary or declare the position static;
  * **unstable static values** — ``tuple(<set>)`` / ``list(<set>)`` at a
    ``static_argnums``/``static_argnames`` position hashes differently
    per process (set iteration order), so every run retraces; iterate
    ``sorted(...)`` to make the static key canonical;
  * **mutable module state closed over by a jitted body** — a wrapped
    function reading a module-level ``list``/``dict``/``set`` bakes the
    value at trace time; later mutation is silently invisible (or forces
    a retrace when the shape leaks into the key).

Entry resolution comes from the taint layer's binding maps (module vars,
``self._step`` attrs, factory returns, dataclass fields), so the check
crosses modules: a scalar passed to ``self._step(...)`` is judged
against the ``instrument_jit`` contract declared in the factory that
built it.

Codes:
  BX911  recompile hazard at a jit entry call site / inside a wrapped
         body
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.boxlint.core import SourceFile, Violation
from tools.boxlint.callgraph import get_index
from tools.boxlint.purity import dotted
from tools.boxlint.taint import JitEntry, get_contracts

_EXEMPT_PARTS = {"tools", "tests", "examples"}


def _exempt(rel: str) -> bool:
    return bool(_EXEMPT_PARTS.intersection(rel.split("/")[:-1]))


def check(files: Sequence[SourceFile]) -> List[Violation]:
    index = get_index(files)
    c = get_contracts(files)
    out: List[Violation] = []
    seen: Set[Tuple[str, int, str]] = set()

    def flag(rel: str, line: int, msg: str) -> None:
        key = (rel, line, msg[:40])
        if key not in seen:
            seen.add(key)
            out.append(Violation(rel, line, "BX911", msg))

    # ---- call-site hazards -------------------------------------------
    for node in index.nodes:
        if _exempt(node.file.rel):
            continue
        local = c._local_jits(node, direct_only=False)
        own = index._own_statement_ids(node)
        for sub in ast.walk(node.fn):
            if id(sub) not in own or not isinstance(sub, ast.Call):
                continue
            entry = c.entry_for_call(sub, node, local)
            if entry is None:
                continue
            _check_site(node.file.rel, sub, entry, flag)

    # ---- closure capture of mutable module state ----------------------
    for entry in c.entries:
        w = entry.wrapped
        if w is None or _exempt(w.file.rel):
            continue
        mutables = _module_mutables(w.file.tree)
        if not mutables:
            continue
        assigned = _assigned_names(w.fn)
        for sub in ast.walk(w.fn):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load) \
                    and sub.id in mutables and sub.id not in assigned:
                flag(w.file.rel, sub.lineno,
                     f"jitted body `{w.qual}` (entry "
                     f"{entry.describe()}) closes over mutable module "
                     f"state `{sub.id}` — the value is baked at trace "
                     f"time and later mutation is invisible until an "
                     f"unrelated retrace; pass it as an argument or make "
                     f"it an immutable constant")
    return out


def _check_site(rel: str, call: ast.Call, entry: JitEntry, flag) -> None:
    for i, arg in enumerate(call.args):
        if isinstance(arg, ast.Starred):
            continue
        is_static = i in entry.static_nums
        if is_static:
            unstable = _set_ordered(arg)
            if unstable:
                flag(rel, call.lineno,
                     f"static_argnums value at position {i} of jit entry "
                     f"{entry.describe()} is derived from set iteration "
                     f"order ({unstable}) — the static key differs per "
                     f"process, so every run retraces; canonicalize with "
                     f"sorted(...)")
            continue
        hazard = _traced_hazard(arg)
        if hazard:
            flag(rel, call.lineno,
                 f"{hazard} at traced position {i} of jit entry "
                 f"{entry.describe()} — it keys a different executable "
                 f"than the array the other call sites pass (the "
                 f"recompile sentinel fires one miss per variant); wrap "
                 f"in jnp.asarray at the boundary or declare the "
                 f"position static")
    for kw in call.keywords:
        if kw.arg is None:
            continue
        if kw.arg in entry.static_names:
            unstable = _set_ordered(kw.value)
            if unstable:
                flag(rel, call.lineno,
                     f"static_argnames value `{kw.arg}` of jit entry "
                     f"{entry.describe()} is derived from set iteration "
                     f"order ({unstable}) — canonicalize with "
                     f"sorted(...)")
            continue
        hazard = _traced_hazard(kw.value)
        if hazard:
            flag(rel, call.lineno,
                 f"{hazard} at traced keyword `{kw.arg}` of jit entry "
                 f"{entry.describe()} — wrap in jnp.asarray or declare "
                 f"it static")


def _traced_hazard(arg: ast.AST) -> Optional[str]:
    """Why this argument destabilizes the signature at a traced position,
    or None. Scalar literals only — a variable may well hold an array."""
    if isinstance(arg, ast.Constant) and type(arg.value) in (int, float,
                                                             bool):
        return f"python scalar literal {arg.value!r}"
    if isinstance(arg, (ast.Set, ast.SetComp)):
        return "set display"
    if isinstance(arg, ast.Call):
        tail = (dotted(arg.func) or "").split(".")[-1]
        if tail == "set":
            return "set(...) value"
    return None


def _set_ordered(expr: ast.AST) -> Optional[str]:
    """An expression whose VALUE depends on set iteration order:
    tuple(<set>)/list(<set>) or a bare set-ish. sorted(...) is stable."""
    if isinstance(expr, (ast.Set, ast.SetComp)):
        return "a set display"
    if isinstance(expr, ast.Call):
        tail = (dotted(expr.func) or "").split(".")[-1]
        if tail == "set":
            return "set(...)"
        if tail in ("tuple", "list") and expr.args:
            inner = expr.args[0]
            if isinstance(inner, (ast.Set, ast.SetComp)):
                return f"{tail}(<set display>)"
            if isinstance(inner, ast.Call):
                itail = (dotted(inner.func) or "").split(".")[-1]
                if itail == "set":
                    return f"{tail}(set(...))"
    return None


def _module_mutables(tree: ast.Module) -> Set[str]:
    """Module-level names bound to mutable containers."""
    out: Set[str] = set()
    for stmt in tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        v = stmt.value
        mutable = isinstance(v, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                                 ast.DictComp, ast.SetComp))
        if isinstance(v, ast.Call):
            tail = (dotted(v.func) or "").split(".")[-1]
            mutable = tail in ("list", "dict", "set", "defaultdict",
                               "OrderedDict", "deque")
        if not mutable:
            continue
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
    return out


def _assigned_names(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    args = getattr(fn, "args", None)
    if args is not None:
        out |= {a.arg for a in args.posonlyargs + args.args
                + args.kwonlyargs}
        if args.vararg:
            out.add(args.vararg.arg)
        if args.kwarg:
            out.add(args.kwarg.arg)
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            out.add(sub.id)
    return out
