"""tracing-hazard: Python-side effects inside traced JAX code.

Encodes the backend-detection bug class (PR 3): calling
``jax.default_backend()`` inside a jitted function returns the backend
captured at *trace* time and silently bakes it into the compiled
artifact; the interpret-mode fallback it guarded then never triggers on
CPU.  Same family: ``bool(tracer)`` / ``tracer.item()`` raise
``ConcretizationTypeError`` only on the first real trace, and 64-bit
literals inside kernel bodies down-cast silently unless ``enable_x64``
is managed explicitly.

Scope: ``src/repro/kernels/`` and ``src/repro/serving/jaxengine/``.
Traced bodies are discovered syntactically:

* functions decorated ``@jax.jit`` / ``@partial(jax.jit, ...)`` /
  ``@jit``;
* functions wrapped at assignment time (``f = jax.jit(g)``,
  ``f = functools.partial(jax.jit, ...)(g)``);
* kernel functions handed to ``pl.pallas_call`` / ``pallas_call``;
* function arguments of ``lax.scan`` / ``lax.while_loop`` /
  ``lax.fori_loop`` / ``lax.cond`` / ``jax.vmap``;
* plus a fix-point closure over module-local helpers called from any
  traced body (a hazard two calls deep still fires at trace time).

Hazards flagged inside traced bodies:

* ``jax.default_backend()`` / ``jax.devices()`` /
  ``jax.local_devices()`` — trace-time constants masquerading as
  runtime queries; hoist to the un-jitted wrapper and pass the result
  as a static argument;
* ``bool(x)`` / ``x.item()`` / ``float(x)`` / ``int(x)`` on
  non-literal operands — concretization errors under trace;
* ``np.float64`` / ``np.int64`` / dtype-string ``"float64"`` literals —
  silent down-cast unless the module manages ``enable_x64`` itself (a
  module that mentions ``enable_x64`` is trusted and skipped).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.astutil import FuncDef, call_name, dotted, walk_calls
from repro.analysis.core import Finding, RepoContext, register_rule

RULE = "tracing-hazard"

SCAN_DIRS: Tuple[str, ...] = (
    "src/repro/kernels",
    "src/repro/serving/jaxengine",
)

_JIT_NAMES = {"jax.jit", "jit"}
_PALLAS_NAMES = {"pl.pallas_call", "pallas_call", "jax.experimental.pallas.pallas_call"}
_TRACED_HOFS = {
    "lax.scan": 0, "jax.lax.scan": 0,
    "lax.while_loop": (0, 1), "jax.lax.while_loop": (0, 1),
    "lax.fori_loop": 2, "jax.lax.fori_loop": 2,
    "lax.cond": (1, 2), "jax.lax.cond": (1, 2),
    "jax.vmap": 0, "vmap": 0,
}
_BACKEND_QUERIES = {
    "jax.default_backend", "jax.devices", "jax.local_devices",
    "jax.device_count", "jax.local_device_count",
}
_X64_NAMES = {
    "np.float64", "numpy.float64", "np.int64", "numpy.int64",
    "jnp.float64", "jnp.int64",
}


def _is_jit_decorator(dec: ast.expr) -> bool:
    name = dotted(dec)
    if name in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        cname = call_name(dec) or ""
        if cname in _JIT_NAMES:
            return True
        # functools.partial(jax.jit, static_argnames=...)
        if cname.split(".")[-1] == "partial" and dec.args:
            if dotted(dec.args[0]) in _JIT_NAMES:
                return True
    return False


def _func_ref_names(node: ast.expr) -> List[str]:
    """Local function names referenced by an argument expression."""
    out: List[str] = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.append(sub.id)
    return out


def _collect_traced_roots(tree: ast.AST) -> Set[str]:
    """Names of module-level/local functions whose bodies are traced."""
    roots: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_decorator(d) for d in node.decorator_list):
                roots.add(node.name)
        elif isinstance(node, ast.Call):
            cname = call_name(node) or ""
            if cname in _JIT_NAMES and node.args:
                name = dotted(node.args[0])
                if name:
                    roots.add(name.split(".")[-1])
            elif cname.split(".")[-1] == "partial" and node.args:
                if dotted(node.args[0]) in _JIT_NAMES:
                    for arg in node.args[1:]:
                        name = dotted(arg)
                        if name:
                            roots.add(name.split(".")[-1])
            elif cname in _PALLAS_NAMES and node.args:
                roots.update(_func_ref_names(node.args[0]))
            elif cname in _TRACED_HOFS:
                pos = _TRACED_HOFS[cname]
                positions = pos if isinstance(pos, tuple) else (pos,)
                for p in positions:
                    if p < len(node.args):
                        roots.update(_func_ref_names(node.args[p]))
    return roots


def _function_table(tree: ast.AST) -> Dict[str, FuncDef]:
    out: Dict[str, FuncDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # first definition wins; shadowing is rare in these modules
            out.setdefault(node.name, node)
    return out


def _closure(roots: Set[str], table: Dict[str, FuncDef]) -> Set[str]:
    """Fix-point: helpers called from traced bodies are traced too."""
    traced = set(roots)
    changed = True
    while changed:
        changed = False
        for name in list(traced):
            fn = table.get(name)
            if fn is None:
                continue
            for call in walk_calls(fn):
                cname = call_name(call)
                if cname and cname in table and cname not in traced:
                    traced.add(cname)
                    changed = True
    return traced


def _is_literal(node: ast.expr) -> bool:
    try:
        ast.literal_eval(node)
        return True
    except (ValueError, SyntaxError, TypeError):
        return False


def _body_findings(
    path: str, fn: FuncDef, check_x64: bool
) -> List[Finding]:
    out: List[Finding] = []
    # inner defs have their own entry in the traced set; skip their bodies
    inner = {
        n for sub in ast.walk(fn)
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
        and sub is not fn
        for n in [sub.name]
    }

    def nodes():
        skip: Set[int] = set()
        for sub in ast.walk(fn):
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub is not fn and sub.name in inner
            ):
                skip.update(id(s) for s in ast.walk(sub) if s is not sub)
            if id(sub) not in skip:
                yield sub

    for node in nodes():
        if isinstance(node, ast.Call):
            cname = call_name(node) or ""
            if cname in _BACKEND_QUERIES:
                out.append(Finding(
                    rule=RULE, path=path, line=node.lineno, symbol=fn.name,
                    message=f"{cname}() inside traced function "
                            f"{fn.name!r} is evaluated at trace time and "
                            "baked into the compiled artifact",
                    hint="query the backend in the un-jitted wrapper and "
                         "pass the answer in via static_argnames",
                ))
            elif cname in {"bool", "float", "int"} and node.args and not (
                _is_literal(node.args[0])
            ):
                out.append(Finding(
                    rule=RULE, path=path, line=node.lineno, symbol=fn.name,
                    message=f"{cname}() on a traced value inside "
                            f"{fn.name!r} concretizes the tracer — "
                            "ConcretizationTypeError on first real trace",
                    hint="keep the value abstract (jnp.where/lax.cond) or "
                         "mark the argument static",
                ))
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"
                and not node.args
            ):
                out.append(Finding(
                    rule=RULE, path=path, line=node.lineno, symbol=fn.name,
                    message=f".item() inside traced function {fn.name!r} "
                            "forces a device sync / concretization under "
                            "trace",
                    hint="return the array and call .item() outside the "
                         "jitted region",
                ))
        if check_x64:
            name = dotted(node) if isinstance(node, ast.Attribute) else None
            if name in _X64_NAMES:
                out.append(Finding(
                    rule=RULE, path=path, line=node.lineno, symbol=fn.name,
                    message=f"{name} inside traced function {fn.name!r}: "
                            "without enable_x64 JAX silently down-casts "
                            "to 32-bit",
                    hint="use 32-bit dtypes, or scope 64-bit mode "
                         "explicitly with jax.enable_x64(True)",
                ))
    return out


@register_rule(
    RULE,
    "no backend queries, tracer concretization, or unmanaged 64-bit "
    "literals inside jitted/pallas/scan bodies in kernels/ and jaxengine/",
)
def run(ctx: RepoContext) -> List[Finding]:
    findings: List[Finding] = []
    for d in SCAN_DIRS:
        for path in ctx.py_files(d):
            tree = ctx.tree(path)
            if tree is None:
                continue
            src = ctx.source(path) or ""
            check_x64 = "enable_x64" not in src
            table = _function_table(tree)
            traced = _closure(_collect_traced_roots(tree), table)
            for name in sorted(traced):
                fn = table.get(name)
                if fn is not None:
                    findings += _body_findings(path, fn, check_x64)
    findings.sort(key=lambda f: (f.path, f.line, f.symbol))
    return findings
