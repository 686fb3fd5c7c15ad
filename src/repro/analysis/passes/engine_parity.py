"""engine-parity: a spec field one engine honors, every engine must honor.

The RTT-timeout bug class (PR 7): one serving engine applied the
client-RTT term to timeout expiry while another did not — both
"supported" ``SimSpec.timeout_s`` yet made different decisions from the
same spec.  The cheap, statically checkable proxy for that invariant:
every ``SimSpec`` / ``ServingSpec`` / ``MigrationSpec`` field *consumed*
(attribute read, keyword, parameter) by one engine's file set must be
consumed by the other, or the field must be exempted with a
justification naming the fallback contract.

File sets:

* ``vector`` — ``serving/engine.py``, the reference engine;
* ``jax`` — ``serving/jaxengine/*``, which *inherits* the vector set
  (``JaxServingEngine`` subclasses ``VectorizedServingEngine``, so
  everything the vector engine consumes is consumed on the jax path);
* shared data-plane/migration modules (``serving/token/*``,
  ``migration/planner|runtime``) count for every engine — jax delegates
  token cells to the vector path's token batches and migration runtime.

Because jax inherits vector, what the pass can flag is a field the jax
files consume and the vector engine does not: a knob only the fast
engine honors.

Fields consumed by *no* engine are builder-resolved (horizon, seeds,
engine selection) and are the spec-drift pass's problem, not parity's.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from repro.analysis.astutil import (
    consumed_names,
    dataclass_fields,
)
from repro.analysis.core import Finding, RepoContext, register_rule

RULE = "engine-parity"

#: spec classes whose fields engines consume directly (name -> source file)
SPEC_CLASSES: Dict[str, str] = {
    "SimSpec": "src/repro/service/spec.py",
    "ServingSpec": "src/repro/service/spec.py",
    "MigrationSpec": "src/repro/migration/config.py",
}

ENGINE_FILES: Dict[str, Tuple[str, ...]] = {
    "vector": ("src/repro/serving/engine.py",),
    "jax": (
        "src/repro/serving/jaxengine/engine.py",
        "src/repro/serving/jaxengine/kernel.py",
        "src/repro/serving/jaxengine/schedule.py",
    ),
}

#: engine -> engine whose consumption it inherits (subclass relationship)
ENGINE_INHERITS: Dict[str, str] = {"jax": "vector"}

#: modules shared by every engine's data plane
SHARED_FILES: Tuple[str, ...] = (
    "src/repro/serving/token/batch.py",
    "src/repro/serving/token/config.py",
    "src/repro/serving/token/metrics.py",
    "src/repro/migration/planner.py",
    "src/repro/migration/runtime.py",
)


def _spec_fields(ctx: RepoContext) -> Dict[str, List[Tuple[str, str, int]]]:
    """class name -> [(field, source path, line)] for the spec classes."""
    out: Dict[str, List[Tuple[str, str, int]]] = {}
    for cls_name, path in SPEC_CLASSES.items():
        tree = ctx.tree(path)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name == cls_name:
                out[cls_name] = [
                    (f.target.id, path, f.lineno)  # type: ignore[union-attr]
                    for f in dataclass_fields(node)
                ]
                break
    return out


def _engine_consumption(ctx: RepoContext) -> Dict[str, Set[str]]:
    shared: Set[str] = set()
    for path in SHARED_FILES:
        tree = ctx.tree(path)
        if tree is not None:
            shared |= consumed_names(tree)
    consumed: Dict[str, Set[str]] = {}
    for engine, paths in ENGINE_FILES.items():
        names = set(shared)
        for path in paths:
            tree = ctx.tree(path)
            if tree is not None:
                names |= consumed_names(tree)
        consumed[engine] = names
    for engine, base in ENGINE_INHERITS.items():
        consumed[engine] |= consumed[base]
    return consumed


@register_rule(
    RULE,
    "spec fields consumed by one serving engine must be consumed by all "
    "engines (or carry an exemption naming the fallback contract)",
)
def run(ctx: RepoContext) -> List[Finding]:
    # engines whose files are entirely absent (fixture trees) drop out of
    # the comparison rather than reading as "consumes nothing"
    present = [
        e for e, paths in ENGINE_FILES.items()
        if any(ctx.tree(p) is not None for p in paths)
        or any(
            ctx.tree(p) is not None
            for p in ENGINE_FILES.get(ENGINE_INHERITS.get(e, ""), ())
        )
    ]
    if len(present) < 2:
        return []
    consumed = _engine_consumption(ctx)
    findings: List[Finding] = []
    for cls_name, fields in sorted(_spec_fields(ctx).items()):
        for field, path, line in fields:
            consumers = [e for e in present if field in consumed[e]]
            if not consumers or len(consumers) == len(present):
                continue
            missing = [e for e in present if e not in consumers]
            findings.append(Finding(
                rule=RULE,
                path=path,
                line=line,
                symbol=f"{cls_name}.{field}",
                message=(
                    f"{cls_name}.{field} is consumed by the "
                    f"{'/'.join(consumers)} engine"
                    f"{'s' if len(consumers) > 1 else ''} but not by "
                    f"{'/'.join(missing)} — engines must stay "
                    "decision-identical for every spec knob"
                ),
                hint=(
                    "consume the field on the missing engine path, or add "
                    "an analysis exemption whose justification names the "
                    "documented fallback (e.g. 'token cells delegate to "
                    "the vector data plane')"
                ),
            ))
    return findings
