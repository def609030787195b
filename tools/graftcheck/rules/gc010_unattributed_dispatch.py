"""GC010 — unattributed hot-path dispatch.

The device-time attribution layer (``obs.devprof``) splits every
scheduler node's wall into device / dispatch / transfer / host — but the
dispatch share is only as complete as the ``timed()`` coverage: a public
ops entry point that dispatches jitted programs WITHOUT a ``timed()``
wrapper (or an explicit ``devprof.dispatch_bracket``) books its dispatch
wall as anonymous host time, and the flight recorder loses the op name a
wedged node died in (``last_op: null`` — exactly the field a postmortem
needs).

This rule flags **public module-level functions in ``anovos_tpu/ops/``
that dispatch device programs unattributed**.  Engine v2: both sides of
the test ride the whole-program call graph.  "Dispatches" means the
function's transitive call chain — across module boundaries — reaches

* a jitted callable (``X = jax.jit(f)`` / ``functools.partial(jax.jit,
  ...)`` assignments, ``@jax.jit`` / ``@partial(jax.jit, ...)`` decorated
  defs, anywhere in the repo), or
* ``jax.device_get`` / ``.block_until_ready()`` (a host-blocking fetch
  is the dispatch tail by definition);

the finding anchors at this function's OWN call site that starts the
dispatching chain.  A function is ATTRIBUTED (quiet) when any of:

* it is decorated ``@timed(...)`` (the ``obs.timed`` wrapper);
* it enters ``devprof.dispatch_bracket(...)`` / ``node_bracket(...)``;
* it is a transitive callee of an attributed function — attribution flows
  down REAL call edges, cross-module (helpers under a timed entry point
  must NOT be double-wrapped, that would double-count dispatch);
* it is private (``_``-prefixed — not an entry point).

Deliberate exemptions (cold paths, fit-once model code) go in the
baseline with a justification, as ever.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.graftcheck.registry import FileContext, Rule, register


@register
class UnattributedDispatchRule(Rule):
    id = "GC010"
    title = "public ops entry point dispatches device programs without timed()/devprof attribution"

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("anovos_tpu/ops/") or "gc010" in relpath

    def check(self, ctx: FileContext) -> Iterable:
        attributed = set(ctx.view.get("attributed", ()))
        dispatch = ctx.view.get("dispatch", {})
        for node in ctx.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            name = node.name
            if name.startswith("_") or name in attributed:
                continue
            evidence = dispatch.get(name)
            if evidence is None:
                continue
            line, _desc = evidence
            yield ctx.finding_at(
                self.id, line, name,
                f"public ops entry point {name!r} dispatches device programs "
                "with no timed()/devprof attribution — its dispatch wall "
                "books as anonymous host time and flight-recorder dumps "
                "cannot name it as a node's last op; wrap it in "
                "obs.timed() (or devprof.dispatch_bracket) or baseline "
                "with a justification")
