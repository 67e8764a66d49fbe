"""recompile-hazard: jitted calls fed unbucketed Python-varying shapes.

XLA compiles one executable per distinct argument shape.  A jitted call
whose input shape tracks raw Python data (``len(texts)``, a tail that
grew by one row, an unpadded last chunk) recompiles on every new size —
seconds of XLA time on a latency path that budgets milliseconds.  The
repo-wide discipline is to bucket every host-fed dimension
(``_bucket``/``seg_bucket``/``row_length_bucket``/``pad_packed_rows``)
so each callable compiles a small closed set of signatures.

Lexical check, per function scope: a call to a jitted function with a
``jnp.asarray(...)``/``jnp.array(...)``-converted argument (host data
uploaded at call time — the shape comes from Python-land) in a scope
that never invokes a bucketing helper is flagged.  Scopes that bucket
anywhere cover all their dispatches: the helpers normalize every shape
they touch, and finer data-flow than that is beyond a lexical pass.

The static rule is paired with a runtime tripwire
(``ops/recompile_guard.py``): every compiled-fn cache in the serving
stack counts its distinct signatures and trips past a bound — so a
hazard that slips past the lexical pass still fails loudly under tests
instead of silently recompiling in production.
"""

from __future__ import annotations

import ast
from typing import Set

from .core import ModuleContext, Rule
from .registry import dotted_name, is_jit_call, scope_jit_and_device_vars, walk_scope

__all__ = ["RecompileHazardRule"]

_UPLOAD_CALLS = {"jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.numpy.array"}
_BUCKET_HELPERS = {
    "_bucket", "seg_bucket", "row_length_bucket", "pad_packed_rows",
    # pack + pad to (_bucket(R), row_length_bucket, seg_bucket) in one call
    "pack_padded", "_pack_pairs_padded",
}


class RecompileHazardRule(Rule):
    name = "recompile-hazard"
    salt_sources = ("recompile_hazard.py",)
    description = (
        "jitted call fed jnp.asarray(host data) in a scope with no shape "
        "bucketing — every distinct input size compiles a new executable"
    )

    def run(self, ctx: ModuleContext) -> None:
        self._visit_scope(ctx, ctx.tree, None, None)

    def _visit_scope(self, ctx, scope, inherited_fns, inherited_vars) -> None:
        jit_fns, device_vars = scope_jit_and_device_vars(
            scope, ctx.jit_names, inherited_fns, inherited_vars
        )
        if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._check_scope(ctx, scope, jit_fns)
        for child in ast.iter_child_nodes(scope):
            self._recurse_defs(ctx, child, jit_fns, device_vars)

    def _recurse_defs(self, ctx, node, fns, dvars) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._visit_scope(ctx, node, fns, dvars)
            return
        if isinstance(node, ast.Lambda):
            return
        for child in ast.iter_child_nodes(node):
            self._recurse_defs(ctx, child, fns, dvars)

    def _check_scope(self, ctx, scope, jit_fns: Set[str]) -> None:
        if scope.name in ctx.jit_names:
            return  # the jitted body itself: jnp.asarray there is traced
        buckets = False
        for node in walk_scope(scope):
            if isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                leaf = callee.rsplit(".", 1)[-1] if callee else ""
                if leaf in _BUCKET_HELPERS:
                    buckets = True
                    break
        if buckets:
            return
        for node in walk_scope(scope):
            if not isinstance(node, ast.Call) or not is_jit_call(node, jit_fns):
                continue
            for arg in node.args:
                if (
                    isinstance(arg, ast.Call)
                    and dotted_name(arg.func) in _UPLOAD_CALLS
                ):
                    callee = dotted_name(node.func)
                    ctx.report(
                        self.name, node,
                        f"jitted `{callee}(...)` takes "
                        f"`{dotted_name(arg.func)}(host data)` but the "
                        "scope never buckets shapes — every distinct "
                        "input size recompiles (bucket with _bucket/"
                        "seg_bucket/row_length_bucket/pad_packed_rows, "
                        "or pad to a fixed shape and suppress with the "
                        "reason)",
                    )
                    break
