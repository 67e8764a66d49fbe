"""hidden-sync: implicit host round trips on serve-path modules.

The serving budget is "2 dispatches + 2 fetches per retrieve→rerank call"
(ops/dispatch_counter.py proves it at runtime; README serving docs).  A
single stray ``float(score)`` on a device array, an un-``submit``ted
``predict`` call, or a ``block_until_ready`` quietly adds a host sync
to every serve — and nothing fails, it just gets slower.
This rule makes those host round trips lexically visible in the modules
marked serve-path (``# pathway: serve-path`` marker, plus the default
list in core.py).

Checks, per function scope:

- **blocking dispatch+sync**: a scope that both dispatches a jitted call
  and coerces its result to host (``np.asarray``/``float``/``int``/
  ``.item()``) is a synchronous round trip.  The sanctioned pattern is
  submit/complete: dispatch in one scope, fetch inside the completion
  closure (closures are separate scopes, so the async pattern is clean);
- **``.block_until_ready()``** anywhere on a serve path — latency fences
  belong in bench/tests, not serving code;
- **un-``submit``ted ``predict``**: ``.predict(...)`` blocks on its
  result; serve paths must use ``.submit(...)`` and complete later;
- **budget accounting** (only in modules that import the dispatch
  counter): a scope that dispatches a jitted call must call
  ``record_dispatch``, and a scope that fetches (host coercion of a
  device value) must call ``record_fetch`` — otherwise the runtime
  dispatch/fetch assertion silently under-counts and the "two round
  trips" claim stops being ground truth.

  **Cache-wrapper exemption** (pathway_tpu/cache): a scope named
  ``_cached_*`` / ``get_or_*`` wraps its dispatch behind a cache lookup
  — the launch fires only on a miss and is booked inside the CALLER's
  logical dispatch group (``record_dispatch(tag, shards=<launches>)``),
  so the budget checks skip wrapper scopes.  A cache lookup guarding a
  dispatch is not a hidden sync; the blocking dispatch+sync check and
  every lock-discipline check still apply inside wrappers.
- **fan-out width** (only in budget modules): a scope that fans stream
  I/O out in a loop — the partitioned fabric's scatter-gather
  (serve/fabric.py ``fabric.scatter``/``fabric.gather``), same shape as
  the sharded index's per-shard launches — and books dispatches must
  declare the physical width on the booking
  (``record_dispatch(tag, shards=N)``, 1 logical + N physical).
  Booking an H-way scatter without ``shards=`` records one physical
  send and the runtime shard-dispatch counters silently under-count by
  H−1.  See ``registry.is_dispatch_booking`` /
  ``registry.booking_declares_fanout`` for the convention.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from .core import ModuleContext, Rule
from .registry import (
    booking_declares_fanout,
    dotted_name,
    is_cache_wrapper,
    is_device_value_arg,
    is_device_value_base,
    is_jit_call,
    is_stream_io,
    scope_jit_and_device_vars,
    walk_scope,
)

__all__ = ["HiddenSyncRule"]

_COERCIONS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
              "float", "int", "jax.device_get"}


class HiddenSyncRule(Rule):
    name = "hidden-sync"
    salt_sources = ("hidden_sync.py",)
    description = (
        "implicit host sync / unaccounted dispatch on a serve-path module"
    )

    def run(self, ctx: ModuleContext) -> None:
        if not ctx.serve_path:
            return
        self._budget_module = (
            "record_dispatch" in ctx.source or "record_fetch" in ctx.source
        )
        self._visit_scope(ctx, ctx.tree, None, None)

    def _visit_scope(self, ctx, scope, inherited_fns, inherited_vars) -> None:
        jit_fns, device_vars = scope_jit_and_device_vars(
            scope, ctx.jit_names, inherited_fns, inherited_vars
        )
        if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._check_scope(ctx, scope, jit_fns, device_vars)
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

    def _check_scope(self, ctx, scope, jit_fns, device_vars) -> None:
        # jitted functions themselves run ON device; their bodies are not
        # host code (np/float inside them is trace-time, not a sync)
        if scope.name in ctx.jit_names:
            return
        # cache wrappers (_cached_* / get_or_*): the miss-path dispatch
        # is accounted by the caller's dispatch group, so the BUDGET
        # checks below are waived — sync-in-scope checks still apply
        cache_wrapper = is_cache_wrapper(scope.name)
        dispatches: List[ast.Call] = []
        syncs: List[Tuple[ast.Call, str]] = []
        bookings: List[ast.Call] = []
        has_record_dispatch = False
        has_record_fetch = False
        for node in walk_scope(scope):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            leaf = callee.rsplit(".", 1)[-1] if callee else ""
            if leaf == "record_dispatch":
                has_record_dispatch = True
                bookings.append(node)
            elif leaf == "record_fetch":
                has_record_fetch = True
                bookings.append(node)
            elif is_jit_call(node, jit_fns):
                dispatches.append(node)
            elif leaf == "block_until_ready":
                ctx.report(
                    self.name, node,
                    f"`{callee}()` on a serve path — a blocking device "
                    "fence is a host sync per call; fences belong in "
                    "bench/tests",
                )
            elif leaf == "predict" and isinstance(node.func, ast.Attribute):
                ctx.report(
                    self.name, node,
                    f"blocking `{callee}(...)` on a serve path — use "
                    "`.submit(...)` and complete asynchronously so "
                    "consecutive serves pipeline",
                )
            elif callee in _COERCIONS and is_device_value_arg(
                node, jit_fns, device_vars
            ):
                syncs.append((node, callee))
            elif leaf == "item" and is_device_value_base(node, device_vars):
                syncs.append((node, callee or ".item"))
        for node, callee in syncs:
            if dispatches:
                ctx.report(
                    self.name, node,
                    f"`{callee}` of a device value in the same scope that "
                    "dispatched it — a synchronous round trip; move the "
                    "fetch into a completion closure (submit/complete)",
                )
            elif self._budget_module and not has_record_fetch and not cache_wrapper:
                ctx.report(
                    self.name, node,
                    f"`{callee}` fetches a device value but the scope "
                    "never calls record_fetch — the serving fetch budget "
                    "under-counts this round trip",
                )
        if cache_wrapper:
            return
        # fan-out width: a booked scope whose stream I/O fans out in a
        # loop (the scatter-gather shape) must declare the physical
        # width on the booking — record_dispatch(tag, shards=N)
        if self._budget_module and bookings and not any(
            booking_declares_fanout(b) for b in bookings
        ):
            fanned = self._loop_stream_io(scope)
            if fanned is not None:
                ctx.report(
                    self.name, bookings[0],
                    f"stream fan-out (`{fanned}` inside a loop) booked "
                    "without its physical width — book the scatter as "
                    "record_dispatch(tag, shards=N) / record_fetch(tag, "
                    "shards=N) so the budget stays 1 logical + N physical",
                )
        if self._budget_module and dispatches and not has_record_dispatch:
            for node in dispatches:
                ctx.report(
                    self.name, node,
                    "jitted dispatch without record_dispatch in scope — "
                    "the serving dispatch budget under-counts this launch",
                )

    @staticmethod
    def _loop_stream_io(scope) -> Optional[str]:
        """The dotted spelling of the first stream I/O call lexically
        inside a loop of this scope (nested defs excluded), or None."""
        for node in walk_scope(scope):
            if not isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                continue
            for inner in walk_scope(node):
                if isinstance(inner, ast.Call):
                    spelled = is_stream_io(inner)
                    if spelled:
                        return spelled
        return None
