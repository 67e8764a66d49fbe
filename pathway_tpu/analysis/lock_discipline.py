"""lock-discipline: no device work or GIL-holding C calls under a lock.

The round-5 bug class: ``ops/ivf.py`` ran a device matmul + host fetch
inside ``add()``'s lock section (every concurrent ``search``/``submit``
stalled for the whole absorb), and ``parallel/exchange.py`` held the GIL
in one multi-hundred-MB ``pickle.dumps`` so the heartbeat thread starved
and healthy peers were declared dead.  Both are invisible to tests that
don't race the exact schedule — but both are *lexically visible*: a call
with device-dispatch / host-sync / GIL-holding semantics sitting inside a
``with <lock>:`` body.

Flagged inside lock bodies (nested ``def``/``lambda`` bodies excluded —
they execute later, not under the lock):

- calls to jitted functions (module ``jax.jit``/``pjit`` registry +
  cache-getter convention — see ``registry.py``): a dispatch enqueues
  device work and can block in C on a full device queue;
- ``.block_until_ready()`` — an unbounded host sync;
- ``jax.device_put`` / ``jax.device_get`` — blocking transfers;
- ``np.asarray``/``np.array``/``float``/``int``/``.item()`` on a value
  produced by a jitted call — an implicit device→host sync;
- ``pickle.dumps`` / ``pickle.loads`` / ``Pickler.dump`` /
  ``Unpickler.load`` — one GIL-holding C call for the whole payload;
- completing a serve handle (``handle = <obj>.submit(...)`` then
  ``handle()`` / ``handle.result()`` / ``handle.advance()``) — the
  completion IS the host fetch.  The coalescing scheduler's
  future-handoff contract (serve/scheduler.py) is dispatch on the
  scheduler thread, fetch on the WAITER: blocking on a batch while
  holding the admission lock would stall every admitter for a full
  device round trip;
- serve-cache access (``<*_cache>.get/put/lookup/...`` — the
  pathway_tpu/cache tiers): a cache call takes the tier's own lock and
  fires the ``cache.get``/``cache.put`` chaos sites, which may delay or
  HANG — under a serve lock the fault (or just the tier's contention)
  would stall every admitter instead of only the calling request.  The
  in-flight ownership pattern (persistence/object_cache.py
  ``get_or_compute``) is the sanctioned shape: the global lock guards
  only the owner dict; compute, backend I/O and pickling run off it;
- stream network I/O (``<stream|link|peer|conn>.send/.recv/
  .send_request`` — the fabric/exchange convention): a frame send can
  stall for a full heartbeat timeout on a congested peer and fires the
  ``fabric.send``/``fabric.recv`` chaos sites.  The sanctioned shape is
  serve/fabric.py's swap-under-lock / I/O-off-lock discipline.

And the INVERSE scope check on serve-path modules: a span opened as a
context manager (``with observe.span(...):`` — observe/spans.py, the
program's one bracket — or any ``.span`` / ``start_span`` /
``span_timer`` of an OTLP-style tracer) whose body ACQUIRES a lock.
Spans time *work*, not lock waits — a span held across ``with <lock>:``
silently folds queue contention into the stage it claims to measure,
which is exactly the mis-attribution per-request tracing exists to kill.
The order on the serve paths is therefore lock-then-span: take the lock,
then open ``observe.span``; the wait for the lock is measured as an
``observe.interval`` of its own, from clock reads taken around it.

Deliberate cases (e.g. a dispatch-only launch under the lock that
snapshots device state consistently and never blocks on the result) are
suppressed at the ``with`` statement with a reviewed reason:
``with self._lock:  # pathway: allow(lock-discipline): <why it is safe>``
"""

from __future__ import annotations

import ast
import re
from typing import Set

from .core import ModuleContext, Rule
from .registry import (
    dotted_name,
    is_cache_access,
    is_device_value_arg,
    is_device_value_base,
    is_handle_fetch,
    is_jit_call,
    is_lock_context,
    is_observability_callback,
    is_stream_io,
    scope_handle_vars,
    scope_jit_and_device_vars,
    walk_scope,
)

__all__ = ["LockDisciplineRule"]

_TRANSFER_CALLS = {
    "jax.device_put": "host→device transfer",
    "jax.device_get": "device→host sync",
}
_PICKLE_CALLS = {
    "pickle.dumps", "pickle.loads", "pickle.dump", "pickle.load",
    "marshal.dumps", "marshal.loads",
}
_COERCIONS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
              "float", "int"}
# span-opening context managers, by the callee's last name: the program's
# own `with observe.span(...)` (observe/spans.py) and OTLP-style APIs
# (`with tracer.span(...)`, `with tracer.start_span(...)`, span timers)
_SPAN_CM_LEAVES = {"span", "start_span", "span_timer"}


def _is_span_context(with_node: ast.With) -> bool:
    """``with <something>.span(...):`` / ``start_span`` / ``span_timer``
    — a context manager that TIMES its body as a trace span."""
    return _span_item_index(with_node) is not None


def _span_item_index(with_node: ast.With):
    """Index of the first span-opening item in the with statement, or
    None."""
    for i, item in enumerate(with_node.items):
        expr = item.context_expr
        if not isinstance(expr, ast.Call):
            continue
        callee = dotted_name(expr.func)
        if callee is None:
            continue
        if callee.rsplit(".", 1)[-1] in _SPAN_CM_LEAVES:
            return i
    return None


def _lock_item_index(with_node: ast.With):
    """Index of the first lock item in the with statement, or None."""
    for i, item in enumerate(with_node.items):
        name = dotted_name(item.context_expr)
        if name and _LOCK_ITEM_RE.search(name.rsplit(".", 1)[-1]):
            return i
    return None


# mirrors registry.is_lock_context's name heuristic, applied per item so
# the combined `with tracer.span(...), self._lock:` form resolves with
# ITEM ORDER (span before lock = the lock wait is timed)
_LOCK_ITEM_RE = re.compile(r"lock|mutex|cv\b|cond", re.IGNORECASE)


class LockDisciplineRule(Rule):
    name = "lock-discipline"
    salt_sources = ("lock_discipline.py",)
    description = (
        "device dispatch / host sync / GIL-holding C call inside a "
        "`with <lock>:` body"
    )

    def run(self, ctx: ModuleContext) -> None:
        # map each function scope to its (jit callables, device vars,
        # serve handles), inheriting through closures so `with` bodies
        # resolve names bound by the enclosing function
        scope_envs = {}

        def visit_scope(scope, inherited_fns, inherited_vars, inherited_handles):
            fns, dvars = scope_jit_and_device_vars(
                scope, ctx.jit_names, inherited_fns, inherited_vars
            )
            handles = scope_handle_vars(scope, inherited_handles)
            scope_envs[scope] = (fns, dvars, handles)
            # walk_scope stops at nested defs; recurse into them explicitly
            # so closures inherit the enclosing scope's environment
            for child in ast.iter_child_nodes(scope):
                self._recurse_defs(child, fns, dvars, handles, visit_scope)

        visit_scope(ctx.tree, None, None, None)

        for scope, (jit_fns, device_vars, handles) in scope_envs.items():
            for node in walk_scope(scope):
                if isinstance(node, ast.With) and is_lock_context(node):
                    self._check_lock_body(ctx, node, jit_fns, device_vars, handles)

        # the inverse scope check (serve-path modules): a span context
        # manager whose body acquires a lock times the lock WAIT as if
        # it were stage work
        if ctx.serve_path:
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.With) and _is_span_context(node):
                    self._check_span_body(ctx, node)

    def _check_span_body(self, ctx: ModuleContext, span_node: ast.With) -> None:
        message = (
            "trace span opened across a `with <lock>:` boundary on "
            "a serve-path module — spans time WORK, not lock waits; "
            "take the lock first and open observe.span inside it, and "
            "record the wait as its own observe.interval(name, t0, t1)"
        )
        # combined single-statement form: `with tracer.span(...),
        # self._lock:` acquires the lock INSIDE the span timing when the
        # span item comes first (`with self._lock, tracer.span(...)` is
        # the nested span-under-lock shape, which is allowed)
        span_i = _span_item_index(span_node)
        lock_i = _lock_item_index(span_node)
        if lock_i is not None and span_i is not None and span_i < lock_i:
            ctx.report(self.name, span_node, message)
            return
        for inner in walk_scope(span_node):
            if isinstance(inner, ast.With) and is_lock_context(inner):
                ctx.report(self.name, span_node, message)
                return

    def _recurse_defs(self, node, fns, dvars, handles, visit_scope) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            visit_scope(node, fns, dvars, handles)
            return
        if isinstance(node, (ast.Lambda,)):
            return
        for child in ast.iter_child_nodes(node):
            self._recurse_defs(child, fns, dvars, handles, visit_scope)

    def _check_lock_body(
        self,
        ctx: ModuleContext,
        with_node: ast.With,
        jit_fns: Set[str],
        device_vars: Set[str],
        handle_vars: Set[str],
    ) -> None:
        for node in walk_scope(with_node):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            leaf = callee.rsplit(".", 1)[-1] if callee else ""
            if is_jit_call(node, jit_fns):
                ctx.report(
                    self.name, node,
                    f"jitted dispatch `{callee}(...)` under lock — device "
                    "work (and a possible C-level block on a full queue) "
                    "while every other thread waits on this lock",
                )
            elif leaf == "block_until_ready":
                ctx.report(
                    self.name, node,
                    f"`{callee}()` under lock — unbounded host sync while "
                    "holding the lock",
                )
            elif callee in _TRANSFER_CALLS:
                ctx.report(
                    self.name, node,
                    f"`{callee}` under lock — {_TRANSFER_CALLS[callee]} "
                    "blocks the lock for a full link round trip",
                )
            elif callee in _PICKLE_CALLS or leaf in ("dump", "load") and (
                callee or ""
            ).split(".", 1)[0].lower().find("pickl") >= 0:
                ctx.report(
                    self.name, node,
                    f"`{callee}` under lock — one GIL-holding C call for "
                    "the whole payload starves every other thread "
                    "(heartbeats included) for its duration",
                )
            elif callee in _COERCIONS and is_device_value_arg(
                node, jit_fns, device_vars
            ):
                ctx.report(
                    self.name, node,
                    f"`{callee}` of a jitted-call result under lock — "
                    "implicit device→host sync while holding the lock",
                )
            elif leaf == "item" and is_device_value_base(node, device_vars):
                ctx.report(
                    self.name, node,
                    "`.item()` on a jitted-call result under lock — "
                    "implicit device→host sync while holding the lock",
                )
            else:
                handle = is_handle_fetch(node, handle_vars)
                cache = is_cache_access(node)
                obs = is_observability_callback(node)
                stream = is_stream_io(node)
                if handle is not None:
                    ctx.report(
                        self.name, node,
                        f"serve handle `{handle}(...)` completed under lock "
                        "— the completion is the host fetch; the "
                        "future-handoff contract is dispatch on the "
                        "scheduler thread, fetch on the WAITER off-lock "
                        "(blocking here stalls every admitter)",
                    )
                elif cache is not None:
                    ctx.report(
                        self.name, node,
                        f"serve-cache access `{cache}(...)` under lock — "
                        "cache calls take the tier's own lock and fire "
                        "the cache.get/cache.put chaos sites (delay/hang);"
                        " keep lookups off the serve locks so a cache "
                        "fault wedges only its own request",
                    )
                elif obs is not None:
                    ctx.report(
                        self.name, node,
                        f"observability callback `{obs}(...)` under lock "
                        "— profiler/ledger/SLO sampling is pull-based by "
                        "design (walks weak registries, fires the "
                        "profile.sample/hbm.ledger/slo.evaluate chaos "
                        "sites, may delay or hang); it belongs on "
                        "scrape/bench threads, never inside a serve-path "
                        "lock where the walk stalls every admitter",
                    )
                elif stream is not None:
                    ctx.report(
                        self.name, node,
                        f"stream network I/O `{stream}(...)` under lock — "
                        "a frame send can stall for a full heartbeat "
                        "timeout on a congested peer and fires the "
                        "fabric.send/fabric.recv chaos sites (delay/hang);"
                        " swap the stream slot under the lock and perform "
                        "the I/O after releasing it (the fabric "
                        "mark_down/close discipline)",
                    )
