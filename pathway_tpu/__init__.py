"""pathway_tpu — a TPU-native live-data framework.

A from-scratch reimplementation of the capabilities of Pathway
(reference: /root/reference, v0.16.2 — incremental streaming dataflow with a
Python table API, connectors, persistence, and an LLM/RAG xpack), designed
for JAX/XLA on TPU: columnar micro-batch deltas, batched jit ML UDFs, and a
mesh-sharded live vector index (see SURVEY.md).

Usage mirrors the reference's ``import pathway as pw`` surface::

    import pathway_tpu as pw

    t = pw.debug.table_from_markdown(...)
    out = t.filter(pw.this.x > 0).groupby(pw.this.k).reduce(
        k=pw.this.k, s=pw.reducers.sum(pw.this.x))
    pw.debug.compute_and_print(out)
"""

from __future__ import annotations

# the runtime lock-order sanitizer must patch the threading constructors
# BEFORE any pathway module creates its locks — this import chain is
# where they all get created, so the hook runs first.  The knob registry
# is pure stdlib and import-cycle-free, so it loads before everything;
# the analysis package (six modules) loads only when the knob is ON.
from . import config

# persistent XLA compile cache, placed from outside: JAX reads
# JAX_COMPILATION_CACHE_DIR itself and nothing here touches it then.
# Without it the cache sits at a fixed path in the checkout — the path is
# part of the cache key, so a temp name would never hit — and keeps every
# executable, not only those that took JAX's default 1 s to compile: a
# compile time near that threshold would make two runs of one program
# cache different sets.  This is the only place the directory is set;
# every entry point (cli, bench phase children, chip_smoke) passes
# through this import.
import os as _os

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import jax as _jax
    from pathlib import Path as _Path

    _checkout = _Path(__file__).resolve().parents[1]
    _jax.config.update("jax_compilation_cache_dir", str(_checkout / ".jax_cache"))
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

if config.get("analysis.lock_sanitizer"):
    from .analysis.sanitizer import install as _sanitizer_install

    _sanitizer_install()

from .internals import dtype as dt
from .internals import api_reducers as reducers
from .internals.expression import (
    ApplyExpression,
    AsyncApplyExpression,
    CoalesceExpression,
    ColumnExpression,
    ColumnReference,
    IfElseExpression,
    MakeTupleExpression,
    RequireExpression,
)
from .internals.keys import Pointer, ref_scalar
from .internals.parse_graph import G
from .internals.run import run, run_all
from .internals.schema import (
    ColumnDefinition,
    Schema,
    SchemaProperties,
    column_definition,
    schema_builder,
    schema_from_csv,
    schema_from_dict,
    schema_from_types,
)
from .internals.table import (
    GroupedJoinResult,
    GroupedTable,
    Joinable,
    JoinMode,
    JoinResult,
    Table,
    TableLike,
    TableSlice,
)
from .internals.thisclass import left, right, this
from .internals.universe import Universe
from .internals.py_object_wrapper import PyObjectWrapper, wrap_py_object
from .internals.interactive import LiveTable, enable_interactive_mode

# submodules
from . import debug  # noqa: E402
from . import demo  # noqa: E402
from . import io  # noqa: E402
from . import universes  # noqa: E402
from .internals import udfs  # noqa: E402
from .internals.udfs import UDF, udf, udf_async  # noqa: E402
from .internals.yaml_loader import load_yaml  # noqa: E402
from .internals.export_import import ExportedTable, export_table, import_table  # noqa: E402
from .internals.sql import sql  # noqa: E402
from .internals.config import (  # noqa: E402
    PathwayConfig,
    get_config,
    set_license_key,
    set_monitoring_config,
)
from .internals.monitoring import MonitoringLevel  # noqa: E402
from .internals.api_reducers import BaseCustomAccumulator  # noqa: E402
from . import persistence  # noqa: E402
from .persistence import PersistenceMode  # noqa: E402
from . import parallel  # noqa: E402
from . import robust  # noqa: E402
from . import serve  # noqa: E402
from . import stdlib  # noqa: E402
from .stdlib import (  # noqa: E402
    graphs,
    indexing,
    ml,
    ordered,
    stateful,
    statistical,
    temporal,
    utils,
    viz,
)
from .stdlib.temporal import (  # noqa: E402
    AsofJoinResult,
    IntervalJoinResult,
    WindowJoinResult,
    asof_join,
    interval_join,
    window_join,
    windowby,
)
from .stdlib.utils.async_transformer import AsyncTransformer  # noqa: E402
from .stdlib.utils.pandas_transformer import pandas_transformer  # noqa: E402

# deprecated aliases kept for reference compatibility (pathway.asynchronous,
# UDFSync/UDFAsync pre-date the unified pw.UDF)
UDFSync = UDF
UDFAsync = UDF

__version__ = "0.1.0"


def reset() -> None:
    """Clear the global computation graph (fresh build)."""
    G.clear()
    from .internals.error_log import clear_error_log, reset_local_sinks

    clear_error_log()
    reset_local_sinks()
    from .internals.export_import import close_all_exports

    close_all_exports()
    from .internals.universe_solver import get_solver

    get_solver().clear()


def global_error_log() -> list:
    """Row-level errors recorded this run (reference pw.global_error_log —
    error-log table routing, src/engine/error.rs:337); see
    internals/error_log.py."""
    from .internals.error_log import global_error_log as _gel

    return _gel()


def local_error_log():
    """Context manager capturing errors raised while open (reference
    pw.local_error_log, internals/errors.py:13)."""
    from .internals.error_log import local_error_log as _lel

    return _lel()


# ---------------------------------------------------------------------------
# free functions of the pw.* namespace
# ---------------------------------------------------------------------------

def apply(fun, *args, **kwargs) -> ApplyExpression:
    """Row-wise python function application (reference pw.apply)."""
    return ApplyExpression(fun, None, args=args, kwargs=kwargs)


def apply_with_type(fun, ret_type, *args, **kwargs) -> ApplyExpression:
    return ApplyExpression(fun, ret_type, args=args, kwargs=kwargs)


def apply_async(fun, *args, **kwargs) -> AsyncApplyExpression:
    return AsyncApplyExpression(fun, None, args=args, kwargs=kwargs)


def if_else(if_clause, then_clause, else_clause) -> IfElseExpression:
    return IfElseExpression(if_clause, then_clause, else_clause)


def coalesce(*args) -> CoalesceExpression:
    return CoalesceExpression(*args)


def require(val, *args) -> RequireExpression:
    return RequireExpression(val, *args)


def make_tuple(*args) -> MakeTupleExpression:
    return MakeTupleExpression(*args)


def cast(target_type, expr):
    from .internals.expression import CastExpression

    return CastExpression(expr, target_type)


def declare_type(target_type, col):
    """Retype a column in the schema only; values pass through unchanged
    (reference internals/common.py:215)."""
    from .internals.expression import DeclareTypeExpression

    return DeclareTypeExpression(col, target_type)


def fill_error(col, replacement):
    """Replace Error cells with ``replacement`` per row (reference
    internals/common.py:438; Error cells: internals/error_value.py)."""
    from .internals.expression import FillErrorExpression

    return FillErrorExpression(col, replacement)


# free-function flavors of the Table/Joinable methods (reference
# internals/table.py:2574 `groupby`, internals/joins.py:1163 `join_inner` …)

def join(left_table, right_table, *on, id=None, how=JoinMode.INNER) -> JoinResult:
    return left_table.join(right_table, *on, id=id, how=how)


def join_inner(left_table, right_table, *on, id=None) -> JoinResult:
    return left_table.join_inner(right_table, *on, id=id)


def join_left(left_table, right_table, *on, id=None) -> JoinResult:
    return left_table.join_left(right_table, *on, id=id)


def join_right(left_table, right_table, *on, id=None) -> JoinResult:
    return left_table.join_right(right_table, *on, id=id)


def join_outer(left_table, right_table, *on, id=None) -> JoinResult:
    return left_table.join_outer(right_table, *on, id=id)


def groupby(grouped, *args, **kwargs):
    return grouped.groupby(*args, **kwargs)


def unwrap(expr):
    from .internals.expression import smart_coerce

    return smart_coerce(expr)


def assert_table_has_schema(table, schema, *, allow_superset=False) -> None:
    th = table.typehints()
    for name in schema.column_names():
        if name not in th:
            raise AssertionError(f"column {name} missing from table")
    if not allow_superset:
        extra = set(th) - set(schema.column_names())
        if extra:
            raise AssertionError(f"unexpected columns: {extra}")


def table_transformer(fn=None, **kwargs):
    """Decorator marking a Table→Table transformer (typing sugar)."""

    def wrap(f):
        return f

    return wrap(fn) if fn is not None else wrap


from .internals.iterate import iterate, iterate_universe  # noqa: E402
from .internals.row_transformer import (  # noqa: E402
    ClassArg,
    attribute,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)


# Heavy subpackages (flax model zoo, LLM xpack, device kernels) load lazily
# so plain ETL pipelines don't pay the model-stack import cost (PEP 562);
# `asynchronous` is lazy so its DeprecationWarning only fires on use.
_LAZY_SUBMODULES = ("xpacks", "models", "ops", "asynchronous")


def __getattr__(name: str):
    if name in _LAZY_SUBMODULES:
        import importlib

        module = importlib.import_module(f".{name}", __name__)
        globals()[name] = module
        return module
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# Type aliases exposed like reference pw.* (DateTime*/Duration are plain
# datetime types — engine columns hold them natively, dtype.py:107-109)
import datetime as _datetime  # noqa: E402

Json = dt.JSON
Pointer_ = Pointer
DateTimeNaive = _datetime.datetime
DateTimeUtc = _datetime.datetime
Duration = _datetime.timedelta
# pw.Type — the reference's engine type vocabulary (engine.pyi:33)
Type = dt.PathwayType
# outer joins return a JoinResult here; the reference's docstrings call that
# an "OuterJoinResult object" (internals/joins.py:393) and its __all__ lists
# the name without ever defining it — alias for drop-in compat. (`window`,
# the other stale reference __all__ entry, is deliberately NOT provided.)
OuterJoinResult = JoinResult
