"""Optimize() entry point (ref: planner.Optimize -> logical rules -> cost
based physical search; here rules + deterministic lowering)."""

from __future__ import annotations

from typing import Callable, Optional

from tidb_tpu.parser import ast as A
from tidb_tpu.planner.binder import Binder
from tidb_tpu.planner.logical import BuildContext, build_select
from tidb_tpu.planner.physical import (
    PhysicalPlan,
    PTopN,
    inject_point_get,
    lower,
    resolve_topn_pushdown,
)
from tidb_tpu.planner.rules import optimize_logical
from tidb_tpu.utils import tracing

__all__ = ["plan_statement"]


def plan_statement(
    stmt,
    catalog,
    db: str = "test",
    execute_subplan: Optional[Callable] = None,
    cascades: bool = False,
    n_parts: int = 1,
    session_info: Optional[dict] = None,
    agg_push_down: bool = True,
    scalar_joins: bool = False,
) -> PhysicalPlan:
    """SELECT/UNION AST -> optimized physical plan. `scalar_joins`: an
    uncorrelated scalar aggregate subquery in WHERE becomes a join with
    its one row instead of a literal (logical._scalar_subquery_join)."""
    assert isinstance(stmt, (A.SelectStmt, A.UnionStmt)), type(stmt)
    binder = Binder()
    binder.session_info = dict(session_info or {}, db=db)
    ctx = BuildContext(
        catalog=catalog, db=db, binder=binder, execute_subplan=execute_subplan,
        scalar_joins=scalar_joins)
    # the three parts of the caller's span (``session.plan``), by name
    with tracing.phase("bind"):
        logical = build_select(stmt, ctx)
    with tracing.phase("rules"):
        logical = optimize_logical(
            logical, hints=getattr(stmt, "hints", ()) or (),
            cascades=cascades, n_parts=n_parts, agg_push_down=agg_push_down)
    with tracing.phase("lower"):
        phys = inject_point_get(lower(logical))
        if n_parts > 1:
            _annotate_topn(phys)
    return phys


def _annotate_topn(plan: PhysicalPlan) -> None:
    """Mark TopN nodes whose sort keys resolve onto a distributable
    generic agg below (per-shard partial top-k; SURVEY.md:93). The
    dist builder consumes the descriptor; EXPLAIN shows the intent."""
    if isinstance(plan, PTopN):
        plan.pushdown = resolve_topn_pushdown(plan)
    for c in plan.children:
        _annotate_topn(c)
