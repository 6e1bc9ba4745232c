"""executorBuilder (ref: executor/builder.go): physical plan -> executors.

The builder performs pipeline fusion: chains of Selection/Projection above
a TableFullScan collapse into the scan's jitted fragment (stages), so a
scan+filter+project runs as ONE device dispatch per chunk — the shape the
coprocessor gives the reference for free.
"""

from __future__ import annotations

from typing import List

from tidb_tpu.errors import PlanError
from tidb_tpu.executor.aggregate import HashAggExec
from tidb_tpu.executor.base import Executor
from tidb_tpu.executor.join import HashJoinExec, IndexJoinExec
from tidb_tpu.executor.scan import ProjectionExec, SelectionExec, TableScanExec
from tidb_tpu.executor.sort import LimitExec, SortExec, TopNExec, UnionExec
from tidb_tpu.planner.physical import (
    PHashAgg,
    PHashJoin,
    PIndexJoin,
    PIndexRangeScan,
    PPartitionScan,
    PLimit,
    PProjection,
    PPointGet,
    PScan,
    PSelection,
    PSort,
    PTopN,
    PUnion,
    PWindow,
    PhysicalPlan,
)

__all__ = ["build_executor", "peel_stages", "scan_stages_for"]


def peel_stages(plan: PhysicalPlan):
    """Strip the fusible Selection/Projection chain off `plan`.

    Returns (stages, base): stages in execution order, base the first
    non-fusible node. Shared by the single-chip fusion below and the
    distributed fragment matcher (parallel/executor.py) so both tiers
    always fuse the same plan shapes."""
    rev, base = [], plan
    while True:
        if isinstance(base, PSelection):
            rev.append(("filter", base.cond))
            base = base.child
        elif isinstance(base, PProjection):
            rev.append(("project", list(zip([c.uid for c in base.schema], base.exprs))))
            base = base.child
        else:
            break
    return list(reversed(rev)), base


def scan_stages_for(scan: PScan, stages) -> list:
    """Prepend the scan's pushed filter to a fused stage list."""
    out = []
    if scan.pushed_cond is not None:
        out.append(("filter", scan.pushed_cond))
    out.extend(stages)
    return out


def scan_prune_bounds(scan: PScan):
    """Zone-consultable bounds from the scan's pushed filter (ISSUE 8):
    the columnar segment store prunes whole segments against these
    before any host→device staging. Computed here — at executor-build
    time — so a plan-cache hit with freshly patched literal slots
    always re-derives bounds from the CURRENT literals."""
    if scan.pushed_cond is None or scan.table is None:
        return ()
    from tidb_tpu.columnar.zonemap import collect_prune_bounds

    uid_map = {c.uid: (c.name, c.type_) for c in scan.schema}
    return collect_prune_bounds(scan.pushed_cond, uid_map)


def _try_fused_scan_agg(plan: PHashAgg):
    """HashAgg whose child peels to a PLAIN table scan pipeline runs as
    one fused scan→filter→project→partial-agg fragment (ISSUE 9): one
    jitted program per chunk, device-resident state, one fetch at
    finalize. Point/range/partition access paths keep the classic tree
    (their row sets come from literal-keyed host probes), as does
    anything the context later rules out — FusedScanAggExec falls back
    through `fallback_build` at open() in that case, so the routing
    decision needing ExecContext state doesn't have to happen here."""
    from tidb_tpu.executor.pipeline import FusedScanAggExec

    stages, base = peel_stages(plan.child)
    if type(base) is not PScan or base.table is None:
        return None
    if plan.strategy != "segment":
        # plan-STATIC generic-strategy gates decide here so permanently
        # unfusible shapes (DISTINCT, non-core funcs, global generic)
        # keep the classic tree — and its per-operator EXPLAIN ANALYZE
        # breakdown. Only ctx-dependent gates (sysvars, device_agg)
        # defer to the open()-time delegate.
        from tidb_tpu.planner.logical import core_generic_agg

        if not core_generic_agg(plan.group_exprs, plan.aggs):
            return None

    def fallback(plan=plan):
        return HashAggExec(
            plan.schema, build_executor(plan.child), plan.group_exprs,
            plan.group_uids, plan.aggs, plan.strategy,
            segment_sizes=getattr(plan, "segment_sizes", None))

    return FusedScanAggExec(
        plan.schema, base.schema, base.table,
        scan_stages_for(base, stages), scan_prune_bounds(base),
        plan.group_exprs, plan.group_uids, plan.aggs, plan.strategy,
        segment_sizes=getattr(plan, "segment_sizes", None),
        fallback_build=fallback)


def _build_hash_join(plan: PHashJoin) -> HashJoinExec:
    """The classic pull-based hash-join tree (also the fused path's
    open()-time fallback delegate)."""
    probe_idx = 1 - plan.build_side
    probe_plan = plan.children[probe_idx]
    build_plan = plan.children[plan.build_side]
    probe_keys = plan.eq_left if probe_idx == 0 else plan.eq_right
    build_keys = plan.eq_right if plan.build_side == 1 else plan.eq_left
    # semi/anti joins need no build payload — unless an other_cond must
    # evaluate build columns during the probe, and then only those
    if plan.kind in ("semi", "anti"):
        if plan.other_cond is None:
            build_payload_schema = []
        else:
            from tidb_tpu.expression.expr import ColumnRef, walk

            refs = {n.name for n in walk(plan.other_cond)
                    if isinstance(n, ColumnRef)}
            build_payload_schema = [c for c in build_plan.schema
                                    if c.uid in refs]
    else:
        build_payload_schema = list(build_plan.schema)
    return HashJoinExec(
        plan.schema,
        build_executor(probe_plan),
        build_executor(build_plan),
        # a scalar subquery's one row joins every probe row: a keyless
        # inner join (the mesh tier compiles it into its program)
        "inner" if plan.kind == "scalar" else plan.kind,
        probe_keys,
        build_keys,
        other_cond=plan.other_cond,
        probe_schema=list(probe_plan.schema),
        build_schema=build_payload_schema,
        exists_sem=plan.exists_sem,
    )


def _try_fused_scan_probe(plan: PHashJoin):
    """Inner or LEFT OUTER hash join whose probe side peels to a PLAIN
    table scan pipeline runs as a fused scan→probe fragment (ISSUE 10,
    widened by ISSUE 18 to composite keys and the left-outer pad): one
    jitted decode+filter+project+probe+expand program per staged probe
    chunk, the build side device-resident (and device-buffer-cached
    when it is itself a plain scan over a stored table). Plan-STATIC
    gates decide here — semi/anti kinds and other_cond keep the classic
    tree with its per-operator EXPLAIN ANALYZE breakdown; ctx-dependent
    gates (sysvars, device-engine routing) defer to the open()-time
    delegate, as does the data-dependent hash-mode packing escape
    (composite key ranges overflowing int64 need the classic probe's
    exact re-verification, known only after the build drain)."""
    from tidb_tpu.executor.pipeline import FusedScanProbeExec

    if plan.kind not in ("inner", "left") or plan.other_cond is not None:
        return None
    if plan.exists_sem:
        return None
    if plan.kind == "left" and plan.build_side != 1:
        # the fused probe streams the PRESERVED side; a left join built
        # on the left would pad the wrong side
        return None
    probe_idx = 1 - plan.build_side
    probe_plan = plan.children[probe_idx]
    build_plan = plan.children[plan.build_side]
    probe_keys = plan.eq_left if probe_idx == 0 else plan.eq_right
    build_keys = plan.eq_right if plan.build_side == 1 else plan.eq_left
    if len(probe_keys) != len(build_keys) or not probe_keys:
        return None
    stages, base = peel_stages(probe_plan)
    if type(base) is not PScan or base.table is None:
        return None
    # build-side cache eligibility: only a plain scan pipeline over a
    # stored table proves a parked build current via table_ident; the
    # tag carries the peeled plan's full shape (incl. literal values —
    # a plan-cache hit patches literals before the builder runs)
    bstages, bbase = peel_stages(build_plan)
    build_table = bbase.table if type(bbase) is PScan else None
    build_tag = None
    if build_table is not None:
        build_tag = repr((bstages, getattr(bbase, "pushed_cond", None),
                          build_keys,
                          tuple(c.uid for c in build_plan.schema)))

    def fallback(plan=plan):
        return _build_hash_join(plan)

    return FusedScanProbeExec(
        plan.schema, base.schema, base.table,
        scan_stages_for(base, stages), scan_prune_bounds(base),
        list(probe_plan.schema), probe_keys, build_keys,
        list(build_plan.schema),
        build_child_build=lambda: build_executor(build_plan),
        build_table=build_table, build_tag=build_tag,
        kind=plan.kind, fallback_build=fallback)


def _try_fused_scan_topn(plan):
    """ORDER BY [+ LIMIT] root whose child peels to a PLAIN table scan
    pipeline runs as a fused scan→top-k fragment (ISSUE 18): one jitted
    decode+filter+project+top-k-merge program per staged chunk, a
    bounded device state of the current winners, one fetch at finalize.
    Plan-static gates only reject shapes with no scan pipeline to fuse;
    the capacity gates (LIMIT + offset vs the chunk capacity, table
    size for a full ORDER BY) are ctx/data-dependent and defer to the
    open()-time delegate — which is where the k-overflow feedback
    record comes from."""
    from tidb_tpu.executor.pipeline import FusedScanTopNExec

    if not plan.items:
        return None
    stages, base = peel_stages(plan.child)
    if type(base) is not PScan or base.table is None:
        return None
    topn = isinstance(plan, PTopN)

    def fallback(plan=plan):
        if isinstance(plan, PTopN):
            return TopNExec(plan.schema, build_executor(plan.child),
                            plan.items, plan.count, plan.offset)
        return SortExec(plan.schema, build_executor(plan.child),
                        plan.items)

    return FusedScanTopNExec(
        plan.schema, base.schema, base.table,
        scan_stages_for(base, stages), scan_prune_bounds(base),
        plan.items, plan.count if topn else None,
        plan.offset if topn else 0, full_sort=not topn,
        fallback_build=fallback)


def build_executor(plan: PhysicalPlan) -> Executor:
    """Build the executor for `plan` and annotate it with the plan node
    it answers for: plan feedback (ISSUE 15) and EXPLAIN ANALYZE's
    est/drift columns pair each executor's actual row count with its
    node's est_rows through this link. Fused/peeled executors carry the
    TOP of the chain they absorbed — their output is that node's."""
    e = _build_executor(plan)
    e._feedback_plan = plan
    return e


def _build_executor(plan: PhysicalPlan) -> Executor:
    # pipeline fusion: Selection/Projection chains over a scan
    stages, base = peel_stages(plan)
    if isinstance(base, PPointGet):
        from tidb_tpu.executor.scan import PointGetExec

        return PointGetExec(
            schema=base.schema,
            table=base.table,
            # a key-covered filter is subsumed by the unique-index probe
            # itself; only this single-chip point path may skip it — the
            # dist tier treats PPointGet as a plain scan and still needs
            # the pushed filter
            stages=(stages if base.cond_covered
                    else scan_stages_for(base, stages)),
            index_name=base.index_name,
            key_values=base.key_values,
            out_schema=plan.schema,
        )
    if isinstance(base, PIndexRangeScan):
        from tidb_tpu.executor.scan import IndexRangeScanExec

        return IndexRangeScanExec(
            schema=base.schema,
            table=base.table,
            stages=scan_stages_for(base, stages),
            index_name=base.index_name,
            eq_values=base.eq_values,
            range_lo=base.range_lo,
            range_hi=base.range_hi,
            lo_incl=base.lo_incl,
            hi_incl=base.hi_incl,
            out_schema=plan.schema,
        )
    if isinstance(base, PPartitionScan):
        from tidb_tpu.executor.scan import PartitionScanExec

        return PartitionScanExec(
            schema=base.schema,
            table=base.table,
            stages=scan_stages_for(base, stages),
            part_ids=base.part_ids,
            out_schema=plan.schema,
        )
    if isinstance(base, PScan):
        return TableScanExec(
            schema=base.schema,
            table=base.table,
            stages=scan_stages_for(base, stages),
            out_schema=plan.schema,
            prune_bounds=scan_prune_bounds(base),
        )

    if isinstance(plan, PSelection):
        return SelectionExec(plan.schema, build_executor(plan.child), plan.cond)
    if isinstance(plan, PProjection):
        return ProjectionExec(plan.schema, build_executor(plan.child), plan.exprs)
    if isinstance(plan, PScan):
        scan_stages = []
        if plan.pushed_cond is not None:
            scan_stages.append(("filter", plan.pushed_cond))
        return TableScanExec(schema=plan.schema, table=plan.table,
                             stages=scan_stages,
                             prune_bounds=scan_prune_bounds(plan))
    if isinstance(plan, PHashAgg):
        fused = _try_fused_scan_agg(plan)
        if fused is not None:
            return fused
        return HashAggExec(
            plan.schema,
            build_executor(plan.child),
            plan.group_exprs,
            plan.group_uids,
            plan.aggs,
            plan.strategy,
            segment_sizes=getattr(plan, "segment_sizes", None),
        )
    if isinstance(plan, PIndexJoin):
        return IndexJoinExec(
            plan.schema,
            build_executor(plan.child),
            plan.eq_outer,
            plan.inner_table,
            plan.index_name,
            plan.inner_schema,
            plan.inner_cond,
            plan.other_cond,
        )
    if isinstance(plan, PHashJoin):
        fused = _try_fused_scan_probe(plan)
        if fused is not None:
            return fused
        return _build_hash_join(plan)
    if isinstance(plan, PSort):
        fused = _try_fused_scan_topn(plan)
        if fused is not None:
            return fused
        return SortExec(plan.schema, build_executor(plan.child), plan.items)
    if isinstance(plan, PWindow):
        from tidb_tpu.executor.window import WindowExec

        return WindowExec(plan.schema, build_executor(plan.child), plan.func,
                          plan.args, plan.partition_by, plan.order_by,
                          plan.out_uid, plan.out_type, plan.params,
                          frame=plan.frame)
    if isinstance(plan, PTopN):
        fused = _try_fused_scan_topn(plan)
        if fused is not None:
            return fused
        return TopNExec(plan.schema, build_executor(plan.child), plan.items, plan.count, plan.offset)
    if isinstance(plan, PLimit):
        return LimitExec(plan.schema, build_executor(plan.child), plan.count, plan.offset)
    if isinstance(plan, PUnion):
        return UnionExec(plan.schema, [build_executor(c) for c in plan.children])

    raise PlanError(f"no executor for {type(plan).__name__}")
