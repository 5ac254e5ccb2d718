"""dpark_tpu.query — the columnar query plane (ISSUE 13 tentpole).

The table/SQL DSL (dpark_tpu/table.py) and the SQL front end both lower
into a LOGICAL PLAN (scan -> project -> filter -> group-agg -> join ->
sort/top nodes, dpark_tpu/query/logical.py); a rule-driven physical
planner (dpark_tpu/query/planner.py) then compiles each node onto the
shipped device machinery instead of per-row Python lambdas:

  * column pruning + predicate pushdown into the tabular scan — only
    referenced columns are read, filter predicates evaluate as
    vectorized array programs over column batches BEFORE any row tuple
    materializes, and whole chunks skip via the per-chunk min/max
    footer stats (dpark_tpu/tabular.py v2);
  * group-by aggregates (sum/count/min/max/avg + traceable UDAs)
    lower onto the device combine exchange / SegAggOp / SegMapOp over
    the tuple-key shuffle (PRs 3-4);
  * equi-joins, a left-deep chain of them over part files, driver
    slices or tables resident on the device, lower onto the device join
    source (PR 3), their sums proved from keys shown unique;
  * string group/join keys ride dictionary-encoded (TokenDict) and
    decode at egest;
  * per-operator device-vs-host choice is priced through the adaptive
    store (adapt decision point 2) and every host choice is recorded
    with a reason — the `table-host-fallback` lint rule reports the
    same reasons pre-flight.

The planner's rewrite rules reuse the PR 1 lint rule engine's lineage
walk (analysis.plan_rules.iter_lineage over the logical nodes), so
every rule doubles as a lintable explanation.
"""

from dpark_tpu.query.logical import (Filter, GroupAgg, Join, Node,
                                     Project, Scan, Sort, iter_plan)
from dpark_tpu.query.exprs import ColumnExpr, compile_expr, vectorize
from dpark_tpu.query.planner import PlannedQuery, plan_query

__all__ = ["Node", "Scan", "Project", "Filter", "GroupAgg", "Join",
           "Sort", "iter_plan", "ColumnExpr", "compile_expr",
           "vectorize", "PlannedQuery", "plan_query"]
