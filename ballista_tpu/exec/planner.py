"""Physical planner: logical plan -> ExecutionPlan tree.

The reference delegates this to DataFusion's physical planner (invoked at
ballista/rust/scheduler/src/scheduler_server/grpc.rs:453-460); the node
vocabulary mirrors PhysicalPlanNode (ballista.proto:275-623). Aggregates
lower to partial/final pairs (the distributed repartition boundary), SEMI/
ANTI join build sides are deduplicated on the join keys when there is no
residual filter, and sorts always run over column keys (expressions are
pre-projected by the SQL planner).
"""

from __future__ import annotations

from ballista_tpu.datatypes import DataType
from ballista_tpu.errors import PlanError
from ballista_tpu.exec.aggregate import HashAggregateExec
from ballista_tpu.exec.base import ExecutionPlan
from ballista_tpu.exec.joins import (
    CrossJoinExec,
    EmptyExec,
    HashJoinExec,
    UnionExec,
)
from ballista_tpu.exec.pipeline import (
    CoalescePartitionsExec,
    FilterExec,
    ProjectionExec,
    RenameExec,
)
from ballista_tpu.exec.scan import (
    AvroScanExec,
    CsvScanExec,
    MemoryScanExec,
    ParquetScanExec,
)
from ballista_tpu.exec.sort import GlobalLimitExec, SortExec
from ballista_tpu.expr import logical as L
from ballista_tpu.plan import logical as P


class TableProvider:
    """Resolves a table name to a scan operator (the client keeps this
    registry per-session, ref client/src/context.rs:258-308)."""

    def scan(
        self, table: str, projection: list[str] | None, partitions: int
    ) -> ExecutionPlan:
        raise NotImplementedError


class PhysicalPlanner:
    def __init__(
        self,
        provider: TableProvider,
        partitions: int = 2,
        mesh_runtime=None,
        config=None,
        distributed: bool = False,
    ):
        """``mesh_runtime``: a ``ballista_tpu.exec.mesh.MeshRuntime`` when
        the ICI collective-shuffle tier is active (>= 2 devices and
        ``ballista.tpu.collective_shuffle`` on). Repartitioned aggregates
        and partitioned joins then lower to mesh (shard_map + all_to_all)
        operators instead of the serial coalesce funnel. The distributed
        (cross-host file/Flight) tier plans with ``mesh_runtime=None`` —
        mesh operators are process-local and not part of the serde
        vocabulary.

        ``distributed``: plan for the multi-executor tier — insert
        ``HashRepartitionExec`` boundaries at aggregates/joins (honoring
        ``ballista.repartition.aggregations/joins``) so the stage splitter
        can cut hash-shuffle exchanges there (ref planner.rs:133-157). The
        in-process tier leaves them out: a single device gains nothing
        from masked K-way fan-out."""
        self.provider = provider
        self.partitions = partitions
        self.mesh_runtime = mesh_runtime
        self.config = config
        self.distributed = distributed

    def _repartition_aggregations(self) -> bool:
        return (
            self.distributed
            and self.partitions > 1
            and (
                self.config is None or self.config.repartition_aggregations()
            )
        )

    def _repartition_joins(self) -> bool:
        return (
            self.distributed
            and self.partitions > 1
            and (self.config is None or self.config.repartition_joins())
        )

    def _repartition_windows(self) -> bool:
        return (
            self.distributed
            and self.partitions > 1
            and (self.config is None or self.config.repartition_windows())
        )

    def _whole_groups(
        self, child: ExecutionPlan, keys: list[L.Expr]
    ) -> ExecutionPlan:
        """What a window or a percentile stands on: an input in which every
        group lies whole inside one partition, since the operators work a
        partition at a time. On the distributed tier that is the hash
        exchange on the group's keys (``ballista.repartition.windows``): the
        stage splitter cuts there and each bucket is a task, as the
        reference plans ``WindowAggExec`` over ``RepartitionExec(Hash)``.
        Without keys, or in one process, where an exchange is K masked views
        of every batch, it is the gather into one partition (docs/sql.md)."""
        if keys and self._repartition_windows():
            from ballista_tpu.exec.repartition import HashRepartitionExec

            return HashRepartitionExec(child, keys, self.partitions)
        if child.output_partitioning().n > 1:
            return CoalescePartitionsExec(child)
        return child

    def plan(self, logical: P.LogicalPlan) -> ExecutionPlan:
        return self._plan(logical)

    def _plan(self, node: P.LogicalPlan) -> ExecutionPlan:
        if isinstance(node, P.TableScan):
            projection = list(node.projection) if node.projection else None
            if node.source is not None and node.source[0] in (
                "csv", "parquet", "avro"
            ):
                # file tables are self-describing — no shared catalog needed
                kind, path, has_header, delimiter = node.source
                if kind == "csv":
                    scan: ExecutionPlan = CsvScanExec(
                        path, node.source_schema, has_header, delimiter,
                        projection, self.partitions,
                    )
                elif kind == "avro":
                    scan = AvroScanExec(
                        path, node.source_schema, projection, self.partitions,
                    )
                else:
                    scan = ParquetScanExec(
                        path, node.source_schema, projection, self.partitions,
                        predicates=list(node.filters),
                    )
                scan.table_name = node.table_name
            else:
                scan = self.provider.scan(
                    node.table_name, projection, self.partitions
                )
                if isinstance(scan, ParquetScanExec):
                    scan.predicates = list(node.filters)
                scan.table_name = node.table_name
            for f in node.filters:
                scan = FilterExec(scan, f)
            return scan
        if isinstance(node, P.Projection):
            return ProjectionExec(self._plan(node.input), list(node.exprs))
        if isinstance(node, P.Filter):
            return FilterExec(self._plan(node.input), node.predicate)
        if isinstance(node, P.Percentile):
            from ballista_tpu.exec.percentile import PercentileExec

            return PercentileExec(
                self._whole_groups(
                    self._plan(node.input), list(node.group_exprs)
                ),
                node.group_exprs,
                node.group_names,
                node.requests,
            )
        if isinstance(node, P.Window):
            from ballista_tpu.exec.window import WindowExec

            child = self._plan(node.input)
            if self.mesh_runtime is not None:
                # partition-keyed windows hash-exchange by PARTITION BY
                # and run shard-local; exprs without a shared non-empty
                # key set fall through to the gather funnel
                from ballista_tpu.exec.mesh import MeshWindowExec

                try:
                    return MeshWindowExec(
                        child,
                        list(node.window_exprs),
                        list(node.names),
                        self.mesh_runtime,
                    )
                except PlanError:
                    pass
            # the columns every expression partitions by route the
            # exchange: a partition of any of them then lies in one bucket
            shared = [
                e for e in node.window_exprs[0].partition_by
                if all(
                    any(e.name() == o.name() for o in w.partition_by)
                    for w in node.window_exprs[1:]
                )
            ] if node.window_exprs else []
            return WindowExec(
                self._whole_groups(child, shared),
                list(node.window_exprs),
                list(node.names),
            )
        if isinstance(node, P.Aggregate):
            return self._plan_aggregate(node)
        if isinstance(node, P.Distinct):
            child = self._plan(node.input)
            groups = [L.Column(f.name) for f in node.input.schema()]
            if self.mesh_runtime is not None:
                from ballista_tpu.exec.mesh import MeshAggregateExec

                return MeshAggregateExec(
                    child, groups, [], self.mesh_runtime
                )
            partial = HashAggregateExec(child, groups, [], mode="partial")
            return HashAggregateExec(
                CoalescePartitionsExec(partial), groups, [],
                mode="final", spec=partial.spec,
                planned_input_schema=partial.planned_input_schema,
            )
        if isinstance(node, P.Sort):
            child = self._plan(node.input)
            if self.mesh_runtime is not None:
                # full ORDER BY over the mesh: sample sort (range
                # exchange + local sort) instead of the coalesce funnel
                from ballista_tpu.exec.mesh import MeshSortExec

                try:
                    return MeshSortExec(
                        child, list(node.sort_exprs), None,
                        self.mesh_runtime,
                    )
                except PlanError:
                    pass  # non-column keys: canonical funnel below
            if self.distributed and child.output_partitioning().n > 1:
                # explicit gather boundary: the stage splitter cuts here, so
                # an upstream K-way final aggregate keeps its K parallel
                # tasks and only the sort itself runs single-task (ref
                # 3-stage q1 golden plan, planner.rs:328-344)
                child = CoalescePartitionsExec(child)
            return SortExec(child, list(node.sort_exprs))
        if isinstance(node, P.Limit):
            # ORDER BY + LIMIT over the mesh: distributed TopK (local
            # top-k per shard -> all_gather -> replicated merge) instead
            # of gathering everything to one device and sorting there
            if (
                self.mesh_runtime is not None
                and node.fetch is not None
                and isinstance(node.input, P.Sort)
            ):
                from ballista_tpu.exec.mesh import MeshSortExec

                sort_node = node.input
                child = self._plan(sort_node.input)
                try:
                    ms = MeshSortExec(
                        child, list(sort_node.sort_exprs),
                        node.skip + node.fetch, self.mesh_runtime,
                    )
                    return GlobalLimitExec(ms, node.skip, node.fetch)
                except PlanError:
                    pass  # non-column keys / fetch 0: the canonical
                    # P.Sort lowering below handles it (re-plans the
                    # sort input; planning is side-effect free)
            child = self._plan(node.input)
            if child.output_partitioning().n > 1:
                child = CoalescePartitionsExec(child)
            return GlobalLimitExec(child, node.skip, node.fetch)
        if isinstance(node, P.Join):
            return self._plan_join(node)
        if isinstance(node, P.CrossJoin):
            return CrossJoinExec(self._plan(node.left), self._plan(node.right))
        if isinstance(node, P.Union):
            return UnionExec([self._plan(c) for c in node.inputs])
        if isinstance(node, P.SubqueryAlias):
            return RenameExec(self._plan(node.input), node.schema())
        if isinstance(node, P.EmptyRelation):
            return EmptyExec(node.produce_one_row, node.out_schema)
        raise PlanError(f"cannot lower {type(node).__name__} to physical plan")

    def _plan_aggregate(self, node: P.Aggregate) -> ExecutionPlan:
        child = self._plan(node.input)
        if self.mesh_runtime is not None and node.group_exprs:
            # grouped aggregate -> one mesh program (partial + all_to_all
            # state exchange + final merge); scalar aggregates stay on the
            # local funnel (their state is one row — nothing to shuffle)
            from ballista_tpu.exec.mesh import MeshAggregateExec

            return MeshAggregateExec(
                child, list(node.group_exprs), list(node.agg_exprs),
                self.mesh_runtime,
            )
        partial = HashAggregateExec(
            child, list(node.group_exprs), list(node.agg_exprs),
            mode="partial", subquery=node.subquery,
        )
        if node.group_exprs and self._repartition_aggregations():
            # hash-exchange the partial states on the group keys: the final
            # merge becomes K parallel tasks, one per hash bucket (ref
            # planner.rs:133-157 + the 3-stage q1 golden plan :328-344)
            from ballista_tpu.exec.repartition import HashRepartitionExec

            ng = len(node.group_exprs)
            keys = [
                L.Column(f.name) for f in partial.schema().fields[:ng]
            ]
            merged = HashRepartitionExec(partial, keys, self.partitions)
        else:
            merged = CoalescePartitionsExec(partial)
        return HashAggregateExec(
            merged, list(node.group_exprs), list(node.agg_exprs),
            mode="final", spec=partial.spec,
            planned_input_schema=partial.planned_input_schema,
            subquery=node.subquery,
        )

    def _plan_join(self, node: P.Join) -> ExecutionPlan:
        jt = node.join_type
        if jt == P.JoinType.FULL:
            # FULL = LEFT(l,r) UNION ALL (r ANTI-join l, left columns padded
            # with typed NULLs). The ANTI side carries the residual filter:
            # a right row is unmatched when no pair passed equi+filter.
            # Known cost: both input subtrees execute twice (once per
            # branch); a native full-outer probe sharing one build table
            # would halve that — acceptable until FULL shows up hot.
            left_part = P.Join(
                node.left, node.right, node.on, P.JoinType.LEFT, node.filter
            )
            anti_part = P.Join(
                node.right, node.left,
                tuple((b, a) for a, b in node.on),
                P.JoinType.ANTI, node.filter,
            )
            a = self._plan_join(left_part)
            b = self._plan_join(anti_part)
            ls = node.left.schema()
            rs = node.right.schema()
            pad = [
                L.Alias(L.Literal(None, f.dtype), f.name) for f in ls
            ] + [L.Column(f.name) for f in rs]
            padded = ProjectionExec(b, pad)
            # the LEFT branch already has node.schema()'s names in order —
            # no identity projection needed
            return UnionExec([a, padded])
        if jt == P.JoinType.RIGHT:
            # flip to LEFT; column order restored by a projection
            flipped = P.Join(
                node.right, node.left,
                tuple((b, a) for a, b in node.on),
                P.JoinType.LEFT, node.filter,
            )
            child = self._plan_join(flipped)
            out = node.schema()
            return ProjectionExec(
                child, [L.Column(f.name) for f in out]
            )
        left = self._plan(node.left)
        right = self._plan(node.right)
        if node.reduction:
            # the semi join below a decorrelated subquery's aggregate: its
            # domain is small, its probe side the subquery's scan. Collected,
            # the probe runs inside the scan's stage and no probe row is
            # exchanged before the partial aggregate
            return self._collected_existence_join(left, right, node)
        if self.mesh_runtime is not None and (
            jt == P.JoinType.INNER
            or (
                jt in (P.JoinType.LEFT, P.JoinType.SEMI, P.JoinType.ANTI)
                and node.filter is None
            )
        ):
            # PARTITIONED mode over the mesh. SEMI/ANTI need no build-side
            # dedup here — the mesh probe counts matches, so duplicate
            # build keys are existence-correct natively.
            from ballista_tpu.exec.mesh import MeshJoinExec

            return MeshJoinExec(
                left, right, list(node.on), jt, node.filter,
                self.mesh_runtime,
            )
        # STRING keys are dictionary-coded; two executors cannot hash-route
        # codes consistently without a shared dictionary, so string-keyed
        # joins stay in collect (broadcast-build) mode.
        no_string_keys = all(
            a.data_type(node.left.schema()) != DataType.STRING
            and b.data_type(node.right.schema()) != DataType.STRING
            for a, b in node.on
        )
        if (
            self._repartition_joins()
            and no_string_keys
            and jt in (
                P.JoinType.INNER, P.JoinType.LEFT, P.JoinType.SEMI,
                P.JoinType.ANTI,
            )
        ):
            # PARTITIONED mode: hash-exchange both sides on the join keys;
            # each of K tasks joins its bucket (ref planner.rs:133-157 +
            # the 5-stage join golden plan :442-471). Duplicate build keys
            # run the per-bucket expansion path, so no dedup pre-pass is
            # needed even for SEMI/ANTI.
            from ballista_tpu.exec.repartition import HashRepartitionExec

            lkeys = [a for a, _ in node.on]
            rkeys = [b for _, b in node.on]
            left = HashRepartitionExec(left, lkeys, self.partitions)
            right = HashRepartitionExec(right, rkeys, self.partitions)
            return HashJoinExec(
                left, right, list(node.on), jt, node.filter,
                partition_mode="partitioned",
            )
        if jt in (P.JoinType.SEMI, P.JoinType.ANTI) and node.filter is None:
            return self._collected_existence_join(left, right, node)
        return HashJoinExec(left, right, list(node.on), jt, node.filter)

    def _collected_existence_join(
        self, left: ExecutionPlan, right: ExecutionPlan, node: P.Join
    ) -> ExecutionPlan:
        """A SEMI or ANTI join without a residual filter in collect mode.
        The kernel needs a unique build side; existence semantics allow
        dedup on the join keys (ref HashJoinExec handles dup builds
        natively — our sort-probe kernel dedups instead)."""
        keys = [b for _, b in node.on]
        dpartial = HashAggregateExec(right, keys, [], mode="partial")
        right = HashAggregateExec(
            CoalescePartitionsExec(dpartial), keys, [],
            mode="final", spec=dpartial.spec,
            planned_input_schema=dpartial.planned_input_schema,
        )
        on = [(a, L.Column(k.name())) for (a, _), k in zip(node.on, keys)]
        return HashJoinExec(
            left, right, on, node.join_type, None, reduction=node.reduction
        )
