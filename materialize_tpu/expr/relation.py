"""Relation expressions (MIR).

Analog of the reference's ``MirRelationExpr`` — all 15 variants
(src/expr/src/relation.rs:100): Constant, Get, Let, LetRec, Project, Map,
FlatMap, Filter, Join, Reduce, TopK, Negate, Threshold, Union, ArrangeBy —
plus the aggregate function vocabulary (src/expr/src/relation/func.rs:1878
``AggregateFunc``). The optimizer (materialize_tpu.transform) rewrites
these; plan.lowering lowers them to LIR for rendering.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Sequence

from ..repr.schema import Column, ColumnType, Schema
from .scalar import ScalarExpr


class AggregateFunc(enum.Enum):
    """Aggregates; accumulable ones fold into the diff field
    (render/reduce.rs:1357 Accum), hierarchical ones need tournament
    trees (render/reduce.rs:850)."""

    COUNT = "count"        # accumulable
    SUM_INT = "sum_int"    # accumulable (int64/decimal)
    SUM_FLOAT = "sum_float"  # accumulable (f64; non-deterministic order OK)
    MIN = "min"            # hierarchical
    MAX = "max"            # hierarchical
    ANY = "any"            # accumulable over bools (true count > 0)
    ALL = "all"            # accumulable (false count == 0)
    # Basic (collection) aggregates — the analog of the reference's
    # build_basic_aggregate tier (compute/src/render/reduce.rs:369;
    # StringAgg / ArrayConcat / ListConcat in expr/src/relation/
    # func.rs:1878). The maintained device state is the sorted
    # (group key, value) multiset plus an order-insensitive digest
    # accumulator for change detection; the variable-width result is
    # produced at the serving edge (Dataflow.peek) where a host
    # readback happens anyway — variable-width concatenation per step
    # would break the zero-readback hot loop. Values order by
    # dictionary code == lexicographic order, so the output is
    # deterministic (pg leaves un-ORDER BY'd aggs unspecified).
    STRING_AGG = "string_agg"  # basic: join with separator
    ARRAY_AGG = "array_agg"    # basic: pg-style {a,b,c} text rendering
    LIST_AGG = "list_agg"      # basic: mz list, same rendering

    @property
    def is_accumulable(self) -> bool:
        return self in (
            AggregateFunc.COUNT,
            AggregateFunc.SUM_INT,
            AggregateFunc.SUM_FLOAT,
            AggregateFunc.ANY,
            AggregateFunc.ALL,
        )

    @property
    def is_hierarchical(self) -> bool:
        return self in (AggregateFunc.MIN, AggregateFunc.MAX)

    @property
    def is_basic(self) -> bool:
        return self in (
            AggregateFunc.STRING_AGG,
            AggregateFunc.ARRAY_AGG,
            AggregateFunc.LIST_AGG,
        )

    @property
    def preserves_nulls(self) -> bool:
        """array_agg/list_agg keep NULL elements (pg semantics; the
        reference's SQL layer wraps each value in ArrayCreate before
        ArrayConcat so NULLs survive, sql/src/func.rs:3668).
        string_agg drops them."""
        return self in (AggregateFunc.ARRAY_AGG, AggregateFunc.LIST_AGG)


@dataclass(frozen=True)
class AggregateExpr:
    """func applied to a scalar expression over the group
    (reference: expr AggregateExpr {func, expr, distinct})."""

    func: AggregateFunc
    expr: ScalarExpr
    distinct: bool = False
    # Host-side parameters (e.g. string_agg's separator TEXT). Part of
    # the plan, not a scalar input: basic-aggregate finalization runs at
    # the serving edge on the host.
    params: tuple = ()

    def output_col(self, input_schema: Schema) -> Column:
        inner = self.expr.typ(input_schema)
        if self.func is AggregateFunc.COUNT:
            return Column("count", ColumnType.INT64, False)
        if self.func is AggregateFunc.SUM_INT:
            return Column("sum", inner.ctype, True, inner.scale)
        if self.func is AggregateFunc.SUM_FLOAT:
            return Column("sum", ColumnType.FLOAT64, True)
        if self.func in (AggregateFunc.MIN, AggregateFunc.MAX):
            return Column(
                self.func.value, inner.ctype, True, inner.scale
            )
        if self.func in (AggregateFunc.ANY, AggregateFunc.ALL):
            return Column(self.func.value, ColumnType.BOOL, True)
        if self.func.is_basic:
            # The device column carries an opaque change-detection
            # digest until edge finalization substitutes the encoded
            # result string (ops/reduce.py basic tier).
            return Column(self.func.value, ColumnType.STRING, True)
        raise NotImplementedError(self.func)


class RelationExpr:
    """Base class for MIR relation expressions."""

    def schema(self) -> Schema:
        raise NotImplementedError

    def children(self) -> list["RelationExpr"]:
        return []

    # builder sugar
    def project(self, outputs: Sequence[int]) -> "Project":
        return Project(self, tuple(outputs))

    def map(self, exprs: Sequence[ScalarExpr]) -> "Map":
        return Map(self, tuple(exprs))

    def filter(self, preds: Sequence[ScalarExpr]) -> "Filter":
        return Filter(self, tuple(preds))

    def reduce(self, group_key, aggregates) -> "Reduce":
        return Reduce(self, tuple(group_key), tuple(aggregates))

    def distinct(self) -> "Reduce":
        return Reduce(
            self, tuple(range(self.schema().arity)), ()
        )

    def negate(self) -> "Negate":
        return Negate(self)

    def threshold(self) -> "Threshold":
        return Threshold(self)

    def union(self, *others) -> "Union":
        return Union((self, *others))

    def arrange_by(self, key) -> "ArrangeBy":
        return ArrangeBy(self, tuple(key))


@dataclass(frozen=True)
class Constant(RelationExpr):
    """Literal collection: rows with diffs (relation.rs Constant)."""

    rows: tuple  # tuple of (row_tuple, diff)
    _schema: Schema

    def schema(self):
        return self._schema

    def __reduce__(self):
        # string fields are codes of this process's dictionary: the
        # strings travel (see expr/scalar.py Literal.__reduce__)
        from ..repr.schema import GLOBAL_DICT, ColumnType

        at = tuple(
            i for i, c in enumerate(self._schema.columns)
            if c.ctype is ColumnType.STRING
        )
        if at and self.rows:
            # an undecodable code is a KeyError here: it cannot travel
            rows = _map_fields(self.rows, at, GLOBAL_DICT.decode)
            return (_string_constant, (rows, self._schema, at))
        return (Constant, (self.rows, self._schema))


def _map_fields(rows: tuple, at: tuple, fn) -> tuple:
    """``rows`` with ``fn`` applied to the non-NULL fields at ``at``."""
    return tuple(
        (
            tuple(
                fn(v) if i in at and v is not None else v
                for i, v in enumerate(row)
            ),
            diff,
        )
        for row, diff in rows
    )


def _string_constant(rows: tuple, schema: Schema, at: tuple) -> "Constant":
    from ..repr.schema import GLOBAL_DICT

    return Constant(_map_fields(rows, at, GLOBAL_DICT.encode), schema)


@dataclass(frozen=True)
class Get(RelationExpr):
    """Reference to a named collection (source, index, or let binding)."""

    name: str
    _schema: Schema

    def schema(self):
        return self._schema


@dataclass(frozen=True)
class Let(RelationExpr):
    name: str
    value: RelationExpr
    body: RelationExpr

    def schema(self):
        return self.body.schema()

    def children(self):
        return [self.value, self.body]


@dataclass(frozen=True)
class LetRec(RelationExpr):
    """WITH MUTUALLY RECURSIVE: bindings may reference each other and
    themselves; semantics are per-binding fixpoint iteration
    (relation.rs LetRec, rendered at compute render.rs:887)."""

    names: tuple  # binding names
    values: tuple  # RelationExpr per binding (may Get any binding name)
    value_schemas: tuple  # declared schema per binding
    body: RelationExpr
    # Iteration cap (reference LetRecLimit / RETURN AT RECURSION LIMIT,
    # expr/src/relation.rs LetRec limits). None = run to fixpoint.
    max_iters: int | None = None

    def schema(self):
        return self.body.schema()

    def children(self):
        return list(self.values) + [self.body]


@dataclass(frozen=True)
class Project(RelationExpr):
    input: RelationExpr
    outputs: tuple

    def schema(self):
        return self.input.schema().project(self.outputs)

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class Map(RelationExpr):
    input: RelationExpr
    scalars: tuple

    def schema(self):
        cols = list(self.input.schema().columns)
        for e in self.scalars:
            c = e.typ(Schema(cols))
            cols.append(Column(f"c{len(cols)}", c.ctype, c.nullable, c.scale))
        return Schema(cols)

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class FlatMap(RelationExpr):
    """Table function application (unnest, generate_series...)."""

    input: RelationExpr
    func: str
    exprs: tuple
    output_cols: tuple  # Columns appended by the table function

    def schema(self):
        return Schema(
            tuple(self.input.schema().columns) + tuple(self.output_cols)
        )

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class Filter(RelationExpr):
    input: RelationExpr
    predicates: tuple

    def schema(self):
        return self.input.schema()

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class Join(RelationExpr):
    """Multiway equi-join. equivalences: classes of scalar expressions
    (over the concatenated columns of all inputs) asserted equal
    (relation.rs Join; the optimizer picks Linear vs Delta plans,
    transform/src/join_implementation.rs)."""

    inputs: tuple
    equivalences: tuple  # tuple of tuples of ScalarExpr
    # "auto" | "linear" | "delta" — JoinImplementation's decision
    # (transform/src/join_implementation.rs). auto: delta for >=3 inputs
    # (the delta join's sweet spot; delta_join.rs:10-12), linear for 2.
    implementation: str = "auto"

    def schema(self):
        cols = []
        for inp in self.inputs:
            cols.extend(inp.schema().columns)
        return Schema(cols)

    def children(self):
        return list(self.inputs)


@dataclass(frozen=True)
class Reduce(RelationExpr):
    input: RelationExpr
    group_key: tuple  # column indices (simple keys; exprs pre-mapped)
    aggregates: tuple  # AggregateExpr

    def schema(self):
        in_schema = self.input.schema()
        cols = [in_schema[i] for i in self.group_key]
        for j, agg in enumerate(self.aggregates):
            c = agg.output_col(in_schema)
            cols.append(Column(f"{c.name}_{j}", c.ctype, c.nullable, c.scale))
        return Schema(cols)

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class TopK(RelationExpr):
    """Per-group top-k by ordering (relation.rs TopK; plans at
    compute-types/src/plan/top_k.rs:28)."""

    input: RelationExpr
    group_key: tuple
    order_by: tuple  # (col_index, desc: bool, nulls_last: bool)
    limit: int | None
    offset: int = 0

    def schema(self):
        return self.input.schema()

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class Negate(RelationExpr):
    input: RelationExpr

    def schema(self):
        return self.input.schema()

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class Threshold(RelationExpr):
    """Keep rows with positive multiplicity (render/threshold.rs)."""

    input: RelationExpr

    def schema(self):
        return self.input.schema()

    def children(self):
        return [self.input]


@dataclass(frozen=True)
class Union(RelationExpr):
    inputs: tuple

    def schema(self):
        # Names/ctypes/scales come from branch 0; NULLABILITY is the
        # least upper bound across branches. Outer-join and
        # scalar-subquery lowerings build unions whose NULL-padding
        # branch is nullable while branch 0 is not — deriving the
        # schema from branch 0 alone claimed non-nullable columns that
        # carry NULLs, which let column_knowledge fold IS_NULL(col) to
        # false unsoundly (found by analysis/typecheck.py T-SCHEMA
        # over the SLT corpus). Memoized: the lub walks EVERY branch,
        # and lowerings nest union towers whose repeated schema() calls
        # would otherwise be quadratic in the tower depth. The node is
        # frozen/immutable, so the cache can never go stale.
        memo = self.__dict__.get("_schema_memo")
        if memo is not None:
            return memo
        base = self.inputs[0].schema()
        cols = list(base.columns)
        for inp in self.inputs[1:]:
            for i, c in enumerate(inp.schema().columns):
                if i < len(cols) and c.nullable and not cols[i].nullable:
                    old = cols[i]
                    cols[i] = Column(old.name, old.ctype, True, old.scale)
        sch = Schema(tuple(cols))
        object.__setattr__(self, "_schema_memo", sch)
        return sch

    def __getstate__(self):
        # The memo must not leak into pickled state:
        # DataflowDescription.fingerprint() pickles the expr, and
        # replica reconciliation compares fingerprints byte-for-byte —
        # a cache populated on one side but not the other would make an
        # unchanged dataflow look changed and trigger a full rebuild.
        d = dict(self.__dict__)
        d.pop("_schema_memo", None)
        return d

    def children(self):
        return list(self.inputs)


@dataclass(frozen=True)
class ArrangeBy(RelationExpr):
    """Assert arrangement by key (relation.rs ArrangeBy)."""

    input: RelationExpr
    key: tuple  # column indices

    def schema(self):
        return self.input.schema()

    def children(self):
        return [self.input]
