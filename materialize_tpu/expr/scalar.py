"""Scalar expressions (MIR) and their XLA evaluation.

Analog of the reference's ``MirScalarExpr``
(src/expr/src/scalar.rs:69: Column / Literal / CallUnary / CallBinary /
CallVariadic / If) and its scalar function library
(src/expr/src/scalar/func.rs). Where the reference interprets expressions
row-at-a-time over ``Datum``s, here evaluation happens at *trace time*:
``eval_expr`` recursively builds a fused XLA computation over whole columns
— the "MirScalarExpr JIT-compiled to XLA" of the north star
(BASELINE.json). SQL NULL semantics are carried as an optional bool mask
per intermediate (three-valued logic).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Sequence

import jax.numpy as jnp
import numpy as np

from ..repr.batch import Batch
from ..repr.schema import Column, ColumnType, Schema

# ---------------------------------------------------------------------------
# Expression tree


class ScalarExpr:
    """Base class. Subclasses are immutable dataclasses."""

    def typ(self, schema: Schema) -> Column:
        raise NotImplementedError

    # convenience builders
    def __add__(self, other):
        return CallBinary(BinaryFunc.ADD, self, _lift(other))

    def __sub__(self, other):
        return CallBinary(BinaryFunc.SUB, self, _lift(other))

    def __mul__(self, other):
        return CallBinary(BinaryFunc.MUL, self, _lift(other))

    def eq(self, other):
        return CallBinary(BinaryFunc.EQ, self, _lift(other))

    def lt(self, other):
        return CallBinary(BinaryFunc.LT, self, _lift(other))

    def lte(self, other):
        return CallBinary(BinaryFunc.LTE, self, _lift(other))

    def gt(self, other):
        return CallBinary(BinaryFunc.GT, self, _lift(other))

    def gte(self, other):
        return CallBinary(BinaryFunc.GTE, self, _lift(other))


def _lift(x) -> "ScalarExpr":
    if isinstance(x, ScalarExpr):
        return x
    if isinstance(x, bool):
        return Literal(x, ColumnType.BOOL)
    if isinstance(x, int):
        return Literal(x, ColumnType.INT64)
    if isinstance(x, float):
        return Literal(x, ColumnType.FLOAT64)
    raise TypeError(x)


@dataclass(frozen=True)
class MzNow(ScalarExpr):
    """The current virtual timestamp: CallUnmaterializable::MzNow
    (expr/src/scalar.rs). Evaluates to the step's time; predicates over
    it become TEMPORAL FILTERS (expr/src/linear.rs:404-408) that
    schedule future retractions/insertions."""

    def typ(self, schema: Schema) -> Column:
        return Column("mz_now", ColumnType.TIMESTAMP)


def contains_mz_now(expr: ScalarExpr) -> bool:
    if isinstance(expr, MzNow):
        return True
    for f in getattr(expr, "__dataclass_fields__", {}):
        v = getattr(expr, f)
        if isinstance(v, ScalarExpr) and contains_mz_now(v):
            return True
        if isinstance(v, tuple) and any(
            isinstance(x, ScalarExpr) and contains_mz_now(x) for x in v
        ):
            return True
    return False


@dataclass(frozen=True)
class ColumnRef(ScalarExpr):
    """Column reference by position (like MirScalarExpr::Column)."""

    index: int

    def typ(self, schema):
        return schema[self.index]


@dataclass(frozen=True)
class Literal(ScalarExpr):
    value: Any  # python scalar; None = NULL
    ctype: ColumnType
    scale: int = 0

    def typ(self, schema):
        return Column("literal", self.ctype, self.value is None, self.scale)

    def __reduce__(self):
        # A STRING literal's value is a code of THIS process's
        # dictionary (repr/schema.py); a plan is pickled to reach a
        # replica, which may be another process with a dictionary of
        # its own (coord/protocol.py). The string travels, and is
        # encoded again where it lands: `c_mktsegment = 'BUILDING'`
        # compared another string's code, or none, on a subprocess
        # replica before PR 30.
        if self.ctype is ColumnType.STRING and self.value is not None:
            from ..repr.schema import GLOBAL_DICT

            # a code no string was given cannot travel: KeyError here,
            # never another process's string for the same number
            return (
                _string_literal,
                (GLOBAL_DICT.decode(self.value), self.scale),
            )
        return (Literal, (self.value, self.ctype, self.scale))


def _string_literal(text: str, scale: int = 0) -> Literal:
    from ..repr.schema import GLOBAL_DICT

    return Literal(GLOBAL_DICT.encode(text), ColumnType.STRING, scale)


class UnaryFunc:
    NOT = "not"
    NEG = "neg"
    IS_NULL = "is_null"
    ABS = "abs"
    # math family (scalar func library analog, expr/src/scalar/func/impls)
    FLOOR = "floor"
    CEIL = "ceil"
    ROUND = "round"
    TRUNC = "trunc"
    SQRT = "sqrt"
    CBRT = "cbrt"
    EXP = "exp"
    LN = "ln"
    LOG10 = "log10"
    LOG2 = "log2"
    SIGN = "sign"
    SIN = "sin"
    COS = "cos"
    TAN = "tan"
    ASIN = "asin"
    ACOS = "acos"
    ATAN = "atan"
    RADIANS = "radians"
    DEGREES = "degrees"
    # cast family
    CAST_INT64 = "cast_int64"
    CAST_INT32 = "cast_int32"
    CAST_FLOAT64 = "cast_float64"
    CAST_BOOL = "cast_bool"
    CAST_DATE = "cast_date"
    CAST_TIMESTAMP = "cast_timestamp"
    # date parts (DATE = days since epoch; TIMESTAMP = ms since epoch)
    EXTRACT_YEAR = "extract_year"
    EXTRACT_MONTH = "extract_month"
    EXTRACT_DAY = "extract_day"
    EXTRACT_QUARTER = "extract_quarter"
    EXTRACT_DOW = "extract_dow"
    EXTRACT_ISODOW = "extract_isodow"
    EXTRACT_DOY = "extract_doy"
    EXTRACT_WEEK = "extract_week"
    EXTRACT_EPOCH = "extract_epoch"
    EXTRACT_HOUR = "extract_hour"
    EXTRACT_MINUTE = "extract_minute"
    EXTRACT_SECOND = "extract_second"
    EXTRACT_MILLENNIUM = "extract_millennium"
    EXTRACT_CENTURY = "extract_century"
    EXTRACT_DECADE = "extract_decade"
    # date_trunc family: value-preserving truncation to a boundary
    DATE_TRUNC_YEAR = "date_trunc_year"
    DATE_TRUNC_QUARTER = "date_trunc_quarter"
    DATE_TRUNC_MONTH = "date_trunc_month"
    DATE_TRUNC_WEEK = "date_trunc_week"
    DATE_TRUNC_DAY = "date_trunc_day"
    DATE_TRUNC_HOUR = "date_trunc_hour"
    DATE_TRUNC_MINUTE = "date_trunc_minute"
    DATE_TRUNC_SECOND = "date_trunc_second"

    EXTRACTS = {}  # filled below
    DATE_TRUNCS = {}  # filled below


UnaryFunc.EXTRACTS = {
    "year": UnaryFunc.EXTRACT_YEAR,
    "month": UnaryFunc.EXTRACT_MONTH,
    "day": UnaryFunc.EXTRACT_DAY,
    "quarter": UnaryFunc.EXTRACT_QUARTER,
    "dow": UnaryFunc.EXTRACT_DOW,
    "isodow": UnaryFunc.EXTRACT_ISODOW,
    "doy": UnaryFunc.EXTRACT_DOY,
    "week": UnaryFunc.EXTRACT_WEEK,
    "epoch": UnaryFunc.EXTRACT_EPOCH,
    "hour": UnaryFunc.EXTRACT_HOUR,
    "minute": UnaryFunc.EXTRACT_MINUTE,
    "second": UnaryFunc.EXTRACT_SECOND,
    "millennium": UnaryFunc.EXTRACT_MILLENNIUM,
    "century": UnaryFunc.EXTRACT_CENTURY,
    "decade": UnaryFunc.EXTRACT_DECADE,
}

UnaryFunc.DATE_TRUNCS = {
    "year": UnaryFunc.DATE_TRUNC_YEAR,
    "quarter": UnaryFunc.DATE_TRUNC_QUARTER,
    "month": UnaryFunc.DATE_TRUNC_MONTH,
    "week": UnaryFunc.DATE_TRUNC_WEEK,
    "day": UnaryFunc.DATE_TRUNC_DAY,
    "hour": UnaryFunc.DATE_TRUNC_HOUR,
    "minute": UnaryFunc.DATE_TRUNC_MINUTE,
    "second": UnaryFunc.DATE_TRUNC_SECOND,
}


class BinaryFunc:
    ADD = "add"
    SUB = "sub"
    MUL = "mul"
    DIV = "div"
    MOD = "mod"
    POWER = "power"
    LOG_BASE = "log_base"
    ROUND_TO = "round_to"  # round(x, n): n must be a literal
    CAST_DECIMAL = "cast_decimal"  # cast(x as decimal(p, s)): s a literal
    EQ = "eq"
    NEQ = "neq"
    LT = "lt"
    LTE = "lte"
    GT = "gt"
    GTE = "gte"


# String functions evaluate as dictionary side-table gathers (see
# expr/strings.py): CallVariadic("str:<fn>", (col, literal params...)).
STRING_FUNC_PREFIX = "str:"


def string_call(func: str, expr: "ScalarExpr", *params) -> "CallVariadic":
    return CallVariadic(
        STRING_FUNC_PREFIX + func, (expr,) + tuple(params)
    )


def _string_func_key(func: str, param_exprs) -> str:
    """Trace/render-time env key: literal params decoded to text."""
    from ..repr.schema import GLOBAL_DICT
    from . import strings

    vals = []
    for p in param_exprs:
        if not isinstance(p, Literal):
            raise NotImplementedError(
                f"{func}: non-literal string-function arguments are "
                "not supported (the mapping table is precomputed per "
                "distinct dictionary entry)"
            )
        if p.value is None:
            raise NotImplementedError(
                f"{func}: NULL parameters are not supported"
            )
        if p.ctype is ColumnType.STRING:
            vals.append(GLOBAL_DICT.decode(int(p.value)))
        else:
            vals.append(p.value)
    return strings.env_key(func, *vals)


class VariadicFunc:
    AND = "and"
    OR = "or"
    COALESCE = "coalesce"
    GREATEST = "greatest"
    LEAST = "least"
    # (expr, months, days, ms) with literal interval parts; subtraction
    # negates the parts at plan time
    ADD_INTERVAL = "add_interval"


@dataclass(frozen=True)
class CallUnary(ScalarExpr):
    func: str
    expr: ScalarExpr

    def typ(self, schema):
        inner = self.expr.typ(schema)
        f = self.func
        if f in (UnaryFunc.NOT,):
            return Column("f", ColumnType.BOOL, inner.nullable)
        if f == UnaryFunc.IS_NULL:
            return Column("f", ColumnType.BOOL, False)
        if f == UnaryFunc.CAST_INT64:
            return Column("f", ColumnType.INT64, inner.nullable)
        if f == UnaryFunc.CAST_INT32:
            return Column("f", ColumnType.INT32, inner.nullable)
        if f == UnaryFunc.CAST_FLOAT64:
            return Column("f", ColumnType.FLOAT64, inner.nullable)
        if f == UnaryFunc.CAST_BOOL:
            return Column("f", ColumnType.BOOL, inner.nullable)
        if f == UnaryFunc.CAST_DATE:
            return Column("f", ColumnType.DATE, inner.nullable)
        if f == UnaryFunc.CAST_TIMESTAMP:
            return Column("f", ColumnType.TIMESTAMP, inner.nullable)
        if f in _EXTRACT_INT_FUNCS:
            return Column("f", ColumnType.INT64, inner.nullable)
        if f in (UnaryFunc.EXTRACT_EPOCH, UnaryFunc.EXTRACT_SECOND):
            return Column("f", ColumnType.FLOAT64, inner.nullable)
        if f in (UnaryFunc.FLOOR, UnaryFunc.CEIL, UnaryFunc.TRUNC,
                 UnaryFunc.ROUND):
            # type-preserving on numerics (floor(numeric) is numeric)
            return inner
        if f in _FLOAT_UNARY_FUNCS:
            # domain errors (sqrt of negative, ln of nonpositive) yield
            # NULL here where the reference raises an EvalError
            nullable = inner.nullable or f in (
                UnaryFunc.SQRT, UnaryFunc.LN, UnaryFunc.LOG10,
                UnaryFunc.LOG2, UnaryFunc.ASIN, UnaryFunc.ACOS,
            )
            return Column("f", ColumnType.FLOAT64, nullable)
        if f == UnaryFunc.SIGN:
            return Column("f", ColumnType.INT64, inner.nullable)
        if f in UnaryFunc.DATE_TRUNCS.values():
            return Column("f", inner.ctype, inner.nullable)
        return inner  # NEG, ABS preserve type


@dataclass(frozen=True)
class CallBinary(ScalarExpr):
    func: str
    left: ScalarExpr
    right: ScalarExpr

    def typ(self, schema):
        lt_, rt = self.left.typ(schema), self.right.typ(schema)
        nullable = lt_.nullable or rt.nullable
        if self.func in (
            BinaryFunc.EQ,
            BinaryFunc.NEQ,
            BinaryFunc.LT,
            BinaryFunc.LTE,
            BinaryFunc.GT,
            BinaryFunc.GTE,
        ):
            return Column("f", ColumnType.BOOL, nullable)
        if self.func in (BinaryFunc.POWER, BinaryFunc.LOG_BASE):
            return Column("f", ColumnType.FLOAT64, True)
        if self.func == BinaryFunc.ROUND_TO:
            return Column("f", lt_.ctype, nullable, lt_.scale)
        if self.func == BinaryFunc.CAST_DECIMAL:
            assert isinstance(self.right, Literal)
            return Column(
                "f", ColumnType.DECIMAL, lt_.nullable, int(self.right.value)
            )
        if self.func == BinaryFunc.DIV:
            # SQL: division may produce NULL (div by zero -> error in MZ;
            # we produce NULL for now). int/int is INTEGER division
            # truncating toward zero (pg int4div/int8div); decimals keep
            # the left scale; anything float goes float.
            if lt_.ctype is ColumnType.DECIMAL:
                return Column("f", ColumnType.DECIMAL, True, lt_.scale)
            if lt_.ctype in (
                ColumnType.INT32, ColumnType.INT64
            ) and rt.ctype in (ColumnType.INT32, ColumnType.INT64):
                return Column("f", ColumnType.INT64, True)
            return Column("f", ColumnType.FLOAT64, True)
        # arithmetic: unify types
        ctype, scale = _unify_arith(lt_, rt, self.func)
        return Column("f", ctype, nullable, scale)


@dataclass(frozen=True)
class CallVariadic(ScalarExpr):
    func: str
    exprs: tuple

    def __init__(self, func, exprs):
        object.__setattr__(self, "func", func)
        object.__setattr__(self, "exprs", tuple(exprs))

    def typ(self, schema):
        if self.func.startswith(STRING_FUNC_PREFIX):
            from . import strings

            kind = strings.RESULT_KINDS[
                self.func[len(STRING_FUNC_PREFIX):]
            ]
            inner = self.exprs[0].typ(schema)
            if inner.ctype is not ColumnType.STRING:
                # gathering a non-code column through a dictionary
                # table would silently produce unrelated strings
                raise TypeError(
                    f"{self.func} requires a text operand, got "
                    f"{inner.ctype.value}"
                )
            ctype = {
                "str": ColumnType.STRING,
                "int": ColumnType.INT64,
                "bool": ColumnType.BOOL,
            }[kind]
            return Column("f", ctype, inner.nullable)
        if self.func in (VariadicFunc.AND, VariadicFunc.OR):
            nullable = any(e.typ(schema).nullable for e in self.exprs)
            return Column("f", ColumnType.BOOL, nullable)
        if self.func == VariadicFunc.COALESCE:
            first = self.exprs[0].typ(schema)
            nullable = all(e.typ(schema).nullable for e in self.exprs)
            return Column("f", first.ctype, nullable, first.scale)
        if self.func == VariadicFunc.ADD_INTERVAL:
            x = self.exprs[0].typ(schema)
            ms = self.exprs[3]
            has_ms = not (isinstance(ms, Literal) and ms.value == 0)
            if x.ctype is ColumnType.DATE and not has_ms:
                return Column("f", ColumnType.DATE, x.nullable)
            return Column("f", ColumnType.TIMESTAMP, x.nullable)
        if self.func in (VariadicFunc.GREATEST, VariadicFunc.LEAST):
            # unified numeric type; NULL inputs are skipped (pg semantics)
            typs = [e.typ(schema) for e in self.exprs]
            out = typs[0]
            for t in typs[1:]:
                ctype, scale = _unify_arith(out, t, BinaryFunc.ADD)
                out = Column("f", ctype, False, scale)
            nullable = all(t.nullable for t in typs)
            return Column("f", out.ctype, nullable, out.scale)
        raise NotImplementedError(self.func)


@dataclass(frozen=True)
class If(ScalarExpr):
    cond: ScalarExpr
    then: ScalarExpr
    els: ScalarExpr

    def _principal(self) -> str:
        """Which branch determines the result type: an untyped NULL
        literal defers to the other branch (CASE WHEN c THEN NULL
        ELSE 1.5 END is float, not int)."""
        if (
            isinstance(self.then, Literal)
            and self.then.value is None
            and not (
                isinstance(self.els, Literal) and self.els.value is None
            )
        ):
            return "els"
        return "then"

    def typ(self, schema):
        t = self.then.typ(schema)
        e = self.els.typ(schema)
        p = t if self._principal() == "then" else e
        return Column("f", p.ctype, t.nullable or e.nullable, p.scale)


def _unify_arith(lt_: Column, rt: Column, func: str) -> tuple[ColumnType, int]:
    a, b = lt_.ctype, rt.ctype
    if ColumnType.FLOAT64 in (a, b):
        return ColumnType.FLOAT64, 0
    if a is ColumnType.DECIMAL or b is ColumnType.DECIMAL:
        if func == BinaryFunc.MUL:
            return ColumnType.DECIMAL, lt_.scale + rt.scale
        scale = max(lt_.scale, rt.scale)
        return ColumnType.DECIMAL, scale
    if a is ColumnType.DATE and b in (ColumnType.INT32, ColumnType.INT64):
        return ColumnType.DATE, 0
    if ColumnType.INT64 in (a, b):
        return ColumnType.INT64, 0
    return a, 0


# ---------------------------------------------------------------------------
# Evaluation: trace-time construction of fused XLA ops


@dataclass
class Evaled:
    """An evaluated intermediate: column values + optional null mask."""

    values: jnp.ndarray
    nulls: jnp.ndarray | None
    col: Column  # type info

    def null_mask(self) -> jnp.ndarray:
        if self.nulls is None:
            return jnp.zeros(self.values.shape, dtype=bool)
        return self.nulls


def _to_decimal_scale(e: Evaled, scale: int) -> jnp.ndarray:
    """Rescale a decimal (or int) value array to the given decimal scale."""
    if e.col.ctype is ColumnType.DECIMAL:
        shift = scale - e.col.scale
    else:
        shift = scale
    v = e.values.astype(jnp.int64)
    if shift > 0:
        return v * (10**shift)
    if shift < 0:
        return v // (10 ** (-shift))
    return v


def eval_expr(expr: ScalarExpr, batch: Batch, time=None) -> Evaled:
    """Recursively build the XLA computation for `expr` over `batch`.

    ``time`` is the step's virtual timestamp, consumed by MzNow (the
    CallUnmaterializable mz_now() of expr/src/scalar.rs) — None outside
    a timed step, where MzNow is an error."""
    schema = batch.schema
    cap = batch.capacity

    if isinstance(expr, MzNow):
        if time is None:
            raise ValueError(
                "mz_now() evaluated outside a timed dataflow step"
            )
        vals = jnp.full(cap, time, dtype=jnp.int64)
        return Evaled(vals, None, expr.typ(schema))

    if isinstance(expr, ColumnRef):
        return Evaled(
            batch.cols[expr.index], batch.nulls[expr.index], schema[expr.index]
        )

    if isinstance(expr, Literal):
        col = expr.typ(schema)
        if expr.value is None:
            vals = jnp.zeros(cap, dtype=col.dtype)
            return Evaled(vals, jnp.ones(cap, dtype=bool), col)
        vals = jnp.full(cap, expr.value, dtype=col.dtype)
        return Evaled(vals, None, col)

    if isinstance(expr, CallUnary):
        e = eval_expr(expr.expr, batch, time)
        col = expr.typ(schema)
        f = expr.func
        if f == UnaryFunc.NOT:
            return Evaled(jnp.logical_not(e.values), e.nulls, col)
        if f == UnaryFunc.NEG:
            return Evaled(-e.values, e.nulls, col)
        if f == UnaryFunc.ABS:
            return Evaled(jnp.abs(e.values), e.nulls, col)
        if f == UnaryFunc.IS_NULL:
            return Evaled(e.null_mask(), None, col)
        if f == UnaryFunc.CAST_INT64:
            if e.col.ctype is ColumnType.DECIMAL:
                v = e.values // (10**e.col.scale)
            elif e.col.ctype is ColumnType.FLOAT64:
                from . import errors as _err

                x = e.values
                # asymmetric bounds: -2^63 is exactly representable
                bad = (
                    jnp.isnan(x)
                    | (x >= float(2**63))
                    | (x < -float(2**63))
                )
                _err.emit(
                    _err.NUMERIC_OUT_OF_RANGE,
                    jnp.logical_and(bad, jnp.logical_not(e.null_mask())),
                )
                v = jnp.where(bad, 0.0, x).astype(jnp.int64)
                return Evaled(v, _or_nulls(e.nulls, bad), col)
            else:
                v = e.values.astype(jnp.int64)
            return Evaled(v, e.nulls, col)
        if f == UnaryFunc.CAST_FLOAT64:
            if e.col.ctype is ColumnType.DECIMAL:
                v = e.values.astype(jnp.float64) / (10.0**e.col.scale)
            else:
                v = e.values.astype(jnp.float64)
            return Evaled(v, e.nulls, col)
        if f == UnaryFunc.CAST_INT32:
            if e.col.ctype is ColumnType.DECIMAL:
                v = (e.values // (10**e.col.scale)).astype(jnp.int32)
            else:
                from . import errors as _err

                x = e.values
                if e.col.ctype is ColumnType.FLOAT64:
                    bad = (
                        jnp.isnan(x)
                        | (x >= float(2**31))
                        | (x < -float(2**31))
                    )
                    x = jnp.where(bad, 0.0, x)
                else:
                    xi = x.astype(jnp.int64)
                    bad = jnp.logical_or(
                        xi >= 2**31, xi < -(2**31)
                    )
                _err.emit(
                    _err.NUMERIC_OUT_OF_RANGE,
                    jnp.logical_and(bad, jnp.logical_not(e.null_mask())),
                )
                v = x.astype(jnp.int32)
                v = jnp.where(bad, 0, v)
                return Evaled(v, _or_nulls(e.nulls, bad), col)
            return Evaled(v, e.nulls, col)
        if f == UnaryFunc.CAST_BOOL:
            return Evaled(e.values != 0, e.nulls, col)
        if f == UnaryFunc.CAST_DATE:
            if e.col.ctype is ColumnType.TIMESTAMP:
                v = (e.values.astype(jnp.int64) // _MS_PER_DAY).astype(
                    jnp.int32
                )
            else:
                v = e.values.astype(jnp.int32)
            return Evaled(v, e.nulls, col)
        if f == UnaryFunc.CAST_TIMESTAMP:
            if e.col.ctype is ColumnType.DATE:
                v = e.values.astype(jnp.int64) * _MS_PER_DAY
            else:
                v = e.values.astype(jnp.int64)
            return Evaled(v, e.nulls, col)
        if f in _EXTRACT_INT_FUNCS or f in (
            UnaryFunc.EXTRACT_EPOCH,
            UnaryFunc.EXTRACT_SECOND,
        ):
            return _eval_extract(f, e, col)
        if f in UnaryFunc.DATE_TRUNCS.values():
            return _eval_date_trunc(f, e, col)
        if f in (UnaryFunc.FLOOR, UnaryFunc.CEIL, UnaryFunc.TRUNC,
                 UnaryFunc.ROUND):
            return _eval_round_family(f, e, col)
        if f in _FLOAT_UNARY_FUNCS:
            x = _as_float(e)
            if f == UnaryFunc.SQRT:
                bad = x < 0.0
                v = jnp.sqrt(jnp.where(bad, 0.0, x))
                return Evaled(v, _or_nulls(e.nulls, bad), col)
            if f in (UnaryFunc.LN, UnaryFunc.LOG10, UnaryFunc.LOG2):
                bad = x <= 0.0
                safe = jnp.where(bad, 1.0, x)
                v = {
                    UnaryFunc.LN: jnp.log,
                    UnaryFunc.LOG10: lambda a: jnp.log(a)
                    / jnp.log(10.0),
                    UnaryFunc.LOG2: jnp.log2,
                }[f](safe)
                return Evaled(v, _or_nulls(e.nulls, bad), col)
            if f in (UnaryFunc.ASIN, UnaryFunc.ACOS):
                bad = jnp.abs(x) > 1.0
                safe = jnp.where(bad, 0.0, x)
                op = jnp.arcsin if f == UnaryFunc.ASIN else jnp.arccos
                return Evaled(op(safe), _or_nulls(e.nulls, bad), col)
            op = {
                UnaryFunc.CBRT: jnp.cbrt,
                UnaryFunc.EXP: jnp.exp,
                UnaryFunc.SIN: jnp.sin,
                UnaryFunc.COS: jnp.cos,
                UnaryFunc.TAN: jnp.tan,
                UnaryFunc.ATAN: jnp.arctan,
                UnaryFunc.RADIANS: jnp.radians,
                UnaryFunc.DEGREES: jnp.degrees,
            }[f]
            return Evaled(op(x), e.nulls, col)
        if f == UnaryFunc.SIGN:
            v = jnp.sign(
                _as_float(e) if e.col.ctype is ColumnType.FLOAT64
                else e.values
            ).astype(jnp.int64)
            return Evaled(v, e.nulls, col)
        raise NotImplementedError(f)

    if isinstance(expr, CallBinary):
        l = eval_expr(expr.left, batch, time)
        r = eval_expr(expr.right, batch, time)
        col = expr.typ(schema)
        nulls = _merge_nulls(l, r)
        f = expr.func
        if f in (
            BinaryFunc.EQ,
            BinaryFunc.NEQ,
            BinaryFunc.LT,
            BinaryFunc.LTE,
            BinaryFunc.GT,
            BinaryFunc.GTE,
        ):
            # Strings compare directly: dictionary codes are
            # order-preserving labels (repr/schema.py StringDictionary),
            # so integer comparison == lexicographic comparison.
            lv, rv = _coerce_comparable(l, r)
            op = {
                BinaryFunc.EQ: jnp.equal,
                BinaryFunc.NEQ: jnp.not_equal,
                BinaryFunc.LT: jnp.less,
                BinaryFunc.LTE: jnp.less_equal,
                BinaryFunc.GT: jnp.greater,
                BinaryFunc.GTE: jnp.greater_equal,
            }[f]
            return Evaled(op(lv, rv), nulls, col)
        if col.ctype is ColumnType.DECIMAL:
            if f == BinaryFunc.MUL:
                v = l.values.astype(jnp.int64) * r.values.astype(jnp.int64)
                return Evaled(v, nulls, col)
            lv = _to_decimal_scale(l, col.scale)
            rv = _to_decimal_scale(r, col.scale)
            if f == BinaryFunc.ADD:
                return Evaled(lv + rv, nulls, col)
            if f == BinaryFunc.SUB:
                return Evaled(lv - rv, nulls, col)
            if f == BinaryFunc.DIV:
                # decimal / decimal at left scale; the zero-divisor rows
                # become NULL here and surface through the error stream
                # (render.rs ok/err trees) when a collector is active
                zero = rv == 0
                from . import errors as _err

                _err.emit(
                    _err.DIVISION_BY_ZERO,
                    # pg: NULL numerator or divisor yields NULL, no error
                    jnp.logical_and(
                        zero,
                        jnp.logical_not(
                            jnp.logical_or(r.null_mask(), l.null_mask())
                        ),
                    ),
                )
                safe = jnp.where(zero, 1, rv)
                # Both operands are at col.scale after rescaling, so
                # the scale-preserving quotient multiplies by
                # 10^col.scale (NOT the divisor's original scale —
                # decimal/int division like avg's sum/count would
                # otherwise come out 10^scale too small).
                v = (lv * (10**col.scale)) // safe
                nulls = _or_nulls(nulls, zero)
                return Evaled(v, nulls, col)
        if f == BinaryFunc.ADD:
            return Evaled(l.values + r.values, nulls, col)
        if f == BinaryFunc.SUB:
            return Evaled(l.values - r.values, nulls, col)
        if f == BinaryFunc.MUL:
            return Evaled(l.values * r.values, nulls, col)
        if f == BinaryFunc.DIV:
            from . import errors as _err

            if col.ctype is ColumnType.INT64:
                # integer division truncates toward zero (pg int8div;
                # jnp // floors, wrong for mixed signs)
                li = l.values.astype(jnp.int64)
                ri = r.values.astype(jnp.int64)
                zero = ri == 0
                _err.emit(
                    _err.DIVISION_BY_ZERO,
                    jnp.logical_and(
                        zero,
                        jnp.logical_not(
                            jnp.logical_or(r.null_mask(), l.null_mask())
                        ),
                    ),
                )
                safe = jnp.where(zero, 1, ri)
                q = jnp.abs(li) // jnp.abs(safe)
                v = jnp.where(jnp.sign(li) == jnp.sign(safe), q, -q)
                return Evaled(v, _or_nulls(nulls, zero), col)
            lv = _as_float(l)
            rv = _as_float(r)
            zero = rv == 0.0
            _err.emit(
                _err.DIVISION_BY_ZERO,
                # pg: NULL numerator or divisor yields NULL, no error
                jnp.logical_and(
                    zero,
                    jnp.logical_not(
                        jnp.logical_or(r.null_mask(), l.null_mask())
                    ),
                ),
            )
            v = lv / jnp.where(zero, 1.0, rv)
            return Evaled(v, _or_nulls(nulls, zero), col)
        if f == BinaryFunc.MOD:
            from . import errors as _err

            zero = r.values == 0
            _err.emit(
                _err.DIVISION_BY_ZERO,
                # pg: NULL numerator or divisor yields NULL, no error
                jnp.logical_and(
                    zero,
                    jnp.logical_not(
                        jnp.logical_or(r.null_mask(), l.null_mask())
                    ),
                ),
            )
            # pg mod truncates toward zero: result takes the DIVIDEND's
            # sign (jnp % floors, giving the divisor's sign). Floats use
            # fmod (already truncating); the integer path also covers
            # DECIMAL (scaled-int mod IS decimal mod at that scale).
            if col.ctype is ColumnType.FLOAT64:
                lv, rv = _as_float(l), _as_float(r)
                v = jnp.fmod(lv, jnp.where(zero, 1.0, rv))
                return Evaled(
                    jnp.where(zero, 0.0, v), _or_nulls(nulls, zero), col
                )
            li = l.values.astype(jnp.int64)
            ri = jnp.where(zero, 1, r.values.astype(jnp.int64))
            q = jnp.abs(li) // jnp.abs(ri)
            tq = jnp.where(jnp.sign(li) == jnp.sign(ri), q, -q)
            v = jnp.where(zero, 0, li - tq * ri)
            if l.values.dtype != jnp.int64:
                v = v.astype(l.values.dtype)
            return Evaled(v, _or_nulls(nulls, zero), col)
        if f == BinaryFunc.POWER:
            lv, rv = _as_float(l), _as_float(r)
            v = jnp.power(lv, rv)
            bad = jnp.isnan(v) | jnp.isinf(v)
            return Evaled(
                jnp.where(bad, 0.0, v), _or_nulls(nulls, bad), col
            )
        if f == BinaryFunc.LOG_BASE:
            b, x = _as_float(l), _as_float(r)
            bad = (b <= 0.0) | (b == 1.0) | (x <= 0.0)
            v = jnp.log(jnp.where(bad, 2.0, x)) / jnp.log(
                jnp.where(bad, 2.0, b)
            )
            return Evaled(v, _or_nulls(nulls, bad), col)
        if f == BinaryFunc.CAST_DECIMAL:
            scale = col.scale
            if l.col.ctype is ColumnType.FLOAT64:
                v = jnp.round(l.values * (10.0**scale)).astype(jnp.int64)
            elif (
                l.col.ctype is ColumnType.DECIMAL and l.col.scale > scale
            ):
                # narrowing rescale rounds half away from zero (pg numeric)
                v = _round_half_away(
                    l.values, 10 ** (l.col.scale - scale), rescale=True
                )
            else:
                v = _to_decimal_scale(l, scale)
            return Evaled(v, l.nulls, col)
        if f == BinaryFunc.ROUND_TO:
            if not isinstance(expr.right, Literal):
                raise NotImplementedError("round(x, n): n must be a literal")
            n = int(expr.right.value)
            if l.col.ctype is ColumnType.FLOAT64:
                factor = 10.0**n
                v = jnp.round(l.values * factor) / factor
                return Evaled(v, nulls, col)
            if l.col.ctype is ColumnType.DECIMAL:
                drop = l.col.scale - n
                if drop <= 0:
                    return Evaled(l.values, nulls, col)
                v = _round_half_away(l.values, 10**drop)
                return Evaled(v, nulls, col)
            if n < 0:  # integers: round(123, -1) = 120 (pg numeric)
                v = _round_half_away(
                    l.values.astype(jnp.int64), 10 ** (-n)
                ).astype(l.values.dtype)
                return Evaled(v, nulls, col)
            return Evaled(l.values, nulls, col)
        raise NotImplementedError(f)

    if isinstance(expr, CallVariadic):
        col = expr.typ(schema)
        if expr.func.startswith(STRING_FUNC_PREFIX):
            from . import strings

            fn = expr.func[len(STRING_FUNC_PREFIX):]
            key = _string_func_key(fn, expr.exprs[1:])
            e = eval_expr(expr.exprs[0], batch, time)
            vals = strings.lookup(strings.trace_env()[key], e.values)
            return Evaled(vals, e.nulls, col)
        if expr.func == VariadicFunc.COALESCE:
            # pg evaluates COALESCE arguments in order until the first
            # non-NULL: an argument's evaluation errors only count for
            # rows that actually REACH it (all earlier args NULL).
            from . import errors as _err

            evaled, masksets = [], []
            for x in expr.exprs:
                with _err.collect() as m:
                    evaled.append(eval_expr(x, batch, time))
                masksets.append(m)
            reached = jnp.ones(cap, dtype=bool)
            for p, ms_ in zip(evaled, masksets):
                for code, mask in ms_:
                    _err.emit(code, jnp.logical_and(mask, reached))
                reached = jnp.logical_and(reached, p.null_mask())
            out_v = evaled[-1].values
            out_n = evaled[-1].null_mask()
            for p in reversed(evaled[:-1]):
                take = jnp.logical_not(p.null_mask())
                out_v = jnp.where(take, p.values, out_v)
                out_n = jnp.where(take, jnp.zeros_like(out_n), out_n)
            return Evaled(out_v, out_n, col)
        parts = [eval_expr(e, batch, time) for e in expr.exprs]
        if expr.func == VariadicFunc.AND:
            # SQL 3VL: FALSE dominates NULL
            val = jnp.ones(cap, dtype=bool)
            known_false = jnp.zeros(cap, dtype=bool)
            any_null = jnp.zeros(cap, dtype=bool)
            for p in parts:
                val = jnp.logical_and(val, p.values)
                known_false = jnp.logical_or(
                    known_false,
                    jnp.logical_and(
                        jnp.logical_not(p.values),
                        jnp.logical_not(p.null_mask()),
                    ),
                )
                any_null = jnp.logical_or(any_null, p.null_mask())
            nulls = jnp.logical_and(any_null, jnp.logical_not(known_false))
            return Evaled(
                jnp.logical_and(val, jnp.logical_not(known_false)), nulls, col
            )
        if expr.func == VariadicFunc.OR:
            val = jnp.zeros(cap, dtype=bool)
            known_true = jnp.zeros(cap, dtype=bool)
            any_null = jnp.zeros(cap, dtype=bool)
            for p in parts:
                val = jnp.logical_or(val, p.values)
                known_true = jnp.logical_or(
                    known_true,
                    jnp.logical_and(p.values, jnp.logical_not(p.null_mask())),
                )
                any_null = jnp.logical_or(any_null, p.null_mask())
            nulls = jnp.logical_and(any_null, jnp.logical_not(known_true))
            return Evaled(val, nulls, col)
        if expr.func == VariadicFunc.ADD_INTERVAL:
            e = parts[0]
            months, days, ms = (
                int(x.value) for x in expr.exprs[1:]  # plan-time literals
            )
            dd, msod = _days_and_ms(e)
            if months:
                y, m, d = _civil_from_days(dd)
                m0 = m - 1 + months
                y2 = y + m0 // 12
                m2 = m0 % 12 + 1
                # clamp to the target month's last day (pg semantics)
                next_month_start = _days_from_civil(
                    y2 + (m2 == 12), jnp.where(m2 == 12, 1, m2 + 1),
                    jnp.ones_like(m2),
                )
                month_len = next_month_start - _days_from_civil(
                    y2, m2, jnp.ones_like(m2)
                )
                d2 = jnp.minimum(d, month_len)
                dd = _days_from_civil(y2, m2, d2)
            dd = dd + days
            if col.ctype is ColumnType.DATE:
                return Evaled(dd.astype(col.dtype), e.nulls, col)
            return Evaled(dd * _MS_PER_DAY + msod + ms, e.nulls, col)
        if expr.func in (VariadicFunc.GREATEST, VariadicFunc.LEAST):
            # pg semantics: NULL arguments are ignored; result is NULL
            # only when every argument is NULL
            if col.ctype is ColumnType.FLOAT64:
                coerced = [_as_float(p) for p in parts]
            elif col.ctype is ColumnType.DECIMAL:
                coerced = [_to_decimal_scale(p, col.scale) for p in parts]
            else:
                coerced = [p.values.astype(col.dtype) for p in parts]
            better = (
                jnp.greater
                if expr.func == VariadicFunc.GREATEST
                else jnp.less
            )
            acc_v = coerced[0]
            acc_n = parts[0].null_mask()
            for p, v in zip(parts[1:], coerced[1:]):
                pn = p.null_mask()
                take = jnp.logical_and(
                    jnp.logical_not(pn),
                    jnp.logical_or(acc_n, better(v, acc_v)),
                )
                acc_v = jnp.where(take, v, acc_v)
                acc_n = jnp.logical_and(acc_n, pn)
            return Evaled(acc_v, acc_n, col)
        raise NotImplementedError(expr.func)

    if isinstance(expr, If):
        from . import errors as _err

        c = eval_expr(expr.cond, batch, time)
        # CASE/If is SQL's error guard: both branches evaluate
        # vectorized, but a branch's evaluation errors only count for
        # rows that actually SELECT that branch (the reference's MfpPlan
        # evaluates per-row lazily; here the masks are filtered).
        cond_sel = jnp.logical_and(
            c.values, jnp.logical_not(c.null_mask())
        )
        with _err.collect() as t_masks:
            t = eval_expr(expr.then, batch, time)
        with _err.collect() as e_masks:
            e = eval_expr(expr.els, batch, time)
        for code, m in t_masks:
            _err.emit(code, jnp.logical_and(m, cond_sel))
        for code, m in e_masks:
            _err.emit(
                code, jnp.logical_and(m, jnp.logical_not(cond_sel))
            )
        col = expr.typ(schema)
        cond = cond_sel
        tv, ev = t.values, e.values
        # branches of different device dtypes (an untyped NULL literal):
        # the principal branch (If.typ) defines the type; the NULL
        # branch's zeros are cast to it (values there are masked anyway)
        if ev.dtype != tv.dtype:
            if expr._principal() == "then":
                ev = ev.astype(tv.dtype)
            else:
                tv = tv.astype(ev.dtype)
        vals = jnp.where(cond, tv, ev)
        nulls = jnp.where(cond, t.null_mask(), e.null_mask())
        return Evaled(vals, nulls, col)

    raise NotImplementedError(type(expr))


def _merge_nulls(l: Evaled, r: Evaled):
    if l.nulls is None and r.nulls is None:
        return None
    return jnp.logical_or(l.null_mask(), r.null_mask())


def _or_nulls(nulls, extra):
    if nulls is None:
        return extra
    return jnp.logical_or(nulls, extra)


def _round_half_away(v: jnp.ndarray, step: int, rescale: bool = False):
    """Round a scaled integer to a multiple of ``step``, half away from
    zero (pg numeric). ``rescale`` divides the result by step (narrowing
    a decimal's scale) instead of keeping the original scale."""
    mag = (jnp.abs(v) + step // 2) // step
    if not rescale:
        mag = mag * step
    return jnp.sign(v) * mag


def _as_float(e: Evaled) -> jnp.ndarray:
    if e.col.ctype is ColumnType.DECIMAL:
        return e.values.astype(jnp.float64) / (10.0**e.col.scale)
    return e.values.astype(jnp.float64)


def _coerce_comparable(l: Evaled, r: Evaled):
    """Align decimal scales / numeric types for comparison."""
    if (
        l.col.ctype is ColumnType.DECIMAL
        or r.col.ctype is ColumnType.DECIMAL
    ) and ColumnType.FLOAT64 not in (l.col.ctype, r.col.ctype):
        scale = max(l.col.scale, r.col.scale)
        return _to_decimal_scale(l, scale), _to_decimal_scale(r, scale)
    if ColumnType.FLOAT64 in (l.col.ctype, r.col.ctype):
        return _as_float(l), _as_float(r)
    return l.values, r.values


def _civil_from_days(days: jnp.ndarray):
    """Howard Hinnant's civil_from_days, vectorized: (year, month, day)
    int64 arrays from days-since-epoch (proleptic Gregorian)."""
    z = days + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    return jnp.where(m <= 2, y + 1, y), m, d


def _days_from_civil(y, m, d):
    """Inverse of _civil_from_days, vectorized (proleptic Gregorian)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


_EXTRACT_INT_FUNCS = frozenset(
    {
        UnaryFunc.EXTRACT_YEAR,
        UnaryFunc.EXTRACT_MONTH,
        UnaryFunc.EXTRACT_DAY,
        UnaryFunc.EXTRACT_QUARTER,
        UnaryFunc.EXTRACT_DOW,
        UnaryFunc.EXTRACT_ISODOW,
        UnaryFunc.EXTRACT_DOY,
        UnaryFunc.EXTRACT_WEEK,
        UnaryFunc.EXTRACT_HOUR,
        UnaryFunc.EXTRACT_MINUTE,
        UnaryFunc.EXTRACT_MILLENNIUM,
        UnaryFunc.EXTRACT_CENTURY,
        UnaryFunc.EXTRACT_DECADE,
    }
)

_FLOAT_UNARY_FUNCS = frozenset(
    {
        UnaryFunc.SQRT,
        UnaryFunc.CBRT,
        UnaryFunc.EXP,
        UnaryFunc.LN,
        UnaryFunc.LOG10,
        UnaryFunc.LOG2,
        UnaryFunc.SIN,
        UnaryFunc.COS,
        UnaryFunc.TAN,
        UnaryFunc.ASIN,
        UnaryFunc.ACOS,
        UnaryFunc.ATAN,
        UnaryFunc.RADIANS,
        UnaryFunc.DEGREES,
    }
)

_MS_PER_DAY = 86_400_000


def _days_and_ms(e: Evaled):
    """(days-since-epoch, ms-of-day) for a DATE or TIMESTAMP input."""
    if e.col.ctype is ColumnType.TIMESTAMP:
        ms = e.values.astype(jnp.int64)
        return ms // _MS_PER_DAY, ms % _MS_PER_DAY
    return e.values.astype(jnp.int64), jnp.zeros_like(
        e.values, dtype=jnp.int64
    )


def _eval_extract(f: str, e: Evaled, col: Column) -> Evaled:
    days, msod = _days_and_ms(e)
    if f == UnaryFunc.EXTRACT_EPOCH:
        if e.col.ctype is ColumnType.TIMESTAMP:
            v = e.values.astype(jnp.float64) / 1000.0
        else:
            v = days.astype(jnp.float64) * 86400.0
        return Evaled(v, e.nulls, col)
    if f == UnaryFunc.EXTRACT_HOUR:
        return Evaled(msod // 3_600_000, e.nulls, col)
    if f == UnaryFunc.EXTRACT_MINUTE:
        return Evaled((msod // 60_000) % 60, e.nulls, col)
    if f == UnaryFunc.EXTRACT_SECOND:
        v = (msod % 60_000).astype(jnp.float64) / 1000.0
        return Evaled(v, e.nulls, col)
    if f == UnaryFunc.EXTRACT_DOW:
        # pg: Sunday=0..Saturday=6; 1970-01-01 was a Thursday
        return Evaled((days + 4) % 7, e.nulls, col)
    if f == UnaryFunc.EXTRACT_ISODOW:
        return Evaled((days + 3) % 7 + 1, e.nulls, col)
    y, m, d = _civil_from_days(days)
    if f == UnaryFunc.EXTRACT_YEAR:
        return Evaled(y, e.nulls, col)
    if f == UnaryFunc.EXTRACT_MONTH:
        return Evaled(m, e.nulls, col)
    if f == UnaryFunc.EXTRACT_DAY:
        return Evaled(d, e.nulls, col)
    if f == UnaryFunc.EXTRACT_QUARTER:
        return Evaled((m + 2) // 3, e.nulls, col)
    if f == UnaryFunc.EXTRACT_DOY:
        return Evaled(days - _days_from_civil(y, 1, 1) + 1, e.nulls, col)
    if f == UnaryFunc.EXTRACT_WEEK:
        # ISO 8601 week: the week containing this date's Thursday
        thursday = days + (3 - (days + 3) % 7)
        ty, _, _ = _civil_from_days(thursday)
        week = (thursday - _days_from_civil(ty, 1, 1)) // 7 + 1
        return Evaled(week, e.nulls, col)
    if f == UnaryFunc.EXTRACT_MILLENNIUM:
        return Evaled((y - 1) // 1000 + 1, e.nulls, col)
    if f == UnaryFunc.EXTRACT_CENTURY:
        return Evaled((y - 1) // 100 + 1, e.nulls, col)
    if f == UnaryFunc.EXTRACT_DECADE:
        return Evaled(y // 10, e.nulls, col)
    raise NotImplementedError(f)


def _eval_date_trunc(f: str, e: Evaled, col: Column) -> Evaled:
    days, msod = _days_and_ms(e)
    T = UnaryFunc
    if f in (T.DATE_TRUNC_YEAR, T.DATE_TRUNC_QUARTER, T.DATE_TRUNC_MONTH):
        y, m, _ = _civil_from_days(days)
        if f == T.DATE_TRUNC_YEAR:
            tdays = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(m))
        elif f == T.DATE_TRUNC_QUARTER:
            qm = 3 * ((m - 1) // 3) + 1
            tdays = _days_from_civil(y, qm, jnp.ones_like(m))
        else:
            tdays = _days_from_civil(y, m, jnp.ones_like(m))
        tmsod = jnp.zeros_like(msod)
    elif f == T.DATE_TRUNC_WEEK:
        tdays = days - (days + 3) % 7  # back to Monday
        tmsod = jnp.zeros_like(msod)
    elif f == T.DATE_TRUNC_DAY:
        tdays, tmsod = days, jnp.zeros_like(msod)
    else:
        step = {
            T.DATE_TRUNC_HOUR: 3_600_000,
            T.DATE_TRUNC_MINUTE: 60_000,
            T.DATE_TRUNC_SECOND: 1_000,
        }[f]
        tdays, tmsod = days, msod - msod % step
    if e.col.ctype is ColumnType.TIMESTAMP:
        return Evaled(tdays * _MS_PER_DAY + tmsod, e.nulls, col)
    return Evaled(tdays.astype(e.values.dtype), e.nulls, col)


def _eval_round_family(f: str, e: Evaled, col: Column) -> Evaled:
    T = UnaryFunc
    if e.col.ctype is ColumnType.FLOAT64:
        op = {
            T.FLOOR: jnp.floor,
            T.CEIL: jnp.ceil,
            T.TRUNC: jnp.trunc,
            T.ROUND: jnp.round,  # half-even, like pg float8
        }[f]
        return Evaled(op(e.values), e.nulls, col)
    if e.col.ctype is ColumnType.DECIMAL and e.col.scale > 0:
        step = 10**e.col.scale
        v = e.values
        if f == T.FLOOR:
            out = (v // step) * step
        elif f == T.CEIL:
            out = -((-v) // step) * step
        elif f == T.TRUNC:
            out = jnp.where(v >= 0, v // step, -((-v) // step)) * step
        else:  # ROUND: half away from zero, like pg numeric
            out = _round_half_away(v, step)
        return Evaled(out, e.nulls, col)
    return Evaled(e.values, e.nulls, col)  # integers unchanged


# Convenience helpers for building expressions in tests/plans.
def col(i: int) -> ColumnRef:
    return ColumnRef(i)


def lit(value, ctype: ColumnType | None = None, scale: int = 0) -> Literal:
    if ctype is None:
        return _lift(value)
    return Literal(value, ctype, scale)


def and_(*exprs) -> CallVariadic:
    return CallVariadic(VariadicFunc.AND, [_lift(e) for e in exprs])


def or_(*exprs) -> CallVariadic:
    return CallVariadic(VariadicFunc.OR, [_lift(e) for e in exprs])
