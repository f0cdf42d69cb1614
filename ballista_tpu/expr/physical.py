"""Compile logical expressions into device evaluators.

The DataFusion ``PhysicalExpr`` equivalent (the reference serializes those at
ballista/rust/core/src/serde/physical_plan/to_proto.rs:252-458 /
from_proto.rs). A compiled expression evaluates against a
:class:`~ballista_tpu.columnar.batch.DeviceBatch` and returns a
:class:`ColumnValue` — one jnp array (full batch capacity), an optional null
mask, and a host dictionary for STRING results.

Evaluation happens at trace time inside whatever ``jit`` wraps the operator,
so Python-level dispatch on dtypes/dictionaries is free: string predicates
are resolved against the (small, sorted, order-preserving) dictionary on
host and become pure code arithmetic on device — no string bytes ever reach
the TPU (SURVEY.md §7 "Strings/dictionaries on TPU").

SQL three-valued logic: AND/OR use Kleene semantics; comparisons and
arithmetic propagate null as the OR of operand nulls.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ballista_tpu.columnar.batch import DeviceBatch, Dictionary
from ballista_tpu.columnar import dict_util
from ballista_tpu.datatypes import DataType, Schema, common_type
from ballista_tpu.errors import PlanError
from ballista_tpu.expr import logical as L


@dataclasses.dataclass
class ColumnValue:
    """One evaluated expression column (capacity-length device array)."""

    values: jnp.ndarray
    nulls: jnp.ndarray | None
    dtype: DataType
    dictionary: Dictionary | None = None

    def null_or(self, other: "ColumnValue") -> jnp.ndarray | None:
        if self.nulls is None:
            return other.nulls
        if other.nulls is None:
            return self.nulls
        return self.nulls | other.nulls


def _or_nulls(*masks: jnp.ndarray | None) -> jnp.ndarray | None:
    out = None
    for m in masks:
        if m is None:
            continue
        out = m if out is None else (out | m)
    return out


class PhysExpr:
    """A compiled expression: static dtype + evaluate(batch)."""

    def __init__(self, dtype: DataType, fn, display: str):
        self.dtype = dtype
        self._fn = fn
        self.display = display

    def evaluate(self, batch: DeviceBatch) -> ColumnValue:
        return self._fn(batch)

    def __repr__(self) -> str:
        return f"PhysExpr({self.display})"


def compile_expr(expr: L.Expr, schema: Schema) -> PhysExpr:
    """Logical expression -> device evaluator against ``schema`` batches."""
    dtype = expr.data_type(schema)
    fn = _compile(expr, schema)
    return PhysExpr(dtype, fn, expr.name())


def _compile(expr: L.Expr, schema: Schema):
    if isinstance(expr, L.Alias):
        return _compile(expr.expr, schema)
    if isinstance(expr, L.Column):
        return _compile_column(expr, schema)
    if isinstance(expr, L.Literal):
        return _compile_literal(expr)
    if isinstance(expr, L.IntervalLiteral):
        return _compile_interval(expr)
    if isinstance(expr, L.BinaryExpr):
        return _compile_binary(expr, schema)
    if isinstance(expr, L.Not):
        return _compile_not(expr, schema)
    if isinstance(expr, L.Negative):
        return _compile_negative(expr, schema)
    if isinstance(expr, (L.IsNull, L.IsNotNull)):
        return _compile_is_null(expr, schema)
    if isinstance(expr, L.Cast):
        return _compile_cast(expr, schema)
    if isinstance(expr, L.Case):
        return _compile_case(expr, schema)
    if isinstance(expr, L.Between):
        low = L.BinaryExpr(expr.expr, L.Operator.GTEQ, expr.low)
        high = L.BinaryExpr(expr.expr, L.Operator.LTEQ, expr.high)
        both: L.Expr = L.BinaryExpr(low, L.Operator.AND, high)
        if expr.negated:
            both = L.Not(both)
        return _compile(both, schema)
    if isinstance(expr, L.InList):
        return _compile_in_list(expr, schema)
    if isinstance(expr, L.Like):
        return _compile_like(expr, schema)
    if isinstance(expr, L.ScalarFunction):
        return _compile_scalar_fn(expr, schema)
    if isinstance(expr, L.AggregateExpr):
        raise PlanError(
            f"aggregate {expr.name()} cannot be compiled as a row expression; "
            "the physical planner must split it into an Aggregate operator"
        )
    raise PlanError(f"cannot compile expression {expr!r}")


# -- leaves -------------------------------------------------------------------


def _compile_column(expr: L.Column, schema: Schema):
    idx = L.resolve_field_index(schema, expr.cname)
    field = schema.fields[idx]

    def fn(batch: DeviceBatch) -> ColumnValue:
        d = None
        if field.dtype == DataType.STRING:
            d = batch.dictionaries.get(batch.schema.fields[idx].name)
        return ColumnValue(batch.columns[idx], batch.nulls[idx], field.dtype, d)

    return fn


def _compile_literal(expr: L.Literal):
    dtype = expr.dtype

    def fn(batch: DeviceBatch) -> ColumnValue:
        cap = batch.capacity
        if expr.value is None:
            if dtype == DataType.NULL:
                return ColumnValue(
                    jnp.zeros(cap, dtype=bool), jnp.ones(cap, dtype=bool),
                    DataType.NULL,
                )
            # typed NULL (e.g. the FULL-join padding columns): carrier
            # zeros of the declared dtype under an all-null mask
            if dtype == DataType.STRING:
                return ColumnValue(
                    jnp.zeros(cap, dtype=jnp.int32),
                    jnp.ones(cap, dtype=bool),
                    dtype,
                    Dictionary(()),
                )
            return ColumnValue(
                jnp.zeros(cap, dtype=dtype.to_np()),
                jnp.ones(cap, dtype=bool),
                dtype,
            )
        if dtype == DataType.STRING:
            return ColumnValue(
                jnp.zeros(cap, dtype=jnp.int32), None, dtype,
                Dictionary((expr.value,)),
            )
        np_dtype = dtype.to_np()
        return ColumnValue(
            jnp.full(cap, expr.value, dtype=np_dtype), None, dtype
        )

    return fn


def _compile_interval(expr: L.IntervalLiteral):
    if expr.months:
        raise PlanError(
            f"{expr.name()} with months reached device compilation; "
            "month intervals must be constant-folded against date literals"
        )

    def fn(batch: DeviceBatch) -> ColumnValue:
        return ColumnValue(
            jnp.full(batch.capacity, expr.days, dtype=jnp.int32),
            None,
            DataType.INT32,
        )

    return fn


# -- binary -------------------------------------------------------------------

_CMP = {
    L.Operator.EQ: lambda a, b: a == b,
    L.Operator.NEQ: lambda a, b: a != b,
    L.Operator.LT: lambda a, b: a < b,
    L.Operator.LTEQ: lambda a, b: a <= b,
    L.Operator.GT: lambda a, b: a > b,
    L.Operator.GTEQ: lambda a, b: a >= b,
}


def _trunc_div(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """SQL integer division truncates toward zero (jnp // floors)."""
    safe_b = jnp.where(b == 0, jnp.ones_like(b), b)
    q = jnp.abs(a) // jnp.abs(safe_b)
    return jnp.where((a < 0) != (b < 0), -q, q).astype(a.dtype)


def _compile_binary(expr: L.BinaryExpr, schema: Schema):
    op = expr.op
    lf = _compile(expr.left, schema)
    rf = _compile(expr.right, schema)
    lt = expr.left.data_type(schema)
    rt = expr.right.data_type(schema)

    if op.is_logical:
        return _compile_logical(op, lf, rf)

    if DataType.STRING in (lt, rt) and op.is_comparison:
        return _compile_string_cmp(op, lf, rf, lt, rt)
    if DataType.STRING in (lt, rt):
        raise PlanError(f"arithmetic on strings: {expr.name()}")

    out_dtype = expr.data_type(schema)

    def fn(batch: DeviceBatch) -> ColumnValue:
        lv = lf(batch)
        rv = rf(batch)
        nulls = _or_nulls(lv.nulls, rv.nulls)
        a, b = lv.values, rv.values
        if op.is_comparison:
            ct = common_type(lt, rt)
            npd = ct.to_np()
            return ColumnValue(
                _CMP[op](a.astype(npd), b.astype(npd)), nulls, DataType.BOOL
            )
        # arithmetic
        npd = out_dtype.to_np()
        if op == L.Operator.DIVIDE:
            if out_dtype.is_integer:
                return ColumnValue(
                    _trunc_div(a.astype(npd), b.astype(npd)), nulls, out_dtype
                )
            a = a.astype(npd)
            b = b.astype(npd)
            return ColumnValue(a / b, nulls, out_dtype)
        if op == L.Operator.MODULO:
            sb = b.astype(npd)
            safe = jnp.where(sb == 0, jnp.ones_like(sb), sb)
            av = a.astype(npd)
            return ColumnValue(
                av - _trunc_div(av, safe) * safe, nulls, out_dtype
            )
        f = {
            L.Operator.PLUS: jnp.add,
            L.Operator.MINUS: jnp.subtract,
            L.Operator.MULTIPLY: jnp.multiply,
        }[op]
        return ColumnValue(
            f(a.astype(npd), b.astype(npd)).astype(npd), nulls, out_dtype
        )

    return fn


def _compile_logical(op: L.Operator, lf, rf):
    """Kleene three-valued AND/OR."""

    def fn(batch: DeviceBatch) -> ColumnValue:
        lv = lf(batch)
        rv = rf(batch)
        a = lv.values.astype(bool)
        b = rv.values.astype(bool)
        ln, rn = lv.nulls, rv.nulls
        if op == L.Operator.AND:
            vals = a & b
            if ln is None and rn is None:
                nulls = None
            else:
                ln_ = ln if ln is not None else jnp.zeros_like(a)
                rn_ = rn if rn is not None else jnp.zeros_like(a)
                # NULL unless the other side is definite FALSE.
                nulls = (ln_ & (rn_ | b)) | (rn_ & (ln_ | a))
        else:
            vals = a | b
            if ln is None and rn is None:
                nulls = None
            else:
                ln_ = ln if ln is not None else jnp.zeros_like(a)
                rn_ = rn if rn is not None else jnp.zeros_like(a)
                # NULL unless the other side is definite TRUE.
                nulls = (ln_ & (rn_ | ~b)) | (rn_ & (ln_ | ~a))
        return ColumnValue(vals, nulls, DataType.BOOL)

    return fn


def _compile_string_cmp(op: L.Operator, lf, rf, lt: DataType, rt: DataType):
    """String comparison by dictionary code.

    col-vs-literal resolves the literal against the column's sorted
    dictionary with bisect; col-vs-col remaps both sides onto a merged
    dictionary (host lookup tables) and compares codes.
    """
    if not (lt == DataType.STRING and rt == DataType.STRING):
        raise PlanError("string compared against non-string")

    def fn(batch: DeviceBatch) -> ColumnValue:
        lv = lf(batch)
        rv = rf(batch)
        nulls = _or_nulls(lv.nulls, rv.nulls)
        ld, rd = lv.dictionary, rv.dictionary
        if ld is None or rd is None:
            raise PlanError("string column without dictionary in comparison")

        # Literal side = single-value dictionary with constant code 0.
        if len(rd) == 1 and rv.values.ndim == 1 and _is_const(rv.values):
            return ColumnValue(
                _cmp_codes_vs_literal(op, lv.values, ld, rd.values[0]),
                nulls, DataType.BOOL,
            )
        if len(ld) == 1 and _is_const(lv.values):
            flipped = {
                L.Operator.LT: L.Operator.GT,
                L.Operator.LTEQ: L.Operator.GTEQ,
                L.Operator.GT: L.Operator.LT,
                L.Operator.GTEQ: L.Operator.LTEQ,
            }.get(op, op)
            return ColumnValue(
                _cmp_codes_vs_literal(flipped, rv.values, rd, ld.values[0]),
                nulls, DataType.BOOL,
            )

        if ld.values == rd.values:
            lcodes, rcodes = lv.values, rv.values
        else:
            _, ra, rb = dict_util.merge_dictionaries(ld, rd)
            lcodes = dict_util.remap_codes(lv.values, ra)
            rcodes = dict_util.remap_codes(rv.values, rb)
        return ColumnValue(_CMP[op](lcodes, rcodes), nulls, DataType.BOOL)

    return fn


def _is_const(v: jnp.ndarray) -> bool:
    """True for the broadcast-literal pattern (trace-time check is not
    possible on traced arrays; literals compile to jnp.zeros/full which are
    concrete only outside jit — so detect via weak heuristic: literal
    dictionaries have length 1 and we only build length-1 dicts for
    literals)."""
    return True  # length-1 dictionary is only produced by _compile_literal


def _cmp_codes_vs_literal(
    op: L.Operator, codes: jnp.ndarray, d: Dictionary, s: str
) -> jnp.ndarray:
    if op == L.Operator.EQ:
        i = d.index_of(s)
        if i < 0:
            return jnp.zeros(codes.shape, dtype=bool)
        return codes == i
    if op == L.Operator.NEQ:
        i = d.index_of(s)
        if i < 0:
            return jnp.ones(codes.shape, dtype=bool)
        return codes != i
    if op == L.Operator.LT:
        return codes < dict_util.bisect_left(d, s)
    if op == L.Operator.LTEQ:
        return codes < dict_util.bisect_right(d, s)
    if op == L.Operator.GT:
        return codes >= dict_util.bisect_right(d, s)
    if op == L.Operator.GTEQ:
        return codes >= dict_util.bisect_left(d, s)
    raise PlanError(f"unsupported string comparison {op}")


# -- unary / null checks ------------------------------------------------------


def _compile_not(expr: L.Not, schema: Schema):
    f = _compile(expr.expr, schema)

    def fn(batch: DeviceBatch) -> ColumnValue:
        v = f(batch)
        return ColumnValue(~v.values.astype(bool), v.nulls, DataType.BOOL)

    return fn


def _compile_negative(expr: L.Negative, schema: Schema):
    f = _compile(expr.expr, schema)
    dtype = expr.data_type(schema)

    def fn(batch: DeviceBatch) -> ColumnValue:
        v = f(batch)
        return ColumnValue(-v.values, v.nulls, dtype)

    return fn


def _compile_is_null(expr, schema: Schema):
    f = _compile(expr.expr, schema)
    want_null = isinstance(expr, L.IsNull)

    def fn(batch: DeviceBatch) -> ColumnValue:
        v = f(batch)
        if v.nulls is None:
            # no null mask = nothing is null: IS NULL -> all False,
            # IS NOT NULL -> all True
            out = jnp.full(v.values.shape, not want_null, dtype=bool)
            return ColumnValue(out, None, DataType.BOOL)
        vals = v.nulls if want_null else ~v.nulls
        return ColumnValue(vals, None, DataType.BOOL)

    return fn


def _compile_cast(expr: L.Cast, schema: Schema):
    f = _compile(expr.expr, schema)
    src = expr.expr.data_type(schema)
    dst = expr.to

    if src == DataType.STRING and dst != DataType.STRING:
        # Parse dictionary values host-side; codes gather the parsed table.
        def fn(batch: DeviceBatch) -> ColumnValue:
            v = f(batch)
            if v.dictionary is None:
                raise PlanError("cast of string column without dictionary")
            npd = dst.to_np()
            table = np.asarray(
                [_parse_scalar(s, dst) for s in v.dictionary.values], dtype=npd
            )
            if len(table) == 0:
                vals = jnp.zeros(v.values.shape, dtype=npd)
            else:
                vals = jnp.asarray(table)[
                    jnp.clip(v.values, 0, len(table) - 1)
                ]
            return ColumnValue(vals, v.nulls, dst)

        return fn

    def fn(batch: DeviceBatch) -> ColumnValue:
        v = f(batch)
        if src == dst:
            return v
        if dst == DataType.STRING:
            raise PlanError(f"cast {src.value} -> string is not supported")
        if src == DataType.DATE32 and dst == DataType.TIMESTAMP_US:
            vals = v.values.astype(jnp.int64) * jnp.int64(86_400_000_000)
        elif src == DataType.TIMESTAMP_US and dst == DataType.DATE32:
            vals = (v.values // jnp.int64(86_400_000_000)).astype(jnp.int32)
        else:
            npd = dst.to_np()
            vals = v.values
            if dst.is_integer and src.is_floating:
                vals = jnp.trunc(vals)  # SQL casts truncate
            vals = vals.astype(npd)
        return ColumnValue(vals, v.nulls, dst)

    return fn


def _parse_scalar(s: str, dtype: DataType):
    if dtype.is_integer:
        return int(float(s))
    if dtype.is_floating:
        return float(s)
    if dtype == DataType.BOOL:
        return s.strip().lower() in ("true", "t", "1", "yes")
    if dtype == DataType.DATE32:
        import datetime

        return (
            datetime.date.fromisoformat(s.strip())
            - datetime.date(1970, 1, 1)
        ).days
    raise PlanError(f"cannot parse string as {dtype}")


# -- CASE ---------------------------------------------------------------------


def _compile_case(expr: L.Case, schema: Schema):
    out_dtype = expr.data_type(schema)
    conds = [_compile(c, schema) for c, _ in expr.branches]
    vals = [_compile(v, schema) for _, v in expr.branches]
    other = _compile(expr.otherwise, schema) if expr.otherwise is not None else None
    if out_dtype == DataType.STRING:
        raise PlanError("CASE producing strings is not supported on device yet")

    def fn(batch: DeviceBatch) -> ColumnValue:
        npd = out_dtype.to_np()
        cvs = [c(batch) for c in conds]
        vvs = [v(batch) for v in vals]
        if other is not None:
            ov = other(batch)
            acc = ov.values.astype(npd) if ov.dtype != DataType.NULL else jnp.zeros(batch.capacity, dtype=npd)
            acc_null = ov.nulls if ov.dtype != DataType.NULL else jnp.ones(batch.capacity, dtype=bool)
        else:
            acc = jnp.zeros(batch.capacity, dtype=npd)
            acc_null = jnp.ones(batch.capacity, dtype=bool)
        if acc_null is None:
            acc_null = jnp.zeros(batch.capacity, dtype=bool)
        # Fold from last WHEN to first so earlier branches win.
        for cv, vv in zip(reversed(cvs), reversed(vvs)):
            hit = cv.values.astype(bool)
            if cv.nulls is not None:
                hit = hit & ~cv.nulls  # NULL condition = no match
            branch_vals = (
                vv.values.astype(npd)
                if vv.dtype != DataType.NULL
                else jnp.zeros(batch.capacity, dtype=npd)
            )
            branch_null = (
                vv.nulls
                if vv.dtype != DataType.NULL
                else jnp.ones(batch.capacity, dtype=bool)
            )
            acc = jnp.where(hit, branch_vals, acc)
            bn = branch_null if branch_null is not None else jnp.zeros(
                batch.capacity, dtype=bool
            )
            acc_null = jnp.where(hit, bn, acc_null)
        return ColumnValue(acc, acc_null, out_dtype)

    return fn


# -- IN / LIKE ----------------------------------------------------------------


def _compile_in_list(expr: L.InList, schema: Schema):
    et = expr.expr.data_type(schema)
    f = _compile(expr.expr, schema)
    lits = []
    for v in expr.values:
        if not isinstance(v, L.Literal):
            raise PlanError("IN list values must be literals")
        lits.append(v.value)

    def fn(batch: DeviceBatch) -> ColumnValue:
        v = f(batch)
        if et == DataType.STRING:
            if v.dictionary is None:
                raise PlanError("string IN without dictionary")
            codes = dict_util.in_codes(v.dictionary, tuple(lits))
            if not len(codes):
                hit = jnp.zeros(v.values.shape, dtype=bool)
            else:
                hit = jnp.isin(v.values, jnp.asarray(codes))
        else:
            arr = np.asarray(lits, dtype=et.to_np())
            hit = jnp.isin(v.values, jnp.asarray(arr))
        if expr.negated:
            hit = ~hit
        return ColumnValue(hit, v.nulls, DataType.BOOL)

    return fn


def _compile_like(expr: L.Like, schema: Schema):
    if expr.expr.data_type(schema) != DataType.STRING:
        raise PlanError("LIKE on non-string column")
    f = _compile(expr.expr, schema)

    def fn(batch: DeviceBatch) -> ColumnValue:
        v = f(batch)
        if v.dictionary is None:
            raise PlanError("LIKE on string column without dictionary")
        table = dict_util.like_table(v.dictionary, expr.pattern)
        if expr.negated:
            table = ~table
        if len(table) == 0:
            hit = jnp.zeros(v.values.shape, dtype=bool)
        else:
            hit = jnp.asarray(table)[jnp.clip(v.values, 0, len(table) - 1)]
        return ColumnValue(hit, v.nulls, DataType.BOOL)

    return fn


# -- scalar functions ---------------------------------------------------------


def _compile_scalar_fn(expr: L.ScalarFunction, schema: Schema):
    name = expr.fname
    args = [_compile(a, schema) for a in expr.args]
    out_dtype = expr.data_type(schema)

    if name in ("extract_year", "extract_month", "extract_day"):
        part = name.split("_")[1]
        src = expr.args[0].data_type(schema)

        def fn(batch: DeviceBatch) -> ColumnValue:
            v = args[0](batch)
            days = v.values
            if src == DataType.TIMESTAMP_US:
                days = (days // jnp.int64(86_400_000_000)).astype(jnp.int32)
            y, m, d = civil_from_days(days.astype(jnp.int32))
            out = {"year": y, "month": m, "day": d}[part]
            return ColumnValue(out, v.nulls, DataType.INT32)

        return fn

    if name == "coalesce":

        def fn(batch: DeviceBatch) -> ColumnValue:
            npd = out_dtype.to_np()
            vs = [a(batch) for a in args]
            acc = vs[-1].values.astype(npd)
            acc_null = vs[-1].nulls
            for v in reversed(vs[:-1]):
                if v.nulls is None:
                    acc = v.values.astype(npd)
                    acc_null = None
                    continue
                acc = jnp.where(v.nulls, acc, v.values.astype(npd))
                if acc_null is None:
                    acc_null = jnp.zeros(batch.capacity, dtype=bool)
                acc_null = v.nulls & acc_null
            return ColumnValue(acc, acc_null, out_dtype)

        return fn

    if name == "substr":
        for a in expr.args[1:]:
            if not isinstance(a, L.Literal):
                raise PlanError("substr start/length must be literals")
        start = expr.args[1].value  # SQL substr is 1-based
        length = expr.args[2].value if len(expr.args) > 2 else None

        def fn(batch: DeviceBatch) -> ColumnValue:
            v = args[0](batch)
            if v.dictionary is None:
                raise PlanError("substr on string column without dictionary")
            table, uniq = dict_util.substr_table(v.dictionary, start, length)
            codes = dict_util.remap_codes(v.values, table)
            return ColumnValue(codes, v.nulls, DataType.STRING, uniq)

        return fn

    simple = {
        "abs": jnp.abs,
        "floor": jnp.floor,
        "ceil": jnp.ceil,
        "sqrt": lambda x: jnp.sqrt(x.astype(jnp.float64)),
    }
    if name in simple:
        g = simple[name]

        def fn(batch: DeviceBatch) -> ColumnValue:
            v = args[0](batch)
            return ColumnValue(g(v.values).astype(out_dtype.to_np()), v.nulls, out_dtype)

        return fn

    if name == "round":
        ndigits = 0
        if len(expr.args) > 1:
            if not isinstance(expr.args[1], L.Literal):
                raise PlanError("round() digits must be a literal")
            ndigits = int(expr.args[1].value)

        def fn(batch: DeviceBatch) -> ColumnValue:
            v = args[0](batch)
            scale = 10.0 ** ndigits
            vals = jnp.round(v.values * scale) / scale
            return ColumnValue(vals.astype(out_dtype.to_np()), v.nulls, out_dtype)

        return fn

    # UDF plugins: the body is jax-traceable, so it fuses into the stage
    # program like a built-in (ballista_tpu/plugin.py, ref core/src/plugin/)
    from ballista_tpu.plugin import global_registry

    udf = global_registry.get(name)
    if udf is not None:
        g = udf.fn

        def fn(batch: DeviceBatch) -> ColumnValue:
            vs = [a(batch) for a in args]
            out = g(*[v.values for v in vs])
            # null-strict: result is NULL where any argument is NULL
            nulls = None
            for v in vs:
                if v.nulls is not None:
                    nulls = v.nulls if nulls is None else (nulls | v.nulls)
            return ColumnValue(
                jnp.asarray(out).astype(out_dtype.to_np()), nulls, out_dtype
            )

        return fn

    raise PlanError(f"unknown scalar function {name!r}")


def civil_from_days(z: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Days-since-epoch -> (year, month, day). Branchless proleptic-Gregorian
    conversion (Howard Hinnant's civil_from_days), exact for all int32 days —
    pure vector integer math, ideal for the VPU."""
    z = z.astype(jnp.int32) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)
