"""Expression compiler: IR -> whole-batch JAX computation.

The analog of OceanBase's expression code generator + eval function library
(sql/code_generator/ob_static_engine_expr_cg.h:70,
sql/engine/expr/ob_expr_eval_functions.cpp:554). Differences by design:

- One eval mode: whole-batch arrays through XLA (the reference keeps scalar /
  batch / rich-vector triples, ob_expr.h:888-898). XLA fuses the resulting
  elementwise graphs into the surrounding operator kernels, which is the TPU
  replacement for the reference's hand-fused SIMD eval functions.
- Decimals are scaled integers with compile-time scales: + - rescale to the
  max scale, * adds scales (promoting storage to int64), / leaves the decimal
  domain and produces float (matching how the reference routes decimal
  division through lib/number only on the CPU).
- String predicates (=, <, LIKE, IN, substr, fts_match, ...) on
  dictionary-encoded columns are evaluated once against the host-side
  dictionary, producing either a code threshold (sorted dicts) or a table
  with one entry per dictionary value. dict_lookup() is the one place that
  turns such a table into a per-row device array, and it tests the code
  where it can instead of gathering: a boolean table whose true entries form
  a few runs (every prefix LIKE on a sorted dictionary, short IN-lists)
  becomes range compares, and only scattered or numeric tables stay a
  gather — the global-dictionary version of the reference's dict-decoder
  pushdown filters (storage/blocksstable/encoding/ob_dict_decoder_simd.cpp).
- NULL semantics: separate validity masks, Kleene AND/OR, comparisons yield
  NULL if either side is NULL; filters treat NULL as reject. (Reference:
  ObBitVector skip/eval flags, sql/engine/ob_bit_vector.h.)

evaluate() runs during jit tracing: host work (dictionary lookups, literal
parsing) folds into compile-time constants; everything per-row becomes XLA.
"""

from __future__ import annotations

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..core.column import ColumnBatch
from ..core.dtypes import (
    BOOL,
    DataType,
    Schema,
    TypeKind,
    common_numeric_type,
)
from .ir import (
    Between,
    BinaryOp,
    BoolOp,
    Case,
    Cast,
    ColRef,
    Compare,
    Expr,
    Func,
    InList,
    IsNull,
    Literal,
    Not,
)

MAX_DECIMAL_SCALE = 6


# ---------------------------------------------------------------------------
# query parameters (plan-cache parameterized literals)
# ---------------------------------------------------------------------------

# Traced scalars for slotted Literals, active only while an executor traces /
# runs a parameterized plan. Reference: parameter frames bound into ObEvalCtx
# at execution (sql/plan_cache parameterization); here the "frame" is a tuple
# of 0-d device arrays passed as an extra jit argument.
_ACTIVE_PARAMS: tuple | None = None


def set_params(params: tuple | None):
    """Install the active parameter tuple; returns the previous one."""
    global _ACTIVE_PARAMS
    prev = _ACTIVE_PARAMS
    _ACTIVE_PARAMS = params
    return prev


def literal_scalar(e):
    """Traced storage-domain value of a Literal (slotted literals read
    the active parameter tuple so one executable serves every value) —
    for kernels that consume a literal directly (range-scan bounds, ANN
    query vectors) rather than as a broadcast column."""
    if e.slot is not None and _ACTIVE_PARAMS is not None:
        return _ACTIVE_PARAMS[e.slot]
    return jnp.asarray(bind_value(e.value, e.dtype))


# VECTOR literals resolve identically (the 'scalar' is a (d,) array)
evaluate_vector_literal = literal_scalar


def bind_value(value, dtype: DataType) -> np.generic:
    """Convert a python literal to its physical storage scalar (host side).

    Mirrors _literal_as so a bound parameter lands in exactly the domain the
    trace assumed: decimals as scaled ints, dates as int32 days."""
    if dtype.kind is TypeKind.VECTOR:
        if isinstance(value, str):
            value = [float(x) for x in value.strip("[] ").split(",")]
        a = np.asarray(value, dtype=np.float32)
        if a.shape != (dtype.precision,):
            raise ValueError(
                f"vector literal dim {a.shape} != column dim "
                f"({dtype.precision},)"
            )
        return a
    if dtype.kind is TypeKind.DATE:
        if isinstance(value, str):
            value = _parse_date(value)
        return np.int32(value)
    if dtype.is_decimal:
        return dtype.storage_np.type(int(round(float(value) * dtype.decimal_factor)))
    return dtype.storage_np.type(value)


# ---------------------------------------------------------------------------
# type inference
# ---------------------------------------------------------------------------


import functools


@functools.lru_cache(maxsize=65536)
def infer_type(e: Expr, schema: Schema) -> DataType:
    if isinstance(e, ColRef):
        return schema[e.name]
    if isinstance(e, Literal):
        return e.dtype
    if isinstance(e, BinaryOp):
        lt, rt = infer_type(e.left, schema), infer_type(e.right, schema)
        if e.op == "/":
            return DataType.float64(lt.nullable or rt.nullable)
        if lt.is_decimal or rt.is_decimal:
            # float operand forces float result
            if lt.is_float or rt.is_float:
                return DataType.float64(lt.nullable or rt.nullable)
            ls = lt.scale if lt.is_decimal else 0
            rs = rt.scale if rt.is_decimal else 0
            if e.op == "*":
                scale = min(ls + rs, MAX_DECIMAL_SCALE)
                return DataType.decimal(18, scale, lt.nullable or rt.nullable)
            scale = max(ls, rs)
            prec = 18 if (lt.storage_np.itemsize > 4 or rt.storage_np.itemsize > 4 or e.op in "+-") else 9
            return DataType.decimal(prec, scale, lt.nullable or rt.nullable)
        return common_numeric_type(lt, rt)
    if isinstance(e, (Compare, BoolOp, Not, IsNull, InList, Between)):
        return BOOL
    if isinstance(e, Cast):
        return e.dtype
    if isinstance(e, Case):
        branch_types = [infer_type(v, schema) for _, v in e.whens]
        if e.default is not None:
            branch_types.append(infer_type(e.default, schema))
        t = branch_types[0]
        for bt in branch_types[1:]:
            if bt != t:
                t = common_numeric_type(t, bt)
        return t
    if isinstance(e, Func):
        if e.name in ("vec_l2", "vec_ip", "vec_cosine"):
            return DataType.float32()
        if e.name in ("extract_year", "extract_month", "extract_day"):
            return DataType.int32()
        if e.name in ("like", "prefix", "contains", "fts_match",
                      "json_valid"):
            return BOOL
        if e.name in ("json_extract", "json_unquote", "json_type"):
            # path misses / invalid docs yield SQL NULL
            return DataType.varchar(nullable=True)
        if e.name == "json_array_length":
            return DataType.int64(nullable=True)
        if e.name in ("abs", "neg"):
            return infer_type(e.args[0], schema)
        if e.name in ("least", "greatest"):
            t = infer_type(e.args[0], schema)
            for a in e.args[1:]:
                t = common_numeric_type(t, infer_type(a, schema))
            return t
        if e.name == "substr" or e.name in CASE_FUNC_IMPL:
            return DataType.varchar(infer_type(e.args[0], schema).nullable)
        raise NotImplementedError(f"function {e.name}")
    raise NotImplementedError(type(e))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _parse_date(s: str) -> int:
    return int(np.datetime64(s, "D").astype(np.int64))


def _like_to_regex(pattern: str) -> re.Pattern:
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


# every function evaluable as a per-dictionary-value transform (the
# string-view family): ONE list shared by type inference, projection
# derivation, value-context errors, and the planner's group-key
# pre-projection — add new string functions here once
STRING_VIEW_FUNCS = (
    "substr", "json_extract", "json_unquote", "json_type",
    "lower", "upper", "trim",
)
# host implementations of the simple case/space transforms
CASE_FUNC_IMPL = {"lower": str.lower, "upper": str.upper, "trim": str.strip}


def _merge_valid(*vs):
    vs = [v for v in vs if v is not None]
    if not vs:
        return None
    out = vs[0]
    for v in vs[1:]:
        out = out & v
    return out


# dict_lookup's limit: at most this many runs of true entries become range
# compares. From a sweep on a TPU v5e (tools/dict_lookup_sweep.py;
# CHANGES.md, PR 27), 6 M codes fused into a sum: a gather costs 48-49 ms at
# any table of 150 entries or more, 32 runs 0.8-1.0 ms, beside 0.65 ms for
# the sum alone. 32 is the most the sweep measured.
LOOKUP_MAX_RUNS = 32

_lookup_tls = threading.local()


def set_lookup_metrics(metrics):
    """Install the registry (share/metrics.py) that counts this thread's
    lowering choices (`dict lookup <lowering>`, the executor's `clustered
    agg bounds <shared|gathered>`); returns the previous one. The server
    sets it for the length of a statement: lowerings are chosen at trace
    time, so the counters move per compiled program."""
    prev = getattr(_lookup_tls, "metrics", None)
    _lookup_tls.metrics = metrics
    return prev


def count_lowering(name: str, n: int = 1) -> None:
    """Count a trace-time choice between lowerings (`n` of them) into the
    registry set_lookup_metrics installed on this thread (none installed:
    no-op)."""
    m = getattr(_lookup_tls, "metrics", None)
    if m is not None:
        m.add(name, n)


def _true_runs(table: np.ndarray) -> list[tuple[int, int]]:
    """[lo, hi) of each run of true entries."""
    edges = np.flatnonzero(np.diff(np.r_[False, table, False].view(np.int8)))
    return list(zip(edges[::2].tolist(), edges[1::2].tolist()))


def dict_lookup(table: np.ndarray, codes) -> jnp.ndarray:
    """Per-row values of a host table with one entry per dictionary value:
    bit for bit `table[clip(codes, 0, len(table) - 1)]` (an empty table
    answers false / zero), lowered by what the table itself shows.

      constant  boolean, all true or all false: a broadcast constant
      runs      boolean, the true entries in at most LOOKUP_MAX_RUNS runs:
                the OR of `lo <= c < hi`, a one-entry run as `c == lo` —
                every prefix LIKE over a sorted dictionary, at any
                dictionary size, and short IN-lists
      gather    everything else (scattered boolean tables, numeric
                tables): an XLA gather

    The first two are elementwise and fuse into their consumer; on a TPU
    the gather is a kernel of its own that costs 8 ns per row whatever the
    table's size (150 entries or 200 K)."""
    table = np.asarray(table)
    n = len(table)

    if n == 0 or (table.dtype == np.bool_ and table.all() == table.any()):
        count_lowering("dict lookup constant")
        fill = table[0] if n else np.zeros((), table.dtype)
        return jnp.full(jnp.shape(codes), fill, dtype=table.dtype)
    c = jnp.clip(codes, 0, n - 1)
    if table.dtype == np.bool_:
        runs = _true_runs(table)
        if len(runs) <= LOOKUP_MAX_RUNS:
            count_lowering("dict lookup runs")
            out = None
            for lo, hi in runs:
                t = c == lo if hi == lo + 1 else (c >= lo) & (c < hi)
                out = t if out is None else out | t
            return out
    count_lowering("dict lookup gather")
    return jnp.asarray(table)[c]


def _rescale_decimal(vals, from_scale: int, to_scale: int):
    if to_scale == from_scale:
        return vals
    if to_scale > from_scale:
        return vals.astype(jnp.int64) * (10 ** (to_scale - from_scale))
    # scale down, SQL round-half-away-from-zero (sign-aware)
    f = 10 ** (from_scale - to_scale)
    half = f // 2
    return jnp.where(vals >= 0, (vals + half) // f, -((-vals + half) // f))


def _literal_as(value, target: DataType, batch: ColumnBatch, col_name: str | None):
    """Materialize a python literal in the physical domain of `target`.

    Single source of truth is bind_value: traced constants and bound
    plan-cache parameters MUST land in bit-identical physical domains."""
    if value is None:
        return None
    if target.kind is TypeKind.VARCHAR:
        raise AssertionError("string literals handled by dictionary paths")
    return jnp.asarray(bind_value(value, target))


# ---------------------------------------------------------------------------
# evaluation (runs under jit tracing)
# ---------------------------------------------------------------------------


def evaluate(e: Expr, batch: ColumnBatch):
    """Evaluate an expression over a batch -> (values, valid|None).

    Traced under the named scope `expr`, inside the scope of the plan
    node that asked: a device trace tells an operator's expression work
    (a string predicate's dict_lookup, a decimal rescale) from its own
    (a gather)."""
    with jax.named_scope("expr"):
        return _evaluate(e, batch)


def _evaluate(e: Expr, batch: ColumnBatch):
    schema = batch.schema

    if isinstance(e, ColRef):
        return batch.cols[e.name], batch.valid.get(e.name)

    if isinstance(e, Literal):
        t = e.dtype
        if e.value is None:
            cap = batch.capacity
            return (
                jnp.zeros(cap, dtype=t.storage_np),
                jnp.zeros(cap, dtype=jnp.bool_),
            )
        if e.slot is not None and _ACTIVE_PARAMS is not None:
            # parameterized plan: the value is a traced scalar already in
            # the literal's physical storage domain (bind_value)
            return _ACTIVE_PARAMS[e.slot], None
        if t.kind is TypeKind.VARCHAR:
            raise NotImplementedError(
                "bare string literal outside a dictionary comparison"
            )
        return _literal_as(e.value, t, batch, None), None

    if isinstance(e, BinaryOp):
        return _eval_arith(e, batch)

    if isinstance(e, Compare):
        return _eval_compare(e, batch)

    if isinstance(e, BoolOp):
        vals_valid = [_evaluate(a, batch) for a in e.args]
        if e.op == "and":
            out = vals_valid[0][0]
            for v, _ in vals_valid[1:]:
                out = out & v
            # Kleene: NULL unless result decidable
            if all(vv is None for _, vv in vals_valid):
                return out, None
            known_false = jnp.zeros_like(out)
            all_valid = jnp.ones_like(out)
            for v, vv in vals_valid:
                if vv is None:
                    known_false = known_false | ~v
                    continue
                known_false = known_false | (vv & ~v)
                all_valid = all_valid & vv
            return out, all_valid | known_false
        else:
            out = vals_valid[0][0]
            for v, _ in vals_valid[1:]:
                out = out | v
            if all(vv is None for _, vv in vals_valid):
                return out, None
            known_true = jnp.zeros_like(out)
            all_valid = jnp.ones_like(out)
            for v, vv in vals_valid:
                if vv is None:
                    known_true = known_true | v
                    continue
                known_true = known_true | (vv & v)
                all_valid = all_valid & vv
            return out, all_valid | known_true

    if isinstance(e, Not):
        v, valid = _evaluate(e.arg, batch)
        return ~v, valid

    if isinstance(e, IsNull):
        # string-view exprs (json_*/substr) carry NULLness in their view,
        # not in a device validity channel: fold it here
        view = (
            _string_view(e.arg, batch)
            if isinstance(e.arg, Func) else None
        )
        if view is not None:
            codes, valid, vals = view
            valid = _fold_view_nulls(codes, valid, vals)
        else:
            _, valid = _evaluate(e.arg, batch)
        if valid is None:
            out = jnp.zeros(batch.capacity, dtype=jnp.bool_)
        else:
            out = ~valid
        if e.negated:
            out = ~out
        return out, None

    if isinstance(e, Cast):
        return _eval_cast(e, batch)

    if isinstance(e, Case):
        return _eval_case(e, batch)

    if isinstance(e, InList):
        return _eval_in_list(e, batch)

    if isinstance(e, Between):
        from .ir import and_

        lo = Compare(">=", e.arg, e.low)
        hi = Compare("<=", e.arg, e.high)
        v, valid = _evaluate(and_(lo, hi), batch)
        return (~v if e.negated else v), valid

    if isinstance(e, Func):
        return _eval_func(e, batch)

    raise NotImplementedError(type(e))


def _numeric_align(e_left: Expr, e_right: Expr, batch: ColumnBatch):
    """Evaluate two numeric operands into a common physical domain.

    Returns (lv, rv, lvalid, rvalid, result_kind, scale) where result_kind is
    'float' or 'decimal'/'int' with the given scale (0 for pure ints).
    """
    schema = batch.schema
    lt, rt = infer_type(e_left, batch.schema), infer_type(e_right, batch.schema)
    lv, lvalid = _evaluate(e_left, batch)
    rv, rvalid = _evaluate(e_right, batch)

    if lt.is_float or rt.is_float:
        tgt = jnp.result_type(lv.dtype if lt.is_float else jnp.float32,
                              rv.dtype if rt.is_float else jnp.float32)
        if lt.is_decimal:
            lv = lv.astype(tgt) / lt.decimal_factor
        else:
            lv = lv.astype(tgt)
        if rt.is_decimal:
            rv = rv.astype(tgt) / rt.decimal_factor
        else:
            rv = rv.astype(tgt)
        return lv, rv, lvalid, rvalid, "float", 0

    ls = lt.scale if lt.is_decimal else 0
    rs = rt.scale if rt.is_decimal else 0
    s = max(ls, rs)
    if s > 0:
        # literals were already scaled by _literal_as via evaluate()? No —
        # Literal ints evaluate at scale 0; rescale both sides to s.
        lv = _rescale_decimal(lv, ls, s)
        rv = _rescale_decimal(rv, rs, s)
        return lv, rv, lvalid, rvalid, "decimal", s
    return lv, rv, lvalid, rvalid, "int", 0


def _eval_arith(e: BinaryOp, batch: ColumnBatch):
    out_t = infer_type(e, batch.schema)
    lt = infer_type(e.left, batch.schema)
    rt = infer_type(e.right, batch.schema)

    if e.op == "/" or out_t.is_float:
        lv, rv, lvalid, rvalid, _, _ = _numeric_align_float(e.left, e.right, batch)
        ops = {
            "+": jnp.add,
            "-": jnp.subtract,
            "*": jnp.multiply,
            "/": jnp.divide,
            "%": jnp.mod,
        }
        return ops[e.op](lv, rv), _merge_valid(lvalid, rvalid)

    if e.op == "*" and (lt.is_decimal or rt.is_decimal):
        lv, lvalid = _evaluate(e.left, batch)
        rv, rvalid = _evaluate(e.right, batch)
        prod = lv.astype(jnp.int64) * rv.astype(jnp.int64)
        ls = lt.scale if lt.is_decimal else 0
        rs = rt.scale if rt.is_decimal else 0
        prod = _rescale_decimal(prod, ls + rs, out_t.scale)
        return prod.astype(out_t.storage_np), _merge_valid(lvalid, rvalid)

    lv, rv, lvalid, rvalid, kind, s = _numeric_align(e.left, e.right, batch)
    tgt = out_t.storage_np
    lv = lv.astype(tgt)
    rv = rv.astype(tgt)
    if e.op == "+":
        out = lv + rv
    elif e.op == "-":
        out = lv - rv
    elif e.op == "*":
        out = lv * rv
    elif e.op == "%":
        out = jnp.where(rv != 0, lv % jnp.where(rv == 0, 1, rv), 0)
    else:
        raise NotImplementedError(e.op)
    return out, _merge_valid(lvalid, rvalid)


def _numeric_align_float(e_left: Expr, e_right: Expr, batch: ColumnBatch):
    lt, rt = infer_type(e_left, batch.schema), infer_type(e_right, batch.schema)
    lv, lvalid = _evaluate(e_left, batch)
    rv, rvalid = _evaluate(e_right, batch)
    tgt = jnp.float64 if (lt.kind is TypeKind.FLOAT64 or rt.kind is TypeKind.FLOAT64
                          or not (lt.is_float or rt.is_float)) else jnp.float32
    if lt.is_decimal:
        lv = lv.astype(tgt) / lt.decimal_factor
    else:
        lv = lv.astype(tgt)
    if rt.is_decimal:
        rv = rv.astype(tgt) / rt.decimal_factor
    else:
        rv = rv.astype(tgt)
    return lv, rv, lvalid, rvalid, "float", 0


_CMP = {
    "=": lambda a, b: a == b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<>": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}

def _eval_compare(e: Compare, batch: ColumnBatch):
    lt = infer_type(e.left, batch.schema)
    rt = infer_type(e.right, batch.schema)

    # date vs 'YYYY-MM-DD' string literal: parse on host, compare as int days
    if lt.kind is TypeKind.DATE and isinstance(e.right, Literal) and isinstance(e.right.value, str):
        lv, lvalid = _evaluate(e.left, batch)
        rv = _literal_as(e.right.value, lt, batch, None)
        return _CMP[e.op](lv, rv), lvalid
    if rt.kind is TypeKind.DATE and isinstance(e.left, Literal) and isinstance(e.left.value, str):
        rv, rvalid = _evaluate(e.right, batch)
        lv = _literal_as(e.left.value, rt, batch, None)
        return _CMP[e.op](lv, rv), rvalid

    # --- dictionary string comparisons -------------------------------
    if lt.kind is TypeKind.VARCHAR or rt.kind is TypeKind.VARCHAR:
        if isinstance(e.right, Literal) and isinstance(e.left, ColRef):
            return _dict_compare(e.left, e.op, e.right.value, batch)
        if isinstance(e.left, Literal) and isinstance(e.right, ColRef):
            flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
            op = flip.get(e.op, e.op)
            return _dict_compare(e.right, op, e.left.value, batch)
        # string transforms (substr) vs literal: boolean table over the view
        if isinstance(e.right, Literal):
            view = _string_view(e.left, batch)
            if view is not None:
                codes, valid, vals = view
                valid = _fold_view_nulls(codes, valid, vals)
                lut = np.fromiter(
                    (
                        False if v is None else _CMP[e.op](v, e.right.value)
                        for v in vals
                    ),
                    dtype=np.bool_, count=len(vals),
                )
                return dict_lookup(lut, codes), valid
        if lt.kind is TypeKind.VARCHAR and rt.kind is TypeKind.VARCHAR:
            # col-vs-col code comparison is only sound when both columns
            # share one dictionary object (e.g. post-join copies); distinct
            # dictionaries assign incomparable codes.
            if (
                isinstance(e.left, ColRef)
                and isinstance(e.right, ColRef)
                and batch.dicts.get(e.left.name) is not batch.dicts.get(e.right.name)
            ):
                raise NotImplementedError(
                    f"varchar comparison {e.left.name} vs {e.right.name}: "
                    "columns use different dictionaries; requires dictionary "
                    "translation (not yet implemented)"
                )
            lv, lvalid = _evaluate(e.left, batch)
            rv, rvalid = _evaluate(e.right, batch)
            return _CMP[e.op](lv, rv), _merge_valid(lvalid, rvalid)
        raise NotImplementedError("varchar comparison form")

    lv, rv, lvalid, rvalid, _, _ = _numeric_align(e.left, e.right, batch)
    return _CMP[e.op](lv, rv), _merge_valid(lvalid, rvalid)


def _dict_compare(col_expr: ColRef, op: str, value: str, batch: ColumnBatch):
    d = batch.dicts.get(col_expr.name)
    if d is None:
        raise KeyError(f"no dictionary for varchar column {col_expr.name}")
    codes, valid = _evaluate(col_expr, batch)
    if d.sorted and op in ("<", "<=", ">", ">="):
        import bisect

        vals = d.values()
        if op in ("<", ">="):
            thr = bisect.bisect_left(vals, value)
            out = codes < thr if op == "<" else codes >= thr
        else:
            thr = bisect.bisect_right(vals, value)
            out = codes < thr if op == "<=" else codes >= thr
        return out, valid
    if op in ("=", "=="):
        code = d.encode_one(value, add=False)
        return codes == jnp.asarray(code, dtype=jnp.int32), valid
    if op in ("!=", "<>"):
        code = d.encode_one(value, add=False)
        return codes != jnp.asarray(code, dtype=jnp.int32), valid
    # general fallback: boolean table over dictionary values
    lut = np.fromiter(
        (_CMP[op](v, value) for v in d.values()), dtype=np.bool_, count=len(d)
    )
    return dict_lookup(lut, codes), valid


def _eval_cast(e: Cast, batch: ColumnBatch):
    src_t = infer_type(e.arg, batch.schema)
    dst = e.dtype
    if src_t.kind is TypeKind.VARCHAR and dst.kind is not TypeKind.VARCHAR:
        # string -> number through the dictionary: parse each DISTINCT
        # value once into a numeric table (unparseable -> SQL NULL); this
        # is what makes predicates on extracted JSON scalars pushable —
        # CAST(j->>'$.price' AS decimal) compiles to one gather + compare
        view = _string_view(e.arg, batch)
        if view is None:
            raise NotImplementedError(
                f"CAST from varchar requires a dictionary view: {e.arg}")
        codes, valid, vals = view

        def parse(v):
            if v is None:
                return None
            try:
                return float(v)
            except ValueError:
                return None

        nums = [parse(v) for v in vals]
        nn = np.fromiter(
            (x is not None for x in nums), dtype=np.bool_,
            count=len(nums),
        )
        fl = np.fromiter(
            (0.0 if x is None else x for x in nums), dtype=np.float64,
            count=len(nums),
        )
        fv = dict_lookup(fl, codes)
        valid = _merge_valid(valid, dict_lookup(nn, codes))
        if dst.is_decimal:
            out = jnp.round(fv * dst.decimal_factor).astype(dst.storage_np)
        elif dst.is_integer:
            out = jnp.round(fv).astype(dst.storage_np)
        else:
            out = fv.astype(dst.storage_np)
        return out, valid
    v, valid = _evaluate(e.arg, batch)
    if src_t.is_decimal and dst.is_decimal:
        return _rescale_decimal(v, src_t.scale, dst.scale).astype(dst.storage_np), valid
    if src_t.is_decimal and dst.is_float:
        return (v.astype(dst.storage_np) / src_t.decimal_factor), valid
    if src_t.is_decimal and dst.is_integer:
        return _rescale_decimal(v, src_t.scale, 0).astype(dst.storage_np), valid
    if dst.is_decimal:
        if src_t.is_float:
            return jnp.round(v * dst.decimal_factor).astype(dst.storage_np), valid
        return (v.astype(dst.storage_np) * dst.decimal_factor), valid
    return v.astype(dst.storage_np), valid


def _eval_case(e: Case, batch: ColumnBatch):
    out_t = infer_type(e, batch.schema)
    np_dt = out_t.storage_np
    if e.default is not None:
        out, out_valid = _evaluate(Cast(e.default, out_t), batch)
    else:
        out = jnp.zeros(batch.capacity, dtype=np_dt)
        out_valid = jnp.zeros(batch.capacity, dtype=jnp.bool_)
    for cond, val in reversed(e.whens):
        c, cvalid = _evaluate(cond, batch)
        take = c if cvalid is None else (c & cvalid)
        v, vvalid = _evaluate(Cast(val, out_t), batch)
        out = jnp.where(take, v, out)
        if out_valid is not None or vvalid is not None:
            ov = out_valid if out_valid is not None else jnp.ones(batch.capacity, jnp.bool_)
            vv = vvalid if vvalid is not None else jnp.ones(batch.capacity, jnp.bool_)
            out_valid = jnp.where(take, vv, ov)
    return out, out_valid


def _eval_in_list(e: InList, batch: ColumnBatch):
    t = infer_type(e.arg, batch.schema)
    if t.kind is TypeKind.VARCHAR:
        view = _string_view(e.arg, batch)
        if view is None:
            raise NotImplementedError(f"IN over varchar expr {e.arg}")
        codes, valid, vals = view
        valid = _fold_view_nulls(codes, valid, vals)
        members = set(e.values)
        lut = np.fromiter(
            (v is not None and v in members for v in vals),
            dtype=np.bool_, count=len(vals),
        )
        out = dict_lookup(lut, codes)
        return (~out if e.negated else out), valid
    v, valid = _evaluate(e.arg, batch)
    out = jnp.zeros(batch.capacity, dtype=jnp.bool_)
    for item in e.values:
        out = out | (v == _literal_as(item, t, batch, None))
    return (~out if e.negated else out), valid


# --- date decomposition (Howard Hinnant's civil-from-days, branch-free) ----


def _civil_from_days(days):
    z = days.astype(jnp.int32) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - doe // 1460 + doe // 36524 - doe // 146096, 365
    )
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _string_view(e: Expr, batch: ColumnBatch):
    """A 'string view' of an expression: (codes, valid, per-code values).

    Works for a dictionary-encoded column or a host-computable string
    transform of one (substr with literal bounds). The per-code value list
    lets predicates become boolean tables with one entry per code, which
    dict_lookup turns into tests of the code — the TPU-friendly compile of
    string functions (strings never reach the device; this is the
    global-dictionary analog of the reference's dict-encoded pushdowns,
    storage/blocksstable/encoding/ob_dict_decoder_simd.cpp).
    """
    if isinstance(e, ColRef):
        d = batch.dicts.get(e.name)
        if d is None:
            return None
        codes, valid = _evaluate(e, batch)
        return codes, valid, list(d.values())
    if isinstance(e, Func) and e.name == "substr":
        base = _string_view(e.args[0], batch)
        if base is None:
            return None
        codes, valid, vals = base
        if not (isinstance(e.args[1], Literal) and isinstance(e.args[2], Literal)):
            return None
        s0 = int(e.args[1].value) - 1  # SQL is 1-based
        length = int(e.args[2].value)
        if length >= 0:
            vals2 = [None if v is None else v[s0 : s0 + length] for v in vals]
        else:
            vals2 = [None if v is None else v[s0:] for v in vals]
        return codes, valid, vals2
    if isinstance(e, Func) and e.name in CASE_FUNC_IMPL:
        # case mapping / trimming once per DISTINCT value: the engine's
        # answer to case-insensitive collations (ob_charset.h) — compare /
        # group / join on lower(col) instead of a per-row collation sweep
        base = _string_view(e.args[0], batch)
        if base is None:
            return None
        codes, valid, vals = base
        f = CASE_FUNC_IMPL[e.name]
        return codes, valid, [None if v is None else f(v) for v in vals]
    if isinstance(e, Func) and e.name in (
        "json_extract", "json_unquote", "json_type"
    ):
        # JSON transforms compose through the view like substr: evaluated
        # once per DISTINCT document, rows map by code; a None in vals is
        # SQL NULL and is folded into `valid` by _fold_view_nulls at the
        # consumer boundary (ob_expr_json_extract.cpp evaluates per row —
        # the per-document table is the redesign)
        from .jsonpath import (
            extract_repr,
            json_type_of,
            parse_path,
            unquote,
        )

        base = _string_view(e.args[0], batch)
        if base is None:
            return None
        codes, valid, vals = base
        if e.name == "json_extract":
            if not isinstance(e.args[1], Literal):
                return None
            steps = parse_path(str(e.args[1].value))
            vals2 = [
                None if v is None else extract_repr(v, steps) for v in vals
            ]
        elif e.name == "json_unquote":
            vals2 = [unquote(v) for v in vals]
        else:
            vals2 = [json_type_of(v) for v in vals]
        return codes, valid, vals2
    return None


def _fold_view_nulls(codes, valid, vals):
    """NULL results in a string view (None entries) become row-level
    invalidity; remaining values are safe to feed table builders."""
    if any(v is None for v in vals):
        nn = np.fromiter(
            (v is not None for v in vals), dtype=np.bool_, count=len(vals)
        )
        valid = _merge_valid(valid, dict_lookup(nn, codes))
    return valid


def derive_dict_column(e: Expr, batch: ColumnBatch):
    """Materialize a string-transform expr as a NEW dict column:
    (codes, valid, Dictionary). Used by projections so downstream operators
    (group-by, joins, output decode) see an ordinary dict column."""
    from ..core.dictionary import Dictionary

    if not (isinstance(e, Func) and e.name in STRING_VIEW_FUNCS):
        return None
    view = _string_view(e, batch)
    if view is None:
        return None
    codes, valid, vals = view
    valid = _fold_view_nulls(codes, valid, vals)
    safe = ["" if v is None else v for v in vals]  # NULL rows are invalid
    d2, mapping = Dictionary.from_strings_bulk(np.asarray(safe, dtype=str))
    return dict_lookup(mapping.astype(np.int32), codes), valid, d2


def _eval_func(e: Func, batch: ColumnBatch):
    if e.name in ("extract_year", "extract_month", "extract_day"):
        v, valid = _evaluate(e.args[0], batch)
        y, m, d = _civil_from_days(v)
        return {"extract_year": y, "extract_month": m, "extract_day": d}[e.name], valid

    if e.name == "like":
        # the pattern runs once per DISTINCT value on the host; over a
        # sorted dictionary the matches of a prefix pattern are one run of
        # codes, so dict_lookup emits `lo <= code < hi` (TPC-H Q14's
        # `p_type like 'PROMO%'`: two compares per row, no table)
        col_expr, pat = e.args
        assert isinstance(col_expr, ColRef) and isinstance(pat, Literal)
        d = batch.dicts[col_expr.name]
        rx = _like_to_regex(str(pat.value))
        lut = np.fromiter(
            (rx.match(v) is not None for v in d.values()),
            dtype=np.bool_,
            count=len(d),
        )
        codes, valid = _evaluate(col_expr, batch)
        return dict_lookup(lut, codes), valid

    if e.name == "fts_match":
        # word-level full-text match against a dict-encoded column: the
        # dictionary IS the index (reference: src/storage/fts tokenizes
        # raw rows into an inverted index; here every distinct value
        # tokenizes ONCE into a boolean table and rows match by code:
        # dict_lookup's range compares or gather, as the table's runs say)
        col_expr, q = e.args
        assert isinstance(col_expr, ColRef) and isinstance(q, Literal)
        d = batch.dicts[col_expr.name]
        want = [t for t in str(q.value).lower().split() if t]
        lut = np.fromiter(
            (
                all(t in v.lower().split() for t in want)
                for v in d.values()
            ),
            dtype=np.bool_,
            count=len(d),
        )
        codes, valid = _evaluate(col_expr, batch)
        return dict_lookup(lut, codes), valid

    if e.name == "json_valid":
        view = _string_view(e.args[0], batch)
        if view is None:
            raise NotImplementedError("json_valid needs a dictionary view")
        from .jsonpath import is_valid

        codes, valid, vals = view
        lut = np.fromiter(
            (v is not None and is_valid(v) for v in vals),
            dtype=np.bool_, count=len(vals),
        )
        return dict_lookup(lut, codes), valid

    if e.name == "json_array_length":
        from .jsonpath import array_length, parse_path

        view = _string_view(e.args[0], batch)
        if view is None:
            raise NotImplementedError(
                "json_array_length needs a dictionary view")
        codes, valid, vals = view
        steps = (
            parse_path(str(e.args[1].value)) if len(e.args) > 1 else ()
        )
        lens = [None if v is None else array_length(v, steps) for v in vals]
        valid = _fold_view_nulls(codes, valid, lens)
        lut = np.fromiter(
            (0 if x is None else x for x in lens), dtype=np.int64,
            count=len(lens),
        )
        return dict_lookup(lut, codes), valid

    if e.name in STRING_VIEW_FUNCS and e.name != "substr":
        # value context without a dictionary sink (e.g. a join key):
        # unreachable from projections (derive_dict_column handles those)
        raise NotImplementedError(
            f"{e.name} used where a dictionary column cannot form")

    if e.name in ("prefix", "contains"):
        col_expr, pat = e.args
        assert isinstance(col_expr, ColRef) and isinstance(pat, Literal)
        d = batch.dicts[col_expr.name]
        p = str(pat.value)
        test = (lambda v: v.startswith(p)) if e.name == "prefix" else (lambda v: p in v)
        lut = np.fromiter((test(v) for v in d.values()), dtype=np.bool_, count=len(d))
        codes, valid = _evaluate(col_expr, batch)
        return dict_lookup(lut, codes), valid

    if e.name in ("vec_l2", "vec_ip", "vec_cosine"):
        # vector distances in matmul form (the n*d work lands on the MXU
        # instead of a VPU sweep): squared L2 = ||x||^2 - 2 x.q + ||q||^2;
        # vec_ip = NEGATIVE inner product and vec_cosine = 1 - cosine
        # similarity, both oriented so ORDER BY <dist> ASC LIMIT k means
        # "nearest" for every metric. Used by the brute-force exact path
        # (plain TopN) and IVF candidate re-ranking.
        xv, valid = _evaluate(e.args[0], batch)
        q = evaluate_vector_literal(e.args[1])
        xq = xv @ q
        if e.name == "vec_ip":
            return -xq, valid
        if e.name == "vec_cosine":
            xn = jnp.sqrt(jnp.sum(xv * xv, axis=1))
            qn = jnp.sqrt(jnp.sum(q * q))
            return 1.0 - xq / jnp.maximum(xn * qn, 1e-30), valid
        xn = jnp.sum(xv * xv, axis=1)
        return xn - 2.0 * xq + jnp.sum(q * q), valid
    if e.name == "abs":
        v, valid = _evaluate(e.args[0], batch)
        return jnp.abs(v), valid
    if e.name == "neg":
        v, valid = _evaluate(e.args[0], batch)
        return -v, valid
    if e.name in ("least", "greatest"):
        op = jnp.minimum if e.name == "least" else jnp.maximum
        v, valid = _evaluate(e.args[0], batch)
        for a in e.args[1:]:
            v2, valid2 = _evaluate(a, batch)
            v = op(v, v2)
            valid = _merge_valid(valid, valid2)
        return v, valid
    raise NotImplementedError(f"function {e.name}")


def compile_predicate(e: Expr, batch: ColumnBatch) -> jnp.ndarray:
    """Predicate -> bool mask over the batch; NULL results reject the row."""
    with jax.named_scope("expr"):
        v, valid = _evaluate(e, batch)
        mask = v if valid is None else (v & valid)
        return mask & batch.sel
