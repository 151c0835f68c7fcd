"""Column batches: the device-resident unit of execution.

This is the TPU redesign of OceanBase's expression frames + rich vector
formats + ObBatchRows:

- reference frames hold per-expr ObDatum[batch_size] + VectorHeader
  (sql/engine/expr/ob_expr.h:541, code_generator/ob_static_engine_expr_cg.h:70);
  here a batch is a dict of SoA device arrays, one per column.
- reference VectorFormat {FIXED, DISCRETE, CONTINUOUS, UNIFORM, UNIFORM_CONST}
  (share/vector/type_traits.h:23) collapses to: FIXED = dense array,
  DISCRETE/CONTINUOUS (varlen) = dictionary codes (core/dictionary.py),
  UNIFORM_CONST = jnp scalar broadcast (XLA folds it).
- reference ObBatchRows {skip_ bitmap, size_, all_rows_active_}
  (sql/engine/ob_batch_rows.h:26) becomes `sel` (bool mask, True = row live)
  plus `nrows` (live-row count). Capacities are static for XLA; dead tail
  rows are simply masked out, which the VPU handles at full width anyway.

ColumnBatch is a pytree so whole batches flow through jit/shard_map.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from .dictionary import Dictionary, DictPin
from .dtypes import DataType, Field, Schema, TypeKind


@jax.tree_util.register_dataclass
@dataclass
class ColumnBatch:
    """A batch of rows as SoA device arrays, with a live-row mask.

    cols:  name -> values array, shape [capacity], dtype = DataType.storage_np
    valid: name -> bool array (True = non-null); absent for non-nullable cols
    sel:   bool [capacity] live-row mask (ObBatchRows.skip_ inverted)
    nrows: traced scalar count of live rows
    schema: static metadata (field names, logical types)
    dicts: static host-side dictionaries for VARCHAR columns
    """

    cols: dict[str, jnp.ndarray]
    valid: dict[str, jnp.ndarray]
    sel: jnp.ndarray
    nrows: jnp.ndarray
    schema: Schema = field(metadata=dict(static=True), default=Schema())
    dicts: dict[str, Dictionary] = field(
        metadata=dict(static=True), default_factory=dict
    )

    @property
    def capacity(self) -> int:
        return int(self.sel.shape[0])

    @property
    def nbytes(self) -> int:
        """Device bytes: the columns, their validity and the mask."""
        return sum(int(a.nbytes) for d in (self.cols, self.valid)
                   for a in d.values()) + int(self.sel.nbytes)

    def col(self, name: str) -> jnp.ndarray:
        return self.cols[name]

    def validity(self, name: str) -> jnp.ndarray:
        """Validity mask for a column (all-True if non-nullable)."""
        v = self.valid.get(name)
        if v is None:
            return jnp.ones(self.capacity, dtype=jnp.bool_)
        return v

    def with_sel(self, sel: jnp.ndarray) -> "ColumnBatch":
        return replace(self, sel=sel, nrows=jnp.sum(sel, dtype=jnp.int64))

    def project(self, names: list[str]) -> "ColumnBatch":
        fields = tuple(Field(n, self.schema[n]) for n in names)
        return replace(
            self,
            cols={n: self.cols[n] for n in names},
            valid={n: v for n, v in self.valid.items() if n in names},
            schema=Schema(fields),
            dicts={n: d for n, d in self.dicts.items() if n in names},
        )


def pin_dicts(inputs: dict, deps: dict) -> dict:
    """One call's inputs to a plan's programs with each batch's
    dictionaries pinned (`DictPin`, sharing the plan's `deps`): one pin
    per dictionary, so columns that shared a dictionary still share it."""
    pins: dict[int, DictPin] = {}
    out = {}
    for alias, b in inputs.items():
        if isinstance(b, ColumnBatch) and b.dicts:
            ds = {}
            for c, d in b.dicts.items():
                p = pins.get(id(d))
                if p is None:
                    p = pins[id(d)] = (d if type(d) is DictPin
                                       else DictPin(d, deps))
                ds[c] = p
            b = replace(b, dicts=ds)
        out[alias] = b
    return out


def unpin_dicts(out: ColumnBatch, inputs: dict) -> ColumnBatch:
    """A program's output with the dictionaries of THIS call's inputs in
    place of its pins: a reused program hands back the pins of the call
    that traced it, which may hold an older version of a lineage."""
    if not any(type(d) is DictPin for d in out.dicts.values()):
        return out
    now = {}
    for b in inputs.values():
        if isinstance(b, ColumnBatch):
            for d in b.dicts.values():
                if type(d) is DictPin:
                    now[d._key()] = d.dictionary
    return replace(out, dicts={
        c: (now.get(d._key(), d.dictionary) if type(d) is DictPin else d)
        for c, d in out.dicts.items()})


def batch_rows_storage(batch, names) -> dict:
    """Live rows of a device batch in STORAGE domain (no decimal/date
    decoding — callers materializing Tables need exact round-trips)."""
    sel = np.asarray(batch.sel)
    return {n: np.ascontiguousarray(np.asarray(batch.cols[n])[sel])
            for n in names}


def batch_valid_storage(batch, names) -> dict:
    """Live-row validity masks (only for columns that HAVE one) — the
    NULL half of an exact materialization; dropping it would turn NULLs
    into storage sentinel values."""
    sel = np.asarray(batch.sel)
    return {
        n: np.ascontiguousarray(np.asarray(batch.valid[n])[sel])
        for n in names if n in batch.valid
    }


def renamed_storage_schema(schema_src, names) -> "Schema":
    """Schema of a materialized result: output names zipped positionally
    onto the planned output schema's field types."""
    return Schema(tuple(
        Field(n, schema_src[sn])
        for n, sn in zip(names, schema_src.names())
    ))


def narrow_tier(amin: int, amax: int, itemsize: int):
    """Smallest unsigned dtype that holds [0, amax - amin], if narrower
    than the storage width (the shared frame-of-reference tier rule for
    wire-narrowed uploads)."""
    span = amax - amin
    for nt in (np.uint8, np.uint16, np.uint32):
        if span <= np.iinfo(nt).max and np.dtype(nt).itemsize < itemsize:
            return np.dtype(nt)
    return None


def narrowed_upload(a: np.ndarray, cap: int | None = None):
    """Host->device transfer with the wire cost of the VALUE RANGE, not
    the storage width: integer columns ship frame-of-reference narrowed
    (a - min, downcast to the smallest unsigned dtype that fits the
    span) and decode on device with one cast + one add.

    Wire bytes bound both first-touch table residency and every
    out-of-core streamed chunk (the one record, from before PR 1, saw
    12-30 MB/s host->device on the link of that time; the local host
    link is not measured); TPC-H's int64-stored decimals/dates narrow
    2-8x. The device-side cache still holds the full-width
    column — this is a transport encoding, the device-resident analog
    of the reference's FOR-encoded micro-blocks decoded by SIMD readers
    (blocksstable/encoding/ob_dict_decoder_simd.cpp)."""
    def pad(arr, fill=0):
        if cap is None or cap <= len(arr):
            return arr
        return np.concatenate([
            arr,
            np.full((cap - len(arr),) + arr.shape[1:], fill,
                    dtype=arr.dtype),
        ])

    if a.dtype.kind not in "iu" or a.ndim != 1 or len(a) == 0:
        return jnp.asarray(pad(a))
    # frame from the UNPADDED values: zero-padding an all-positive column
    # (dates, keys, scaled decimals) would drag the frame base to 0 and
    # forfeit most of the narrowing; dead pad rows carry amin instead
    amin = int(a.min())
    nt = narrow_tier(amin, int(a.max()), a.dtype.itemsize)
    if nt is None:
        return jnp.asarray(pad(a))
    narrow = pad((a - amin).astype(nt))
    return (jnp.asarray(narrow).astype(a.dtype)
            + np.asarray(amin, dtype=a.dtype))


def make_batch(
    data: dict[str, np.ndarray],
    schema: Schema,
    dicts: dict[str, Dictionary] | None = None,
    capacity: int | None = None,
    valid: dict[str, np.ndarray] | None = None,
) -> ColumnBatch:
    """Build a ColumnBatch from host arrays, padding to `capacity`.

    Capacity defaults to nrows rounded up to a multiple of 1024 (keeps XLA
    tiling happy: last-dim lanes of 128, sublane multiples).
    """
    names = schema.names()
    n = len(next(iter(data.values()))) if data else 0
    for name in names:
        if len(data[name]) != n:
            raise ValueError(f"column {name} length mismatch")
    cap = capacity if capacity is not None else max(1024, -(-n // 1024) * 1024)
    if cap < n:
        raise ValueError(f"capacity {cap} < nrows {n}")

    cols: dict[str, jnp.ndarray] = {}
    vmap_: dict[str, jnp.ndarray] = {}
    for f in schema.fields:
        a = np.asarray(data[f.name], dtype=f.dtype.storage_np)
        cols[f.name] = narrowed_upload(a, cap)
        if f.dtype.nullable:
            v = (
                np.asarray(valid[f.name], dtype=np.bool_)
                if valid and f.name in valid
                else np.ones(n, dtype=np.bool_)
            )
            if cap > n:
                v = np.concatenate([v, np.zeros(cap - n, dtype=np.bool_)])
            vmap_[f.name] = jnp.asarray(v)
    sel = np.zeros(cap, dtype=np.bool_)
    sel[:n] = True
    return ColumnBatch(
        cols=cols,
        valid=vmap_,
        sel=jnp.asarray(sel),
        nrows=jnp.asarray(n, dtype=jnp.int64),
        schema=schema,
        dicts=dict(dicts or {}),
    )


def batch_rows_normalized(
    batch: ColumnBatch, names, ndigits: int = 4
) -> list[tuple]:
    """Result rows as a sorted list of comparable tuples: floats rounded,
    NaN -> None, numpy scalars unboxed. The canonical form for comparing
    two executions of the same plan (distributed vs single-chip checks,
    oracle comparisons)."""
    host = batch_to_host(batch)
    n = len(next(iter(host.values()))) if host else 0
    out = []
    for i in range(n):
        row = []
        for nm in names:
            v = host[nm][i]
            if isinstance(v, (float, np.floating)):
                v = None if np.isnan(v) else round(float(v), ndigits)
            elif isinstance(v, np.integer):
                v = int(v)
            row.append(v)
        out.append(tuple(row))
    return sorted(out, key=lambda r: tuple((x is None, str(x)) for x in r))


def batch_to_host(batch: ColumnBatch, decode_strings: bool = True) -> dict[str, np.ndarray | list]:
    """Pull live rows back to host (compacting out dead rows).

    NULL rows of nullable columns surface as None (lists) / NaN (floats) /
    masked ints via an object-dtype fallback, so callers never see the
    garbage payloads stored under invalid slots.
    """
    sel = np.asarray(batch.sel)
    cols = {f.name: np.asarray(batch.cols[f.name]) for f in batch.schema.fields}
    valid = {n: np.asarray(v) for n, v in batch.valid.items()}
    return host_rows(
        batch.schema, batch.dicts, cols, valid, sel,
        decode_strings=decode_strings,
    )


def host_rows(schema, dicts, hcols, hvalid, hsel,
              decode_strings: bool = True) -> dict[str, np.ndarray | list]:
    """batch_to_host over ALREADY-FETCHED numpy arrays (the cursor's
    host cache, engine/executor.py DeviceResult)."""
    out: dict[str, np.ndarray | list] = {}
    for f in schema.fields:
        a = np.asarray(hcols[f.name])[hsel]
        v = hvalid.get(f.name)
        vm = np.asarray(v)[hsel] if v is not None else None
        if f.dtype.kind is TypeKind.VARCHAR and decode_strings and f.name in dicts:
            codes = a.copy()
            if vm is not None:
                codes[~vm] = -1  # Dictionary.decode maps negatives to None
            out[f.name] = dicts[f.name].decode(codes)
        elif f.dtype.is_decimal:
            d = a.astype(np.float64) / f.dtype.decimal_factor
            if vm is not None:
                d[~vm] = np.nan
            out[f.name] = d
        elif vm is not None and not vm.all():
            o = a.astype(object)
            o[~vm] = None
            out[f.name] = o
        else:
            out[f.name] = a
    return out


def host_rows_batched(schema, dicts, hcols, hvalid, hsel,
                      decode_strings: bool = True) -> list[dict]:
    """host_rows over a whole statement micro-batch at once.

    `hcols`/`hvalid` values carry a leading [B] lane axis and `hsel` is
    [B, cap]; returns one column dict per lane. One flatten + offset
    slicing per column replaces B per-lane boolean gathers, so the
    batcher's scatter cost stops scaling with lane count. (Lanes share
    one flat decode, so a NULL in any lane switches a nullable column's
    dtype fallback for all lanes of this batch — the surfaced values are
    identical either way.)"""
    nb = int(hsel.shape[0])
    counts = hsel.sum(axis=1)
    offs = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(counts, out=offs[1:])
    flat: dict[str, np.ndarray | list] = {}
    for f in schema.fields:
        a = np.asarray(hcols[f.name])[hsel]
        v = hvalid.get(f.name)
        vm = np.asarray(v)[hsel] if v is not None else None
        if f.dtype.kind is TypeKind.VARCHAR and decode_strings and f.name in dicts:
            codes = a.copy()
            if vm is not None:
                codes[~vm] = -1
            flat[f.name] = dicts[f.name].decode(codes)
        elif f.dtype.is_decimal:
            d = a.astype(np.float64) / f.dtype.decimal_factor
            if vm is not None:
                d[~vm] = np.nan
            flat[f.name] = d
        elif vm is not None and not vm.all():
            o = a.astype(object)
            o[~vm] = None
            flat[f.name] = o
        else:
            flat[f.name] = a
    return [
        {n: c[offs[i]:offs[i + 1]] for n, c in flat.items()}
        for i in range(nb)
    ]
