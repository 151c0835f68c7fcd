"""Direct load: the bulk-ingest bypass path.

Reference surface: observer/table_load (ObTableLoadService,
ob_table_load_service.h:35) + storage/direct_load — bulk loads skip the
memtable/redo path entirely: rows are externally sorted by rowkey and
written straight into sstables, which are then installed on the tablet
(and replicated by data movement rather than redo).

The rebuild mirrors that: vectorized host coercion (no per-row staging),
one np.lexsort by rowkey, one sstable build, installed as a delta on every
replica at a single load version. Dictionary growth is NOT marked durable
here — the log carries no record of this load, so the next regular commit
re-logs any new dictionary entries (see TableInfo.logged_dict_len), and
point-in-time recovery of direct-loaded data requires a backup taken after
the load, exactly like the reference.
"""

from __future__ import annotations

import numpy as np

from ..core.dtypes import TypeKind
from ..storage.sstable import SSTable, write_sstable


class DirectLoadError(Exception):
    pass


def _bulk_encode(d, arr: np.ndarray) -> np.ndarray:
    """Vectorized append-dictionary encode: one encode_one per UNIQUE
    string, inverse-mapped to rows."""
    arr = np.asarray(arr)
    if arr.dtype.kind not in ("U", "S"):
        arr = arr.astype(str)
    uniq, inv = np.unique(arr, return_inverse=True)
    codes = np.fromiter(
        (d.encode_one(str(s)) for s in uniq), dtype=np.int32, count=len(uniq)
    )
    return codes[inv]


def direct_load(db, table_name: str, data: dict[str, object]) -> int:
    """Bulk-load rows into a table; returns rows loaded.

    `data` maps every column name to an array-like. Primary keys must be
    unique within the batch AND not collide with existing rows."""
    ti = db.tables.get(table_name)
    if ti is None:
        raise DirectLoadError(f"no such table {table_name}")
    names = ti.schema.names()
    missing = [c for c in names if c not in data]
    if missing:
        raise DirectLoadError(f"missing columns {missing}")

    cols: dict[str, np.ndarray] = {}
    n = None
    for f in ti.schema.fields:
        a = data[f.name]
        if f.dtype.kind is TypeKind.VARCHAR:
            v = _bulk_encode(ti.dicts[f.name], a)
        elif f.dtype.kind is TypeKind.DATE:
            arr = np.asarray(a)
            if arr.dtype.kind in ("U", "S"):
                v = arr.astype("datetime64[D]").astype(np.int64)
            else:
                v = arr.astype(np.int64)
            v = v.astype(f.dtype.storage_np)
        elif f.dtype.is_decimal:
            arr = np.asarray(a)
            if np.issubdtype(arr.dtype, np.floating):
                arr = np.round(arr * f.dtype.decimal_factor)
            v = arr.astype(f.dtype.storage_np)
        else:
            v = np.asarray(a, dtype=f.dtype.storage_np)
        if n is None:
            n = len(v)
        elif len(v) != n:
            raise DirectLoadError(f"column {f.name} length mismatch")
        cols[f.name] = v
    if not n:
        return 0

    # rowkey sort (the external-sort stage; np.lexsort is the in-memory
    # fast path, ops/spill.external_sort the beyond-memory one)
    key_arrays = [cols[k].astype(np.int64) for k in ti.key_cols]
    order = np.lexsort(tuple(reversed(key_arrays)))
    cols = {c: v[order] for c, v in cols.items()}
    keys2d = np.stack([cols[k].astype(np.int64) for k in ti.key_cols], axis=1)
    dup = (keys2d[1:] == keys2d[:-1]).all(axis=1)
    if dup.any():
        raise DirectLoadError(
            f"duplicate primary key in batch: {tuple(keys2d[1:][dup][0])}"
        )

    # partition routing: each hash partition gets its own sorted sstable
    # (the parallel direct-load shape — per-partition sort + install)
    part_ids = np.array(
        [0] * n if ti.part_col is None or len(ti.all_partitions()) == 1
        else [
            _part_route(keys2d[i], ti) for i in range(n)
        ],
        dtype=np.int64,
    )
    # open on the table from before its version is drawn until the bump
    # (Database.tx_shared_entry)
    with db.bulk_write([ti]):
        version = db.cluster.gts.next_ts()
        for p_idx, (pls, ptab) in enumerate(ti.all_partitions()):
            m = part_ids == p_idx
            if not m.any():
                continue
            pcols = {c: v[m] for c, v in cols.items()}
            pk2d = keys2d[m]
            # existing-key collision check through the tablet's read path
            rep = db._leader_replica_ls(pls)
            tablet = rep.tablets[ptab]
            if tablet.nrows_estimate:
                maybe = np.zeros(len(pk2d), dtype=bool)
                for st in ([tablet.base] if tablet.base else []) + list(tablet.deltas):
                    maybe |= st.may_contain_keys(pk2d)
                for mt in [tablet.active] + list(tablet.frozen):
                    if mt.nkeys:
                        for i in np.flatnonzero(~maybe):
                            if mt.get(tuple(pk2d[i]), 2**62) is not None:
                                maybe[i] = True
                for i in np.flatnonzero(maybe):
                    if tablet.get(tuple(pk2d[i]), 2**62) is not None:
                        raise DirectLoadError(
                            f"primary key {tuple(pk2d[i])} already exists"
                        )
            blob = write_sstable(
                ti.schema, ti.key_cols, pcols,
                versions=np.full(int(m.sum()), version, np.int64),
                ops=np.zeros(int(m.sum()), np.int8),
                base_version=0, end_version=version,
            )
            # install on every replica (the data-movement replication analog)
            for r in db.cluster.ls_groups[pls].values():
                t = r.tablets[ptab]
                with t._meta_lock:
                    t.deltas.append(
                        SSTable(blob, ti.schema, ti.key_cols, cache=db.block_cache)
                    )
    return int(n)


def _part_route(key_row: np.ndarray, ti) -> int:
    from .database import _part_of

    v = key_row[ti.key_cols.index(ti.part_col)]
    return _part_of(int(v), len(ti.all_partitions()))
