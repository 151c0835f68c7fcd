"""OBKV: the NoSQL table API over tablets.

Reference surface: observer/table + src/libtable — a key-value/HBase-style
API (get/put/delete/batch/scan with filters) that reaches tablets through
the same transaction and storage stack as SQL, without the SQL compiler.

The rebuild's TableApi binds one table: point ops run as single-statement
transactions through TransService (fully transactional, replicated);
scans read a leader MVCC snapshot with optional key-range pruning and a
row filter. Values are python dicts keyed by column name; VARCHAR cells
are strings (codes stay internal)."""

from __future__ import annotations

import numpy as np

from ..core.dtypes import TypeKind
from ..storage import OP_DELETE, OP_PUT
from .database import Database, SqlError, _OpenTx


class TableApi:
    def __init__(self, db: Database, table: str):
        self.db = db
        ti = db.tables.get(table)
        if ti is None:
            raise SqlError(f"no such table {table}")
        self.table = table

    @property
    def _ti(self):
        return self.db.tables[self.table]

    # ------------------------------------------------------------ encode
    def _coerce_row(self, row: dict) -> tuple:
        ti = self._ti
        from .database import _coerce

        vals = []
        for f in ti.schema.fields:
            if f.name not in row:
                raise SqlError(f"missing column {f.name}")
            vals.append(_coerce(row[f.name], f.dtype,
                                ti.dicts.get(f.name), f.name))
        return tuple(vals)

    def _decode_row(self, vals: tuple) -> dict:
        ti = self._ti
        out = {}
        for f, v in zip(ti.schema.fields, vals):
            if f.dtype.kind is TypeKind.VARCHAR:
                out[f.name] = ti.dicts[f.name].decode_one(int(v))
            elif f.dtype.is_decimal:
                out[f.name] = float(v) / f.dtype.decimal_factor
            else:
                out[f.name] = v if not isinstance(v, np.generic) else v.item()
        return out

    def _key_of(self, row_or_key) -> tuple:
        ti = self._ti
        if isinstance(row_or_key, dict):
            return tuple(
                int(self._coerce_row(row_or_key)[ti.schema.index(k)])
                for k in ti.key_cols
            )
        k = row_or_key if isinstance(row_or_key, tuple) else (row_or_key,)
        return tuple(int(x) for x in k)

    # --------------------------------------------------------------- ops
    def _tx_op(self, muts: list[tuple[tuple, int, tuple | None]]) -> None:
        """One autocommit tx staging the given mutations (batch = atomic).
        Secondary indexes are maintained in the same tx: puts are upserts,
        so the OLD row is read first to tombstone superseded entries."""
        ti = self._ti
        tx = _OpenTx(self.db)
        from ..tx.tablelock import LockMode
        from .database import DbSession

        try:
            self.db.lock_mgr.lock(tx.ctx.tx_id, ti.tablet_id, LockMode.ROW_X)
            routed = [
                (*ti.partition_for_key(key), key, op, vals)
                for key, op, vals in muts
            ]
            needed_ls = {ls for ls, _t, _k, _o, _v in routed}
            if ti.indexes:
                needed_ls.add(ti.ls_id)
            for ls in sorted(needed_ls):
                tx.ensure_leader(ls)
            index_muts: list[tuple[int, tuple, int, tuple | None]] = []
            if ti.indexes:
                for ls_id, tab_id, key, op, vals in routed:
                    old = tx.svc.replicas[ls_id].tablets[tab_id].get(
                        key, tx.ctx.read_snapshot, tx_id=tx.ctx.tx_id
                    )
                    rep = tx.svc.replicas[ti.ls_id]
                    for idx in ti.indexes.values():
                        old_ik = (
                            DbSession._index_entry(ti, idx, old[1])[0]
                            if old is not None else None
                        )
                        if op == OP_DELETE:
                            if old_ik is not None:
                                index_muts.append(
                                    (idx.tablet_id, old_ik, OP_DELETE, None))
                            continue
                        new_ik, new_iv = DbSession._index_entry(ti, idx, vals)
                        if old_ik == new_ik:
                            continue
                        if idx.unique:
                            hit = rep.tablets[idx.tablet_id].get(
                                new_ik, tx.ctx.read_snapshot,
                                tx_id=tx.ctx.tx_id)
                            if hit is not None:
                                raise SqlError(
                                    f"unique index {idx.name} violation on "
                                    f"{new_ik}")
                        if old_ik is not None:
                            index_muts.append(
                                (idx.tablet_id, old_ik, OP_DELETE, None))
                        index_muts.append(
                            (idx.tablet_id, new_ik, OP_PUT, new_iv))
            # open on the table until the bump (Database.tx_shared_entry)
            self.db.writer_open(tx, ti)
            for ls_id, tab_id, key, op, vals in routed:
                tx.svc.write(tx.ctx, ls_id, tab_id, key, op, vals)
            for tab_id, key, op, vals in index_muts:
                tx.svc.write(tx.ctx, ti.ls_id, tab_id, key, op, vals)
            self.db.cluster.commit_sync(tx.svc, tx.ctx)
        except Exception:
            if not tx.ctx.is_done:
                tx.svc.abort(tx.ctx)
            raise
        finally:
            self.db.lock_mgr.release_all(tx.ctx.tx_id)
            self.db.writers_end(tx.ctx, tx.writing)

    def put(self, row: dict) -> None:
        """Upsert one row (HBase-put semantics: blind write)."""
        vals = self._coerce_row(row)
        self._tx_op([(self._key_of(row), OP_PUT, vals)])

    def batch_put(self, rows: list[dict]) -> int:
        muts = [(self._key_of(r), OP_PUT, self._coerce_row(r)) for r in rows]
        self._tx_op(muts)
        return len(muts)

    def delete(self, key) -> None:
        self._tx_op([(self._key_of(key), OP_DELETE, None)])

    def get(self, key) -> dict | None:
        ti = self._ti
        k = self._key_of(key)
        ls_id, tab_id = ti.partition_for_key(k)
        rep = self.db._leader_replica_ls(ls_id)
        hit = rep.tablets[tab_id].get(k, self.db.cluster.gts.current())
        return None if hit is None else self._decode_row(hit[1])

    def scan(self, key_min=None, key_max=None, row_filter=None,
             limit: int | None = None) -> list[dict]:
        """Range scan on the FIRST key column with optional row filter
        (the HBase-filter analog, applied host-side post-snapshot)."""
        ti = self._ti
        ranges = None
        if key_min is not None or key_max is not None:
            lo = -float("inf") if key_min is None else float(key_min)
            hi = float("inf") if key_max is None else float(key_max)
            ranges = {ti.key_cols[0]: (lo, hi)}
        snap = self.db.cluster.gts.current()
        parts = []
        for pls, ptab in ti.all_partitions():
            rep = self.db._leader_replica_ls(pls)
            parts.append(rep.tablets[ptab].scan(snap, ranges=ranges))
        data = (
            parts[0] if len(parts) == 1
            else {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}
        )
        names = ti.schema.names()
        n = len(data[names[0]]) if names else 0
        if ranges is not None and n:
            # zone-map pruning is block-approximate: apply the exact bound
            k = data[ti.key_cols[0]]
            m = np.ones(n, dtype=bool)
            if key_min is not None:
                m &= k >= key_min
            if key_max is not None:
                m &= k <= key_max
            data = {c: v[m] for c, v in data.items()}
            n = int(m.sum())
        out = []
        for i in range(n):
            row = self._decode_row(tuple(data[c][i] for c in names))
            if row_filter is not None and not row_filter(row):
                continue
            out.append(row)
            if limit is not None and len(out) >= limit:
                break
        return out
