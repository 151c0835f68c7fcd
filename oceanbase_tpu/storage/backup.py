"""Physical backup + restore (with log-archive PITR).

Reference surface: storage/backup + rootserver/backup (physical backup of
tablet data to object storage) and storage/restore + logservice/
restoreservice (restore a tenant from a backup set plus archived logs up
to a restore SCN).

Backup set layout under <root>/:
  meta.json                 backup_scn, table metadata (schema, key cols,
                            placement, dictionaries)
  <table>.sst               one full-snapshot sstable blob at backup_scn

restore_database() rebuilds a fresh cluster: recreate tables, install the
snapshot sstable as every replica's base, fast-forward GTS past the
backup SCN; with an archive root it then replays committed transactions
with backup_scn < commit_version <= restore_scn through the tablets
(point-in-time recovery).
"""

from __future__ import annotations

import json
import os

import numpy as np

from ..core.dictionary import Dictionary
from ..core.dtypes import Field, Schema
from ..log.archive import ArchiveReader
from ..log.cdc import CdcClient, merge_streams
from ..sql.logical import _parse_type
from .sstable import (OP_DELETE, OP_PUT, SSTable, load_sstable,
                      save_sstable, write_sstable)


def backup_database(db, root: str) -> int:
    """Write a consistent full backup of every user table; returns the
    backup SCN."""
    os.makedirs(root, exist_ok=True)
    scn = db.cluster.gts.current()
    meta = {"backup_scn": scn, "tables": []}
    for name in sorted(db.tables):
        ti = db.tables[name]
        rep = db._leader_replica(ti)
        data = rep.tablets[ti.tablet_id].scan(scn)
        n = len(data[ti.schema.names()[0]]) if ti.schema.names() else 0
        # rows from scan_merge are rowkey-sorted — the sstable invariant
        blob = write_sstable(
            ti.schema, ti.key_cols, data,
            versions=np.full(n, scn, np.int64),
            ops=np.zeros(n, np.int8),
            base_version=0, end_version=scn,
        )
        from ..share.io_manager import GLOBAL_IO

        GLOBAL_IO.account("backup", len(blob))
        save_sstable(os.path.join(root, f"{name}.sst"), blob, fsync=False)
        meta["tables"].append({
            "name": name,
            "tablet_id": ti.tablet_id,  # archived redo references this id
            "fields": [
                (f.name, str(f.dtype), f.dtype.nullable)
                for f in ti.schema.fields
            ],
            "key_cols": list(ti.key_cols),
            "dicts": {c: d.values() for c, d in ti.dicts.items()},
            "rows": int(n),
        })
    from .integrity import BACKUP, write_atomic

    write_atomic(os.path.join(root, "meta.json"),
                 json.dumps(meta).encode(), fsync=False, path_class=BACKUP)
    return scn


def archive_database(db, archive_root: str) -> int:
    """Archive every LS's committed log (continuous-archive entry point)."""
    from ..log.archive import ArchiveWriter

    total = 0
    for ls_id, group in db.cluster.ls_groups.items():
        # any replica's committed prefix is valid; use the leader's
        node = db.location.leader(ls_id)
        palf = group[node].palf
        total += ArchiveWriter(archive_root, ls_id).archive_from(palf)
    return total


def restore_database(root: str, n_nodes: int = 3, n_ls: int = 2,
                     archive_root: str | None = None,
                     restore_scn: int | None = None):
    """Rebuild a Database from a backup set (+ optional archived-log PITR).

    Returns the restored Database. New writes get timestamps beyond the
    restored history (GTS fast-forward)."""
    from ..server.database import Database
    from .integrity import BACKUP, read_verified

    meta = json.loads(read_verified(
        os.path.join(root, "meta.json"), path_class=BACKUP))
    backup_scn = meta["backup_scn"]
    db = Database(n_nodes=n_nodes, n_ls=n_ls)

    # archived redo addresses ORIGINAL tablet ids; map them to the
    # restored placement
    old_to_new: dict[int, tuple] = {}
    for tmeta in meta["tables"]:
        fields = tuple(
            Field(n, _parse_type(t).with_nullable(nullable))
            for n, t, nullable in tmeta["fields"]
        )
        schema = Schema(fields)
        import oceanbase_tpu.sql.ast as A

        cols = tuple(
            A.ColumnDef(f.name, str(f.dtype), not f.dtype.nullable)
            for f in fields
        )
        db.create_table(A.CreateTable(
            tmeta["name"], cols, tuple(tmeta["key_cols"])))
        ti = db.tables[tmeta["name"]]
        for c, values in tmeta["dicts"].items():
            ti.dicts[c] = Dictionary(values)
            # codes inside the backup snapshot are already durable: the
            # first post-restore commit must not re-log the whole dict
            ti.logged_dict_len[c] = len(values)
        ss = load_sstable(os.path.join(root, f"{tmeta['name']}.sst"),
                          schema, ti.key_cols, cache=db.block_cache)
        blob = bytes(ss.buf)
        # the snapshot's rows commit at or below backup_scn
        # (Database.bulk_write)
        with db.bulk_write([ti]):
            for rep in db.cluster.ls_groups[ti.ls_id].values():
                t = rep.tablets[ti.tablet_id]
                t.base = SSTable(blob, schema, ti.key_cols,
                                 cache=db.block_cache)
            db.cluster.gts.advance_to(backup_scn)
        old_to_new[tmeta["tablet_id"]] = (ti, schema)

    db.cluster.gts.advance_to(backup_scn)
    # PRIMARY tablet id -> restored TableInfo: archived redo and standby
    # tailing (ha/standby.py) address original tablet ids
    db._restore_tablet_map = {old: ti for old, (ti, _s) in old_to_new.items()}
    db._restore_backup_scn = backup_scn

    if archive_root is not None:
        # PITR: replay archived commits in version order past the backup
        changes = []
        for ls_id in db.cluster.ls_groups:
            cdc = CdcClient(ls_id)
            changes.extend(cdc.poll_archive(ArchiveReader(archive_root, ls_id)))
        # pre-pass: collect ALL dictionary appends (commit order can differ
        # from code order — a later-committing tx may carry earlier codes;
        # applying by code keeps the mapping dense and order-independent.
        # Codes beyond restore_scn merely add unreferenced strings.)
        appends: dict[tuple[int, str], dict[int, str]] = {}
        for ch in changes:
            for tab_id, col, code, s in ch.dict_appends:
                appends.setdefault((tab_id, col), {})[code] = s
        for (tab_id, col), by_code in appends.items():
            hit = old_to_new.get(tab_id)
            if hit is None:
                continue
            d = hit[0].dicts[col]
            for code in sorted(by_code):
                if code == len(d):
                    d.encode_one(by_code[code])
                elif code < len(d) and d.decode_one(code) != by_code[code]:
                    raise IOError(
                        f"dictionary divergence at code {code} of {col}"
                    )
            hit[0].logged_dict_len[col] = max(
                hit[0].logged_dict_len.get(col, 0), len(d)
            )
        for ch in merge_streams(changes):
            if ch.commit_version <= backup_scn:
                continue  # already inside the backup snapshot
            if restore_scn is not None and ch.commit_version > restore_scn:
                continue
            tis = {old_to_new[r.tablet_id][0].name: old_to_new[r.tablet_id][0]
                   for r in ch.rows if r.tablet_id in old_to_new}
            with db.bulk_write(tis.values()):
                for row in ch.rows:
                    hit = old_to_new.get(row.tablet_id)
                    if hit is None:
                        continue  # table not in the backup set
                    ti, _schema = hit
                    for rep in db.cluster.ls_groups[ti.ls_id].values():
                        rep.tablets[ti.tablet_id].active.replay(
                            row.key, OP_PUT if row.op == "put" else OP_DELETE,
                            row.values, ch.commit_version,
                        )
                db.cluster.gts.advance_to(ch.commit_version)

    return db
