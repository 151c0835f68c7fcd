"""Standby cluster: a follower database fed continuously by the log archive.

Reference surface: logservice/restoreservice (ob_log_restore_service.h) —
a physical standby tenant starts from a backup set, tails the primary's
archived logs, replays continuously, serves reads, and PROMOTES to a
writable primary on failover.

Rebuild shape:
  * base state = restore_database(backup_root) — schema + sstable
    snapshots (DDL is meta-level, not logged; tables created after the
    backup need a fresh backup, matching the reference's restore-source
    schema version gate);
  * catch_up() tails every LS's archive through the stateful CdcClient
    cursors and applies committed transactions in commit-version order;
  * cross-LS (2PC/XA) transactions apply ATOMICALLY: a tx buffers until
    every participant LS's stream has emitted it (the TxChange carries
    the prepare record's participant list) — a lagging participant
    archive can delay a tx but never tear it;
  * reads run through ordinary sessions; every write statement is
    refused while in standby role;
  * promote() stops the tailing role and opens the database for writes
    (GTS already rides ahead of every applied commit version).
"""

from __future__ import annotations

from ..log.archive import ArchiveReader
from ..log.cdc import CdcClient, merge_streams
from ..storage import OP_DELETE, OP_PUT


class StandbyError(Exception):
    pass


_WRITE_PREFIXES = (
    "insert", "update", "delete", "create", "drop", "alter", "grant",
    "revoke", "truncate", "xa", "call", "lock", "refresh",
)


class StandbyCluster:
    def __init__(self, backup_root: str, archive_root: str,
                 n_nodes: int = 1, n_ls: int = 2):
        from ..storage.backup import restore_database

        self.archive_root = archive_root
        self.db = restore_database(backup_root, n_nodes=n_nodes, n_ls=n_ls)
        self.promoted = False
        # per-LS stateful cursors; fast-forward past what the BACKUP
        # already contains happens naturally: replayed versions at or
        # below the snapshot scn are skipped in _apply_tx
        self._cdc = {ls: CdcClient(ls) for ls in self.db.cluster.ls_groups}
        self._snapshot_scn = self.db._restore_backup_scn
        self.applied_scn = self._snapshot_scn
        # tablet id on the PRIMARY -> restored TableInfo (archived redo
        # addresses original tablet ids; restore_database records the map)
        self._by_primary_tablet = dict(self.db._restore_tablet_map)
        # per-LS FIFO of not-yet-applied changes: apply must follow each
        # stream's LOG ORDER — a held cross-LS tx BLOCKS everything behind
        # it on its stream (prefix consistency: a later tx may depend on
        # state — e.g. dictionary codes — the held tx creates)
        from collections import deque

        self._queues: dict[int, deque] = {
            ls: deque() for ls in self.db.cluster.ls_groups
        }
        self.catch_up()

    # ------------------------------------------------------------- tailing
    def catch_up(self) -> int:
        """Poll every LS archive and apply the COMPLETE PREFIX of each
        stream: single-LS txs apply in log order; a cross-LS tx applies
        only once it heads every participant's queue (atomic, and nothing
        behind it on any stream overtakes it). Returns txs applied."""
        if self.promoted:
            raise StandbyError("already promoted; standby tailing ended")
        for ls, cdc in self._cdc.items():
            self._queues[ls].extend(
                cdc.poll_archive(ArchiveReader(self.archive_root, ls)))
        ready = []
        progress = True
        while progress:
            progress = False
            for ls in sorted(self._queues):
                q = self._queues[ls]
                while q:
                    ch = q[0]
                    parts = set(ch.participants) or {ls}
                    if len(parts) <= 1:
                        ready.append(q.popleft())
                        progress = True
                        continue
                    heads_ok = all(
                        self._queues.get(p)
                        and self._queues[p][0].tx_id == ch.tx_id
                        for p in parts
                    )
                    if heads_ok:
                        for p in sorted(parts):
                            ready.append(self._queues[p].popleft())
                        progress = True
                        continue
                    break  # blocked: everything behind waits (prefix order)
        n = 0
        seen_tx = set()
        for ch in merge_streams(ready):
            self._apply_tx(ch)
            if ch.tx_id not in seen_tx:
                seen_tx.add(ch.tx_id)
                n += 1
        return n

    def _apply_tx(self, ch) -> None:
        if ch.commit_version <= self._snapshot_scn:
            return  # inside the restored snapshot already
        from ..server.database import apply_dict_appends

        db = self.db
        # dictionary growth first: row values reference the codes
        apply_dict_appends(self._by_primary_tablet, ch.dict_appends)
        touched = {ti.name: db.tables[ti.name] for ti in (
            self._by_primary_tablet.get(row.tablet_id) for row in ch.rows)
            if ti is not None}  # a table not in the backup set is skipped
        # open on the tables until the bump (Database.tx_shared_entry)
        with db.bulk_write(touched.values()):
            for row in ch.rows:
                ti = self._by_primary_tablet.get(row.tablet_id)
                if ti is None:
                    continue
                for rep in db.cluster.ls_groups[ti.ls_id].values():
                    rep.tablets[ti.tablet_id].active.replay(
                        row.key, OP_PUT if row.op == "put" else OP_DELETE,
                        row.values, ch.commit_version)
            db.cluster.gts.advance_to(ch.commit_version)
        self.applied_scn = max(self.applied_scn, ch.commit_version)

    # ------------------------------------------------------------- serving
    def sql(self, text: str):
        """Read-only statement surface while in standby role."""
        if self.promoted:
            raise StandbyError("promoted: use the database directly")
        head = text.lstrip().split(None, 1)
        if head and head[0].lower().rstrip(";") in _WRITE_PREFIXES:
            raise StandbyError(
                f"standby is read-only (refused {head[0].upper()})")
        return self.db.session().sql(text)

    # ------------------------------------------------------------ failover
    def promote(self):
        """End the standby role: final catch-up, then open for writes.
        Returns the now-primary Database."""
        self.catch_up()
        # a torn multi-LS tx at the failover point: the primary died
        # before every participant archived its COMMIT — the decided
        # half (and everything queued behind it) must not apply (the
        # reference resolves through the coordinator log; without it,
        # consistent = drop the tail)
        for q in self._queues.values():
            q.clear()
        self.promoted = True
        return self.db
