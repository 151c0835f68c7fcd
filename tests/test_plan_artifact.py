"""Persistent compiled-plan artifact invalidation.

Every stale-artifact path must end in a clean recompile with its own
sysstat counter — schema bump ("plan artifact key mismatch"), toolchain
drift ("plan artifact version mismatch"), corrupt or truncated files
("plan artifact load error"), capacity-overflow recompile ("plan
artifact reexport"). A stale executable must never serve rows.
"""

import pickle

from oceanbase_tpu.server import Database
from oceanbase_tpu.storage.integrity import unwrap, wrap


def _read_env(path) -> bytes:
    """Strip the integrity envelope the store writes around every file."""
    with open(path, "rb") as f:
        return unwrap(f.read(), str(path))


def _write_env(path, payload: bytes) -> None:
    """Re-wrap a doctored payload so the store's verified reads accept it
    (the doctoring simulates stale-but-intact files, not corruption)."""
    with open(path, "wb") as f:
        f.write(wrap(payload))

Q = ("select g, count(*) as c, sum(v) as s from art_t "
     "group by g order by g")


def _boot(tmp_path):
    return Database(n_nodes=1, n_ls=1, data_dir=str(tmp_path / "node"),
                    fsync=False)


def _seed(tmp_path, nrows=64):
    """First boot: enable rw artifacts, create + fill art_t, compile Q
    once (exporting it), persist, crash. Returns Q's pre-crash rows."""
    db = _boot(tmp_path)
    s = db.session()
    s.sql("alter system set ob_plan_artifact_mode = 'rw'")
    s.sql("create table art_t (id bigint primary key, "
          "g bigint not null, v bigint not null)")
    s.sql("insert into art_t values " + ", ".join(
        f"({i}, {i % 5}, {i})" for i in range(nrows)))
    rows = s.sql(Q).rows()
    assert db.plan_artifact is not None
    assert db.plan_artifact._index["entries"], "Q was not exported"
    db._save_node_meta()
    db.close()
    return rows


def _first_exec(db):
    """(rows, jit compiles) for the first post-boot execution of Q."""
    ex = db.engine.executor
    c0 = ex.compiles + ex.batched_compiles
    rows = db.session().sql(Q).rows()
    return rows, (ex.compiles + ex.batched_compiles) - c0


def _doctor_metas(tmp_path, fn):
    """Rewrite every exported ArtifactMeta through `fn` on the closed
    store directory — simulates an artifact exported by an older world."""
    root = tmp_path / "node" / "plan_artifacts"
    n = 0
    for meta_p in root.glob("*.meta"):
        meta = pickle.loads(_read_env(meta_p))
        fn(meta)
        _write_env(meta_p,
                   pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL))
        n += 1
    assert n, "no artifacts on disk to doctor"


def test_warm_boot_serves_identical_rows_with_zero_compiles(tmp_path):
    rows0 = _seed(tmp_path)
    db = _boot(tmp_path)
    snap = db.metrics.counters_snapshot()
    assert snap.get("plan artifact warm load", 0) >= 1
    rows, compiles = _first_exec(db)
    assert rows == rows0
    assert compiles == 0
    assert db.metrics.counters_snapshot().get("plan artifact hit", 0) >= 1
    db.close()


def test_schema_bump_rejects_artifact_and_recompiles(tmp_path):
    rows0 = _seed(tmp_path)
    # rewrite the store as if every artifact was exported under an older
    # schema version: key, filenames, and index move together (that is
    # what disk looks like after a genuine bump — the artifact's key no
    # longer matches what the live catalog derives)
    import hashlib
    import json

    root = tmp_path / "node" / "plan_artifacts"
    idx = json.loads(_read_env(root / "index.json"))
    ents = {}
    for old_aid, ent in idx["entries"].items():
        meta = pickle.loads(_read_env(root / f"{old_aid}.meta"))
        meta.art_key = (*meta.art_key[:4],
                        (("art_t", 999_999, "stale-dict"),),
                        meta.art_key[5])
        new_aid = hashlib.md5(repr(meta.art_key).encode()).hexdigest()
        meta.aid = new_aid
        _write_env(root / f"{new_aid}.meta",
                   pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL))
        (root / f"{old_aid}.x").rename(root / f"{new_aid}.x")
        (root / f"{old_aid}.meta").unlink()
        ents[new_aid] = ent
    assert ents
    idx["entries"] = ents
    _write_env(root / "index.json", json.dumps(idx).encode())

    db = _boot(tmp_path)
    snap = db.metrics.counters_snapshot()
    assert snap.get("plan artifact key mismatch", 0) >= 1
    assert snap.get("plan artifact warm load", 0) == 0
    rows, compiles = _first_exec(db)
    assert rows == rows0
    assert compiles == 1  # clean recompile, not a stale executable
    # the session-path lookup under the LIVE schema key was a miss
    assert db.metrics.counters_snapshot().get("plan artifact miss", 0) >= 1
    db.close()


def test_toolchain_drift_rejects_artifact_and_recompiles(tmp_path):
    rows0 = _seed(tmp_path)
    def bump(meta):
        meta.env = dict(meta.env, jax="0.0.0-doctored")
    _doctor_metas(tmp_path, bump)
    db = _boot(tmp_path)
    snap = db.metrics.counters_snapshot()
    assert snap.get("plan artifact version mismatch", 0) >= 1
    assert snap.get("plan artifact warm load", 0) == 0
    rows, compiles = _first_exec(db)
    assert rows == rows0
    assert compiles == 1
    # the session-path rejection was counted too (hydrate retried on use)
    assert db.metrics.counters_snapshot().get(
        "plan artifact version mismatch", 0) >= 2
    db.close()


def test_corrupted_blob_recompiles_cleanly(tmp_path):
    rows0 = _seed(tmp_path)
    root = tmp_path / "node" / "plan_artifacts"
    blobs = list(root.glob("*.x"))
    assert blobs
    for p in blobs:
        p.write_bytes(b"\x00garbage" * 16)
    db = _boot(tmp_path)
    snap = db.metrics.counters_snapshot()
    assert snap.get("plan artifact load error", 0) >= 1
    assert snap.get("plan artifact warm load", 0) == 0
    rows, compiles = _first_exec(db)
    assert rows == rows0
    assert compiles == 1
    db.close()


def test_truncated_blob_recompiles_cleanly(tmp_path):
    rows0 = _seed(tmp_path)
    root = tmp_path / "node" / "plan_artifacts"
    for p in root.glob("*.x"):
        p.write_bytes(p.read_bytes()[: max(8, p.stat().st_size // 3)])
    db = _boot(tmp_path)
    assert db.metrics.counters_snapshot().get(
        "plan artifact load error", 0) >= 1
    rows, compiles = _first_exec(db)
    assert rows == rows0
    assert compiles == 1
    db.close()


def test_capacity_overflow_reexports_at_new_capacity(tmp_path):
    _seed(tmp_path, nrows=64)

    # grow the table far past the exported capacity, then re-run Q: the
    # overflow recompile must re-export (or the overflow replays on
    # every warm boot)
    db = _boot(tmp_path)
    s = db.session()
    s.sql("insert into art_t values " + ", ".join(
        f"({i}, {i % 5}, {i})" for i in range(64, 1600)))
    rows1 = s.sql(Q).rows()
    assert db.metrics.counters_snapshot().get(
        "plan artifact reexport", 0) >= 1
    db._save_node_meta()
    db.close()

    # next boot hydrates the RE-exported executable: zero compiles and
    # the post-growth rows, not the pre-growth capacity
    db2 = _boot(tmp_path)
    rows2, compiles = _first_exec(db2)
    assert rows2 == rows1
    assert compiles == 0
    assert db2.metrics.counters_snapshot().get("plan artifact hit", 0) >= 1
    db2.close()


def test_store_flush_forgets_artifacts(tmp_path):
    rows0 = _seed(tmp_path)
    db = _boot(tmp_path)
    assert db.metrics.counters_snapshot().get(
        "plan artifact warm load", 0) >= 1
    db.plan_cache.flush()  # schema/privilege-driven invalidation path
    snap = db.metrics.counters_snapshot()
    assert snap.get("plan artifact flush", 0) >= 1
    assert not db.plan_artifact._index["entries"]
    rows, compiles = _first_exec(db)
    assert rows == rows0
    assert compiles == 1  # nothing hydrates back after the flush
    db.close()


def test_a_dictionary_grown_after_warm_boot_recompiles(tmp_path):
    """A hydrated executable hands back the dictionaries it was exported
    with: after a string lands in its table it recompiles once (and is then
    a traceable program like any other), never decodes through the old."""
    q = "select id, s from art_s order by s"
    db = _boot(tmp_path)
    s = db.session()
    s.sql("alter system set ob_plan_artifact_mode = 'rw'")
    s.sql("create table art_s (id bigint primary key, s varchar(8))")
    s.sql("insert into art_s values (1, 'b'), (2, 'd')")
    assert s.sql(q).rows() == [(1, "b"), (2, "d")]
    db._save_node_meta()
    db.close()
    db = _boot(tmp_path)
    assert db.metrics.counters_snapshot().get("plan artifact warm load", 0)
    s = db.session()
    assert s.sql(q).rows() == [(1, "b"), (2, "d")]
    ex = db.engine.executor
    c0 = ex.compiles
    s.sql("insert into art_s values (3, 'a'), (4, 'c')")
    assert s.sql(q).rows() == [(3, "a"), (1, "b"), (4, "c"), (2, "d")]
    assert ex.compiles - c0 == 1
    db.close()
