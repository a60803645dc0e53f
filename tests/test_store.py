"""Store tests: write-then-read round trips per table and idempotent
upserts — the reference's pattern minus the live Cassandra container
(test/test_cassandra.py, test_chip/pixel/segment/tile.py)."""

import re
import sqlite3

import numpy as np
import pytest

from firebird_tpu.store import (AsyncWriter, CassandraStore, MemoryStore,
                                ParquetStore, SqliteStore, open_store)
from firebird_tpu.store.schema import TABLES, primary_key


def seg_frame(cx=1, cy=2, px=3, py=4, sday="1999-01-01", chprob=1.0):
    f = {"cx": [cx], "cy": [cy], "px": [px], "py": [py],
         "sday": [sday], "eday": ["2000-01-01"], "bday": [sday],
         "chprob": [chprob], "curqa": [8], "rfrawp": [None]}
    for p in ("bl", "gr", "re", "ni", "s1", "s2", "th"):
        f[f"{p}mag"] = [1.5]
        f[f"{p}rmse"] = [0.5]
        f[f"{p}coef"] = [[0.1, 0.2, 0.3]]
        f[f"{p}int"] = [7.0]
    return f


def make_stores(tmp_path):
    return [MemoryStore("ks"),
            SqliteStore(str(tmp_path / "s.db"), "ks"),
            ParquetStore(str(tmp_path / "pq"), "ks")]


@pytest.mark.parametrize("backend", ["memory", "sqlite", "parquet"])
def test_roundtrip_all_tables(tmp_path, backend):
    store = open_store(backend, str(tmp_path / "st"), "ks")
    store.write("chip", {"cx": [10], "cy": [20],
                         "dates": [["1999-01-01", "1999-02-01"]]})
    store.write("pixel", {"cx": [10], "cy": [20], "px": [10], "py": [20],
                          "mask": [[1, 0]]})
    store.write("segment", seg_frame(cx=10, cy=20))
    store.write("tile", {"tx": [1], "ty": [2], "name": ["rf"],
                         "model": ["BLOB"], "updated": ["2020-01-01"]})
    assert store.read("chip", {"cx": 10, "cy": 20})["dates"][0] == \
        ["1999-01-01", "1999-02-01"]
    assert store.read("pixel")["mask"][0] == [1, 0]
    seg = store.read("segment")
    assert seg["blcoef"][0] == [0.1, 0.2, 0.3]
    assert seg["chprob"][0] == 1.0
    assert store.read("tile")["model"] == ["BLOB"]
    store.close()


@pytest.mark.parametrize("backend", ["memory", "sqlite"])
def test_upsert_idempotence(tmp_path, backend):
    """Rerunning the same keys must not duplicate rows — the reference's
    durability model (PK upserts, SURVEY.md §5)."""
    store = open_store(backend, str(tmp_path / "st"), "ks")
    store.write("segment", seg_frame(chprob=0.5))
    store.write("segment", seg_frame(chprob=0.9))  # same key, new value
    out = store.read("segment")
    assert len(out["cx"]) == 1
    assert out["chprob"][0] == 0.9
    # different sday -> second row (sday is part of the segment key)
    store.write("segment", seg_frame(sday="2001-01-01"))
    assert store.count("segment") == 2
    store.close()


def test_parquet_chip_rewrite_idempotent(tmp_path):
    store = ParquetStore(str(tmp_path / "pq"), "ks")
    store.write("segment", seg_frame(cx=5, cy=6, chprob=0.1))
    store.write("segment", seg_frame(cx=5, cy=6, chprob=0.7))
    out = store.read("segment", {"cx": 5})
    assert len(out["cx"]) == 1 and out["chprob"][0] == 0.7


def test_keyspace_isolation(tmp_path):
    a = SqliteStore(str(tmp_path / "s.db"), "ks_a")
    b = SqliteStore(str(tmp_path / "s.db"), "ks_b")
    a.write("tile", {"tx": [1], "ty": [1], "name": ["m"], "model": ["A"],
                     "updated": ["x"]})
    assert b.count("tile") == 0


def test_async_writer_keyed_ordering():
    """Frames sharing a key drain in submission order even with many
    workers — the driver's resume invariant (segment frame last per
    chip)."""
    order: dict[tuple, list] = {}
    lock = __import__("threading").Lock()

    class Recorder(MemoryStore):
        def write(self, table, frame):
            k = (frame["cx"][0], frame["cy"][0])
            with lock:
                order.setdefault(k, []).append(table)
            return 1

    w = AsyncWriter(Recorder(), workers=4)
    for i in range(24):
        cid = (i, 0)
        for t in ("chip", "pixel", "segment"):
            w.write(t, {"cx": [i], "cy": [0]}, key=cid)
    w.flush()
    w.close()
    assert len(order) == 24
    for seq in order.values():
        assert seq == ["chip", "pixel", "segment"]


def test_async_writer_multiworker_raises_on_error():
    class Boom(MemoryStore):
        def write(self, table, frame):
            raise RuntimeError("disk full")

    w = AsyncWriter(Boom(), workers=3)
    # the error may surface from write() (if a worker already failed) or
    # from flush() — both are the contract
    with pytest.raises(RuntimeError, match="disk full"):
        for i in range(6):
            w.write("chip", {"cx": [i], "cy": [0], "dates": [[]]}, key=(i,))
        w.flush()
    w.close()


def test_queue_depth_gauge_drains_on_flush_failure():
    """Regression: the store_queue_depth gauge must read 0 after a flush
    whose writes FAILED, not just after successful drains — a failing
    backend must not leave a phantom backlog on the egress-backpressure
    signal (and the failure itself must still be counted + raised).

    The backend gates on an event so every frame is verifiably queued
    (gauge > 0) before the first failure fires — no interleaving can
    short-circuit the test through write()'s error re-raise path."""
    import threading

    from firebird_tpu.obs import metrics as obs_metrics

    obs_metrics.reset_registry()
    gate = threading.Event()

    class Boom(MemoryStore):
        def write(self, table, frame):
            gate.wait(timeout=10)
            raise RuntimeError("disk full")

    w = AsyncWriter(Boom(), workers=2)
    for i in range(8):
        w.write("chip", {"cx": [i], "cy": [0], "dates": [[]]}, key=(i,))
    # a real backlog exists while the backend is stuck
    assert obs_metrics.gauge("store_queue_depth").value > 0
    gate.set()
    with pytest.raises(RuntimeError, match="disk full"):
        w.flush()
    # all queued frames drained (through the failure path) by flush time
    assert obs_metrics.gauge("store_queue_depth").value == 0
    assert obs_metrics.counter("store_write_errors").value >= 1
    w.close()
    assert obs_metrics.gauge("store_queue_depth").value == 0


def test_full_queue_backpressure_and_writer_cpu_are_measured():
    """A slow backend behind a one-frame queue: the caller's puts wait
    (store_queue_wait_seconds > 0, one observation per write), and the
    writer's CPU seconds inside the backend are at most its wall seconds
    (a sleeping backend is nearly all wait)."""
    import time

    from firebird_tpu.obs import metrics as obs_metrics

    obs_metrics.reset_registry()

    class Slow(MemoryStore):
        def write(self, table, frame):
            time.sleep(0.02)
            return super().write(table, frame)

    w = AsyncWriter(Slow(), max_queue=1)
    for i in range(5):
        w.write("chip", {"cx": [i], "cy": [0], "dates": [["1999-01-01"]]})
    w.flush()
    w.close()
    snap = obs_metrics.get_registry().snapshot()["histograms"]
    obs_metrics.reset_registry()
    qw = snap["store_queue_wait_seconds"]
    assert qw["count"] == 5 and qw["sum"] > 0.01
    wall, cpu = snap["store_write_seconds"], snap["store_write_cpu_seconds"]
    assert wall["count"] == cpu["count"] == 5
    assert cpu["sum"] <= wall["sum"]
    assert wall["sum"] >= 5 * 0.02


def test_async_writer_drains_and_raises(tmp_path):
    store = MemoryStore()
    w = AsyncWriter(store)
    for i in range(20):
        w.write("chip", {"cx": [i], "cy": [0], "dates": [["1999-01-01"]]})
    w.flush()
    assert store.count("chip") == 20

    class Boom(MemoryStore):
        def write(self, table, frame):
            raise RuntimeError("disk full")

    w2 = AsyncWriter(Boom())
    w2.write("chip", {"cx": [1], "cy": [0], "dates": [[]]})
    with pytest.raises(RuntimeError, match="disk full"):
        w2.flush()
    w.close()


def test_async_writer_worker_survives_base_exception():
    """The writer.py BaseException branch (previously untested): a
    backend raising KeyboardInterrupt must not kill the worker thread
    with un-acked queue items (flush would hang forever) — the item is
    acked, the error surfaces from flush as a wrapped Exception, and the
    writer keeps working afterward."""
    calls = {"n": 0}

    class Interrupted(MemoryStore):
        def write(self, table, frame):
            calls["n"] += 1
            if calls["n"] == 1:
                raise KeyboardInterrupt("operator mashed ^C")
            return super().write(table, frame)

    store = Interrupted()
    w = AsyncWriter(store)
    w.write("chip", {"cx": [1], "cy": [0], "dates": [[]]})
    with pytest.raises(RuntimeError, match="writer interrupted"):
        w.flush()                       # surfaces, does NOT hang
    assert all(t.is_alive() for t in w._threads)
    # the worker is still functional: later writes land normally
    w.write("chip", {"cx": [2], "cy": [0], "dates": [[]]})
    w.flush()
    assert store.count("chip") == 1
    w.close()


def test_async_writer_retry_policy_heals_brownout():
    """A store brownout shorter than the retry budget heals inline: no
    error reaches flush, every row lands, and the retries are counted as
    store_write_retries (the chaos-smoke store path in miniature)."""
    from firebird_tpu.obs import metrics as obs_metrics
    from firebird_tpu.retry import RetryPolicy

    obs_metrics.reset_registry()
    calls = {"n": 0}

    class Brownout(MemoryStore):
        def write(self, table, frame):
            calls["n"] += 1
            if calls["n"] in (2, 3):   # two consecutive failures
                raise IOError("store browned out")
            return super().write(table, frame)

    store = Brownout()
    w = AsyncWriter(store, retry=RetryPolicy(3, sleep=lambda s: None,
                                             counter_name="store_write_retries"))
    for i in range(4):
        w.write("chip", {"cx": [i], "cy": [0], "dates": [[]]}, key=(i,))
    w.flush()                           # heals: nothing raises
    w.close()
    assert store.count("chip") == 4
    assert obs_metrics.counter("store_write_retries").value == 2
    assert obs_metrics.counter("store_write_errors").value == 0


# ---------------------------------------------------------------------------
# Cassandra backend (injectable-session seam; no cluster needed)
# ---------------------------------------------------------------------------

class FakePrepared:
    def __init__(self, cql):
        self.cql = cql
        m = re.match(r"INSERT INTO \w+\.(\w+) \(([^)]*)\)", cql)
        self.table = m.group(1)
        self.cols = [c.strip() for c in m.group(2).split(",")]


class FakeFuture:
    def __init__(self):
        self.done = False

    def result(self):
        self.done = True


class FakeCqlSession:
    """Executes the exact CQL shapes CassandraStore generates against an
    in-memory table dict — enough to run the generic round-trip tests."""

    def __init__(self):
        self.ddl: list[str] = []
        self.tables: dict[str, dict] = {}
        self.max_in_flight = 0
        self._in_flight: list[FakeFuture] = []

    def prepare(self, cql):
        return FakePrepared(cql)

    def execute_async(self, stmt, params):
        row = dict(zip(stmt.cols, params))
        key = tuple(row[k] for k in primary_key(stmt.table))
        self.tables.setdefault(stmt.table, {})[key] = row
        f = FakeFuture()
        self._in_flight = [x for x in self._in_flight if not x.done] + [f]
        self.max_in_flight = max(self.max_in_flight, len(self._in_flight))
        return f

    def execute(self, cql, params=()):
        if cql.startswith(("CREATE KEYSPACE", "CREATE TABLE")):
            self.ddl.append(cql)
            return []
        m = re.match(r"SELECT (.+) FROM \w+\.(\w+)(?: WHERE (.+?))?"
                     r"(?: ALLOW FILTERING)?$", cql)
        cols, table, where = m.group(1), m.group(2), m.group(3)
        rows = list(self.tables.get(table, {}).values())
        if where:
            keys = re.findall(r"(\w+) = %s", where)
            rows = [r for r in rows
                    if all(r.get(k) == v for k, v in zip(keys, params))]
        if cols.startswith("COUNT"):
            return [(len(rows),)]
        distinct = cols.startswith("DISTINCT ")
        names = [c.strip() for c in cols.removeprefix("DISTINCT ").split(",")]
        out = [tuple(r.get(c) for c in names) for r in rows]
        return list(dict.fromkeys(out)) if distinct else out


def test_cassandra_roundtrip_all_tables():
    sess = FakeCqlSession()
    store = CassandraStore(keyspace="ks", session=sess)
    store.write("chip", {"cx": [10], "cy": [20],
                         "dates": [["1999-01-01", "1999-02-01"]]})
    store.write("segment", seg_frame(cx=10, cy=20))
    assert store.read("chip", {"cx": 10, "cy": 20})["dates"][0] == \
        ["1999-01-01", "1999-02-01"]
    seg = store.read("segment")
    assert seg["blcoef"][0] == [0.1, 0.2, 0.3]
    assert store.count("segment") == 1
    assert store.chip_ids("segment") == {(10, 20)}


def test_cassandra_schema_parity():
    """DDL mirrors resources/schema.cql key design: partition key = first
    two key columns, remaining key columns clustering."""
    sess = FakeCqlSession()
    CassandraStore(keyspace="my-ks!", session=sess)
    assert any("CREATE KEYSPACE IF NOT EXISTS my_ks_" in d for d in sess.ddl)
    seg_ddl = next(d for d in sess.ddl if ".segment" in d)
    assert "PRIMARY KEY ((cx, cy), px, py, sday, eday)" in seg_ddl
    chip_ddl = next(d for d in sess.ddl if ".chip" in d)
    assert "PRIMARY KEY ((cx, cy))" in chip_ddl


def test_cassandra_ddl_generator_matches_backend():
    """`firebird schema` prints exactly what CassandraStore executes (the
    reference's resources/schema.cql + `make db-schema` path)."""
    from firebird_tpu.store import cassandra_ddl

    sess = FakeCqlSession()
    CassandraStore(keyspace="my-ks!", session=sess)
    assert sess.ddl == cassandra_ddl("my-ks!")
    assert [d for d in cassandra_ddl("ks") if "CREATE TABLE" in d] \
        and all(t in " ".join(cassandra_ddl("ks"))
                for t in ("chip", "pixel", "segment", "tile", "product"))
    # unquoted CQL identifiers must start with a letter: digit- and
    # underscore-leading names get the ks_ prefix (deploy/README.md)
    from firebird_tpu.store.backends import sanitize_keyspace

    assert sanitize_keyspace("!prod") == "ks__prod"
    assert sanitize_keyspace("_prod") == "ks__prod"
    assert sanitize_keyspace("9lives") == "ks_9lives"
    assert sanitize_keyspace("") == "default"


def test_cli_schema_command():
    from click.testing import CliRunner

    from firebird_tpu.cli import entrypoint

    res = CliRunner().invoke(entrypoint, ["schema", "-k", "1bad ks!"])
    assert res.exit_code == 0, res.output
    assert "CREATE KEYSPACE IF NOT EXISTS ks_1bad_ks_" in res.output
    for t in ("chip", "pixel", "segment", "tile", "product"):
        assert f"ks_1bad_ks_.{t} " in res.output
    assert res.output.rstrip().endswith(";")


def test_cassandra_upsert_and_bounded_writes():
    sess = FakeCqlSession()
    store = CassandraStore(keyspace="ks", session=sess, concurrent_writes=2)
    f = seg_frame(chprob=0.5)
    multi = {k: v * 50 for k, v in f.items()}
    multi["px"] = list(range(50))
    store.write("segment", multi)
    assert store.count("segment") == 50
    assert sess.max_in_flight <= 3     # 2 waiting + the one being issued
    # same-key rewrite upserts
    store.write("segment", seg_frame(chprob=0.9))
    before = store.count("segment")
    store.write("segment", seg_frame(chprob=0.2))
    assert store.count("segment") == before


def test_cassandra_missing_driver_is_clear():
    try:
        import cassandra  # noqa: F401
        pytest.skip("cassandra-driver is installed here")
    except ImportError:
        pass
    with pytest.raises(RuntimeError, match="cassandra-driver"):
        CassandraStore(keyspace="ks")


def test_schema_matches_reference_column_set():
    """Segment column set mirrors ccdc/segment.py:16-56 (38 cols: 9 meta +
    28 band + rfrawp); chip/pixel/tile match their modules."""
    seg_cols = [c for c, _ in TABLES["segment"]["columns"]]
    assert len(seg_cols) == 38
    for p in ("bl", "gr", "re", "ni", "s1", "s2", "th"):
        for suffix in ("mag", "rmse", "coef", "int"):
            assert f"{p}{suffix}" in seg_cols
    assert TABLES["segment"]["key"] == ("cx", "cy", "px", "py", "sday", "eday")
    assert [c for c, _ in TABLES["chip"]["columns"]] == ["cx", "cy", "dates"]
    assert [c for c, _ in TABLES["pixel"]["columns"]] == \
        ["cx", "cy", "px", "py", "mask"]
    assert [c for c, _ in TABLES["tile"]["columns"]] == \
        ["tx", "ty", "name", "model", "updated"]


def test_sqlite_chip_reads_use_secondary_index(tmp_path):
    """The serve-path point read `WHERE cx=? AND cy=?` must be
    index-backed on BOTH result tables.  The segment PK's autoindex
    already leads with (cx, cy), but the product PK leads with
    (name, date) — without idx_product_chip a per-chip product read
    scans the whole table (backends.SqliteStore._create).  The segment
    table is created by the store's first segment frame."""
    store = SqliteStore(str(tmp_path / "idx.db"), "ks")
    try:
        store.write("segment", seg_frame())
        con = store._conn()
        for table in ("segment", "product"):
            plan = " ".join(
                row[3] for row in con.execute(
                    f'EXPLAIN QUERY PLAN SELECT * FROM "{table}" '
                    "WHERE cx = ? AND cy = ?", (1, 2)))
            assert "USING INDEX" in plan.upper(), \
                f"{table} chip read is not index-backed: {plan}"
            assert "SCAN" not in plan.upper(), \
                f"{table} chip read scans: {plan}"
        # the product index is the explicit secondary one
        plan = " ".join(
            row[3] for row in con.execute(
                'EXPLAIN QUERY PLAN SELECT * FROM "product" '
                "WHERE cx = ? AND cy = ?", (1, 2)))
        assert "idx_product_chip" in plan
    finally:
        store.close()


# ---------------------------------------------------------------------------
# Read-only replica connections (serve fleet; docs/SERVING.md)
# ---------------------------------------------------------------------------

def test_sqlite_read_only_replica_cannot_write(tmp_path):
    """A mode=ro replica open can read everything and write NOTHING —
    neither through the refusing facade nor past it at the SQL layer
    (PRAGMA query_only)."""
    import pytest

    path = str(tmp_path / "repl.db")
    writer = SqliteStore(path, "t")
    writer.write("segment", {
        "cx": [1], "cy": [2], "px": [1], "py": [2],
        "sday": ["1995-01-01"], "eday": ["1999-01-01"],
        "bday": ["0001-01-01"], "chprob": [0.0], "curqa": [4],
    })
    replica = open_store("sqlite", path, "t", read_only=True)
    try:
        assert replica.read("segment", {"cx": 1, "cy": 2})["px"] == [1]
        assert replica.count("segment") == 1
        with pytest.raises(RuntimeError, match="read-only replica"):
            replica.write("segment", {"cx": [9], "cy": [9], "px": [9],
                                      "py": [9]})
        # defense in depth: even a raw statement on the connection is
        # refused by PRAGMA query_only / the ro VFS open
        with pytest.raises(sqlite3.OperationalError):
            replica._conn().execute(
                'INSERT INTO "segment" (cx, cy, px, py) '
                "VALUES (9, 9, 9, 9)")
    finally:
        replica.close()
        writer.close()


def test_sqlite_read_only_requires_existing_db(tmp_path):
    import pytest

    with pytest.raises(FileNotFoundError, match="read-only replica"):
        open_store("sqlite", str(tmp_path / "nope.db"), "t",
                   read_only=True)
    with pytest.raises(ValueError, match="replica mode"):
        open_store("memory", "", "t", read_only=True)


def test_read_only_replica_does_not_block_live_writer(tmp_path):
    """The satellite regression: N replicas reading a WAL store must
    never contend on the writer's lock — a replica holding a long read
    cannot stall a live AsyncWriter flush."""
    import threading
    import time

    path = str(tmp_path / "live.db")
    store = SqliteStore(path, "t")
    frame = {
        "cx": [5], "cy": [6], "px": [5], "py": [6],
        "sday": ["1995-01-01"], "eday": ["1999-01-01"],
        "bday": ["0001-01-01"], "chprob": [0.0], "curqa": [4],
    }
    store.write("segment", frame)
    replica = open_store("sqlite", path, "t", read_only=True)
    stop = threading.Event()

    def read_loop():
        while not stop.is_set():
            replica.read("segment")

    readers = [threading.Thread(target=read_loop, daemon=True)
               for _ in range(3)]
    for t in readers:
        t.start()
    w = AsyncWriter(store)
    try:
        t0 = time.monotonic()
        for i in range(30):
            w.write("segment", dict(frame, px=[5 + i]), key=(5, 6))
            if i % 10 == 9:
                w.flush()
        elapsed = time.monotonic() - t0
        # WAL: writer never waits on readers.  The generous bound only
        # fails if the replica actually BLOCKED the writer (the
        # pre-mode=ro failure was 'database is locked' stalls).
        assert elapsed < 20.0
    finally:
        w.close()
        stop.set()
        for t in readers:
            t.join(5)
        replica.close()
        store.close()


# ---------------------------------------------------------------------------
# The segment table keyed by sensor: Landsat's pinned, one sensor a store
# ---------------------------------------------------------------------------

# The reference's segment table (ccdc/segment.py:16-56, schema.cql:103-142)
# as this store has always written it: names, order and types.
LANDSAT_SEGMENT_COLUMNS = [
    ("cx", "INTEGER"), ("cy", "INTEGER"), ("px", "INTEGER"),
    ("py", "INTEGER"), ("sday", "TEXT"), ("eday", "TEXT"), ("bday", "TEXT"),
    ("chprob", "REAL"), ("curqa", "INTEGER"),
    ("blmag", "REAL"), ("blrmse", "REAL"), ("blcoef", "F64S"),
    ("blint", "REAL"),
    ("grmag", "REAL"), ("grrmse", "REAL"), ("grcoef", "F64S"),
    ("grint", "REAL"),
    ("remag", "REAL"), ("rermse", "REAL"), ("recoef", "F64S"),
    ("reint", "REAL"),
    ("nimag", "REAL"), ("nirmse", "REAL"), ("nicoef", "F64S"),
    ("niint", "REAL"),
    ("s1mag", "REAL"), ("s1rmse", "REAL"), ("s1coef", "F64S"),
    ("s1int", "REAL"),
    ("s2mag", "REAL"), ("s2rmse", "REAL"), ("s2coef", "F64S"),
    ("s2int", "REAL"),
    ("thmag", "REAL"), ("thrmse", "REAL"), ("thcoef", "F64S"),
    ("thint", "REAL"),
    ("rfrawp", "F64S")]

S2_PREFIXES = ("ca", "bl", "gr", "re", "r1", "r2", "r3", "ni", "n8", "wv",
               "s1", "s2")


def s2_frame(cx=1, cy=2, px=3, py=4):
    """A one-row Sentinel-2 segment frame: Landsat's decision columns and
    the twelve bands' four columns each."""
    f = {k: v for k, v in seg_frame(cx, cy, px, py).items()
         if not k.endswith(("mag", "rmse", "coef", "int"))}
    for i, p in enumerate(S2_PREFIXES):
        f[f"{p}mag"] = [float(i)]
        f[f"{p}rmse"] = [0.5 + i]
        f[f"{p}coef"] = [[0.1 * i, 0.2, 0.3]]
        f[f"{p}int"] = [7.0 + i]
    return f


def test_landsat_segment_table_columns_are_pinned(tmp_path):
    """Landsat's segment table is today's, column for column, in the
    schema and in the sqlite file a Landsat run creates."""
    from firebird_tpu.ccd.sensor import LANDSAT_ARD
    from firebird_tpu.store import schema

    assert TABLES["segment"]["columns"] == LANDSAT_SEGMENT_COLUMNS
    assert schema.segment_columns(LANDSAT_ARD.store_prefixes) == \
        LANDSAT_SEGMENT_COLUMNS
    store = SqliteStore(str(tmp_path / "ls.db"), "ks")
    try:
        store.write("segment", seg_frame())
        sql = {"F64S": "BLOB"}
        got = [(r[1], r[2]) for r in store._conn().execute(
            'PRAGMA table_info("segment")')]
        assert got == [(c, sql.get(t, t))
                       for c, t in LANDSAT_SEGMENT_COLUMNS]
    finally:
        store.close()


@pytest.mark.parametrize("backend", ["memory", "sqlite"])
@pytest.mark.parametrize("first", ["landsat", "sentinel2"])
def test_a_store_holds_one_sensor(tmp_path, backend, first):
    """The segment table takes its band columns from the first segment
    frame; a frame of the other sensor is refused, naming both sets, and
    nothing of it lands."""
    frames = {"landsat": seg_frame(), "sentinel2": s2_frame(px=9)}
    other = "sentinel2" if first == "landsat" else "landsat"
    store = open_store(backend, str(tmp_path / "st"), "ks")
    try:
        store.write("segment", frames[first])
        with pytest.raises(ValueError, match="one sensor") as e:
            store.write("segment", frames[other])
        assert "'th'" in str(e.value) and "'n8'" in str(e.value)
        assert store.count("segment") == 1
        out = store.read("segment")
        prefixes = S2_PREFIXES if first == "sentinel2" else \
            ("bl", "gr", "re", "ni", "s1", "s2", "th")
        assert [c for c in out if c.endswith("coef")] == \
            [f"{p}coef" for p in prefixes]
        # a frame with no band column writes none and passes either way
        store.write("segment", {k: v for k, v in seg_frame(px=7).items()
                                if not k.endswith(("mag", "rmse", "coef",
                                                   "int"))})
        assert store.count("segment") == 2
    finally:
        store.close()


def test_sqlite_reopen_takes_the_bands_of_its_table(tmp_path):
    """A reopened store (writer or read-only replica) reads its segment
    columns from the table on disk, and keeps refusing the other
    sensor."""
    path = str(tmp_path / "s2.db")
    store = SqliteStore(path, "ks")
    store.write("segment", s2_frame())
    store.close()
    again = SqliteStore(path, "ks")
    replica = open_store("sqlite", path, "ks", read_only=True)
    try:
        for s in (again, replica):
            out = s.read("segment")
            assert out["n8coef"] == [[0.8, 0.2, 0.3]]
            assert out["wvint"] == [16.0]
            assert "thcoef" not in out
        with pytest.raises(ValueError, match="one sensor"):
            again.write("segment", seg_frame(px=5))
    finally:
        again.close()
        replica.close()


def test_sqlite_reads_before_the_first_segment_frame(tmp_path):
    """Until its first segment frame a store has no segment table: reads
    see an empty Landsat table, and the other tables are there."""
    store = SqliteStore(str(tmp_path / "e.db"), "ks")
    try:
        assert store.count("segment") == 0
        assert store.chip_ids("segment") == set()
        assert store.read("segment", {"cx": 1})["blcoef"] == []
        assert store.count("pixel") == 0
    finally:
        store.close()


@pytest.mark.parametrize("backend", ["parquet", "cassandra", "object"])
def test_landsat_only_backends_refuse_sentinel2(tmp_path, backend,
                                                 monkeypatch):
    """Parquet, Cassandra and the object tier keep Landsat's segment
    columns: a Sentinel-2 frame is refused before any row lands."""
    if backend == "cassandra":
        store = CassandraStore(keyspace="ks", session=FakeCqlSession())
    else:
        monkeypatch.setenv("FIREBIRD_OBJECT_ROOT", str(tmp_path / "obj"))
        store = open_store(backend, str(tmp_path / "st"), "ks")
    with pytest.raises(ValueError, match="one sensor"):
        store.write("segment", s2_frame())
    store.write("segment", seg_frame())
    assert store.count("segment") == 1


@pytest.mark.parametrize("reader", ["products", "features"])
def test_landsat_readers_refuse_sentinel2_segments(tmp_path, reader):
    """Readers that name Landsat's band columns refuse a Sentinel-2
    store's segments instead of reading NULLs."""
    from firebird_tpu import products
    from firebird_tpu.rf import features

    store = SqliteStore(str(tmp_path / "r.db"), "ks")
    try:
        store.write("segment", s2_frame(cx=0, cy=3000, px=0, py=3000))
        seg = store.read("segment")
    finally:
        store.close()
    with pytest.raises(ValueError, match="Landsat ARD's segment columns"):
        if reader == "products":
            products.ChipSegmentArrays(0, 3000, seg)
        else:
            features.assemble(seg, {}, 0, 3000)
