"""End-to-end driver + CLI tests: the minimum slice of SURVEY.md §7 —
synthetic source -> packer -> CCD kernel -> format -> store -> CLI."""

import numpy as np
import pytest
from click.testing import CliRunner

from firebird_tpu import cli, grid
from firebird_tpu.config import Config
from firebird_tpu.driver import core
from firebird_tpu.ingest import SyntheticSource
from firebird_tpu.store import MemoryStore

ACQ = "1995-01-01/1997-06-01"  # short archive so CPU compile stays fast
# chips_per_batch=1 keeps every kernel dispatch on the same [1,7,P,T]
# compiled shape, so all tests in this module share one jit cache entry;
# device_sharding='off' keeps full-chip dispatches from padding 1 -> 8
# virtual devices (the sharded driver path is covered on sliced batches by
# test_detect_batch_shards_and_pads).
CFG = Config(store_backend="memory", source_backend="synthetic",
             chips_per_batch=1, dtype="float64", device_sharding="off",
             fetch_retries=0)


@pytest.fixture(scope="module")
def run_result():
    store = MemoryStore("test")
    src = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1)
    done = core.changedetection(x=100, y=200, acquired=ACQ, number=2,
                                chunk_size=2, cfg=CFG, source=src,
                                store=store)
    return done, store


def test_changedetection_end_to_end(run_result):
    done, store = run_result
    assert len(done) == 2
    # chip table: one row per chip with the aligned ISO dates
    chips = store.read("chip")
    assert len(chips["cx"]) == 2
    assert all(d.startswith("1995-") for d in chips["dates"][0][:1])
    # pixel table: 10k masks per chip
    assert store.count("pixel") == 20000
    # segment table: at least one row per pixel (sentinel or real)
    assert store.count("segment") >= 20000
    seg = store.read("segment", {"cx": done[0][0], "cy": done[0][1]})
    assert len(seg["cx"]) >= 10000
    # real segments carry models
    real = [i for i, s in enumerate(seg["sday"]) if s != "0001-01-01"]
    assert len(real) >= 9000
    i = real[0]
    assert seg["nicoef"][i] is not None and len(seg["nicoef"][i]) == 7
    assert seg["nirmse"][i] > 0


def test_rerun_is_idempotent(run_result):
    done, store = run_result
    src = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1)
    before = store.count("segment")
    core.changedetection(x=100, y=200, acquired=ACQ, number=1, chunk_size=1,
                         cfg=CFG, source=src, store=store)
    assert store.count("segment") == before


def test_float64_config_enables_x64():
    """FIREBIRD_DTYPE=float64 must actually compute in f64 — without
    jax_enable_x64, jnp silently downcasts and a 'bit-parity run' would
    run at single precision."""
    import jax

    assert jax.config.jax_enable_x64      # conftest baseline
    try:
        jax.config.update("jax_enable_x64", False)
        store = MemoryStore("x64test")
        src = SyntheticSource(seed=9, start="1995-01-01", end="1996-06-01")
        core.changedetection(x=100, y=200, acquired="1995-01-01/1996-06-01",
                             number=1, chunk_size=1, cfg=CFG, source=src,
                             store=store)
        assert jax.config.jax_enable_x64  # detect_chunk turned it back on
        # and the store actually holds results (the run happened)
        assert store.count("segment") >= 10000
    finally:
        jax.config.update("jax_enable_x64", True)


def test_host_shard_partitions_without_overlap(monkeypatch):
    """Multi-host runs split the chip list disjointly and completely —
    the union of all hosts' work equals the single-host run."""
    import jax

    cids = [(i, 0) for i in range(10)]
    assert core.host_shard(cids) == cids      # single-process: unchanged

    shards = []
    monkeypatch.setattr(jax, "process_count", lambda: 3)
    for i in range(3):
        monkeypatch.setattr(jax, "process_index", lambda i=i: i)
        shards.append(core.host_shard(cids))
    flat = [c for s in shards for c in s]
    assert sorted(flat) == cids               # complete, no overlap
    assert max(len(s) for s in shards) - min(len(s) for s in shards) <= 1


def test_chunk_failure_isolation():
    """A source that explodes on one chunk must not kill the run
    (core.py:115-124 semantics)."""
    store = MemoryStore("test")
    good = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01")
    calls = {"n": 0}

    class Flaky:
        def chip(self, cx, cy, acquired=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise IOError("chipmunk down")
            return good.chip(cx, cy, acquired)

    done = core.changedetection(x=100, y=200, acquired=ACQ, number=2,
                                chunk_size=1, cfg=CFG, source=Flaky(),
                                store=store)
    assert len(done) == 1           # first chunk failed, second landed
    assert store.count("chip") == 1


def test_resume_skips_stored_chips(run_result):
    done, store = run_result

    class Explodes:
        def chip(self, cx, cy, acquired=None):
            raise AssertionError("resume must not refetch stored chips")

    out = core.changedetection(x=100, y=200, acquired=ACQ, number=2,
                               chunk_size=2, cfg=CFG, source=Explodes(),
                               store=store, resume=True)
    assert set(out) == set(done)    # all skipped, none refetched


def test_transient_fetch_retries(monkeypatch):
    """A transient per-chip fetch failure is absorbed by the retry loop
    instead of failing the chunk (Spark-task-retry semantics)."""
    monkeypatch.setattr(core.time, "sleep", lambda s: None)
    store = MemoryStore("test")
    good = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01")
    calls = {"n": 0}

    class Transient:
        def chip(self, cx, cy, acquired=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise IOError("blip")
            return good.chip(cx, cy, acquired)

    cfg = Config(store_backend="memory", source_backend="synthetic",
                 chips_per_batch=1, dtype="float64", device_sharding="off",
                 fetch_retries=2)
    done = core.changedetection(x=100, y=200, acquired=ACQ, number=1,
                                chunk_size=1, cfg=cfg, source=Transient(),
                                store=store)
    assert len(done) == 1 and calls["n"] == 2
    assert store.count("chip") == 1


def test_detect_batch_shards_and_pads():
    """detect_batch pads a 3-chip batch over the 8 virtual devices and
    matches the single-device result (pixel-sliced to stay quick)."""
    import jax

    from firebird_tpu.ccd import kernel
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips

    assert jax.local_device_count() == 8
    src = SyntheticSource(seed=3, start="1995-01-01", end="1997-01-01")
    p = pack([src.chip(100 + 3000 * i, 200) for i in range(3)], bucket=32)
    small = PackedChips(cids=p.cids, dates=p.dates,
                        spectra=p.spectra[:, :, :64, :],
                        qas=p.qas[:, :64, :], n_obs=p.n_obs)
    import jax.numpy as jnp
    seg, n_real = core.detect_batch(small, jnp.float64, "auto")
    assert n_real == 3
    assert seg.n_segments.shape[0] == 8      # padded over the mesh
    ref = kernel.detect_packed(small, dtype=jnp.float64)
    for f in ("n_segments", "seg_meta", "mask", "procedure"):
        np.testing.assert_array_equal(
            np.asarray(getattr(seg, f))[:3], np.asarray(getattr(ref, f)))


def test_pad_batch_noop_and_repeat():
    from firebird_tpu.ingest import SyntheticSource, pack

    src = SyntheticSource(seed=3, start="1995-01-01", end="1996-01-01")
    p = pack([src.chip(100, 200)], bucket=32)
    same, n = core._pad_batch(p, 1)
    assert same is p and n == 1
    padded, n = core._pad_batch(p, 4)
    assert n == 1 and padded.n_chips == 4
    np.testing.assert_array_equal(padded.spectra[3], p.spectra[0])


def test_drain_recomputes_on_capacity_overflow():
    """The driver dispatches without the capacity check (to stay
    asynchronous); the drain thread must detect an overflowed result and
    recompute before persisting — all segments land in the store."""
    import jax.numpy as jnp

    from firebird_tpu.ccd import kernel
    from firebird_tpu.obs import Counters
    from firebird_tpu.store import AsyncWriter
    from test_ccd_kernel import overflow_packed

    p = overflow_packed()
    seg = kernel.detect_packed(p, dtype=jnp.float64, check_capacity=False)
    worst = int(np.asarray(seg.n_segments).max())
    assert worst > kernel.MAX_SEGMENTS     # raw result really overflows
    store = MemoryStore("overflow")
    writer = AsyncWriter(store)
    try:
        core.drain_batch(seg, p, 1, writer=writer, counters=Counters(),
                         dtype=jnp.float64)
        writer.flush()
    finally:
        writer.close()
    rows = store.read("segment", {"px": 0, "py": 0})
    real = [s for s in rows["sday"] if s != "0001-01-01"]
    assert len(real) == worst              # every closed segment persisted


def test_drain_recomputes_packed_payload_on_capacity_overflow(monkeypatch):
    """An overflowed float32 batch whose egress was packed at dispatch:
    the drain probes the payload, re-dispatches with the check on, packs
    the recomputed result itself, and lands every segment."""
    import jax.numpy as jnp

    from firebird_tpu.ccd import kernel
    from firebird_tpu.obs import Counters
    from firebird_tpu.obs import metrics as obs_metrics
    from firebird_tpu.store import AsyncWriter
    from test_ccd_kernel import overflow_packed

    monkeypatch.setenv("FIREBIRD_WIRE_EGRESS", "1")
    p = overflow_packed()
    seg = kernel.detect_packed(p, dtype=jnp.float32, check_capacity=False)
    payload = core.pack_results(seg)
    assert isinstance(payload, core.Egress)
    assert core.segment_capacity(payload) == kernel.MAX_SEGMENTS
    worst = core.segment_depth(payload)
    assert worst > kernel.MAX_SEGMENTS
    store = MemoryStore("overflow32")
    writer = AsyncWriter(store)
    obs_metrics.reset_registry()
    try:
        core.drain_batch(payload, p, 1, writer=writer, counters=Counters(),
                         dtype=jnp.float32)
        writer.flush()
        counts = obs_metrics.get_registry().snapshot()["counters"]
    finally:
        writer.close()
        obs_metrics.reset_registry()
    assert counts["capacity_redispatches"] == 1
    rows = store.read("segment", {"px": 0, "py": 0})
    real = [s for s in rows["sday"] if s != "0001-01-01"]
    assert len(real) == worst


def test_cli_status_reports_store_and_tile_progress(tmp_path, monkeypatch):
    from firebird_tpu.store import SqliteStore

    db = str(tmp_path / "fb.db")
    monkeypatch.setenv("FIREBIRD_STORE_BACKEND", "sqlite")
    monkeypatch.setenv("FIREBIRD_STORE_PATH", db)
    store = SqliteStore(db, Config.from_env().keyspace())
    tile = grid.tile(542000, 1650000)
    cx, cy = (int(v) for v in tile["chips"][0])
    store.write("segment", {
        "cx": [cx], "cy": [cy], "px": [cx], "py": [cy],
        "sday": ["2000-01-01"], "eday": ["2005-01-01"],
        "bday": ["2005-01-01"], "chprob": [1.0], "curqa": [8]})
    res = CliRunner().invoke(cli.entrypoint, [
        "status", "-x", "542000", "-y", "1650000"])
    assert res.exit_code == 0, res.output
    import json

    rep = json.loads(res.output)
    assert rep["backend"] == "sqlite"
    assert rep["tables"]["segment"] == 1
    assert rep["chips_with_segments"] == 1
    assert rep["tile"] == {"h": 20, "v": 11, "chips_done": 1,
                           "chips_total": 2500}
    # one coordinate without the other is a usage error
    res = CliRunner().invoke(cli.entrypoint, ["status", "-x", "542000"])
    assert res.exit_code != 0


def test_fetch_mirrors_tile_to_file_source(tmp_path):
    """fetch writes a FileSource archive that reproduces the live source:
    same chip payloads, usable by a subsequent file-sourced run."""
    import numpy as np

    from firebird_tpu.driver import core
    from firebird_tpu.ingest import FileSource, SyntheticSource

    src = SyntheticSource(seed=2, start="1995-01-01", end="1996-06-01")
    cfg = Config(source_backend="synthetic", store_backend="memory")
    n, attempted = core.fetch(x=542000, y=1650000, outdir=str(tmp_path),
                              number=3, aux=True, cfg=cfg, source=src,
                              aux_source=src)
    assert (n, attempted) == (3, 3)
    files = sorted(p.name for p in tmp_path.iterdir())
    assert len([f for f in files if f.startswith("chip_")]) == 3
    assert len([f for f in files if f.startswith("aux_")]) == 3
    # round-trip equality against the live source for one chip
    cx, cy = (int(v) for v in grid.tile(542000, 1650000)["chips"][0])
    live = src.chip(cx, cy, "1995-01-01/1996-06-01")
    mirrored = FileSource(str(tmp_path)).chip(cx, cy,
                                              "1995-01-01/1996-06-01")
    np.testing.assert_array_equal(live.spectra, mirrored.spectra)
    np.testing.assert_array_equal(live.qas, mirrored.qas)
    np.testing.assert_array_equal(live.dates, mirrored.dates)
    aux = FileSource(str(tmp_path)).aux(cx, cy)
    assert set(aux) == {"dem", "trends", "aspect", "posidex", "slope",
                        "mpw"}


def test_cli_changedetection(monkeypatch, tmp_path):
    monkeypatch.setenv("FIREBIRD_SOURCE", "synthetic")
    monkeypatch.setenv("FIREBIRD_STORE_BACKEND", "sqlite")
    monkeypatch.setenv("FIREBIRD_STORE_PATH", str(tmp_path / "fb.db"))
    monkeypatch.setenv("FIREBIRD_DTYPE", "float64")
    monkeypatch.setenv("FIREBIRD_DEVICE_SHARDING", "off")
    res = CliRunner().invoke(
        cli.entrypoint,
        ["changedetection", "-x", "100", "-y", "200", "-n", "1",
         "-a", ACQ, "-c", "1"])
    assert res.exit_code == 0, res.output

    from firebird_tpu.store import SqliteStore
    ks = Config.from_env().keyspace()
    store = SqliteStore(str(tmp_path / "fb.db"), ks)
    assert store.count("chip") == 1
    assert store.count("segment") >= 10000


def test_driver_source_factory():
    assert isinstance(core.make_source(Config(source_backend="synthetic")),
                      SyntheticSource)
    from firebird_tpu.ingest import ChipmunkSource
    assert isinstance(core.make_source(Config(source_backend="chipmunk")),
                      ChipmunkSource)
    with pytest.raises(ValueError):
        core.make_source(Config(source_backend="nope"))


def test_cli_tiles_csv_and_sharding():
    runner = CliRunner()
    args = ["tiles", "-b", "-543585,2378805", "-b", "-393585,2228805"]
    r = runner.invoke(cli.entrypoint, args, catch_exceptions=False)
    assert r.exit_code == 0
    lines = r.output.strip().splitlines()
    assert lines[0] == "h,v,ulx,uly,lrx,lry"
    assert len(lines) == 1 + 4
    # shards partition the full list
    rows = set(lines[1:])
    sharded = []
    for i in range(3):
        ri = runner.invoke(cli.entrypoint, args + ["-s", f"{i}/3"],
                           catch_exceptions=False)
        assert ri.exit_code == 0
        sharded.extend(ri.output.strip().splitlines()[1:])
    assert set(sharded) == rows and len(sharded) == len(rows)
    # each row's tile center round-trips through grid.tile
    h, v, ulx, uly, lrx, lry = lines[1].split(",")
    t = grid.tile((float(ulx) + float(lrx)) / 2, (float(uly) + float(lry)) / 2)
    assert (t["h"], t["v"]) == (int(h), int(v))


class FakeDevice:
    def __init__(self, limit):
        self._limit = limit

    def memory_stats(self):
        return {"bytes_limit": self._limit} if self._limit else {}


def test_auto_chips_per_batch_sizes_from_device_memory():
    """VERDICT r1 weak #5: chips_per_batch auto-sizes from the device
    memory budget and the acquired range instead of a static config."""
    from firebird_tpu.ccd import kernel
    from firebird_tpu.driver.core import (auto_chips_per_batch, estimate_obs,
                                          resolve_batching)

    cfg = Config(chips_per_batch=0)
    acq = "1982-01-01/2017-12-31"
    # a 16 GB HBM device fits several chips of the full-archive workload
    n16 = auto_chips_per_batch(cfg, acq, device=FakeDevice(16e9))
    n8 = auto_chips_per_batch(cfg, acq, device=FakeDevice(8e9))
    assert n16 >= 2 * n8 >= 2
    # shorter archives -> smaller working set -> bigger batches
    n_short = auto_chips_per_batch(cfg, "1998-01-01/1999-12-31",
                                   device=FakeDevice(16e9))
    assert n_short > n16
    # the estimate honors the packer's max_obs ceiling
    assert estimate_obs(acq, cfg) == cfg.max_obs
    assert estimate_obs("1998-01-01/1998-06-01", cfg) == cfg.obs_bucket
    # max_obs=0 is the packer's "uncapped", NOT a zero cap: the full
    # archive estimate must stay ~1700 obs, not collapse to 0
    assert estimate_obs(acq, Config(chips_per_batch=0, max_obs=0)) > 1600
    # budget math is consistent with the working-set model
    t = estimate_obs(acq, cfg)
    assert n16 == max(1, int(16e9 * 0.6 / kernel.working_set_bytes(t)))
    # no memory stats (CPU) -> static default; explicit setting -> no-op
    assert auto_chips_per_batch(cfg, acq, device=FakeDevice(None)) == \
        Config.chips_per_batch
    assert resolve_batching(Config(chips_per_batch=5), acq).chips_per_batch == 5


@pytest.mark.parametrize("pallas", ["tmask", "1"])
def test_auto_chips_per_batch_is_route_independent(pallas, monkeypatch):
    """Every Pallas route runs the XLA INIT block and its [P,W,T] one-hot
    window peak, so batch sizing is the same whichever components run."""
    from firebird_tpu.ccd import kernel
    from firebird_tpu.driver.core import auto_chips_per_batch

    cfg = Config(chips_per_batch=0)
    acq = "1982-01-01/2017-12-31"
    monkeypatch.delenv("FIREBIRD_PALLAS", raising=False)
    base = auto_chips_per_batch(cfg, acq, device=FakeDevice(16e9))
    base_ws = kernel.working_set_bytes(512)
    monkeypatch.setenv("FIREBIRD_PALLAS", pallas)
    assert auto_chips_per_batch(cfg, acq, device=FakeDevice(16e9)) == base
    assert kernel.working_set_bytes(512) == base_ws


