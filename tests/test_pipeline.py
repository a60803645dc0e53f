"""Zero-stall pipeline: bulk batch egress, device-side input staging,
buffer donation, and compile-warm startup (ISSUE 3).

The egress contract is the load-bearing one: ``drain_batch`` must issue
exactly ONE bulk device->host transfer per batch (``jax.device_get`` of
the whole batched ChipSegments), and the vectorized ``batch_frames``
must reproduce per-chip ``chip_frames`` bit-for-bit on a ragged, padded
final batch — both drivers drain through this one code path.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from firebird_tpu.ccd import format as ccdformat
from firebird_tpu.ccd import kernel
from firebird_tpu.config import Config
from firebird_tpu.driver import core
from firebird_tpu.ingest import SyntheticSource, pack
from firebird_tpu.ingest.packer import PackedChips
from firebird_tpu.obs import Counters
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.store import AsyncWriter, MemoryStore


@pytest.fixture(scope="module")
def ragged_batch():
    """3 real (pixel-sliced) chips padded to a 4-chip compiled shape —
    the ragged-final-batch case — plus the kernel result."""
    src = SyntheticSource(seed=3, start="1995-01-01", end="1997-01-01")
    p = pack([src.chip(100 + 3000 * i, 200) for i in range(3)], bucket=32)
    small = PackedChips(cids=p.cids, dates=p.dates,
                        spectra=p.spectra[:, :, :64, :],
                        qas=p.qas[:, :64, :], n_obs=p.n_obs)
    padded, n_real = core._pad_batch(small, 4)
    seg = kernel.detect_packed(padded, dtype=jnp.float64)
    return small, padded, n_real, seg


def _assert_col_equal(table, col, got, ref):
    assert len(got) == len(ref), (table, col)
    for a, b in zip(got, ref):
        if a is None or b is None:
            assert a is None and b is None, (table, col)
        elif isinstance(a, (list, np.ndarray)) \
                or isinstance(b, (list, np.ndarray)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{table}.{col}")
        else:
            # NaN sentinel floats compare equal-as-NaN
            assert a == b or (a != a and b != b), (table, col, a, b)


def test_batch_frames_matches_chip_frames_on_ragged_padded_batch(
        ragged_batch):
    """The vectorized whole-batch formatter must equal the per-chip path
    on every column of every table, and drop the padded chips."""
    _, padded, n_real, seg = ragged_batch
    host = jax.device_get(seg)
    out = ccdformat.batch_frames(padded, host, n_real)
    assert len(out) == n_real                  # padded chips dropped
    for c, (cid, frames) in enumerate(out):
        assert cid == (int(padded.cids[c][0]), int(padded.cids[c][1]))
        ref = ccdformat.chip_frames(
            padded, c, kernel.chip_slice(seg, c, to_host=True))
        for table in ("chip", "pixel", "segment"):
            assert set(frames[table]) == set(ref[table])
            for col in ref[table]:
                _assert_col_equal(table, col, frames[table][col],
                                  ref[table][col])


def test_drain_batch_issues_one_bulk_device_get(ragged_batch, monkeypatch):
    """The egress regression contract: one ``jax.device_get`` per drained
    batch — never the old per-chip, per-field transfer pattern."""
    _, padded, n_real, seg = ragged_batch
    calls = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        calls["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    store = MemoryStore("bulk")
    writer = AsyncWriter(store)
    counters = Counters()
    try:
        core.drain_batch(seg, padded, n_real, writer=writer,
                         counters=counters, dtype=jnp.float64)
        writer.flush()
    finally:
        writer.close()
    assert calls["n"] == 1
    # ... and the keyed per-chip writes all landed (resume invariant path)
    assert store.count("chip") == n_real
    assert store.count("pixel") == n_real * 64
    assert store.count("segment") >= n_real * 64
    assert counters.get("chips") == n_real
    assert counters.get("pixels") == n_real * 64


def test_drain_records_egress_metrics(ragged_batch):
    _, padded, n_real, seg = ragged_batch
    obs_metrics.reset_registry()
    store = MemoryStore("m")
    writer = AsyncWriter(store)
    try:
        core.drain_batch(seg, padded, n_real, writer=writer,
                         counters=Counters(), dtype=jnp.float64)
        writer.flush()
    finally:
        writer.close()
    snap = obs_metrics.get_registry().snapshot()
    assert snap["histograms"]["pipeline_d2h_seconds"]["count"] == 1
    assert snap["counters"]["wire_d2h_bytes"] > 0
    assert snap["counters"]["store_rows_written"] >= n_real * (1 + 64 + 64)
    obs_metrics.reset_registry()


def test_stage_batch_then_staged_dispatch_matches(ragged_batch):
    """The prefetch thread's product (StagedBatch) dispatches to the same
    result as the unstaged path, pads to the compiled shape, and records
    the staging histogram + H2D byte counter."""
    small, padded, n_real, seg = ragged_batch
    obs_metrics.reset_registry()
    staged = core.stage_batch(small, jnp.float64, "off", pad_to=4)
    assert staged.mesh is None
    assert staged.packed.n_chips == 4 and staged.n_real == 3
    seg2, r2 = core.detect_batch(small, jnp.float64, "off",
                                 staged=staged, donate=False)
    assert r2 == 3
    for f in ("n_segments", "seg_meta", "mask", "procedure"):
        np.testing.assert_array_equal(np.asarray(getattr(seg2, f))[:3],
                                      np.asarray(getattr(seg, f))[:3])
    snap = obs_metrics.get_registry().snapshot()
    assert snap["histograms"]["pipeline_stage_seconds"]["count"] == 1
    assert snap["counters"]["wire_h2d_bytes"] > 0
    obs_metrics.reset_registry()


def test_staged_sharded_dispatch_matches(ragged_batch):
    """Staging under the local device mesh: pads 3 -> 8 chips over the
    virtual devices and matches the single-device result."""
    small, _, _, seg = ragged_batch
    assert jax.local_device_count() == 8
    staged = core.stage_batch(small, jnp.float64, "auto")
    assert staged.mesh is not None and staged.packed.n_chips == 8
    seg2, r2 = core.detect_batch(small, jnp.float64, "auto", staged=staged)
    assert r2 == 3 and seg2.n_segments.shape[0] == 8
    np.testing.assert_array_equal(np.asarray(seg2.n_segments)[:3],
                                  np.asarray(seg.n_segments)[:3])


@pytest.mark.filterwarnings("ignore:Some donated buffers")
def test_donated_dispatch_matches_and_consumes_inputs(ragged_batch):
    """The donated jit twin computes the same result; donation is only
    honored on the single-dispatch (check_capacity=False) path."""
    small, _, _, seg = ragged_batch
    args = kernel.stage_packed(small, jnp.float64)
    out = kernel.detect_packed(small, dtype=jnp.float64,
                               check_capacity=False, staged=args,
                               donate=True)
    np.testing.assert_array_equal(np.asarray(out.n_segments),
                                  np.asarray(seg.n_segments)[:3])


@pytest.mark.slow  # ~46s (two full driver runs back-to-back); tier-1 (-m 'not slow') keeps the staging/egress pipeline rungs and `make pipeline-smoke` still proves the second-run compile-cache hit end-to-end
def test_warm_start_compile_cache_hit_on_second_run(compile_cache_at):
    """Persistent-cache acceptance: run-1 warm compile populates
    the persistent cache (miss counted), and after dropping the in-memory
    jit cache a second warm compile of the same predicted shape HITS."""
    cfg = Config(store_backend="memory", source_backend="synthetic",
                 chips_per_batch=1, device_sharding="off")
    acq = "1995-01-01/1995-09-01"
    try:
        assert core.setup_compile_cache() == compile_cache_at
        # Run 1 must trace from a clean slate: module-level lowering dedup
        # depends on the in-memory tracing caches, so a run 1 traced with
        # caches warmed by EARLIER tests (e.g. an x64 driver run) emits a
        # differently-numbered module — and writes a persistent-cache key
        # run 2's post-clear_caches canonical trace can never look up.
        jax.clear_caches()
        obs_metrics.reset_registry()
        t = core.warm_start(cfg, acq)
        assert t is not None
        t.join(timeout=600)
        assert not t.is_alive()
        snap = obs_metrics.get_registry().snapshot()
        assert snap["counters"]["warm_compiles"] == 1
        assert snap["histograms"]["warm_compile_seconds"]["count"] == 1
        assert os.listdir(compile_cache_at)        # entry written
        assert snap["counters"].get("compile_cache_misses", 0) > 0

        jax.clear_caches()
        obs_metrics.reset_registry()
        t2 = core.warm_start(cfg, acq)
        t2.join(timeout=600)
        assert not t2.is_alive()
        snap2 = obs_metrics.get_registry().snapshot()
        assert snap2["counters"].get("compile_cache_hits", 0) > 0
    finally:
        obs_metrics.reset_registry()


def test_jax_cache_dir_wins_over_in_code_default(compile_cache_at):
    """JAX_COMPILATION_CACHE_DIR, when set, is THE cache directory: the
    driver sets no other (no checkout default, no config knob)."""
    assert core.compile_cache_dir() == compile_cache_at
    assert core.setup_compile_cache() == compile_cache_at
    assert jax.config.jax_compilation_cache_dir == compile_cache_at
    assert os.path.isdir(compile_cache_at)
    assert not hasattr(Config(), "compile_cache")


def test_cache_dir_is_fixed_across_processes_when_unset():
    """Unset, the cache lives at <checkout>/.cache/jax in every process —
    the path is part of what makes a later run hit, so it is never built
    from a tmp name, pid or time."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("from firebird_tpu.driver import core; "
            "print(core.compile_cache_dir())")
    seen = [subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                           capture_output=True, text=True, check=True,
                           timeout=120).stdout.strip() for _ in range(2)]
    assert seen[0] == seen[1] == os.path.join(root, ".cache", "jax")


def test_predict_batch_shape_is_padded_and_bucketed():
    cfg = Config(chips_per_batch=3, device_sharding="off")
    C, T, wcap = core.predict_batch_shape(cfg, "1995-01-01/1996-06-01")
    assert C == 3
    assert T % cfg.obs_bucket == 0 and T >= 64
    assert wcap % 8 == 0 and wcap <= T
    # sharded: C rounds up to the device-count multiple
    C8, _, _ = core.predict_batch_shape(
        Config(chips_per_batch=3), "1995-01-01/1996-06-01")
    assert C8 == 8


def test_pipeline_depth_config():
    # default 3 since the wire diet: int-coded depth-sliced egress freed
    # the HBM one more in-flight batch pins (config.py rationale)
    assert Config().pipeline_depth == 3
    with pytest.raises(ValueError):
        Config(pipeline_depth=0)
    cfg = Config.from_env({"FIREBIRD_PIPELINE_DEPTH": "4"})
    assert cfg.pipeline_depth == 4


def test_progress_reports_pipeline_occupancy():
    from firebird_tpu.obs import server as obs_server

    st = obs_server.RunStatus("r1", "changedetection", chips_total=4,
                              pipeline_depth=3)
    st.batch_dispatched()
    st.batch_dispatched()
    st.batch_done()
    prog = st.progress()
    kern = prog["pipeline"].pop("kernel")   # lane occupancy (test_compact)
    assert set(kern) == {"active_lane_rounds", "wasted_lane_rounds",
                         "wasted_share", "compactions"}
    assert prog["pipeline"] == {"depth": 3, "in_flight": 1,
                                "occupancy": round(1 / 3, 3)}
    assert obs_metrics.gauge("pipeline_inflight").value == 1


def test_first_dispatch_waits_for_same_shape_warm_compile(monkeypatch):
    """A dispatch of the shape the in-flight warm compile is building
    waits for it (then reads the program from the persistent cache)
    instead of compiling the same program alongside it; any other shape
    does not wait."""
    import threading

    release = threading.Event()
    warm = threading.Thread(target=release.wait, daemon=True)
    warm.start()
    monkeypatch.setattr(core, "_warm_thread", warm)
    monkeypatch.setattr(core, "_warm_shape", (2, 64, 24))

    def staged(C, T, wcap):
        packed = PackedChips(cids=np.zeros((C, 2), np.int64),
                             dates=np.zeros((C, T), np.int64),
                             spectra=np.zeros((C, 7, 4, T), np.int16),
                             qas=np.zeros((C, 4, T), np.uint16),
                             n_obs=np.zeros(C, np.int64))
        return core.StagedBatch(packed=packed, args=(), n_real=C,
                                mesh=None, wcap=wcap)

    core.await_warm_compile(staged(2, 64, 16))      # other shape: no wait
    assert warm.is_alive()
    threading.Timer(0.2, release.set).start()
    core.await_warm_compile(staged(2, 64, 24))      # same shape: waits
    assert not warm.is_alive()
