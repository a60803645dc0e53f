"""Observability: per-subsystem logger categories and level config
(log4j.properties:48-53 parity), throughput counters, and the telemetry
layer (span tracer, metrics registry, per-run report artifacts)."""

import json
import logging
import threading

import pytest

from firebird_tpu import obs
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.obs import report as obs_report
from firebird_tpu.obs import tracing


# ---------------------------------------------------------------------------
# Logging (the original obs.py surface, now the package __init__)
# ---------------------------------------------------------------------------

def test_categories_mirror_reference():
    assert set(obs.CATEGORIES) == {
        "ids", "change-detection", "random-forest-training",
        "random-forest-classification", "timeseries", "pyccd"}


def test_logger_namespaced_and_configured():
    log = obs.logger("pyccd")
    assert log.name == "firebird.pyccd"
    root = logging.getLogger("firebird")
    assert root.handlers and not root.propagate


def test_level_env_overrides(monkeypatch):
    monkeypatch.setenv("FIREBIRD_LOG_LEVELS", "ids=DEBUG, pyccd=ERROR")
    monkeypatch.setattr(obs, "_configured", False)
    obs.configure()
    assert logging.getLogger("firebird.ids").getEffectiveLevel() \
        == logging.DEBUG
    assert logging.getLogger("firebird.pyccd").getEffectiveLevel() \
        == logging.ERROR
    # restore: re-run configure with defaults so later tests see INFO
    logging.getLogger("firebird.ids").setLevel(logging.NOTSET)
    logging.getLogger("firebird.pyccd").setLevel(logging.NOTSET)


def test_counters_snapshot_rates():
    c = obs.Counters()
    c.add("chips")
    c.add("pixels", 10000)
    snap = c.snapshot()
    assert snap["chips"] == 1 and snap["pixels"] == 10000
    assert "pixels_per_sec" in snap and snap["elapsed_sec"] >= 0


def test_counters_rate_clock_excludes_preconstruction_idle():
    """*_per_sec divides by ACTIVE run time: the clock starts at the
    first add (or an explicit start()), not at construction — a long
    setup/compile gap before the run must not deflate the rates."""
    import time

    c = obs.Counters()
    time.sleep(0.25)                    # pre-run idle (setup, compile)
    assert c.snapshot() == {"elapsed_sec": 0.0}   # no clock yet, no rates
    c.add("chips", 10)
    snap = c.snapshot()
    # elapsed measures from the first add, not from construction
    assert snap["elapsed_sec"] < 0.2, snap
    assert snap["chips_per_sec"] > 10 / 0.2
    # explicit start() re-bases the clock (drivers call it at the first
    # productive moment)
    c2 = obs.Counters()
    time.sleep(0.1)
    c2.start()
    c2.add("pixels", 100)
    assert c2.snapshot()["elapsed_sec"] < 0.1


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_export_roundtrip():
    t = tracing.start()
    try:
        with tracing.span("fetch", chip=(1, 2)):
            with tracing.span("pack", chips=3):
                pass
    finally:
        assert tracing.stop() is t
    trace = json.loads(json.dumps(t.to_chrome_trace()))   # wire round-trip
    obs_report.validate_trace(trace)
    evs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"fetch", "pack"}
    # nesting: the child interval is contained in the parent's, same track
    f, p = evs["fetch"], evs["pack"]
    assert f["tid"] == p["tid"]
    assert f["ts"] <= p["ts"]
    assert p["ts"] + p["dur"] <= f["ts"] + f["dur"] + 1e-3
    # args survive export; non-scalar values stringify
    assert p["args"]["chips"] == 3
    assert f["args"]["chip"] == "(1, 2)"
    # summary table aggregates per name
    s = t.summary()
    assert s["fetch"]["count"] == 1 and s["fetch"]["max_ms"] >= 0


def test_spans_are_thread_aware():
    t = tracing.start()
    try:
        def work():
            with tracing.span("worker"):
                pass
        th = threading.Thread(target=work, name="obs-test-worker")
        with tracing.span("main"):
            th.start()
            th.join()
    finally:
        tracing.stop()
    trace = t.to_chrome_trace()
    tids = {e["name"]: e["tid"] for e in trace["traceEvents"]
            if e["ph"] == "X"}
    assert tids["main"] != tids["worker"]
    meta = {e["args"]["name"] for e in trace["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "obs-test-worker" in meta


def test_span_noop_when_disabled():
    assert tracing.active() is None
    with tracing.span("fetch") as s:           # records nowhere, raises never
        assert s is tracing._NULL_SPAN


def test_span_is_the_shared_noop_with_jax_imported_and_no_profiler():
    """jax imported but not recording, no tracer/recorder/spool: still the
    shared no-op — the profiler check costs no allocation."""
    import jax

    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.active() is None and tracing._recorder is None \
        and tracing._spool is None
    assert tracing.span("drain", chips=3) is tracing._NULL_SPAN


def test_span_histogram_times_itself_with_every_sink_off():
    """One span, one timing: a span given histograms observes its wall
    and thread-CPU seconds on exit with tracing off, and a span left by
    an exception observes nothing."""
    obs_metrics.reset_registry()
    wall = obs_metrics.histogram("egress_format_seconds")
    cpu = obs_metrics.histogram("egress_format_cpu_seconds")
    with tracing.span("format", histogram=wall, cpu_histogram=cpu) as sp:
        sum(range(200_000))
    assert sp is not tracing._NULL_SPAN
    w, c = wall.snapshot(), cpu.snapshot()
    assert w["count"] == c["count"] == 1
    assert w["sum"] == sp.elapsed > 0
    assert 0 <= c["sum"] <= w["sum"] + 1e-3
    with pytest.raises(RuntimeError):
        with tracing.span("format", histogram=wall):
            raise RuntimeError("boom")
    assert wall.snapshot()["count"] == 1
    obs_metrics.reset_registry()


# The drain's parts, each a span of its own inside ``drain``.
DRAIN_PARTS = ("egress_wait_device_seconds", "pipeline_d2h_seconds",
               "egress_format_seconds", "store_queue_wait_seconds")
# span name -> the histogram it observes
SPAN_HISTOGRAMS = {
    "wait_input": "pipeline_wait_input_seconds",
    "wait_egress": "pipeline_wait_egress_seconds",
    "wait_device": "egress_wait_device_seconds",
    "format": "egress_format_seconds",
    "queue_wait": "store_queue_wait_seconds",
    "drain": "pipeline_drain_seconds",
    "store_write": "store_write_seconds",
    "store_flush": "store_flush_seconds",
}


@pytest.fixture(scope="module")
def profiled_chunk(tmp_path_factory):
    """A two-batch run_chunk of tiny (10x10 px) chips under the jax
    profiler on the CPU backend, with the span tracer on too: (xplane
    events, tracer, registry snapshot)."""
    import glob
    import os

    import jax

    from firebird_tpu import grid
    from firebird_tpu.ccd.sensor import SENSORS
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.ingest import SyntheticSource
    from firebird_tpu.store import MemoryStore

    cfg = Config(store_backend="memory", source_backend="synthetic",
                 chips_per_batch=1, dtype="float32", device_sharding="off",
                 fetch_retries=0, pipeline_depth=2)
    src = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01",
                          sensor=SENSORS["landsat-ard-tiny"])
    source, _, writer, policy, _, quarantine = core.robustness_setup(
        cfg, "obs-test", source=src, store=MemoryStore("obs-test"))
    cids = list(grid.chips(grid.tile(x=100, y=200)))[:2]
    chunk = dict(source=source, writer=writer,
                 acquired="1995-01-01/1997-06-01", cfg=cfg,
                 counters=obs.Counters(),
                 log=obs.logger("change-detection"), policy=policy,
                 quarantine=quarantine, reraise=True)
    core.run_chunk(cids[:1], **chunk)          # compile outside the trace
    obs_metrics.reset_registry()
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    tracer = tracing.start()
    jax.profiler.start_trace(trace_dir)
    try:
        done = core.run_chunk(cids, **chunk)
    finally:
        jax.profiler.stop_trace()
        tracing.stop()
        snap = obs_metrics.get_registry().snapshot()
        writer.close()
        obs_metrics.reset_registry()
    assert len(done) == 2
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    events = [(plane.name, e.name, e.duration_ns / 1e9)
              for plane in data.planes for line in plane.lines
              for e in line.events if e.name.startswith("firebird.")]
    return events, tracer, snap


def test_program_spans_land_on_the_profiler_clock(profiled_chunk):
    events, _, _ = profiled_chunk
    names = {n for plane, n, _ in events if plane.startswith("/host:")}
    assert {"firebird." + s for s in SPAN_HISTOGRAMS} <= names, names
    # the pre-existing stage spans ride along
    assert {"firebird.fetch", "firebird.pack", "firebird.stage",
            "firebird.dispatch", "firebird.d2h",
            "firebird.transfer"} <= names, names


@pytest.mark.parametrize("span", sorted(SPAN_HISTOGRAMS))
def test_span_histograms_match_the_spans(profiled_chunk, span):
    """Each histogram observes exactly its span's interval: the same
    count and seconds as the tracer's events (the same two clock reads),
    and no more than the profiler's annotation around them."""
    events, tracer, snap = profiled_chunk
    h = snap["histograms"][SPAN_HISTOGRAMS[span]]
    spans = [e["dur"] / 1e6 for e in tracer.to_chrome_trace()["traceEvents"]
             if e.get("ph") == "X" and e["name"] == span]
    assert h["count"] == len(spans) > 0
    assert h["sum"] == pytest.approx(sum(spans), rel=1e-6, abs=1e-9)
    xp = [d for _, n, d in events if n == "firebird." + span]
    assert len(xp) == h["count"]
    assert sum(xp) >= h["sum"] - 1e-4


def test_drain_parts_fit_inside_the_drain(profiled_chunk):
    _, _, snap = profiled_chunk
    hists = snap["histograms"]
    parts = sum(hists[k]["sum"] for k in DRAIN_PARTS)
    assert 0 < parts <= hists["pipeline_drain_seconds"]["sum"]
    assert hists["egress_format_cpu_seconds"]["count"] == \
        hists["egress_format_seconds"]["count"]
    assert hists["store_write_cpu_seconds"]["count"] == \
        hists["store_write_seconds"]["count"]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_histogram_percentiles():
    h = obs_metrics.Histogram("t_seconds")
    for ms in range(1, 101):                   # 1..100 ms, uniform
        h.observe(ms / 1000.0)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(5.05, rel=1e-6)
    assert snap["min"] == 0.001 and snap["max"] == 0.1
    # fixed-bucket interpolation: tolerance is the containing bucket width
    assert snap["p50"] == pytest.approx(0.050, abs=0.015)
    assert snap["p95"] == pytest.approx(0.095, abs=0.01)
    # percentiles never exceed the observed range
    assert snap["min"] <= snap["p99"] <= snap["max"]


def test_histogram_observe_many_matches_observe():
    """Bulk ingestion (the drain thread's occupancy feed) lands the same
    state as per-value observe — identical snapshot, one lock hold."""
    vals = [ms / 1000.0 for ms in range(1, 101)] + [1e6]  # incl. overflow
    one = obs_metrics.Histogram("t_seconds")
    for v in vals:
        one.observe(v)
    bulk = obs_metrics.Histogram("t_seconds")
    bulk.observe_many(vals)
    bulk.observe_many([])                       # no-op, not a crash
    s1, s2 = one.snapshot(), bulk.snapshot()
    assert s1 == pytest.approx(s2)
    assert s2["count"] == len(vals)


def test_histogram_empty_and_overflow():
    h = obs_metrics.Histogram("t_seconds")
    assert h.snapshot() == {"count": 0}
    assert h.quantile(0.5) is None
    h.observe(1e6)                             # beyond the last bucket
    assert h.quantile(0.5) == 1e6              # overflow reports observed max


def test_histogram_quantile_edge_cases():
    # empty: every quantile is None, including the extremes
    h = obs_metrics.Histogram("t_seconds")
    assert h.quantile(0.0) is None and h.quantile(1.0) is None
    # single observation: every quantile IS that observation
    h.observe(0.03)
    for q in (0.0, 0.5, 0.95, 1.0):
        assert h.quantile(q) == pytest.approx(0.03)
    snap = h.snapshot()
    assert snap["count"] == 1
    assert snap["min"] == snap["max"] == pytest.approx(0.03)
    # q=0 / q=1 clamp to the observed range, never the bucket edges
    h2 = obs_metrics.Histogram("t2_seconds")
    for v in (0.012, 0.07, 0.9):
        h2.observe(v)
    assert h2.quantile(0.0) == pytest.approx(0.012)
    assert h2.quantile(1.0) == pytest.approx(0.9)
    assert 0.012 <= h2.quantile(0.5) <= 0.9


def test_reset_registry_isolates_runs():
    """A new driver run must not inherit the previous run's metrics —
    and handles captured from the OLD registry must not leak into the
    new one."""
    reg1 = obs_metrics.reset_registry()
    obs_metrics.counter("chips").inc(7)
    obs_metrics.histogram("pipeline_fetch_seconds").observe(0.5)
    old_counter = obs_metrics.counter("chips")
    reg2 = obs_metrics.reset_registry()
    assert reg2 is obs_metrics.get_registry() and reg2 is not reg1
    # fresh registry: clean slate for the same names
    assert obs_metrics.counter("chips").value == 0
    assert obs_metrics.histogram("pipeline_fetch_seconds").snapshot() \
        == {"count": 0}
    # the old handle still works but writes to the dead registry only
    old_counter.inc()
    assert obs_metrics.counter("chips").value == 0
    assert reg1.counter("chips").value == 8


def test_prometheus_exposition_format():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("chips").inc(5)
    reg.gauge("store_queue_depth").set(3)
    h = reg.histogram("pipeline_fetch_seconds")
    h.observe(0.002)
    h.observe(0.2)
    text = reg.prometheus()
    assert "# TYPE firebird_chips_total counter" in text
    assert "firebird_chips_total 5" in text
    assert "# TYPE firebird_store_queue_depth gauge" in text
    assert "firebird_store_queue_depth 3" in text
    assert "# TYPE firebird_pipeline_fetch_seconds histogram" in text
    assert 'firebird_pipeline_fetch_seconds_bucket{le="+Inf"} 2' in text
    assert "firebird_pipeline_fetch_seconds_count 2" in text
    # cumulative buckets are monotonic
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("firebird_pipeline_fetch_seconds_bucket")]
    assert cums == sorted(cums)


def test_prometheus_help_lines_and_total_guard():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("chips", help="chips drained to the store").inc(2)
    # a counter already named *_total must not become *_total_total
    reg.counter("watchdog_stall_total").inc()
    reg.gauge("store_queue_depth").set(1)
    reg.histogram("pipeline_fetch_seconds").observe(0.01)
    text = reg.prometheus()
    assert "# HELP firebird_chips_total chips drained to the store" in text
    assert "firebird_watchdog_stall_total 1" in text
    assert "firebird_watchdog_stall_total_total" not in text
    # every metric gets a HELP line (declared or derived)
    assert "# HELP firebird_store_queue_depth " in text
    assert "# HELP firebird_pipeline_fetch_seconds " in text
    # _prom_name only suffixes counters
    assert obs_metrics._prom_name("chips", "counter") \
        == "firebird_chips_total"
    assert obs_metrics._prom_name("x_total", "counter") \
        == "firebird_x_total"
    assert obs_metrics._prom_name("chips") == "firebird_chips"


def test_prometheus_exposition_roundtrips_format_regex():
    """Every exposition line is `# HELP|# TYPE ...` or
    `name{labels} value` — the format a scraper actually parses (the
    shared contract regex, also applied by tools/obs_smoke.py)."""
    prom_line = obs_metrics.PROM_LINE_RE
    reg = obs_metrics.MetricsRegistry()
    reg.counter("chips").inc(3)
    reg.counter("watchdog_stall_total")
    reg.gauge("negative").set(-2.5)
    reg.gauge("tiny").set(1e-07)
    h = reg.histogram("pipeline_fetch_seconds")
    for v in (0.0001, 0.02, 4.0, 1e6):
        h.observe(v)
    reg.histogram("empty_seconds")
    lines = reg.prometheus().splitlines()
    assert lines, "exposition must not be empty"
    for ln in lines:
        assert prom_line.match(ln), f"malformed exposition line: {ln!r}"


def test_counter_thread_safety():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("hits")
    n_threads, n_incs = 8, 2000

    def work():
        for _ in range(n_incs):
            c.inc()
    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_incs


def test_metrics_env_gate(monkeypatch):
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setenv("FIREBIRD_METRICS", "0")
    reg.counter("c").inc()
    reg.gauge("g").set(9)
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 0
    assert snap["gauges"]["g"] == 0.0
    assert snap["histograms"]["h"] == {"count": 0}
    monkeypatch.delenv("FIREBIRD_METRICS")
    reg.counter("c").inc()
    assert reg.counter("c").value == 1


def test_registry_once_is_per_registry():
    reg = obs_metrics.reset_registry()
    assert reg.once(("shape", 1)) and not reg.once(("shape", 1))
    assert obs_metrics.reset_registry().once(("shape", 1))


# ---------------------------------------------------------------------------
# Report artifact + driver smoke
# ---------------------------------------------------------------------------

def test_report_build_and_validate(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("chips").inc(2)
    reg.histogram("pipeline_fetch_seconds").observe(0.01)
    t = tracing.Tracer()
    with t.span("fetch"):
        pass
    path = str(tmp_path / "obs_report.json")
    rep = obs_report.write_report(path, registry=reg, tracer=t,
                                  run={"kind": "test"},
                                  run_counters={"chips": 2})
    obs_report.validate_report(json.load(open(path)))
    assert rep["run"]["kind"] == "test"
    assert rep["spans"]["fetch"]["count"] == 1
    with pytest.raises(ValueError):
        obs_report.validate_report({"schema": "bogus"})
    with pytest.raises(ValueError):
        obs_report.validate_trace({"traceEvents": [{"ph": "X"}]})


@pytest.mark.slow
def test_driver_run_emits_report_and_trace(tmp_path):
    """End-to-end: a synthetic changedetection run with tracing on writes
    obs_report.json (all driver stage keys populated) and a valid Chrome
    trace containing the fetch/pack/dispatch/drain spans."""
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.ingest import SyntheticSource

    # Same shape/dtype as test_driver.py so the jit cache entry is shared.
    cfg = Config(store_backend="sqlite",
                 store_path=str(tmp_path / "fb.db"),
                 source_backend="synthetic", chips_per_batch=1,
                 dtype="float64", device_sharding="off", fetch_retries=0,
                 trace=str(tmp_path / "trace.json"))
    src = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1)
    done = core.changedetection(x=100, y=200,
                                acquired="1995-01-01/1997-06-01",
                                number=2, chunk_size=2, cfg=cfg, source=src)
    assert len(done) == 2

    trace = json.load(open(tmp_path / "trace.json"))
    rep = json.load(open(tmp_path / "obs_report.json"))
    # the shared obs-smoke contract (same check `make obs-smoke` runs)
    obs_report.validate_driver_artifacts(trace, rep)
    assert rep["run"]["kind"] == "changedetection"
    assert rep["run_counters"]["chips"] == 2
    # spans surfaced in the summary table too
    assert rep["spans"]["dispatch"]["count"] >= 1


def test_memory_store_run_writes_no_report(tmp_path, monkeypatch):
    """Auto mode must not litter artifacts for memory-backed (test) runs."""
    from firebird_tpu.config import Config

    monkeypatch.chdir(tmp_path)
    cfg = Config(store_backend="memory", source_backend="synthetic")
    assert obs_report.run_report_path(cfg) is None
    cfg = Config(store_backend="memory", obs_report=str(tmp_path / "r.json"))
    assert obs_report.run_report_path(cfg) == str(tmp_path / "r.json")
    cfg = Config(store_backend="sqlite", store_path="x/fb.db",
                 obs_report="0")
    assert obs_report.run_report_path(cfg) is None
