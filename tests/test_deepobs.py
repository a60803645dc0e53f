"""Deep observability: device profiling, cross-thread trace propagation,
SLO tracking, and the crash flight recorder (obs/profiling.py, obs/slo.py,
obs/flightrec.py + the TraceContext plumbing in obs/tracing.py,
obs/metrics.py exemplars, obs/jsonlog.py, and the drivers)."""

import glob
import json
import logging
import os
import threading
import time

import pytest

from firebird_tpu.config import Config
from firebird_tpu.obs import flightrec, jsonlog, profiling
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.obs import server as obs_server
from firebird_tpu.obs import slo as slomod
from firebird_tpu.obs import tracing
from firebird_tpu.obs.watchdog import Watchdog


@pytest.fixture
def fresh_metrics():
    obs_metrics.reset_registry()
    yield
    obs_metrics.reset_registry()


@pytest.fixture
def disarmed():
    """Every flight-recorder test leaves the process hooks restored."""
    yield
    flightrec.disarm()


# ---------------------------------------------------------------------------
# TraceContext: thread-local activation, ids, exemplars
# ---------------------------------------------------------------------------

def test_trace_context_activation_is_thread_local():
    assert tracing.current_context() is None
    ctx = tracing.TraceContext("run-x/b0", run_id="run-x")
    seen = {}

    def other():
        seen["other"] = tracing.current_context()

    with tracing.activate(ctx):
        assert tracing.current_context() is ctx
        t = threading.Thread(target=other)
        t.start()
        t.join()
        inner = tracing.TraceContext("run-x/b1")
        with tracing.activate(inner):
            assert tracing.current_context() is inner
        assert tracing.current_context() is ctx
    assert tracing.current_context() is None
    assert seen["other"] is None          # contexts never leak across threads
    # activate(None) is a no-op so call sites thread optional contexts
    with tracing.activate(None):
        assert tracing.current_context() is None


def test_new_batch_ids_are_unique_and_run_scoped():
    a = tracing.new_batch_id("rid")
    b = tracing.new_batch_id("rid")
    assert a != b and a.startswith("rid/b") and b.startswith("rid/b")
    assert tracing.new_batch_id(None).startswith("run/b")


def test_exemplar_payload_carries_batch_and_last_span_id():
    assert tracing.exemplar() is None     # outside any unit of work
    tracing.start(run_id="rid")           # span ids mint only when spans
    try:                                  # actually record
        with tracing.activate(tracing.TraceContext("rid/b7")):
            with tracing.span("fetch"):
                pass
            ex = tracing.exemplar()
            assert ex["batch"] == "rid/b7" and ex["span_id"] > 0
    finally:
        tracing.stop()


def test_span_records_batch_and_span_id_in_args(tmp_path):
    tr = tracing.start(run_id="rid")
    try:
        with tracing.activate(tracing.TraceContext("rid/b0", run_id="rid")):
            with tracing.span("fetch", chips=2):
                pass
        with tracing.span("pack"):        # outside any context
            pass
    finally:
        tracing.stop()
    events = [e for e in tr.to_chrome_trace()["traceEvents"]
              if e.get("ph") == "X"]
    fetch = next(e for e in events if e["name"] == "fetch")
    assert fetch["args"]["batch"] == "rid/b0"
    assert fetch["args"]["span_id"] > 0
    pack = next(e for e in events if e["name"] == "pack")
    assert "batch" not in pack["args"] and pack["args"]["span_id"] > 0


# ---------------------------------------------------------------------------
# Histogram exemplars
# ---------------------------------------------------------------------------

def test_histogram_keeps_slowest_exemplars(fresh_metrics):
    h = obs_metrics.histogram("x_seconds")
    for i in range(6):
        with tracing.activate(tracing.TraceContext(f"r/b{i}")):
            h.observe(float(i))
    h.observe(99.0)                       # no context: no exemplar
    snap = h.snapshot()
    ex = snap["exemplars"]
    assert len(ex) == obs_metrics.EXEMPLAR_SLOTS
    assert [e["value"] for e in ex] == sorted(
        (e["value"] for e in ex), reverse=True)
    assert ex[0]["batch"] == "r/b5"       # the slowest traced observation
    assert all("batch" in e for e in ex)


def test_exemplars_survive_fleet_merge(fresh_metrics):
    a = obs_metrics.Histogram("m_seconds")
    b = obs_metrics.Histogram("m_seconds")
    with tracing.activate(tracing.TraceContext("hostA/b0")):
        a.observe(1.0)
    with tracing.activate(tracing.TraceContext("hostB/b0")):
        b.observe(5.0)
    merged = obs_metrics.merge_histogram_snapshots(
        [a.snapshot(), b.snapshot()])
    assert merged["count"] == 2
    assert merged["exemplars"][0]["batch"] == "hostB/b0"   # fleet slowest


def test_jsonlog_line_carries_batch_inside_context():
    fmt = jsonlog.JsonFormatter()
    rec = logging.LogRecord("firebird.x", logging.INFO, __file__, 1,
                            "hello", (), None)
    with tracing.activate(tracing.TraceContext("rid/b3", run_id="rid")):
        doc = json.loads(fmt.format(rec))
    assert doc["batch"] == "rid/b3"
    doc = json.loads(fmt.format(rec))     # outside: no batch key
    assert "batch" not in doc


# ---------------------------------------------------------------------------
# SLO tracking
# ---------------------------------------------------------------------------

def test_slo_spec_grammar():
    assert slomod.parse_spec("batch_p95=30;serve_p99=2") == \
        [("batch_p95", 30.0), ("serve_p99", 2.0)]
    assert slomod.parse_spec("") == []
    with pytest.raises(ValueError, match="unknown SLO objective"):
        slomod.parse_spec("bogus=1")
    with pytest.raises(ValueError, match="not name=target"):
        slomod.parse_spec("batch_p95")
    with pytest.raises(ValueError, match="not a number"):
        slomod.parse_spec("batch_p95=fast")
    with pytest.raises(ValueError, match="must be > 0"):
        slomod.parse_spec("batch_p95=0")


def test_slo_config_fail_fast():
    Config(slo="batch_p95=10")            # valid
    Config(slo="0")                       # disabled is valid
    with pytest.raises(ValueError):
        Config(slo="nope=1")


def test_slo_evaluation_pass_fail_and_no_data():
    metrics = {"histograms": {
        "pipeline_drain_seconds": {"count": 10, "p95": 12.0},
    }}
    out = slomod.evaluate_snapshot(metrics, spec="batch_p95=30;serve_p99=2")
    assert out["ok"] is True and out["violations"] == 0
    by = {o["name"]: o for o in out["objectives"]}
    assert by["batch_p95"]["ok"] is True
    assert by["batch_p95"]["value_sec"] == 12.0
    # serve never served: neither pass nor fail
    assert by["serve_p99"]["ok"] is None

    out = slomod.evaluate_snapshot(metrics, spec="batch_p95=10")
    assert out["ok"] is False and out["violations"] == 1


def test_slo_violation_carries_exemplars_and_freshness_reads_watchdog():
    metrics = {"histograms": {"pipeline_drain_seconds": {
        "count": 3, "p95": 50.0,
        "exemplars": [{"value": 55.0, "batch": "r/b9", "span_id": 4}]}}}
    out = slomod.evaluate_snapshot(metrics, watchdog={
        "last_beat_age_sec": 700.0}, spec="batch_p95=30;freshness=600")
    by = {o["name"]: o for o in out["objectives"]}
    assert by["batch_p95"]["ok"] is False
    assert by["batch_p95"]["exemplars"][0]["batch"] == "r/b9"
    assert by["freshness"]["ok"] is False
    assert out["violations"] == 2
    # "0" disables wholesale
    assert slomod.evaluate_snapshot(metrics, spec="0")["objectives"] == []


def test_slo_endpoint_and_report_block(fresh_metrics):
    """/slo serves the evaluation against the LIVE registry and
    build_report always carries the slo block."""
    from firebird_tpu.obs import report as obs_report

    obs_metrics.histogram("pipeline_drain_seconds").observe(1.0)
    status = obs_server.set_status(obs_server.RunStatus(
        "r", "test", slo_spec="batch_p95=30"))
    try:
        srv = obs_server.start_ops_server(0, status, host="127.0.0.1")
        try:
            import urllib.request
            r = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/slo", timeout=5)
            doc = json.loads(r.read())
            assert doc["spec"] == "batch_p95=30" and doc["ok"] is True
            assert doc["objectives"][0]["value_sec"] == 1.0
        finally:
            srv.close()
        rep = obs_report.build_report(run={"run_id": "r"})
        assert rep["slo"]["spec"] == "batch_p95=30"
        assert rep["profile"]["device_time"]["source"] == "none"
    finally:
        obs_server.clear_status()


def test_slo_reevaluated_over_merged_fleet_reports(fresh_metrics):
    """Per-host verdicts cannot be combined — the merge re-evaluates over
    the merged histograms (a fleet p95 is not any host's p95)."""
    from firebird_tpu.obs import report as obs_report

    def host_report(v):
        obs_metrics.reset_registry()
        h = obs_metrics.histogram("pipeline_drain_seconds")
        for _ in range(50):
            h.observe(v)
        rep = obs_report.build_report(run={"run_id": "r"})
        return json.loads(json.dumps(rep))

    fast, slow = host_report(1.0), host_report(40.0)
    assert fast["slo"]["ok"] is True
    merged = obs_report.merge_reports([fast, slow])
    by = {o["name"]: o for o in merged["slo"]["objectives"]}
    assert by["batch_p95"]["ok"] is False     # the fleet p95 is the slow half
    assert merged["profile"]["device_time"]["source"] == "none"


# ---------------------------------------------------------------------------
# Device profiling
# ---------------------------------------------------------------------------

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start_ms, end_ms):
    return (plane, line, name, start_ms * 1e6, end_ms * 1e6)


def test_device_time_idle_under_the_dispatch_waits():
    """Busy is the union of the device's ops; idle is put down to the
    dispatch thread's egress waits (wait_egress + store_flush) and input
    waits only where the device had nothing running."""
    a = profiling.reduce_window([
        _ev(HOST, "python", "firebird.wait_input", 0, 10),    # 10 ms idle
        _ev(DEV, "XLA Ops", "fusion.1", 10, 40),
        _ev(DEV, "XLA Ops", "fusion.2", 20, 30),              # nested
        _ev(HOST, "python", "firebird.wait_egress", 30, 60),  # 20 ms idle
        _ev(DEV, "XLA Modules", "jit_detect", 10, 40),        # not an op
        _ev(DEV, "XLA Ops", "fusion.3", 70, 80),
        _ev(HOST, "python", "firebird.store_flush", 75, 100),  # 20 ms idle
        _ev(HOST, "drain", "firebird.format", 80, 100),        # not a wait
    ])
    assert a["source"] == "trace" and a["devices"] == 1
    assert a["window_s"] == pytest.approx(0.1)
    assert a["busy_s"] == pytest.approx(0.04)
    assert a["idle_pct"] == pytest.approx(60.0)
    assert a["idle_wait_egress_s"] == pytest.approx(0.04)
    assert a["idle_wait_input_s"] == pytest.approx(0.01)
    merged = profiling.merge_device_time([a, a])
    assert merged["window_s"] == pytest.approx(0.2)
    assert merged["idle_wait_egress_s"] == pytest.approx(0.08)
    assert merged["idle_pct"] == pytest.approx(60.0)


def test_device_time_zero_structure_when_no_trace(tmp_path):
    a = profiling.window_device_time(str(tmp_path))
    assert a["source"] == "no-trace-files" and a["busy_s"] == 0.0
    assert a["idle_pct"] is None
    assert set(profiling.SECONDS) < set(a)
    # A host-only trace (the CPU backend) has no device to be idle.
    host = profiling.reduce_window([_ev(HOST, "python", "x", 0, 5)])
    assert host["devices"] == 0 and host["idle_pct"] is None


def test_profiler_window_real_capture(tmp_path, fresh_metrics):
    """A real (tiny) jax.profiler window on the CPU backend: artifact
    files land under window_00/ and the summary carries device time —
    the POST /profile acceptance path minus HTTP."""
    import jax.numpy as jnp

    prof = profiling.DeviceProfiler(str(tmp_path / "device_profile"))
    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    info = prof.window(0.05, block=True)
    assert "error" not in info, info
    assert info["trace_files"] >= 1
    assert glob.glob(os.path.join(info["dir"], "**", "*.xplane.pb"),
                     recursive=True)
    s = prof.summary()
    assert len(s["windows"]) == 1 and not s["in_flight"]
    dt = s["device_time"]
    assert set(profiling.empty_device_time()) == set(dt)
    assert dt["source"] == "trace" and dt["window_s"] > 0
    assert obs_metrics.counter("profile_windows").value == 1


def test_profiler_single_window_at_a_time_and_early_close(tmp_path):
    prof = profiling.DeviceProfiler(str(tmp_path / "dp"))
    prof.window(60.0)                     # async; would run a minute
    with pytest.raises(profiling.ProfilerBusy):
        prof.window(1.0)
    # Generous join bound: on a contended host the capture thread's
    # start/stop_trace can take tens of seconds to get scheduled, and a
    # timed-out join here reads as a lost window (observed flake under
    # full-suite load).  The join returns the moment the thread ends,
    # so the typical cost is unchanged.
    prof.close(timeout=240.0)             # interrupts the wait
    s = prof.summary()
    assert len(s["windows"]) == 1 and not s["in_flight"]


def test_profile_report_block_always_structured():
    profiling.set_active(None)
    block = profiling.report_block()
    assert block["windows"] == [] and block["in_flight"] is False
    assert block["device_time"]["source"] == "none"
    assert block["device_time"]["busy_s"] == 0.0


def test_auto_window_armed_fires_once(tmp_path, monkeypatch):
    prof = profiling.DeviceProfiler(str(tmp_path / "dp"))
    started = []
    monkeypatch.setattr(prof, "window", lambda s: started.append(s))
    prof.arm_auto(2.5)
    prof.maybe_start_auto()
    prof.maybe_start_auto()               # one-shot: second is a no-op
    assert started == [2.5]


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_rings_are_per_thread_and_bounded():
    rec = flightrec.FlightRecorder(None, ring=4)
    for i in range(10):
        rec.mark("m", i=i)

    def worker():
        rec.log_event("INFO", "firebird.x", "from-worker")

    t = threading.Thread(target=worker, name="fr-worker")
    t.start()
    t.join()
    doc = rec.bundle("test")
    main_ring = doc["threads"][threading.current_thread().name]
    assert len(main_ring) == 4            # bounded
    assert [ev["i"] for ev in main_ring] == [6, 7, 8, 9]
    assert doc["threads"]["fr-worker"][0]["message"] == "from-worker"
    assert doc["reasons"] == ["test"]


def test_ring_events_stamp_active_batch():
    rec = flightrec.FlightRecorder(None, ring=8)
    with tracing.activate(tracing.TraceContext("rid/b2")):
        rec.mark("stage", stage="drain")
        rec.log_event("INFO", "firebird.x", "inside")
    doc = rec.bundle("test")
    ring = doc["threads"][threading.current_thread().name]
    assert all(ev["batch"] == "rid/b2" for ev in ring)


def test_dump_writes_bundle_and_counts(tmp_path, fresh_metrics):
    path = str(tmp_path / "sub" / "postmortem.json")
    rec = flightrec.FlightRecorder(path, ring=8, run_id="rid",
                                   fingerprint="fp")
    rec.mark("stage", stage="fetch")
    try:
        raise RuntimeError("boom")
    except RuntimeError as e:
        doc = rec.dump("unhandled_exception", e)
    assert doc["exception"]["type"] == "RuntimeError"
    on_disk = json.load(open(path))
    assert on_disk["schema"] == flightrec.SCHEMA
    assert on_disk["run_id"] == "rid"
    assert on_disk["config_fingerprint"] == "fp"
    assert on_disk["exception"]["message"] == "boom"
    assert obs_metrics.counter("postmortems_written").value == 1
    # repeat dumps overwrite, accumulating reasons
    rec.dump("sigterm")
    assert json.load(open(path))["reasons"] == \
        ["unhandled_exception", "sigterm"]


def test_armed_recorder_feeds_spans_without_a_tracer(tmp_path, disarmed):
    """While armed, span() records into the rings even when no tracer
    runs — a postmortem always has recent spans to show."""
    rec = flightrec.arm(None, ring=8)
    assert tracing.active() is None
    with tracing.span("drain", chips=1):
        pass
    ring = rec.bundle("t")["threads"][threading.current_thread().name]
    assert ring and ring[0]["kind"] == "span" and ring[0]["name"] == "drain"


def test_thread_excepthook_dumps(tmp_path, disarmed):
    path = str(tmp_path / "postmortem.json")
    quiet = lambda args: None             # silence the chained default hook
    orig = threading.excepthook
    threading.excepthook = quiet
    try:
        flightrec.arm(path, ring=8)

        def crash():
            raise ValueError("thread died")

        t = threading.Thread(target=crash, name="doomed")
        t.start()
        t.join()
    finally:
        flightrec.disarm()
        threading.excepthook = orig
    doc = json.load(open(path))
    assert doc["reason"] == "unhandled_exception"
    assert doc["exception"]["message"] == "thread died"


def test_watchdog_stall_triggers_postmortem(tmp_path, fresh_metrics,
                                            disarmed):
    path = str(tmp_path / "postmortem.json")
    flightrec.arm(path, ring=8, run_id="rid")
    clock = [0.0]
    wd = Watchdog(stall_sec=10.0, clock=lambda: clock[0])
    wd.beat()
    clock[0] = 11.0
    assert wd.check() is True
    doc = json.load(open(path))
    assert doc["reason"] == "watchdog_stall"
    # disarmed: a second stall in another run dumps nothing new
    flightrec.disarm()
    os.unlink(path)
    wd2 = Watchdog(stall_sec=10.0, clock=lambda: clock[0])
    wd2.beat()
    clock[0] = 22.5
    assert wd2.check() is True
    assert not os.path.exists(path)


def test_arm_disarm_restore_hooks(disarmed):
    import signal as sigmod
    import sys

    prev_except = sys.excepthook
    prev_thread = threading.excepthook
    prev_sig = sigmod.getsignal(sigmod.SIGTERM)
    flightrec.arm(None, ring=4)
    assert sys.excepthook is not prev_except
    assert threading.excepthook is not prev_thread
    assert sigmod.getsignal(sigmod.SIGTERM) is not prev_sig
    flightrec.disarm()
    assert sys.excepthook is prev_except
    assert threading.excepthook is prev_thread
    assert sigmod.getsignal(sigmod.SIGTERM) == (prev_sig or sigmod.SIG_DFL)
    assert flightrec.active() is None


def test_progress_marks_flow_from_runstatus(disarmed):
    rec = flightrec.arm(None, ring=16)
    status = obs_server.RunStatus("r", "test", chips_total=1)
    try:
        status.set_stage("dispatch")
        status.batch_dispatched()
        status.batch_done(3)
    finally:
        obs_server.clear_status()
    ring = rec.bundle("t")["threads"][threading.current_thread().name]
    kinds = [(ev["kind"], ev["name"]) for ev in ring]
    assert ("mark", "stage") in kinds
    assert ("mark", "batch_dispatched") in kinds
    assert ("mark", "batch_done") in kinds


# ---------------------------------------------------------------------------
# Watchdog throughput-drop surfacing (satellite)
# ---------------------------------------------------------------------------

def test_throughput_drop_events_surface_in_degraded_block(fresh_metrics):
    clock = [0.0]
    wd = Watchdog(stall_sec=1000.0, clock=lambda: clock[0])
    for i in range(20):
        clock[0] = float(i)
        wd.beat()
    for i in range(6):
        clock[0] = 20.0 + 5.0 * (i + 1)
        wd.beat()
    snap = wd.snapshot()
    ev = snap["throughput_drops"][0]
    # the event is operator-readable: wall-clock stamp + the crossed
    # threshold, not just two rates and a monotonic offset
    assert "at" in ev and "threshold_per_sec" in ev
    assert ev["recent_per_sec"] < ev["threshold_per_sec"]
    status = obs_server.RunStatus("r", "test", watchdog=wd)
    try:
        deg = status.degraded_block()
    finally:
        obs_server.clear_status()
    assert deg["throughput_drops"] == snap["throughput_drops"]


# ---------------------------------------------------------------------------
# End-to-end propagation: one batch id across four threads (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~30s (full small changedetection run); telemetry-smoke proves trace propagation across real processes in `make test`
def test_driver_trace_propagation_end_to_end(tmp_path):
    """A real (small) changedetection run: every pipeline span in
    fetch→pack→stage→dispatch→drain→d2h→store_write carries the SAME
    per-batch id across the prefetch, main, drain, and writer threads,
    JSON log lines inside a batch carry it too, and the drain histogram
    gains exemplars pointing at real batches."""
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

    captured: list[str] = []

    class _Cap(logging.Handler):
        def __init__(self):
            super().__init__(logging.DEBUG)
            self._fmt = jsonlog.JsonFormatter()

        def emit(self, record):
            captured.append(self._fmt.format(record))

    fblog = logging.getLogger("firebird")
    cap = _Cap()
    fblog.addHandler(cap)
    old_level = fblog.level
    fblog.setLevel(logging.DEBUG)
    try:
        done = core.changedetection(x=100, y=200,
                                    acquired="1995-01-01/1997-06-01",
                                    number=2, chunk_size=2, cfg=cfg,
                                    source=src)
    finally:
        fblog.removeHandler(cap)
        fblog.setLevel(old_level)
    assert len(done) == 2

    rep = json.load(open(tmp_path / "obs_report.json"))
    run_id = rep["run"]["run_id"]
    trace = json.load(open(tmp_path / "trace.json"))
    events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    staged = [e for e in events
              if e["name"] in ("fetch", "pack", "stage", "dispatch",
                               "drain", "d2h", "store_write")]
    assert staged
    # EVERY pipeline span parents to a batch of THIS run and has a span id
    for e in staged:
        assert e["args"]["batch"].startswith(run_id + "/b"), e
        assert e["args"]["span_id"] > 0
    by_batch: dict = {}
    for e in staged:
        by_batch.setdefault(e["args"]["batch"], []).append(e)
    assert len(by_batch) == 2             # 2 chips, chips_per_batch=1
    for batch, evs in by_batch.items():
        names = {e["name"] for e in evs}
        # the full pipeline, fetch through store write, on one id
        assert {"fetch", "pack", "stage", "dispatch", "drain", "d2h",
                "store_write"} <= names, (batch, names)
        # ...across at least three OS threads (prefetch stages, the main
        # thread dispatches, the drain executor drains, a writer writes)
        tids = {e["tid"] for e in evs}
        assert len(tids) >= 3, (batch, tids)
        main_tid = next(e["tid"] for e in evs if e["name"] == "dispatch")
        assert {e["tid"] for e in evs if e["name"] == "fetch"} != {main_tid}
        assert {e["tid"] for e in evs
                if e["name"] == "store_write"} != {main_tid}

    # JSON log lines inside a batch carry the same parent id + run id
    docs = [json.loads(s) for s in captured]
    batch_lines = [d for d in docs if "batch" in d]
    assert batch_lines, "no in-context log lines captured"
    for d in batch_lines:
        assert d["batch"] in by_batch
        assert d["run_id"] == run_id

    # the drain histogram's exemplars point at real batches of this run
    ex = rep["metrics"]["histograms"]["pipeline_drain_seconds"]["exemplars"]
    assert ex and all(e["batch"] in by_batch for e in ex)

    # and the report's slo/profile blocks are structurally present
    assert "objectives" in rep["slo"]
    assert rep["profile"]["device_time"]["source"] == "none"
