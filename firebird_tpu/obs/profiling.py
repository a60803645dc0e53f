"""On-demand device profiling: windowed jax.profiler captures mid-run.

The host span tracer (obs/tracing.py) says *that* a batch was slow; it
cannot say *where on the device* the time went — and every bench round
r01-r05 ran blind on exactly that question (the reference had the same
gap: no throughput numbers anywhere, PAPER.md §6).  The LASP CPU→GPU
port (PAPERS.md) attributes every optimization step to profiler-measured
kernel phases *before* touching code; this module makes that workflow a
one-request operation on a live run:

- ``FIREBIRD_PROFILE=<seconds>`` (``Config.profile``) arms an automatic
  window that starts at the run's FIRST dispatched batch — steady-state
  kernels, not bring-up compile noise.
- ``POST /profile?seconds=N`` on the ops endpoint (obs/server.py)
  captures a window on demand at any point mid-run.

Each window wraps ``jax.profiler.start_trace``/``stop_trace`` around a
bounded wait and writes the standard XLA/TensorBoard artifact
(``.xplane.pb`` + ``.trace.json.gz``) under ``<store dir>/device_profile/
window_<n>/`` — linkable from the run's other artifacts, loadable in
Perfetto/TensorBoard.  The window's ``.xplane.pb`` is then reduced to
**why the device was idle**: its busy seconds (the union of each device
plane's ``XLA Ops`` intervals, averaged over planes), its idle share,
and the idle seconds that fall inside the dispatch thread's waits on
egress (``firebird.wait_egress`` / ``firebird.store_flush``) and on
input (``firebird.wait_input``) — the program's own spans, which
obs/tracing.py puts on the profiler's clock.  The reduction is folded
into ``obs_report.json`` (``profile`` block — structure always present,
zeros allowed on backends with no device plane) and from there into
bench artifacts.

``Config.profile_dir`` (FIREBIRD_PROFILE_DIR) remains the whole-run
capture; this module is the *windowed* complement a multi-hour run
needs (a full-run device trace of a tile run is gigabytes).
"""

from __future__ import annotations

import glob
import os
import threading
import time

from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.obs import tracing

OPS_LINE = "XLA Ops"
# Idle time inside these spans is put down to the dispatch thread
# waiting on egress (a pipeline slot, the chunk's last drains, the
# writer's flush) or on its next input batch.
EGRESS_WAIT_SPANS = ("firebird.wait_egress", "firebird.store_flush")
INPUT_WAIT_SPANS = ("firebird.wait_input",)
SECONDS = ("window_s", "busy_s", "idle_wait_egress_s", "idle_wait_input_s")


def empty_device_time(source: str = "none") -> dict:
    out = dict.fromkeys(SECONDS, 0.0)
    out.update({"idle_pct": None, "devices": 0, "source": source})
    return out


def _union(intervals) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _idle_pct(block: dict):
    if not block["devices"] or block["window_s"] <= 0:
        return None
    return round(100.0 * (1.0 - block["busy_s"] / block["window_s"]), 3)


def reduce_window(events) -> dict:
    """A capture's device time from ``(plane, line, name, start_ns,
    end_ns)`` events.  The window is the capture's extent (first to last
    event); idle is its complement of the busy union, per device plane."""
    events = list(events)
    if not events:
        return empty_device_time("empty-trace")
    lo = min(e[3] for e in events)
    hi = max(e[4] for e in events)
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    waits = [_union((e[3], e[4]) for e in events if e[2] in names)
             for names in (EGRESS_WAIT_SPANS, INPUT_WAIT_SPANS)]
    busy, idle = 0.0, [0.0, 0.0]
    for p in planes:
        on = [e for e in events if e[0] == p]
        ops = _union((e[3], e[4]) for e in
                     ([e for e in on if e[1] == OPS_LINE] or on))
        busy += sum(e - s for s, e in ops)
        for k, w in enumerate(waits):
            idle[k] += sum(e - s for s, e in w) - _overlap(w, ops)
    n = max(len(planes), 1)
    out = {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
           "idle_wait_egress_s": idle[0] / n / 1e9,
           "idle_wait_input_s": idle[1] / n / 1e9,
           "devices": len(planes), "source": "trace"}
    out["idle_pct"] = _idle_pct(out)
    return out


def merge_device_time(blocks) -> dict:
    """Windows (or hosts) combined: seconds sum, the idle share is
    recomputed from the sums.  Provenance survives: 'trace' when any
    block really reduced a trace, else 'error' when any failed (a fleet
    whose every profiler broke must not read as one that never
    profiled), else 'none'."""
    blocks = [b for b in blocks if b]
    sources = {b.get("source") for b in blocks}
    out = empty_device_time(
        "trace" if "trace" in sources
        else "error" if sources & {"error", "no-trace-files", "empty-trace"}
        else "none")
    for b in blocks:
        for k in SECONDS:
            out[k] += b.get(k) or 0.0
        out["devices"] = max(out["devices"], b.get("devices") or 0)
    out["idle_pct"] = _idle_pct(out)
    return out


def window_device_time(trace_dir: str) -> dict:
    """:func:`reduce_window` over the newest ``.xplane.pb`` of a capture
    window, read with ``jax.profiler.ProfileData``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return empty_device_time("no-trace-files")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return reduce_window(
        (plane.name, line.name, e.name, e.start_ns,
         e.start_ns + e.duration_ns)
        for plane in data.planes for line in plane.lines
        for e in line.events)


class ProfilerBusy(RuntimeError):
    """A capture window is already in flight (jax allows one trace at a
    time per process)."""


class DeviceProfiler:
    """Windowed device-trace capture for one run.

    ``outdir`` is the artifact root (``<store dir>/device_profile``);
    each window writes ``window_<n>/`` under it.  One window at a time —
    jax.profiler is a process singleton.
    """

    def __init__(self, outdir: str):
        self.outdir = os.path.abspath(outdir)
        self._lock = threading.Lock()
        self._busy = False  # guarded-by: _lock
        self._n = 0  # guarded-by: _lock
        self._windows: list[dict] = []  # guarded-by: _lock
        self._auto_seconds = 0.0  # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- capture -------------------------------------------------------------

    def window(self, seconds: float, block: bool = False) -> dict:
        """Start one capture window of ``seconds`` (bounded 0.05..600).
        Raises :class:`ProfilerBusy` when one is already in flight.
        ``block=True`` runs the capture synchronously (tests, tools);
        the default returns immediately and captures on a daemon thread.
        """
        seconds = min(max(float(seconds), 0.05), 600.0)
        with self._lock:
            if self._busy:
                raise ProfilerBusy("a profile window is already capturing")
            self._busy = True
            n = self._n
            self._n += 1
        info = {"window": n, "seconds": seconds,
                "dir": os.path.join(self.outdir, f"window_{n:02d}"),
                # UTC with designator — the written_at/generated_at
                # convention, so windows correlate across artifacts.
                "started_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime())}
        if block:
            self._capture(info)
            return info
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._capture, args=(info,),
            name="firebird-profile", daemon=True)
        self._thread.start()
        return info

    def _capture(self, info: dict) -> None:
        try:
            import jax

            os.makedirs(info["dir"], exist_ok=True)
            with tracing.span("profile", seconds=info["seconds"]):
                jax.profiler.start_trace(info["dir"])
                try:
                    # Interruptible wait: close() ends an in-flight
                    # window early instead of leaking a started trace.
                    self._stop.wait(info["seconds"])
                finally:
                    jax.profiler.stop_trace()
            info["device_time"] = window_device_time(info["dir"])
            info["trace_files"] = len(glob.glob(
                os.path.join(info["dir"], "**", "*"), recursive=True))
            obs_metrics.counter(
                "profile_windows",
                help="on-demand device-profile windows captured").inc()
        except Exception as e:
            # A broken profiler (unsupported backend, concurrent trace)
            # must cost the operator a diagnosable record, not the run.
            info["error"] = f"{type(e).__name__}: {e}"
            info["device_time"] = empty_device_time("error")
            from firebird_tpu.obs import logger
            logger("change-detection").warning(
                "device-profile window failed: %s", info["error"])
        finally:
            with self._lock:
                self._windows.append(info)
                self._busy = False

    # -- FIREBIRD_PROFILE auto window ---------------------------------------

    def arm_auto(self, seconds: float) -> None:
        """Arm a one-shot window that starts at the first dispatched
        batch (obs/server.py's ``batch_dispatched`` hook) — steady-state
        kernels, not bring-up compile."""
        with self._lock:
            self._auto_seconds = float(seconds)

    def maybe_start_auto(self) -> None:
        with self._lock:
            seconds, self._auto_seconds = self._auto_seconds, 0.0
        if seconds > 0:
            try:
                self.window(seconds)
            except ProfilerBusy:
                pass

    # -- reads / teardown ----------------------------------------------------

    def summary(self) -> dict:
        """The report's ``profile`` block: windows so far + their device
        time combined (structure matches :func:`report_block`)."""
        with self._lock:
            windows = [dict(w) for w in self._windows]
            busy = self._busy
        return {"windows": windows, "in_flight": busy,
                "device_time": merge_device_time(
                    w.get("device_time") for w in windows),
                "dir": self.outdir}

    def close(self, timeout: float = 10.0) -> None:
        """End any in-flight window early and collect it — called before
        the report is written so a run's last window is never lost."""
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
        self._thread = None


# ---------------------------------------------------------------------------
# Process-global slot (one run's profiler; obs/report reads it)
# ---------------------------------------------------------------------------

# Mutated by start_ops/stop_ops on the run-owning thread; readers grab
# the reference once (the obs/server._status discipline).
_active: DeviceProfiler | None = None


def set_active(prof: DeviceProfiler | None) -> DeviceProfiler | None:
    global _active
    _active = prof  # firebird-lint: disable=ownership-global-mutation
    return prof


def active() -> DeviceProfiler | None:
    return _active


def close_active() -> None:
    """Flush an in-flight window (never raises) — obs.report.finish_run
    calls this before building the report so the artifact carries the
    final window's device time."""
    prof = _active
    if prof is not None:
        try:
            prof.close()
        except Exception:
            pass


def report_block() -> dict:
    """The obs_report ``profile`` block — ALWAYS structurally present
    (the acceptance contract: zeros allowed, structure never absent)."""
    prof = _active
    if prof is None:
        return {"windows": [], "in_flight": False,
                "device_time": empty_device_time("none"), "dir": None}
    return prof.summary()
