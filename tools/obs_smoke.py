"""Observability smoke test (``make obs-smoke``).

Runs the synthetic-source driver end to end with the span tracer on AND
the embedded ops endpoint bound to an ephemeral port, polling
``/healthz`` / ``/readyz`` / ``/metrics`` / ``/progress`` while batches
are in flight, then validates the emitted artifacts against the shared
schema checks (firebird_tpu.obs.report): the Chrome-trace JSON must
parse, pass ``validate_trace``, and contain every pipeline span name
(DRIVER_SPAN_NAMES, incl. the stage/d2h staging-egress spans); the
obs_report.json must pass ``validate_report`` and carry every
DRIVER_STAGE_HISTOGRAMS stage key; and the live ``/progress`` chip
totals must agree with the final report.  The deep-dive layer rides the
same run: one ``POST /profile?seconds=N`` window is captured mid-run and
must leave a device-trace artifact + its device-time reduction (busy,
idle share, idle under the dispatch thread's waits) in the report's
``profile`` block (zeros allowed on the CPU backend, structure always
present), ``/slo`` must answer live, and the report's ``slo``
block must have evaluated the batch objective against real data.  Exits
non-zero on any violation — the CI-greppable proof that the telemetry layer still wires
through every pipeline stage and that the live ops surface serves during
a real run.
"""

import json
import os
import socket
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(base: str, path: str, timeout: float = 2.0):
    """(status, body bytes) — HTTP errors return their status, transport
    errors return (None, b'')."""
    try:
        r = urllib.request.urlopen(base + path, timeout=timeout)
        return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except Exception:
        return None, b""


def _post(base: str, path: str, timeout: float = 2.0):
    try:
        req = urllib.request.Request(base + path, data=b"", method="POST")
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except Exception:
        return None, b""


def main() -> int:
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.ingest import SyntheticSource
    from firebird_tpu.obs import report as obs_report
    # The shared scrape-format contract (every exposition line is a
    # comment or a sample; also asserted by the test suite).
    from firebird_tpu.obs.metrics import PROM_LINE_RE as PROM_LINE

    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    with tempfile.TemporaryDirectory(prefix="fb_obs_smoke_") as tmp:
        cfg = Config(store_backend="sqlite",
                     store_path=os.path.join(tmp, "smoke.db"),
                     source_backend="synthetic", chips_per_batch=1,
                     device_sharding="off", fetch_retries=0, trace="1",
                     ops_port=port, stall_sec=120.0)
        src = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01",
                              cloud_frac=0.1)

        result: dict = {}

        def run():
            result["done"] = core.changedetection(
                x=100, y=200, acquired="1995-01-01/1997-06-01",
                number=2, chunk_size=2, cfg=cfg, source=src)

        driver = threading.Thread(target=run, name="smoke-driver")
        driver.start()

        # Poll the live surface while the run is in flight; keep the last
        # good sample of each endpoint.  As soon as the endpoint answers,
        # fire ONE windowed device-profile capture (POST /profile) so the
        # final report must carry its device time — the on-demand
        # profiling acceptance path.
        live: dict = {}
        posted: dict = {}
        while driver.is_alive():
            for p in ("/healthz", "/readyz", "/metrics", "/progress",
                      "/slo"):
                code, body = _get(base, p)
                if code is not None:
                    live[p] = (code, body)
            if "started" not in posted and "/healthz" in live:
                code, body = _post(base, "/profile?seconds=0.2")
                if code == 202:
                    posted["started"] = json.loads(body)
            time.sleep(0.05)
        driver.join()

        if len(result.get("done", ())) != 2:
            print(f"obs-smoke: driver processed "
                  f"{len(result.get('done', ()))}/2 chips", file=sys.stderr)
            return 1
        for p in ("/healthz", "/readyz", "/metrics", "/progress"):
            if p not in live:
                print(f"obs-smoke: {p} never responded during the run",
                      file=sys.stderr)
                return 1
        if live["/healthz"][0] != 200:
            print(f"obs-smoke: /healthz was {live['/healthz'][0]}, not 200",
                  file=sys.stderr)
            return 1
        if live["/readyz"][0] != 200:
            print("obs-smoke: /readyz never reached 200 during the run",
                  file=sys.stderr)
            return 1
        bad = [ln for ln in live["/metrics"][1].decode().splitlines()
               if ln and not PROM_LINE.match(ln)]
        if bad:
            print(f"obs-smoke: malformed /metrics lines: {bad[:3]}",
                  file=sys.stderr)
            return 1

        trace = json.load(open(os.path.join(tmp, "trace.json")))
        rep = json.load(open(os.path.join(tmp, "obs_report.json")))
        try:
            # The one shared contract (also asserted by the driver smoke
            # test): schema validity + span/stage-key coverage.
            obs_report.validate_driver_artifacts(trace, rep)
        except ValueError as e:
            print(f"obs-smoke: {e}", file=sys.stderr)
            return 1

        # --- deep-dive layer: POST /profile + /slo + report blocks ---
        if "started" not in posted:
            print("obs-smoke: POST /profile never got a 202 during the run",
                  file=sys.stderr)
            return 1
        prof = rep.get("profile")
        if not prof or not prof.get("windows"):
            print(f"obs-smoke: report profile block has no windows: {prof}",
                  file=sys.stderr)
            return 1
        from firebird_tpu.obs.profiling import empty_device_time
        dt = prof.get("device_time") or {}
        missing = [k for k in empty_device_time() if k not in dt]
        if missing or dt.get("source") != "trace":
            print(f"obs-smoke: device_time incomplete (missing {missing}, "
                  f"source {dt.get('source')!r}): {dt}", file=sys.stderr)
            return 1
        win = prof["windows"][0]
        if "error" in win or not os.path.isdir(win["dir"]) \
                or win.get("trace_files", 0) < 1:
            print(f"obs-smoke: profile window left no device-trace "
                  f"artifact: {win}", file=sys.stderr)
            return 1
        if "/slo" not in live or live["/slo"][0] != 200:
            print(f"obs-smoke: /slo never answered 200 "
                  f"({live.get('/slo', ('never', b''))[0]})",
                  file=sys.stderr)
            return 1
        slo_rep = rep.get("slo")
        if not slo_rep or "objectives" not in slo_rep:
            print(f"obs-smoke: report slo block malformed: {slo_rep}",
                  file=sys.stderr)
            return 1
        # The driver drained batches, so the batch objective must have
        # evaluated against real data (ok True/False, not no-data null).
        batch = [o for o in slo_rep["objectives"]
                 if o["name"] == "batch_p95"]
        if not batch or batch[0]["ok"] is None:
            print(f"obs-smoke: batch_p95 objective never evaluated: "
                  f"{slo_rep['objectives']}", file=sys.stderr)
            return 1

        # The live surface and the final artifact must tell one story:
        # same run, same chip totals.
        prog = json.loads(live["/progress"][1])
        if prog["run_id"] != rep["run"]["run_id"]:
            print(f"obs-smoke: /progress run_id {prog['run_id']} != report "
                  f"{rep['run']['run_id']}", file=sys.stderr)
            return 1
        if prog["chips_total"] != rep["run"]["chips"]:
            print(f"obs-smoke: /progress chips_total {prog['chips_total']} "
                  f"!= report chips {rep['run']['chips']}", file=sys.stderr)
            return 1
        if prog["chips_done"] > rep["run_counters"]["chips"]:
            print(f"obs-smoke: /progress chips_done {prog['chips_done']} "
                  f"exceeds final count {rep['run_counters']['chips']}",
                  file=sys.stderr)
            return 1
        print("obs-smoke OK: "
              f"{len(trace['traceEvents'])} trace events, "
              f"{len(rep['metrics']['histograms'])} stage histograms, "
              f"counters {rep['metrics']['counters']}, "
              f"live progress {prog['chips_done']}/{prog['chips_total']} "
              f"chips at stage {prog['stage']!r}, "
              f"profile window {win['trace_files']} trace files "
              f"({dt['window_s']:.3f} s window, {dt['devices']} device "
              f"planes), "
              f"slo ok={slo_rep['ok']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
