"""Fleet telemetry-plane smoke (``make telemetry-smoke``): one scene's
causal chain crosses the whole fleet in ONE collected trace.

The proof behind docs/OBSERVABILITY.md "Fleet telemetry plane": a
standing fleet — `firebird watch` plus two `firebird fleet work
--forever` workers over a FileSource landing zone — drains a scene
series whose final scene confirms a break on every pixel, a webhook
deliverer pushes the alerts out, and `firebird trace collect` merges
every process's on-disk telemetry spool into one Perfetto trace plus
per-alert critical-path breakdowns.  Mid-final-scene the smoke SIGKILLs
the worker holding the alerting job, so the collected trace must
include spool segments recovered from a process that never got to exit.

Asserts:

- **one causal chain, >=4 OS processes**: the alerting scene's trace id
  joins events from the watcher, BOTH workers (the SIGKILLed claimant's
  recovered spool and the survivor that re-ran the re-delivered job),
  and the deliverer — distinct pids in one Chrome-trace artifact that
  obs_report.validate_trace accepts;
- **SIGKILL recovery**: the killed worker's pid appears among the
  collected processes — its spool segments survived it;
- **critical-path attribution**: the breakdown's consecutive stages sum
  to its publish->append total exactly, and that total agrees with the
  ``measured_acq_to_alert`` the emitting process observed into
  ``acquisition_to_alert_seconds`` within 10%; a ``delivery`` leg rides
  past it once the webhook 2xx lands;
- **zero-cost disarmed**: a `firebird watch --once` leg under
  ``FIREBIRD_TELEMETRY=0`` leaves NO telemetry directory behind.

Writes ``telemetry_smoke.json`` under FIREBIRD_TELEMETRY_SMOKE_DIR
(folded into bench artifacts by bench.py's ``_telemetry_fold``) and
exits non-zero on any violation.
"""

import json
import os
import signal
import sqlite3
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)

from firebird_tpu.config import env_knob  # noqa: E402

ACQ_START = "1995-01-01"
BOOT_END = "1999-01-01"
N_CHIPS = 2                 # two stream jobs per scene: the final scene
N_SCENES = 6                # MUST fan across both workers
CHANGE_SCENE = 0            # every scene exceeds; the 6th (last) confirms
N_WORKERS = 2
TILE_XY = (100.0, 200.0)
DEADLINE = 540.0

# The deliverer leg runs as its own OS process (the fleet deployment
# shape: delivery lives in `firebird serve`, not in a worker), arming
# the spool under the "deliverer" role and sweeping until the backlog
# is out or the deadline hits.
DELIVER_SRC = """
import sys, time
from firebird_tpu.alerts.feed import WebhookDeliverer
from firebird_tpu.alerts.log import AlertLog, alert_db_path
from firebird_tpu.config import Config
from firebird_tpu.obs import spool as obs_spool

cfg = Config.from_env()
obs_spool.arm(cfg, "deliverer")
alog = AlertLog(alert_db_path(cfg))
d = WebhookDeliverer(alog, cfg)
deadline = time.time() + float(sys.argv[1])
try:
    while time.time() < deadline:
        d.deliver_once()
        if all(s["lag"] == 0 for s in alog.subscribers()):
            sys.exit(0)
        time.sleep(0.2)
    sys.exit(2)
finally:
    obs_spool.disarm()
    alog.close()
"""


def fail(msg: str) -> int:
    print(f"telemetry-smoke: {msg}", file=sys.stderr)
    return 1


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


def dump_failure(failures, logs) -> int:
    import shutil

    keep = os.path.join(env_knob("FIREBIRD_TELEMETRY_SMOKE_DIR"),
                        "failure_logs")
    os.makedirs(keep, exist_ok=True)
    for f_ in failures:
        print(f"telemetry-smoke: {f_}", file=sys.stderr)
    for p in logs:
        try:
            shutil.copy(p, keep)
        except OSError:
            continue
        print(f"--- {os.path.basename(p)} (kept in {keep}) ---\n"
              f"{tail(p)}", file=sys.stderr)
    return 1


# ---------------------------------------------------------------------------
# world + plumbing (the stream_fleet_soak idiom: the parent stays JAX-free)
# ---------------------------------------------------------------------------

def build_world(outdir: str, cids):
    import numpy as np

    from firebird_tpu.ccd import synthetic
    from firebird_tpu.utils import dates as dt

    os.makedirs(outdir, exist_ok=True)
    boot_t = synthetic.acquisition_dates(ACQ_START, BOOT_END, 16)
    scene_t = boot_t[-1] + 16 * np.arange(1, N_SCENES + 1)
    full_t = np.concatenate([boot_t, scene_t])
    rng = np.random.default_rng(23)
    base = synthetic.harmonic_series(full_t, rng)
    chips = {}
    for cx, cy in cids:
        noise = rng.normal(0.0, 10.0, (7, full_t.shape[0], 100, 100))
        spectra = base[:, :, None, None] + noise
        spectra[:, full_t >= scene_t[CHANGE_SCENE]] += 800.0
        chips[(cx, cy)] = np.clip(
            spectra, -32768, 32767).astype(np.int16)
    scenes = [(f"LC08_{dt.to_iso(int(d))}", dt.to_iso(int(d)))
              for d in scene_t]
    return full_t, chips, scenes


def land(outdir: str, cids, full_t, chips, upto_ordinal, scene=None):
    import numpy as np

    from firebird_tpu.ccd import synthetic
    from firebird_tpu.ingest.packer import ChipData
    from firebird_tpu.ingest.sources import FileSource

    fs = FileSource(outdir)
    m = full_t <= upto_ordinal
    for cx, cy in cids:
        fs.save_chip(ChipData(
            cx=int(cx), cy=int(cy), dates=full_t[m],
            spectra=chips[(cx, cy)][:, m],
            qas=np.full((int(m.sum()), 100, 100), synthetic.QA_CLEAR,
                        np.uint16)))
    if scene is not None:
        fs.append_scene(scene[0], date=scene[1])


def smoke_env(tmp: str) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONFAULTHANDLER": "1",
        "PYTHONPATH": HERE + os.pathsep + env.get("PYTHONPATH", ""),
        "FIREBIRD_STORE_BACKEND": "sqlite",
        "FIREBIRD_STORE_PATH": os.path.join(tmp, "fleet", "smoke.db"),
        "FIREBIRD_STREAM_DIR": os.path.join(tmp, "fleet", "state"),
        "FIREBIRD_SOURCE": "file",
        "FIREBIRD_SOURCE_PATH": os.path.join(tmp, "archive"),
        "FIREBIRD_CHIPS_PER_BATCH": "1",
        "FIREBIRD_DEVICE_SHARDING": "off",
        "FIREBIRD_FLEET_LEASE_SEC": "3",
        "FIREBIRD_ALERT_REPAIR": "0",
        "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        # tight snapshot cadence so even short-lived processes leave a
        # metric snapshot for `firebird top` / the collector
        "FIREBIRD_TELEMETRY_SNAPSHOT_SEC": "1",
    })
    for k in ("FIREBIRD_FAULTS", "FIREBIRD_ALERT_DB", "FIREBIRD_FLEET_DB",
              "FIREBIRD_WATCH_DB", "FIREBIRD_STREAM_STATESTORE",
              "FIREBIRD_TELEMETRY", "FIREBIRD_TELEMETRY_DIR"):
        env.pop(k, None)
    return env


def run_cli(args: list, env: dict, log_path: str, *,
            timeout: float = DEADLINE) -> int:
    cmd = [sys.executable, "-m", "firebird_tpu.cli", *args]
    with open(log_path, "a") as logf:
        return subprocess.run(cmd, env=env, cwd=HERE, stdout=logf,
                              stderr=subprocess.STDOUT,
                              timeout=timeout).returncode


def spawn_cli(args: list, env: dict, log_path: str):
    logf = open(log_path, "a")
    return subprocess.Popen(
        [sys.executable, "-m", "firebird_tpu.cli", *args],
        env=env, cwd=HERE, stdout=logf, stderr=subprocess.STDOUT)


def start_receiver():
    """A webhook sink in the (JAX-free) parent: counts 2xx-acknowledged
    alert records.  Returns (server, port, counts dict)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    got = {"batches": 0, "records": 0}

    class Sink(BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            got["batches"] += 1
            got["records"] += len(json.loads(body).get("alerts", ()))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, srv.server_address[1], got


def main() -> int:  # noqa: C901 (one linear drill, read top to bottom)
    from firebird_tpu import grid
    from firebird_tpu.alerts.log import AlertLog, alert_db_path
    from firebird_tpu.config import Config
    from firebird_tpu.fleet.queue import FleetQueue, queue_path
    from firebird_tpu.obs import report as obs_report
    from firebird_tpu.utils import dates as dt
    from firebird_tpu.utils.fn import take

    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="fb_telemetry_") as tmp:
        tile = grid.tile(x=TILE_XY[0], y=TILE_XY[1])
        cids = [tuple(int(v) for v in c)
                for c in take(N_CHIPS, grid.chips(tile))]
        archive = os.path.join(tmp, "archive")
        full_t, chips, scenes = build_world(archive, cids)
        boot_t_max = int(full_t[len(full_t) - N_SCENES - 1])
        land(archive, cids, full_t, chips, boot_t_max)
        os.makedirs(os.path.join(tmp, "fleet"), exist_ok=True)
        env = smoke_env(tmp)
        cfg = Config.from_env(env=env)
        qpath = queue_path(cfg)
        adb = alert_db_path(cfg)
        from firebird_tpu.obs import spool as spool_mod

        spool_root = spool_mod.spool_dir(cfg)
        watch_args = ["watch", "-x", str(TILE_XY[0]),
                      "-y", str(TILE_XY[1]), "-n", str(N_CHIPS),
                      "--acquired-start", ACQ_START, "-i", "0.2"]
        worker_args = ["fleet", "work", "--forever", "--poll", "0.2"]

        # ---- zero-cost leg: FIREBIRD_TELEMETRY=0 leaves no spool ------
        env0 = dict(env, FIREBIRD_TELEMETRY="0")
        zlog = os.path.join(tmp, "zerocost.log")
        if run_cli(["watch", "-x", str(TILE_XY[0]), "-y", str(TILE_XY[1]),
                    "-n", str(N_CHIPS), "--once"], env0, zlog):
            print(tail(zlog), file=sys.stderr)
            return fail("FIREBIRD_TELEMETRY=0 watch --once failed")
        if spool_root and os.path.isdir(spool_root):
            return fail("FIREBIRD_TELEMETRY=0 still created a telemetry "
                        f"spool directory at {spool_root}")

        # ---- webhook sink + durable subscriber ------------------------
        recv, port, got = start_receiver()
        alog = AlertLog(adb)
        alog.subscribe(f"http://127.0.0.1:{port}/alerts")
        alog.close()

        # ---- standing fleet -------------------------------------------
        watcher_log = os.path.join(tmp, "watcher.log")
        worker_logs = [os.path.join(tmp, f"worker{i}.log")
                       for i in range(N_WORKERS)]
        watcher = spawn_cli(watch_args, env, watcher_log)
        workers = [spawn_cli(worker_args, env, worker_logs[i])
                   for i in range(N_WORKERS)]
        deadline = t0 + DEADLINE
        failures = []
        killed_pid = None
        deliver_log = os.path.join(tmp, "deliver.log")

        def counts():
            q = FleetQueue(qpath)
            try:
                return q.counts()
            finally:
                q.close()

        def leased_worker_pid():
            q = FleetQueue(qpath)
            try:
                for w in q.workers():
                    if w.get("lease"):
                        return int(w["pid"])
            finally:
                q.close()
            return None

        def horizons_at(ordinal) -> bool:
            from firebird_tpu.streamops.statestore import TileStateStore

            store = TileStateStore(os.path.join(tmp, "fleet", "state"))
            try:
                return all((store.peek_horizon(c) or 0) >= ordinal
                           for c in cids)
            except Exception:
                return False
            finally:
                store.close()

        try:
            # Scenes 0..N-2: bootstrap detect + per-scene stream updates
            # drain fully, so the ONLY jobs in flight after the final
            # scene lands are the alert-confirming ones.
            for sid, date in scenes[:-1]:
                land(archive, cids, full_t, chips, dt.to_ordinal(date),
                     scene=(sid, date))
                time.sleep(1.0)
            pre_ord = dt.to_ordinal(scenes[-2][1])
            while time.time() < deadline:
                c = counts()
                if c.get("pending", 0) == 0 and c.get("leased", 0) == 0 \
                        and horizons_at(pre_ord):
                    break
                time.sleep(0.25)
            else:
                failures.append(
                    f"pre-drain never completed: queue={counts()}")

            # Final scene: the 6th exceeding acquisition — its stream
            # jobs confirm the break on every pixel.  SIGKILL the first
            # worker seen holding one of them (its unacked lease
            # re-delivers to the survivor under the 3s lease), so the
            # alerting trace spans the killed claimant's recovered
            # spool AND the survivor.
            if not failures:
                sid, date = scenes[-1]
                land(archive, cids, full_t, chips, dt.to_ordinal(date),
                     scene=(sid, date))
                while time.time() < deadline and killed_pid is None:
                    killed_pid = leased_worker_pid()
                    if killed_pid is None:
                        time.sleep(0.05)
                for i, w in enumerate(workers):
                    if w.pid == killed_pid:
                        w.send_signal(signal.SIGKILL)
                        w.wait(timeout=30)
                        workers[i] = spawn_cli(worker_args, env,
                                               worker_logs[i])
                        break
                else:
                    failures.append(
                        f"no worker held a lease for the final scene "
                        f"(saw pid {killed_pid})")

            # Drain the final scene + its re-delivered job, then let the
            # deliverer (its own OS process, own spool role) push the
            # alert backlog to the webhook sink.
            last_ord = dt.to_ordinal(scenes[-1][1])
            while time.time() < deadline:
                c = counts()
                if c.get("pending", 0) == 0 and c.get("leased", 0) == 0 \
                        and horizons_at(last_ord):
                    break
                time.sleep(0.25)
            else:
                failures.append(
                    f"final drain never completed: queue={counts()}")
            rc = subprocess.run(
                [sys.executable, "-c", DELIVER_SRC,
                 str(max(deadline - time.time(), 10.0))],
                env=env, cwd=HERE, timeout=DEADLINE,
                stdout=open(deliver_log, "a"),
                stderr=subprocess.STDOUT).returncode
            if rc:
                failures.append(f"deliverer leg exited {rc}")
        finally:
            # SIGTERM-drain the standing fleet so every spool closes
            # with a final metric snapshot (the SIGKILLed worker's ring
            # is the deliberate exception the collector must survive).
            for p in [watcher, *workers]:
                if p.poll() is None:
                    p.terminate()
            for p in [watcher, *workers]:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)
            recv.shutdown()

        con = sqlite3.connect(adb)
        try:
            n_alerts = con.execute(
                "SELECT COUNT(*) FROM alerts").fetchone()[0]
            n_traced = con.execute(
                "SELECT COUNT(*) FROM alerts WHERE trace IS NOT NULL"
            ).fetchone()[0]
        finally:
            con.close()
        if n_alerts < N_CHIPS * 9000:
            failures.append(f"only {n_alerts} alerts — the step change "
                            "did not break the tile")
        if n_traced != n_alerts:
            failures.append(f"{n_alerts - n_traced} alert rows lost "
                            "their trace id")
        if got["records"] < n_alerts:
            failures.append(f"webhook sink got {got['records']} of "
                            f"{n_alerts} records")

        # ---- collect: every spool -> one trace + attribution ----------
        clog = os.path.join(tmp, "collect.log")
        cpath = os.path.join(tmp, "telemetry_collect.json")
        if run_cli(["trace", "collect", "-o", cpath], env, clog):
            print(tail(clog), file=sys.stderr)
            return fail("firebird trace collect failed")
        with open(cpath) as f:
            doc = json.load(f)
        try:
            obs_report.validate_trace(doc["trace"])
        except Exception as e:
            failures.append(f"collected trace invalid: {e}")
        procs = {f"{p['role']}:{p['pid']}" for p in doc["processes"]}
        roles = {p["role"] for p in doc["processes"]}
        for role in ("watcher", "worker", "deliverer"):
            if role not in roles:
                failures.append(f"no {role} process in the collected "
                                f"trace (saw {sorted(procs)})")
        if killed_pid is not None and f"worker:{killed_pid}" not in procs:
            failures.append(
                f"SIGKILLed worker {killed_pid}'s spool segments were "
                f"not recovered (processes: {sorted(procs)})")

        # The alerting scene's chain: delivered, fully staged, and
        # spanning >=4 distinct OS processes on ONE trace id.
        chains = [p for p in doc["critical_paths"]
                  if p.get("stages") and "delivery" in p
                  and p.get("measured_acq_to_alert") is not None]
        chain = max(chains, key=lambda p: len(p["processes"]),
                    default=None)
        if chain is None:
            failures.append(
                "no critical path with stages + delivery + measured "
                f"total (paths: {doc['critical_paths']})")
        else:
            if len(chain["processes"]) < 4:
                failures.append(
                    f"causal chain {chain['trace']} spans only "
                    f"{chain['processes']} — expected >=4 distinct OS "
                    "processes (watcher, both workers, deliverer)")
            ssum = sum(chain["stages"].values())
            if abs(ssum - chain["total"]) > 0.01 * max(chain["total"],
                                                       0.01):
                failures.append(
                    f"stages sum {ssum} != total {chain['total']} — "
                    "the residual accounting broke")
            measured = chain["measured_acq_to_alert"]
            if abs(chain["total"] - measured) > 0.10 * measured:
                failures.append(
                    f"breakdown total {chain['total']}s disagrees with "
                    f"measured acquisition_to_alert {measured}s by more "
                    "than 10%")

        logs = (zlog, watcher_log, *worker_logs, deliver_log, clog)
        if failures:
            return dump_failure(failures, logs)

        report = {
            "schema": "firebird-telemetry-smoke/1",
            "chips": N_CHIPS,
            "scenes": N_SCENES,
            "workers": N_WORKERS,
            "alerts": n_alerts,
            "alerts_traced": n_traced,
            "webhook_records": got["records"],
            "processes": sorted(procs),
            "worker_sigkilled_pid": killed_pid,
            "sigkilled_spool_recovered": True,
            "zero_cost_disarmed": True,
            "chain": {
                "trace": chain["trace"],
                "processes": chain["processes"],
                "stages": chain["stages"],
                "total_sec": chain["total"],
                "measured_acq_to_alert_sec":
                    chain["measured_acq_to_alert"],
                "delivery_sec": chain["delivery"],
            },
            "trace_events": len(doc["trace"]["traceEvents"]),
            "critical_paths": len(doc["critical_paths"]),
            "wall_seconds": round(time.time() - t0, 1),
        }
        art_dir = env_knob("FIREBIRD_TELEMETRY_SMOKE_DIR")
        os.makedirs(art_dir, exist_ok=True)
        art = os.path.join(art_dir, "telemetry_smoke.json")
        with open(art, "w") as f:
            json.dump(report, f, indent=1)
        print("telemetry-smoke OK: scene "
              f"{report['chain']['trace']} crossed "
              f"{len(chain['processes'])} OS processes "
              f"({', '.join(chain['processes'])}) in one collected "
              f"trace; breakdown total {chain['total']}s vs measured "
              f"{chain['measured_acq_to_alert']}s; delivery "
              f"{chain['delivery']}s; SIGKILLed worker {killed_pid} "
              f"recovered from its spool; {report['wall_seconds']}s; "
              f"artifact {art}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
