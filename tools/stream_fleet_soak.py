"""Stream-fleet chaos soak (``make streamfleet-smoke``): the
scene -> alert freshness pipeline survives death.

The end-to-end proof behind docs/STREAMING.md: a STANDING fleet —
`firebird watch` polling the acquisition manifest plus N
`firebird fleet work --forever` workers — must drain every scene that
lands while it runs, with the watcher and one worker SIGKILLed
mid-drain, and still deliver every alert exactly once into a packed
statestore that matches a clean serial leg byte for byte.

Legs, over a FileSource archive whose every pixel steps +800 partway
through the scene series (so later scenes confirm a break on every
standard pixel):

serial (the reference)
    Bootstrap via `firebird stream`, then land each scene and run a
    scoped stream update for it, serially, in one process.  Its alert
    rowset and its packed per-chip state payloads are the reference.
fleet (the drill)
    A fresh tree: the same bootstrap, then workers + watcher come up,
    and the parent lands the same scenes onto the manifest while they
    run.  Mid-drain the parent SIGKILLs the watcher (restarting it —
    the durable scene cursor resumes it) and one worker (the fleet
    lease protocol re-delivers its job).

Every JAX leg is a SUBPROCESS and the parent stays JAX-free (forking
workers from a parent with live XLA threads is how you get glibc heap
corruption instead of a chaos drill).  A stream subprocess that logs
"stream complete" and THEN dies of jax 0.4.37's CPU PJRT teardown
SIGABRT is success-with-a-warning (its outputs are already durable);
the rc + log evidence lands in the artifact's ``teardown_races``.

Asserts:

- **drain**: every scene's jobs enqueue (scene-id dedup across the two
  watcher incarnations — no double-enqueue) and the queue fully drains;
- **exactly-once alerts**: the fleet leg's (px, py, break_day) rowset
  EQUALS the serial leg's, with zero duplicates, through the SIGKILLs;
- **state identity**: every chip's packed statestore payload
  (statestore.serialize_state canonical bytes) is byte-identical to
  the serial leg's — the kill/re-delivery/resume machinery converged
  to the same state a single clean process produces;
- **freshness**: the ``acquisition_to_alert_seconds`` histogram has
  real observations and the ``alert_freshness`` SLO leg over it
  evaluates in the last stream job's obs report.

Writes ``stream_fleet_soak.json`` under FIREBIRD_STREAMFLEET_DIR
(folded into bench artifacts by bench.py's ``_streamfleet_fold``; its
``acquisition_to_alert_p95`` rides next to the e2e block) and exits
non-zero on any violation.
"""

import json
import os
import signal
import sqlite3
import subprocess
import sys
import tempfile
import time

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)

from firebird_tpu.config import env_knob  # noqa: E402

ACQ_START = "1995-01-01"
BOOT_END = "1999-01-01"          # bootstrap window: [ACQ_START, BOOT_END)
N_CHIPS = 2
N_SCENES = 10
# Scenes >= this index carry the +800 step; with PEEK_SIZE=6 the 6th
# exceeding acquisition — the LAST scene — confirms the break, so the
# alert-committing jobs are the fleet's final ones (their obs report
# carries the freshness histogram the SLO assert reads).
CHANGE_SCENE = 4
KILL_SCENE = 5                   # SIGKILL watcher+worker after this lands
N_WORKERS = 2
TILE_XY = (100.0, 200.0)
DEADLINE = 540.0
SLO_TARGET = 300.0


def fail(msg: str) -> int:
    print(f"streamfleet-smoke: {msg}", file=sys.stderr)
    return 1


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


# jax 0.4.37's CPU PJRT client can crash during interpreter teardown
# (a C++ "terminate called" SIGABRT out of the XLA thread-pool
# destructor, or a SIGSEGV in the same destructor region — the
# faulthandler dump shows "<no Python frame>") AFTER the run finished:
# the driver has already logged "stream complete" and flushed the
# store/statestore/alert log, so the work product is whole — only the
# exit status is corrupted (and the rowset-identity checks below still
# gate correctness).  Classify exactly that signature (nonzero rc +
# completion marker in the log + an abort fingerprint) as
# success-with-a-warning, preserving the rc and log evidence in the
# artifact; ANY other nonzero rc stays fatal.
TEARDOWN_SIGNATURES = ("terminate called", "SIGABRT",
                       "Fatal Python error: Aborted",
                       "Fatal Python error: Segmentation fault")


def stream_rc_ok(rc: int, log_path: str, step: str, warnings: list) -> bool:
    """True if the stream subprocess's work completed: rc 0, or the
    post-completion PJRT teardown race (recorded into ``warnings``)."""
    if rc == 0:
        return True
    logtxt = tail(log_path, 8000)
    aborted = rc in (-6, 134, -11, 139) or any(s in logtxt
                                               for s in TEARDOWN_SIGNATURES)
    if "stream complete" in logtxt and aborted:
        warnings.append({
            "step": step,
            "rc": rc,
            "log": os.path.basename(log_path),
            "log_excerpt": logtxt[-600:],
        })
        print(f"streamfleet-smoke: WARNING {step}: stream exited rc={rc} "
              "AFTER logging 'stream complete' with a PJRT teardown-abort "
              "signature — outputs are durable; continuing",
              file=sys.stderr)
        return True
    return False


def dump_failure(failures, logs) -> int:
    import shutil

    keep = os.path.join(env_knob("FIREBIRD_STREAMFLEET_DIR"),
                        "failure_logs")
    os.makedirs(keep, exist_ok=True)
    for f_ in failures:
        print(f"streamfleet-smoke: {f_}", file=sys.stderr)
    for p in logs:
        try:
            shutil.copy(p, keep)
        except OSError:
            continue
        print(f"--- {os.path.basename(p)} (kept in {keep}) ---\n"
              f"{tail(p)}", file=sys.stderr)
    return 1


# ---------------------------------------------------------------------------
# archive + scenes
# ---------------------------------------------------------------------------

def build_world(outdir: str, cids):
    """The full archive (bootstrap era + N_SCENES future acquisitions)
    and the per-scene slices.  Returns the scene list: [(scene_id,
    date_iso, chip_arrays_already_in_archive)]."""
    import numpy as np

    from firebird_tpu.ccd import synthetic
    from firebird_tpu.utils import dates as dt

    os.makedirs(outdir, exist_ok=True)
    boot_t = synthetic.acquisition_dates(ACQ_START, BOOT_END, 16)
    scene_t = boot_t[-1] + 16 * np.arange(1, N_SCENES + 1)
    full_t = np.concatenate([boot_t, scene_t])
    rng = np.random.default_rng(11)
    base = synthetic.harmonic_series(full_t, rng)                # [7, T]
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


def land(outdir: str, cids, full_t, chips, upto_ordinal,
         scene=None):
    """(Re)write each chip archive truncated at ``upto_ordinal``
    (inclusive), then publish ``scene`` on the manifest — archive
    first, manifest second, the FileSource landing-zone contract."""
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


# ---------------------------------------------------------------------------
# process plumbing (the parent stays JAX-free)
# ---------------------------------------------------------------------------

def leg_env(tmp: str, leg: str) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONFAULTHANDLER": "1",
        "PYTHONPATH": HERE + os.pathsep + env.get("PYTHONPATH", ""),
        "FIREBIRD_STORE_BACKEND": "sqlite",
        "FIREBIRD_STORE_PATH": os.path.join(tmp, leg, "soak.db"),
        "FIREBIRD_STREAM_DIR": os.path.join(tmp, leg, "state"),
        "FIREBIRD_SOURCE": "file",
        "FIREBIRD_SOURCE_PATH": os.path.join(tmp, "archive"),
        "FIREBIRD_CHIPS_PER_BATCH": "1",
        "FIREBIRD_DEVICE_SHARDING": "off",
        "FIREBIRD_SLO": f"alert_freshness={SLO_TARGET:.0f}",
        # short leases so the SIGKILLed worker's job re-delivers fast
        "FIREBIRD_FLEET_LEASE_SEC": "3",
        # the repair roll-up would race the drill's drain accounting;
        # the soak asserts on stream/detect jobs only
        "FIREBIRD_ALERT_REPAIR": "0",
        "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    for k in ("FIREBIRD_FAULTS", "FIREBIRD_ALERT_DB", "FIREBIRD_FLEET_DB",
              "FIREBIRD_WATCH_DB", "FIREBIRD_STREAM_STATESTORE"):
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


def alert_rows(path: str):
    if not os.path.exists(path):
        return [], 0
    con = sqlite3.connect(path)
    try:
        rows = con.execute(
            "SELECT px, py, break_day FROM alerts").fetchall()
    finally:
        con.close()
    return sorted(rows), len(rows)


def state_payloads(state_dir: str, cids) -> dict:
    """{cid: canonical payload bytes} — the byte-identity surface (the
    double-bank generation counters legitimately differ between legs;
    the STATE must not)."""
    from firebird_tpu.streamops.statestore import (TileStateStore,
                                                   _layout, _canonical)

    store = TileStateStore(state_dir)
    out = {}
    try:
        for cid in cids:
            a = store.peek_arrays(cid)
            P, B, K = a["coefs"].shape
            out[cid] = b"".join(
                _canonical(n, a[n], d, s).tobytes()
                for n, d, s in _layout(P, B, K))
    finally:
        store.close()
    return out


def main() -> int:  # noqa: C901 (one linear drill, read top to bottom)
    from firebird_tpu import grid
    from firebird_tpu.alerts.log import alert_db_path
    from firebird_tpu.config import Config
    from firebird_tpu.fleet.queue import FleetQueue, queue_path
    from firebird_tpu.utils import dates as dt
    from firebird_tpu.utils.fn import take

    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="fb_streamfleet_") as tmp:
        tile = grid.tile(x=TILE_XY[0], y=TILE_XY[1])
        cids = [tuple(int(v) for v in c)
                for c in take(N_CHIPS, grid.chips(tile))]
        archive = os.path.join(tmp, "archive")
        full_t, chips, scenes = build_world(archive, cids)
        boot_t_max = int(full_t[len(full_t) - N_SCENES - 1])
        # bootstrap-era archive only; scenes land later
        land(archive, cids, full_t, chips, boot_t_max)
        boot_acq = f"{ACQ_START}/{BOOT_END}"
        stream_base = ["-x", str(TILE_XY[0]), "-y", str(TILE_XY[1]),
                       "-n", str(N_CHIPS)]

        # ---- serial leg: the reference rowset + state ----------------
        env = leg_env(tmp, "serial")
        os.makedirs(os.path.join(tmp, "serial"), exist_ok=True)
        scfg = Config.from_env(env=env)
        serial_log = os.path.join(tmp, "serial.log")
        teardown_races = []
        if not stream_rc_ok(
                run_cli(["stream", *stream_base, "-a", boot_acq], env,
                        serial_log),
                serial_log, "serial bootstrap", teardown_races):
            print(tail(serial_log), file=sys.stderr)
            return fail("serial bootstrap failed")
        for sid, date in scenes:
            land(archive, cids, full_t, chips, dt.to_ordinal(date),
                 scene=(sid, date))
            end = dt.to_iso(dt.to_ordinal(date) + 1)
            if not stream_rc_ok(
                    run_cli(["stream", *stream_base,
                             "-a", f"{ACQ_START}/{end}"], env, serial_log),
                    serial_log, f"serial update {sid}", teardown_races):
                print(tail(serial_log), file=sys.stderr)
                return fail(f"serial update for {sid} failed")
        serial_rows, serial_n = alert_rows(alert_db_path(scfg))
        if serial_n < N_CHIPS * 9000:
            return fail(f"serial leg logged only {serial_n} alerts — "
                        "the step change did not break the tile")
        serial_state = state_payloads(os.path.join(tmp, "serial",
                                                   "state"), cids)

        # ---- fleet leg: watcher + standing workers + SIGKILLs --------
        # Fresh store/state/queue tree, fresh manifest (the archive
        # directory is per-leg scene history: wipe scenes.jsonl and
        # rewind the chip archives to the bootstrap era).
        land(archive, cids, full_t, chips, boot_t_max)
        os.remove(os.path.join(archive, "scenes.jsonl"))
        env = leg_env(tmp, "fleet")
        os.makedirs(os.path.join(tmp, "fleet"), exist_ok=True)
        fcfg = Config.from_env(env=env)
        fleet_log = os.path.join(tmp, "fleet_boot.log")
        if not stream_rc_ok(
                run_cli(["stream", *stream_base, "-a", boot_acq], env,
                        fleet_log),
                fleet_log, "fleet bootstrap", teardown_races):
            print(tail(fleet_log), file=sys.stderr)
            return fail("fleet bootstrap failed")

        watch_args = ["watch", "-x", str(TILE_XY[0]),
                      "-y", str(TILE_XY[1]), "-n", str(N_CHIPS),
                      "--acquired-start", ACQ_START, "-i", "0.2"]
        worker_args = ["fleet", "work", "--forever", "--poll", "0.2"]
        watcher_log = os.path.join(tmp, "watcher.log")
        worker_logs = [os.path.join(tmp, f"worker{i}.log")
                       for i in range(N_WORKERS)]
        watcher = spawn_cli(watch_args, env, watcher_log)
        workers = [spawn_cli(worker_args, env, worker_logs[i])
                   for i in range(N_WORKERS)]
        qpath = queue_path(fcfg)
        chaos_db = alert_db_path(fcfg)
        fleet_state_dir = os.path.join(tmp, "fleet", "state")
        report_path = os.path.join(tmp, "fleet", "obs_report.json")
        last_ordinal = dt.to_ordinal(scenes[-1][1])
        failures = []
        killed_worker = killed_watcher = False
        best_report = None          # the max-count freshness snapshot

        def snap_report():
            """Retain the obs report with the richest freshness
            histogram: every stream job atomically rewrites the shared
            obs_report.json, so the LAST writer is racy — the poll
            keeps the best-evidence snapshot instead."""
            nonlocal best_report
            try:
                with open(report_path) as f:
                    rep = json.load(f)
            except (OSError, ValueError):
                return
            n = ((rep.get("metrics", {}).get("histograms", {})
                  .get("acquisition_to_alert_seconds") or {})
                 .get("count") or 0)
            best_n = 0 if best_report is None else (
                (best_report.get("metrics", {}).get("histograms", {})
                 .get("acquisition_to_alert_seconds") or {})
                .get("count") or 0)
            if best_report is None or n >= best_n:
                best_report = rep

        def horizons_caught_up() -> bool:
            from firebird_tpu.streamops.statestore import TileStateStore

            store = TileStateStore(fleet_state_dir)
            try:
                return all((store.peek_horizon(c) or 0) >= last_ordinal
                           for c in cids)
            except Exception:
                return False
            finally:
                store.close()

        try:
            deadline = time.time() + DEADLINE
            for k, (sid, date) in enumerate(scenes):
                land(archive, cids, full_t, chips, dt.to_ordinal(date),
                     scene=(sid, date))
                # mid-drain chaos: SIGKILL the watcher (its replacement
                # resumes from the durable scene cursor) and one worker
                # (the fleet lease re-delivers its in-flight job) with
                # scenes still arriving behind them
                if k == KILL_SCENE:
                    watcher.send_signal(signal.SIGKILL)
                    watcher.wait(timeout=30)
                    killed_watcher = True
                    workers[0].send_signal(signal.SIGKILL)
                    workers[0].wait(timeout=30)
                    killed_worker = True
                    watcher = spawn_cli(watch_args, env, watcher_log)
                    workers[0] = spawn_cli(worker_args, env,
                                           worker_logs[0])
                # pace the landings so the fleet genuinely interleaves
                # with them (a burst would collapse into one job)
                t_scene = time.time() + 1.2
                while time.time() < min(t_scene, deadline):
                    time.sleep(0.1)
                    snap_report()
            # drain: queue empty AND every chip's checkpoint horizon
            # reached the last scene (the watcher's coverage sweep may
            # still be about to re-enqueue a lagging chip, so an empty
            # queue alone is not drained)
            c = {}
            while time.time() < deadline:
                snap_report()
                q = FleetQueue(qpath)
                c = q.counts()
                q.close()
                if c.get("pending", 0) == 0 and c.get("leased", 0) == 0 \
                        and horizons_caught_up():
                    break
                time.sleep(0.25)
            else:
                failures.append(
                    f"fleet did not drain to the last scene: queue={c}, "
                    f"horizons_caught_up={horizons_caught_up()}")
            time.sleep(1.0)         # let the final jobs' reports land
            snap_report()
        finally:
            for p in [watcher, *workers]:
                if p.poll() is None:
                    p.terminate()
            for p in [watcher, *workers]:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)

        q = FleetQueue(qpath)
        counts = q.counts()
        q.close()
        if counts["dead"]:
            failures.append(f"dead-lettered jobs: {counts}")
        if not killed_worker:
            failures.append("the worker SIGKILL never fired")

        # ---- exactly-once alerts + byte-identical state --------------
        fleet_rows, fleet_n = alert_rows(chaos_db)
        if fleet_rows != serial_rows:
            failures.append(
                f"alert rowsets differ: serial {serial_n} vs fleet "
                f"{fleet_n} — alerts lost or fabricated through the "
                "SIGKILLs")
        if fleet_n != len(set(fleet_rows)):
            failures.append("duplicate (px, py, break_day) alerts in "
                            "the fleet leg")
        try:
            fleet_state = state_payloads(
                os.path.join(tmp, "fleet", "state"), cids)
        except Exception as e:
            fleet_state = {}
            failures.append(f"fleet statestore unreadable: "
                            f"{type(e).__name__}: {e}")
        state_identical = fleet_state and all(
            fleet_state.get(c) == serial_state.get(c) for c in cids)
        if not state_identical:
            diff = [c for c in cids
                    if fleet_state.get(c) != serial_state.get(c)]
            failures.append(f"packed statestore differs from the clean "
                            f"serial leg on chips {diff}")

        # ---- scene exactly-once across watcher incarnations ----------
        wdb = os.path.join(tmp, "fleet", "watcher.db")
        con = sqlite3.connect(wdb)
        try:
            n_scenes, n_ids = con.execute(
                "SELECT COUNT(*), COUNT(DISTINCT scene_id) FROM scenes"
            ).fetchone()
        finally:
            con.close()
        if n_scenes != N_SCENES or n_ids != N_SCENES:
            failures.append(
                f"watcher cursor saw {n_scenes} scenes ({n_ids} "
                f"distinct), expected {N_SCENES} exactly once across "
                "both incarnations")

        # ---- freshness: the SLO leg over acquisition_to_alert ---------
        snap_report()
        fresh = p95 = None
        slo = {}
        hist = {}
        if best_report is None:
            failures.append("no readable obs_report.json")
        else:
            slo = best_report.get("slo") or {}
            fresh = next((o for o in slo.get("objectives", ())
                          if o["name"] == "alert_freshness"), None)
            hist = (best_report.get("metrics", {}).get("histograms", {})
                    .get("acquisition_to_alert_seconds") or {})
            p95 = hist.get("p95")
        if fresh is None or fresh.get("value_sec") is None:
            failures.append(f"alert_freshness not evaluated: {fresh}")
        elif fresh.get("metric") != "acquisition_to_alert_seconds":
            failures.append(
                "alert_freshness judged the stream-local leg, not the "
                f"end-to-end histogram: {fresh}")
        if not hist.get("count"):
            failures.append("acquisition_to_alert_seconds recorded no "
                            "observations — the publish timestamp never "
                            "reached the stream driver")

        logs = (serial_log, fleet_log, watcher_log, *worker_logs)
        if failures:
            return dump_failure(failures, logs)

        report = {
            "schema": "firebird-streamfleet-soak/1",
            "chips": N_CHIPS,
            "scenes": N_SCENES,
            "workers": N_WORKERS,
            "alerts": fleet_n,
            "duplicates": 0,
            "lost": 0,
            "watcher_sigkilled_and_resumed": killed_watcher,
            "worker_sigkilled_and_redelivered": killed_worker,
            "statestore_byte_identical": bool(state_identical),
            "queue_after": counts,
            "acquisition_to_alert_p95": p95,
            "acquisition_to_alert_count": hist.get("count"),
            "slo": {"spec": slo.get("spec"), "ok": slo.get("ok"),
                    "alert_freshness": fresh},
            # post-completion PJRT teardown aborts tolerated (rc + log
            # evidence) — empty on a clean run
            "teardown_races": teardown_races,
            "wall_seconds": round(time.time() - t0, 1),
        }
        art_dir = env_knob("FIREBIRD_STREAMFLEET_DIR")
        os.makedirs(art_dir, exist_ok=True)
        art = os.path.join(art_dir, "stream_fleet_soak.json")
        with open(art, "w") as f:
            json.dump(report, f, indent=1)
        print("streamfleet-smoke OK: "
              f"{N_SCENES} scenes -> {fleet_n} alerts exactly-once "
              "through watcher+worker SIGKILLs; packed state "
              "byte-identical to the serial leg; "
              f"acquisition_to_alert p95 {p95}s "
              f"(target {fresh['target_sec']}s, ok={fresh['ok']}) in "
              f"{report['wall_seconds']}s; artifact {art}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
