"""Alert-loop chaos soak (``make alert-smoke``): alerting survives death.

The end-to-end proof behind docs/ALERTS.md: a streaming run whose tail
breaks MUST surface every break on the alert feed exactly once — under
injected ingest faults and a SIGKILL mid-stream — and the flagged
pixels must schedule and drain their own cold-path repair.

Legs, over a file-source archive whose every pixel steps +800 after the
bootstrap horizon (so the update pass confirms a break on every
standard pixel):

clean
    Bootstrap + update to completion; its alert rowset is the
    reference.
chaos
    A fresh store/state/alert-db tree: bootstrap, then the update run
    under an ingest fault plan.  The parent polls the alert db and
    SIGKILLs the run the moment the first chip's alerts land —
    mid-stream, chips still pending.  The same command re-runs to
    completion (stream checkpoints ARE the resume).

Every JAX leg is a SUBPROCESS (`firebird stream` / `firebird fleet
work`) and the parent stays JAX-free — forking workers from a parent
with live XLA threads is how you get glibc heap corruption instead of
a chaos drill.

Asserts:

- **zero lost alerts**: the chaos alert rowset equals the clean one —
  the kill window (alert committed, checkpoint not yet saved) re-emits
  on resume and dedup absorbs it; the reverse order would lose alerts;
- **zero duplicates**: (px, py, break_day) is unique across the chaos
  log (count == distinct) despite the resume re-applying a delta;
- **webhook catch-up**: a registered subscriber receives every record
  exactly once across TWO deliverer incarnations — the first delivers
  partially and dies, the second resumes from the durable cursor;
- **sharded fanout catch-up**: the fanout plane's half of the same
  proof — on a copy of the chaos log widened to span MULTIPLE quadkey
  shards, a FanoutDeliverer incarnation dies mid-shard and a second
  one re-drains every shard job; the per-(subscriber, shard) cursors
  must compose to exactly-once records with zero duplicate POSTs;
- **repair**: the update runs enqueued exactly one repair job per
  broken chip (idempotent across the kill + resume), a fleet worker
  drains them, the reseeded checkpoints clear needs_batch, and a
  post-repair stream update emits nothing new;
- **freshness SLO**: the resume run's obs_report.json evaluates the
  ``alert_freshness`` objective against real alert_visible_seconds
  observations.

Writes ``alert_soak.json`` under FIREBIRD_ALERT_DIR (folded into bench
artifacts by bench.py's ``_alert_fold``) and exits non-zero on any
violation.
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

ACQ_BOOT = "1995-01-01/1998-12-31"
ACQ_FULL = "1995-01-01/2000-12-31"
CHANGE_DATE = "1999-06-01"
N_CHIPS = 3
TILE_XY = (100.0, 200.0)
DEADLINE = 540.0


def fail(msg: str) -> int:
    print(f"alert-smoke: {msg}", file=sys.stderr)
    return 1


def dump_failure(failures, logs) -> int:
    """Report violations and preserve the leg logs under the artifact
    dir (the temp tree is gone by the time anyone reads the failure)."""
    import shutil

    keep = os.path.join(env_knob("FIREBIRD_ALERT_DIR"), "failure_logs")
    os.makedirs(keep, exist_ok=True)
    for f_ in failures:
        print(f"alert-smoke: {f_}", file=sys.stderr)
    for p in logs:
        try:
            shutil.copy(p, keep)
        except OSError:
            continue
        print(f"--- {os.path.basename(p)} (kept in {keep}) ---\n"
              f"{tail(p, 8000)}", file=sys.stderr)
    return 1


def build_archive(outdir: str, cids) -> None:
    """A FileSource archive: every pixel of every chip steps +800 on all
    bands at CHANGE_DATE (after the bootstrap horizon)."""
    import numpy as np

    from firebird_tpu.ccd import synthetic
    from firebird_tpu.ingest.packer import ChipData
    from firebird_tpu.ingest.sources import FileSource
    from firebird_tpu.utils import dates as dt

    os.makedirs(outdir, exist_ok=True)
    fs = FileSource(outdir)
    t = synthetic.acquisition_dates("1995-01-01", "2001-01-01", 16)
    rng = np.random.default_rng(11)
    base = synthetic.harmonic_series(t, rng)                     # [7, T]
    for cx, cy in cids:
        noise = rng.normal(0.0, 10.0, (7, t.shape[0], 100, 100))
        spectra = base[:, :, None, None] + noise
        spectra[:, t >= dt.to_ordinal(CHANGE_DATE)] += 800.0
        fs.save_chip(ChipData(
            cx=int(cx), cy=int(cy), dates=t,
            spectra=np.clip(spectra, -32768, 32767).astype(np.int16),
            qas=np.full((t.shape[0], 100, 100), synthetic.QA_CLEAR,
                        np.uint16)))


def leg_env(tmp: str, leg: str) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONFAULTHANDLER": "1",   # a native crash leaves a traceback
        "PYTHONPATH": HERE + os.pathsep + env.get("PYTHONPATH", ""),
        "FIREBIRD_STORE_BACKEND": "sqlite",
        "FIREBIRD_STORE_PATH": os.path.join(tmp, leg, "soak.db"),
        "FIREBIRD_STREAM_DIR": os.path.join(tmp, leg, "state"),
        "FIREBIRD_SOURCE": "file",
        "FIREBIRD_SOURCE_PATH": os.path.join(tmp, "archive"),
        "FIREBIRD_CHIPS_PER_BATCH": "1",
        "FIREBIRD_DEVICE_SHARDING": "off",
        "FIREBIRD_SLO": "alert_freshness=120",
        # One shared XLA cache: the first leg's compiles warm every
        # later subprocess.
        "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    env.pop("FIREBIRD_FAULTS", None)
    env.pop("FIREBIRD_ALERT_DB", None)
    env.pop("FIREBIRD_FLEET_DB", None)
    return env


def run_cli(args: list, env: dict, log_path: str, *,
            timeout: float = DEADLINE) -> int:
    cmd = [sys.executable, "-m", "firebird_tpu.cli", *args]
    with open(log_path, "a") as logf:
        return subprocess.run(cmd, env=env, cwd=HERE, stdout=logf,
                              stderr=subprocess.STDOUT,
                              timeout=timeout).returncode


def stream_args(acquired: str) -> list:
    return ["stream", "-x", str(TILE_XY[0]), "-y", str(TILE_XY[1]),
            "-n", str(N_CHIPS), "-a", acquired]


def alert_rows(path: str):
    """Canonical (px, py, break_day) rowset + total count."""
    con = sqlite3.connect(path)
    try:
        rows = con.execute(
            "SELECT px, py, break_day FROM alerts").fetchall()
    finally:
        con.close()
    return sorted(rows), len(rows)


def flagged_pixels(state_dir: str, cids) -> int:
    """needs_batch pixels summed straight from the packed checkpoint
    slots (no jax in the parent — statestore.peek_arrays is the
    JAX-free read path; break_day > 0 IS the flag)."""
    from firebird_tpu.streamops.statestore import TileStateStore

    store = TileStateStore(state_dir)
    try:
        return sum(int((store.peek_arrays((cx, cy))["break_day"] > 0)
                       .sum()) for cx, cy in cids)
    finally:
        store.close()


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


class Receiver:
    """A local webhook endpoint recording every delivered alert id."""

    def __init__(self):
        import http.server

        self.ids: list[int] = []
        self.batches = 0
        outer = self

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                doc = json.loads(self.rfile.read(n))
                outer.ids.extend(a["id"] for a in doc["alerts"])
                outer.batches += 1
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def log_message(self, *a):
                pass

        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}/hook"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def main() -> int:  # noqa: C901 (one linear drill, read top to bottom)
    from firebird_tpu import grid
    from firebird_tpu.alerts import AlertLog, WebhookDeliverer, \
        alert_db_path
    from firebird_tpu.config import Config
    from firebird_tpu.fleet.queue import FleetQueue, queue_path
    from firebird_tpu.utils.fn import take

    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="fb_alert_soak_") as tmp:
        tile = grid.tile(x=TILE_XY[0], y=TILE_XY[1])
        cids = [tuple(int(v) for v in c)
                for c in take(N_CHIPS, grid.chips(tile))]
        build_archive(os.path.join(tmp, "archive"), cids)

        # ---- clean leg: the reference alert rowset -------------------
        env = leg_env(tmp, "clean")
        os.makedirs(os.path.join(tmp, "clean"), exist_ok=True)
        cfg = Config.from_env(env=env)
        clean_log = os.path.join(tmp, "clean.log")
        for acq in (ACQ_BOOT, ACQ_FULL):
            rc = run_cli(stream_args(acq), env, clean_log)
            if rc != 0:
                print(tail(clean_log), file=sys.stderr)
                return fail(f"clean stream over {acq} exited {rc}")
        clean_rows, clean_n = alert_rows(alert_db_path(cfg))
        if clean_n < 9000:
            return fail(f"clean leg logged only {clean_n} alerts — the "
                        "step change did not break the tile")
        q = FleetQueue(queue_path(cfg))
        clean_pending = q.counts()["pending"]
        q.close()
        if clean_pending != N_CHIPS:
            return fail(f"clean leg enqueued {clean_pending} repair jobs, "
                        f"expected {N_CHIPS}")

        # ---- chaos leg: faults + SIGKILL mid-stream ------------------
        env = leg_env(tmp, "chaos")
        os.makedirs(os.path.join(tmp, "chaos"), exist_ok=True)
        ccfg = Config.from_env(env=env)
        chaos_log = os.path.join(tmp, "chaos.log")
        rc = run_cli(stream_args(ACQ_BOOT), env, chaos_log)
        if rc != 0:
            print(tail(chaos_log), file=sys.stderr)
            return fail(f"chaos bootstrap exited {rc}")
        chaos_db = alert_db_path(ccfg)

        # p low enough that a chip exhausting its retries (which would
        # legitimately change the alert rowset) is vanishingly unlikely,
        # high enough that retries demonstrably fire during the leg.
        env_kill = dict(env, FIREBIRD_FAULTS="ingest:p=0.1,seed=3")
        # The victim gets a THROWAWAY compile cache: a SIGKILL mid-write
        # can truncate a cache entry, and a successor deserializing it
        # dies to a segfault inside XLA — the victim's corruption must
        # be as disposable as the victim.
        victim_env = dict(env_kill, JAX_COMPILATION_CACHE_DIR=os.path.join(
            tmp, "victim_cache"))
        victim_log = os.path.join(tmp, "victim.log")
        with open(victim_log, "w") as logf:
            proc = subprocess.Popen(
                [sys.executable, "-m", "firebird_tpu.cli",
                 *stream_args(ACQ_FULL)],
                env=victim_env, cwd=HERE, stdout=logf,
                stderr=subprocess.STDOUT)
            deadline = time.time() + DEADLINE
            seen = 0
            while time.time() < deadline and proc.poll() is None:
                try:
                    _, seen = alert_rows(chaos_db)
                except sqlite3.Error:
                    seen = 0
                if seen:
                    break
                time.sleep(0.05)
            if not seen:
                proc.kill()
                proc.wait(timeout=30)
                print(tail(victim_log), file=sys.stderr)
                return fail("no alert landed before the deadline (victim "
                            f"exited {proc.returncode})")
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        if proc.returncode != -signal.SIGKILL:
            return fail(f"victim exit {proc.returncode}, expected -9")
        _, killed_n = alert_rows(chaos_db)
        if killed_n <= 0:
            return fail("alerts did not survive the SIGKILL")
        if killed_n >= clean_n:
            return fail(f"SIGKILL landed after the whole tile finished "
                        f"({killed_n}/{clean_n} alerts) — the kill "
                        "window proved nothing")

        # Resume: the same command re-runs to completion (stream
        # checkpoints are the resume; the fault plan stays on).
        resume_log = os.path.join(tmp, "resume.log")
        rc = run_cli(stream_args(ACQ_FULL), env_kill, resume_log)
        if rc != 0:
            dump_failure([f"resume run exited {rc}"],
                         (victim_log, resume_log))
            return 1

        failures = []
        # Snapshot the RESUME run's report now — the post-repair stream
        # below overwrites obs_report.json in the same store dir.
        report_path = os.path.join(tmp, "chaos", "obs_report.json")
        slo = {}
        try:
            with open(report_path) as f:
                slo = json.load(f).get("slo") or {}
        except (OSError, ValueError) as e:
            failures.append(f"no readable obs_report.json: {e}")
        chaos_rows, chaos_n = alert_rows(chaos_db)
        if chaos_rows != clean_rows:
            failures.append(
                f"alert rowsets differ: clean {clean_n} vs chaos "
                f"{chaos_n} — alerts were lost or fabricated")
        if chaos_n != len(set(chaos_rows)):
            failures.append("duplicate (px, py, break_day) records "
                            "survived the resume")

        # Copy the chaos log NOW — before the flat subscriber below
        # registers — so the sharded fanout leg further down starts
        # from the same alert rowset but a clean subscriber table.
        import shutil

        fanout_db = os.path.join(tmp, "fanout_alerts.db")
        shutil.copyfile(chaos_db, fanout_db)

        # ---- webhook catch-up across deliverer incarnations ----------
        recv = Receiver()
        alog = AlertLog(chaos_db)
        batch_n = max(chaos_n // 4, 1)
        try:
            alog.subscribe(recv.url)
            part = WebhookDeliverer(alog, ccfg).deliver_once(
                batch=batch_n, max_batches=1)
            # Incarnation 1 "dies" here; incarnation 2 resumes from the
            # durable cursor and must deliver exactly the remainder.
            d2 = WebhookDeliverer(alog, ccfg)
            while d2.deliver_once(batch=batch_n):
                pass
            subs = alog.subscribers()
        finally:
            alog.close()
            recv.close()
        if part <= 0 or part >= chaos_n:
            failures.append(f"first deliverer incarnation delivered "
                            f"{part}/{chaos_n} — no catch-up to prove")
        if sorted(recv.ids) != sorted(set(recv.ids)) \
                or len(recv.ids) != chaos_n:
            failures.append(
                f"webhook received {len(recv.ids)} records "
                f"({len(set(recv.ids))} distinct), expected {chaos_n} "
                "exactly once")
        if subs and (subs[0]["lag"] != 0 or subs[0]["failures"] != 0):
            failures.append(f"subscriber did not catch up: {subs[0]}")

        # ---- sharded fanout catch-up across deliverer incarnations ---
        # Same exactly-once proof through the fanout plane: the copied
        # chaos log is widened with a burst at a far tile so the rollup
        # spans MULTIPLE shards (the soak tile's chips share one
        # quadkey prefix), then a FanoutDeliverer incarnation dies
        # mid-shard and a fresh one re-drains every shard job from the
        # durable per-(subscriber, shard) cursors.
        from firebird_tpu.alerts import FanoutDeliverer
        from firebird_tpu.alerts import subindex
        from firebird_tpu.alerts.feed import _default_post
        from firebird_tpu.serve import pyramid as pyr

        ext = pyr.tile_extent(subindex.Z_BASE, 1500, 300)
        far = [{"cx": 1500, "cy": 300, "px": ext["ulx"] + 1.0,
                "py": ext["uly"] - 1.0, "break_day": 730000 + i}
               for i in range(40)]
        falog = AlertLog(fanout_db)
        recv2 = Receiver()
        try:
            falog.append(far)
            fan_sub = falog.subscribe(recv2.url)
            shards = falog.shards_since(0, ccfg.fanout_shard_prefix)
            con = sqlite3.connect(fanout_db)
            try:
                fan_ids = sorted(r[0] for r in con.execute(
                    "SELECT id FROM alerts"))
            finally:
                con.close()
            # Incarnation 1's post budget runs out mid-FIRST-shard —
            # the stand-in for a SIGKILLed fanout worker (the loadtest
            # kills a real one; here the parent must stay in-process).
            # Sized one POST short of the first shard so no shard ever
            # completes cleanly under it: every shard's cursor row is
            # left pinned, and incarnation 2's re-drain resumes each
            # one mid-stream instead of re-POSTing a retired shard.
            count0 = int(shards[0]["count"]) if shards else 1
            fan_batch = max(1, count0 // 3)
            needed0 = -(-count0 // fan_batch)        # ceil division
            budget = {"left": max(1, needed0 - 1)}

            def dying_post(url, body, timeout):
                if budget["left"] <= 0:
                    raise RuntimeError("incarnation 1 died mid-shard")
                budget["left"] -= 1
                return _default_post(url, body, timeout)

            d1 = FanoutDeliverer(falog, ccfg, post=dying_post,
                                 sleep=lambda s: None)
            part_fan = sum(d1.drain_shard(s["shard"], s["upto"],
                                          batch=fan_batch)
                           for s in shards)
            # The durable mid-stream state incarnation 2 resumes from:
            # a pinned cursor row part-way through the first shard.
            mid_cursor = falog.fanout_cursor(fan_sub,
                                             shards[0]["shard"]) \
                if shards else 0
            d2 = FanoutDeliverer(falog, ccfg)
            rest_fan = sum(d2.drain_shard(s["shard"], s["upto"],
                                          batch=fan_batch)
                           for s in shards)
            fan_cursors = {s["shard"]: falog.fanout_cursor(fan_sub,
                                                           s["shard"])
                           for s in shards}
            fan_state = falog.shard_subscribers(shards[0]["shard"])[0]
        finally:
            falog.close()
            recv2.close()
        if len(shards) < 2:
            failures.append(f"fanout leg rolled up {len(shards)} shard "
                            "(expected >= 2) — nothing sharded to prove")
        if part_fan <= 0 or part_fan >= len(fan_ids):
            failures.append(f"first fanout incarnation delivered "
                            f"{part_fan}/{len(fan_ids)} — no shard "
                            "catch-up to prove")
        if sorted(recv2.ids) != fan_ids:
            failures.append(
                f"fanout delivered {len(recv2.ids)} records "
                f"({len(set(recv2.ids))} distinct), expected "
                f"{len(fan_ids)} exactly once across incarnations")
        if shards and not (0 < mid_cursor < int(shards[0]["upto"])):
            failures.append(
                f"no durable mid-shard cursor after incarnation 1 "
                f"(got {mid_cursor}, shard upto "
                f"{shards[0]['upto']}) — nothing resumed from")
        # Clean completion RETIRES the catch-up row (no row reads as
        # cursor 0): any surviving nonzero cursor means a shard never
        # finished.
        bad_cursors = {sh: c for sh, c in fan_cursors.items() if c}
        if bad_cursors:
            failures.append("fanout catch-up rows not retired after "
                            f"the second incarnation: {bad_cursors}")
        if fan_state["failures"] != 0 or fan_state["parked_until"]:
            failures.append("fanout subscriber did not heal after the "
                            f"second incarnation: {fan_state}")

        # ---- repair jobs: enqueued once, drained, state repaired ------
        qpath = queue_path(ccfg)
        queue = FleetQueue(qpath)
        counts = queue.counts()
        queue.close()
        if counts["pending"] != N_CHIPS:
            failures.append(
                f"expected {N_CHIPS} pending repair jobs (one per "
                f"chip, idempotent across kill + resume), got {counts}")
        worker_log = os.path.join(tmp, "worker.log")
        rc = run_cli(["fleet", "work", "--until-drained", "--poll",
                      "0.25"], env, worker_log)
        if rc != 0:
            print(tail(worker_log), file=sys.stderr)
            failures.append(f"fleet worker exited {rc}")
        queue = FleetQueue(qpath)
        counts = queue.counts()
        open_after = queue.open_jobs("repair")
        queue.close()
        acked = counts["done"]
        if acked < N_CHIPS or counts["pending"] or counts["leased"] \
                or counts["dead"]:
            failures.append(f"repair drain failed: queue={counts}")
        if open_after:
            failures.append(f"repair jobs still open: {open_after}")
        flagged = flagged_pixels(os.path.join(tmp, "chaos", "state"), cids)
        if flagged:
            failures.append(f"{flagged} pixels still flagged needs_batch "
                            "after repair")
        # Post-repair stream update: nothing new, nothing re-alerted,
        # nothing re-scheduled.
        rc = run_cli(stream_args(ACQ_FULL), env, resume_log)
        if rc != 0:
            failures.append(f"post-repair stream exited {rc}")
        _, post_n = alert_rows(chaos_db)
        queue = FleetQueue(qpath)
        post_counts = queue.counts()
        queue.close()
        if post_n != chaos_n:
            failures.append(f"post-repair stream re-alerted: {post_n} "
                            f"records vs {chaos_n}")
        if post_counts["pending"]:
            failures.append("post-repair stream re-enqueued repair jobs: "
                            f"{post_counts}")

        # ---- freshness SLO from the resume run's report --------------
        fresh = next((o for o in slo.get("objectives", ())
                      if o["name"] == "alert_freshness"), None)
        if fresh is None or fresh.get("value_sec") is None:
            failures.append(
                f"alert_freshness not evaluated in the resume run's "
                f"report: {slo}")

        if failures:
            return dump_failure(failures,
                               (victim_log, resume_log, worker_log))

        report = {
            "schema": "firebird-alert-soak/1",
            "chips": N_CHIPS,
            "alerts": chaos_n,
            "alerts_at_sigkill": killed_n,
            "duplicates": 0,
            "lost": 0,
            "webhook": {"delivered": len(recv.ids),
                        "first_incarnation": part,
                        "batches": recv.batches,
                        "exactly_once": True},
            "fanout": {"shards": len(shards),
                       "delivered": len(recv2.ids),
                       "first_incarnation": part_fan,
                       "second_incarnation": rest_fan,
                       "exactly_once": True},
            "repair": {"jobs": N_CHIPS,
                       "acked": acked,
                       "pixels_flagged_after": flagged},
            "slo": {"spec": slo.get("spec"), "ok": slo.get("ok"),
                    "alert_freshness": fresh},
            "wall_seconds": round(time.time() - t0, 1),
        }
        art_dir = env_knob("FIREBIRD_ALERT_DIR")
        os.makedirs(art_dir, exist_ok=True)
        art = os.path.join(art_dir, "alert_soak.json")
        with open(art, "w") as f:
            json.dump(report, f, indent=1)
        print("alert-smoke OK: "
              f"{chaos_n} alerts exactly-once through SIGKILL at "
              f"{killed_n} + resume; webhook caught up from cursor "
              f"({part} then {chaos_n - part}); fanout exactly-once "
              f"over {len(shards)} shards across incarnations "
              f"({part_fan} then {rest_fan}); {N_CHIPS} repair jobs "
              f"drained, 0 pixels flagged after; alert_freshness p95 "
              f"{fresh['value_sec']}s (target {fresh['target_sec']}s, "
              f"ok={fresh['ok']}) in {report['wall_seconds']}s; "
              f"artifact {art}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
