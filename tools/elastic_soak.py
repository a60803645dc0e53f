"""Elastic fleet soak (``make elastic-smoke``): a full 726-tile CONUS
drain under kill/partition/supervisor-restart chaos, at 10x the worker
count of any prior soak.

The reference claims "runs on 2000 cores as easily as it runs on 1"
(PAPER.md); this drill is our equivalent claim made falsifiable.  Two
legs over the SAME 726-tile CONUS enumeration (33x22 tiles, one
tiny-sensor synthetic chip per tile — FIREBIRD_SYNTH_SENSOR keeps
every production code path while the math stays smoke-sized):

clean
    One in-process worker drains the whole plan serially — the
    reference store and the shared-XLA-cache warmer.
chaos
    A fresh store + queue with the same plan, drained by a SUPERVISED
    elastic fleet (``firebird fleet supervise --min 0 --max 30
    --until-drained``) under adversity:

    - **SIGKILLs**: random live workers killed mid-drain (their leases
      expire and re-deliver; enough of them trips the crash-loop
      circuit and parks a slot);
    - **partition**: a zombie worker with every heartbeat dropped
      (``FIREBIRD_FAULTS=lease:p=1``), a 0.5 s lease, and no compile
      cache — every job it claims expires mid-flight and its late
      writes MUST hit the fence;
    - **supervisor death**: the supervisor itself is SIGKILLed
      mid-drain and restarted — the successor must ADOPT the orphaned
      live workers from the queue's worker registry (never
      double-spawning past the ceiling).

    Asserts: every job ends ``done``, stale-fence WRITE rejections are
    nonzero with ZERO accepted (the merged store is row-identical to
    the clean leg), the fleet actually scaled (peak live workers >= 24
    on a max of 30 — 10x the 3-worker PR 9 soak), the successor
    supervisor adopted orphans, and after the drain the fleet scaled
    back TO ZERO (empty worker registry, target 0).

Writes ``elastic_soak.json`` (scale-decision log included) under
FIREBIRD_ELASTIC_DIR; bench.py folds it via ``_elastic_fold``.
Exits non-zero on any violation.
"""

import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)

from firebird_tpu.config import env_knob  # noqa: E402

ACQ = "1995-01-01/1997-06-01"
TILES_W, TILES_H = 33, 22          # 33 * 22 = 726: the CONUS tile count
MAX_WORKERS = 30                   # 10x the PR 9 fleet-chaos soak's 3
PEAK_FLOOR = 24                    # scale proof: peak live must reach this
KILLS_BEFORE_RESTART = 2
KILLS_AFTER_RESTART = 3            # trips the crash-loop circuit (limit 3)
LEASE_SEC = "4"
DEADLINE = 540.0


def conus_tiles() -> list[tuple[float, float]]:
    """One in-tile point per tile of a 33x22 (=726) tile enumeration —
    the reference deploy loop's conus.csv, computed from the grid."""
    from firebird_tpu import grid

    h0, v0 = grid.grid_pt(100.0, 200.0, grid.CONUS.tile)
    out = []
    for v in range(v0, v0 + TILES_H):
        for h in range(h0, h0 + TILES_W):
            tx, ty = grid.proj_pt(h, v, grid.CONUS.tile)
            out.append((tx + 1.0, ty - 1.0))
    return out


def store_rows(store) -> dict:
    """Canonical row-set per table (the fleet_chaos.py comparison)."""
    out = {}
    for table in ("chip", "pixel", "segment"):
        frame = store.read(table)
        cols = sorted(frame)
        n = len(frame[cols[0]]) if cols else 0
        out[table] = sorted(
            json.dumps([(c, frame[c][i]) for c in cols], sort_keys=True)
            for i in range(n))
    return out


def base_env(tmp: str, leg: str) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": HERE + os.pathsep + env.get("PYTHONPATH", ""),
        "FIREBIRD_STORE_BACKEND": "sqlite",
        "FIREBIRD_STORE_PATH": os.path.join(tmp, leg, "elastic.db"),
        "FIREBIRD_SOURCE": "synthetic",
        "FIREBIRD_SYNTH_SENSOR": "landsat-ard-tiny",
        "FIREBIRD_FLEET_DB": os.path.join(tmp, leg, "queue.db"),
        "FIREBIRD_FLEET_LEASE_SEC": LEASE_SEC,
        "FIREBIRD_FLEET_MAX_ATTEMPTS": "30",
        "FIREBIRD_FLEET_MIN_WORKERS": "0",
        "FIREBIRD_FLEET_MAX_WORKERS": str(MAX_WORKERS),
        "FIREBIRD_FLEET_GRACE_SEC": "20",
        "FIREBIRD_CHIPS_PER_BATCH": "1",
        "FIREBIRD_DEVICE_SHARDING": "off",
        "FIREBIRD_DTYPE": "float64",
        # One shared XLA cache: the clean leg's compiles warm every
        # chaos-leg worker subprocess (the zombie deliberately forgoes
        # it so its first job outlives its 0.5 s lease on any host).
        "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    env.pop("FIREBIRD_FAULTS", None)
    return env


def spawn_supervisor(env: dict, log_path: str):
    logf = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "firebird_tpu.cli", "fleet", "supervise",
         "--until-drained", "--tick", "0.5"],
        env=env, cwd=HERE, stdout=logf, stderr=subprocess.STDOUT)
    proc._fb_log = logf
    return proc


def spawn_zombie(env: dict, log_path: str):
    e = dict(env)
    e.update({"FIREBIRD_FAULTS": "lease:p=1",
              "FIREBIRD_FLEET_LEASE_SEC": "0.5",
              # an empty cache of its own: the zombie compiles cold
              "JAX_COMPILATION_CACHE_DIR": os.path.join(
                  os.path.dirname(log_path), "zombie_cache")})
    logf = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "firebird_tpu.cli", "fleet", "work",
         "--until-drained", "--drain-on-term", "--poll", "0.25"],
        env=e, cwd=HERE, stdout=logf, stderr=subprocess.STDOUT)
    proc._fb_log = logf
    return proc


def live_worker_pids(queue) -> list[int]:
    pids = []
    for row in queue.workers(kind="batch"):
        try:
            os.kill(int(row["pid"]), 0)
        except OSError:
            continue
        pids.append(int(row["pid"]))
    return pids


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path) as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "<no log>"


def main() -> int:
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core as dcore
    from firebird_tpu.driver import quarantine as qlib
    from firebird_tpu.fleet import (FleetQueue, FleetWorker,
                                    enqueue_tile_plan, make_queue)
    from firebird_tpu.store import SqliteStore

    rng = random.Random(0xE1A5)
    tiles = conus_tiles()
    with tempfile.TemporaryDirectory(prefix="fb_elastic_") as tmp:
        # ---- clean leg: one in-process worker, serially --------------
        env = base_env(tmp, "clean")
        os.makedirs(os.path.join(tmp, "clean"), exist_ok=True)
        cfg = Config.from_env(env=env)
        # the in-process leg shares the subprocesses' cache
        os.environ["JAX_COMPILATION_CACHE_DIR"] = \
            env["JAX_COMPILATION_CACHE_DIR"]
        dcore.setup_compile_cache()
        queue = make_queue(cfg)
        t0 = time.time()
        plan = enqueue_tile_plan(queue, tiles, acquired=ACQ, number=1,
                                 chunk_size=1,
                                 max_attempts=cfg.fleet_max_attempts)
        n_jobs = plan["jobs"]
        summary = FleetWorker(cfg, queue).run(until_drained=True)
        clean_wall = time.time() - t0
        counts = queue.counts()
        queue.close()
        if n_jobs != 726 or summary["acked"] != n_jobs \
                or counts["done"] != n_jobs:
            print(f"elastic-smoke: clean leg acked {summary['acked']}/"
                  f"{n_jobs} jobs (queue {counts})", file=sys.stderr)
            return 1
        clean = store_rows(SqliteStore(cfg.store_path, cfg.keyspace()))
        print(f"elastic-smoke: clean leg drained {n_jobs} jobs in "
              f"{clean_wall:.1f}s")

        # ---- chaos leg: supervised elastic fleet under adversity -----
        env = base_env(tmp, "chaos")
        os.makedirs(os.path.join(tmp, "chaos"), exist_ok=True)
        cfg = Config.from_env(env=env)
        queue = make_queue(cfg)
        enqueue_tile_plan(queue, tiles, acquired=ACQ, number=1,
                          chunk_size=1, max_attempts=cfg.fleet_max_attempts)
        t0 = time.time()
        deadline = t0 + DEADLINE
        peak_live = 0
        killed = []
        sup_logs = [os.path.join(tmp, "supervisor_1.log"),
                    os.path.join(tmp, "supervisor_2.log")]
        procs = []
        try:
            sup1 = spawn_supervisor(env, sup_logs[0])
            procs.append(sup1)
            zombie = spawn_zombie(env, os.path.join(tmp, "zombie.log"))
            procs.append(zombie)

            # Wait for the fleet to actually scale: peak live workers
            # must reach the 10x floor before any chaos is injected.
            while time.time() < deadline:
                pids = live_worker_pids(queue)
                peak_live = max(peak_live, len(pids))
                if peak_live >= PEAK_FLOOR:
                    break
                if sup1.poll() is not None:
                    print("elastic-smoke: supervisor exited before the "
                          f"fleet scaled (peak {peak_live})\n"
                          f"{tail(sup_logs[0])}", file=sys.stderr)
                    return 1
                time.sleep(0.25)
            if peak_live < PEAK_FLOOR:
                print(f"elastic-smoke: fleet never reached {PEAK_FLOOR} "
                      f"live workers (peak {peak_live})", file=sys.stderr)
                return 1

            # SIGKILL random workers while supervisor 1 watches.
            for pid in rng.sample(live_worker_pids(queue),
                                  KILLS_BEFORE_RESTART):
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)

            # Kill the supervisor itself; its workers are orphans now.
            sup1.send_signal(signal.SIGKILL)
            sup1.wait(timeout=30)
            orphans = live_worker_pids(queue)
            if not orphans:
                print("elastic-smoke: no orphaned workers survived the "
                      "supervisor kill", file=sys.stderr)
                return 1

            # The successor must adopt those orphans, not double-spawn.
            sup2 = spawn_supervisor(env, sup_logs[1])
            procs.append(sup2)
            adopted = 0
            while time.time() < deadline:
                st = queue.supervisor_state() or {}
                if st.get("pid") == sup2.pid:
                    adopted = int(st.get("adopted_total") or 0)
                    if adopted > 0:
                        break
                if sup2.poll() is not None:
                    break
                time.sleep(0.25)
            pids = live_worker_pids(queue)
            peak_live = max(peak_live, len(pids))
            if len(pids) > MAX_WORKERS + 1:      # +1: our zombie
                print(f"elastic-smoke: {len(pids)} live workers after "
                      f"restart — the successor double-spawned past the "
                      f"{MAX_WORKERS} ceiling", file=sys.stderr)
                return 1

            # More kills under supervisor 2: three abnormal exits in
            # one window trip the crash-loop circuit (a parked slot).
            alive = live_worker_pids(queue)
            for pid in rng.sample(alive,
                                  min(KILLS_AFTER_RESTART, len(alive))):
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)

            # Wait for the drain + scale-to-zero exit, reaping the
            # zombie as we go (an unreaped defunct child would read as
            # an immortal adopted worker without the /proc guard —
            # keeping it reaped exercises the normal path too).
            while time.time() < deadline:
                zombie.poll()
                if sup2.poll() is not None:
                    break
                time.sleep(0.5)
            if sup2.poll() is None:
                print(f"elastic-smoke: supervisor 2 still running after "
                      f"{DEADLINE:.0f}s\n--- supervisor 2 log ---\n"
                      f"{tail(sup_logs[1])}", file=sys.stderr)
                return 1
            try:
                zombie.wait(timeout=max(deadline - time.time(), 1.0))
            except subprocess.TimeoutExpired:
                zombie.kill()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p._fb_log.close()
            # Belt and braces: no stray workers may outlive the soak.
            for pid in live_worker_pids(queue):
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass

        wall = time.time() - t0
        counts = queue.counts()
        status = queue.status()
        sup_state = queue.supervisor_state() or {}
        workers_left = queue.workers()
        rejects_write = queue.fence_rejects("write")
        rejects_total = queue.fence_rejects()
        queue.close()

        failures = []
        if counts["done"] != n_jobs or counts["dead"] \
                or counts["pending"] or counts["leased"]:
            failures.append(f"queue not cleanly drained: {counts} "
                            f"(dead: {status['dead']})")
        if rejects_write <= 0:
            failures.append(
                "no stale-fence WRITE rejections — the partitioned "
                f"zombie never hit the fence (total {rejects_total}: "
                f"{status['fence_rejects_by_op']})")
        chaos = store_rows(SqliteStore(cfg.store_path, cfg.keyspace()))
        for table in ("chip", "pixel", "segment"):
            if clean[table] != chaos[table]:
                failures.append(
                    f"{table} rows differ: clean {len(clean[table])} vs "
                    f"chaos {len(chaos[table])} — a stale write was "
                    "accepted or work was lost")
        if sup2.returncode != 0:
            failures.append(
                f"supervisor 2 exit {sup2.returncode}, expected 0\n"
                f"{tail(sup_logs[1])}")
        # The mid-run poll can lose the race with a fast drain (sup2
        # exits before a 0.25s poll sees adopted_total > 0); the final
        # persisted heartbeat is authoritative.
        if sup_state.get("pid") == sup2.pid:
            adopted = max(adopted,
                          int(sup_state.get("adopted_total") or 0))
        if adopted < 1:
            failures.append("successor supervisor adopted no orphans "
                            f"(state: {sup_state})")
        if workers_left:
            failures.append(
                f"worker registry not empty after drain: {workers_left}")
        if sup_state.get("target") != 0 or sup_state.get("live") != 0:
            failures.append(
                "fleet did not scale to zero: final supervisor state "
                f"target={sup_state.get('target')} "
                f"live={sup_state.get('live')}")
        qpath = qlib.quarantine_path(cfg)
        if qpath and os.path.exists(qpath):
            with open(qpath) as f:
                qchips = json.load(f).get("chips", {})
            if qchips:
                failures.append(
                    f"unexpected quarantine entries: {sorted(qchips)}")
        if failures:
            for f_ in failures:
                print(f"elastic-smoke: {f_}", file=sys.stderr)
            print(f"--- supervisor 2 log ---\n{tail(sup_logs[1])}",
                  file=sys.stderr)
            return 1

        report = {
            "schema": "firebird-elastic-soak/1",
            "tiles": len(tiles),
            "jobs": n_jobs,
            "max_workers": MAX_WORKERS,
            "peak_live_workers": peak_live,
            "workers_killed": len(killed),
            "partitioned": 1,
            "supervisor_restarts": 1,
            "adopted": adopted,
            "parks": int((sup_state.get("tallies") or {})
                         .get("parked", 0)),
            "fence_rejects": rejects_total,
            "fence_rejects_by_op": status["fence_rejects_by_op"],
            "stale_writes_accepted": 0,
            "scaled_to_zero": True,
            "queue": counts,
            "rows": {t: len(clean[t]) for t in clean},
            "store_identical": True,
            "clean_wall_seconds": round(clean_wall, 1),
            "wall_seconds": round(wall, 1),
            "supervisor": {k: sup_state.get(k) for k in
                           ("target", "live", "min", "max",
                            "adopted_total", "tallies")},
            # The scale-decision log: every target change the surviving
            # supervisor made, with its reason — folded into bench
            # round artifacts by _elastic_fold.
            "decisions": sup_state.get("decisions") or [],
        }
        art_dir = env_knob("FIREBIRD_ELASTIC_DIR")
        os.makedirs(art_dir, exist_ok=True)
        art = os.path.join(art_dir, "elastic_soak.json")
        with open(art, "w") as f:
            json.dump(report, f, indent=1)
        print("elastic-smoke OK: "
              f"{n_jobs} jobs over {len(tiles)} CONUS tiles drained by "
              f"an elastic fleet (peak {peak_live}/{MAX_WORKERS} "
              f"workers) through {len(killed)} SIGKILLs + 1 partition + "
              f"1 supervisor restart ({adopted} orphans adopted); "
              f"{rejects_write} stale writes rejected, 0 accepted; "
              f"store identical ({sum(report['rows'].values())} rows); "
              f"scaled to zero in {report['wall_seconds']}s; "
              f"artifact {art}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
