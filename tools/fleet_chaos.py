"""Fleet chaos (``make fleet-smoke``): worker death is boring.

The end-to-end proof behind docs/ROBUSTNESS.md "Fleet scheduling".  Two
legs over the same two-tile synthetic plan (detect jobs, chunk size 1):

clean
    ONE in-process worker drains the whole plan — the reference store
    and the baseline queue accounting.
chaos
    A fresh store + queue with the same plan, drained by worker
    subprocesses under adversity:

    - the **victim** claims a job and is SIGKILLed mid-lease;
    - the **zombie** runs with ``FIREBIRD_FAULTS=lease:p=1`` (every
      heartbeat dropped — a worker partitioned from the queue) and a
      short lease, so every job it claims expires mid-flight, gets
      re-claimed by a healthy worker, and the zombie's late writes hit
      the fence;
    - the **healthy** worker just works.

    Asserts: every job ends ``done`` (none dead, none stuck), the
    stale-fence WRITE rejection count is nonzero (the zombie really
    tried), zero stale writes were accepted (the merged store is
    **row-for-row identical** to the clean leg — a foreign row would
    break identity), and no quarantine manifest exists (fencing losses
    are not dead letters).

Writes ``fleet_chaos.json`` under FIREBIRD_FLEET_DIR (folded into bench
artifacts by bench.py) and exits non-zero on any violation.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)

from firebird_tpu.config import env_knob  # noqa: E402

ACQ = "1995-01-01/1996-06-01"
N_CHIPS = 2          # per tile
CHUNK = 1            # chips per detect job -> 4 jobs over 2 tiles
TILES = [(100.0, 200.0), (150100.0, 200.0)]   # two adjacent CONUS tiles
DEADLINE = 540.0     # whole-chaos-leg wall clock budget (seconds)


def store_rows(store) -> dict:
    """Canonical row-set per table (the chaos_soak.py comparison rule)."""
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
        "FIREBIRD_STORE_PATH": os.path.join(tmp, leg, "chaos.db"),
        "FIREBIRD_SOURCE": "synthetic",
        "FIREBIRD_FLEET_DB": os.path.join(tmp, leg, "queue.db"),
        "FIREBIRD_FLEET_LEASE_SEC": "2",
        "FIREBIRD_FLEET_MAX_ATTEMPTS": "20",
        "FIREBIRD_CHIPS_PER_BATCH": "1",
        "FIREBIRD_DEVICE_SHARDING": "off",
        "FIREBIRD_DTYPE": "float64",
        # One shared XLA cache: the clean leg's compiles warm every
        # chaos-leg worker subprocess.
        "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    env.pop("FIREBIRD_FAULTS", None)
    return env


def spawn_worker(env: dict, log_path: str, extra_env: dict | None = None):
    e = dict(env)
    e.update(extra_env or {})
    logf = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "firebird_tpu.cli", "fleet", "work",
         "--until-drained", "--poll", "0.25"],
        env=e, cwd=HERE, stdout=logf, stderr=subprocess.STDOUT)
    proc._fb_log = logf          # keep the handle alive with the proc
    return proc


def wait_for_lease(queue, owner_suffix: str, deadline: float) -> bool:
    while time.time() < deadline:
        for lease in queue.status()["leases"]:
            if (lease["owner"] or "").endswith(owner_suffix):
                return True
        time.sleep(0.1)
    return False


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

    with tempfile.TemporaryDirectory(prefix="fb_fleet_") as tmp:
        # ---- clean leg: one in-process worker ------------------------
        env = base_env(tmp, "clean")
        os.makedirs(os.path.join(tmp, "clean"), exist_ok=True)
        cfg = Config.from_env(env=env)
        # the in-process leg shares the subprocesses' cache
        os.environ["JAX_COMPILATION_CACHE_DIR"] = \
            env["JAX_COMPILATION_CACHE_DIR"]
        dcore.setup_compile_cache()
        queue = make_queue(cfg)
        plan = enqueue_tile_plan(queue, TILES, acquired=ACQ,
                                 number=N_CHIPS, chunk_size=CHUNK,
                                 max_attempts=cfg.fleet_max_attempts)
        n_jobs = plan["jobs"]
        summary = FleetWorker(cfg, queue).run(until_drained=True)
        queue_counts = queue.counts()
        queue.close()
        if summary["acked"] != n_jobs or queue_counts["done"] != n_jobs:
            print(f"fleet-smoke: clean leg acked {summary['acked']}/"
                  f"{n_jobs} (queue: {queue_counts})", file=sys.stderr)
            return 1
        clean = store_rows(SqliteStore(cfg.store_path, cfg.keyspace()))

        # ---- chaos leg: subprocess workers under adversity -----------
        env = base_env(tmp, "chaos")
        os.makedirs(os.path.join(tmp, "chaos"), exist_ok=True)
        cfg = Config.from_env(env=env)
        queue = make_queue(cfg)
        enqueue_tile_plan(queue, TILES, acquired=ACQ, number=N_CHIPS,
                          chunk_size=CHUNK,
                          max_attempts=cfg.fleet_max_attempts)
        t0 = time.time()
        deadline = t0 + DEADLINE
        procs = {}
        try:
            # Victim first, alone, so it deterministically claims a job;
            # killed 1s into its lease (mid-compute: the job outlives it).
            victim = spawn_worker(env, os.path.join(tmp, "victim.log"))
            procs["victim"] = victim
            if not wait_for_lease(queue, f":{victim.pid}", deadline):
                print("fleet-smoke: victim never claimed a lease",
                      file=sys.stderr)
                return 1
            time.sleep(1.0)
            victim.send_signal(signal.SIGKILL)
            victim.wait(timeout=30)

            # Zombie: partitioned from the queue (every heartbeat fails)
            # with a short lease — each job it claims expires mid-flight
            # and its late writes must fence off.  Healthy: just works.
            # The zombie gets NO compile cache on top of its dropped
            # heartbeats: its first job always pays a full XLA compile
            # (tens of seconds), so the 0.5 s lease is GUARANTEED to
            # expire mid-flight and its drain-time writes to hit the
            # fence — on any host speed, not just slow ones.
            zombie = spawn_worker(
                env, os.path.join(tmp, "zombie.log"),
                {"FIREBIRD_FAULTS": "lease:p=1",
                 "FIREBIRD_FLEET_LEASE_SEC": "0.5",
                 # an empty cache of its own: the zombie compiles cold
                 "JAX_COMPILATION_CACHE_DIR": os.path.join(
                     tmp, "zombie_cache")})
            procs["zombie"] = zombie
            healthy = spawn_worker(env, os.path.join(tmp, "healthy.log"))
            procs["healthy"] = healthy
            for name in ("zombie", "healthy"):
                left = max(deadline - time.time(), 1.0)
                try:
                    procs[name].wait(timeout=left)
                except subprocess.TimeoutExpired:
                    print(f"fleet-smoke: {name} worker still running after "
                          f"{DEADLINE:.0f}s\n--- {name} log ---\n"
                          f"{tail(os.path.join(tmp, name + '.log'))}",
                          file=sys.stderr)
                    return 1
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
                p._fb_log.close()

        counts = queue.counts()
        status = queue.status()
        rejects_write = queue.fence_rejects("write")
        rejects_total = queue.fence_rejects()
        queue.close()
        failures = []
        if counts["done"] != n_jobs or counts["dead"] or counts["pending"] \
                or counts["leased"]:
            failures.append(f"queue not cleanly drained: {counts} "
                            f"(dead: {status['dead']})")
        if victim.returncode != -signal.SIGKILL:
            failures.append(
                f"victim exit {victim.returncode}, expected -9")
        if rejects_write <= 0:
            failures.append(
                "no stale-fence WRITE rejections — the zombie never hit "
                f"the fence (total rejects {rejects_total}: "
                f"{status['fence_rejects_by_op']})")
        chaos = store_rows(SqliteStore(cfg.store_path, cfg.keyspace()))
        for table in ("chip", "pixel", "segment"):
            if clean[table] != chaos[table]:
                failures.append(
                    f"{table} rows differ: clean {len(clean[table])} vs "
                    f"chaos {len(chaos[table])} — a stale write was "
                    "accepted or work was lost")
        qpath = qlib.quarantine_path(cfg)
        if qpath and os.path.exists(qpath):
            with open(qpath) as f:
                qchips = json.load(f).get("chips", {})
            if qchips:
                failures.append(f"unexpected quarantine entries: "
                                f"{sorted(qchips)}")
        if failures:
            for f_ in failures:
                print(f"fleet-smoke: {f_}", file=sys.stderr)
            for name in procs:
                print(f"--- {name} log ---\n"
                      f"{tail(os.path.join(tmp, name + '.log'))}",
                      file=sys.stderr)
            return 1

        report = {
            "schema": "firebird-fleet-chaos/1",
            "tiles": len(TILES),
            "jobs": n_jobs,
            "workers": 3,
            "killed": 1,
            "partitioned": 1,
            "fence_rejects": rejects_total,
            "fence_rejects_by_op": status["fence_rejects_by_op"],
            "stale_writes_accepted": 0,
            "queue": counts,
            "rows": {t: len(clean[t]) for t in clean},
            "store_identical": True,
            "wall_seconds": round(time.time() - t0, 1),
        }
        art_dir = env_knob("FIREBIRD_FLEET_DIR")
        os.makedirs(art_dir, exist_ok=True)
        art = os.path.join(art_dir, "fleet_chaos.json")
        with open(art, "w") as f:
            json.dump(report, f, indent=1)
        print("fleet-smoke OK: "
              f"{n_jobs} jobs drained by survivors after 1 SIGKILL + 1 "
              f"partition; {rejects_write} stale writes rejected "
              f"({rejects_total} rejections total), 0 accepted; store "
              f"identical ({sum(report['rows'].values())} rows) in "
              f"{report['wall_seconds']}s; artifact {art}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
