"""Error-budget plane smoke (``make slo-smoke``): the black-box canary
catches a serving brownout AND a stalled watcher, and the budgets trip.

The proof behind docs/OBSERVABILITY.md "Error budgets": a standing
fleet — `firebird watch`, two `firebird fleet work --forever` workers,
`firebird serve` (SSE + background webhook delivery) — plus a
`firebird probe` canary exercising every surface from outside over a
FileSource landing zone.  The drill then injects real trouble with the
fault plan (faults.py ``serve`` and ``watch`` scopes) and checks the
whole detection chain: prober -> spool -> durable series ->
multi-window burn verdict -> durable budget events -> `firebird slo`
exit code.

Phases / asserts:

- **healthy**: the prober's conveyor pushes synthetic scenes through
  the real watcher/fleet/alert path; at least one end-to-end alert
  probe AND one webhook round trip resolve as successes, serve probes
  succeed, and `firebird slo` exits 0 with zero failures recorded;
- **history survives SIGKILL**: the serving process is SIGKILLed
  mid-run; the next `firebird slo` still lists ``serve:<pid>`` among
  the series sources — the dead process's metric history was ingested
  from its spool and stays queryable;
- **brownout detected, budget trips**: serve restarts under
  ``FIREBIRD_FAULTS=serve:p=1`` (every /v1 request 503s); the prober's
  failure ratio drives the ``probe_errors`` budget's fast AND slow
  burn windows over threshold within ``TRIP_DEADLINE``, `firebird slo`
  exits 1, and the exhaustion/burn transition lands durably in
  ``slo_events.jsonl``;
- **watcher stall detected by a RESTARTED prober**: serve comes back
  healthy, the watcher restarts under ``watch:p=1`` (every poll
  aborts), and a second prober incarnation (fresh pid, fresh probe
  chips) sees its end-to-end alert probes time out while its serve
  probes succeed; the series store then holds BOTH prober
  incarnations' sources — history survived the prober restart too;
- **zero-cost disarmed**: under ``FIREBIRD_TELEMETRY=0`` a watch leg
  leaves no spool/series directory and `firebird slo` exits 2.

Writes ``slo_smoke.json`` under FIREBIRD_SLO_DIR (folded into bench
artifacts by bench.py's ``_slo_fold``) and exits non-zero on any
violation.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)

from firebird_tpu.config import env_knob  # noqa: E402

ACQ_START = "1995-01-01"
TILE_XY = (100.0, 200.0)
N_WATCH_CHIPS = 5           # 3 phase-1 probe chips + 2 for the stall leg
P1_CHIPS = 3
P2_OFFSET = 3
P2_CHIPS = 2
DEADLINE = 600.0
HEALTHY_BUDGET = 330.0      # scene -> alert on cold CPU compile
TRIP_DEADLINE = 150.0       # serve blackout -> burn verdict flips
PROBE_INTERVAL = 4.0
PROBE_TIMEOUT = 120.0       # phase-1 end-to-end deadline (cold compile)
STALL_TIMEOUT = 15.0        # phase-4 prober: tight, we WANT timeouts


def fail(msg: str) -> int:
    print(f"slo-smoke: {msg}", file=sys.stderr)
    return 1


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


def dump_failure(failures, logs) -> int:
    import shutil

    keep = os.path.join(env_knob("FIREBIRD_SLO_DIR"), "failure_logs")
    os.makedirs(keep, exist_ok=True)
    for f_ in failures:
        print(f"slo-smoke: {f_}", file=sys.stderr)
    for p in logs:
        try:
            shutil.copy(p, keep)
        except OSError:
            continue
        print(f"--- {os.path.basename(p)} (kept in {keep}) ---\n"
              f"{tail(p)}", file=sys.stderr)
    return 1


# ---------------------------------------------------------------------------
# plumbing (the telemetry_smoke idiom: the parent stays JAX-free)
# ---------------------------------------------------------------------------

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
        "FIREBIRD_TELEMETRY_SNAPSHOT_SEC": "1",
        # The budget under test: all-surfaces probe failure ratio at
        # 99% over a 5-minute window, judged at fine (10s) resolution;
        # tight fast/slow windows so a real brownout trips in smoke
        # time, default 14.4x burn threshold.
        "FIREBIRD_SLO_BUDGET": "probe_errors@99/5m",
        "FIREBIRD_SLO_FAST_SEC": "45",
        "FIREBIRD_SLO_SLOW_SEC": "90",
    })
    for k in ("FIREBIRD_FAULTS", "FIREBIRD_ALERT_DB", "FIREBIRD_FLEET_DB",
              "FIREBIRD_WATCH_DB", "FIREBIRD_STREAM_STATESTORE",
              "FIREBIRD_TELEMETRY", "FIREBIRD_TELEMETRY_DIR",
              "FIREBIRD_SERIES", "FIREBIRD_SERIES_DIR",
              "FIREBIRD_SERIES_SEGMENTS", "FIREBIRD_SLO_BURN",
              "FIREBIRD_PROBE_SEC", "FIREBIRD_PROBE_TIMEOUT"):
        env.pop(k, None)
    return env


def run_cli(args: list, env: dict, log_path: str, *,
            timeout: float = DEADLINE) -> int:
    cmd = [sys.executable, "-m", "firebird_tpu.cli", *args]
    with open(log_path, "a") as logf:
        return subprocess.run(cmd, env=env, cwd=HERE, stdout=logf,
                              stderr=subprocess.STDOUT,
                              timeout=timeout).returncode


def run_slo(env: dict, *extra) -> tuple:
    """(exit code, parsed verdict-or-None) from `firebird slo`."""
    p = subprocess.run(
        [sys.executable, "-m", "firebird_tpu.cli", "slo", *extra],
        env=env, cwd=HERE, capture_output=True, text=True, timeout=120)
    try:
        doc = json.loads(p.stdout)
    except ValueError:
        doc = None
    return p.returncode, doc


def spawn_cli(args: list, env: dict, log_path: str):
    logf = open(log_path, "a")
    return subprocess.Popen(
        [sys.executable, "-m", "firebird_tpu.cli", *args],
        env=env, cwd=HERE, stdout=logf, stderr=subprocess.STDOUT)


def stop_proc(p, sig=signal.SIGTERM, timeout: float = 30.0) -> None:
    if p.poll() is None:
        p.send_signal(sig)
    try:
        p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        p.wait(timeout=10)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_healthz(port: int, deadline: float) -> bool:
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=2):
                return True
        except OSError:
            time.sleep(0.25)
    return False


def prober_metrics(spool_root: str) -> tuple:
    """(counters, histograms, prober source keys) merged across every
    prober-role spool under the telemetry home — the parent's view of
    what the canary has seen so far."""
    from firebird_tpu.obs import collect as obs_collect

    try:
        snaps = obs_collect.latest_snapshots(
            obs_collect.read_events(spool_root))
    except OSError:
        return {}, {}, set()
    probers = {k: v for k, v in snaps.items() if k.startswith("prober:")}
    merged = obs_collect.merge_snapshots(probers)
    return (merged.get("counters") or {}, merged.get("histograms") or {},
            set(probers))


def hist_count(hists: dict, name: str) -> int:
    h = hists.get(name) or {}
    return int(h.get("count") or 0)


def main() -> int:  # noqa: C901 (one linear drill, read top to bottom)
    from firebird_tpu.config import Config
    from firebird_tpu.obs import spool as spool_mod

    t0 = time.time()
    deadline = t0 + DEADLINE
    with tempfile.TemporaryDirectory(prefix="fb_slo_") as tmp:
        archive = os.path.join(tmp, "archive")
        os.makedirs(archive, exist_ok=True)
        os.makedirs(os.path.join(tmp, "fleet"), exist_ok=True)
        env = smoke_env(tmp)
        cfg = Config.from_env(env=env)
        spool_root = spool_mod.spool_dir(cfg)
        series_dir = os.path.join(spool_root, "series")
        events_path = os.path.join(series_dir, "slo_events.jsonl")
        port = free_port()
        serve_url = f"http://127.0.0.1:{port}"
        xs, ys = str(TILE_XY[0]), str(TILE_XY[1])

        watch_args = ["watch", "-x", xs, "-y", ys,
                      "-n", str(N_WATCH_CHIPS),
                      "--acquired-start", ACQ_START, "-i", "0.2"]
        worker_args = ["fleet", "work", "--forever", "--poll", "0.2"]
        serve_args = ["serve", "--port", str(port), "--host", "127.0.0.1"]

        # ---- zero-cost leg: telemetry off leaves nothing behind -------
        env0 = dict(env, FIREBIRD_TELEMETRY="0")
        zlog = os.path.join(tmp, "zerocost.log")
        if run_cli(["watch", "-x", xs, "-y", ys, "-n", "1", "--once"],
                   env0, zlog):
            print(tail(zlog), file=sys.stderr)
            return fail("FIREBIRD_TELEMETRY=0 watch --once failed")
        if spool_root and os.path.isdir(spool_root):
            return fail("FIREBIRD_TELEMETRY=0 still created a telemetry "
                        f"directory at {spool_root}")
        rc, _ = run_slo(env0)
        if rc != 2:
            return fail(f"FIREBIRD_TELEMETRY=0 `firebird slo` exited "
                        f"{rc}, want 2 (disabled)")

        # ---- standing fleet + canary ----------------------------------
        logs = {n: os.path.join(tmp, f"{n}.log") for n in
                ("watcher", "worker0", "worker1", "serve", "prober",
                 "watcher2", "serve2", "serve3", "prober2", "top")}
        failures = []
        watcher = spawn_cli(watch_args, env, logs["watcher"])
        workers = [spawn_cli(worker_args, env, logs[f"worker{i}"])
                   for i in range(2)]
        serve1 = spawn_cli(serve_args, env, logs["serve"])
        procs = [watcher, *workers, serve1]
        prober1 = prober2 = watcher2 = serve2 = serve3 = None
        try:
            if not wait_healthz(port, t0 + 60):
                print(tail(logs["serve"]), file=sys.stderr)
                return fail("serve never answered /healthz")
            prober1 = spawn_cli(
                ["probe", "--serve-url", serve_url, "--landing", archive,
                 "-x", xs, "-y", ys, "--chip-offset", "0",
                 "--chips", str(P1_CHIPS), "-i", str(PROBE_INTERVAL),
                 "--timeout", str(PROBE_TIMEOUT)],
                env, logs["prober"])
            procs.append(prober1)

            # ---- phase 1: healthy — every surface proves out ----------
            healthy_by = min(t0 + HEALTHY_BUDGET, deadline)
            ctr = hists = {}
            while time.time() < healthy_by:
                ctr, hists, _ = prober_metrics(spool_root)
                if hist_count(hists, "probe_alert_seconds") >= 1 \
                        and hist_count(hists, "probe_webhook_seconds") >= 1 \
                        and ctr.get("probe_attempts_serve", 0) >= 6:
                    break
                if any(p.poll() is not None for p in procs):
                    break
                time.sleep(1.0)
            dead = [p.args[3] if len(p.args) > 3 else p.args[2]
                    for p in procs if p.poll() is not None]
            if dead:
                failures.append(f"fleet process died early: {dead}")
            if hist_count(hists, "probe_alert_seconds") < 1:
                failures.append(
                    "no end-to-end alert probe resolved (scene -> "
                    f"watcher -> fleet -> SSE): counters={ctr}")
            if hist_count(hists, "probe_webhook_seconds") < 1:
                failures.append("no webhook round trip resolved: "
                                f"counters={ctr}")
            if ctr.get("probe_failures", 0):
                failures.append(
                    f"healthy phase recorded probe failures: {ctr}")
            rc, verdict = run_slo(env)
            if rc != 0:
                failures.append(
                    f"healthy `firebird slo` exited {rc} "
                    f"(verdict {verdict})")
            if run_cli(["top", "-n", "1"], env, logs["top"]):
                failures.append("`firebird top -n 1` failed")
            if failures:
                raise _Bail()

            # ---- phase 2: SIGKILL serve — history survives ------------
            serve_pid = serve1.pid
            serve1.send_signal(signal.SIGKILL)
            serve1.wait(timeout=30)
            rc, verdict = run_slo(env)
            srcs = (verdict or {}).get("sources") or []
            if f"serve:{serve_pid}" not in srcs:
                failures.append(
                    f"SIGKILLed serve {serve_pid}'s metric history is "
                    f"gone from the series store (sources: {srcs})")

            # ---- phase 3: brownout — the budget trips durably ---------
            serve2 = spawn_cli(serve_args,
                               dict(env, FIREBIRD_FAULTS="serve:p=1"),
                               logs["serve2"])
            procs.append(serve2)
            t_brown = time.time()
            tripped = None
            while time.time() < min(t_brown + TRIP_DEADLINE, deadline):
                rc, verdict = run_slo(env)
                if rc == 1:
                    tripped = time.time() - t_brown
                    break
                time.sleep(3.0)
            if tripped is None:
                failures.append(
                    f"budget never tripped within {TRIP_DEADLINE}s of "
                    f"the serve brownout (last verdict: {verdict})")
            ctr, _, _ = prober_metrics(spool_root)
            if not ctr.get("probe_failures_serve", 0):
                failures.append(
                    f"prober recorded no serve failures under "
                    f"serve:p=1 brownout: {ctr}")
            bad_states = ()
            try:
                with open(events_path) as f:
                    bad_states = tuple(
                        json.loads(ln).get("state") for ln in f
                        if ln.strip())
            except OSError:
                pass
            if not any(s in ("burning", "exhausted") for s in bad_states):
                failures.append(
                    "no burning/exhausted transition in the durable "
                    f"budget event log {events_path} "
                    f"(states: {bad_states})")
            if failures:
                raise _Bail()

            # ---- phase 4: watcher stall, seen by a restarted prober ---
            stop_proc(prober1)
            stop_proc(serve2, sig=signal.SIGKILL)
            serve3 = spawn_cli(serve_args, env, logs["serve3"])
            procs.append(serve3)
            if not wait_healthz(port, time.time() + 60):
                print(tail(logs["serve3"]), file=sys.stderr)
                failures.append("healthy serve restart never answered "
                                "/healthz")
                raise _Bail()
            stop_proc(watcher)
            watcher2 = spawn_cli(watch_args,
                                 dict(env, FIREBIRD_FAULTS="watch:p=1"),
                                 logs["watcher2"])
            procs.append(watcher2)
            base_ctr, _, _ = prober_metrics(spool_root)
            base_e2e = (base_ctr.get("probe_failures_alert", 0)
                        + base_ctr.get("probe_failures_webhook", 0))
            base_serve_fail = base_ctr.get("probe_failures_serve", 0)
            prober2 = spawn_cli(
                ["probe", "--serve-url", serve_url, "--landing", archive,
                 "-x", xs, "-y", ys, "--chip-offset", str(P2_OFFSET),
                 "--chips", str(P2_CHIPS), "-i", "3",
                 "--timeout", str(STALL_TIMEOUT)],
                env, logs["prober2"])
            procs.append(prober2)
            stalled = False
            ctr = {}
            while time.time() < deadline:
                ctr, _, _ = prober_metrics(spool_root)
                e2e = (ctr.get("probe_failures_alert", 0)
                       + ctr.get("probe_failures_webhook", 0))
                if e2e > base_e2e:
                    stalled = True
                    break
                time.sleep(2.0)
            if not stalled:
                failures.append(
                    "restarted prober never saw the stalled watcher "
                    f"(end-to-end failures stuck at {base_e2e}: {ctr})")
            if ctr.get("probe_failures_serve", 0) > base_serve_fail + 2:
                failures.append(
                    "serve probes failing during the watcher stall — "
                    "the surfaces are not being distinguished: "
                    f"{ctr}")
            rc, verdict = run_slo(env)
            srcs = (verdict or {}).get("sources") or []
            prober_srcs = [s for s in srcs if s.startswith("prober:")]
            if len(prober_srcs) < 2:
                failures.append(
                    "series store lost a prober incarnation across the "
                    f"restart (prober sources: {prober_srcs})")
        except _Bail:
            pass
        finally:
            for p in procs:
                stop_proc(p)

        if failures:
            return dump_failure(failures, list(logs.values()))

        report = {
            "schema": "firebird-slo-smoke/1",
            "watch_chips": N_WATCH_CHIPS,
            "probe_chips": [P1_CHIPS, P2_CHIPS],
            "final_probe_counters": {k: v for k, v in sorted(ctr.items())
                                     if k.startswith("probe_")},
            "serve_sigkilled_pid": serve_pid,
            "history_survived_sigkill": True,
            "burn_tripped_sec": round(tripped, 1),
            "budget_event_states": list(bad_states),
            "prober_sources": prober_srcs,
            "zero_cost_disarmed": True,
            "wall_seconds": round(time.time() - t0, 1),
        }
        art_dir = env_knob("FIREBIRD_SLO_DIR")
        os.makedirs(art_dir, exist_ok=True)
        art = os.path.join(art_dir, "slo_smoke.json")
        with open(art, "w") as f:
            json.dump(report, f, indent=1)
        print(f"slo-smoke OK: budget tripped {report['burn_tripped_sec']}s "
              f"into the brownout; durable events "
              f"{report['budget_event_states']}; SIGKILLed serve "
              f"{serve_pid} kept its history; prober incarnations "
              f"{prober_srcs} both in the series; "
              f"{report['wall_seconds']}s; artifact {art}")
    return 0


class _Bail(Exception):
    """Skip the remaining phases; the failures list already explains."""


if __name__ == "__main__":
    sys.exit(main())
