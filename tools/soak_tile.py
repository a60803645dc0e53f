"""Full-tile soak: 2500 chips end-to-end on CPU with a mid-run kill and
--resume (VERDICT r1 weak #5: "a full 2500-chip tile has never been run
end-to-end; writer backpressure and resume at scale are untested").

Phase A launches `firebird changedetection` over a full synthetic tile
and SIGKILLs it once ~35% of chips have landed in the store (a crash,
not a clean shutdown: the async writer and any in-flight batch die with
it).  Phase B reruns with --resume and must complete the remaining
chips.  The report (docs/SOAK_r02.json) records wall times, the resume
skip count, store row counts, and throughput counters.

Rolling extensions (`--extend`) resume an existing store toward the
2500-chip target without wiping it.  The variogram mode is pinned
explicitly in the child env and recorded in `{workdir}/VARIOGRAM` when
the store is created; an extension whose active mode differs from the
recorded one is refused (mixing modes in one store would blend two
decision surfaces — docs/DIVERGENCE.md #1 says "pick one mode per
archive and keep it").

Usage: python tools/soak_tile.py [--chips N] [--kill-at FRACTION]
           [--workdir DIR] [--variogram plain|adjusted] [--extend]
           [--nice N]
"""

import argparse
import glob
import json
import os
import signal
import sqlite3
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))
from firebird_tpu.config import env_knob  # noqa: E402

X, Y = 542000, 1650000            # tile h=20 v=11
# Full ARD archive (VERDICT r2 #3: the r2 soak's 1-year window could not
# initialize a model — MEOW_SIZE obs over INIT_DAYS — so every row was a
# sentinel; this window closes real segments on every standard pixel).
ACQUIRED = "1985-01-01/2017-12-31"


def store_stats(db: str) -> dict:
    """Canonical row counts + size for a soak store — the one place the
    chip/pixel/segment/closed-segment queries live (soak_report.py reads
    the same stats for the round artifacts)."""
    con = sqlite3.connect(f"file:{db}?mode=ro", uri=True)
    st = {
        "chips_total": con.execute(
            "SELECT COUNT(DISTINCT cx || ',' || cy) FROM segment"
        ).fetchone()[0],
        "pixel_rows": con.execute(
            "SELECT COUNT(*) FROM pixel").fetchone()[0],
        "segment_rows": con.execute(
            "SELECT COUNT(*) FROM segment").fetchone()[0],
        # Closed (non-sentinel) segments: sday is NULL only on sentinel
        # rows (format.py: pixels with no model contribute one sentinel).
        "closed_segment_rows": con.execute(
            "SELECT COUNT(*) FROM segment WHERE sday IS NOT NULL"
            " AND sday != ''").fetchone()[0],
    }
    con.close()
    st["store_mb"] = round(os.path.getsize(db) / 1e6, 1)
    return st


def store_chips(pattern: str) -> int:
    dbs = glob.glob(pattern)
    if not dbs:
        return 0
    try:
        con = sqlite3.connect(f"file:{dbs[0]}?mode=ro", uri=True)
        n = con.execute(
            "SELECT COUNT(DISTINCT cx || ',' || cy) FROM segment").fetchone()[0]
        con.close()
        return int(n)
    except sqlite3.Error:
        return 0


def recorded_mode(workdir: str) -> str | None:
    """The variogram mode this store was built under (None: pre-recording
    legacy store — the operator must state the mode explicitly)."""
    path = os.path.join(workdir, "VARIOGRAM")
    if os.path.exists(path):
        return open(path).read().strip()
    return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, default=2500)
    ap.add_argument("--kill-at", type=float, default=0.35)
    ap.add_argument("--acquired", default=ACQUIRED)
    ap.add_argument("--out", default="docs/SOAK_r03.json")
    ap.add_argument("--workdir", default="/tmp/fb_soak")
    ap.add_argument("--variogram", choices=("plain", "adjusted"),
                    default=None)
    ap.add_argument("--extend", action="store_true",
                    help="resume an existing store toward --chips (no "
                         "wipe, no kill)")
    ap.add_argument("--nice", type=int, default=15)
    args = ap.parse_args()
    n_chips, kill_at, acquired = args.chips, args.kill_at, args.acquired
    out, workdir, extend = args.out, args.workdir, args.extend
    explicit_mode, niceness = args.variogram, str(args.nice)

    # The child NEVER inherits an ambient default: the mode is pinned in
    # its env so a resumed store can't silently mix decision surfaces
    # when the framework default changes (as it did in round 4).
    if extend:
        if not glob.glob(f"{workdir}/soak*.db"):
            print(f"--extend: no store matches {workdir}/soak*.db",
                  file=sys.stderr)
            return 2
        rec = recorded_mode(workdir)
        if rec is None and explicit_mode is None:
            print(f"{workdir} has no recorded VARIOGRAM mode (legacy "
                  "store); state it with --variogram", file=sys.stderr)
            return 2
        if rec is not None and explicit_mode is not None \
                and rec != explicit_mode:
            print(f"refusing to extend: store was built under "
                  f"'{rec}' but --variogram says '{explicit_mode}'",
                  file=sys.stderr)
            return 2
        mode = rec or explicit_mode
    else:
        mode = explicit_mode or env_knob("FIREBIRD_VARIOGRAM")
        if mode not in ("plain", "adjusted"):
            print(f"bad variogram mode {mode!r} (FIREBIRD_VARIOGRAM)",
                  file=sys.stderr)
            return 2

    if not extend:
        subprocess.run(["rm", "-rf", workdir], check=True)
        os.makedirs(workdir)
    store = f"{workdir}/soak.db"
    with open(os.path.join(workdir, "VARIOGRAM"), "w") as f:
        f.write(mode + "\n")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               FIREBIRD_SOURCE="synthetic",
               FIREBIRD_STORE_BACKEND="sqlite",
               FIREBIRD_STORE_PATH=store,
               FIREBIRD_VARIOGRAM=mode,
               FIREBIRD_OBS_BUCKET="32",
               FIREBIRD_CHIPS_PER_BATCH="16",
               JAX_COMPILATION_CACHE_DIR=os.path.abspath(".cache/jax"))
    cmd = [sys.executable, "-m", "firebird_tpu.cli", "changedetection",
           "-x", str(X), "-y", str(Y), "-a", acquired, "-n", str(n_chips)]
    pattern = f"{workdir}/soak*.db"
    report = {"chips": n_chips, "acquired": acquired, "variogram": mode}

    if extend:
        # ---- rolling extension: resume toward the target, no kill ----
        t0 = time.time()
        start_chips = store_chips(pattern)
        with open(f"{workdir}/phaseD.log", "a") as lg:
            rc = subprocess.run(
                ["nice", "-n", niceness] + cmd + ["--resume"],
                env=env, stdout=lg, stderr=subprocess.STDOUT).returncode
        wall = round(time.time() - t0, 1)
        [db] = glob.glob(pattern)
        st = store_stats(db)
        done = st["chips_total"] - start_chips
        report.update(st)
        report.update({
            "extend": True, "extend_rc": rc, "extend_sec": wall,
            "extend_start_chips": start_chips,
            "extend_chips_done": done,
            "extend_pixels_per_sec": round(done * 10000 / max(wall, 1e-9), 1),
            "ok": rc == 0 and st["chips_total"] >= n_chips,
        })
        return write_report(report, out)

    # ---- phase A: run until ~kill_at, then crash it ----
    report["kill_at"] = kill_at
    t0 = time.time()
    with open(f"{workdir}/phaseA.log", "w") as lg:
        p = subprocess.Popen(["nice", "-n", niceness] + cmd, env=env,
                             stdout=lg, stderr=subprocess.STDOUT)
        target = int(n_chips * kill_at)
        while p.poll() is None and store_chips(pattern) < target:
            time.sleep(20)
        if p.poll() is None:
            p.send_signal(signal.SIGKILL)
            p.wait()
    report["phaseA_sec"] = round(time.time() - t0, 1)
    report["phaseA_chips_stored"] = store_chips(pattern)
    report["killed"] = report["phaseA_chips_stored"] < n_chips
    print(f"phase A: {report['phaseA_chips_stored']} chips in "
          f"{report['phaseA_sec']}s (killed={report['killed']})", flush=True)

    # ---- phase B: resume to completion ----
    t0 = time.time()
    with open(f"{workdir}/phaseB.log", "w") as lg:
        rc = subprocess.run(["nice", "-n", niceness] + cmd + ["--resume"],
                            env=env, stdout=lg, stderr=subprocess.STDOUT).returncode
    report["phaseB_sec"] = round(time.time() - t0, 1)
    report["phaseB_rc"] = rc

    logb = open(f"{workdir}/phaseB.log").read()
    for line in logb.splitlines():
        if "resume:" in line:
            report["resume_line"] = line.split("INFO ")[-1].strip()
        if "change-detection complete" in line:
            report["counters"] = line.split("complete: ")[-1].strip()

    # ---- verification ----
    [db] = glob.glob(pattern)
    st = store_stats(db)
    report["segment_chips"] = st["chips_total"]   # historical key name
    report.update({k: st[k] for k in ("pixel_rows", "segment_rows",
                                      "store_mb", "closed_segment_rows")})
    pixels = n_chips * 10000
    wall = report["phaseA_sec"] + report["phaseB_sec"]
    report["e2e_pixels_per_sec"] = round(pixels / max(wall, 1e-9), 1)
    report["ok"] = (rc == 0 and report["segment_chips"] == n_chips
                    and report["pixel_rows"] == pixels
                    and report["closed_segment_rows"] > 0)
    return write_report(report, out)


def write_report(report: dict, out: str) -> int:
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
