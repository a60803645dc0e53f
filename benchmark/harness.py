"""One run of one benchmark cell.

The window drives the fleet's unit of work, ``driver.core.run_chunk``
(fetch -> pack -> h2d -> kernel -> int-coded d2h -> format -> async
writer -> sqlite store, flushed), exactly as a fleet worker calls it.

Set-up (timed as ``setup_s``, from process start): backend, the
checkout's compile cache, a seeded pool of the configuration's
``pool_archives`` distinct chip archives, and a warm-up chunk of one batch
of the cell's own shape and traffic, which compiles (or reads from the
cache) every program the window runs.  The window is ONE ``run_chunk``
over a fixed number of the tile's chips (:func:`window_batches`);
``pixels_per_s`` is every pixel whose rows were flushed over the wall time
of that call.

Afterwards ``correct`` compares stored rows of pixels drawn from the
window against the plain reference (``reference.py``, ``compare.py``).
With ``--trace 1`` the window runs under the profiler and the cell's
per-layer metrics are read by the files in ``metrics/``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import compare, reference, traffic, work
from . import trace as tracelib

SAMPLE_PX = 96          # pixels of the window checked against the reference
DETECT_PROGRAM = "_detect_batch_wire"
# Logged after every window: the host stages' summed seconds, and the
# device memory beside its limit.
STAGE_HISTOGRAMS = ("pipeline_fetch_seconds", "pipeline_pack_seconds",
                    "pipeline_stage_seconds", "pipeline_drain_seconds",
                    "store_write_seconds")
MEMORY_STATS = ("peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved",
                "bytes_limit")


class RunFailed(Exception):
    """A run that must print no result."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# What a cell is: found by name in BENCHMARK.json, its configuration, mix
# and metric readers found by file name
# ---------------------------------------------------------------------------

def load_cell(root: str, workload: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise RunFailed(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "mixes",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return dict(root=root, spec=spec, cell=cell, config=config, mix=mix,
                end_to_end=mine(spec["end_to_end"]),
                per_layer=mine(spec["per_layer"]))


def limits(root: str, workload: str) -> dict:
    """The compared numbers' limits: ``limits/<workload>.json``, else
    ``limits/default.json``."""
    d = os.path.join(root, "benchmark", "limits")
    path = os.path.join(d, workload + ".json")
    if not os.path.exists(path):
        path = os.path.join(d, "default.json")
    with open(path) as f:
        return json.load(f)


def read_metric(root: str, name: str, ctx: dict):
    """The reader ``metrics/<name>.py`` applied to the run's context; None
    where it finds nothing to read."""
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# ---------------------------------------------------------------------------
# The program's side: configuration, a replay source, an instrumented store
# ---------------------------------------------------------------------------

def window_batches(cellspec: dict, seconds: float, C: int) -> int:
    """The window's whole batches of ``C`` chips: the mix's
    ``window_chips`` at the benchmark's ``run_seconds``, scaled by
    ``seconds / run_seconds`` for shorter trial runs, rounded up, at least
    two.  The work is fixed, the same for every seed; it does not follow
    the program's speed."""
    chips = traffic.load_mix(cellspec["mix"])["window_chips"] * seconds \
        / cellspec["spec"]["run_seconds"]
    return max(2, math.ceil(chips / C - 1e-9))


def program_sensor(config: dict):
    from firebird_tpu.ccd.sensor import SENSORS

    sn = config["sensor"]
    s = SENSORS[sn["program_sensor"]]
    if s.chip_side != sn["chip_side"]:
        s = dataclasses.replace(s, chip_side=int(sn["chip_side"]))
    for role in ("band_names", "detection_bands", "tmask_bands",
                 "optical_bands", "thermal_bands"):
        if tuple(getattr(s, role)) != tuple(sn[role]):
            raise RunFailed(f"program sensor {s.name} {role} "
                            f"{getattr(s, role)} != configuration {sn[role]}")
    if s.blue_band != sn["blue_band"] or s.pixel_size_m != sn["pixel_size_m"]:
        raise RunFailed(f"program sensor {s.name} geometry differs from "
                        "the configuration")
    return s


def program_config(config: dict, store_path: str):
    from firebird_tpu.config import Config

    d = config["driver"]
    return dataclasses.replace(
        Config(), store_backend=d["store_backend"], store_path=store_path,
        chips_per_batch=int(d["chips_per_batch"]), max_obs=int(d["max_obs"]),
        obs_bucket=int(d["obs_bucket"]),
        pipeline_depth=int(d["pipeline_depth"]),
        input_parallelism=int(d["input_parallelism"]),
        writer_threads=int(d["writer_threads"]), dtype=d["dtype"],
        compact=bool(d["compact"]), device_sharding=d["device_sharding"])


class ReplaySource:
    """Serves the pool under the tile's real chip ids: chip id i of the
    tile gets pool archive i mod len(pool)."""

    def __init__(self, pool, cids, sensor):
        from firebird_tpu.ingest.packer import ChipData

        self._chip = ChipData
        self.pool, self.sensor = pool, sensor
        self.index = {tuple(c): i for i, c in enumerate(cids)}

    def archive(self, cx, cy):
        return self.pool[self.index[(int(cx), int(cy))] % len(self.pool)]

    def chip(self, cx, cy, acquired=None):
        import jax

        with jax.profiler.TraceAnnotation("bench.source.chip"):
            t, spectra, qas = self.archive(cx, cy)
            return self._chip(cx=int(cx), cy=int(cy), dates=t,
                              spectra=spectra, qas=qas, sensor=self.sensor)


class SpannedStore:
    """The sqlite store, with a host span around every write."""

    def __init__(self, store):
        self.store = store

    def write(self, table, frame):
        import jax

        with jax.profiler.TraceAnnotation("bench.store.write"):
            return self.store.write(table, frame)

    def __getattr__(self, name):
        return getattr(self.store, name)


class DroppedAcquisitions(logging.Handler):
    """Counts the packer's truncation warning."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.count = 0

    def emit(self, record):
        if "DROPPED" in record.getMessage():
            self.count += 1


class CompileCounter:
    """Backend compiles and compile-cache reads, from jax's own events."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise RunFailed(f"no TPU: jax sees {d.platform} ({d.device_kind})")
    if require_tpu and len(devs) < chips:
        raise RunFailed(f"the cell needs {chips} chips, jax sees {len(devs)}")
    return devs


def run(cellspec: dict, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True) -> dict:
    """One run; returns the result object (the contract's last line)."""
    cell, config, mix = cellspec["cell"], cellspec["config"], \
        cellspec["mix"]
    workdir = tempfile.mkdtemp(prefix="bench-")
    dropped = DroppedAcquisitions()
    logging.getLogger("firebird").addHandler(dropped)
    try:
        return _run(cellspec, cell, config, mix, int(seed), float(seconds),
                    bool(trace), t_start, require_tpu, workdir, dropped)
    finally:
        logging.getLogger("firebird").removeHandler(dropped)
        shutil.rmtree(workdir, ignore_errors=True)


def _run(cellspec, cell, config, mix, seed, seconds, trace, t_start,
         require_tpu, workdir, dropped):
    devs = device_info(int(cell["chips"]), require_tpu)
    import jax

    from firebird_tpu import grid
    from firebird_tpu.driver import core
    from firebird_tpu.obs import logger
    from firebird_tpu.obs import metrics as obs_metrics
    from firebird_tpu.store import open_store

    peak = None
    if trace:
        peak = work.peaks(devs[0].device_kind) if require_tpu else None

    cfg = program_config(config, os.path.join(workdir, "store", "fb.db"))
    sensor = program_sensor(config)
    C, P = cfg.chips_per_batch, sensor.pixels
    core.setup_compile_cache()
    compiles = CompileCounter()

    # Set-up: pool, replay source, the production bring-up.
    t0 = time.perf_counter()
    pool = traffic.pool(config, mix, seed, int(config["pool_archives"]))
    log(f"pool: {len(pool)} chips x {pool[0][1].shape} in "
        f"{time.perf_counter() - t0:.3f} s")
    x, y = config["tile_point"]
    cids = grid.chips(grid.tile(x=x, y=y))
    source = ReplaySource(pool, cids, sensor)
    acquired = config["acquired"]
    store = SpannedStore(open_store(cfg.store_backend, cfg.store_path,
                                  cfg.keyspace()))
    run_id = "bench"
    source, _, writer, policy, _, quarantine = core.robustness_setup(
        cfg, run_id, source=source, store=store)
    counters = obs_metrics.Counters()
    plog = logger("change-detection")

    def chunk(ids):
        return core.run_chunk(ids, source=source, writer=writer,
                              acquired=acquired, cfg=cfg,
                              counters=counters, log=plog, policy=policy,
                              quarantine=quarantine, reraise=True)

    # Warm-up: one batch of the cell's own shape and traffic, which
    # compiles (or reads from the cache) every program the window runs:
    # the window's batches are full too, so they share its one shape.
    warm_ids = cids[:C]
    t0 = time.perf_counter()
    chunk(warm_ids)
    log(f"warm-up: {len(warm_ids)} chips in {time.perf_counter() - t0:.3f}"
        f" s, compiles {compiles.compiles}, cache reads "
        f"{compiles.cache_hits}")
    n_batches = window_batches(cellspec, seconds, C)
    win_ids = cids[C:C + n_batches * C]
    if len(win_ids) < n_batches * C:
        raise RunFailed("the tile has too few chips for this window")

    obs_metrics.reset_registry()
    compiles_before = compiles.compiles
    trace_dir = os.path.join(workdir, "trace")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    t_w = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        done = chunk(win_ids)
    window_s = time.perf_counter() - t_w
    if trace:
        jax.profiler.stop_trace()
    snap = obs_metrics.get_registry().snapshot()
    in_window = compiles.compiles - compiles_before
    stats = devs[0].memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:int(cell["chips"])])
    writer.close()
    store.close()
    n_dead = len(quarantine)
    counts = snap.get("counters", {})
    hists = snap.get("histograms", {})
    log("window host seconds (summed over threads): " + ", ".join(
        f"{h} {hists[h]['sum']:.3f}" for h in STAGE_HISTOGRAMS
        if h in hists))
    log("device memory: " + ", ".join(
        f"{k} {stats[k]}" for k in MEMORY_STATS if k in stats))
    log(f"window: {len(win_ids)} chips ({n_batches} batches of {C}) in "
        f"{window_s:.3f} s; compiles inside the window {in_window}; "
        f"capacity_redispatches {counts.get('capacity_redispatches', 0)}; "
        f"dead letters {n_dead}; dropped-acquisition warnings "
        f"{dropped.count}; rows stored {counts.get('store_rows_written', 0)}")
    if dropped.count:
        raise RunFailed("the packer dropped acquisitions")
    if n_dead or len(done) != len(win_ids):
        raise RunFailed(f"{n_dead} chips dead-lettered, {len(done)} of "
                        f"{len(win_ids)} processed")

    # Correctness: stored rows against the reference.
    t0 = time.perf_counter()
    db = store.store.path
    files = [p for p in (db, db + "-wal") if os.path.exists(p)]
    log(f"store files: {sum(os.path.getsize(p) for p in files)} bytes")
    checks = check_rows(config, seed, source, win_ids, db)
    lim = limits(cellspec["root"], cell["name"])
    correct = all(checks[k] <= lim[k] for k in lim)
    log(f"reference on {SAMPLE_PX} px in {time.perf_counter() - t0:.3f} s")

    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": bool(correct), "attempted": len(win_ids),
              "failed": len(win_ids) - len(done)}
    ctx = dict(snapshot=snap, chips=len(win_ids), pixels=P,
               acquisitions=int(pool[0][0].shape[0]),
               bands=len(config["sensor"]["band_names"]),
               detection_bands=len(config["sensor"]["detection_bands"]),
               memory_stats=stats, peak=peak)
    if trace:
        t0 = time.perf_counter()
        events = tracelib.load(trace_dir)
        red = tracelib.reduce(events)
        log(f"trace: {len(events)} events read and reduced in "
            f"{time.perf_counter() - t0:.3f} s")
        ctx["trace"] = red
        ctx["kernel_s"] = tracelib.module_seconds(red, DETECT_PROGRAM)
        progs = sorted(red.get("modules", {}).items(), key=lambda kv: -kv[1])
        log(f"trace: window {red['window_s']:.3f} s, devices "
            f"{red['devices']}, busy {red.get('busy_s')}, idle after the "
            f"last operation {red.get('tail_idle_s')}, programs "
            f"{progs[:6]}")
        if not red["devices"] or red["busy_s"] <= 0:
            raise RunFailed("the trace holds no device operation")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in cellspec["per_layer"]:
            v = read_metric(cellspec["root"], m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red["device_ops"]],
            "idle_gaps": [[n, s] for n, s in red["idle_gaps"]]}
    else:
        e2e = {"pixels_per_s": len(win_ids) * P / window_s,
               "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cellspec["end_to_end"]}
        result["device"] = device
    result["checks"] = {k: {"value": checks[k], "limit": lim[k]}
                        for k in lim}
    return result


def sample_pixels(seed: int, win_ids: list, side: int) -> list:
    """(chip id, row, col) of SAMPLE_PX pixels drawn from the window."""
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 7])
    ci = rng.integers(0, len(win_ids), SAMPLE_PX)
    pi = rng.choice(side * side, SAMPLE_PX, replace=False)
    return [(tuple(win_ids[c]), int(p) // side, int(p) % side)
            for c, p in zip(ci, pi)]


def sample_records(config, seed, archive, win_ids,
                   precision: str = "float64"):
    """Reference records of the pixels sampled from the window, computed
    from the archives the source served (``archive(cx, cy)``): (keys,
    anchors, records)."""
    sn = config["sensor"]
    side, psz = int(sn["chip_side"]), int(sn["pixel_size_m"])
    keys, recs, anchors = [], {}, {}
    for (cx, cy), r, c in sample_pixels(seed, win_ids, side):
        t, spectra, qas = archive(cx, cy)
        key = (cx, cy, cx + c * psz, cy - r * psz)
        keys.append(key)
        anchors[(cx, cy)] = int(t[0])
        recs[key] = compare.reference_record(reference.detect(
            t, spectra[:, :, r, c], qas[:, r, c], sn, precision))
    return keys, anchors, recs


def check_rows(config, seed, source, win_ids, db) -> dict:
    """The compared numbers of one run: stored rows of the sampled pixels
    against the reference."""
    keys, anchors, ref = sample_records(config, seed, source.archive,
                                        win_ids)
    got = compare.store_records(db, keys, config["store_prefixes"], anchors)
    out = compare.compare(got, ref)
    side = int(config["sensor"]["chip_side"])
    out["missing_rows"] = float(len(win_ids) * side * side
                                - stored_pixels(db, win_ids))
    return out


def stored_pixels(db: str, win_ids: list) -> int:
    import sqlite3

    con = sqlite3.connect(f"file:{db}?mode=ro", uri=True)
    try:
        return sum(con.execute(
            'SELECT COUNT(*) FROM "pixel" WHERE cx=? AND cy=?',
            (int(cx), int(cy))).fetchone()[0] for cx, cy in win_ids)
    finally:
        con.close()
