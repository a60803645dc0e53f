#!/usr/bin/env python3
"""Chip smoke: CONUS change detection end to end on a TPU, checked.

    python chip_smoke.py              # one chip: main path + parity
    python chip_smoke.py --chips 4    # the sharded driver path vs one device

One chip:

1. Device: refuse anything but a TPU (no CPU fallback, no number).
2. Main path: ``core.changedetection`` — the call ``firebird
   changedetection`` makes — on the tile at x=542000, y=1650000
   (h=20, v=11), 16 full-width chips (100x100 px, 7 Landsat bands), over
   the whole 1985-2017 archive, default kernel routes, into a sqlite
   store.  Run twice in one process: run 1 compiles, run 2 is steady
   state.  The store must hold all 160,000 pixels, with no dead letters.
3. Parity: ``validate.validate_chip`` at float32 on 200 pixels of one of
   those chips against the float64 oracle; structural agreement must be
   100%.

``--chips 4`` runs only the sharded driver path (``driver.core``
stage/detect over a mesh of the host's local devices) and the
single-device kernel on device 0 over the same 16 packed chips, and
requires identical rows.

Rates printed here are smoke figures, not benchmark metrics.  The last
line of standard output is one JSON object naming the device; any failed
phase exits non-zero before it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
X, Y = 542000.0, 1650000.0            # CONUS Albers -> tile h=20, v=11
ACQUIRED = "1985-01-01/2017-12-31"
N_CHIPS = 16
PARITY_PIXELS = 200


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def archive_source():
    """The synthetic archive over the whole acquired range.

    ``FIREBIRD_SOURCE=synthetic`` (driver ``make_source``) serves a fixed
    1995-2005 archive at one platform's 16-day cadence: 229 acquisitions,
    T=256 — never the full record.  This source spans 1985-2017 at the
    two-platform 8-day cadence the driver's shape prediction assumes
    (``predict_batch_shape``), so the packer fills the default
    ``FIREBIRD_MAX_OBS`` T=512 like a production tile does."""
    from firebird_tpu.ingest import SyntheticSource

    lo, hi = ACQUIRED.split("/")
    return SyntheticSource(seed=0, start=lo, end=hi, cadence_days=8)


def tile_chip_ids() -> list:
    from firebird_tpu import grid
    from firebird_tpu.utils.fn import take

    return list(take(N_CHIPS, grid.chips(grid.tile(x=X, y=Y))))


def pack_chips(cids, source, cfg):
    from firebird_tpu.ingest import pack

    return pack([source.chip(cx, cy, ACQUIRED) for cx, cy in cids],
                bucket=cfg.obs_bucket, max_obs=cfg.max_obs)


def wire_avatars(packed, sharding=None):
    import jax

    from firebird_tpu.ccd import kernel
    from firebird_tpu.driver import core

    return tuple(jax.ShapeDtypeStruct(a.shape, d, sharding=sharding)
                 for a, d in zip(kernel.wire_args(packed),
                                 core.wire_avatar_dtypes()))


def in_background(fn, *args, **kw) -> threading.Thread:
    """Run a compile on a thread; its failure fails the smoke at join."""
    box = {}

    def run():
        try:
            fn(*args, **kw)
        except BaseException as e:          # re-raised by join_ok
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.box = box
    t.start()
    return t


def join_ok(t: threading.Thread) -> None:
    t.join()
    if "error" in t.box:
        raise t.box["error"]


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (summed over
    threads), from jax's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.seconds += duration


def device_phase(want: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"no TPU: jax sees {d.platform} ({d.device_kind})")
    if len(devs) < want:
        fail(f"--chips {want} needs {want} devices, jax sees {len(devs)}")
    stats = d.memory_stats() or {}
    say(f"device: kind={d.device_kind} count={len(devs)} "
        f"bytes_limit={stats.get('bytes_limit')}")
    return devs


def store_rows(cfg) -> dict:
    from firebird_tpu.driver import quarantine as qlib
    from firebird_tpu.store import open_store

    store = open_store(cfg.store_backend, cfg.store_path, cfg.keyspace(),
                       read_only=True)
    try:
        rows = {t: store.count(t) for t in ("chip", "pixel", "segment")}
        rows["chips_with_segments"] = len(store.chip_ids("segment"))
    finally:
        store.close()
    qpath = qlib.quarantine_path(cfg)
    rows["dead_letters"] = len(qlib.Quarantine.load(qpath)) if qpath else 0
    return rows


def run_main_path(out: str, clock: CompileClock) -> dict:
    """One ``core.changedetection`` run into a fresh sqlite store (with
    its quarantine and run manifest) in the new directory ``out``."""
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.obs import metrics as obs_metrics

    os.makedirs(out)
    os.environ["FIREBIRD_STORE_PATH"] = os.path.join(out, "fb.db")
    cfg = Config.from_env()
    source = archive_source()
    c0, t0 = clock.seconds, time.perf_counter()
    done = core.changedetection(x=X, y=Y, acquired=ACQUIRED, number=N_CHIPS,
                                chunk_size=2500, cfg=cfg, source=source)
    wall = time.perf_counter() - t0
    snap = obs_metrics.get_registry().snapshot()
    rows = store_rows(cfg)
    P = source.sensor.pixels
    if len(done) != N_CHIPS:
        fail(f"{len(done)} of {N_CHIPS} chips processed")
    if rows["chips_with_segments"] != N_CHIPS or \
            rows["pixel"] != N_CHIPS * P or rows["chip"] != N_CHIPS:
        fail(f"store rows {rows}, want {N_CHIPS} chips x {P} pixels")
    if rows["dead_letters"]:
        fail(f"{rows['dead_letters']} chips dead-lettered")
    return dict(wall=wall, compile=clock.seconds - c0, rows=rows, pixels=P,
                counters=snap["counters"], histograms=snap["histograms"])


def main_phase(devs) -> None:
    import jax
    import jax.numpy as jnp

    from firebird_tpu import native
    from firebird_tpu.ccd import kernel, params
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core

    os.environ.update(FIREBIRD_SOURCE="synthetic",
                      FIREBIRD_STORE_BACKEND="sqlite",
                      FIREBIRD_SYNTH_SENSOR="landsat-ard",
                      FIREBIRD_PALLAS="0")
    say(f"native fastpack available: {native.available()}")
    cfg = core.resolve_batching(Config.from_env(), ACQUIRED)
    say(f"compile cache: {core.setup_compile_cache()}")

    # The parity audit runs one driver-shaped batch; its program
    # (capacity-checked, not donated) is a second compile of that shape,
    # so pack and build it alongside the driver's own warm start instead
    # of after the main path.
    parity = {}

    def prepare_parity():
        batch = pack_chips(tile_chip_ids()[:cfg.chips_per_batch],
                           archive_source(), cfg)
        kernel.aot_compile(wire_avatars(batch), dtype=jnp.float32,
                           wcap=kernel.window_cap(batch), sensor=batch.sensor)
        parity["batch"] = batch

    parity_prep = in_background(prepare_parity)

    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as out:
        runs = [run_main_path(os.path.join(out, f"run{i}"), clock)
                for i in (1, 2)]
    r1, r2 = runs
    c = r2["counters"]
    h = r2["histograms"]
    n_batches = h.get("pipeline_dispatch_seconds", {}).get("count")
    say(f"main path: {N_CHIPS} chips x {r2['pixels']} px, acquired "
        f"{ACQUIRED}, "
        f"T={core.predict_batch_shape(cfg, ACQUIRED)[1]}, "
        f"{n_batches} batches of chips_per_batch={cfg.chips_per_batch}, "
        f"pipeline_depth={cfg.pipeline_depth}")
    say(f"  run 1 (cold): wall {r1['wall']:.3f} s, of which jax "
        f"trace+lower+compile {r1['compile']:.3f} s (summed over threads)")
    say(f"  run 2 (steady): wall {r2['wall']:.3f} s, compile "
        f"{r2['compile']:.3f} s; smoke figure (not a benchmark metric): "
        f"{N_CHIPS * r2['pixels'] / r2['wall']:.1f} px/s end to end incl. "
        "synthetic ingest and sqlite writes")
    say(f"  wire_h2d_bytes={c.get('wire_h2d_bytes')} "
        f"wire_d2h_bytes={c.get('wire_d2h_bytes')} (run 2)")
    stages = {k: round(h[f"pipeline_{k}_seconds"]["sum"], 3)
              for k in ("fetch", "pack", "stage", "dispatch", "drain", "d2h")
              if f"pipeline_{k}_seconds" in h}
    say(f"  run 2 stage seconds, summed over batches (threads overlap): "
        f"{stages}")
    say(f"  compile_cache_hits={r1['counters'].get('compile_cache_hits', 0)}"
        f"/{c.get('compile_cache_hits', 0)} "
        f"compile_cache_misses="
        f"{r1['counters'].get('compile_cache_misses', 0)}"
        f"/{c.get('compile_cache_misses', 0)} (run 1/run 2), "
        f"warm_compiles={r1['counters'].get('warm_compiles', 0)}")
    route = sorted(kernel.pallas_components())
    say(f"  kernel route: {'pallas ' + ','.join(route) if route else 'xla'}"
        f", compaction={int(params.compact_default())}, backend="
        f"{jax.default_backend()}, kernel_pallas_refused="
        f"{c.get('kernel_pallas_refused', 0)}")
    say(f"  store (run 2): {r2['rows']}")
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(f"  device peak_bytes_in_use={peak}")

    join_ok(parity_prep)
    parity_phase(parity["batch"])


def parity_phase(packed) -> None:
    from firebird_tpu import validate

    t0 = time.perf_counter()
    rep = validate.validate_chip(packed, n_pixels=PARITY_PIXELS,
                                 dtype="float32")
    say(f"parity: validate_chip float32 vs float64 oracle, chip "
        f"{tuple(int(v) for v in packed.cids[0])}, "
        f"{rep['pixels_audited']} px, T={rep['obs_per_pixel']}: "
        f"structural_agreement={rep['structural_agreement']} "
        f"mismatches={rep['mismatches']} "
        f"numeric_max_rel_err={rep['numeric_max_rel_err']} "
        f"({time.perf_counter() - t0:.3f} s)")
    if rep["pixels_audited"] != PARITY_PIXELS:
        fail(f"audited {rep['pixels_audited']} pixels")
    if not rep["structural_agreement"]:
        fail(f"structural mismatches {rep['mismatches']}")


def same_column(a, b) -> bool:
    """Exact equality of two store columns (NaN equals NaN; object
    columns hold per-row arrays, strings or blobs)."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return False
    if a.dtype == object or b.dtype == object:
        return all(same_column(x, y) if isinstance(x, np.ndarray)
                   else x == y for x, y in zip(a, b))
    return np.array_equal(a, b, equal_nan=a.dtype.kind in "fc")


def four_chip_phase(devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from firebird_tpu.ccd import format as ccdformat
    from firebird_tpu.ccd import kernel
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.ingest.packer import PackedChips
    from firebird_tpu.obs import metrics as obs_metrics
    from firebird_tpu.parallel import make_mesh
    from firebird_tpu.parallel.mesh import aot_compile_sharded

    devs = devs[:4]
    cfg = Config.from_env()
    # The reference runs device 0's share at a time: the same per-device
    # batch as the sharded program, and a v5e compiles the default
    # program for C=4 in about a third of the time C=8 takes (PR 21).
    step = N_CHIPS // len(devs)
    core.setup_compile_cache()
    obs_metrics.reset_registry()

    # Both programs compile (minutes each) from the driver's predicted
    # shapes while the chips are generated; the dispatches below then
    # read them from the persistent cache.
    t0 = time.perf_counter()
    C, T, wcap = core.predict_batch_shape(
        dataclasses.replace(cfg, chips_per_batch=N_CHIPS), ACQUIRED)
    sensor = archive_source().sensor
    B, P = sensor.n_bands, sensor.pixels
    sharded = in_background(
        aot_compile_sharded, make_mesh(devices=jax.local_devices()),
        jnp.float32, wcap, sensor, ((C, T), (C,), (C, B, P, T), (C, P, T)),
        compact=cfg.compact)
    one = jax.sharding.SingleDeviceSharding(devs[0])
    single = in_background(
        kernel.aot_compile, tuple(
            jax.ShapeDtypeStruct(shape, d, sharding=one) for shape, d in zip(
                ((step, T), (step,), (step, B, P, T), (step, P, T)),
                core.wire_avatar_dtypes())),
        dtype=jnp.float32, wcap=wcap, sensor=sensor, compact=cfg.compact)
    packed = pack_chips(tile_chip_ids(), archive_source(), cfg)
    if (packed.n_chips, packed.spectra.shape[-1],
            kernel.window_cap(packed)) != (C, T, wcap):
        say(f"note: packed shape {packed.spectra.shape} wcap "
            f"{kernel.window_cap(packed)} differs from the predicted "
            f"{(C, T, wcap)}; the dispatches compile their own")

    def chips(lo, hi):
        return PackedChips(cids=packed.cids[lo:hi], dates=packed.dates[lo:hi],
                           spectra=packed.spectra[lo:hi],
                           qas=packed.qas[lo:hi], n_obs=packed.n_obs[lo:hi],
                           sensor=packed.sensor)

    join_ok(sharded)
    join_ok(single)
    say(f"4-chip: {N_CHIPS} chips x {P} px packed and both programs "
        f"compiled in {time.perf_counter() - t0:.3f} s")

    staged = core.stage_batch(packed, jnp.float32, "auto")
    if staged.mesh is None or list(staged.mesh.devices.flat) != devs:
        fail(f"driver staged without a {len(devs)}-device mesh")

    for a in staged.args:
        if a.sharding.device_set != set(devs):
            fail(f"staged input on {a.sharding.device_set}")
    t0 = time.perf_counter()
    seg, n_real = core.detect_batch(staged.packed, jnp.float32, "auto",
                                    staged=staged, check_capacity=True,
                                    compact=cfg.compact)
    per_device = {s.device.id: s.data.shape[0]
                  for s in seg.n_segments.addressable_shards}
    if set(per_device) != {d.id for d in devs} or \
            set(per_device.values()) != {N_CHIPS // len(devs)}:
        fail(f"sharded result chips per device {per_device}")
    got = core.fetch_results(seg)
    t_sharded = time.perf_counter() - t0

    t0 = time.perf_counter()
    with jax.default_device(devs[0]):
        parts = []
        for lo in range(0, N_CHIPS, step):
            part = kernel.detect_packed(chips(lo, lo + step),
                                        dtype=jnp.float32,
                                        compact=cfg.compact)
            if part.n_segments.sharding.device_set != {devs[0]}:
                fail("single-device reference left device 0")
            parts.append(core.fetch_results(part))
    t_single = time.perf_counter() - t0

    # Rows as the store would receive them (format.batch_frames), so a
    # capacity retry that widened one side's buffers cannot matter.
    rows_got = ccdformat.batch_frames(packed, got, n_real)
    rows_ref = [r for lo, part in zip(range(0, N_CHIPS, step), parts)
                for r in ccdformat.batch_frames(chips(lo, lo + step), part)]
    if len(rows_got) != len(rows_ref) or len(rows_got) != N_CHIPS:
        fail(f"{len(rows_got)} sharded vs {len(rows_ref)} reference chips")
    n_rows = 0
    for (cid_g, fg), (cid_r, fr) in zip(rows_got, rows_ref):
        if cid_g != cid_r:
            fail(f"chip order {cid_g} != {cid_r}")
        for table in ("chip", "pixel", "segment"):
            a, b = fg[table], fr[table]
            if set(a) != set(b):
                fail(f"{table} columns differ for chip {cid_g}")
            for col in a:
                if not same_column(a[col], b[col]):
                    fail(f"{table}.{col} differs for chip {cid_g}")
            n_rows += len(next(iter(a.values())))
    cc = obs_metrics.get_registry().snapshot()["counters"]
    say(f"4-chip: sharded driver path == single-device kernel on device 0, "
        f"{len(rows_got)} chips, {n_rows} rows identical; chips per device "
        f"{per_device}; sharded {t_sharded:.3f} s vs single-device "
        f"{t_single:.3f} s (smoke figures); compile_cache_hits="
        f"{cc.get('compile_cache_hits', 0)} compile_cache_misses="
        f"{cc.get('compile_cache_misses', 0)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "firebird_tpu")):
        fail(f"no firebird_tpu package next to {__file__}")
    sys.path.insert(0, ROOT)

    devs = device_phase(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(devs)
    else:
        main_phase(devs)
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.3f} s")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
