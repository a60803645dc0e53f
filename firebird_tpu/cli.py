"""Command line interface (reference: ccdc/cli.py).

Commands mirror the reference's click group: `changedetection` and
`classification` with the same option names (cli.py:25-74).  The driver
wiring lands with the end-to-end slice; until then the commands surface a
clear error rather than silently doing nothing.
"""

from __future__ import annotations

import click

from firebird_tpu.ccd.sensor import SENSORS
from firebird_tpu.utils import dates


def context_settings():
    """Normalized (lower-cased) tokens, as the reference (cli.py:9-16)."""
    return dict(token_normalize_func=lambda x: x.lower())


def apply_platform(platform: str) -> None:
    """Pin THIS process's JAX platform before first use, leaving the
    environment its children inherit untouched (the fleet supervisor pins
    itself to 'cpu').  Everyone else selects a platform the plain way:
    ``JAX_PLATFORMS=cpu``."""
    import jax

    jax.config.update("jax_platforms", platform)


@click.group(context_settings=context_settings())
def entrypoint():
    """firebird_tpu — TPU-native LCMAP CCDC."""


@entrypoint.command()
@click.option("--x", "-x", required=True, type=float)
@click.option("--y", "-y", required=True, type=float)
@click.option("--acquired", "-a", required=False, default=None)
@click.option("--number", "-n", required=False, default=2500, type=int)
@click.option("--chunk_size", "-c", required=False, default=2500, type=int)
@click.option("--resume", "-r", is_flag=True, default=False,
              help="skip chips whose segments are already stored (assumes "
                   "the same acquired range as the stored run)")
@click.option("--trace", "-t", default=None,
              help="host span tracer output (Chrome-trace JSON, opens in "
                   "Perfetto): '1' writes trace.json next to the store, a "
                   "path writes there; overrides FIREBIRD_TRACE — see "
                   "docs/OBSERVABILITY.md")
@click.option("--ops-port", default=None, type=int,
              help="serve the live ops endpoints (/healthz /readyz "
                   "/metrics /progress /report) on this port for the "
                   "duration of the run; overrides FIREBIRD_OPS_PORT — "
                   "off (no port bound) when neither is set")
@click.option("--faults", default=None,
              help="deterministic fault-injection plan for chaos drills, "
                   "e.g. 'ingest:p=0.05,seed=7;store:after=40,brownout=3' "
                   "(docs/ROBUSTNESS.md); overrides FIREBIRD_FAULTS — "
                   "off (no injection, no proxies) when neither is set")
@click.option("--profile", default=None, type=float,
              help="capture ONE automatic device-profile window of this "
                   "many seconds starting at the first dispatched batch "
                   "(artifact under <store dir>/device_profile/; further "
                   "windows via POST /profile on the ops endpoint); "
                   "overrides FIREBIRD_PROFILE — see docs/OBSERVABILITY.md")
@click.option("--slo", default=None,
              help="SLO spec 'name=target;...' evaluated at /slo and in "
                   "the obs report (objectives: batch_p95, serve_p99, "
                   "freshness; '0' disables); overrides FIREBIRD_SLO")
def changedetection(x, y, acquired, number, chunk_size, resume, trace,
                    ops_port, faults, profile, slo):
    """Run change detection for a tile and save results to the store."""
    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.parallel import init_distributed

    # Multi-host bring-up when the standard env vars are present
    # (JAX_COORDINATOR_ADDRESS etc.); no-op single-process.  Only this
    # command shards over hosts (driver host_shard) — classification is
    # not host-sharded, and initialize() blocks until every process
    # joins, so it must not run from the group callback.
    init_distributed()
    overrides = {k: v for k, v in
                 (("trace", trace), ("ops_port", ops_port),
                  ("faults", faults), ("profile", profile),
                  ("slo", slo)) if v is not None}
    return core.changedetection(
        x=x, y=y,
        acquired=acquired or dates.default_acquired(),
        number=number, chunk_size=chunk_size, resume=resume,
        cfg=Config.from_env(**overrides) if overrides else None,
    )


@entrypoint.command()
@click.option("--x", "-x", required=True, type=float)
@click.option("--y", "-y", required=True, type=float)
@click.option("--msday", "-s", required=True, type=int)
@click.option("--meday", "-e", required=True, type=int)
@click.option("--acquired", "-a", required=False, default=None)
def classification(x, y, msday, meday, acquired):
    """Train on the 3x3 tile neighborhood and classify the tile."""
    from firebird_tpu.driver import core

    return core.classification(
        x=x, y=y, msday=msday, meday=meday,
        acquired=acquired or dates.default_acquired(),
    )


@entrypoint.command()
def products():
    """List the products that can be run (ref `ccdc-products`,
    docs/faq.rst:63-67)."""
    from firebird_tpu import products as prod

    for name in prod.available():
        click.echo(name)


def _parse_bounds(bounds) -> list[tuple[float, float]]:
    out = []
    for b in bounds:
        x, y = b.split(",")
        out.append((float(x), float(y)))
    return out


@entrypoint.command()
@click.option("--bounds", "-b", multiple=True, required=True,
              help="x,y projection point; repeat to extend the area")
@click.option("--products", "-p", "product_names", multiple=True,
              required=True, help="product name; repeat for several")
@click.option("--product_dates", "-d", multiple=True, required=True,
              help="ISO query date; repeat for several")
@click.option("--acquired", "-a", required=False, default=None,
              help="ISO8601 range; chips lacking stored segments are "
                   "detected over it first")
@click.option("--clip", is_flag=True, default=False,
              help="mask pixels outside the bounds polygon")
def save(bounds, product_names, product_dates, acquired, clip):
    """Compute and save product rasters (ref `ccdc-save`,
    docs/faq.rst:38-109 — the 0.5 capability dropped by 1.0)."""
    from firebird_tpu import products as prod

    return prod.save(bounds=_parse_bounds(bounds), products=product_names,
                     product_dates=product_dates, acquired=acquired,
                     clip=clip)


@entrypoint.command()
@click.option("--x", "-x", required=True, type=float)
@click.option("--y", "-y", required=True, type=float)
@click.option("--acquired", "-a", required=False, default=None)
@click.option("--number", "-n", required=False, default=2500, type=int)
@click.option("--trace", "-t", default=None,
              help="host span tracer output (see changedetection --trace)")
@click.option("--ops-port", default=None, type=int,
              help="live ops endpoints for the run (see changedetection "
                   "--ops-port)")
@click.option("--faults", default=None,
              help="fault-injection plan (see changedetection --faults)")
@click.option("--profile", default=None, type=float,
              help="auto device-profile window seconds (see "
                   "changedetection --profile)")
@click.option("--slo", default=None,
              help="SLO spec (see changedetection --slo)")
def stream(x, y, acquired, number, trace, ops_port, faults, profile, slo):
    """Streaming incremental change detection (no reference equivalent —
    its only mode is full reruns, ccdc/pyccd.py:171-183).  First run per
    chip bootstraps batch detection and a state checkpoint; later runs
    apply only new acquisitions and re-test change probability."""
    from firebird_tpu.config import Config
    from firebird_tpu.driver import stream as sdrv
    from firebird_tpu.parallel import init_distributed

    init_distributed()
    overrides = {k: v for k, v in
                 (("trace", trace), ("ops_port", ops_port),
                  ("faults", faults), ("profile", profile),
                  ("slo", slo)) if v is not None}
    return sdrv.stream(
        x=x, y=y, acquired=acquired, number=number,
        cfg=Config.from_env(**overrides) if overrides else None)


@entrypoint.command()
@click.option("--x", "-x", required=True, type=float)
@click.option("--y", "-y", required=True, type=float)
@click.option("--number", "-n", required=False, default=2500, type=int,
              help="chips of the tile the watcher covers (testing)")
@click.option("--acquired-start", default="1982-01-01",
              help="archive start date for the jobs' acquired ranges "
                   "(ends derive from each scene's date, half-open)")
@click.option("--interval", "-i", default=None, type=float,
              help="manifest poll interval seconds; overrides "
                   "FIREBIRD_WATCH_INTERVAL")
@click.option("--once", is_flag=True, default=False,
              help="one poll, print its summary JSON, exit (the "
                   "cron/test mode; the default is a standing loop)")
@click.option("--ops-port", default=None, type=int,
              help="live ops endpoints for the watcher (adds a "
                   "`streamops` block to /progress); overrides "
                   "FIREBIRD_OPS_PORT")
def watch(x, y, number, acquired_start, interval, once, ops_port):
    """Watch the configured source's acquisition manifest and keep the
    fleet queue fed: each new scene becomes idempotent per-chip
    ``stream`` jobs (at most one open per chip), with ``detect``
    bootstrap jobs dep'd ahead for chips that have no stream checkpoint
    yet.  Scene ids dedupe against a durable sqlite cursor, so a killed
    watcher's replacement resumes without double-enqueueing — see
    docs/STREAMING.md."""
    import json as _json
    import signal
    import threading

    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.obs import jsonlog
    from firebird_tpu.streamops import AcquisitionWatcher

    from firebird_tpu.obs import spool as obs_spool

    overrides = {"ops_port": ops_port} if ops_port is not None else {}
    cfg = Config.from_env(**overrides)
    watcher = AcquisitionWatcher(cfg, x, y, number=number,
                                 acquired_start=acquired_start)
    if once:
        obs_spool.arm(cfg, "watcher")
        try:
            summary = watcher.poll_once()
        finally:
            obs_spool.disarm()
            watcher.close()
        click.echo(_json.dumps(summary, indent=1))
        return
    run_id = jsonlog.new_run_id()
    run_block = {"kind": "watcher", "run_id": run_id,
                 "host": jsonlog.HOST, "tile_h": watcher.tile["h"],
                 "tile_v": watcher.tile["v"]}
    from firebird_tpu.obs import Counters

    _, srv, wd = core.start_ops(cfg, run_id, "watcher", chips_total=0,
                                counters=Counters(), run_block=run_block,
                                streamops=watcher.status)
    obs_spool.arm(cfg, "watcher", run_id)
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        summary = watcher.run(interval=interval, stop=stop)
    finally:
        obs_spool.disarm()
        core.stop_ops(srv, wd)
        watcher.close()
    click.echo(_json.dumps(summary, indent=1))


@entrypoint.command()
@click.option("--bounds", "-b", multiple=True, required=True,
              help="x,y projection point; repeat to extend the area")
@click.option("--shard", "-s", required=False, default=None,
              help="i/n: print only the i-th of n strided shards, for "
                   "splitting a fleet launch across workers")
def tiles(bounds, shard):
    """Enumerate tiles covering an area as h,v,ulx,uly,lrx,lry CSV rows.

    Plays the role of the reference's resources/conus.csv + deploy loop
    (one changedetection job per CSV row): generate the rows for any area,
    optionally pre-sharded, and feed any point inside each row's tile
    (e.g. its ulx,uly corner) to `changedetection`."""
    from firebird_tpu import grid

    recs = grid.tiles_for_bounds(_parse_bounds(bounds))
    if shard is not None:
        try:
            i, n = (int(v) for v in shard.split("/"))
        except ValueError as e:
            raise click.BadParameter(
                "shard must be i/n with 0 <= i < n") from e
        if not 0 <= i < n:
            raise click.BadParameter("shard must be i/n with 0 <= i < n")
        recs = recs[i::n]
    click.echo("h,v,ulx,uly,lrx,lry")
    for r in recs:
        click.echo(f"{r['h']},{r['v']},{r['ulx']:.0f},{r['uly']:.0f},"
                   f"{r['lrx']:.0f},{r['lry']:.0f}")


@entrypoint.command()
@click.option("--bounds", "-b", multiple=True, required=True,
              help="x,y projection point; repeat to extend the area")
@click.option("--products", "-p", "product_names", multiple=True,
              required=True, help="product name; repeat for several")
@click.option("--product_dates", "-d", multiple=True, required=True,
              help="ISO query date; repeat for several")
@click.option("--outdir", "-o", required=True,
              help="directory for the raster files")
@click.option("--format", "-f", "fmt", default="envi",
              type=click.Choice(["envi", "npy"]),
              help="envi: .dat+.hdr (opens in QGIS/GDAL); npy: .npy+.json")
@click.option("--sensor", "-s", "sensor_name", default="landsat-ard",
              type=click.Choice(sorted(SENSORS)),
              help="campaign sensor spec (chip/pixel geometry)")
def export(bounds, product_names, product_dates, outdir, fmt, sensor_name):
    """Export stored product rasters as georeferenced files.

    Mosaics the per-chip product rows (computed by `firebird save`) over
    the bounds area into one int32 raster per (product, date) and writes
    it to --outdir; chips with no stored row fill with -9999."""
    from firebird_tpu import export as exp

    for p in exp.export(product_names, product_dates,
                        _parse_bounds(bounds), outdir, fmt=fmt,
                        sensor=SENSORS[sensor_name]):
        click.echo(p)


@entrypoint.command()
@click.option("--x", "-x", required=True, type=float)
@click.option("--y", "-y", required=True, type=float)
@click.option("--acquired", "-a", required=False, default=None)
@click.option("--number", "-n", required=False, default=2500, type=int)
@click.option("--outdir", "-o", required=True,
              help="directory for the .npz chip archive")
@click.option("--aux", is_flag=True, default=False,
              help="also mirror the AUX layers (training inputs)")
def fetch(x, y, acquired, number, outdir, aux):
    """Mirror a tile's chips into a local file archive.

    Fetches from the configured source (FIREBIRD_SOURCE) and writes
    FileSource .npz files; later runs read them offline with
    FIREBIRD_SOURCE=file FIREBIRD_SOURCE_PATH=<outdir>."""
    from firebird_tpu.driver import core

    n, attempted = core.fetch(x=x, y=y, outdir=outdir, acquired=acquired,
                              number=number, aux=aux)
    click.echo(f"{n} chips written to {outdir}")
    if n < attempted:
        click.echo(f"WARNING: {attempted - n} chips failed permanently — "
                   "the archive is incomplete", err=True)
        raise SystemExit(3)


@entrypoint.command()
@click.option("--x", "-x", required=False, default=None, type=float)
@click.option("--y", "-y", required=False, default=None, type=float)
@click.option("--acquired", "-a", required=False, default=None)
@click.option("--n_pixels", "-n", required=False, default=100, type=int)
@click.option("--dtype", required=False, default="float64",
              type=click.Choice(["float32", "float64"]))
@click.option("--seed", required=False, default=0, type=int)
def validate(x, y, acquired, n_pixels, dtype, seed):
    """Audit kernel-vs-oracle parity on one chip's sampled pixels.

    Runs the accelerator kernel over the chip containing (x, y) (or a
    default synthetic chip), replays sampled pixels through the float64
    CPU oracle, and prints a JSON agreement report.  Exits non-zero if
    structural agreement (procedures, model counts, break/start/end days,
    masks) is not 100%."""
    import json as _json

    from firebird_tpu import validate as val

    report = val.validate(x=x, y=y, acquired=acquired, n_pixels=n_pixels,
                          dtype=dtype, seed=seed)
    click.echo(_json.dumps(report, indent=1))
    if not report["structural_agreement"]:
        raise SystemExit(2)


@entrypoint.command()
@click.option("--port", "-p", default=None, type=int,
              help="listen port; overrides FIREBIRD_SERVE_PORT "
                   "(default 8080); 0 binds an ephemeral port")
@click.option("--host", default=None,
              help="bind address; overrides FIREBIRD_SERVE_HOST "
                   "(default 0.0.0.0 — use 127.0.0.1 to stay host-local)")
@click.option("--cache-entries", default=None, type=int,
              help="in-memory cache bound (entries); overrides "
                   "FIREBIRD_SERVE_CACHE_ENTRIES")
@click.option("--cache-dir", default=None,
              help="disk spill tier for evicted cache entries; overrides "
                   "FIREBIRD_SERVE_CACHE_DIR — off when neither is set")
@click.option("--no-compute", is_flag=True, default=False,
              help="disable compute-on-miss: absent product rows answer "
                   "404 instead of running the products.save-path "
                   "computation (strictly read-only serving)")
@click.option("--read-only", is_flag=True, default=False,
              help="open the store as a mode=ro replica connection "
                   "(sqlite): this replica can never take the writer's "
                   "lock; implies --no-compute")
@click.option("--replica-id", default=None,
              help="stable changefeed replica id (cursor resume across "
                   "restarts); overrides FIREBIRD_SERVE_REPLICA — "
                   "default host:pid, which replays the feed on start")
@click.option("--pyramid-dir", default=None,
              help="quadkey tile-pyramid root for /v1/pyramid; "
                   "overrides FIREBIRD_SERVE_PYRAMID_DIR (default: "
                   "pyramid/ under the cache dir, else next to the "
                   "store)")
def serve(port, host, cache_entries, cache_dir, no_compute, read_only,
          replica_id, pyramid_dir):
    """Serve the query API over the configured results store.

    Endpoints: /v1/segments?cx=&cy=, /v1/pixel?x=&y=&date=,
    /v1/product/<name>?cx=&cy=&date=, /v1/tile/<name>?bounds=&date=,
    /v1/pyramid/<name>/<z>/<x>/<y>?date= (quadkey map tiles), plus
    /healthz and /metrics.  Cold product requests compute through the
    products.save path (once per key, coalesced) and persist, so the
    store warms as it serves; /v1/product, /v1/tile and /v1/pyramid
    carry strong ETags + Cache-Control so edge caches revalidate with
    304s.  A changefeed consumer tails the alert log + product_writes
    cursors so N replicas and a live writer stay coherent
    (docs/SERVING.md).  When the store has an alert log next to it, the
    change-alert feed mounts too: /v1/alerts (cursor pull),
    /v1/alerts/stream (SSE push), /v1/alerts/webhooks (POST registers a
    subscriber; delivery runs in the background from each subscriber's
    durable cursor).  See docs/SERVING.md and docs/ALERTS.md."""
    import signal
    import threading

    from firebird_tpu.alerts import AlertFeed, AlertLog, alert_db_path
    from firebird_tpu.config import Config
    from firebird_tpu.serve import api as serve_api
    from firebird_tpu.serve import changefeed as cflib
    from firebird_tpu.serve import pyramid as pyrlib
    from firebird_tpu.store import open_store

    overrides = {k: v for k, v in
                 (("serve_port", port), ("serve_host", host),
                  ("serve_cache_entries", cache_entries),
                  ("serve_cache_dir", cache_dir),
                  ("serve_replica", replica_id),
                  ("serve_pyramid_dir", pyramid_dir)) if v is not None}
    # --port 0 means "ephemeral bind", which Config rejects as a
    # deploy-time port; thread it past validation separately.
    bind_port = overrides.pop("serve_port", None)
    cfg = Config.from_env(**overrides)
    if bind_port is None:
        bind_port = cfg.serve_port
    store = open_store(cfg.store_backend, cfg.store_path, cfg.keyspace(),
                       read_only=read_only)
    # Mount the alert feed when this store has an alert log behind it
    # (docs/ALERTS.md): /v1/alerts endpoints + background webhook
    # delivery.  Unavailable/corrupt log degrades to a serve layer
    # without alerts, not a dead server.
    feed = None
    alog = None
    if cfg.alerts_enabled:
        apath = alert_db_path(cfg)
        if apath is not None:
            try:
                alog = AlertLog(apath)
                feed = AlertFeed(alog, cfg)
                feed.deliverer.start()
            except Exception as e:
                click.echo(f"WARNING: alert log {apath} unavailable "
                           f"({type(e).__name__}: {e}); serving without "
                           "/v1/alerts", err=True)
                feed = alog = None
    # Fanout rollup coordinator (docs/ALERTS.md "Fanout plane"): poll
    # the alert log, enqueue per-shard `fanout` fleet jobs that elastic
    # delivery workers drain.  Needs a fleet queue location; without
    # one (or with FIREBIRD_FANOUT=0) the serve layer degrades to the
    # flat in-process deliverer only.
    coordinator = None
    if alog is not None and cfg.fanout_enabled:
        try:
            from firebird_tpu.alerts.fanout import FanoutCoordinator
            from firebird_tpu.fleet.worker import make_queue

            coordinator = FanoutCoordinator(
                alog, make_queue(cfg), cfg).start()
        except Exception as e:
            click.echo(f"WARNING: fanout rollup unavailable "
                       f"({type(e).__name__}: {e}); webhook delivery "
                       "runs unsharded", err=True)
    # Quadkey tile pyramid (docs/SERVING.md): static versioned tiles
    # under the pyramid root; absent root -> /v1/pyramid answers 404.
    proot = pyrlib.pyramid_root(cfg)
    pyr = pyrlib.TilePyramid(
        proot, storage=pyrlib.pyramid_storage(cfg, proot)) \
        if proot else None
    # Changefeed consumer: this replica's cache-coherence loop — tail
    # the alert log + product_writes cursors, bump the touched chip
    # generations, stale-stamp pyramid ancestors, checkpoint into the
    # replica registry.  A corrupt feed db degrades to in-process-only
    # invalidation (the PR 5 behavior), not a dead server.
    consumer = None
    service = serve_api.ServeService(
        store, cfg, compute_on_miss=not no_compute and not read_only,
        alerts=feed, pyramid=pyr)
    try:
        fpath = cflib.changefeed_db_path(cfg)
        wfeed = cflib.ProductWrites(fpath) if fpath else None
        if wfeed is not None or alog is not None:
            consumer = cflib.ChangefeedConsumer(
                service.gens, feed=wfeed, alerts=alog,
                replica=cflib.default_replica_id(cfg),
                poll_sec=cfg.serve_feed_poll_sec).start()
            service.changefeed = consumer
    except Exception as e:
        click.echo(f"WARNING: changefeed unavailable "
                   f"({type(e).__name__}: {e}); serving with in-process "
                   "invalidation only", err=True)
    from firebird_tpu.obs import spool as obs_spool

    obs_spool.arm(cfg, "serve")
    srv = serve_api.start_serve_server(bind_port, service,
                                       host=cfg.serve_host)
    click.echo(f"serving {cfg.store_backend}:{cfg.store_path} "
               f"[{cfg.keyspace()}] on port {srv.port} (ctrl-c to stop)")
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    finally:
        obs_spool.disarm()
        srv.close()
        if coordinator is not None:
            coordinator.stop()
            coordinator.queue.close()
        if consumer is not None:
            consumer.stop()
        if feed is not None:
            feed.close()
        store.close()


@entrypoint.group()
def pyramid():
    """Quadkey tile-pyramid precompute (docs/SERVING.md): materialize
    the standard products as versioned static map tiles under the
    pyramid root, so /v1/pyramid hot traffic is a file read."""


@pyramid.command("build")
@click.option("--bounds", "-b", multiple=True, required=True,
              help="x,y projection point; repeat to extend the area")
@click.option("--products", "-p", "product_names", multiple=True,
              required=True, help="product name; repeat for several")
@click.option("--product_dates", "-d", multiple=True, required=True,
              help="ISO query date; repeat for several")
@click.option("--levels", "-l", default=2, type=int,
              help="pyramid levels to materialize, base upward "
                   "(1 = base tiles only)")
@click.option("--refresh", is_flag=True, default=False,
              help="rebuild fresh tiles too (default: skip them)")
@click.option("--no-compute", is_flag=True, default=False,
              help="render only stored product rows; chips without one "
                   "render as fill instead of computing")
@click.option("--enqueue", is_flag=True, default=False,
              help="enqueue a fleet `pyramid` job instead of building "
                   "inline (any `firebird fleet work` worker executes "
                   "it)")
def pyramid_build(bounds, product_names, product_dates, levels, refresh,
                  no_compute, enqueue):
    """Materialize pyramid tiles over an area, bottom-up: base tiles
    render chips (byte-identical to `firebird save` rasters), each
    parent level downsamples its children 2x.  Run it over hot regions
    so map traffic never waits on a cold build — tiles farther than the
    compute-on-miss floor from the base ONLY serve precomputed."""
    import json as _json

    from firebird_tpu import products as prodlib
    from firebird_tpu.config import Config
    from firebird_tpu.serve import pyramid as pyrlib
    from firebird_tpu.store import open_store

    for p in product_names:
        if p not in prodlib.PRODUCTS:
            raise click.BadParameter(
                f"unknown product {p!r}; available: {prodlib.PRODUCTS}")
    cfg = Config.from_env()
    if enqueue:
        from firebird_tpu.fleet import make_queue

        queue = make_queue(cfg)
        try:
            jid = queue.enqueue("pyramid", {
                "bounds": [list(b) for b in _parse_bounds(bounds)],
                "products": list(product_names),
                "product_dates": list(product_dates),
                "levels": int(levels), "refresh": bool(refresh),
                "compute": not no_compute,
            }, max_attempts=cfg.fleet_max_attempts)
            click.echo(_json.dumps({"queue": queue.path, "job": jid}))
        finally:
            queue.close()
        return
    root = pyrlib.pyramid_root(cfg)
    if root is None:
        raise click.ClickException(
            "no pyramid root: set FIREBIRD_SERVE_PYRAMID_DIR (or use a "
            "file-backed store for the next-to-store default)")
    store = open_store(cfg.store_backend, cfg.store_path, cfg.keyspace())
    try:
        pyr = pyrlib.TilePyramid(
            root, pyrlib.store_read_chip(store, compute=not no_compute),
            storage=pyrlib.pyramid_storage(cfg, root))
        summary = pyr.build_area(list(product_names), list(product_dates),
                                 _parse_bounds(bounds), levels=levels,
                                 refresh=refresh)
    finally:
        store.close()
    click.echo(_json.dumps({"root": root, **summary}, indent=1))


@entrypoint.command()
@click.option("--x", "-x", required=False, default=None, type=float,
              help="with -y: also report this tile's chip progress")
@click.option("--y", "-y", required=False, default=None, type=float)
def status(x, y):
    """Inspect the configured results store: per-table row counts, chips
    with stored segments, quarantine state, the fleet queue, the alert
    log (depth, cursor, subscriber lag, open repair jobs), the serving
    fleet (changefeed replicas with cursor lag, pyramid tile census by
    level), and (with -x/-y) one tile's completion — the operational
    view behind `changedetection --resume`."""
    import collections
    import json as _json
    import os as _os

    from firebird_tpu import grid
    from firebird_tpu.config import Config
    from firebird_tpu.driver import quarantine as _quarantine
    from firebird_tpu.store import TABLES, open_store

    if (x is None) != (y is None):
        raise click.BadParameter("tile progress needs both -x and -y")
    cfg = Config.from_env()
    store = open_store(cfg.store_backend, cfg.store_path, cfg.keyspace())
    done = store.chip_ids("segment")
    out = {
        "backend": cfg.store_backend,
        "path": cfg.store_path,
        "keyspace": cfg.keyspace(),
        "tables": {t: store.count(t) for t in TABLES},
        "chips_with_segments": len(done),
    }
    # Dead-letter quarantine next to the store (driver/quarantine.py):
    # chips a run could not land, with their error classes — the part of
    # "how is my run doing" that table counts cannot show.
    qpath = _quarantine.quarantine_path(cfg)
    if qpath is not None and _os.path.exists(qpath):
        q = _quarantine.Quarantine.load(qpath)
        errors = collections.Counter(
            e.get("error", "unknown") for e in q.snapshot()["chips"].values())
        out["quarantine"] = {"path": qpath, "chips": len(q),
                             "errors": dict(sorted(errors.items()))}
    else:
        out["quarantine"] = {"path": qpath, "chips": 0, "errors": {}}
    # Fleet view (docs/ROBUSTNESS.md "Fleet scheduling"): when a fleet
    # queue sits next to this store, surface its depth by job type and
    # state, the active leases (age + holder host), and the dead-letter
    # ledger — the "how is my FLEET doing" half of this command.
    try:
        from firebird_tpu.fleet import FleetQueue, queue_path

        fpath = queue_path(cfg)
    except ValueError:
        fpath = None            # memory backend without FIREBIRD_FLEET_DB
    if fpath is not None and _os.path.exists(fpath):
        # Guarded like /progress's fleet block: a corrupt/locked/
        # read-only queue db must degrade THIS diagnostic command's
        # fleet section, not crash the store/quarantine output above.
        try:
            fq = FleetQueue(fpath, lease_sec=cfg.fleet_lease_sec)
            try:
                s = fq.status()
            finally:
                fq.close()
            sup = s.get("supervisor")
            out["fleet"] = {
                "path": fpath,
                "jobs": s["jobs"],
                "by_type": s["by_type"],
                "blocked": s["blocked"],
                "leases": s["leases"],
                "workers": s.get("workers", []),
                # Elastic control plane (docs/ROBUSTNESS.md "Elastic
                # operation"): target vs live, last scale decision +
                # reason, crash-loop parks — from the supervisor's
                # heartbeat in the queue db.
                "supervisor": None if sup is None else {
                    k: sup.get(k) for k in
                    ("pid", "host", "target", "live", "retiring", "min",
                     "max", "adopted_total", "parks", "drain_eta_sec",
                     "last_decision", "beat_age_sec")},
                "dead": len(s["dead"]),
                "dead_errors": s["dead_errors"],
                "fence_rejects": s["fence_rejects"],
            }
        except Exception as e:
            out["fleet"] = {"path": fpath,
                            "error": f"{type(e).__name__}: {e}"}
    # Alerts view (docs/ALERTS.md): log depth, latest cursor, per-
    # subscriber delivery lag, and the open repair-job count — guarded
    # like the fleet view: a locked/corrupt alert db degrades THIS
    # section, not the store/quarantine/fleet output above.
    from firebird_tpu.alerts import AlertLog, alert_db_path

    apath = alert_db_path(cfg)
    if apath is not None and _os.path.exists(apath):
        try:
            al = AlertLog(apath)
            try:
                s = al.status()
            finally:
                al.close()
            by_type = (out.get("fleet") or {}).get("by_type") or {}
            rep = by_type.get("repair", {})
            fan = by_type.get("fanout", {})
            out["alerts"] = {
                "path": apath,
                "depth": s["depth"],
                "latest_cursor": s["latest_cursor"],
                "subscribers": s["subscribers"],
                "open_repair_jobs": int(rep.get("pending", 0))
                + int(rep.get("leased", 0)),
                # Fanout plane (docs/ALERTS.md "Fanout plane"): index
                # size, policy mix, parked endpoints, the rollup
                # watermark, and the open shard-job count.
                "fanout": dict(s.get("fanout") or {},
                               open_jobs=int(fan.get("pending", 0))
                               + int(fan.get("leased", 0))),
            }
        except Exception as e:
            out["alerts"] = {"path": apath,
                             "error": f"{type(e).__name__}: {e}"}
    # Streamops view (docs/STREAMING.md): the packed checkpoint store's
    # per-tile slot occupancy + disk bytes, and the acquisition
    # watcher's durable cursor — guarded like the fleet/alerts views.
    try:
        from firebird_tpu.streamops import open_statestore, watch_db_path
        from firebird_tpu.streamops.watcher import SceneCursor

        sstore = open_statestore(cfg)
        try:
            scan = sstore.scan() if hasattr(sstore, "scan") \
                else sstore.status()
        finally:
            sstore.close()
        out["streamops"] = {"statestore": scan}
        try:
            wpath = watch_db_path(cfg)
        except ValueError:
            wpath = None
        if wpath is not None and _os.path.exists(wpath):
            cur = SceneCursor(wpath)
            try:
                out["streamops"]["watcher"] = cur.status()
            finally:
                cur.close()
    except Exception as e:
        out["streamops"] = {"error": f"{type(e).__name__}: {e}"}
    # Serving view (docs/SERVING.md): the replica fleet as the shared
    # changefeed db sees it (replica count, per-replica cursor lag) and
    # the pyramid's tile census by level — guarded like the fleet/
    # alerts views: a corrupt feed db or unreadable pyramid root
    # degrades THIS section, never the store output above.
    try:
        from firebird_tpu.serve import changefeed as _cflib
        from firebird_tpu.serve import pyramid as _pyrlib

        serving: dict = {}
        fpath = _cflib.changefeed_db_path(cfg)
        if fpath is not None and _os.path.exists(fpath):
            pw = _cflib.ProductWrites(fpath)
            try:
                reps = pw.replicas()
                serving["changefeed"] = {
                    "path": fpath,
                    "latest_cursor": pw.latest_cursor(),
                    "replicas_seen": len(reps),
                    "replicas": reps,
                }
            finally:
                pw.close()
        proot = _pyrlib.pyramid_root(cfg)
        pstorage = None if proot is None \
            else _pyrlib.pyramid_storage(cfg, proot)
        if pstorage is not None or (proot is not None
                                    and _os.path.isdir(proot)):
            serving["pyramid"] = _pyrlib.TilePyramid(
                proot, storage=pstorage).status()
        if serving:
            out["serving"] = serving
    except Exception as e:
        out["serving"] = {"error": f"{type(e).__name__}: {e}"}
    # Object-tier view (docs/ROBUSTNESS.md "Object tier"): key/manifest/
    # chunk census + orphan count over the configured object root —
    # guarded like every other section: an unreachable or corrupt object
    # root degrades THIS section honestly (census never raises; anything
    # else lands as an error entry), never the store output above.
    if getattr(cfg, "object_root", ""):
        try:
            from firebird_tpu.store import open_object_root

            ostore = open_object_root(cfg=cfg)
            try:
                out["object"] = {"backend": "local-dir",
                                 **ostore.census()}
            finally:
                ostore.close()
        except Exception as e:
            out["object"] = {"root": cfg.object_root,
                             "error": f"{type(e).__name__}: {e}"}
    # Error-budget view (docs/OBSERVABILITY.md "Error budgets"): the
    # multi-window burn verdict over the durable metric series next to
    # the telemetry spools — read-only here (no event recording; that
    # belongs to `firebird slo` and the ops endpoint), and guarded like
    # every other section.
    try:
        from firebird_tpu.obs import series as _series
        from firebird_tpu.obs import slo as _slo

        sstore = _series.open_store(cfg)
        if sstore is not None:
            try:
                sstore.ingest_spools()
                v = _slo.evaluate_budgets(
                    sstore.dir, cfg.slo_budget or None,
                    fast_sec=cfg.slo_fast_sec, slow_sec=cfg.slo_slow_sec,
                    burn_threshold=cfg.slo_burn)
            finally:
                sstore.close()
            out["budgets"] = {
                "ok": v["ok"], "violations": v["violations"],
                "budgets": {b["name"]: {
                    "ok": b["ok"], "budget_spent": b["budget_spent"],
                    "exhausted": b["exhausted"], "burning": b["burning"],
                    "fast_burn": b["fast_burn"],
                    "slow_burn": b["slow_burn"],
                    "empty_windows": b["empty_windows"],
                } for b in v["budgets"]},
            }
    except Exception as e:
        out["budgets"] = {"error": f"{type(e).__name__}: {e}"}
    if x is not None:
        tile = grid.tile(x, y)
        cids = [tuple(int(v) for v in c) for c in grid.chips(tile)]
        out["tile"] = {
            "h": tile["h"], "v": tile["v"],
            "chips_done": sum(1 for c in cids if c in done),
            "chips_total": len(cids),
        }
    click.echo(_json.dumps(out, indent=1))


@entrypoint.group()
def objectstore():
    """Chunked object-tier maintenance (docs/ROBUSTNESS.md "Object
    tier"): census and orphan-chunk scrub over the configured
    FIREBIRD_OBJECT_ROOT."""


def _open_object_root_or_die():
    from firebird_tpu.config import Config
    from firebird_tpu.store import open_object_root

    cfg = Config.from_env()
    if not cfg.object_root:
        raise click.ClickException(
            "no object root: set FIREBIRD_OBJECT_ROOT")
    return cfg, open_object_root(cfg=cfg)


@objectstore.command("scrub")
@click.option("--grace", default=None, type=float,
              help="minimum orphan age in seconds before reclaim "
                   "(default: FIREBIRD_OBJECT_SCRUB_GRACE_SEC); a live "
                   "writer's chunks-uploaded-manifest-pending window is "
                   "younger than any sane grace, so the race resolves "
                   "to keep")
@click.option("--dry-run", is_flag=True, default=False,
              help="report what would be reclaimed without deleting")
def objectstore_scrub(grace, dry_run):
    """Reclaim orphaned chunks: content-addressed chunks no retained
    manifest references — the debris a crash between chunk upload and
    manifest commit (or a torn-manifest fault) leaves behind.  Never
    touches referenced chunks or manifests, so it is safe to run
    against a live fleet."""
    import json as _json

    cfg, store = _open_object_root_or_die()
    try:
        rep = store.scrub(
            grace_sec=cfg.object_scrub_grace_sec if grace is None
            else grace, dry_run=dry_run)
    finally:
        store.close()
    click.echo(_json.dumps(rep, indent=1))


@objectstore.command("census")
def objectstore_census():
    """Key/manifest/chunk/orphan counts over the object root — the
    `firebird status` object section as a standalone command."""
    import json as _json

    _cfg, store = _open_object_root_or_die()
    try:
        click.echo(_json.dumps(store.census(), indent=1))
    finally:
        store.close()


@entrypoint.group()
def fleet():
    """Crash-tolerant multi-host work queue (docs/ROBUSTNESS.md "Fleet
    scheduling"): enqueue a tile plan once, run `firebird fleet work` on
    N hosts, and the lease/heartbeat/fence protocol makes worker death,
    zombies, and partitions boring."""


@fleet.command("enqueue")
@click.option("--tile", "-t", "tiles", multiple=True, required=True,
              help="x,y projection point inside a tile; repeat for a "
                   "multi-tile plan (any point inside the tile works — "
                   "`firebird tiles` emits candidates)")
@click.option("--acquired", "-a", required=False, default=None)
@click.option("--number", "-n", required=False, default=2500, type=int,
              help="chips per tile (testing)")
@click.option("--chunk-size", "-c", required=False, default=500, type=int,
              help="chips per detect job — the re-delivery granularity: "
                   "a dead worker forfeits at most one chunk")
@click.option("--msday", "-s", required=False, default=None, type=int,
              help="with --meday: also enqueue a classify job per tile, "
                   "blocked on that tile's detection")
@click.option("--meday", "-e", required=False, default=None, type=int)
@click.option("--products", "-p", "product_names", multiple=True,
              help="with --product-dates: enqueue product jobs per tile, "
                   "blocked on the latest upstream stage")
@click.option("--product-dates", "-d", multiple=True)
@click.option("--max-attempts", required=False, default=None, type=int,
              help="per-job attempt budget before dead-lettering; "
                   "overrides FIREBIRD_FLEET_MAX_ATTEMPTS")
def fleet_enqueue(tiles, acquired, number, chunk_size, msday, meday,
                  product_names, product_dates, max_attempts):
    """Enqueue a dependency-ordered multi-tile plan on the shared queue."""
    import json as _json

    from firebird_tpu.config import Config
    from firebird_tpu.fleet import enqueue_tile_plan, make_queue

    cfg = Config.from_env()
    queue = make_queue(cfg)
    try:
        summary = enqueue_tile_plan(
            queue, _parse_bounds(tiles),
            acquired=acquired or dates.default_acquired(), number=number,
            chunk_size=chunk_size, msday=msday, meday=meday,
            products=product_names, product_dates=product_dates,
            max_attempts=max_attempts or cfg.fleet_max_attempts)
        click.echo(_json.dumps({"queue": queue.path, **summary}, indent=1))
    finally:
        queue.close()


@fleet.command("work")
@click.option("--max-jobs", required=False, default=None, type=int,
              help="exit after this many executed jobs")
@click.option("--until-drained", is_flag=True, default=False,
              help="poll until every job is done or dead (default: exit "
                   "when nothing is claimable)")
@click.option("--forever", is_flag=True, default=False,
              help="standing worker: keep polling through an empty "
                   "queue until signalled — the steady-state streaming "
                   "fleet mode behind `firebird watch`")
@click.option("--hold-idle", is_flag=True, default=False,
              help="batch worker that polls through an empty queue "
                   "instead of exiting — how `fleet supervise` holds a "
                   "min-workers floor (retired by SIGTERM); unlike "
                   "--forever it still counts as batch drain capacity")
@click.option("--drain-on-term", is_flag=True, default=False,
              help="graceful drain: SIGTERM finishes the current lease "
                   "then exits cleanly instead of dying mid-job — how "
                   "`fleet supervise` retires workers")
@click.option("--poll", required=False, default=1.0, type=float,
              help="idle claim-poll interval, seconds")
@click.option("--ops-port", default=None, type=int,
              help="live ops endpoints for this worker (adds a `fleet` "
                   "block to /progress); overrides FIREBIRD_OPS_PORT")
def fleet_work(max_jobs, until_drained, forever, hold_idle, drain_on_term,
               poll, ops_port):
    """Run one fleet worker against the shared queue until it drains."""
    import json as _json
    import signal
    import threading

    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.fleet import FleetWorker, make_queue

    if sum((forever, until_drained, hold_idle)) > 1:
        raise click.BadParameter("--forever, --until-drained and "
                                 "--hold-idle are exclusive")
    overrides = {"ops_port": ops_port} if ops_port is not None else {}
    cfg = Config.from_env(**overrides)
    core.setup_compile_cache()
    queue = make_queue(cfg)
    worker = FleetWorker(cfg, queue, poll_sec=poll,
                         kind="stream" if forever else "batch")
    from firebird_tpu.obs import spool as obs_spool

    obs_spool.arm(cfg, "worker", worker.run_id)
    stop = threading.Event()
    if forever or hold_idle or drain_on_term:
        signal.signal(signal.SIGINT, lambda *_: stop.set())
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
    _, srv, wd = worker.start_ops()
    try:
        summary = worker.run(max_jobs=max_jobs,
                             until_drained=until_drained,
                             forever=forever or hold_idle, stop=stop)
    finally:
        obs_spool.disarm()
        core.stop_ops(srv, wd)
        queue.close()
    click.echo(_json.dumps(summary, indent=1))
    if summary.get("wedged"):
        from firebird_tpu.fleet import WEDGED_EXIT
        raise SystemExit(WEDGED_EXIT)


@fleet.command("supervise")
@click.option("--min", "min_workers", default=None, type=int,
              help="worker floor (0 = scale-to-zero); overrides "
                   "FIREBIRD_FLEET_MIN_WORKERS")
@click.option("--max", "max_workers", default=None, type=int,
              help="worker ceiling; overrides FIREBIRD_FLEET_MAX_WORKERS")
@click.option("--until-drained", is_flag=True, default=False,
              help="exit once every batch job is done or dead AND the "
                   "fleet has scaled back to zero (stream jobs don't "
                   "gate the exit; default: supervise until signalled)")
@click.option("--tick", default=1.0, type=float,
              help="control-loop interval, seconds")
@click.option("--grace", default=None, type=float,
              help="retiring worker SIGTERM->SIGKILL deadline, seconds; "
                   "overrides FIREBIRD_FLEET_GRACE_SEC")
@click.option("--log-dir", default=None,
              help="directory for spawned workers' stdout logs "
                   "(default: worker_logs/ next to the queue db)")
@click.option("--ops-port", default=None, type=int,
              help="live ops endpoints for the supervisor (the `fleet` "
                   "/progress block gains the supervisor view); "
                   "overrides FIREBIRD_OPS_PORT")
def fleet_supervise(min_workers, max_workers, until_drained, tick, grace,
                    log_dir, ops_port):
    """Autoscale a local worker fleet from queue pressure
    (docs/ROBUSTNESS.md "Elastic operation"): spawn `fleet work`
    subprocesses on sustained backlog, retire them gracefully after an
    idle window (scale-to-zero by default), park crash-looping slots
    with backoff, and adopt orphaned workers left by a dead supervisor
    instead of double-spawning over them."""
    import dataclasses
    import json as _json
    import os as _os
    import signal
    import threading

    from firebird_tpu.config import Config
    from firebird_tpu.driver import core
    from firebird_tpu.fleet import (Supervisor, make_queue,
                                    workers_hold_accelerator)
    from firebird_tpu.obs import Counters, jsonlog, logger

    # The supervisor runs no kernels: pin ITS jax to CPU so start_ops'
    # topology probe (jax.devices()) cannot acquire the TPU exclusively
    # — the spawned workers need it, and a supervisor holding it would
    # crash-loop every child at TPU bring-up.  In-process config only:
    # children inherit the untouched environment.
    apply_platform("cpu")
    overrides = {k: v for k, v in
                 (("fleet_min_workers", min_workers),
                  ("fleet_max_workers", max_workers),
                  ("fleet_grace_sec", grace),
                  ("ops_port", ops_port)) if v is not None}
    cfg = Config.from_env(**overrides)
    if workers_hold_accelerator() and cfg.fleet_max_workers > 1:
        # One accelerator worker per host: every further one would fail
        # to open the chip at bring-up, and the policy would crash-loop
        # spawning it.
        logger("fleet").warning(
            "workers will open this host's accelerator (JAX_PLATFORMS=%r): "
            "a chip serves one process, so max_workers %d -> 1; set "
            "JAX_PLATFORMS=cpu for a CPU fleet",
            _os.environ.get("JAX_PLATFORMS", ""), cfg.fleet_max_workers)
        cfg = dataclasses.replace(
            cfg, fleet_max_workers=1,
            fleet_min_workers=min(cfg.fleet_min_workers, 1))
    queue = make_queue(cfg)
    sup = Supervisor(
        cfg, queue,
        tick_sec=tick, grace_sec=cfg.fleet_grace_sec,
        log_dir=log_dir or _os.path.join(
            _os.path.dirname(_os.path.abspath(queue.path)), "worker_logs"))
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    run_block = {"kind": "fleet-supervisor", "run_id": sup.run_id,
                 "host": jsonlog.HOST, "queue": queue.path}
    _, srv, wd = core.start_ops(cfg, sup.run_id, "fleet-supervisor",
                                chips_total=0, counters=Counters(),
                                run_block=run_block, fleet=sup.fleet_block)
    from firebird_tpu.obs import spool as obs_spool

    obs_spool.arm(cfg, "supervisor", sup.run_id)
    try:
        summary = sup.run(until_drained=until_drained, stop=stop)
        if stop.is_set() and not sup.drain_out(
                timeout=cfg.fleet_grace_sec + 10.0):
            click.echo("warning: workers still draining at supervisor "
                       "exit (pids %s)" % sorted(sup.workers), err=True)
    except RuntimeError as e:
        # The succession guard: a LIVE supervisor already runs here.
        click.echo(f"error: {e}", err=True)
        raise SystemExit(3)
    finally:
        obs_spool.disarm()
        core.stop_ops(srv, wd)
        queue.close()
    click.echo(_json.dumps(summary, indent=1))
    if summary.get("wedged"):
        from firebird_tpu.fleet import WEDGED_EXIT
        raise SystemExit(WEDGED_EXIT)


@fleet.command("status")
def fleet_status():
    """Inspect the shared queue: depth by job type/state, active leases
    with age and holder, per-worker registry rows (pid, current lease,
    jobs acked), the supervisor's last heartbeat/decision, dead letters
    with error classes, and the stale-fence rejection tally.  A
    corrupt/locked queue db degrades to an error report, not a crash —
    the `firebird status` guard rule."""
    import json as _json

    from firebird_tpu.config import Config
    from firebird_tpu.fleet import make_queue, queue_path

    cfg = Config.from_env()
    try:
        queue = make_queue(cfg)
        try:
            click.echo(_json.dumps(queue.status(), indent=1))
        finally:
            queue.close()
    except Exception as e:
        try:
            path = queue_path(cfg)
        except ValueError:
            path = None
        click.echo(_json.dumps(
            {"path": path, "error": f"{type(e).__name__}: {e}"}, indent=1))
        raise SystemExit(3)


@fleet.command("requeue")
@click.argument("job_id", required=False, default=None, type=int)
@click.option("--dead", is_flag=True, default=False,
              help="requeue EVERY dead-lettered job")
def fleet_requeue(job_id, dead):
    """Return dead-lettered jobs to the queue with a fresh attempt
    budget (one JOB_ID, or all of them with --dead)."""
    from firebird_tpu.config import Config
    from firebird_tpu.fleet import make_queue

    if (job_id is None) == (not dead):
        raise click.BadParameter("pass a JOB_ID or --dead (not both)")
    queue = make_queue(Config.from_env())
    try:
        n = queue.requeue(job_id)
    finally:
        queue.close()
    click.echo(f"{n} job(s) requeued")


@entrypoint.group("trace")
def trace_group():
    """Fleet telemetry plane (docs/OBSERVABILITY.md "Fleet telemetry
    plane"): every fleet-role process spools its spans, causal-chain
    marks, and metric snapshots to disk; these commands are the read
    side."""


@trace_group.command("collect")
@click.option("--dir", "-d", "directory", default=None,
              help="spool directory to collect (default: the configured "
                   "FIREBIRD_TELEMETRY_DIR, else telemetry/ next to the "
                   "store)")
@click.option("--out", "-o", default=None,
              help="write the full collected artifact (Perfetto trace + "
                   "critical paths + merged metrics) to this JSON path; "
                   "default telemetry_collect.json in the spool dir")
def trace_collect(directory, out):
    """Merge every process's telemetry spool into ONE artifact: a
    process/thread-aware Perfetto trace where a scene's causal chain
    (watcher -> queue -> worker -> alert append -> webhook delivery)
    shares one filterable trace id across OS processes — including
    segments a SIGKILLed worker left behind — plus per-alert
    critical-path breakdowns of acquisition_to_alert_seconds and the
    fleet-merged metric view."""
    import json as _json
    import os as _os

    from firebird_tpu.config import Config
    from firebird_tpu.obs import collect as obs_collect
    from firebird_tpu.obs import spool as obs_spool

    cfg = Config.from_env()
    directory = directory or obs_spool.spool_dir(cfg)
    if directory is None:
        raise click.ClickException(
            "no spool directory: pass --dir or set FIREBIRD_TELEMETRY_DIR "
            "(the memory store backend has no 'next to the store' default)")
    doc = obs_collect.collect(directory)
    path = obs_collect.write(
        doc, out or _os.path.join(directory, "telemetry_collect.json"))
    click.echo(_json.dumps({
        "spool_dir": directory,
        "out": path,
        "processes": [f"{p['role']}:{p['pid']}" for p in doc["processes"]],
        "trace_events": len(doc["trace"]["traceEvents"]),
        "critical_paths": len(doc["critical_paths"]),
    }, indent=1))


def _top_frame(cfg) -> dict:
    """One `firebird top` sample: queue + alert + telemetry views, each
    guarded (a locked db or empty spool degrades its section, never the
    frame)."""
    import os as _os

    from firebird_tpu.obs import collect as obs_collect
    from firebird_tpu.obs import spool as obs_spool

    frame: dict = {}
    try:
        from firebird_tpu.fleet import make_queue

        queue = make_queue(cfg)
        try:
            frame["fleet"] = queue.status()
        finally:
            queue.close()
    except Exception as e:
        frame["fleet"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        from firebird_tpu.alerts import AlertLog, alert_db_path

        apath = alert_db_path(cfg)
        if apath is not None and _os.path.exists(apath):
            al = AlertLog(apath)
            try:
                frame["alerts"] = al.status()
            finally:
                al.close()
    except Exception as e:
        frame["alerts"] = {"error": f"{type(e).__name__}: {e}"}
    d = obs_spool.spool_dir(cfg)
    if d is not None and _os.path.isdir(d):
        try:
            events = obs_collect.read_events(d)
            snaps = obs_collect.latest_snapshots(events)
            frame["telemetry"] = {
                "spool_dir": d,
                "snapshots": snaps,
                "metrics": obs_collect.merge_snapshots(snaps),
            }
        except Exception as e:
            frame["telemetry"] = {"error": f"{type(e).__name__}: {e}"}
    # Durable history (obs/series.py): ingest the spools into the
    # series store (reader-side ingestion — the monitored processes
    # never pay for history) and pull the busiest metrics' last ~30
    # fine-resolution buckets for sparklines.  Off (no section) when
    # telemetry or the series store is disabled.
    try:
        import time as _time

        from firebird_tpu.obs import series as obs_series

        sstore = obs_series.open_store(cfg)
        if sstore is not None:
            try:
                sstore.ingest_spools()
                res = sstore.resolutions[0]
                now = _time.time()
                pts = sstore.points(res, now - 30 * res, now)
            finally:
                sstore.close()
            names: dict = {}
            for p in pts:
                m = p.get("m") or {}
                for n in (m.get("counters") or {}):
                    names.setdefault(n, "counter")
                for n in (m.get("histograms") or {}):
                    names.setdefault(n, "histogram")
            spark = {}
            for n, kind in names.items():
                vals = [v for _, v in
                        obs_series.bucket_series(pts, n, kind, res)]
                if any(v > 0 for v in vals):
                    spark[n] = {"kind": kind, "values": vals}
            frame["series"] = {
                "res_sec": res,
                "sparklines": dict(sorted(
                    spark.items(),
                    key=lambda kv: -sum(kv[1]["values"]))[:8]),
            }
    except Exception as e:
        frame["series"] = {"error": f"{type(e).__name__}: {e}"}
    return frame


_SPARK_GLYPHS = "▁▂▃▄▅▆▇█"


def _sparkline(values) -> str:
    """Unicode block sparkline, scaled to the window's max (pure)."""
    if not values:
        return ""
    hi = max(values)
    if hi <= 0:
        return _SPARK_GLYPHS[0] * len(values)
    top = len(_SPARK_GLYPHS) - 1
    return "".join(
        _SPARK_GLYPHS[min(int(v / hi * top + 0.5), top)] for v in values)


def _render_top(frame: dict) -> list[str]:
    """Render one top frame as terminal lines (pure — tested directly)."""
    import time as _time

    lines = [f"firebird top — {_time.strftime('%H:%M:%S')}"]
    fl = frame.get("fleet") or {}
    if "error" in fl:
        lines.append(f"fleet: unavailable ({fl['error']})")
    elif fl:
        jobs = fl.get("jobs") or {}
        lines.append(
            "fleet: " + " ".join(f"{k}={jobs.get(k, 0)}" for k in
                                 ("pending", "leased", "done", "dead"))
            + f" workers={len(fl.get('workers') or [])}"
            + f" leases={len(fl.get('leases') or [])}")
        sup = fl.get("supervisor")
        if sup:
            lines.append(
                f"supervisor: target={sup.get('target')} "
                f"live={sup.get('live')} "
                f"last={sup.get('last_decision')}")
    al = frame.get("alerts") or {}
    if "error" in al:
        lines.append(f"alerts: unavailable ({al['error']})")
    elif al:
        subs = al.get("subscribers") or []
        lag = max((s["lag"] for s in subs), default=0)
        lines.append(f"alerts: depth={al.get('depth')} "
                     f"cursor={al.get('latest_cursor')} "
                     f"subscribers={len(subs)} max_lag={lag}")
    tel = frame.get("telemetry") or {}
    if "error" in tel:
        lines.append(f"telemetry: unavailable ({tel['error']})")
    elif tel:
        import time as _t

        now = _t.time()
        for key in sorted(tel.get("snapshots") or {}):
            s = tel["snapshots"][key]
            lines.append(f"  {key:<24} snap {now - s['t']:5.1f}s ago")
        m = tel.get("metrics") or {}
        for n, v in sorted((m.get("counters") or {}).items()):
            lines.append(f"  {n:<40} {v:g}")
        for n, h in sorted((m.get("histograms") or {}).items()):
            if h.get("count"):
                lines.append(
                    f"  {n:<40} n={h['count']} p50={h['p50']:.3g}s "
                    f"p95={h['p95']:.3g}s max={h['max']:.3g}s")
    sr = frame.get("series") or {}
    if "error" in sr:
        lines.append(f"history: unavailable ({sr['error']})")
    elif sr.get("sparklines"):
        lines.append(f"history ({sr['res_sec']:g}s buckets, "
                     "rate per bucket):")
        for n, s in sorted(sr["sparklines"].items()):
            lines.append(f"  {n:<40} {_sparkline(s['values'])} "
                         f"max={max(s['values']):g}")
    if len(lines) == 1:
        lines.append("(no fleet, alert, or telemetry state found)")
    return lines


@entrypoint.command()
@click.option("--interval", "-i", default=2.0, type=float,
              help="refresh interval, seconds")
@click.option("--iterations", "-n", default=0, type=int,
              help="frames to render before exiting (0 = until ctrl-c) "
                   "— tests and scripts use -n 1")
def top(interval, iterations):
    """Live fleet console: one merged view of the queue (depth, leases,
    supervisor), the alert log (depth, subscriber lag), and the
    telemetry plane (per-process spool freshness plus fleet-merged
    counters and histogram percentiles re-derived from bucket counts,
    with sparkline history from the durable series store).  Reads only
    the fleet's on-disk state — run it anywhere the store is visible
    (the series store it refreshes lives next to the spools)."""
    import time as _time

    from firebird_tpu.config import Config

    cfg = Config.from_env()
    n = 0
    while True:
        click.echo("\n".join(_render_top(_top_frame(cfg))))
        n += 1
        if iterations and n >= iterations:
            break
        try:
            _time.sleep(interval)
        except KeyboardInterrupt:
            break
        click.echo("")


@entrypoint.command()
@click.option("--budget", "-b", default=None,
              help="objective spec override ('name[<thr]@target/window;"
                   "...'); default FIREBIRD_SLO_BUDGET, else the "
                   "built-in spec; '0' disables")
@click.option("--fast", default=None, type=float,
              help="fast burn window seconds (default "
                   "FIREBIRD_SLO_FAST_SEC)")
@click.option("--slow", default=None, type=float,
              help="slow burn window seconds (default "
                   "FIREBIRD_SLO_SLOW_SEC)")
@click.option("--burn", default=None, type=float,
              help="paging burn-rate threshold (default "
                   "FIREBIRD_SLO_BURN)")
@click.option("--record/--no-record", default=True,
              help="append budget state transitions to the durable "
                   "event log (slo_events.jsonl); --no-record is a "
                   "pure read")
def slo(budget, fast, slow, burn, record):
    """Evaluate the error budgets over the durable metric series.

    Ingests every telemetry spool under the spool home into the series
    store, evaluates each budget objective's multi-window burn rate
    (fast AND slow window over threshold pages; cumulative bad over
    the full window exhausts), records state transitions durably, and
    prints the verdict as JSON.  Exit status is CI-able: 0 = every
    budget ok (or no data yet), 1 = a budget burning or exhausted,
    2 = the series store is disabled.  Fleet verdicts come from the
    merged per-host series — never one host's view
    (docs/OBSERVABILITY.md "Error budgets")."""
    import json as _json

    from firebird_tpu.config import Config
    from firebird_tpu.obs import series as obs_series
    from firebird_tpu.obs import slo as obs_slo

    cfg = Config.from_env()
    store = obs_series.open_store(cfg)
    if store is None:
        click.echo(_json.dumps(
            {"disabled": True,
             "reason": "series store off (FIREBIRD_TELEMETRY / "
                       "FIREBIRD_SERIES / no spool home)"}))
        raise SystemExit(2)
    try:
        store.ingest_spools()
        kw = dict(
            fast_sec=fast if fast is not None else cfg.slo_fast_sec,
            slow_sec=slow if slow is not None else cfg.slo_slow_sec,
            burn_threshold=burn if burn is not None else cfg.slo_burn)
        spec = budget if budget is not None else (cfg.slo_budget or None)
        verdict = obs_slo.evaluate_and_record(store.dir, spec, **kw) \
            if record else obs_slo.evaluate_budgets(store.dir, spec, **kw)
    finally:
        store.close()
    click.echo(_json.dumps(verdict, indent=1))
    if not verdict.get("ok", True):
        raise SystemExit(1)


@entrypoint.command()
@click.option("--serve-url", default=None,
              help="serve base URL to probe from outside "
                   "(e.g. http://127.0.0.1:8080)")
@click.option("--landing", default=None,
              help="FileSource landing zone directory — arms the "
                   "end-to-end alert probe (synthetic scenes through "
                   "the real watcher/fleet/alert path)")
@click.option("--x", "-x", default=None, type=float,
              help="watched tile x (required with --landing)")
@click.option("--y", "-y", default=None, type=float,
              help="watched tile y (required with --landing)")
@click.option("--chip-offset", default=8, type=int,
              help="first probe chip's index in the tile chip list — "
                   "reserve probe chips INSIDE the watcher's -n window "
                   "but past the production chips")
@click.option("--chips", default=24, type=int,
              help="probe-chip reserve (each end-to-end alert probe "
                   "consumes one; the prober stops attempting when "
                   "spent)")
@click.option("--interval", "-i", default=None, type=float,
              help="seconds between probe cycles (default "
                   "FIREBIRD_PROBE_SEC)")
@click.option("--timeout", default=None, type=float,
              help="per-request timeout seconds (default "
                   "FIREBIRD_PROBE_TIMEOUT)")
@click.option("--cycles", "-n", default=0, type=int,
              help="probe cycles before exiting (0 = until "
                   "SIGTERM/ctrl-c)")
@click.option("--pyramid-product", default="ccd",
              help="product name for the pyramid tile probe")
def probe(serve_url, landing, x, y, chip_offset, chips, interval,
          timeout, cycles, pyramid_product):
    """Black-box canary prober (docs/OBSERVABILITY.md "The canary").

    A standalone process that exercises the REAL surfaces from outside
    — /v1 GETs with ETag revalidation, synthetic scenes through the
    watcher to SSE alerts, webhook round-trips through the deliverer —
    and spools probe_* metrics the error budgets read like any other
    host's.  Outage detection stops depending on the sick process
    reporting itself."""
    import json as _json
    import signal
    import threading as _threading

    from firebird_tpu.config import Config
    from firebird_tpu.obs import prober as obs_prober

    cfg = Config.from_env()
    try:
        p = obs_prober.CanaryProber(
            cfg, serve_url=serve_url, landing=landing, x=x, y=y,
            chip_offset=chip_offset, chips=chips, interval=interval,
            timeout=timeout, pyramid_product=pyramid_product)
    except ValueError as e:
        raise click.BadParameter(str(e))
    stop = _threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    p.arm()
    try:
        p.run(stop=stop, cycles=cycles or None)
    finally:
        p.close()
        click.echo(_json.dumps(p.status()))


@entrypoint.command(context_settings=dict(
    ignore_unknown_options=True, help_option_names=[]))
@click.argument("args", nargs=-1, type=click.UNPROCESSED)
def lint(args):
    """Run the repo's static contract checker (docs/STATIC_ANALYSIS.md).

    Four AST rule families: jax-hotpath (no host syncs / traced
    branches / static-arg drift in jitted code), knob-registry
    (FIREBIRD_* env vars vs config.KNOBS and the docs), metrics-contract
    (obs instruments vs naming/help/doc tables), and thread-ownership
    (`# guarded-by:` annotated state only touched under its lock).
    Exits nonzero on findings not absorbed by the committed baseline.
    All options (--json, --update-baseline, --rules, --list-rules, ...)
    pass through to `python -m firebird_tpu.analysis --help`."""
    from firebird_tpu.analysis import main as lint_main

    raise SystemExit(lint_main(list(args)))


@entrypoint.command()
@click.option("--keyspace", "-k", required=False, default=None,
              help="keyspace name; defaults to Config.keyspace() "
                   "(derived from input URLs + version)")
@click.option("--replication", "-r", required=False, default=1, type=int)
def schema(keyspace, replication):
    """Print the Cassandra DDL for the result tables as CQL.

    The reference ships this as resources/schema.cql and loads it with
    `make db-schema`; here the statements are generated from the table
    definitions (store.schema.TABLES) — pipe to cqlsh to load:
    `firebird schema | cqlsh`."""
    from firebird_tpu.config import Config
    from firebird_tpu.store.backends import cassandra_ddl

    if keyspace is None:
        keyspace = Config.from_env().keyspace()
    for stmt in cassandra_ddl(keyspace, replication):
        click.echo(stmt + ";")


if __name__ == "__main__":
    entrypoint()
