"""Streaming driver: bootstrap, checkpoint, apply new acquisitions, publish.

The reference's only operating mode is a full rerun of ``ccd.detect`` over
the whole archive (ccdc/pyccd.py:171-183).  ccd/incremental.py implements
the hot path that avoids that — extend each pixel's open tail segment by
one acquisition, re-testing change probability only; this driver makes it
operational:

- **bootstrap**: first run per chip does batch detection over ``acquired``,
  persists the normal chip/pixel/segment frames, and seeds a per-chip
  :class:`~firebird_tpu.ccd.incremental.StreamState` checkpoint in the
  stream statestore (tile-packed crash-safe slot files by default —
  streamops/statestore.py, docs/STREAMING.md).
- **update**: later runs fetch the chip, apply only observations past the
  checkpoint's horizon through ``incremental.step`` (one jitted [P]-wide
  step each), and re-publish the open tail segments' rows — same sday key,
  advanced eday/chprob — as keyed upserts.
- **repair**: pixels whose tail broke are only re-initialized by a batch
  rerun (``StreamState.needs_batch``); they roll up per chip into
  idempotent ``repair`` jobs on the fleet queue (alerts/repair.py — at
  most one open job per chip), and the summary still reports the count.
- **alerting**: a tail break confirmed by an update (``break_day``
  0→>0) appends one durable record to the alert log
  (firebird_tpu.alerts, docs/ALERTS.md) BEFORE the checkpoint saves —
  a crash between the two re-applies the delta on resume and the
  (pixel, break_day) dedup key absorbs the re-emission, so alerts are
  exactly-once and never lost.

Checkpoint contents are the StreamState arrays plus the tail segments'
identity (sday, curqa), the design anchor, and the horizon (last ingested
ordinal day).
"""

from __future__ import annotations

import concurrent.futures as cf
import time

import jax.numpy as jnp
import numpy as np

from firebird_tpu import grid
from firebird_tpu.alerts import log as alerts_log
from firebird_tpu.alerts import repair as alerts_repair
from firebird_tpu.ccd import format as ccdformat
from firebird_tpu.ccd import harmonic, incremental, kernel, params
from firebird_tpu.ccd.sensor import LANDSAT_ARD
from firebird_tpu.config import Config
from firebird_tpu.driver import core as dcore
from firebird_tpu.ingest import pack
from firebird_tpu.obs import jsonlog, logger
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.obs import report as obs_report
from firebird_tpu.obs import server as obs_server
from firebird_tpu.obs import spool as obs_spool
from firebird_tpu.obs import tracing
from firebird_tpu.streamops import statestore as sstore_mod
from firebird_tpu.utils import dates as dt
from firebird_tpu.utils.fn import partition_all, take

# Checkpoint plumbing lives in streamops/statestore.py now — ONE
# serialization/path/crash-safety implementation shared by this driver,
# the repair path, and the fleet (PR 13 deleted the duplicated copies).
# The names below stay as aliases for the legacy (.npz) layout's
# direct users (tests, tools).
_STATE_FIELDS = sstore_mod.STATE_FIELDS
_SIDE_FIELDS = sstore_mod.SIDE_FIELDS
state_dir = sstore_mod.state_dir
save_state = sstore_mod.save_state
load_state = sstore_mod.load_state


def _tail_identity(one: kernel.ChipSegments) -> tuple[np.ndarray, np.ndarray]:
    """(sday, curqa) of each pixel's last segment — the open tail whose row
    the stream will keep re-publishing under the same (sday, px, py) key."""
    nseg = np.asarray(one.n_segments, np.int64)
    # clip to buffer capacity: guards raw check_capacity=False results
    last = np.minimum(np.maximum(nseg - 1, 0), one.seg_meta.shape[-2] - 1)
    meta = np.asarray(one.seg_meta, np.float64)[np.arange(nseg.shape[0]), last]
    return meta[:, 0], meta[:, 4].astype(np.int64)


def publish_frame(packed, st: incremental.StreamState, side: dict) -> dict:
    """Active pixels' updated tail segments as a segment-table frame.

    Same row contract as format.chip_frames; the (cx,cy,px,py,sday,eday)
    key matches the bootstrap row only while eday is unchanged — advancing
    eday upserts a new row for the same open segment, the same artifact a
    batch rerun over a longer acquired range produces (the reference's PK
    design, schema.cql:142, behaves identically).  Magnitudes publish as 0
    for unbroken tails and stay 0 on a stream-confirmed break until the
    cold-path batch rerun computes the residual medians.
    """
    cx, cy = (int(v) for v in packed.cids[0])
    a = np.asarray(st.active)
    idx = np.nonzero(a)[0]
    coords = packed.pixel_coords(0)[idx]
    anchor = float(side["anchor"])

    broke = np.asarray(st.break_day)[idx] > 0
    eday = np.asarray(st.end_day, np.float64)[idx]
    bday = np.where(broke, np.asarray(st.break_day, np.float64)[idx], eday)
    chprob = np.where(
        broke, 1.0,
        np.asarray(st.n_exceed, np.float64)[idx] / params.PEEK_SIZE)
    curqa0 = np.asarray(side["curqa"], np.int64)[idx]
    # a confirmed break closes the tail: END drops, START survives, an
    # interior segment becomes INSIDE (kernel.py qa_brk rule)
    curqa = np.where(broke,
                     np.where(curqa0 & params.CURVE_QA_START,
                              params.CURVE_QA_START, params.CURVE_QA_INSIDE),
                     curqa0)
    coefs7, intercept = harmonic.to_pyccd_convention(
        np.asarray(st.coefs, np.float64)[idx], anchor)
    rmse = np.asarray(st.rmse, np.float64)[idx]

    R = idx.shape[0]
    ones = np.ones(R, bool)
    frame = {
        "cx": np.full(R, cx, np.int64), "cy": np.full(R, cy, np.int64),
        "px": coords[:, 0], "py": coords[:, 1],
        "sday": ccdformat._iso_col(np.asarray(side["sday"], np.float64)[idx]),
        "eday": ccdformat._iso_col(eday),
        "bday": ccdformat._iso_col(bday),
        "chprob": chprob,
        "curqa": ccdformat._int_or_none(curqa, ones),
        "rfrawp": np.full(R, None, object),
    }
    for b, p in enumerate(packed.sensor.store_prefixes):
        frame[f"{p}mag"] = np.zeros(R)
        frame[f"{p}rmse"] = rmse[:, b]
        frame[f"{p}int"] = intercept[:, b]
        col = np.empty(R, object)
        col[:] = list(coefs7[:, b])
        frame[f"{p}coef"] = col
    return frame


def _new_break_records(packed, st: incremental.StreamState,
                       bday0: np.ndarray, anchor: float) -> list[dict]:
    """Alert records for the pixels whose tail break confirmed in THIS
    update pass (``break_day`` 0→>0 against the pre-update snapshot).

    ``score`` is the confirmation change probability (n_exceed /
    PEEK_SIZE — 1.0 at confirm).  ``magnitude`` is the rmse/vario-
    normalized detection-band residual of each pixel's newest USABLE
    observation (QA clear/water, in sensor range — the step()'s own
    triage; a cloudy or fill-padded last acquisition must not publish a
    garbage magnitude) against the frozen tail model — a provisional
    deviation scale; the cold-path batch rerun computes the canonical
    per-band residual medians (the publish_frame magnitude contract).
    Pixels with no usable observation in the window report 0.0.
    """
    sensor = packed.sensor
    bday1 = np.asarray(st.break_day, np.float64)
    newly = (bday0 <= 0) & (bday1 > 0)
    idx = np.nonzero(newly)[0]
    if not idx.size:
        return []
    cx, cy = (int(v) for v in packed.cids[0])
    coords = packed.pixel_coords(0)[idx]
    score = np.asarray(st.n_exceed, np.float64)[idx] / params.PEEK_SIZE
    T = int(packed.n_obs[0])
    t = packed.dates[0][:T].astype(np.float64)
    qa = packed.qas[0][idx, :T].astype(np.int64)               # [N, T]
    fill = (qa >> params.QA_FILL_BIT) & 1 == 1
    usable = ((((qa >> params.QA_CLEAR_BIT) & 1 == 1)
               | ((qa >> params.QA_WATER_BIT) & 1 == 1)) & ~fill)
    y = packed.spectra[0][:, idx, :T].astype(np.float64)       # [B, N, T]
    opt = list(sensor.optical_bands)
    usable &= np.all((y[opt] > params.OPTICAL_MIN)
                     & (y[opt] < params.OPTICAL_MAX), axis=0)
    if sensor.thermal_bands:
        th = list(sensor.thermal_bands)
        usable &= np.all((y[th] > params.THERMAL_MIN)
                         & (y[th] < params.THERMAL_MAX), axis=0)
    any_usable = usable.any(axis=1)                            # [N]
    last_t = np.where(any_usable,
                      T - 1 - np.argmax(usable[:, ::-1], axis=1), 0)
    n_arange = np.arange(idx.shape[0])
    y_last = y[:, n_arange, last_t].T                          # [N, B]
    x_rows = harmonic.design_matrix(t, anchor,
                                    params.MAX_COEFS)[last_t]  # [N, 8]
    coefs = np.asarray(st.coefs, np.float64)[idx]
    pred = np.einsum("nbc,nc->nb", coefs, x_rows)
    den = np.maximum(np.asarray(st.rmse, np.float64),
                     np.asarray(st.vario, np.float64))[idx]
    det = list(sensor.detection_bands)
    rel = (y_last - pred)[:, det] / np.maximum(den[:, det], 1e-9)
    magnitude = np.where(any_usable,
                         np.sqrt(np.mean(rel ** 2, axis=1)), 0.0)
    return [{"cx": cx, "cy": cy,
             "px": int(coords[n, 0]), "py": int(coords[n, 1]),
             "break_day": float(bday1[i]), "score": float(score[n]),
             "magnitude": float(magnitude[n])}
            for n, i in enumerate(idx)]


def stream(x, y, acquired: str | None = None, number: int = 2500,
           cfg: Config | None = None, source=None, store=None,
           reset_metrics: bool = True, cids=None,
           published: float | None = None) -> dict:
    """Streaming incremental change detection over one tile.

    First run per chip bootstraps (batch detect + checkpoint); later runs
    apply only acquisitions newer than the checkpoint horizon.  Returns a
    summary dict: chips bootstrapped/updated, observations applied, and
    pixels flagged for the cold-path batch rerun.

    ``reset_metrics=False`` keeps the caller's metrics registry: a fleet
    worker (fleet/worker.py) hosts MANY jobs in one process, and a
    stream job must not wipe the worker's fleet counters the way a
    standalone run wipes the previous run's telemetry.

    ``cids`` scopes the pass to specific chips instead of the tile
    enumeration — the acquisition watcher's per-chip stream jobs
    (streamops/watcher.py).  ``published`` is the driving scene's
    publish timestamp (unix seconds): alerts this pass commits observe
    publish -> durable-append latency into the
    ``acquisition_to_alert_seconds`` histogram, the feed of the
    ``alert_freshness`` SLO's end-to-end leg (docs/STREAMING.md).
    """
    cfg = cfg or Config.from_env()
    acquired = acquired or dt.default_acquired()
    cfg = dcore.resolve_batching(cfg, acquired)
    log = logger("stream")
    # Run identity + run-scoped telemetry, same contract as the batch
    # driver (tracer starts below, just before the try/finally that
    # stops it).
    run_id = dcore.fleet_run_id()            # one id for the whole fleet
    jsonlog.set_run_context(run_id=run_id)   # setup log lines carry it too
    if reset_metrics:
        obs_metrics.reset_registry()
    # Compile-warm startup, same contract as the batch driver.  The
    # bootstrap dispatches at float32 with the capacity check ON (no
    # donation), so the warm shape must match that variant.
    dcore.setup_compile_cache()
    warm = dcore.warm_start(cfg, acquired, dtype=jnp.float32, donate=False) \
        if dcore._on_accelerator() else None
    # Same robustness plumbing as the batch driver (one code path:
    # dcore.robustness_setup): fault-plan proxies, shared retry budget +
    # ingest breaker, store-write retries, per-chip quarantine.
    source, store, writer, policy, breaker, quarantine = \
        dcore.robustness_setup(cfg, run_id, source=source, store=store)
    # The stream checkpoint store (streamops/statestore.py): tile-packed
    # slot files by default, with read-through migration from legacy
    # per-chip .npz; FIREBIRD_STREAM_STATESTORE=npz keeps the old layout.
    sstore = sstore_mod.open_statestore(cfg)
    # The durable alert log (firebird_tpu.alerts): None when alerting is
    # off or the store has no file-backed "next to".  An unopenable log
    # degrades alerting, never detection — breaks still publish to the
    # segment table either way.
    alog = None
    if cfg.alerts_enabled:
        apath = alerts_log.alert_db_path(cfg)
        if apath is not None:
            try:
                alog = alerts_log.AlertLog(apath)
            except Exception as e:
                log.error("alert log %s unavailable (%s: %s) — alert "
                          "emission disabled for this run", apath,
                          type(e).__name__, e)

    tile = grid.tile(x=x, y=y)
    if cids is None:
        cids = dcore.host_shard(list(take(number, grid.chips(tile))))
    else:
        # Watcher-scoped pass: exactly the scene's affected chips, no
        # host sharding (the fleet queue already spread the work).
        cids = [tuple(int(v) for v in c) for c in cids]
    log.info("streaming tile h=%s v=%s: %d chips (acquired %s, state "
             "%s:%s, alerts %s)", tile["h"], tile["v"], len(cids),
             acquired, sstore.backend, sstore_mod.state_dir(cfg),
             alog.path if alog is not None else "off")
    summary = dict(bootstrapped=0, updated=0, obs_applied=0,
                   pixels_need_batch=0, alerts_emitted=0,
                   alerts_deduped=0, repair_jobs_enqueued=0,
                   state_voided=0)
    # Per-chip needs_batch rollup: the update loop fills it (serial), the
    # repair scheduler turns it into fleet jobs at end of run.
    needs_by_chip: dict = {}

    # Chips whose fetch failed THIS run: a just-quarantined chip must not
    # be drained by the success path below (set add/membership is
    # GIL-atomic; the fetch pool writes, the serial loops read).
    failed_cids: set = set()

    def fetch_chip(cid, rng_iso):
        try:
            chip = dcore._with_retries(
                cfg, log, f"chip ({cid[0]},{cid[1]}) fetch",
                lambda: source.chip(cid[0], cid[1], rng_iso),
                policy=policy)
        except Exception as e:
            # Per-chip isolation, batch-driver semantics: dead-letter the
            # chip and keep streaming the rest of the tile.
            log.error("chip (%s,%s) failed after retries (%s: %s); "
                      "quarantined", cid[0], cid[1], type(e).__name__, e)
            quarantine.record(cid, e, attempts=cfg.fetch_retries + 1,
                              stage="stream")
            failed_cids.add(tuple(int(v) for v in cid))
            return None
        if chip.sensor != LANDSAT_ARD:
            raise ValueError(
                "stream publishes the reference's Landsat segment "
                f"schema; got sensor {chip.sensor.name!r}")
        if not chip.dates.shape[0]:
            log.warning("chip (%s,%s): no acquisitions in %s; skipping",
                        cid[0], cid[1], rng_iso)
            return None
        return chip

    def fetch_packed(cid, rng_iso):
        chip = fetch_chip(cid, rng_iso)
        # pack() itself warns when the archive exceeds max_obs capacity
        # (oldest kept, newest truncated — for a stream that would freeze
        # the horizon forever).
        return None if chip is None else pack(
            [chip], bucket=cfg.obs_bucket, max_obs=cfg.max_obs)

    hi_iso = acquired.split("/")[1]
    boot = [c for c in cids if not sstore.exists(c)]
    upd = [c for c in cids if sstore.exists(c)]
    run_block = dict(kind="stream", run_id=run_id, host=jsonlog.HOST,
                     process_id=dcore._process_index(), tile_h=tile["h"],
                     tile_v=tile["v"], acquired=acquired, chips=len(cids))
    # The stream's progress unit is a chip (bootstrapped or updated), so
    # /progress tracks chips over the tile and every bootstrap batch /
    # update publish beats the watchdog.
    counters = obs_metrics.Counters()
    _, ops_srv, wd = dcore.start_ops(
        cfg, run_id, "stream", chips_total=len(cids), counters=counters,
        run_block=run_block, quarantine=quarantine, breaker=breaker,
        alerts=(None if alog is None else lambda: dict(
            alog.status(),
            run={k: summary[k] for k in ("alerts_emitted",
                                         "alerts_deduped",
                                         "pixels_need_batch",
                                         "repair_jobs_enqueued")})),
        streamops=sstore.status)
    tracer = tracing.start(run_id=run_id) \
        if tracing.wants_trace(cfg.trace) else None
    counters.start()   # rate clock from first productive work, not setup
    try:
        # --- bootstrap: batched, chip axis sharded over local devices ---
        # Same two data-parallel levels as the batch driver: host_shard
        # split the tile across processes above; detect_batch splits each
        # batch over this process's local device mesh (driver/core.py).
        # Streaming updates stay per-chip ([P]-wide steps, cheap); the
        # batch detection is where the device time goes.
        batches = list(partition_all(max(cfg.chips_per_batch, 1), boot))
        pad_to = cfg.chips_per_batch if len(batches) > 1 else None
        obs_server.set_stage("bootstrap")
        # Mirror of the batch driver's zero-stall loop (driver/core.py
        # detect_chunk): the prefetch thread fetches, packs, and STAGES
        # batch i+1's arrays onto the device while batch i computes; the
        # drain goes through the shared bulk-egress helpers (one
        # device_get + vectorized batch_frames) — one code path, one test
        # surface, for both drivers.
        # Per-batch TraceContext, carried across the prefetch hop (the
        # batch driver's contract, driver/core.py detect_chunk): spans,
        # queued writes, and JSON log lines of one bootstrap batch all
        # parent to one <run_id>/b<seq> id.  A fleet-job pass runs
        # under the WORKER's adopted context (the watcher's per-scene
        # id, fleet/worker.py) — inherit it instead of minting, so the
        # whole pass stays on the scene's cross-process causal chain.
        inherit = tracing.current_context()
        ctxs = [inherit
                or tracing.TraceContext(tracing.new_batch_id(run_id),
                                        run_id=run_id) for _ in batches]
        with cf.ThreadPoolExecutor(
                max_workers=max(cfg.input_parallelism, 1)) as ex, \
                cf.ThreadPoolExecutor(max_workers=1) as prefetch_ex:

            def prepare(bids, ctx):
                with tracing.activate(ctx):
                    with tracing.span("fetch", chips=len(bids),
                                      histogram=obs_metrics.histogram(
                                          "pipeline_fetch_seconds")):
                        fetched = list(ex.map(
                            lambda c: fetch_chip(c, acquired), bids))
                    # fetch_chip already logged/quarantined each dropped
                    # chip.
                    keep = [(cid, ch) for cid, ch in zip(bids, fetched)
                            if ch is not None]
                    if not keep:
                        return None
                    with tracing.span("pack", chips=len(keep),
                                      histogram=obs_metrics.histogram(
                                          "pipeline_pack_seconds")):
                        p = pack([ch for _, ch in keep],
                                 bucket=cfg.obs_bucket,
                                 max_obs=cfg.max_obs)
                    return keep, dcore.stage_batch(
                        p, jnp.float32, cfg.device_sharding, pad_to=pad_to)

            nxt = prefetch_ex.submit(prepare, batches[0], ctxs[0]) \
                if batches else None
            for i in range(len(batches)):
                prep = nxt.result()
                nxt = (prefetch_ex.submit(prepare, batches[i + 1],
                                          ctxs[i + 1])
                       if i + 1 < len(batches) else None)
                if prep is None:
                    continue
                keep, staged = prep
                with tracing.activate(ctxs[i]):
                    with tracing.span("dispatch", chips=staged.n_real,
                                      histogram=obs_metrics.histogram(
                                          "pipeline_dispatch_seconds")):
                        # capacity check ON (synchronous retry): staged
                        # args may be re-dispatched, so NOT donated.
                        seg, n_real = dcore.detect_batch(
                            staged.packed, jnp.float32,
                            cfg.device_sharding, pad_to=pad_to,
                            check_capacity=True, staged=staged,
                            compact=cfg.compact)
                    obs_server.batch_dispatched()
                    with tracing.span("drain", chips=n_real,
                                      histogram=obs_metrics.histogram(
                                          "pipeline_drain_seconds")):
                        host = dcore.fetch_results(seg)
                        kernel.record_occupancy(host)
                        dcore.write_batch_frames(staged.packed, host,
                                                 n_real, writer=writer)
                        for c in range(n_real):
                            cid = keep[c][0]
                            one = kernel.chip_slice(host, c)
                            st = incremental.StreamState.from_chip(one)
                            sday, curqa = _tail_identity(one)
                            T = int(staged.packed.n_obs[c])
                            side = dict(
                                sday=sday, curqa=curqa,
                                anchor=np.float64(staged.packed.dates[c][0]),
                                horizon=np.float64(
                                    staged.packed.dates[c][T - 1]))
                            summary["bootstrapped"] += 1
                            counters.add("chips")
                            sstore.save(cid, st, side)
                            quarantine.discard(cid)
                            summary["pixels_need_batch"] += int(
                                np.asarray(st.needs_batch).sum())
                obs_server.batch_done(n_real)

        # --- update: apply only acquisitions past each chip's horizon ---
        obs_server.set_stage("update")

        def update_one(cid) -> None:
            t_seen = time.monotonic()   # the freshness-SLO clock start
            try:
                st, side = sstore.load(cid)
            except sstore_mod.StateStoreError as e:
                # Unrecoverable checkpoint (every bank failed its
                # checksum — e.g. power loss persisted a commit header
                # before its payload).  Void the slot so `exists` turns
                # False and the NEXT stream run re-bootstraps the chip;
                # erroring here forever would leave the heal path
                # (bootstrap) permanently gated off by exists().
                log.error("chip (%s,%s): checkpoint unrecoverable (%s); "
                          "voided — the next stream run re-bootstraps",
                          cid[0], cid[1], e)
                sstore.void(cid)
                summary["state_voided"] += 1
                counters.add("chips")
                return
            horizon = float(side["horizon"])
            # fetch only the delta past the horizon — the whole point
            # of the hot path is not re-ingesting the archive (span only
            # around a real fetch: an up-to-date chip records nothing)
            if horizon < dt.to_ordinal(hi_iso):
                with tracing.span("fetch", chip=tuple(cid), delta=True):
                    p = fetch_packed(
                        cid, f"{dt.to_iso(int(horizon) + 1)}/{hi_iso}")
            else:
                p = None
            if p is not None:
                T = int(p.n_obs[0])
                t = p.dates[0][:T].astype(np.float64)
                new_idx = np.nonzero(t > horizon)[0]
                anchor = float(side["anchor"])
                # Pre-update break snapshot: the 0→>0 transition against
                # it is what emits alerts (host copy, immune to whatever
                # the step loop does to the state's buffers).
                bday0 = np.array(np.asarray(st.break_day), np.float64)
                with tracing.span("step", chip=tuple(cid),
                                  obs=int(new_idx.size)):
                    for ti in new_idx:
                        x_row = jnp.asarray(
                            incremental.design_row(float(t[ti]), anchor))
                        y_new = jnp.asarray(
                            p.spectra[0, :, :, ti].T.astype(np.float32))
                        qa_new = jnp.asarray(
                            p.qas[0, :, ti].astype(np.int32))
                        st = incremental.step(st, x_row, y_new, qa_new,
                                              float(t[ti]),
                                              sensor=p.sensor)
                if new_idx.size:
                    side = dict(side, horizon=np.float64(t[-1]))
                    # Alert BEFORE the checkpoint saves: a crash in the
                    # window between them re-applies this delta on
                    # resume and the (pixel, break_day) dedup absorbs
                    # the re-emission — the reverse order would LOSE the
                    # alert (horizon advanced, delta never re-fetched).
                    if alog is not None:
                        recs = _new_break_records(p, st, bday0, anchor)
                        if recs:
                            actx = tracing.current_context()
                            trace_id = actx.batch_id \
                                if actx is not None else None
                            with tracing.span("alert", chip=tuple(cid),
                                              alerts=len(recs)):
                                ins, dup = alog.append(recs, run_id=run_id,
                                                       trace=trace_id)
                            obs_metrics.histogram(
                                "alert_visible_seconds",
                                help="stream-update ingest start to "
                                     "durable alert commit (the "
                                     "alert_freshness SLO feed)").observe(
                                time.monotonic() - t_seen)
                            acq_to_alert = None
                            if published is not None:
                                # The END-TO-END freshness leg: scene
                                # publish (the watcher job carries the
                                # manifest timestamp) to durable alert
                                # append — queue wait, bootstrap deps,
                                # fetch and step all included.
                                acq_to_alert = max(
                                    time.time() - published, 0.0)
                                obs_metrics.histogram(
                                    "acquisition_to_alert_seconds",
                                    help="scene publish time to durable "
                                         "alert-log append (the "
                                         "end-to-end alert_freshness "
                                         "SLO feed; docs/STREAMING.md)"
                                ).observe(acq_to_alert)
                            # The causal chain's durable-append joint:
                            # carries the SAME measured freshness value
                            # the histogram observed, so the collector's
                            # critical-path breakdown decomposes exactly
                            # what was measured (obs/collect.py).
                            obs_spool.mark(
                                "alert_appended", trace=trace_id,
                                chip=list(int(v) for v in cid),
                                alerts=ins, deduped=dup,
                                published=published,
                                acq_to_alert=acq_to_alert)
                            summary["alerts_emitted"] += ins
                            summary["alerts_deduped"] += dup
                    with tracing.span("publish", chip=tuple(cid),
                                      histogram=obs_metrics.histogram(
                                          "stream_publish_seconds")):
                        writer.write("segment", publish_frame(p, st, side),
                                     key=tuple(cid))
                        sstore.save(cid, st, side)
                    summary["updated"] += 1
                    summary["obs_applied"] += int(new_idx.size)
            n_need = int(np.asarray(st.needs_batch).sum())
            summary["pixels_need_batch"] += n_need
            if n_need:
                needs_by_chip[tuple(int(v) for v in cid)] = n_need
            counters.add("chips")
            if tuple(int(v) for v in cid) not in failed_cids:
                quarantine.discard(cid)

        for cid in upd:
            # The stream's update unit of work is a chip: one
            # TraceContext each, so the delta fetch, publish write, and
            # any failure log line join on one id (the batch driver's
            # per-batch contract at chip granularity).  Under a fleet
            # job the worker's adopted per-scene context wins — the
            # update's spans and alert rows stay on the scene's chain.
            with tracing.activate(inherit or tracing.TraceContext(
                    tracing.new_batch_id(run_id), run_id=run_id)):
                update_one(cid)
            # Per-chip progress beat: updates are host-cheap, so the
            # watchdog's liveness unit here is a processed chip.
            obs_server.batch_done(1)
        # Cold-path repair scheduling (alerts/repair.py): the flagged
        # pixels become idempotent fleet jobs — at most one open job per
        # chip — instead of a count an operator has to act on.  A
        # scheduling failure degrades to the count-only summary.
        obs_metrics.gauge(
            "repair_pixels_pending",
            help="pixels flagged needs_batch awaiting a cold-path "
                 "repair").set(sum(needs_by_chip.values()))
        # Independent of the alert LOG: FIREBIRD_ALERTS=0 darkens the
        # feed, not the cold-path repair loop (docs/ALERTS.md knobs).
        if cfg.alert_repair and needs_by_chip:
            try:
                jids = alerts_repair.schedule_repairs(
                    cfg, needs_by_chip, acquired=acquired, run_id=run_id)
                summary["repair_jobs_enqueued"] = len(jids)
            except Exception as e:
                log.error("repair scheduling failed (%s: %s) — "
                          "needs_batch debt stays count-only",
                          type(e).__name__, e)
        obs_server.set_stage("flush")
        writer.flush()
    finally:
        obs_server.set_stage("finalize")
        writer.close()
        sstore.close()
        if alog is not None:
            alog.close()
        if warm is not None:       # collect warm-compile counters if done
            warm.join(timeout=5.0)
        summary["quarantined"] = len(quarantine)
        if summary["quarantined"]:
            log.warning("%d chips in quarantine (%s) — the next stream "
                        "run retries them", summary["quarantined"],
                        quarantine.path or "in-memory")
        for k, v in summary.items():
            obs_metrics.gauge(f"stream_{k}").set(v)
        if tracer is not None:
            tracing.stop()
        paths = obs_report.finish_run(
            cfg, tracer=tracer, run_counters=counters.snapshot(),
            run=dict(run_block, **summary))
        if paths:
            log.info("observability artifacts: %s", paths)
        obs_server.set_stage("done")
        dcore.stop_ops(ops_srv, wd)
    log.info("stream complete: %s", summary)
    return summary
