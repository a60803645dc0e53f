"""Driver orchestration: tile -> chunks -> prefetch -> device -> drain.

Replaces ccdc/core.py.  The reference's shape is preserved — snap the point
to a tile, enumerate its chips, `partition_all(chunk_size, take(number,
chips))`, run each chunk with failure isolation, persist chip/pixel/segment
(core.py:78-124) — but execution is host-orchestrated TPU dispatch instead
of Spark jobs: chips are fetched by a host thread pool (INPUT_PARTITIONS
semantics), packed into device batches, run through the CCD kernel, and
drained to the store by an async writer so egress overlaps compute.

Failure handling is per-CHIP, not per-chunk: a chip that exhausts its
(jittered, budgeted) fetch retries is dead-lettered to quarantine.json and
its chunk completes without it; kernel/store errors still fail the chunk
as a backstop (core.py:115-124 semantics) but dead-letter its chips too.
Because store writes are keyed upserts, ``--resume`` (gated by
run_manifest.json, draining the quarantine first) repairs any gap
(SURVEY.md §5 durability model; docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import concurrent.futures as cf
import contextlib
import dataclasses
import itertools
import os
import threading
import time
import traceback

import jax.numpy as jnp
import numpy as np

from firebird_tpu import faults as faultlib
from firebird_tpu import grid
from firebird_tpu import retry as retrylib
from firebird_tpu.ccd import format as ccdformat
from firebird_tpu.ccd import kernel
from firebird_tpu.config import Config
from firebird_tpu.driver import quarantine as qlib
from firebird_tpu.ingest import ChipmunkSource, FileSource, SyntheticSource, pack
from firebird_tpu.obs import Counters, jsonlog, logger
from firebird_tpu.obs import flightrec
from firebird_tpu.obs import metrics as obs_metrics
from firebird_tpu.obs import profiling as obs_profiling
from firebird_tpu.obs import report as obs_report
from firebird_tpu.obs import server as obs_server
from firebird_tpu.obs import tracing
from firebird_tpu.obs import watchdog as obs_watchdog
from firebird_tpu.store import AsyncWriter, open_store
from firebird_tpu.utils import dates as dt
from firebird_tpu.utils.fn import partition_all, take

# bfloat16 is deliberately absent: ordinal days (~730000) have a bf16 ulp of
# 4096 days, which would corrupt segment dates; bf16 belongs inside matmul
# precision hints, not the date-carrying compute dtype.
_DTYPES = {"float32": jnp.float32, "float64": jnp.float64}


def _process_index() -> int:
    """JAX process index for run identity; 0 when no backend is up."""
    import jax

    try:
        return jax.process_index()
    except Exception:
        return 0


# Lockstep sequence for run-id broadcast keys: every process of an SPMD
# fleet runs the same program, so the per-process counters agree (the
# same idiom as parallel.mesh._kv_seq).
_run_id_seq = itertools.count()


def fleet_run_id() -> str:
    """One run id for the WHOLE fleet launch.

    Single-process: a fresh id.  Multi-process: process 0 mints it and
    broadcasts through the jax.distributed coordination-service KV store,
    so every host's JSON log lines, report shard, and /progress payload
    carry the SAME id — the cross-host log join is one grep, not an
    out-of-band host table."""
    rid = jsonlog.new_run_id()
    try:
        import jax

        if jax.process_count() <= 1:
            return rid
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            return rid
        seq = next(_run_id_seq)
        if jax.process_index() == 0:
            client.key_value_set(f"fb/run_id/{seq}", rid)
            return rid
        return client.blocking_key_value_get(f"fb/run_id/{seq}", 60_000)
    except Exception:
        return rid           # a broken broadcast degrades to per-host ids


def _mesh_ready() -> bool:
    """The /readyz mesh half: True when no distributed mesh is expected
    (no coordinator configured), or when jax.distributed is actually up.
    An operator who exported JAX_COORDINATOR_ADDRESS but whose bring-up
    failed keeps /readyz at 503 instead of lying."""
    if not os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return True
    try:
        import jax

        return jax.distributed.is_initialized()
    except Exception:
        return False


def record_topology_metrics() -> None:
    """(Re-)record the fleet topology gauges on the CURRENT registry.

    init_distributed sets them at bring-up, but the drivers reset the
    registry per run — so every run re-records them here or /metrics and
    the fleet report would silently lose the topology."""
    import jax

    try:
        obs_metrics.gauge(
            "mesh_processes",
            help="jax.distributed process count").set(jax.process_count())
        obs_metrics.gauge(
            "mesh_global_devices",
            help="global device count").set(len(jax.devices()))
    except Exception:
        pass                   # no backend yet: nothing to record


def start_ops(cfg: Config, run_id: str, kind: str, *, chips_total: int,
              counters, run_block: dict, quarantine=None, breaker=None,
              fleet=None, alerts=None, streamops=None):
    """Bring up the run's live ops surface (shared by both drivers).

    Registers the run context for JSON logs, clears stale report shards
    from a previous run in a reused artifact directory, starts the stall
    watchdog when ``cfg.stall_sec`` asks for one, publishes a
    :class:`~firebird_tpu.obs.server.RunStatus` for the module-level
    progress hooks, and binds the HTTP endpoint ONLY when
    ``cfg.ops_port`` is set — the default run binds no port.  Returns
    (status, server, watchdog); tear down with :func:`stop_ops`.  If the
    port bind fails, everything already started is torn down before the
    error propagates — a half-up ops surface must not outlive the raise.
    """
    jsonlog.set_run_context(run_id=run_id, process_index=_process_index())
    obs_report.clear_stale_artifacts(cfg)
    record_topology_metrics()
    watchdog = None
    server = None
    try:
        # Crash flight recorder (FIREBIRD_FLIGHTREC ring size; 0 off):
        # armed for the run so an unhandled exception, watchdog stall,
        # or SIGTERM leaves postmortem.json next to the store.
        if cfg.flightrec > 0:
            flightrec.arm(flightrec.postmortem_path(cfg),
                          ring=cfg.flightrec, run_id=run_id,
                          fingerprint=qlib.config_fingerprint(cfg))
        # On-demand device profiler: POST /profile windows land next to
        # the store; FIREBIRD_PROFILE=<seconds> arms an automatic window
        # at the first dispatched batch.  Memory-backend runs have no
        # artifact dir and get no profiler (the endpoint answers 503).
        profiler = None
        art_dir = qlib._artifact_dir(cfg)
        if art_dir is not None:
            profiler = obs_profiling.set_active(obs_profiling.DeviceProfiler(
                os.path.join(art_dir, "device_profile")))
            if cfg.profile > 0:
                profiler.arm_auto(cfg.profile)
        if cfg.stall_sec > 0:
            watchdog = obs_watchdog.Watchdog(cfg.stall_sec).start()
        status = obs_server.set_status(obs_server.RunStatus(
            run_id, kind, chips_total=chips_total, counters=counters,
            watchdog=watchdog, run=run_block, mesh_up=_mesh_ready(),
            pipeline_depth=cfg.pipeline_depth, quarantine=quarantine,
            breaker=breaker, profiler=profiler, slo_spec=cfg.slo,
            fleet=fleet, alerts=alerts, streamops=streamops))
        if cfg.ops_port > 0:
            server = obs_server.start_ops_server(cfg.ops_port, status,
                                                 host=cfg.ops_host)
    except Exception:
        stop_ops(server, watchdog)
        raise
    return status, server, watchdog


def stop_ops(server, watchdog) -> None:
    """Tear down :func:`start_ops` state; never raises — ops teardown
    must not mask a run's real outcome.  Called from the drivers'
    ``finally``: when the run is unwinding on an exception, the flight
    recorder dumps its postmortem BEFORE disarming (the excepthook would
    otherwise fire after the recorder is gone)."""
    import sys

    if sys.exc_info()[0] is not None:
        flightrec.dump_if_armed("unhandled_exception", sys.exc_info()[1])
    try:
        if server is not None:
            server.close()
        if watchdog is not None:
            watchdog.stop()
        obs_profiling.close_active()
    except Exception as e:
        logger("change-detection").error("ops teardown failed: %s", e)
    finally:
        obs_profiling.set_active(None)
        flightrec.disarm()
        obs_server.clear_status()
        jsonlog.clear_run_context()


def make_source(cfg: Config, kind: str | None = None):
    """Source factory (cfg.source_backend): chipmunk | synthetic | file."""
    kind = kind or cfg.source_backend
    if kind == "chipmunk":
        return ChipmunkSource(cfg.ard_url,
                              band_parallelism=cfg.band_parallelism,
                              timeout=cfg.http_timeout)
    if kind == "synthetic":
        from firebird_tpu.ccd.sensor import SENSORS

        return SyntheticSource(seed=0, sensor=SENSORS[cfg.synth_sensor])
    if kind == "file":
        return FileSource(cfg.source_path)
    raise ValueError(f"unknown source backend: {kind!r}")


def make_aux_source(cfg: Config, kind: str | None = None):
    kind = kind or cfg.source_backend
    if kind == "chipmunk":
        return ChipmunkSource(cfg.aux_url,
                              band_parallelism=cfg.band_parallelism,
                              timeout=cfg.http_timeout)
    return make_source(cfg, kind)


def robustness_setup(cfg: Config, run_id: str, *, source=None, store=None):
    """The drivers' shared graceful-degradation bring-up (ONE code path
    for batch and stream): the (usually absent) fault plan wraps the
    failure seams, one retry budget + ingest circuit breaker are shared
    by every retry site, the async writer retries store writes, and the
    dead-letter quarantine carries poisoned chips across runs.  With
    FIREBIRD_FAULTS unset the wrap_* calls return their argument
    unchanged — nothing on the hot path.

    Returns (source, store, writer, policy, breaker, quarantine)."""
    plan = faultlib.FaultPlan.from_config(cfg)
    source = faultlib.wrap_source(source or make_source(cfg), plan)
    store = faultlib.wrap_store(
        store or open_store(cfg.store_backend, cfg.store_path,
                            cfg.keyspace()), plan)
    budget = retrylib.RetryBudget(cfg.retry_budget)
    breaker = retrylib.make_breaker(cfg)
    policy = retrylib.RetryPolicy.for_ingest(cfg, budget=budget,
                                             breaker=breaker)
    writer = faultlib.wrap_writer(
        AsyncWriter(store, workers=cfg.writer_threads,
                    retry=retrylib.RetryPolicy.for_store(cfg,
                                                         budget=budget)),
        plan)
    quarantine = qlib.Quarantine.load(qlib.quarantine_path(cfg),
                                      run_id=run_id)
    return source, store, writer, policy, breaker, quarantine


def _pad_target(n_chips: int, pad_to: int | None, use_mesh: bool,
                n_dev: int) -> int:
    """THE batch pad-target rule, shared by stage_batch, detect_batch,
    and predict_batch_shape (the warm-compile shape prediction would
    silently drift from real dispatch padding if this were duplicated):
    at least ``pad_to`` chips, rounded up to a device-count multiple when
    sharded."""
    target = max(pad_to or 0, n_chips)
    if use_mesh:
        target = -n_dev * (-target // n_dev)
    return target


def _pad_batch(packed, target: int):
    """Pad a PackedChips batch to `target` chips (repeating the last chip);
    returns (padded, real_count)."""
    from firebird_tpu.ingest.packer import PackedChips

    C = packed.n_chips
    if C >= target:
        return packed, C
    pad = target - C
    rep = lambda a: np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
    return PackedChips(cids=rep(packed.cids), dates=rep(packed.dates),
                       spectra=rep(packed.spectra), qas=rep(packed.qas),
                       n_obs=rep(packed.n_obs), sensor=packed.sensor), C


def host_shard(cids: list) -> list:
    """This host's slice of a chip-id list under multi-host execution.

    CCDC is embarrassingly parallel over chips, so multi-host scaling is
    pure data decomposition: after parallel.init_distributed each process
    takes a strided slice and runs the normal per-host loop against its
    local devices; the keyed store upserts make the union of all hosts'
    writes identical to a single-host run (the reference instead scaled by
    adding Spark executors, README.rst:11 "2000 cores").  Single-process
    runs return the list unchanged.
    """
    import jax

    n = jax.process_count()
    if n <= 1:
        return cids
    i = jax.process_index()
    logger("change-detection").info(
        "multi-host: process %d/%d takes %d of %d chips",
        i, n, len(cids[i::n]), len(cids))
    return cids[i::n]


def estimate_obs(acquired: str, cfg: Config) -> int:
    """Conservative observation-count estimate for an acquired range:
    two-satellite 8-day effective cadence over the span, rounded/capped
    by the packer's own capacity rule (bucket_capacity — max_obs=0 means
    uncapped there, so the estimate must not treat it as a cap)."""
    from firebird_tpu.ingest.packer import bucket_capacity

    lo, hi = dt.acquired_range(acquired)
    t = (max(hi - lo, 0) // 8) + 8
    return bucket_capacity(t, max(cfg.obs_bucket, 1), cfg.max_obs)


def auto_chips_per_batch(cfg: Config, acquired: str, device=None) -> int:
    """Size the device batch from the accelerator's memory budget.

    VERDICT r1 weak #5: chips_per_batch was a static config while the
    working set scales with T.  With cfg.chips_per_batch <= 0 ("auto"),
    the driver fits  budget = 60% of the device's bytes_limit  against
    kernel.working_set_bytes(T_est) per chip.  Devices that report no
    memory stats (CPU) fall back to the static default.
    """
    import jax

    dev = device if device is not None else jax.local_devices()[0]
    try:
        stats = dev.memory_stats() or {}
    except Exception:
        stats = {}
    limit = stats.get("bytes_limit")
    fallback = Config.chips_per_batch
    if not limit:
        return fallback
    t_est = estimate_obs(acquired, cfg)
    dtype_bytes = 4 if cfg.dtype == "float32" else 8
    per = kernel.working_set_bytes(t_est, dtype_bytes=dtype_bytes)
    # Pipeline-depth residency: each in-flight batch beyond the one
    # computing pins its full-capacity result buffers until its drain
    # (the egress diet shrinks the wire, NOT this residency), so the
    # deeper default depth must shrink the batch, not blow HBM.
    per += (max(cfg.pipeline_depth, 1) - 1) * kernel.result_bytes(
        t_est, dtype_bytes=dtype_bytes)
    n = max(int(limit * 0.6 / per), 1)
    logger("change-detection").info(
        "auto chips_per_batch: T~%d, %.2f GB/chip (incl. depth-%d "
        "in-flight results) against %.1f GB device limit -> %d "
        "chips/batch", t_est, per / 1e9, cfg.pipeline_depth, limit / 1e9,
        n)
    return n


def resolve_batching(cfg: Config, acquired: str) -> Config:
    """cfg with chips_per_batch resolved (<= 0 means auto-size)."""
    if cfg.chips_per_batch > 0:
        return cfg
    return dataclasses.replace(
        cfg, chips_per_batch=auto_chips_per_batch(cfg, acquired))


# ---------------------------------------------------------------------------
# Compile-warm startup: persistent cache + background AOT of the batch shape
# ---------------------------------------------------------------------------

_cache_listener_installed = False
_warm_lock = threading.Lock()
_warm_thread: threading.Thread | None = None  # guarded-by: _warm_lock
_warm_shape: tuple | None = None  # guarded-by: _warm_lock


def _install_cache_counters() -> None:
    """Count persistent compile-cache hits/misses into the run registry.

    jax records monitoring events on every persistent-cache lookup
    (``/jax/compilation_cache/cache_hits``) and write-back (``.../
    cache_misses``); the listener resolves the CURRENT metrics registry at
    event time, so per-run reports see their own counts even though the
    listener itself is registered once per process.  Attribution is
    best-effort across runs: the events carry no run identity, so a warm
    compile abandoned by a short run (the 5s join in the driver's
    finally) lands its hit/miss in whichever run is live when it finishes
    — bounded by warm_start's one-in-flight guard, and never wrong about
    the process-wide totals."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return
    import jax.monitoring

    def _on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            obs_metrics.counter(
                "compile_cache_hits",
                help="persistent XLA compile-cache hits").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            obs_metrics.counter(
                "compile_cache_misses",
                help="persistent XLA compile-cache misses").inc()

    jax.monitoring.register_event_listener(_on_event)
    # Idempotent once-latch; a duplicate listener from a racing second
    # run is harmless (both count the same events) and the driver
    # installs from one thread in practice.
    _cache_listener_installed = True  # firebird-lint: disable=ownership-global-mutation


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The persistent XLA compilation cache directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.cache/jax``.
    The fallback is a FIXED path — never a tmp name, pid or time — because
    a cache that moves between runs never hits."""
    return os.path.abspath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or os.path.join(_CHECKOUT, ".cache", "jax"))


def setup_compile_cache() -> str:
    """Point jax's persistent compilation cache at :func:`compile_cache_dir`
    (always on): compiled programs serialize there, so the SECOND run of
    any shape deserializes instead of compiling — and the background
    :func:`warm_start` AOT compile of run N becomes the cache hit of run
    N+1's first dispatch.  Which compiles are worth keeping stays jax's
    own rule (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``).  Returns
    the cache path."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = compile_cache_dir()
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # Un-latch jax's once-per-process cache probe so pointing the
        # cache mid-process (after an unrelated first compile) still
        # takes effect.
        compilation_cache.reset_cache()
    _install_cache_counters()
    logger("change-detection").info("persistent compile cache at %s", path)
    return path


def wire_avatar_dtypes() -> tuple:
    """The avatar dtype tuple warm_start AOT-compiles the wire signature
    with — ONE definition shared with the test pinning it against
    ``kernel.wire_args``' staged dtypes, because any drift makes every
    warm compile a silent cache miss (the AOT writes one key, the real
    dispatch looks up another)."""
    return (jnp.int32, jnp.int32, jnp.int16,
            jnp.dtype(kernel.wire_qa_dtype()))


def predict_batch_shape(cfg: Config, acquired: str) -> tuple[int, int, int]:
    """The steady-state padded dispatch shape a run is expected to
    compile: (C, T, wcap).  C mirrors detect_batch's padding (rounded to
    a device-count multiple when sharded); T is estimate_obs's bucketed
    estimate; wcap applies window_cap's rule to a dense 8-day acquisition
    grid.  A wrong guess wastes only the background compile — the
    persistent cache still warms the actual shape from run 1's own
    compile on every later run."""
    import jax

    from firebird_tpu.ccd import params

    n_dev = jax.local_device_count()
    use_mesh = cfg.device_sharding != "off" and n_dev > 1
    C = _pad_target(max(cfg.chips_per_batch, 1), None, use_mesh, n_dev)
    T = estimate_obs(acquired, cfg)
    lo, hi = dt.acquired_range(acquired)
    d = np.arange(lo, hi + 1, 8, dtype=np.int64)[:T]
    cap = params.MEOW_SIZE
    if d.size:
        hi_i = np.searchsorted(d, d + params.INIT_DAYS, side="right")
        cap = max(cap, int((hi_i - np.arange(d.size)).max()) + 1)
    wcap = min(-8 * (-cap // 8), T)
    return C, T, wcap


def warm_start(cfg: Config, acquired: str, sensor=None, dtype=None,
               donate: bool | None = None) -> threading.Thread | None:
    """AOT-lower/compile the predicted steady-state batch shape on a
    background thread, so the (multi-second) first XLA compile overlaps
    batch 0's HTTP fetch instead of serializing after it.

    Relies on the persistent compilation cache (:func:`setup_compile_cache`):
    jit keeps its own in-memory table, so the AOT executable can only
    reach the first real dispatch *through* the cache (AOT writes the
    entry, the dispatch deserializes it).  A failed or mispredicted warm
    compile costs nothing but the background work.  Returns the started
    thread (join it to observe ``warm_compile_seconds``), or None when a
    previous warm compile is still running (no duplicate compiles).
    """
    import jax

    from firebird_tpu.ccd.sensor import LANDSAT_ARD

    sensor = sensor or LANDSAT_ARD
    dtype = dtype if dtype is not None else _DTYPES[cfg.dtype]
    # Match the program the steady-state loop will dispatch (detect_chunk
    # donates on accelerators only) — a warm compile of the wrong donation
    # variant would miss the cache at dispatch time.
    if donate is None:
        donate = _on_accelerator()
    kernel.ensure_x64(dtype)
    C, T, wcap = predict_batch_shape(cfg, acquired)
    B, P = sensor.n_bands, sensor.pixels
    # The all-integer wire signature (kernel.wire_args order): day
    # ordinals, per-chip counts, int16 spectra, uint8/uint16 QA.
    shapes = ((C, T), (C,), (C, B, P, T), (C, P, T))
    n_dev = jax.local_device_count()
    use_mesh = cfg.device_sharding != "off" and n_dev > 1
    # Metrics bind to THIS run's registry at start: a long warm compile
    # abandoned by a short run (5s join in the driver's finally) must not
    # record into whichever registry a LATER run has installed.
    reg = obs_metrics.get_registry()

    def _warm():
        try:
            with tracing.span("warm_compile", shape=(C, T, wcap)), \
                    obs_metrics.timer() as tm:
                if use_mesh:
                    from firebird_tpu.parallel import make_mesh
                    from firebird_tpu.parallel.mesh import \
                        aot_compile_sharded

                    aot_compile_sharded(
                        make_mesh(devices=jax.local_devices()), dtype,
                        wcap, sensor, shapes, donate=donate,
                        compact=cfg.compact)
                else:
                    avatars = tuple(
                        jax.ShapeDtypeStruct(s, d) for s, d in zip(
                            shapes, wire_avatar_dtypes()))
                    kernel.aot_compile(avatars, dtype=dtype, wcap=wcap,
                                       sensor=sensor, donate=donate,
                                       compact=cfg.compact)
            reg.histogram("warm_compile_seconds").observe(tm.elapsed)
            reg.counter("warm_compiles",
                        help="background AOT compiles completed").inc()
        except Exception as e:
            # Best-effort: the run proceeds cold; first dispatch compiles.
            logger("change-detection").warning(
                "warm-start compile failed (run proceeds cold): %s", e)

    global _warm_thread, _warm_shape
    with _warm_lock:
        if _warm_thread is not None and _warm_thread.is_alive():
            logger("change-detection").info(
                "warm-start: previous warm compile still in flight; "
                "not starting another")
            return None
        _warm_shape = (C, T, wcap)
        _warm_thread = threading.Thread(
            target=_warm, name="firebird-warm-compile", daemon=True)
        _warm_thread.start()
        return _warm_thread


def await_warm_compile(staged: StagedBatch) -> None:
    """Before a dispatch of the shape an in-flight warm compile is
    building, wait for it: the dispatch then reads the finished program
    from the persistent cache instead of compiling the same program a
    second time alongside it (on a v5e the default program compiles for
    minutes, far longer than batch 0's fetch).  Any other shape, or no
    warm compile in flight, returns at once."""
    with _warm_lock:
        thread, shape = _warm_thread, _warm_shape
    C, _, _, T = staged.packed.spectra.shape
    if thread is not None and thread.is_alive() \
            and shape == (C, T, staged.wcap):
        with tracing.span("await_warm_compile"):
            thread.join()


def _with_retries(cfg: Config, log, what: str, fn, policy=None):
    """Run fn() under the driver's transient-failure policy: the reference
    delegated these to Spark's task retry; here a blip on one fetch must
    not fail the whole chunk.  The real loop lives in
    :class:`firebird_tpu.retry.RetryPolicy` (decorrelated-jitter backoff,
    injectable sleep, optional shared budget + circuit breaker); callers
    without a run-scoped ``policy`` get a one-off built from
    ``cfg.fetch_retries``.  Raises the last error when retries run out."""
    if policy is None:
        policy = retrylib.RetryPolicy(cfg.fetch_retries)
    return policy.run(log, what, fn)


def fetch(x, y, outdir: str, acquired: str | None = None,
          number: int = 2500, aux: bool = False,
          cfg: Config | None = None, source=None,
          aux_source=None) -> tuple[int, int]:
    """Mirror a tile's chips from the configured source into a FileSource
    directory (.npz per chip) for offline reruns and fixture building.

    The write side of ingest's FileSource: fetch once over the network,
    then run any number of campaigns with FIREBIRD_SOURCE=file against the
    local archive.  Uses the driver's fetch retries and INPUT_PARTITIONS
    parallelism.  Chips that exhaust their retries are dead-lettered to
    ``<outdir>/quarantine.json`` (error class + attempt history) so a
    partial archive mirror is resumable like a partial store: rerun the
    same fetch and only the manifest's chips are missing work.  Returns
    (chips written, chips attempted).
    """
    cfg = cfg or Config.from_env()
    acquired = acquired or dt.default_acquired()
    log = logger("timeseries")
    plan = faultlib.FaultPlan.from_config(cfg)
    source = faultlib.wrap_source(source or make_source(cfg), plan)
    aux_source = aux_source or (make_aux_source(cfg) if aux else None)
    if aux_source is not None:
        aux_source = faultlib.wrap_source(aux_source, plan)
    os.makedirs(outdir, exist_ok=True)
    sink = FileSource(outdir)
    policy = retrylib.RetryPolicy.for_ingest(
        cfg, budget=retrylib.RetryBudget(cfg.retry_budget),
        breaker=retrylib.make_breaker(cfg))
    quarantine = qlib.Quarantine.load(
        os.path.join(outdir, "quarantine.json"))

    tile = grid.tile(x=x, y=y)
    cids = list(take(number, grid.chips(tile)))
    log.info("fetch: tile h=%s v=%s -> %s (%d chips, acquired %s, aux=%s)",
             tile["h"], tile["v"], outdir, len(cids), acquired, aux)

    def one(xy):
        # Chip and aux retry independently: a written chip is never
        # re-fetched because the aux side flaked.
        try:
            _with_retries(cfg, log, f"chip ({xy[0]},{xy[1]}) fetch",
                          lambda: sink.save_chip(
                              source.chip(xy[0], xy[1], acquired)),
                          policy=policy)
        except Exception as e:
            log.error("chip (%s,%s) failed: %s", xy[0], xy[1], e)
            quarantine.record(xy, e, attempts=cfg.fetch_retries + 1,
                              stage="fetch")
            return 0
        quarantine.discard(xy)       # a redeemed dead letter drains
        if aux_source is not None:
            try:
                _with_retries(cfg, log, f"aux ({xy[0]},{xy[1]}) fetch",
                              lambda: sink.save_aux(
                                  xy[0], xy[1],
                                  aux_source.aux(xy[0], xy[1], acquired)),
                              policy=policy)
            except Exception as e:
                log.error("aux (%s,%s) failed: %s — archive holds the "
                          "chip but no aux layers", xy[0], xy[1], e)
        return 1

    with cf.ThreadPoolExecutor(
            max_workers=max(cfg.input_parallelism, 1)) as ex:
        n = sum(ex.map(one, cids))
    failed = len(cids) - n
    log.info("fetch complete: %d/%d chips written, %d failed%s",
             n, len(cids), failed,
             f" (dead letters in {quarantine.path})" if failed else "")
    return n, len(cids)


def _on_accelerator() -> bool:
    """Whether the run's device is an accelerator.  Decides two things:
    staged inputs are donated (on the CPU backend the HBM-footprint
    argument is moot, and the donated jit twin would just double-compile
    every shape the CPU test suite already caches), and the driver warm
    starts (a background compile on the CPU backend competes for the very
    cores the fetch it should overlap runs on)."""
    import jax

    return jax.default_backend() in ("tpu", "gpu")


@dataclasses.dataclass
class StagedBatch:
    """A device-staged input batch (the prefetch thread's product): the
    kernel argument tuple already resident under the run's sharding, plus
    the padded host-side PackedChips the drain/recompute path still
    needs.  ``wcap`` is the (cross-host-agreed, sharded case) window cap
    the staged args were prepared for."""

    packed: object             # padded PackedChips (host arrays)
    args: tuple                # device arrays, wire dtypes
    n_real: int
    mesh: object | None        # the local data mesh when sharded
    wcap: int


def stage_batch(packed, dtype, sharding: str = "auto",
                pad_to: int | None = None) -> StagedBatch:
    """Pad and device_put one batch under the run's sharding — the H2D
    half of :func:`detect_batch`, run on the prefetch thread so batch
    i+1's transfer overlaps batch i's compute and the main thread only
    dispatches.  Blocks until the transfer lands (the *prefetch* thread
    eats the wait), records ``pipeline_stage_seconds``, the
    ``wire_h2d_bytes`` counter, and the h2d ``transfer`` span leg."""
    import jax

    from firebird_tpu.ccd import kernel as k

    n_dev = jax.local_device_count()
    use_mesh = sharding != "off" and n_dev > 1
    padded, real = _pad_batch(
        packed, _pad_target(packed.n_chips, pad_to, use_mesh, n_dev))
    with tracing.span("stage", chips=real), obs_metrics.timer() as tm:
        # The `transfer` span leg (leg=h2d; its d2h twin wraps the drain's
        # bulk fetch) makes transfer-vs-compute overlap directly readable
        # off the host trace: a healthy pipeline shows h2d transfer spans
        # riding the prefetch thread UNDER the main thread's dispatch gap.
        with tracing.span("transfer", leg="h2d", chips=real):
            if use_mesh:
                from firebird_tpu.parallel import make_mesh
                from firebird_tpu.parallel.mesh import stage_sharded

                mesh = make_mesh(devices=jax.local_devices())
                args, wcap = stage_sharded(padded, mesh, dtype)
            else:
                mesh = None
                args = k.stage_packed(padded, dtype)
                wcap = k.window_cap(padded)
    obs_metrics.histogram("pipeline_stage_seconds").observe(tm.elapsed)
    obs_metrics.counter(
        "wire_h2d_bytes",
        help="bytes staged host->device (all-integer packed inputs)").inc(
        int(sum(getattr(a, "nbytes", 0) for a in args)))
    return StagedBatch(packed=padded, args=args, n_real=real, mesh=mesh,
                       wcap=wcap)


def detect_batch(packed, dtype, sharding: str = "auto",
                 pad_to: int | None = None, check_capacity: bool = False,
                 max_segments: int | None = None,
                 staged: StagedBatch | None = None, donate: bool = False,
                 compact: bool | None = None):
    """Run the CCD kernel over a packed batch on every local device.

    Single device (or sharding='off'): plain jit dispatch.  Multiple local
    devices (the normal TPU-VM topology): the chip axis is sharded over a
    data mesh of this process's local devices — in multi-host runs each
    process does the same over its own chips (driver host_shard), so the
    two data-parallel levels compose: hosts split the tile, local devices
    split each host's batches.  A single *globally* sharded batch is the
    library path (parallel.mesh.detect_sharded), not the driver loop.

    Batches are padded (repeating the last chip) up to `pad_to` — and to a
    multiple of the device count when sharded — so a chunk's ragged final
    batch reuses the same compiled kernel shape as its full batches; padded
    results are dropped by the caller via the returned real count.

    With ``staged`` (a :class:`StagedBatch` from :func:`stage_batch`) the
    arrays are already device-resident — this call only dispatches.
    ``donate=True`` frees the staged wire inputs at dispatch (honored only
    with ``check_capacity=False``; a donated recompute re-stages from
    ``staged.packed``'s host arrays).
    """
    import jax

    from firebird_tpu.ccd import kernel as k

    # The default check_capacity=False keeps the dispatch asynchronous
    # (no device sync on this thread); the drain thread — which fetches
    # results anyway — detects segment-capacity overflow and re-runs the
    # batch through this same function with the check on (drain_batch).
    kw = dict(check_capacity=check_capacity, compact=compact)
    if max_segments is not None:
        kw["max_segments"] = max_segments
    if staged is not None:
        if staged.mesh is None:
            return k.detect_packed(staged.packed, dtype=dtype,
                                   staged=staged.args, donate=donate,
                                   **kw), staged.n_real
        from firebird_tpu.parallel.mesh import detect_sharded

        return detect_sharded(staged.packed, staged.mesh, dtype=dtype,
                              staged=(staged.args, staged.wcap),
                              donate=donate, **kw), staged.n_real

    n_dev = jax.local_device_count()
    use_mesh = sharding != "off" and n_dev > 1
    padded, real = _pad_batch(
        packed, _pad_target(packed.n_chips, pad_to, use_mesh, n_dev))
    if not use_mesh:
        return k.detect_packed(padded, dtype=dtype, **kw), real
    from firebird_tpu.parallel import make_mesh
    from firebird_tpu.parallel.mesh import detect_sharded

    mesh = make_mesh(devices=jax.local_devices())
    return detect_sharded(padded, mesh, dtype=dtype, **kw), real


def fetch_results(seg, worst: int | None = None):
    """The ONE bulk device->host fetch per batch: ``jax.device_get`` of
    the whole batched result, collapsing the old per-chip, per-field
    ``chip_slice(to_host=True)`` pattern (~C x fields D2H round trips per
    batch) into a single transfer sweep.

    With ``FIREBIRD_WIRE_EGRESS`` (default on) and a float32 result, the
    ChipSegments is first packed ON DEVICE into int-coded tables sliced
    to the batch's observed segment depth (``kernel.pack_egress``) and
    decoded back host-side (``format.decode_egress``) — identical host
    arrays, a fraction of the bytes on the wire (docs/ROOFLINE.md "Wire
    budget").  ``worst`` is the caller's capacity probe (max segments
    any pixel closed) when it already paid that sync; None probes here.
    Records ``pipeline_d2h_seconds``, the ``wire_d2h_bytes`` counter,
    and the d2h ``transfer`` span leg; returns a host-array
    ChipSegments."""
    import jax

    payload, decode_T = seg, None
    if kernel.wire_egress_enabled() and seg.seg_meta.dtype == jnp.float32:
        if worst is None:
            worst = int(np.asarray(seg.n_segments).max())
        s_eff = kernel.egress_bucket(worst, seg.seg_meta.shape[-2])
        payload = kernel.pack_egress(seg, s_eff)
        decode_T = seg.mask.shape[-1]
    nbytes = int(sum(getattr(v, "nbytes", 0)
                     for v in jax.tree_util.tree_leaves(payload)))
    with tracing.span("d2h", bytes=nbytes), obs_metrics.timer() as tm:
        with tracing.span("transfer", leg="d2h", bytes=nbytes):
            host = jax.device_get(payload)
    obs_metrics.histogram("pipeline_d2h_seconds").observe(tm.elapsed)
    obs_metrics.counter(
        "wire_d2h_bytes",
        help="bytes fetched device->host (batch results, int-coded and "
             "depth-sliced when the egress diet is on)").inc(nbytes)
    if decode_T is not None:
        host = ccdformat.decode_egress(host, decode_T)
    return host


def write_batch_frames(packed, host_seg, n_real, *, writer, counters=None):
    """Format + queue one drained batch's frames — the shared egress tail
    of both drivers: ``format.batch_frames`` builds the three tables
    across the chip axis in one numpy pass, split back into the existing
    keyed per-chip writes, so the segment frame still lands last per chip
    (the resume invariant)."""
    P = host_seg.n_segments.shape[1]
    for c, (cid, frames) in enumerate(
            ccdformat.batch_frames(packed, host_seg, n_real)):
        for table in ("chip", "pixel", "segment"):
            # keyed: one chip's frames drain in order, so the segment
            # frame lands last (the resume invariant)
            writer.write(table, frames[table], key=cid)
        if counters is not None:
            counters.add("chips")
            counters.add("pixels", P)
            counters.add("segments", int(host_seg.n_segments[c].sum()))


def drain_batch(seg, packed, n_real, *, writer, counters, dtype=None,
                sharding: str = "auto", pad_to: int | None = None,
                compact: bool | None = None, ctx=None):
    """Fetch one batch's results to the host, format, and queue writes
    (the egress half of ref core.detect, core.py:69-72) — results cross
    D2H as one bulk :func:`fetch_results` transfer and format through the
    vectorized :func:`write_batch_frames` path.

    ``ctx`` is the batch's :class:`~firebird_tpu.obs.tracing.TraceContext`
    — this function runs on the drain executor, so the context must
    cross the thread hop explicitly; everything below (spans, the queued
    writes, the drain histogram's exemplar, log lines) parents to it.

    Also the capacity backstop for the driver's asynchronous dispatch
    (detect_batch defaults check_capacity=False): if any pixel closed
    more segments than the result buffers hold, the batch is recomputed
    here through the same (sharded-aware) dispatch with the capacity
    check on — rare enough that the synchronous re-run does not matter."""
    cap = seg.seg_meta.shape[-2]                   # [.., P, S, 6] -> S
    with tracing.activate(ctx):
        with tracing.span("drain", chips=n_real), obs_metrics.timer() as tm:
            # Capacity probe BEFORE the bulk fetch: n_segments alone is a
            # few hundred KB, so an overflowed batch never pays a
            # full-result transfer whose buffers are about to be discarded
            # (and the d2h telemetry counts only the one real bulk fetch).
            worst = int(np.asarray(seg.n_segments).max())
            if worst > cap:
                logger("pyccd").info(
                    "segment capacity %d overflowed on drain (deepest pixel "
                    "closed %d); recomputing the batch", cap, worst)
                obs_metrics.counter("capacity_redispatches").inc()
                seg, _ = detect_batch(packed, dtype or seg.seg_meta.dtype,
                                      sharding, pad_to=pad_to,
                                      check_capacity=True, compact=compact,
                                      max_segments=min(
                                          2 * cap,
                                          kernel.capacity_bound(packed)))
            host = fetch_results(seg, worst=worst)
            # Occupancy telemetry: the event loop's per-round active/paid
            # lane capture feeds kernel_round_active_fraction and the
            # compaction counters (results are on the host anyway).
            kernel.record_occupancy(host)
            write_batch_frames(packed, host, n_real, writer=writer,
                               counters=counters)
        obs_metrics.histogram("pipeline_drain_seconds").observe(tm.elapsed)
        # In-context completion line: with FIREBIRD_LOG_FORMAT=json this
        # carries the batch id, joining the drain to its spans/exemplars.
        logger("change-detection").debug(
            "batch drained: %d chips in %.3fs", n_real, tm.elapsed)
    # Forward-progress beat: a drained batch is the watchdog's liveness
    # unit and /progress's batches_done tick (no-op when no run registered).
    obs_server.batch_done(n_real)


def detect_chunk(cids, *, source, writer, acquired, cfg, counters, log,
                 policy=None, quarantine=None):
    """Run change detection for one chunk of chip ids (ref core.detect,
    core.py:53-75): ingest -> pack -> stage -> kernel -> chip/pixel/segment
    writes.

    Zero-stall pipeline: the prefetch thread fetches, packs, AND stages
    (H2D under the run's sharding) batch i+1 while batch i is on the
    device — the main thread only dispatches — and a drain thread
    bulk-fetches/formats batch i-1's results while batch i computes.
    Staged wire inputs are donated to the dispatch (freed on device once
    consumed), which is what lets the in-flight bound be a configurable
    ``cfg.pipeline_depth`` instead of a hard 2 without pinning every
    batch's inputs alongside its results.

    Per-chip failure isolation: a chip that exhausts its fetch retries is
    dead-lettered to ``quarantine`` (quarantine.json) and DROPPED from its
    batch — the remaining chips pack, dispatch, and land normally (the old
    behavior lost the whole chunk, driver/core.py pre-PR4).  ``policy`` is
    the run's shared :class:`~firebird_tpu.retry.RetryPolicy` (jitter,
    budget, ingest breaker).  Returns the chip ids actually processed."""
    log.info("finding ccd segments for %d chips", len(cids))
    dtype = _DTYPES[cfg.dtype]
    batches = list(partition_all(cfg.chips_per_batch, cids))
    # Pad a ragged final batch onto the full-batch compiled shape only when
    # a full batch exists to share it with; a single small batch would pay
    # the padding compute for no compile reuse.
    pad_to = cfg.chips_per_batch if len(batches) > 1 else None
    depth = max(cfg.pipeline_depth, 1)

    # Separate single-worker executors: the prefetch slot must not steal
    # the chip-level workers (INPUT_PARTITIONS semantics) or a 1-worker
    # pool would deadlock on the nested map; the drain slot keeps one
    # batch's egress overlapping the next batch's compute.
    with cf.ThreadPoolExecutor(
            max_workers=max(cfg.input_parallelism, 1)) as chips_ex, \
            cf.ThreadPoolExecutor(max_workers=1) as prefetch_ex, \
            cf.ThreadPoolExecutor(max_workers=1) as drain_ex:

        def fetch_one(xy, ctx=None):
            # The chip pool's threads are outside the prefetch thread's
            # context scope — the batch context crosses this hop
            # explicitly too, so per-chip latency exemplars and failure
            # log lines carry the batch id.
            with tracing.activate(ctx):
                try:
                    with obs_metrics.timer() as tm:
                        chip = _with_retries(
                            cfg, log, f"chip ({xy[0]},{xy[1]}) fetch",
                            lambda: source.chip(xy[0], xy[1], acquired),
                            policy=policy)
                except Exception as e:
                    # Per-chip isolation: dead-letter the poisoned chip
                    # and let the rest of the batch proceed — `--resume`
                    # drains the quarantine once the cause clears.
                    log.error(
                        "chip (%s,%s) failed after retries (%s: %s); "
                        "quarantined — its chunk continues without it",
                        xy[0], xy[1], type(e).__name__, e)
                    if quarantine is not None:
                        quarantine.record(xy, e,
                                          attempts=cfg.fetch_retries + 1)
                    return None
                obs_metrics.histogram(
                    "ingest_chip_seconds").observe(tm.elapsed)
                return chip

        # ONE TraceContext per batch, minted here and carried EXPLICITLY
        # across the three thread hops (prefetch stage -> main-thread
        # dispatch -> drain executor -> writer queue): every span, JSON
        # log line, and histogram exemplar those threads record parents
        # to the same <run_id>/b<seq> id.
        run_id = jsonlog.get_run_context().get("run_id")
        ctxs = [tracing.TraceContext(tracing.new_batch_id(run_id),
                                     run_id=run_id) for _ in batches]

        def prepare_batch(bids, ctx):
            """fetch -> pack -> device staging, all on the prefetch
            thread: by the time the main thread picks the batch up, its
            arrays are already resident under the run's sharding.
            Returns (surviving chip ids, StagedBatch), or None when every
            chip of the batch was quarantined."""
            with tracing.activate(ctx):
                with tracing.span("fetch", chips=len(bids)), \
                        obs_metrics.timer() as tm:
                    chips = list(chips_ex.map(
                        lambda xy: fetch_one(xy, ctx), bids))
                obs_metrics.histogram(
                    "pipeline_fetch_seconds").observe(tm.elapsed)
                keep = [(cid, ch) for cid, ch in zip(bids, chips)
                        if ch is not None]
                if not keep:
                    return None
                with tracing.span("pack", chips=len(keep)), \
                        obs_metrics.timer() as tm:
                    packed = pack([ch for _, ch in keep],
                                  bucket=cfg.obs_bucket,
                                  max_obs=cfg.max_obs)
                obs_metrics.histogram(
                    "pipeline_pack_seconds").observe(tm.elapsed)
                return [cid for cid, _ in keep], \
                    stage_batch(packed, dtype, cfg.device_sharding,
                                pad_to=pad_to)

        nxt = prefetch_ex.submit(prepare_batch, batches[0], ctxs[0]) \
            if batches else None
        drains: list[cf.Future] = []
        processed: list = []
        for i in range(len(batches)):
            # Fence-loss fast abort (fleet jobs): a NonRetryable error
            # pending in the writer means every further write will
            # reject — stop paying for batches whose output cannot land
            # instead of discovering it at the final flush.
            err = getattr(writer, "peek_error", lambda: None)()
            if isinstance(err, retrylib.NonRetryable):
                raise err
            obs_server.set_stage("fetch")
            prep = nxt.result()
            nxt = (prefetch_ex.submit(prepare_batch, batches[i + 1],
                                      ctxs[i + 1])
                   if i + 1 < len(batches) else None)
            if prep is None:
                continue                 # whole batch quarantined
            kept, staged = prep
            await_warm_compile(staged)
            # The dispatch span measures enqueue time, not device compute
            # (check_capacity=False keeps it async); compute shows up as
            # the gap before the matching drain span closes.
            obs_server.set_stage("dispatch")
            with tracing.activate(ctxs[i]):
                with tracing.span("dispatch", chips=staged.n_real), \
                        obs_metrics.timer() as tm:
                    seg, n_real = detect_batch(staged.packed, dtype,
                                               cfg.device_sharding,
                                               pad_to=pad_to, staged=staged,
                                               donate=_on_accelerator(),
                                               compact=cfg.compact)
                obs_metrics.histogram(
                    "pipeline_dispatch_seconds").observe(tm.elapsed)
            # /readyz flips here: mesh up + first batch dispatched means
            # compile/bring-up are behind us and the run is steady-state.
            obs_server.batch_dispatched()
            drains.append(drain_ex.submit(
                drain_batch, seg, staged.packed, n_real, writer=writer,
                counters=counters, dtype=dtype,
                sharding=cfg.device_sharding, pad_to=pad_to,
                compact=cfg.compact, ctx=ctxs[i]))
            processed.extend(kept)
            # Bound in-flight batches to cfg.pipeline_depth (the one
            # computing + depth-1 draining): input donation frees each
            # batch's staged wire buffers at dispatch, so depth only pins
            # result buffers — but unbounded depth would still exhaust
            # HBM, hence the config.
            while len(drains) > depth - 1:
                drains.pop(0).result()
        for f in drains:
            f.result()
    return processed


def run_chunk(chunk, *, source, writer, acquired, cfg, counters, log,
              policy=None, quarantine=None, reraise=False):
    """One chunk end-to-end — detect, flush, redeem dead letters — with
    the chunk-level failure backstop.  THE unit of fleet work: the batch
    driver's per-chunk loop body and a fleet ``detect`` job
    (fleet/worker.py) are this same function, so quarantine semantics
    cannot drift between single-process and fleet execution.

    ``reraise=False`` (the driver loop) swallows the chunk failure after
    dead-lettering its chips (core.py:115-124 semantics — later chunks
    continue); ``reraise=True`` (a fleet job) re-raises so the queue's
    attempt accounting sees the failure.  A ``NonRetryable`` error
    (fencing rejection) always propagates WITHOUT dead-lettering: the
    job's chips are a successor's responsibility, not owed work.
    Returns the chip ids processed ([] on a swallowed failure)."""
    try:
        processed = detect_chunk(
            chunk, source=source, writer=writer, acquired=acquired,
            cfg=cfg, counters=counters, log=log, policy=policy,
            quarantine=quarantine)
        obs_server.set_stage("flush")
        writer.flush()  # a chunk counts once its rows landed
        if quarantine is not None:
            quarantine.discard_many(processed)  # redeemed letters
        return processed
    except retrylib.NonRetryable:
        raise
    except Exception as e:
        # Chunk-level failure isolation (core.py:115-124) is the
        # BACKSTOP behind per-chip quarantine (ingest failures never
        # reach here anymore): a kernel or store error still fails the
        # chunk, but its chips are dead-lettered so `--resume` (or a
        # re-delivered fleet job) knows exactly what is owed instead of
        # rediscovering it by store diff.
        obs_metrics.counter("chunk_failures").inc()
        log.error("chunk failed (%d chips): %s", len(chunk), e)
        if quarantine is not None:
            held = quarantine.chip_ids()
            quarantine.record_many(
                [c for c in chunk
                 if tuple(int(v) for v in c) not in held],
                e, attempts=1, stage="chunk")
        if reraise:
            raise
        traceback.print_exc()
        return []


def changedetection(x, y, acquired: str | None = None, number: int = 2500,
                    chunk_size: int = 2500, cfg: Config | None = None,
                    source=None, store=None, resume: bool = False):
    """Run change detection for a tile and save results (ref
    core.changedetection, core.py:78-124).

    Args mirror the reference CLI: tile point (x, y), ISO8601 acquired
    range, number of chips (testing), chunk size (failure-isolation
    granularity).  ``resume=True`` skips chips whose segments are already
    stored (the segment table is written last per chip, so presence
    implies completeness) — the explicit restart the reference only got
    implicitly from rerunning idempotent upserts over a whole tile.  The
    run manifest (run_manifest.json) makes resume REFUSE on a mismatched
    acquired range and warn on a changed config fingerprint instead of
    silently mixing results, and chips dead-lettered to quarantine.json
    by a previous run drain first (docs/ROBUSTNESS.md).

    Returns the tuple of chip ids processed successfully.
    """
    cfg = cfg or Config.from_env()
    acquired = acquired or dt.default_acquired()
    cfg = resolve_batching(cfg, acquired)
    log = logger("change-detection")
    counters = Counters()
    # Run identity: ONE id (broadcast fleet-wide) correlates every
    # host's JSON log lines, spans, /progress payloads, and report
    # shards.  Context is set immediately — the setup log lines (tile
    # geometry, resume accounting) must already carry the id; start_ops
    # re-sets it with the process index once the backend is up.
    run_id = fleet_run_id()
    jsonlog.set_run_context(run_id=run_id)
    # Run-scoped telemetry: a fresh registry so the report reflects THIS
    # run.  (The span tracer starts below, right before the try/finally
    # that guarantees its stop — a setup failure here must not leak an
    # active process-global tracer into later runs.)
    obs_metrics.reset_registry()
    # Compile-warm startup: persistent cache on, then (on an accelerator)
    # AOT-compile the predicted batch shape in the background so the
    # first XLA compile overlaps batch-0 fetch instead of following it.
    setup_compile_cache()
    warm = warm_start(cfg, acquired) if _on_accelerator() else None

    # Refuse-or-warn BEFORE building anything: a resume against a
    # different acquired range must not interleave date windows (and must
    # not leave a half-built writer behind when it refuses).
    if resume:
        qlib.check_resume(cfg, acquired=acquired, log=log)

    source, store, writer, policy, breaker, quarantine = robustness_setup(
        cfg, run_id, source=source, store=store)

    tile = grid.tile(x=x, y=y)
    cids = list(take(number, grid.chips(tile)))
    cids = host_shard(cids)
    skipped: tuple = ()
    if resume:
        # Key on the segment table: it is written LAST per chip through the
        # FIFO writer, so its presence implies the chip/pixel rows landed
        # too.
        have = store.chip_ids("segment")
        # Dead letters whose chips actually landed (quarantined at chunk
        # granularity but persisted before the failure) drain right away.
        quarantine.discard_many(have)
        todo = [c for c in cids if c not in have]
        skipped = tuple(c for c in cids if c in have)
        # Drain the quarantine FIRST: the chips we already know we owe
        # sort to the front of the todo list (stable, so tile order is
        # otherwise preserved).
        qids = quarantine.chip_ids()
        todo.sort(key=lambda c: tuple(int(v) for v in c) not in qids)
        cids = todo
        log.info("resume: %d chips already stored, %d to do (%d draining "
                 "from quarantine first)", len(skipped), len(cids),
                 len(qids))
    else:
        qlib.write_manifest(cfg, acquired=acquired, run_id=run_id,
                            tile=tile)
    chunks = list(partition_all(chunk_size, cids))
    log.info("tile h=%s v=%s: %d chips in %d chunks (acquired %s)",
             tile["h"], tile["v"], len(cids), len(chunks), acquired)

    # Live ops surface: run context for JSON logs, /progress status,
    # optional watchdog + HTTP endpoint (no port bound unless asked).
    run_block = dict(kind="changedetection", run_id=run_id,
                     host=jsonlog.HOST, process_id=_process_index(),
                     tile_h=tile["h"], tile_v=tile["v"], acquired=acquired,
                     chips=len(cids), chunks=len(chunks),
                     resumed=len(skipped))
    _, ops_srv, watchdog = start_ops(
        cfg, run_id, "changedetection", chips_total=len(cids),
        counters=counters, run_block=run_block, quarantine=quarantine,
        breaker=breaker)

    # Opt-in tracing (cfg.profile_dir): the whole run captures a JAX
    # profiler trace viewable in TensorBoard/Perfetto — the tracing
    # subsystem the reference lacked (SURVEY.md §5).
    if cfg.profile_dir:
        import jax

        prof = jax.profiler.trace(cfg.profile_dir)
    else:
        prof = contextlib.nullcontext()

    tracer = tracing.start(run_id=run_id) \
        if tracing.wants_trace(cfg.trace) else None
    done: list = []
    # Rate clock starts at the first productive moment, not Counters()
    # construction — setup/backend idle must not deflate *_per_sec.
    counters.start()
    try:
        with prof:
            for chunk in chunks:
                done.extend(run_chunk(
                    chunk, source=source, writer=writer,
                    acquired=acquired, cfg=cfg, counters=counters,
                    log=log, policy=policy, quarantine=quarantine))
    finally:
        obs_server.set_stage("finalize")
        writer.close()
        # Collect the warm-compile counters for the report when the
        # background compile already finished (a still-compiling warm
        # thread of a short run is abandoned, not awaited).
        if warm is not None:
            warm.join(timeout=5.0)
        snap = counters.snapshot()
        log.info("change-detection complete: %s", snap)
        if len(quarantine):
            run_block["chips_quarantined"] = len(quarantine)
            log.warning(
                "%d chips in quarantine (%s) — rerun with --resume to "
                "drain them once the cause clears", len(quarantine),
                quarantine.path or "in-memory: memory store backend")
        if tracer is not None:
            tracing.stop()
        paths = obs_report.finish_run(
            cfg, tracer=tracer, run_counters=snap, run=run_block)
        if paths:
            log.info("observability artifacts: %s", paths)
        # Server goes down LAST so /progress and /report serve the final
        # state for as long as the process allows.
        obs_server.set_stage("done")
        stop_ops(ops_srv, watchdog)

    return tuple(skipped) + tuple(done)


def classification(x, y, msday: int, meday: int, acquired: str | None = None,
                   cfg: Config | None = None, source=None, aux_source=None,
                   store=None):
    """Train on the 3x3 tile neighborhood, classify the tile, persist
    predictions + the trained model (ref core.classification, core.py:156-251
    — including the predict/save path the reference left commented out)."""
    try:
        from firebird_tpu.rf import pipeline as rf_pipeline
    except ImportError as e:
        raise RuntimeError(
            "classification requires the firebird_tpu.rf module, which is "
            "not available in this build") from e

    cfg = cfg or Config.from_env()
    acquired = acquired or dt.default_acquired()
    store = store or open_store(cfg.store_backend, cfg.store_path,
                                cfg.keyspace())
    return rf_pipeline.classify_tile(
        x=x, y=y, msday=msday, meday=meday, acquired=acquired, cfg=cfg,
        source=source or make_source(cfg),
        aux_source=aux_source or make_aux_source(cfg),
        store=store)
