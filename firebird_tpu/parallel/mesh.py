"""Device mesh + sharding for the chip batch.

The reference's only parallelism is bulk-synchronous data parallelism over
space, realized as Spark partitioning + shuffle (SURVEY.md §2.4); its
shuffles exist to fix partition counts and skew (timeseries.py:125,
repartition to CORES*8).  On TPU that whole machinery collapses to a static
even sharding of the chip axis over a jax.sharding.Mesh: CCDC needs no
inter-chip communication, so XLA inserts no collectives on the forward path
and scaling is embarrassing across ICI and DCN alike.  The mesh axes are
('data',) — tensor/pipeline/sequence parallelism are deliberately absent,
matching the algorithm (SURVEY.md §2.4 table; vmap covers the pixel axis,
the time axis stays on-device per pixel).

Multi-host: the same NamedSharding over a multi-host mesh; each host feeds
its addressable shard of the chip batch (jax.make_array_from_process_local_data),
and jax.distributed handles DCN bring-up (parallel.dist).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Monotonic sequence for KV-store scalar allgathers (_kv_global_max):
# processes call in lockstep, so the per-process counters agree.
_kv_seq = itertools.count()


def _kv_global_max(v: int) -> int:
    """Cross-process max of a host scalar through jax.distributed's
    coordination-service KV store — the fallback where jitted
    multiprocess collectives are unavailable (jax<0.5 raises
    "Multiprocess computations aren't implemented on the CPU backend"
    inside multihost_utils.process_allgather)."""
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError("jax.distributed is not initialized")
    seq = next(_kv_seq)
    client.key_value_set(f"fb/gmax/{seq}/{jax.process_index()}",
                         str(int(v)))
    return max(int(client.blocking_key_value_get(
        f"fb/gmax/{seq}/{j}", 120_000))
        for j in range(jax.process_count()))


def make_mesh(devices=None, n_devices: int | None = None) -> Mesh:
    """A 1-D data mesh over the given (or all) devices.

    If the default platform has fewer than n_devices, falls back to the CPU
    platform (where --xla_force_host_platform_device_count can provide
    virtual devices for sharding validation without hardware).
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None and len(devices) < n_devices:
            devices = jax.devices("cpu")
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}")
            devices = devices[:n_devices]
    mesh = Mesh(np.array(devices), axis_names=("data",))
    # Topology gauge for /metrics and the fleet report (merge "max" —
    # the value is the same on every host of a global mesh).
    from firebird_tpu.obs import metrics as obs_metrics

    obs_metrics.gauge("mesh_devices",
                      help="devices in the active data mesh").set(
                          mesh.devices.size)
    return mesh


def chip_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (chip) axis across the data mesh axis."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def spans_processes(mesh: Mesh) -> bool:
    """True when the mesh includes devices of other processes — the one
    predicate deciding both cross-host array assembly (shard_packed) and
    cross-host wcap agreement (detect_sharded); host-local meshes must
    take neither path or mismatched hosts deadlock."""
    return mesh.devices.size != len(mesh.local_devices)


def shard_packed(packed, mesh: Mesh, dtype, prepped=None):
    """Shard a PackedChips batch over the mesh's chip axis.

    Single-process: device_put onto the NamedSharding.  Multi-process
    (jax.distributed): each host passes its process-local slice of the
    global chip batch and jax.make_array_from_process_local_data assembles
    the global sharded arrays — device_put cannot target non-addressable
    devices.

    Every shipped plane is integer (kernel.wire_args: int32 days + n_obs,
    int16 spectra, uint8/uint16 QA) — the float designs, date grid, and
    validity mask are built per shard on device by the jitted program
    (kernel.device_designs).  ``dtype`` and ``prepped`` are retained for
    signature stability but no longer shape the wire (nothing float
    ships); ``prepped`` is ignored.
    """
    import jax.numpy as jnp
    from firebird_tpu.ccd.kernel import wire_args

    del dtype, prepped                     # wire is dtype-free (all int)
    C = packed.spectra.shape[0]
    # Cross-host assembly only when the mesh actually spans processes —
    # a multi-process run may still shard a host-local batch over a mesh
    # of its own (addressable) devices via plain device_put.
    multiproc = spans_processes(mesh)
    n_local = (len(mesh.local_devices) if multiproc else mesh.devices.size)
    if n_local == 0 or C % n_local:
        raise ValueError(
            f"chip batch ({C}) must divide evenly over {n_local} "
            "local devices — pad the batch (static even sharding, no shuffle)")
    sh = chip_sharding(mesh)
    if multiproc:
        put = lambda a: jax.make_array_from_process_local_data(sh, a)
    else:
        put = lambda a: jax.device_put(jnp.asarray(a), sh)
    return tuple(put(a) for a in wire_args(packed))


# ---------------------------------------------------------------------------
# Cross-device straggler rebalancing ring (FIREBIRD_REBALANCE).
#
# Active-lane compaction (kernel._detect_batch_impl) leaves a ragged
# per-device residue: each shard's event loop runs until ITS slowest lane
# finishes, so after most lanes die, whole chips idle while one device
# grinds its tail.  At the bucketed-tail boundary the survivors sit in a
# dense prefix per chip — the cheapest possible migration point: ship the
# stage-2 carry one ring hop rightward, activate only the lanes the donor
# chose to shed, run the tail over own+guest chips, ship the guest
# results back, and merge them positionally into the donor's rows.  The
# exchange is a fixed ring rotation (every device sends exactly once and
# receives exactly once per hop), realized as lax.ppermute on simulated/
# CPU meshes and as the Pallas async-remote-copy kernel
# (pallas_ops.ring_remote_copy — SNIPPETS.md [1]/[2]'s template) on TPU.
# Row identity holds by construction: the donated lanes' state is
# bit-identical on the host device, the tail loop never permutes lanes
# (kernel passes allow_compact=False), and the merge is positional.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RebalanceSpec:
    """Static configuration of the rebalancing ring for one dispatch.

    Hashable on purpose: it rides the ``sharded_detect_fn`` lru_cache
    key and the jit closure, so a knob change mid-process traces a fresh
    program instead of reusing a stale one.  ``threshold`` is the
    alive-count gap (as a fraction of a device's stage-2 lanes,
    chips x bucket) beyond which the donor sheds half the gap;
    ``rdma=True`` routes each hop through the Pallas remote-copy kernel
    (TPU), False through lax.ppermute (CPU / simulated meshes).
    """

    axis: str = "data"
    n: int = 1
    threshold: float = 0.25
    rdma: bool = False

    def _hop(self, shift: int):
        from jax import lax

        if self.rdma:
            from firebird_tpu.ccd import pallas_ops

            def f(x):
                me = lax.axis_index(self.axis)
                return pallas_ops.ring_remote_copy(
                    x, (me + shift) % self.n)

            return f
        pairs = [(i, (i + shift) % self.n) for i in range(self.n)]
        return lambda x: lax.ppermute(x, self.axis, pairs)

    def to_right(self, tree):
        """One hop rightward: device d's payload lands on d+1; returns
        what arrived from the left neighbor."""
        return jax.tree_util.tree_map(self._hop(+1), tree)

    def to_left(self, tree):
        """One hop leftward (the return path, and the count probe: what
        comes back is the RIGHT neighbor's payload)."""
        return jax.tree_util.tree_map(self._hop(-1), tree)


def rebalance_spec(mesh: Mesh):
    """The dispatch's rebalancing configuration, or None when the ring
    is off (FIREBIRD_REBALANCE, default off) or the mesh has a single
    device.  Resolved at program-construction time — the spec is part of
    the sharded program's cache key, like the other trace-time knobs."""
    from firebird_tpu.config import env_knob

    if env_knob("FIREBIRD_REBALANCE") in ("", "0", None):
        return None
    n = int(mesh.devices.size)
    if n < 2:
        return None
    return RebalanceSpec(
        axis=mesh.axis_names[0], n=n,
        threshold=float(env_knob("FIREBIRD_REBALANCE_THRESHOLD")),
        rdma=jax.default_backend() == "tpu")


def rebalance_tail_out(st2, shared, spec: RebalanceSpec, bucket: int):
    """The migration half of the ring, at the stage-2 (bucketed-tail)
    boundary inside the traced per-shard program.

    ``st2`` is the shard's stage-2 carry (state dict incl. residents and
    result buffers, every leaf chip-leading); ``shared`` the chip-shared
    designs dict.  Decides the donation (gap to the RIGHT neighbor over
    the threshold → shed half the gap, taken from the global tail of the
    shard's dense alive prefixes), ships the full carry one hop
    rightward, and returns ``(st2cat, sharedcat, donated,
    lanes_migrated)`` where st2cat/sharedcat hold own + guest chips
    concatenated on the chip axis, guest lanes active only where the
    donor shed them, and the donor's own copies of those lanes parked
    DONE.  ``donated`` [C, bucket] is kept by the donor for the
    positional merge in :func:`rebalance_tail_back`."""
    import jax.numpy as jnp
    from firebird_tpu.ccd.kernel import PHASE_DONE

    phase = st2["phase"]                                   # [C, bucket]
    C = phase.shape[0]
    alive = phase != PHASE_DONE
    n_alive_c = jnp.sum(alive, -1).astype(jnp.int32)       # [C]
    na = jnp.sum(n_alive_c)
    na_right = spec.to_left(na.reshape(1))[0]
    thresh = max(int(spec.threshold * C * bucket), 1)
    gap = na - na_right
    give = jnp.where(gap > thresh, gap // 2, 0)
    # Global lane index over the shard's dense alive prefixes: the
    # donated set is exactly the global tail of size ``give`` (greedy
    # from the last chips), so the count is exact and deterministic.
    off = jnp.cumsum(n_alive_c) - n_alive_c                # exclusive
    lane = jnp.arange(bucket, dtype=jnp.int32)[None, :]
    g_idx = off[:, None] + lane
    donated = (lane < n_alive_c[:, None]) & (g_idx >= na - give)

    guest_st2, guest_sh, guest_don = spec.to_right(
        (st2, shared, donated))
    own = dict(st2, phase=jnp.where(donated, PHASE_DONE, phase))
    guest = dict(guest_st2, phase=jnp.where(
        guest_don, guest_st2["phase"], PHASE_DONE))
    cat = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], 0), own, guest)
    shcat = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], 0), dict(shared), guest_sh)
    return cat, shcat, donated, jnp.sum(donated, -1).astype(jnp.int32)


def rebalance_tail_back(stcat, donated, spec: RebalanceSpec, C: int):
    """The un-migration half: split own/guest, ship the guest results
    back to their owner (one hop leftward), and merge them into the
    donor's rows — positional, because the tail loop pinned lane order
    (allow_compact=False), so ``donated`` still addresses the same rows.
    Only the per-lane OUTPUTS move back (nseg, alive, result buffers);
    the owner's carried permutation was never touched and stays valid
    for the dispatch-exit unpermute."""
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    own = tm(lambda a: a[:C], stcat)
    guest = tm(lambda a: a[C:], stcat)
    ret = spec.to_left({"nseg": guest["nseg"], "alive": guest["alive"],
                        "bufs": guest["bufs"]})
    pick = lambda o, r, nd: jnp.where(
        donated.reshape(donated.shape + (1,) * nd), r, o)
    return dict(own,
                nseg=jnp.where(donated, ret["nseg"], own["nseg"]),
                alive=pick(own["alive"], ret["alive"], 1),
                bufs=tuple(pick(o, r, 1) for o, r in
                           zip(own["bufs"], ret["bufs"])))


def _wcap_global_max(mesh: Mesh, v: int) -> int:
    """Cross-process agreement on a host scalar (the static wcap trace
    constant): every process of a cross-host SPMD dispatch must trace the
    same program even though each only sees its local chip slice.
    Host-local meshes (the driver's per-host loop) must NOT synchronize —
    hosts run different batch counts and a barrier would deadlock."""
    if not spans_processes(mesh):
        return v
    from jax.experimental import multihost_utils
    try:
        return int(np.max(np.asarray(
            multihost_utils.process_allgather(np.array([v])))))
    except Exception as e:
        # ONLY the jax<0.5 CPU backend's deterministic "Multiprocess
        # computations aren't implemented" falls back to the KV
        # store; a transient allgather failure must re-raise — if
        # some processes fell back while others succeeded, the
        # lockstep _kv_seq counters would skew and every later
        # fallback would read the wrong sequence's keys.
        if "Multiprocess computations aren't implemented" not in str(e):
            raise
        return _kv_global_max(v)


def stage_sharded(packed, mesh: Mesh, dtype) -> tuple[tuple, int]:
    """The H2D half of :func:`detect_sharded`, split out so the driver's
    prefetch thread can ship batch i+1 under the run's sharding while
    batch i computes: returns ``(args, wcap)`` — the sharded device
    arrays plus the cross-host-agreed window cap — to pass back through
    ``detect_sharded(..., staged=...)``."""
    import jax.numpy as jnp
    from firebird_tpu.ccd.kernel import ensure_x64, window_cap

    dtype = dtype or jnp.float32
    ensure_x64(dtype)
    wcap = _wcap_global_max(mesh, window_cap(packed))
    args = shard_packed(packed, mesh, dtype)
    jax.block_until_ready(args)
    return args, wcap


def detect_sharded(packed, mesh: Mesh, dtype=None,
                   check_capacity: bool = True,
                   max_segments: int | None = None,
                   staged: tuple | None = None, donate: bool = False,
                   compact: bool | None = None,
                   fused=None, mixed: bool | None = None):
    """Run the CCD kernel with the chip batch sharded over the mesh.

    This is the multi-device production path: same math as
    kernel.detect_packed, chip axis split across devices.  The program is
    a jitted ``jax.shard_map`` over the data axis, which (a) *guarantees*
    the zero-collective property (any accidental cross-chip dependence
    would fail to trace rather than silently all-gather), and (b) gives
    each shard a plain single-device context, so Mosaic custom calls (the
    Pallas CD kernel, FIREBIRD_PALLAS=1) need no SPMD partitioning rule.

    ``staged`` takes the ``(args, wcap)`` pair from :func:`stage_sharded`
    instead of transferring here; ``donate=True`` (honored only with
    ``check_capacity=False`` — a retry would re-dispatch deleted
    buffers) frees the staged wire inputs at dispatch.  ``compact``
    overrides FIREBIRD_COMPACT per call (kernel._detect_batch_core;
    compaction is per-shard — each shard permutes its own chips' lanes,
    so no cross-shard dependence is introduced and the zero-collective
    property holds).  ``fused`` (False/True/"mon") and ``mixed``
    override FIREBIRD_FUSED_FIT / FIREBIRD_MIXED_PRECISION likewise.

    The one deliberate exception to zero-collectives is the straggler
    rebalancing ring (FIREBIRD_REBALANCE, default off): three
    straight-line ring exchanges at the bucketed-tail boundary (count
    probe, migrate out, migrate back — rebalance_tail_out/_back), never
    a collective inside the event loop, stores row-identical
    (tests/test_fuse.py proves it on the simulated mesh).
    """
    import jax.numpy as jnp
    from firebird_tpu.ccd.kernel import (MAX_SEGMENTS, capacity_bound,
                                         capacity_retry, ensure_x64)

    dtype = dtype or jnp.float32
    ensure_x64(dtype)
    args, wcap = staged if staged is not None \
        else stage_sharded(packed, mesh, dtype)
    do_donate = donate and not check_capacity

    def dispatch(S):
        from firebird_tpu.ccd.kernel import record_first_call

        rb = rebalance_spec(mesh)
        fn = sharded_detect_fn(mesh, jnp.dtype(dtype), wcap,
                               packed.sensor, max_segments=S,
                               donate=do_donate, compact=compact,
                               fused=fused, mixed=mixed, rebalance=rb)
        return record_first_call(
            ("sharded", packed.spectra.shape, str(jnp.dtype(dtype)), wcap,
             packed.sensor.name, S, len(mesh.devices.flat), compact,
             fused, mixed, rb),
            lambda: fn(*args))

    def read_worst(seg):
        # Every process must agree on the retry, so max-reduce the local
        # worst (read from addressable shards only — the global array is
        # not fetchable under multi-process sharding).
        return _wcap_global_max(mesh, max(
            int(np.asarray(s.data).max())
            for s in seg.n_segments.addressable_shards))

    S0 = max_segments or MAX_SEGMENTS
    if not check_capacity:
        return dispatch(max(S0, 1))
    return capacity_retry(dispatch, read_worst, S0, capacity_bound(packed))


@functools.lru_cache(maxsize=None)
def sharded_detect_fn(mesh: Mesh, dtype, wcap: int, sensor,
                      max_segments: int | None = None,
                      donate: bool = False,
                      compact: bool | None = None,
                      fused=None, mixed: bool | None = None,
                      rebalance: RebalanceSpec | None = None):
    """The jitted shard_map program, cached per (mesh, dtype, wcap, sensor,
    capacity) — rebuilding the jit wrapper per batch would retrace every
    dispatch.

    Public two-step API (with shard_packed) for callers that need the
    transfer and the dispatch separately — the bench times them apart;
    detect_sharded composes them for everyone else."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec
    from firebird_tpu.ccd.kernel import MAX_SEGMENTS, _detect_batch_core

    core = functools.partial(_detect_batch_core, wcap=wcap, sensor=sensor,
                             max_segments=max_segments or MAX_SEGMENTS,
                             dtype=dtype, compact=compact, fused=fused,
                             mixed=mixed, rebalance=rebalance)

    def local_batch(days, n_obs, Y_i16, qa_wire):
        # All-integer wire: each shard builds its own chips' float
        # designs/date grid/validity mask on device (kernel.device_designs
        # is per-chip math — no cross-shard dependence), and the core
        # widens the spectra itself, keeping an int16 resident copy for
        # the Pallas fit path's HBM reads.  The batched core (not vmap of
        # the per-chip core): its phase-gated lax.conds must stay scalar
        # per shard to skip work.
        from firebird_tpu.ccd.kernel import device_designs

        Xs, Xts, t, valid = device_designs(days, n_obs, dtype)
        return core(Xs, Xts, t, valid, Y_i16, qa_wire.astype(jnp.int32))

    spec = PartitionSpec("data")
    # check_vma=False: the kernel's scan/while carries start from
    # shard-constant zeros, which the varying-axes checker would demand
    # explicit pcasts for; the collective-freedom claim is structural
    # (nothing in _detect_core mentions the mesh axis at all).
    wrapped = jax.shard_map(local_batch, mesh=mesh, in_specs=(spec,) * 4,
                            out_specs=spec, check_vma=False)
    # Donation frees the sharded wire inputs (spectra + QA) at dispatch —
    # the driver's staged single-dispatch path only; capacity-retry
    # callers take the non-donating cache entry (kernel.detect_packed's
    # same rule).
    return jax.jit(wrapped, donate_argnums=(2, 3) if donate else ())


def aot_compile_sharded(mesh: Mesh, dtype, wcap: int, sensor, shapes,
                        max_segments: int | None = None,
                        donate: bool = False,
                        compact: bool | None = None,
                        fused=None, mixed: bool | None = None):
    """AOT lower+compile the sharded batch program for a shape without
    running it (``shapes``: the 4 global array shapes in shard_packed's
    argument order — days [C,T], n_obs [C], spectra [C,B,P,T], QA
    [C,P,T]; wire dtypes applied here, QA following the
    FIREBIRD_WIRE_QA8 knob like the real stage).  The sharded half of
    kernel.aot_compile, for driver.core.warm_start on multi-device
    topologies.  ``compact`` must match the real dispatch's value (see
    kernel.aot_compile)."""
    import jax.numpy as jnp
    from firebird_tpu.ccd.kernel import wire_qa_dtype

    fn = sharded_detect_fn(mesh, jnp.dtype(dtype), wcap, sensor,
                           max_segments=max_segments, donate=donate,
                           compact=compact, fused=fused, mixed=mixed,
                           rebalance=rebalance_spec(mesh))
    sh = chip_sharding(mesh)
    dts = (jnp.int32, jnp.int32, jnp.int16, wire_qa_dtype())
    avatars = tuple(jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=sh)
                    for s, d in zip(shapes, dts))
    return fn.lower(*avatars).compile()
