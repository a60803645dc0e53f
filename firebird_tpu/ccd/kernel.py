"""The CCDC TPU kernel: whole chips per dispatch, jit + vmap, no per-pixel
Python.

This replaces the reference's hot loop — ``ccd.detect`` called per pixel
inside a Spark flatMap (ccdc/pyccd.py:171-183; "pure CPU, seconds per pixel
series", SURVEY.md §3.1) — with a fixed-shape JAX program that runs all
10,000 pixels of a chip in lockstep and implements the same spec as
:mod:`firebird_tpu.ccd.reference`.

Design: **event-horizon fast-forward.**  CCDC is a per-pixel sequential
state machine, but between model refits its decisions depend only on the
*current* model.  So instead of scanning observation-by-observation, each
round advances every pixel to its next *model event*:

- INIT pixels derive their initialization window, run the Tmask IRLS screen,
  and test stability — one batched fit.
- MONITOR pixels score *all* remaining observations against their current
  model in one shot ([P, T] ops against the chip-shared design matrix) and
  locate the first event in closed form: a confirmed break (six consecutive
  exceeding observations, found via shifted-AND on the compacted alive
  sequence), a refit point (absorbed-count crossing the 1.33x ladder), or
  the series tail.  Everything before the event is absorbed/removed per the
  spec's rules without iteration.

Every round's heavy math is a handful of [P,T]x[T,8] matmuls (MXU) plus
fixed-iteration coordinate descent on [P,7,8] Gram systems; the number of
rounds equals the deepest pixel's event count (typically a few dozen), not
the series length.  The dates grid — and therefore the design matrix — is
shared chip-wide, which is what makes the batching work; the wire path
builds the designs ON DEVICE from the int32 day ordinals (device_designs;
an exact-integer phase reduction keeps the phase argument bit-identical
to the host float64 spec in harmonic.design_matrix), so nothing float
crosses the h2d wire at all.

Batching over chips is a vmap; sharding over devices is a NamedSharding on
the chip axis (firebird_tpu.parallel).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from firebird_tpu.ccd import harmonic, params
from firebird_tpu.ccd.sensor import LANDSAT_ARD, chi2_thresholds

MAX_SEGMENTS = 10

PHASE_INIT, PHASE_MONITOR, PHASE_DONE = 0, 1, 2
PROC_STANDARD, PROC_SNOW, PROC_INSUF, PROC_NODATA = 0, 1, 2, 3


def use_pallas(component: str = "lasso") -> bool:
    """Whether `component` runs as its Pallas VMEM-resident kernel.

    FIREBIRD_PALLAS is "0"/"" (none), "1" (all), or a comma list of
    component names ("lasso,monitor,tmask,fit,score,init") — bench.py
    tunes the components independently on hardware, so a kernel that
    loses on a given toolchain can't drag down the ones that win.
    "fit" (the fused Gram+corr+CD+RMSE kernel) supersedes "lasso" (CD
    loop only) at the fit call sites; "score" (the score-fused monitor
    kernel) supersedes "monitor"; "init" (the fused INIT-window kernel)
    supersedes "tmask" inside the init block; "mega" (the whole-loop
    kernel) supersedes ALL of them and must be named explicitly — "1"
    means every per-component kernel, not the mega route, so existing
    "all-on" configs keep their meaning.  Read at trace time: set it
    before the first detect call — already-compiled programs keep their
    path."""
    from firebird_tpu.config import env_knob

    v = env_knob("FIREBIRD_PALLAS")
    if v in ("", "0"):
        return False
    if v == "1":
        return component != "mega"
    return component in {c.strip() for c in v.split(",")}


def _wire_resident_only() -> bool:
    """True when every event-loop consumer of the widened float spectra
    is routed to a Pallas kernel reading the wire-dtype residents (the
    init, score, and fit components together) — the prologue then keeps
    the float view out of ``res`` so XLA frees it after the pre-loop
    work.  _detect_batch_impl combines this with the f32-on-TPU gate
    (the float64-on-TPU fallback keeps the float view resident) and
    independently with the mega route (which reads only the wire residents by
    construction, but only when mega_fits accepts the shape — a refused
    mega must fall back to a loop that still has its float view)."""
    return (use_pallas("init") and use_pallas("score")
            and use_pallas("fit"))


def use_fused_fit() -> bool:
    """Whether the event loop's segment-close + shared-Lasso-fit pair
    runs as the fused Pallas gram→CD→close kernel
    (pallas_ops.fused_fit_close): one VMEM residency of the wire spectra
    serves both phases instead of two HBM streams plus the [P,*]
    intermediates between the cond-gated fusions.  FIREBIRD_FUSED_FIT,
    default off; read at trace time like use_pallas (f32-on-TPU only
    when compiled; interpret elsewhere); the mega route supersedes it."""
    from firebird_tpu.config import env_knob

    return env_knob("FIREBIRD_FUSED_FIT") not in ("", "0")


def fused_mode():
    """FIREBIRD_FUSED_FIT's three-way resolution: 0 (off), 1 (the fused
    fit+close kernel, byte-identical to the unfused chain), or "mon"
    (value "mon" or "2" — the monitor-fused round kernel
    pallas_ops.fused_round, one VMEM residency for the whole post-INIT
    round; decision-exact with the seg_mag f32 envelope, like the mega
    route).  Read at trace time like use_pallas."""
    from firebird_tpu.config import env_knob

    v = env_knob("FIREBIRD_FUSED_FIT")
    if v in ("", "0"):
        return 0
    if v in ("2", "mon"):
        return "mon"
    return 1


def use_mixed_precision() -> bool:
    """Whether the fit kernels accumulate the Gram/corr dots in bf16
    split form (f32 accumulators, int32 counts) instead of the 6-pass
    f32-"highest" emulation — pallas_ops._gram_cd_core's ``mixed``
    path.  Decision fields stay identical to the f32 path (the split
    exploits the int16-valued spectra and 0/1 weights; coef/rmse drift
    is bounded by params.MIXED_ULP_BUDGET — tools/precision_smoke.py
    enforces both).  FIREBIRD_MIXED_PRECISION, default off; read at
    trace time like use_pallas; applies only to f32 stores (the f64
    bit-parity path keeps full precision) and only to the Pallas fit
    routes — the XLA reference path stays f32, it IS the oracle the
    identity tests compare against."""
    from firebird_tpu.config import env_knob

    return env_knob("FIREBIRD_MIXED_PRECISION") not in ("", "0")


# ---------------------------------------------------------------------------
# Results container
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ChipSegments:
    """Fixed-capacity per-pixel segment results (device or host arrays).

    Leading axes may be [P] (one chip) or [C, P] (a batch).
    seg_meta fields: sday, eday, bday, chprob, curqa, nobs.
    seg_coef holds *internal* coefficients [.., 7 bands, 8]; convert with
    harmonic.to_pyccd_convention(anchor=first series date).
    """

    n_segments: jnp.ndarray      # [.., P] int32
    seg_meta: jnp.ndarray        # [.., P, S, 6] float32
    seg_rmse: jnp.ndarray        # [.., P, S, 7]
    seg_mag: jnp.ndarray         # [.., P, S, 7]
    seg_coef: jnp.ndarray        # [.., P, S, 7, 8]
    mask: jnp.ndarray            # [.., P, T] bool — processing mask
    procedure: jnp.ndarray       # [.., P] int32
    rounds: jnp.ndarray | None = None  # [..] int32 event-loop rounds (diag)
    vario: jnp.ndarray | None = None   # [.., P, 7] variogram (streaming seed)
    round_counts: jnp.ndarray | None = None
    # ^ [.., 3] int32: rounds in which the cond-gated INIT / shared-fit /
    #   segment-close blocks actually executed (diagnostic; feeds the
    #   measurement-driven roofline model in ccd.flops / bench.py).
    occupancy: jnp.ndarray | None = None
    # ^ [.., R_max, 2] int32 per-chip, per-executed-round (active_lanes,
    #   paid_lanes): active = lanes with phase != DONE entering the round,
    #   paid = lanes in COMPACT_LANE_BLOCK-wide blocks containing any
    #   active lane (the skip-guard accounting unit; full width when
    #   compaction is off).  The capture is the same on every backend so
    #   CPU runs predict TPU behavior — which means ``paid`` is measured
    #   compute only where the Pallas per-block guards execute; the lax
    #   fallback paths carry the guards for control-flow parity but
    #   compute every lane (under vmap the slab cond is a select), so
    #   there ``paid`` models what the guards would skip and only the
    #   stage-2 bucket narrows real work.  Rows past ``rounds`` are
    #   zero.  Feeds flops.occupancy_detail and record_occupancy.
    compactions: jnp.ndarray | None = None
    # ^ [..] int32: dense-prefix compactions the batch's loop performed,
    #   recorded at each loop's first chip row and zero elsewhere — sum
    #   over the chip axis for the batch total (correct under sharding,
    #   where each shard runs its own loop; see _detect_batch_impl).
    lanes_migrated: jnp.ndarray | None = None
    # ^ [..] int32 per chip: straggler lanes this chip DONATED to the
    #   right-neighbor device through the rebalancing ring at the
    #   bucketed-tail boundary (parallel.mesh.rebalance_tail_out).  The
    #   donated lanes' results are computed on the neighbor and merged
    #   back positionally, so stores stay row-identical; the chip-sum
    #   feeds the kernel_lanes_migrated counter (record_occupancy).
    #   None on every non-rebalancing dispatch.


jax.tree_util.register_pytree_node(
    ChipSegments,
    lambda s: ((s.n_segments, s.seg_meta, s.seg_rmse, s.seg_mag, s.seg_coef,
                s.mask, s.procedure, s.rounds, s.vario, s.round_counts,
                s.occupancy, s.compactions, s.lanes_migrated),
               None),
    lambda _, c: ChipSegments(*c),
)


# ---------------------------------------------------------------------------
# Small batched primitives
# ---------------------------------------------------------------------------

def _bitonic_sort_last(x):
    """Ascending sort along the (static) last axis as a bitonic network:
    log^2(W) stages of reshape + min/max + select, no generic Sort HLO.
    XLA's Sort is the slowest primitive in this kernel on both CPU and
    TPU for the many-rows/short-axis shapes the medians use; the network
    is pure elementwise VPU work and produces bit-identical values for
    non-NaN data.  A NaN input poisons its whole row (min/max propagate),
    unlike Sort's NaNs-last — acceptable here because the only upstream
    NaN source is a degenerate Tmask Gram, whose terminal behavior
    (comparisons read False, nothing flagged) is NaN-absorbing either
    way.  Non-power-of-two axes pad with +inf (dropped before
    returning)."""
    W = x.shape[-1]
    if W <= 1:
        return x
    n = 1 << (W - 1).bit_length()
    if n != W:
        pad = jnp.full(x.shape[:-1] + (n - W,), jnp.inf, x.dtype)
        x = jnp.concatenate([x, pad], axis=-1)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            shp = x.shape
            x2 = x.reshape(shp[:-1] + (n // (2 * j), 2, j))
            a, b = x2[..., 0, :], x2[..., 1, :]
            lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
            # ascending iff bit k of the element's absolute index is 0;
            # that bit is constant across the (pair, lane) axes.
            asc = ((jnp.arange(n // (2 * j)) * (2 * j)) & k) == 0
            asc = asc[(None,) * (lo.ndim - 2) + (slice(None), None)]
            x = jnp.stack([jnp.where(asc, lo, hi),
                           jnp.where(asc, hi, lo)], axis=-2).reshape(shp)
            j //= 2
        k *= 2
    return x[..., :W]


def _onehot_take(x, i):
    """x[..., i] along the last axis via a masked one-hot reduce.

    take_along_axis per-lane gathers along the minor axis lower to
    serialized loops on TPU (each profiled at ~0.5 ms/round in the event
    loop); the reduce is one fused elementwise pass.
    """
    k = jnp.arange(x.shape[-1])
    return jnp.sum(jnp.where(k == i[..., None], x, 0), -1)


def _masked_median(x, m):
    """Median of x where m, along the last axis (numpy even-count average)."""
    s = _bitonic_sort_last(jnp.where(m, x, jnp.inf))
    n = jnp.sum(m, axis=-1)
    lo = _onehot_take(s, jnp.maximum((n - 1) // 2, 0))
    hi = _onehot_take(s, jnp.maximum(n // 2, 0))
    med = 0.5 * (lo + hi)
    return jnp.where(n > 0, med, 0.0)


def _fit_lasso_coefs(X, Y, w, coefmask, XX=None, active=None):
    """Batched Lasso coefficients via cyclic coordinate descent on Grams.

    Mirrors harmonic.lasso_cd_gram exactly (same update, same iteration
    count, intercept unpenalized); column restriction (4/6/8 coefs) is the
    coefmask — zeroed coordinates never update, which is equivalent to
    fitting with fewer design columns.

    Args:
        X: [T, 8] design (chip-shared).
        Y: [P, 7, T] observations.
        w: [P, T] 0/1 weights (the fit window).
        coefmask: [P, 8] allowed coefficients.
        XX: optional [T, 64] flattened per-row outer products X[t] X[t]^T,
            precomputed once per chip.  The 0/1 weights make the two Gram
            formulations bit-identical per term, and [P,T]x[T,64] is one
            MXU matmul instead of a [P,T,8] broadcast temporary.
        active: optional [P] bool skip guard (compaction mode): pixels
            outside it are guaranteed all-zero ``w`` rows, whose CD
            output is exactly zero — so the Pallas kernel skips whole
            dead lane blocks (a per-block ``pl.when``) and the lax path
            cond-gates the CD slab on any(active).  ``None`` preserves
            the unguarded program.

    Returns:
        coefs [P,7,8].
    """
    K = params.MAX_COEFS
    n = jnp.maximum(jnp.sum(w, -1), 1.0)                       # [P]
    if XX is None:
        XX = (X[:, :, None] * X[:, None, :]).reshape(-1, K * K)
    G = (w @ XX).reshape(-1, K, K) / n[:, None, None]          # [P,8,8]
    c = jnp.einsum("pbt,tc->pbc", Y * w[:, None, :], X) / n[:, None, None]
    diag = jnp.maximum(jnp.diagonal(G, axis1=-2, axis2=-1), 1e-12)  # [P,8]

    if use_pallas("lasso"):
        on_tpu = jax.default_backend() == "tpu"
        # Mosaic cannot lower float64; compiled Pallas is f32-on-TPU only.
        # Off-TPU the same kernel runs interpreted (tests), any dtype.
        if not on_tpu or c.dtype == jnp.float32:
            from firebird_tpu.ccd import pallas_ops

            return pallas_ops.lasso_cd(G, c, diag, coefmask,
                                       active=active,
                                       interpret=not on_tpu)
    if active is None:
        return _lasso_cd_lax(G, c, diag, coefmask)
    # The lax slab guard: an all-dead slab (here the slab is the whole
    # call — the batch-level cond gates already bound it) skips the CD
    # loop for the exact zeros it would compute.  Under vmap the cond
    # degenerates to a select; the value is identical either way, so
    # tier-1 CPU runs exercise the same control flow the Pallas
    # per-block guards take on TPU.
    return lax.cond(jnp.any(active),
                    lambda: _lasso_cd_lax(G, c, diag, coefmask),
                    lambda: jnp.zeros_like(c))


def _lasso_cd_lax(G, c, diag, coefmask):
    """The CD loop as a lax fori_loop (the default / reference path; the
    Pallas VMEM-resident version is pallas_ops.lasso_cd)."""
    alpha = params.LASSO_ALPHA

    def one_iter(_, b):
        for j in range(params.MAX_COEFS):
            rho = (c[..., j] - jnp.sum(G[:, j, None, :] * b, -1)
                   + diag[:, j][:, None] * b[..., j])
            if j == 0:
                bj = rho / diag[:, j][:, None]
            else:
                bj = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - alpha, 0.0) \
                    / diag[:, j][:, None]
            bj = jnp.where(coefmask[:, j][:, None], bj, 0.0)
            b = b.at[..., j].set(bj)
        return b

    b0 = jnp.zeros_like(c)
    return lax.fori_loop(0, params.LASSO_ITERS, one_iter, b0)


def _fit_lasso(X, Y, w, coefmask, XX=None, active=None):
    """_fit_lasso_coefs plus the weighted-window RMSE.

    Returns:
        (coefs [P,7,8], rmse [P,7]).
    """
    b = _fit_lasso_coefs(X, Y, w, coefmask, XX=XX, active=active)
    n = jnp.maximum(jnp.sum(w, -1), 1.0)
    pred = jnp.einsum("pbc,tc->pbt", b, X)
    r = Y - pred
    rmse = jnp.sqrt(jnp.maximum(
        jnp.sum(r * r * w[:, None, :], -1) / n[:, None], 0.0))
    return b, rmse


def _coefmask_for(n):
    """[P,8] allowed-coefficient mask from per-pixel obs counts (4/6/8)."""
    nc = jnp.where(n >= params.MAX_COEFS * params.NUM_OBS_FACTOR, 8,
                   jnp.where(n >= params.MID_COEFS * params.NUM_OBS_FACTOR, 6, 4))
    return jnp.arange(params.MAX_COEFS)[None, :] < nc[:, None]


def _chol_solve_small(G, c):
    """Solve G x = c for SPD G [.., n*n] (row-major flat), c [.., n] with
    n tiny and static: fully unrolled Cholesky + two substitutions as
    elementwise ops over the batch lanes — no LAPACK-style
    Cholesky/TriangularSolve HLOs, which are latency-bound at small n.
    G is FLAT on purpose: a [.., 5, 5] trailing shape takes a TPU tiled
    layout padded 8x128 (20x the logical bytes), and the per-IRLS-round
    relayout copies showed up at ~2 ms each in the profile.

    Numerically non-PD lanes (a pivot <= 0) return NaN, matching
    jnp.linalg.cholesky — callers' downstream comparisons then read
    False, which is the degenerate-Gram contract _tmask_bad relies on
    (flag nothing rather than fabricate huge betas)."""
    n = c.shape[-1]
    ok = None
    L = [[None] * n for _ in range(n)]
    for i in range(n):
        for j in range(i + 1):
            s = G[..., i * n + j]
            for q in range(j):
                s = s - L[i][q] * L[j][q]
            if i == j:
                pos = s > 0
                ok = pos if ok is None else ok & pos
                L[i][j] = jnp.sqrt(jnp.maximum(s, 1e-30))
            else:
                L[i][j] = s / L[j][j]
    y = [None] * n
    for i in range(n):
        s = c[..., i]
        for q in range(i):
            s = s - L[i][q] * y[q]
        y[i] = s / L[i][i]
    x = [None] * n
    for i in reversed(range(n)):
        s = y[i]
        for q in range(i + 1, n):
            s = s - L[q][i] * x[q]
        x[i] = s / L[i][i]
    out = jnp.stack(x, axis=-1)
    return jnp.where(ok[..., None], out, jnp.nan)


def _tmask_bad(Xtw, Y2, w, vario2):
    """Batched Tmask: IRLS Huber harmonic fit on the Tmask bands.

    Mirrors harmonic.irls_huber + reference.tmask_outliers: fixed
    TMASK_IRLS_ITERS iterations, MAD sigma, Huber weights, outlier if the
    final absolute residual exceeds TMASK_CONST * variogram in any band.

    Operates on the *compacted window* axis W (the gathered init-window
    members, bounded by the host-computed window cap) — the per-iteration
    median/MAD selections and Gram builds run over W instead of the full
    series, which is what makes the per-round Tmask cheap.

    Args:
        Xtw: [P, W, 5] no-trend design rows gathered at the window members.
        Y2: [P, 2, W] Tmask-band observations at the window members.
        w: [P, W] 0/1 validity of each gathered slot.
        vario2: [P, 2].

    Returns:
        bad [P, W] bool (within the window).
    """
    k = params.HUBER_K
    nt = Xtw.shape[-1]
    # Per-member design outer products, shared by every IRLS Gram build:
    # each solve is then one [P,2,W]x[P,W,nt^2] dot producing a FLAT Gram
    # instead of a 4-operand einsum whose [.., nt, nt] output takes a
    # padded tiled layout (6 Gram einsums + relayout copies were ~27 ms
    # of the profiled dispatch).
    XtXt = (Xtw[..., :, None] * Xtw[..., None, :]
            ).reshape(*Xtw.shape[:-1], nt * nt)                # [P,W,25]
    eye = (1e-9 * jnp.eye(nt, dtype=Xtw.dtype)).reshape(nt * nt)

    def solve(wt):
        # wt [P,2,W] weights -> beta [P,2,nt].  SPD solve via an unrolled
        # Cholesky over the batch lanes (_chol_solve_small): nt is a tiny
        # static 5, and XLA's batched Cholesky/TriangularSolve run a
        # LAPACK-shaped blocked algorithm that is latency-bound at this
        # size on both CPU and TPU.  Gram/corr are broadcast-multiply-
        # reduce fusions, NOT batched dots: a [2,W]x[W,25] matmul per
        # pixel makes XLA grid over the 10k-pixel batch axis (profiled
        # ~3.4 ms per solve vs ~0.1 ms of actual bytes).
        G = jnp.sum(wt[:, :, :, None] * XtXt[:, None, :, :], axis=2)
        cc = jnp.sum((Y2 * wt)[:, :, :, None] * Xtw[:, None, :, :], axis=2)
        return _chol_solve_small(G + eye, cc)

    def pred(beta):
        return jnp.sum(beta[:, :, None, :] * Xtw[:, None, :, :], axis=-1)

    w2 = jnp.broadcast_to(w[:, None, :], Y2.shape).astype(Y2.dtype)
    beta = solve(w2)
    for _ in range(params.TMASK_IRLS_ITERS):
        r = Y2 - pred(beta)
        med = _masked_median(r, w2 > 0)
        mad = _masked_median(jnp.abs(r - med[..., None]), w2 > 0)
        sigma = jnp.maximum(mad / 0.6745, 1e-6)
        a = jnp.abs(r) / (k * sigma[..., None])
        huber = jnp.where(a <= 1.0, 1.0, 1.0 / jnp.maximum(a, 1e-12))
        beta = solve(w2 * huber)
    r = jnp.abs(Y2 - pred(beta))
    bad = (r > params.TMASK_CONST * vario2[..., None]) & (w2 > 0)
    return jnp.any(bad, axis=1)


# ---------------------------------------------------------------------------
# Preprocessing (QA triage, dedup, variogram)
# ---------------------------------------------------------------------------

def _qa_bit(qa, bit):
    return (qa >> bit) & 1 == 1


def _dedup_first(cand, same_prev):
    """Keep the first candidate per equal-date group.

    cand [P,T]; same_prev [T] marks t[k]==t[k-1] (chip-shared).  Scan over T
    carrying 'a candidate was already kept in this group'.
    """
    def step(carry, xs):
        cand_t, same_t = xs
        seen = jnp.where(same_t, carry, False)
        keep = cand_t & ~seen
        return seen | cand_t, keep

    _, keep = lax.scan(step, jnp.zeros(cand.shape[0], bool),
                       (cand.T, same_prev))
    return keep.T


def _variogram(Y, usable, t=None, adjusted=False):
    """[P,B] median |successive difference| over usable obs, floor 1e-6.

    Successive usable values pair up via an associative last-valid scan
    along T (log T combine steps of elementwise selects) instead of
    compacting with a [P,B,T] gather: per-lane gathers along the time
    axis lower to serialized fusion loops on TPU — profiled at 0.77 s per
    chip, 37% of the whole dispatch.  The difference set is identical to
    the compacted successive-diff formulation (each usable obs with a
    usable predecessor contributes exactly one pair), so the median is
    bit-identical.

    ``adjusted=True`` (with ``t`` [T]) applies the reconstructed
    lcmap-pyccd adjusted_variogram rule (reference.variogram,
    docs/DIVERGENCE.md #1): keep only pairs more than VARIOGRAM_GAP_DAYS
    apart; per pixel, if no pair clears the gap, fall back to the plain
    set.  The selection is date-driven and shared across bands, as in
    pyccd.

    Bands are independent, so the scan + bitonic median run per band
    under lax.map — the sort's working set is [P,T] instead of [P,B,T],
    cutting the prologue's peak memory ~B-fold at identical per-element
    math (one-time cost; wall impact negligible).
    """
    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av), af | bf

    pair_sel = None
    if adjusted:
        tb = jnp.broadcast_to(t[None, :], usable.shape)
        tv, tf = lax.associative_scan(op, (jnp.where(usable, tb, 0.0),
                                           usable), axis=-1)
        prev_t = jnp.concatenate([jnp.zeros_like(tv[..., :1]),
                                  tv[..., :-1]], -1)
        prev_tf = jnp.concatenate([jnp.zeros_like(tf[..., :1]),
                                   tf[..., :-1]], -1)
        gap_ok = (tb - prev_t) > params.VARIOGRAM_GAP_DAYS
        base_ok = usable & prev_tf
        sel = base_ok & gap_ok
        # pyccd's fallback: no qualifying pair -> plain successive diffs
        pair_sel = jnp.where(jnp.any(sel, -1, keepdims=True), sel, base_ok)

    def one_band(yb):                                          # [P,T]
        v, f = lax.associative_scan(op, (jnp.where(usable, yb, 0.0),
                                         usable), axis=-1)
        prev_v = jnp.concatenate([jnp.zeros_like(v[..., :1]),
                                  v[..., :-1]], -1)
        prev_f = jnp.concatenate([jnp.zeros_like(f[..., :1]),
                                  f[..., :-1]], -1)
        pair_ok = usable & prev_f               # usable with a predecessor
        if pair_sel is not None:
            pair_ok = pair_sel
        d = jnp.abs(yb - prev_v)
        return _masked_median(d, pair_ok)                      # [P]

    v = lax.map(one_band, Y.transpose(1, 0, 2)).T              # [P,B]
    m = jnp.sum(usable, -1)                                     # [P]
    return jnp.where((m >= 2)[:, None], jnp.maximum(v, 1e-6), 1.0)


# ---------------------------------------------------------------------------
# The detector
# ---------------------------------------------------------------------------

def _first_at_or_after(mask, i):
    """First True position >= i in mask [P,T]; (exists [P], idx [P])."""
    T = mask.shape[-1]
    ar = jnp.arange(T)[None, :]
    m = mask & (ar >= i[:, None])
    return jnp.any(m, -1), jnp.argmax(m, -1)


def _monitor_chain(s, alive, included, rank, cur_k, n_last_fit, in_mon, *,
                   change_thr: float, outlier_thr: float):
    """The MONITOR fast-forward event logic: score-derived break/refit/
    tail location in rank space (see the body walkthrough in
    _mon_block).  Pure function of the round state so the Pallas
    twin (pallas_ops.monitor_chain, FIREBIRD_PALLAS=1) can replace it —
    the chain is a pipeline of cumulative/reduce ops over T whose
    intermediates otherwise stream through HBM between fusions.

    Returns a dict: m, is_tail, is_brk, is_refit, ev_rank, pos_ev,
    n_exceed, n_rf, inc_q, rem_q.
    """
    P, T = s.shape
    ar = jnp.arange(T)[None, :]
    INF = T + 1
    m = jnp.sum(alive, -1)                                    # [P]
    kq = jnp.sum(alive & (ar < cur_k[:, None]), -1)           # cursor rank

    ex = alive & (s > change_thr)
    # Consecutive-exceeding run length starting at each alive obs:
    # (rank of next alive non-exceeding obs, else m) - own rank.
    reset_r = jnp.where(alive & ~ex, rank, INF)
    nrr = lax.cummin(reset_r, axis=1, reverse=True)
    runlen = jnp.minimum(nrr, m[:, None]) - rank
    elig = alive & (rank >= kq[:, None])
    brk = elig & ex & (runlen >= params.PEEK_SIZE)
    has_brk = jnp.any(brk, -1)
    b_abs = jnp.argmax(brk, -1)

    o = s > outlier_thr
    absq = elig & ~o
    n0 = jnp.sum(included, -1)
    n_inc = n0[:, None] + jnp.cumsum(absq, -1)
    refit_hit = absq & (n_inc >= params.REFIT_FACTOR
                        * n_last_fit[:, None])
    has_refit = jnp.any(refit_hit, -1)
    f_abs = jnp.argmax(refit_hit, -1)

    q_tail = jnp.maximum(m - (params.PEEK_SIZE - 1), kq)      # a rank

    def rank_at(idx):
        return jnp.take_along_axis(rank, idx[:, None], -1)[:, 0]

    b_ev = jnp.where(has_brk, rank_at(b_abs), INF)
    f_ev = jnp.where(has_refit, rank_at(f_abs), INF)
    is_tail = in_mon & (q_tail <= jnp.minimum(b_ev, f_ev))
    is_brk = in_mon & ~is_tail & has_brk & (b_ev <= f_ev)
    is_refit = in_mon & ~is_tail & ~is_brk & has_refit

    ev_rank = jnp.where(is_tail, q_tail, jnp.where(is_brk, b_ev, f_ev))

    # Normal-rules region ends before the event (inclusive for refit).
    normal_hi = jnp.where(is_refit, ev_rank + 1, ev_rank)     # exclusive
    normalq = elig & (rank < normal_hi[:, None])
    inc_q = normalq & ~o
    rem_q = normalq & o
    # Tail region: score <= threshold absorbed, else removed+counted.
    tailq = elig & (rank >= q_tail[:, None]) & is_tail[:, None]
    tail_ex = tailq & (s > change_thr)
    inc_q = inc_q | (tailq & ~tail_ex)
    rem_q = rem_q | tail_ex
    n_exceed = jnp.sum(tail_ex, -1)

    pos_ev = jnp.where(is_brk, b_abs, f_abs)
    n_rf = jnp.take_along_axis(n_inc, pos_ev[:, None], -1)[:, 0]
    return dict(m=m, is_tail=is_tail, is_brk=is_brk, is_refit=is_refit,
                ev_rank=ev_rank, pos_ev=pos_ev, n_exceed=n_exceed,
                n_rf=n_rf, inc_q=inc_q, rem_q=rem_q)


def _detect_core(X, Xt, t, valid, Y, qa, *, wcap: int | None = None,
                 sensor=LANDSAT_ARD, max_segments: int = MAX_SEGMENTS,
                 dtype=None):
    """One chip (X [T,8], Xt [T,5], t [T], valid [T], Y [B,P,T], qa [P,T]
    int32) — a batch of one through :func:`_detect_batch_core`."""
    out = _detect_batch_core(X[None], Xt[None], t[None], valid[None],
                             Y[None], qa[None], wcap=wcap, sensor=sensor,
                             max_segments=max_segments, dtype=dtype)
    return jax.tree_util.tree_map(lambda a: a[0], out)


def _fit_chip(res, w, coefmask, with_rmse=True, *, fit_pallas, on_tpu,
              mixed=False, active=None):
    """One chip's batched Lasso fit, routed to the winning implementation
    (the fused Pallas Gram+corr+CD+RMSE kernel reads the wire-dtype
    resident spectra; the lax path reads the widened float view).
    ``mixed`` (FIREBIRD_MIXED_PRECISION) selects the bf16 split-dot
    Gram on the Pallas route only — the XLA path stays f32 (it is the
    oracle the decision-identity tests compare against).  ``active`` is
    the compaction-mode skip guard: pixels outside it carry all-zero
    windows, so dead lane blocks are skipped for the zeros they would
    compute (see _fit_lasso_coefs)."""
    if fit_pallas:
        from firebird_tpu.ccd import pallas_ops

        b, r = pallas_ops.lasso_fit(res["Yt"], w, res["X"], coefmask,
                                    with_rmse=with_rmse, mixed=mixed,
                                    active=active, interpret=not on_tpu)
        return (b, r) if with_rmse else b
    if with_rmse:
        return _fit_lasso(res["X"], res["Y"], w, coefmask, XX=res["XX"],
                          active=active)
    return _fit_lasso_coefs(res["X"], res["Y"], w, coefmask, XX=res["XX"],
                            active=active)


def _write_seg(bufs, nseg, wmask, meta, rmse_s, mag_s, coef_s, *, S):
    """Append one segment row (where wmask) into the flat result buffers.

    Buffers are FLAT [P, S*k]: trailing [S, 7, 8] shapes take TPU tiled
    layouts padded to (8, 128) — 16x the logical bytes — and the per-round
    buffer select was the loop's single hottest op (24 ms/dispatch
    profiled).  Reshaped once on exit."""
    meta_b, rmse_b, mag_b, coef_b = bufs
    P = nseg.shape[0]
    oh = (nseg[:, None] == jnp.arange(S)[None, :]) & wmask[:, None]  # [P,S]

    def upd(buf, val):                     # buf [P,S*k], val [P,k]
        kk = val.shape[-1]
        m = jnp.broadcast_to(oh[:, :, None], (P, S, kk)).reshape(P, S * kk)
        v = jnp.broadcast_to(val[:, None, :], (P, S, kk)).reshape(P, S * kk)
        return jnp.where(m, v, buf)

    bufs = (upd(meta_b, meta), upd(rmse_b, rmse_s), upd(mag_b, mag_s),
            upd(coef_b, coef_s.reshape(P, -1)))
    return bufs, nseg + wmask.astype(jnp.int32)


def _prologue(X, Xt, t, valid, Y, qa, *, sensor, S, fdtype, fit,
              wire_only=False, guards=False):
    """One chip's pre-loop work: QA triage, usable sets, the one-shot
    snow/insufficient-clear fit, variogram, and the standard-procedure
    start state.  Returns (res, state): ``res`` holds the loop-invariant
    residents (spectra views, designs, variogram, procedure routing),
    ``state`` the event-loop carry."""
    # Resident wire-dtype spectra [B,T,P] for the Pallas consumers (int16
    # reads halve the round loop's dominant HBM term; widening in-register
    # is exact), alongside the widened [P,B,T] float view the XLA paths
    # read.  When the init+score+fit Pallas components are all enabled,
    # the float view leaves ``res`` — the loop then never references it,
    # XLA frees it after the prologue, and its [P,B,T] residency (~4.7 GB
    # at the 8-chip bench shape) comes off the loop's working set.
    Yt_res = Y.transpose(0, 2, 1)                              # [B,T,P]
    Y = Y.astype(fdtype).transpose(1, 0, 2)                    # -> [P,B,T]
    P, B, T = Y.shape
    # Per-row design outer products, shared by every Lasso Gram build.
    XX = (X[:, :, None] * X[:, None, :]).reshape(T, -1)        # [T,64]
    # Detection-band wire-dtype slice for the score-fused monitor kernel
    # (DCE'd from the program when FIREBIRD_PALLAS doesn't enable it).
    Yd = Yt_res[np.asarray(sensor.detection_bands)]            # [nb,T,P]
    res = dict(X=X, Xt=Xt, t=t, Yt=Yt_res, Yd=Yd, XX=XX)
    if not wire_only:
        res["Y"] = Y

    # ---------------- QA triage (reference.detect) ----------------
    fill = _qa_bit(qa, params.QA_FILL_BIT) | ~valid[None, :]
    clear = (_qa_bit(qa, params.QA_CLEAR_BIT) | _qa_bit(qa, params.QA_WATER_BIT)) & ~fill
    snow = _qa_bit(qa, params.QA_SNOW_BIT) & ~fill

    n_nonfill = jnp.sum(~fill, -1)
    n_clear = jnp.sum(clear, -1)
    n_snow = jnp.sum(snow, -1)
    clear_pct = n_clear / jnp.maximum(n_nonfill, 1)
    snow_pct = n_snow / jnp.maximum(n_clear + n_snow, 1)

    opt = list(sensor.optical_bands)
    rng_ok = jnp.all((Y[:, opt] > params.OPTICAL_MIN)
                     & (Y[:, opt] < params.OPTICAL_MAX), axis=1)
    if sensor.thermal_bands:
        th = list(sensor.thermal_bands)
        rng_ok &= jnp.all((Y[:, th] > params.THERMAL_MIN)
                          & (Y[:, th] < params.THERMAL_MAX), axis=1)

    procedure = jnp.where(
        n_nonfill == 0, PROC_NODATA,
        jnp.where(clear_pct >= params.CLEAR_PCT_THRESHOLD, PROC_STANDARD,
                  jnp.where(snow_pct > params.SNOW_PCT_THRESHOLD,
                            PROC_SNOW, PROC_INSUF)))

    same_prev = jnp.concatenate([jnp.array([False]), t[1:] == t[:-1]])

    usable_std = _dedup_first(clear & rng_ok, same_prev)
    usable_snow = _dedup_first((clear | snow) & rng_ok, same_prev)
    cand_ins = ~fill & rng_ok
    Yblue = Y[:, sensor.blue_band]
    blue_med = _masked_median(Yblue, cand_ins)
    cand_ins = cand_ins & (Yblue < blue_med[:, None] + params.INSUF_CLEAR_BLUE_DELTA)
    usable_ins = _dedup_first(cand_ins, same_prev)

    # ---------------- result buffers (flat; see _write_seg) ----------------
    nseg0 = jnp.zeros(P, jnp.int32)
    meta0 = jnp.zeros((P, S * 6), fdtype)
    rmse0 = jnp.zeros((P, S * B), fdtype)
    mag0 = jnp.zeros((P, S * B), fdtype)
    coef0 = jnp.zeros((P, S * B * params.MAX_COEFS), fdtype)

    # ---------------- snow / insufficient-clear: one fit ----------------
    alt_usable = jnp.where((procedure == PROC_SNOW)[:, None], usable_snow,
                           usable_ins)
    is_alt = (procedure == PROC_SNOW) | (procedure == PROC_INSUF)
    alt_n = jnp.sum(alt_usable, -1)
    alt_fit = is_alt & (alt_n >= params.MEOW_SIZE)
    w_alt = (alt_usable & alt_fit[:, None]).astype(fdtype)
    alt_coefs, alt_rmse = fit(res, w_alt, _coefmask_for(alt_n), True,
                              active=alt_fit if guards else None)
    first_i = jnp.argmax(alt_usable, -1)
    last_i = T - 1 - jnp.argmax(alt_usable[:, ::-1], -1)
    alt_meta = jnp.stack([
        jnp.take(t, first_i), jnp.take(t, last_i), jnp.take(t, last_i),
        jnp.zeros(P, fdtype),
        jnp.where(procedure == PROC_SNOW,
                  float(params.CURVE_QA_PERSIST_SNOW),
                  float(params.CURVE_QA_INSUF_CLEAR)).astype(fdtype),
        alt_n.astype(fdtype)], axis=1)
    bufs = (meta0, rmse0, mag0, coef0)
    bufs, nseg = _write_seg(bufs, nseg0, alt_fit, alt_meta, alt_rmse,
                            jnp.zeros((P, B), fdtype), alt_coefs, S=S)
    alt_mask = alt_usable & alt_fit[:, None]

    # ---------------- standard procedure state ----------------
    is_std = procedure == PROC_STANDARD
    alive0 = usable_std & is_std[:, None]
    # Mode read at trace time, like use_pallas — set FIREBIRD_VARIOGRAM
    # before the first detect call (one compiled fn per mode).
    vario = _variogram(Y, alive0, t=t,
                       adjusted=params.variogram_adjusted_default())
    ex0, i0 = _first_at_or_after(alive0, jnp.zeros(P, jnp.int32))
    phase0 = jnp.where(is_std & ex0, PHASE_INIT, PHASE_DONE).astype(jnp.int32)

    res.update(vario=vario, is_std=is_std, is_alt=is_alt,
               alt_mask=alt_mask, procedure=procedure)
    state = dict(
        phase=phase0,
        cur_i=i0.astype(jnp.int32),
        cur_k=jnp.zeros(P, jnp.int32),
        alive=alive0,
        included=jnp.zeros((P, T), bool),
        coefs=jnp.zeros((P, B, params.MAX_COEFS), fdtype),
        rmse=jnp.ones((P, B), fdtype),
        n_last_fit=jnp.ones(P, jnp.int32),
        first_seg=jnp.ones(P, bool),
        nseg=nseg, bufs=bufs,
    )
    return res, state


def _init_block(res, st, *, sensor, W, fdtype, fit, f32_ok, mixed=False,
                guards=False):
    """One chip's INIT-phase round work: initialization-window search, the
    Tmask IRLS screen, and the stability test.  Runs under a scalar
    lax.cond — on rounds where no pixel is initializing (most of them:
    after round 1 the only INIT pixels are post-break restarts) the whole
    block, including its one-hot window tensors (the loop's dominant HBM
    term), is skipped outright.  Every output is consumed downstream only
    under in_init-derived masks, so the skip branch's zeros are inert.
    ``guards`` (compaction mode) threads the in_init lane set into the
    Pallas kernels as a per-block skip guard — dense-prefix compaction
    clusters DONE lanes into whole trailing blocks, which then cost a
    predicate instead of the window search + IRLS."""
    _DET = list(sensor.detection_bands)
    _TMB = list(sensor.tmask_bands)
    X, Xt, t = res["X"], res["Xt"], res["t"]
    alive = st["alive"]
    in_init = st["phase"] == PHASE_INIT
    act = in_init if guards else None

    if use_pallas("init") and f32_ok:
        # f32_ok: the shared Mosaic gate from _detect_batch_impl
        # (f32-on-TPU only — Mosaic cannot lower float64).
        on_tpu = jax.default_backend() == "tpu"
        from firebird_tpu.ccd import pallas_ops

        return pallas_ops.init_window(
            alive, st["cur_i"], in_init, t, X, Xt, res["Yt"],
            res["vario"], W=W, sensor=sensor, mixed=mixed, active=act,
            interpret=not on_tpu)

    Y = res["Y"]
    P, B, T = Y.shape
    ar = jnp.arange(T)[None, :]
    has_i, i = _first_at_or_after(alive, st["cur_i"])
    t_i = jnp.take(t, i)
    Acum = jnp.cumsum(alive, -1)
    rank = Acum - 1                                        # [P,T]
    A_before = jnp.take_along_axis(Acum, i[:, None], -1)[:, 0] \
        - jnp.take_along_axis(alive, i[:, None], -1)[:, 0]
    cnt = Acum - A_before[:, None]
    okj = alive & (ar >= i[:, None]) & (cnt >= params.MEOW_SIZE) \
        & (t[None, :] - t_i[:, None] >= params.INIT_DAYS)
    has_w = has_i & jnp.any(okj, -1)
    j = jnp.argmax(okj, -1)
    w_init = alive & (ar >= i[:, None]) & (ar <= j[:, None]) \
        & (has_w & in_init)[:, None]

    # Tmask screen over the compacted window: the window members are
    # exactly the alive obs with ranks [rank(i), rank(i)+n_win), so a
    # rank-indexed selection bounds all IRLS median/Gram work by
    # W << T.  Member positions come from a one-hot reduce over T
    # (ranks are unique among alive obs) rather than a rank scatter +
    # gather — scatters lower to sort + serialized-loop fusions on
    # TPU (~32 ms/round profiled, the loop body's hottest ops).
    n_win = jnp.sum(w_init, -1)                            # [P] <= W
    r_i = A_before                                         # rank of i
    rel_w = rank - r_i[:, None]                            # [P,T]
    # (the == against arange(W) already implies 0 <= rel_w < W)
    oh_w = alive[:, None, :] \
        & (rel_w[:, None, :] == jnp.arange(W)[None, :, None])  # [P,W,T]
    valid_w = (jnp.arange(W)[None, :] < n_win[:, None])
    # Window members selected by one-hot MXU matmuls — exact (each
    # output is 1.0 x one element; HIGHEST precision keeps f32 inputs
    # unrounded) and an order of magnitude cheaper than per-lane
    # take_along_axis gathers, which serialize on TPU (profiled at
    # ~7 ms/round combined).  Empty slots read 0 and are masked by
    # valid_w downstream, as the gathered garbage was before.
    ohf = oh_w.astype(fdtype)                              # [P,W,T]
    Yw7 = jnp.einsum("pbt,pwt->pbw", Y, ohf,
                     precision=lax.Precision.HIGHEST)      # [P,7,W]
    XW = jnp.einsum("pwt,tc->pwc", ohf,
                    jnp.concatenate([X, Xt], axis=1),
                    precision=lax.Precision.HIGHEST)       # [P,W,13]
    Xw8, Xt_w = XW[..., :8], XW[..., 8:]
    Y2w = Yw7[:, _TMB, :]
    tmask_fn = _tmask_bad
    if use_pallas("tmask") and f32_ok:
        on_tpu = jax.default_backend() == "tpu"
        from firebird_tpu.ccd import pallas_ops

        tmask_fn = functools.partial(pallas_ops.tmask_bad, active=act,
                                     interpret=not on_tpu)
    bad_w = tmask_fn(Xt_w, Y2w, valid_w.astype(fdtype),
                     res["vario"][:, _TMB])
    bad = jnp.any(oh_w & bad_w[:, :, None], axis=1)        # [P,T]
    tm_removed = jnp.any(bad_w, -1)

    # Stability fit: 4 coefs over the (pre-screen-clean) window.  RMSE
    # and the endpoint residuals only involve window members (member 0
    # is i, member n_win-1 is j), so residuals are evaluated on the
    # compacted window instead of the full series.
    w_stab = w_init & ~tm_removed[:, None]
    cm4 = jnp.arange(params.MAX_COEFS)[None, :] < 4
    cm4 = jnp.broadcast_to(cm4, (P, params.MAX_COEFS))
    c4 = fit(res, w_stab.astype(fdtype), cm4, False, active=act)
    r_w = Yw7 - jnp.sum(c4[:, :, None, :] * Xw8[:, None, :, :], -1)
    stab_w = valid_w & ~bad_w
    n4 = jnp.maximum(jnp.sum(stab_w, -1), 1.0)
    r4 = jnp.sqrt(jnp.maximum(
        jnp.sum(r_w * r_w * stab_w[:, None, :], -1) / n4[:, None], 0.0))
    r_first = r_w[:, :, 0]                        # [P,7]
    r_last = _onehot_take(r_w, jnp.maximum(n_win - 1, 0)[:, None])
    span = jnp.take(t, j) - t_i
    denom = params.STABILITY_FACTOR * jnp.maximum(r4, res["vario"])  # [P,7]
    slope_day = c4[..., 1] / 365.25
    band_ok = ((jnp.abs(slope_day * span[:, None]) <= denom)
               & (jnp.abs(r_first) <= denom)
               & (jnp.abs(r_last) <= denom))                  # [P,7]
    stable = jnp.all(band_ok[:, _DET], axis=1)

    init_nowin = in_init & ~has_w
    init_tm = in_init & has_w & tm_removed
    init_ok = in_init & has_w & ~tm_removed & stable
    init_bad = in_init & has_w & ~tm_removed & ~stable

    # Cursor advance for INIT failures; a missing successor parks the
    # cursor at T (out of range -> no-window -> DONE next round).
    ex_tm, i_next_tm = _first_at_or_after(alive & ~bad, i)
    i_next_tm = jnp.where(ex_tm, i_next_tm, T)
    has_adv, i_adv = _first_at_or_after(alive, i + 1)

    return dict(init_nowin=init_nowin, init_tm=init_tm, init_ok=init_ok,
                init_bad=init_bad, has_adv=has_adv,
                i_next_tm=i_next_tm.astype(jnp.int32),
                i_adv=i_adv.astype(jnp.int32), j=j.astype(jnp.int32),
                w_stab=w_stab, n_ok=jnp.sum(w_stab, -1).astype(jnp.int32),
                alive_init=alive & ~bad)


def _init_zeros(st):
    """The skip branch of the INIT cond: inert outputs (every consumer
    masks on in_init-derived flags, all False when no pixel initializes)."""
    C, P, T = st["included"].shape
    zb = jnp.zeros((C, P), bool)
    zi = jnp.zeros((C, P), jnp.int32)
    zp = jnp.zeros((C, P, T), bool)
    return dict(init_nowin=zb, init_tm=zb, init_ok=zb, init_bad=zb,
                has_adv=zb, i_next_tm=zi, i_adv=zi, j=zi, w_stab=zp,
                n_ok=zi, alive_init=st["alive"])


def _mon_block(res, st, *, sensor, change_thr, outlier_thr, f32_ok,
               guards=False):
    """One chip's MONITOR-phase round work: score all remaining
    observations against the current model and locate the first event
    (break / refit / tail) in rank space.  Runs under a scalar lax.cond
    (skipped on round 1, when every standard pixel is still
    initializing).  ``guards`` threads the in_mon lane set into the
    Pallas kernels as a per-block skip guard (see _init_block)."""
    _DET = list(sensor.detection_bands)
    X = res["X"]
    alive, included = st["alive"], st["included"]
    in_mon = st["phase"] == PHASE_MONITOR
    act = in_mon if guards else None

    # All event logic runs in rank space on the absolute time axis:
    # rank[p, t] = index of observation t in pixel p's compacted alive
    # sequence.  Ranks are monotone in t among alive obs, so rank
    # comparisons reproduce the compacted-sequence semantics without the
    # argsort/compaction/scatter round-trip ([P,T] bitonic sorts are the
    # expensive op on TPU, not the matmuls).
    dden = jnp.maximum(st["rmse"], res["vario"])[:, _DET]      # [P,5]
    on_tpu = jax.default_backend() == "tpu"
    # f32_ok (Mosaic cannot lower float64; compiled Pallas is f32-on-TPU
    # only) is computed ONCE from fdtype in _detect_batch_impl and shared
    # with the wire-resident gate, so the monitor can never fall down the
    # XLA path while res["Y"] was dropped by wire-only mode.
    if use_pallas("score") and f32_ok:
        # Score-fused kernel: predictions, score, and rank derived in
        # VMEM from the wire-dtype detection-band spectra — skips the
        # [P,nb,T] prediction einsum and the s/rank plane round-trips.
        from firebird_tpu.ccd import pallas_ops

        mon = pallas_ops.monitor_chain_scored(
            res["Yd"], st["coefs"][:, _DET, :], dden, res["X"], alive,
            included, st["cur_k"], st["n_last_fit"], in_mon,
            change_thr=change_thr, outlier_thr=outlier_thr,
            active=act, interpret=not on_tpu)
    else:
        # HIGHEST is already the context default (_detect_batch_core);
        # pinned explicitly so the score matches the Pallas twin's full-f32
        # dot even if the context ever moves.
        Y = res["Y"]
        pred_d = jnp.einsum("pbc,tc->pbt", st["coefs"][:, _DET, :], X,
                            precision=lax.Precision.HIGHEST)
        s = jnp.sum(((Y[:, _DET, :] - pred_d) / dden[:, :, None]) ** 2,
                    axis=1)
        rank = jnp.cumsum(alive, -1) - 1                       # [P,T]
        chain = _monitor_chain
        if use_pallas("monitor") and f32_ok:
            from firebird_tpu.ccd import pallas_ops

            chain = functools.partial(pallas_ops.monitor_chain,
                                      active=act, interpret=not on_tpu)
        mon = chain(s, alive, included, rank, st["cur_k"],
                    st["n_last_fit"], in_mon,
                    change_thr=change_thr, outlier_thr=outlier_thr)

    inc_abs = mon["inc_q"] & in_mon[:, None]
    rem_abs = mon["rem_q"] & in_mon[:, None]
    i32 = lambda a: a.astype(jnp.int32)   # x64 mode promotes the chain's ints
    return dict(m=i32(mon["m"]), is_tail=mon["is_tail"],
                is_brk=mon["is_brk"], is_refit=mon["is_refit"],
                ev_rank=i32(mon["ev_rank"]), pos_ev=i32(mon["pos_ev"]),
                n_exceed=i32(mon["n_exceed"]), n_rf=i32(mon["n_rf"]),
                included_mon=included | inc_abs,
                alive_mon=alive & ~rem_abs)


def _mon_zeros(st):
    """The skip branch of the MONITOR cond: no events, state passes
    through (every consumer masks on in_mon-derived flags)."""
    C, P, _ = st["included"].shape
    zb = jnp.zeros((C, P), bool)
    zi = jnp.zeros((C, P), jnp.int32)
    return dict(m=zi, is_tail=zb, is_brk=zb, is_refit=zb, ev_rank=zi,
                pos_ev=zi, n_exceed=zi, n_rf=zi,
                included_mon=st["included"], alive_mon=st["alive"])


def _close_mags(res, st, mon, *, fdtype):
    """Break magnitudes: median full-band residual over the PEEK run at
    the break — the spectra-reading half of the close, split out so the
    fused-fit route (FIREBIRD_FUSED_FIT) can run EXACTLY this code under
    its own any(is_brk) cond: break rounds are rare, and sharing the
    very same program keeps the fused-on/off stores byte-identical
    (tests/test_fuse.py golden) where a re-derived in-kernel median
    would differ by backend-fusion ulps."""
    X = res["X"]
    alive = st["alive"]
    P, B, _K = st["coefs"].shape
    T = X.shape[0]
    ev_rank, m = mon["ev_rank"], mon["m"]
    rank = jnp.cumsum(alive, -1) - 1

    # Magnitudes: median full-band residual over the PEEK run at the
    # break.  The run has at most PEEK_SIZE members — locate their
    # absolute positions by a one-hot reduce over T (same scatter-free
    # construction as the init window) and take a tiny median instead of
    # masked medians over the whole [P,T] axis.
    relk = ev_rank[:, None] + jnp.arange(params.PEEK_SIZE)[None, :]
    run_ok = relk < m[:, None]                                # [P,PEEK]
    rel_ev = rank - ev_rank[:, None]                          # [P,T]
    oh_run = (alive[:, None, :] & (
        rel_ev[:, None, :]
        == jnp.arange(params.PEEK_SIZE)[None, :, None])
    ).astype(fdtype)                                          # [P,K,T]
    X_run = jnp.einsum("pkt,tc->pkc", oh_run, X,
                       precision=lax.Precision.HIGHEST)       # [P,K,8]
    pred_run = jnp.sum(st["coefs"][:, :, None, :]
                       * X_run[:, None, :, :], -1)            # [P,B,K]
    if "Y" in res:
        Y_run = jnp.einsum("pbt,pkt->pbk", res["Y"], oh_run,
                           precision=lax.Precision.HIGHEST)
    else:
        # Wire-resident mode: the run members come from the int16 view.
        # Each (p,b,k) output selects exactly one observation (one-hot
        # over t), so this contraction is bit-exact vs the float view.
        Y_run = jnp.einsum("btp,pkt->pbk", res["Yt"].astype(fdtype),
                           oh_run, precision=lax.Precision.HIGHEST)
    resid_run = Y_run - pred_run                              # [P,7,PEEK]
    return _masked_median(
        resid_run, jnp.broadcast_to(run_ok[:, None, :], resid_run.shape))


def _close_block(res, st, mon, *, S, fdtype):
    """One chip's segment-close work: break magnitudes and the segment
    row write.  Runs under a scalar lax.cond on any(close) — segment
    closes land on a handful of rounds (the shared tail round plus break
    rounds), so most rounds skip both the PEEK-run one-hot einsums and
    the full result-buffer rewrite."""
    t = res["t"]
    # Shapes from the always-present carries, not res["Yt"]: compaction
    # mode carries only the residents the traced paths actually read, so
    # the wire view may be absent here when the float view serves.
    P, B, _K = st["coefs"].shape
    T = res["X"].shape[0]
    is_tail, is_brk = mon["is_tail"], mon["is_brk"]
    pos_ev = mon["pos_ev"]
    included_mon = mon["included_mon"]
    mags = _close_mags(res, st, mon, fdtype=fdtype)

    last_inc = T - 1 - jnp.argmax(included_mon[:, ::-1], -1)
    first_inc = jnp.argmax(included_mon, -1)
    end_day = jnp.take(t, last_inc)
    start_day = jnp.take(t, first_inc)

    close = is_tail | is_brk
    qa_tail = params.CURVE_QA_END \
        + jnp.where(st["first_seg"], params.CURVE_QA_START, 0)
    qa_brk = jnp.where(st["first_seg"], params.CURVE_QA_START,
                       params.CURVE_QA_INSIDE)
    meta_new = jnp.stack([
        start_day, end_day,
        jnp.where(is_brk, jnp.take(t, pos_ev), end_day),
        jnp.where(is_brk, 1.0,
                  mon["n_exceed"] / params.PEEK_SIZE).astype(fdtype),
        jnp.where(is_brk, qa_brk, qa_tail).astype(fdtype),
        jnp.sum(included_mon, -1).astype(fdtype)], axis=1)
    mag_new = jnp.where(is_brk[:, None], mags, 0.0)
    return _write_seg(st["bufs"], st["nseg"], close, meta_new,
                      st["rmse"], mag_new, st["coefs"], S=S)


# ---------------------------------------------------------------------------
# Active-lane compaction (docs/ROOFLINE.md "Occupancy"): the event loop's
# cost tracks the ACTIVE pixel set, not the padded batch.
# ---------------------------------------------------------------------------

# The skip-guard accounting unit: a trailing lane block containing no
# active lane costs a per-block predicate in the Pallas kernels instead
# of its Gram/CD/monitor work.  Matches pallas_ops.BLOCK_P's scale (the
# per-kernel widths are 128-512; 512 is the accounting width the
# occupancy capture and flops.occupancy_detail use).
COMPACT_LANE_BLOCK = 512

# State-dict keys permuted along their leading pixel axis by a compaction
# (the [C,P,...] loop carries).
_COMPACT_PIXEL_KEYS = ("phase", "cur_i", "cur_k", "alive", "included",
                       "coefs", "rmse", "n_last_fit", "first_seg", "nseg")
# Carried residents whose pixel axis is NOT leading (wire layout [B,T,P]).
_COMPACT_RESP_AXIS = {"Yt": 2, "Yd": 2}


def _dense_prefix_perm(alive):
    """Stable dense-prefix permutation from an alive mask [P]: returns
    gather indices g (i32 [P]) with out[i] = in[g[i]], alive lanes first,
    original order preserved within each class (cumsum-derived targets,
    inverted by one scatter of iota)."""
    P = alive.shape[0]
    a32 = alive.astype(jnp.int32)
    na = jnp.sum(a32)
    tgt = jnp.where(alive, jnp.cumsum(a32) - 1,
                    na + jnp.cumsum(1 - a32) - 1).astype(jnp.int32)
    return jnp.zeros(P, jnp.int32).at[tgt].set(
        jnp.arange(P, dtype=jnp.int32))


def _take_pixels(a, g, axis=0):
    """Lane gather along ``axis``.  Minor-axis residents ([B,T,P] wire
    layouts) route through a leading-axis move so XLA lowers a major-axis
    gather + copies instead of a serialized per-lane minor-axis gather
    (the same TPU pathology the one-hot selections avoid)."""
    if axis == 0:
        return jnp.take(a, g, axis=0)
    return jnp.moveaxis(jnp.take(jnp.moveaxis(a, axis, 0), g, axis=0),
                        0, axis)


def _compact_state(st):
    """One compaction sweep: permute every per-pixel loop carry — state,
    result buffers, carried residents, and the running permutation — so
    lanes with phase != PHASE_DONE form a dense prefix per chip.  The
    math is permutation-invariant per lane (everything in the round body
    is elementwise over P or a per-lane reduce over T), so results are
    bit-identical; ``perm`` carries current-position -> original-pixel
    for the exit unpermute."""
    def one(stc):
        g = _dense_prefix_perm(stc["phase"] != PHASE_DONE)
        out = {k: _take_pixels(stc[k], g) for k in _COMPACT_PIXEL_KEYS}
        out["bufs"] = tuple(_take_pixels(b, g) for b in stc["bufs"])
        out["resp"] = {k: _take_pixels(v, g, _COMPACT_RESP_AXIS.get(k, 0))
                       for k, v in stc["resp"].items()}
        out["perm"] = _take_pixels(stc["perm"], g)
        return dict(stc, **out)

    return jax.vmap(one)(st)


def _unpermute(a, perm):
    """Invert a carried permutation at loop exit: out[perm[p]] = a[p],
    per chip (one scatter per output field, at most twice per dispatch)."""
    return jax.vmap(lambda ac, pc: jnp.zeros_like(ac).at[pc].set(ac))(
        a, perm)


def _paid_lanes(phase, block_widths):
    """Per-chip lanes the round pays for under the per-block skip
    guards: COMPACT_LANE_BLOCK-wide blocks containing any active lane,
    weighted by their real width ([C] i32).  ``block_widths`` is the
    trace-time numpy width vector (last block may be ragged).  This is
    the guard-accounting MODEL, identical on every backend: measured
    compute where the Pallas guards run, predicted skips on the lax
    fallback (whose slab cond computes every lane under vmap — see the
    ChipSegments.occupancy note)."""
    C, P = phase.shape
    nb = block_widths.shape[0]
    pad = nb * COMPACT_LANE_BLOCK - P
    act = jnp.pad(phase != PHASE_DONE, ((0, 0), (0, pad)))
    blk = jnp.any(act.reshape(C, nb, COMPACT_LANE_BLOCK), -1)
    return jnp.sum(blk * jnp.asarray(block_widths, jnp.int32)[None, :],
                   -1).astype(jnp.int32)


def _block_widths(P: int) -> np.ndarray:
    nb = -(-P // COMPACT_LANE_BLOCK)
    w = np.full(nb, COMPACT_LANE_BLOCK, np.int32)
    w[-1] = P - (nb - 1) * COMPACT_LANE_BLOCK
    return w


def _detect_batch_core(Xs, Xts, ts, valids, Ys, qas, *,
                       wcap: int | None = None, sensor=LANDSAT_ARD,
                       max_segments: int = MAX_SEGMENTS, dtype=None,
                       compact: bool | None = None,
                       fused=None, mixed: bool | None = None,
                       rebalance=None):
    """A chip batch: Xs [C,T,8], Xts [C,T,5], ts [C,T], valids [C,T],
    Ys [C,B,P,T] (wire int16 or float), qas [C,P,T] int32 → ChipSegments
    with [C, ...] leading axes.

    The event loop runs ONE while_loop over the whole batch (not a
    vmapped per-chip loop): each round's phase blocks are vmapped over
    chips *inside* scalar lax.cond gates, so a round where no pixel of
    any chip is initializing skips the INIT block's one-hot window
    tensors outright, a round with no close skips the buffer rewrite,
    and a round with no refit skips the Lasso fit.  Under a vmapped
    while_loop those conds would degenerate to selects (both branches
    execute every round for every chip); hoisting the loop above the
    vmap is what makes them real branches.

    Traced under HIGHEST matmul precision: on TPU the default f32 dot
    runs reduced-precision passes, which would silently degrade every
    Gram/prediction below the f32 the oracle-parity envelope was
    measured at (CPU tests run full f32 and would never catch it).

    ``wcap`` (static) bounds the member count of any initialization
    window; window_cap() derives a rigorous bound from the batch's date
    grids (None falls back to the always-correct T).  ``sensor``
    (static) supplies the band layout.  ``max_segments`` (static) is the
    result-buffer capacity; n_segments counts every closed segment even
    past capacity, so a caller can detect overflow (n_segments >
    max_segments) and re-dispatch with a larger buffer — detect_packed
    does this automatically.

    ``compact`` (static) enables active-lane compaction (None defers to
    FIREBIRD_COMPACT at trace time): the loop periodically permutes the
    per-pixel state so working lanes form a dense prefix, threads
    per-block skip guards into the Pallas kernels, and re-enters a
    power-of-two bucket once the alive fraction falls below
    FIREBIRD_COMPACT_FLOOR — row-identical results, cost tracking the
    active set instead of the padded batch.

    ``fused`` (static) routes each round's segment-close + shared-fit
    pair through the fused gram→CD→close Pallas kernel (None defers to
    FIREBIRD_FUSED_FIT at trace time, like ``compact``); results are
    byte-identical against the unfused Pallas-fit configuration
    (tests/test_fuse.py golden).  The value "mon" (or env "mon"/"2")
    instead fuses the WHOLE post-INIT round — monitor chain + close +
    fit — into one pallas_call (pallas_ops.fused_round); that route is
    decision-exact with seg_mag inside the f32 envelope, like mega.

    ``mixed`` (static) accumulates the fit kernels' Gram/corr dots in
    bf16 split form with f32 accumulators and int32 counts (None defers
    to FIREBIRD_MIXED_PRECISION at trace time) — decision fields stay
    identical to f32, coef/rmse inside params.MIXED_ULP_BUDGET; f32
    stores and Pallas fit routes only (see use_mixed_precision).

    ``rebalance`` (static; a parallel.mesh.RebalanceSpec, sharded
    dispatches only) arms the cross-device straggler rebalancing ring at
    the bucketed-tail boundary — lanes migrate to the right-neighbor
    device when the alive-count imbalance crosses the threshold, results
    migrate back, stores stay row-identical."""
    with jax.default_matmul_precision("highest"):
        return _detect_batch_impl(Xs, Xts, ts, valids, Ys, qas, wcap=wcap,
                                  sensor=sensor, max_segments=max_segments,
                                  dtype=dtype, compact=compact,
                                  fused=fused, mixed=mixed,
                                  rebalance=rebalance)


_PALLAS_COMPONENTS = ("lasso", "monitor", "tmask", "fit", "score", "init",
                      "mega")


def _refuse_route(route: str, why: str) -> None:
    """A requested Pallas route the code declines for this program: say
    so (log line + ``kernel_pallas_refused`` counter) instead of falling
    back to the XLA loop in silence, which would read as the route's own
    result.  Trace-time, so it counts programs, not dispatches."""
    from firebird_tpu.obs import logger
    from firebird_tpu.obs import metrics as obs_metrics

    obs_metrics.counter(
        "kernel_pallas_refused",
        help="programs traced with a requested Pallas route refused "
             "(float64 on TPU, or the mega VMEM guard)").inc()
    logger("pyccd").warning(
        "Pallas route %r requested but refused (%s); this program runs "
        "the XLA loop in its place", route, why)


def _detect_batch_impl(Xs, Xts, ts, valids, Ys, qas, *, wcap, sensor,
                       max_segments, dtype, compact=None, fused=None,
                       mixed=None, rebalance=None):
    C, B, P, T = Ys.shape
    S = max_segments
    W = T if wcap is None else min(wcap, T)
    fdtype = jnp.dtype(dtype) if dtype is not None else Ys.dtype
    _DET = list(sensor.detection_bands)
    change_thr, outlier_thr = chi2_thresholds(len(_DET))
    on_tpu = jax.default_backend() == "tpu"
    f32_ok = not on_tpu or fdtype == jnp.float32
    if not f32_ok:
        for route in [c for c in _PALLAS_COMPONENTS if use_pallas(c)] + (
                ["fused"] if (fused_mode() if fused is None else fused)
                else []):
            _refuse_route(route, f"Mosaic lowers float32 only, the "
                                 f"program is {fdtype.name} on TPU")
    # The mega decision is made ONCE, up front, because it shapes the
    # prologue: mega implies wire-resident mode (drops the float view)
    # and the Pallas fit kernel for the one-shot alt fits — but a mega
    # REFUSED by the VMEM guard must leave both decisions to the
    # per-component flags, or the XLA fallback loop would read a float
    # view the prologue never kept.
    mega = False
    if use_pallas("mega") and f32_ok:
        from firebird_tpu.ccd import pallas_ops

        mega = pallas_ops.mega_fits(T, W, B, S, Ys.dtype.itemsize)
        if not mega:
            _refuse_route("mega", f"mega_fits: T={T} W={W} B={B} S={S} "
                                  f"exceeds VMEM at the 128-lane floor")
    fit_pallas = (use_pallas("fit") or mega) and f32_ok
    # Mixed-precision gram (FIREBIRD_MIXED_PRECISION / explicit mixed=):
    # bf16 split dots + int32 counts inside the Pallas fit routes, f32
    # everywhere decisions are made.  f32 stores only — the f64
    # bit-parity path keeps full precision — and inert on the XLA fit
    # path, which stays the f32 oracle.
    mixed_on = (use_mixed_precision() if mixed is None else bool(mixed)) \
        and f32_ok and fdtype == jnp.float32
    fit = functools.partial(_fit_chip, fit_pallas=fit_pallas,
                            on_tpu=on_tpu, mixed=mixed_on)
    wire_only = (mega or _wire_resident_only()) and f32_ok
    # Active-lane compaction (trace-time resolution, like use_pallas).
    # The mega route already stops paying for finished pixels its own way
    # (each VMEM block's while_loop exits when ITS pixels are done), so
    # compaction applies to the XLA/per-component loop only.
    compact_on = (params.compact_default() if compact is None
                  else bool(compact)) and not mega
    # Fused gram→CD→close round kernel (FIREBIRD_FUSED_FIT / explicit
    # fused=): each round's segment-close + shared-Lasso-fit pair runs
    # as ONE pallas_call on a single VMEM residency of the wire spectra.
    # Mode "mon" widens the fusion to the whole post-INIT round —
    # monitor chain + close + fit in one kernel (pallas_ops.fused_round).
    # The mega route supersedes both (the whole loop is already one
    # kernel); the f64-on-TPU bit-parity path keeps the XLA pair.
    fused_req = fused_mode() if fused is None else fused
    if fused_req in ("mon", 2):
        fused_req = "mon"
    elif fused_req:
        fused_req = 1
    else:
        fused_req = 0
    fused_on = bool(fused_req) and f32_ok and not mega
    fused_mon = fused_on and fused_req == "mon"

    # Trace-time route counters (host code; a jit trace runs once per
    # compiled shape, so these count PROGRAMS built on each route —
    # tools/precision_smoke.py's "counters moving" check).
    from firebird_tpu.obs import metrics as obs_metrics
    if mixed_on:
        obs_metrics.counter(
            "kernel_mixed_traces",
            help="programs traced with the bf16/int32 mixed-precision "
                 "gram (FIREBIRD_MIXED_PRECISION)").inc()
    if fused_mon:
        obs_metrics.counter(
            "kernel_fused_round_traces",
            help="programs traced with the whole-round monitor-fused "
                 "kernel (FIREBIRD_FUSED_FIT=mon)").inc()

    res, state = jax.vmap(functools.partial(
        _prologue, sensor=sensor, S=S, fdtype=fdtype, fit=fit,
        wire_only=wire_only, guards=compact_on))(Xs, Xts, ts, valids, Ys,
                                                 qas)

    if mega:
        # Whole-loop mega kernel: the entire event loop in one
        # pallas_call, wire spectra VMEM-resident, each block exiting as
        # soon as its own pixels finish (pallas_ops._detect_mega_block).
        # mega_fits guarded the 128-lane VMEM floor above: an oversized
        # T falls down the XLA loop below instead of a Mosaic OOM.
        # (pallas_ops is already bound in scope by the guard import.)
        out = pallas_ops.detect_mega(
            res["Yt"], state["phase"], state["cur_i"], state["alive"],
            state["nseg"], state["bufs"], res["t"], res["X"], res["Xt"],
            res["vario"], W=W, S=S, sensor=sensor,
            phases=(PHASE_INIT, PHASE_MONITOR, PHASE_DONE),
            change_thr=float(change_thr), outlier_thr=float(outlier_thr),
            mixed=mixed_on, interpret=not on_tpu)
        final_mask = jnp.where(
            res["is_std"][..., None], out["alive"],
            jnp.where(res["is_alt"][..., None], res["alt_mask"], False))
        return ChipSegments(
            n_segments=out["nseg"], seg_meta=out["meta"],
            seg_rmse=out["rmse"], seg_mag=out["mag"],
            seg_coef=out["coef"], mask=final_mask,
            procedure=res["procedure"], rounds=out["rounds"],
            vario=res["vario"], round_counts=out["counts"])

    initf = jax.vmap(functools.partial(
        _init_block, sensor=sensor, W=W, fdtype=fdtype, fit=fit,
        f32_ok=f32_ok, mixed=mixed_on, guards=compact_on))
    monf = jax.vmap(functools.partial(
        _mon_block, sensor=sensor, change_thr=change_thr,
        outlier_thr=outlier_thr, f32_ok=f32_ok, guards=compact_on))
    closef = jax.vmap(functools.partial(_close_block, S=S, fdtype=fdtype))
    if compact_on:
        fitf = jax.vmap(lambda r, w, n, a: fit(r, w, _coefmask_for(n),
                                               active=a))
    else:
        fitf = jax.vmap(lambda r, w, n: fit(r, w, _coefmask_for(n)))
    if fused_mon:
        from firebird_tpu.ccd import pallas_ops

        def _round_chip(r, st_c, init_c, act=None):
            in_mon_c = st_c["phase"] == PHASE_MONITOR
            return pallas_ops.fused_round(
                r["Yt"], r["X"], r["t"], st_c["alive"], st_c["included"],
                st_c["cur_k"], st_c["n_last_fit"], in_mon_c,
                st_c["coefs"], st_c["rmse"], r["vario"],
                init_c["init_ok"], init_c["w_stab"], init_c["n_ok"],
                st_c["first_seg"], st_c["nseg"], st_c["bufs"], S=S,
                sensor=sensor, change_thr=float(change_thr),
                outlier_thr=float(outlier_thr), mixed=mixed_on,
                active=act, interpret=not on_tpu)

        roundf = jax.vmap(_round_chip) if compact_on \
            else jax.vmap(functools.partial(_round_chip, act=None))
    elif fused_on:
        from firebird_tpu.ccd import pallas_ops

        def _fused_chip(r, w, df, nf, mg, st_c, mn_c, act=None):
            return pallas_ops.fused_fit_close(
                r["Yt"], r["X"], r["t"], w, df, nf,
                mn_c["included_mon"], st_c["coefs"], st_c["rmse"], mg,
                mn_c["is_tail"], mn_c["is_brk"],
                mn_c["pos_ev"], mn_c["n_exceed"],
                st_c["first_seg"], st_c["nseg"], st_c["bufs"], S=S,
                mixed=mixed_on, active=act, interpret=not on_tpu)

        fusedf = jax.vmap(_fused_chip) if compact_on \
            else jax.vmap(functools.partial(_fused_chip, act=None))
        magsf = jax.vmap(functools.partial(_close_mags, fdtype=fdtype))

    max_rounds = 2 * T + 8

    # ---- compaction parameters (trace-time; params.compact_*) ----
    every = params.compact_every()
    floor = params.compact_floor() if compact_on else 0.0
    bucket = 1 << max(int(max(P * floor, 1) - 1).bit_length(), 3) \
        if floor > 0 else P
    # The re-entry loop is a second traced copy of the round body: real
    # lane savings at chip scale, pure compile cost for tiny batches.
    cascade_on = (compact_on and 0 < bucket < P
                  and P >= params.compact_min_lanes())

    # In-loop per-pixel residents: compaction must permute the spectra
    # views the traced block paths actually read alongside the state, so
    # they move into the while_loop carry (originals die after carry
    # init; the compaction sweep permutes the carried copies).  Keys
    # mirror the blocks' trace-time routing exactly — a path that would
    # read an uncarried resident fails loudly at trace (KeyError), never
    # silently reads the unpermuted original.
    score_pallas = use_pallas("score") and f32_ok
    init_pallas = use_pallas("init") and f32_ok
    resp_keys = ["vario"]
    if "Y" in res:
        resp_keys.append("Y")
    if fit_pallas or init_pallas or fused_on or "Y" not in res:
        resp_keys.append("Yt")
    if score_pallas:
        resp_keys.append("Yd")
    res_shared = {k: res[k] for k in ("X", "Xt", "t", "XX")}

    if compact_on:
        state = dict(state,
                     resp={k: res[k] for k in resp_keys},
                     perm=jnp.tile(jnp.arange(P, dtype=jnp.int32)[None],
                                   (C, 1)),
                     # Baseline for the "enough lanes died" trigger: full
                     # width, so never-alive lanes (snow/insufficient/
                     # no-data pixels, DONE from round 0) count toward
                     # the first periodic compaction.
                     base_alive=jnp.full((C,), P, jnp.int32))

    def _loop_res(st, shared=None):
        if not compact_on:
            return res
        return dict(res_shared if shared is None else shared,
                    **st["resp"])

    def cond(carry):
        st, rounds, _, _, _, tail = carry
        return ((rounds < max_rounds)
                & jnp.any(st["phase"] != PHASE_DONE) & ~tail)

    def _make_body(allow_cascade_exit, shared=None, allow_compact=True,
                   occ_fold=None):
        # ``shared``: chip-shared designs override for the rebalanced
        # tail (own + guest chips concatenated).  ``allow_compact=False``
        # pins lane positions through the loop — the rebalancing ring's
        # un-migration merge is positional, so the rebalanced tail must
        # not permute.  ``occ_fold=C`` folds guest chip rows C..2C into
        # their host rows for the occupancy capture, so migrated lanes
        # stay accounted on the device that computes them.
        def body(carry):
            st, rounds, counts, occ, ncomp, tail = carry
            res_l = _loop_res(st, shared)
            phase = st["phase"]
            in_init = phase == PHASE_INIT
            in_mon = phase == PHASE_MONITOR

            # Occupancy capture: lanes entering the round still working,
            # and lanes the guarded kernels pay for (whole blocks with
            # any active lane; the full width when compaction is off).
            Pc = phase.shape[1]
            active_c = jnp.sum(phase != PHASE_DONE, -1).astype(jnp.int32)
            paid_c = _paid_lanes(phase, _block_widths(Pc)) if compact_on \
                else jnp.full_like(active_c, Pc)
            if occ_fold is not None:
                active_c = active_c[:occ_fold] + active_c[occ_fold:]
                paid_c = paid_c[:occ_fold] + paid_c[occ_fold:]
            occ = lax.dynamic_update_slice(
                occ, jnp.stack([active_c, paid_c], -1)[None],
                (rounds, jnp.zeros((), rounds.dtype),
                 jnp.zeros((), rounds.dtype)))

            any_init = jnp.any(in_init)
            init = lax.cond(any_init,
                            lambda: initf(res_l, st),
                            lambda: _init_zeros(st))

            if fused_mon:
                # Whole-round fusion: monitor chain + segment close +
                # shared refit run as ONE pallas_call per chip
                # (pallas_ops.fused_round), so the separate monf/closef/
                # fitf conds collapse into a single any-work gate.  The
                # INIT block stays cond-gated outside (rare after
                # warmup) and hands its fit window into the kernel; the
                # event flags come back in ``ev`` and feed the same
                # next-state code as the other routes.
                def _run_round():
                    if compact_on:
                        return roundf(res_l, st, init,
                                      in_mon | init["init_ok"])
                    return roundf(res_l, st, init)

                def _skip_round():
                    zb = jnp.zeros_like(in_mon)
                    zi = jnp.zeros_like(st["cur_i"])
                    ev0 = dict(is_tail=zb, is_brk=zb, is_refit=zb,
                               pos_ev=zi, do_fit=zb, n_full=zi,
                               included_mon=st["included"],
                               alive_mon=st["alive"])
                    return (st["bufs"], st["nseg"], st["coefs"],
                            st["rmse"], ev0)

                bufs, nseg, cfull, rfull, ev = lax.cond(
                    jnp.any(in_mon) | jnp.any(init["init_ok"]),
                    _run_round, _skip_round)
                mon = dict(is_tail=ev["is_tail"], is_brk=ev["is_brk"],
                           is_refit=ev["is_refit"], pos_ev=ev["pos_ev"],
                           included_mon=ev["included_mon"],
                           alive_mon=ev["alive_mon"])
                close = mon["is_tail"] | mon["is_brk"]
                any_close = jnp.any(close)
                init_ok, is_refit = init["init_ok"], mon["is_refit"]
                do_fit, n_full = ev["do_fit"], ev["n_full"]
                any_fit = jnp.any(do_fit)
            else:
                mon = lax.cond(jnp.any(in_mon),
                               lambda: monf(res_l, st),
                               lambda: _mon_zeros(st))

                close = mon["is_tail"] | mon["is_brk"]
                any_close = jnp.any(close)
                # Refit / init-ok shared fit (skipped when no pixel
                # needs one).
                init_ok, is_refit = init["init_ok"], mon["is_refit"]
                do_fit = init_ok | is_refit
                any_fit = jnp.any(do_fit)
                n_full = jnp.where(init_ok, init["n_ok"], mon["n_rf"])

            def _w_full():
                # The [C,P,T] fit-window build lives inside the branches
                # so a no-fit round materializes nothing.
                return jnp.where(init_ok[..., None], init["w_stab"],
                                 mon["included_mon"] & is_refit[..., None])

            if fused_mon:
                pass        # bufs/nseg/cfull/rfull merged in-kernel above
            elif fused_on:
                # One fused pallas_call serves the close AND the shared
                # fit on a single VMEM residency of the wire spectra;
                # the do_fit coefs/rmse merge happens in-kernel, so the
                # branch returns the MERGED model directly.  The break
                # magnitudes stay on the shared _close_mags program
                # under their own (rare) any-break cond — the identical
                # code on fused and unfused paths, which is what keeps
                # the golden byte-identical instead of envelope-bound.
                def _run_fused():
                    w = _w_full().astype(fdtype)
                    mg = lax.cond(jnp.any(mon["is_brk"]),
                                  lambda: magsf(res_l, st, mon),
                                  lambda: jnp.zeros_like(st["rmse"]))
                    if compact_on:
                        return fusedf(res_l, w, do_fit, n_full, mg, st,
                                      mon, do_fit | close)
                    return fusedf(res_l, w, do_fit, n_full, mg, st, mon)

                bufs, nseg, cfull, rfull = lax.cond(
                    any_close | any_fit, _run_fused,
                    lambda: (st["bufs"], st["nseg"], st["coefs"],
                             st["rmse"]))
            else:
                bufs, nseg = lax.cond(any_close,
                                      lambda: closef(res_l, st, mon),
                                      lambda: (st["bufs"], st["nseg"]))

                def _run_fit():
                    w = _w_full().astype(fdtype)
                    if compact_on:
                        return fitf(res_l, w, n_full, do_fit)
                    return fitf(res_l, w, n_full)

                cfull, rfull = lax.cond(any_fit, _run_fit,
                                        lambda: (st["coefs"], st["rmse"]))

            # ============== next state (batched elementwise) ============
            is_tail, is_brk = mon["is_tail"], mon["is_brk"]
            phase_n = jnp.where(
                init["init_nowin"] | (init["init_bad"] & ~init["has_adv"]),
                PHASE_DONE,
                jnp.where(init_ok, PHASE_MONITOR,
                          jnp.where(is_tail, PHASE_DONE,
                                    jnp.where(is_brk, PHASE_INIT, phase))))
            cur_i_n = jnp.where(
                init["init_tm"], init["i_next_tm"],
                jnp.where(init["init_bad"] & init["has_adv"],
                          init["i_adv"],
                          jnp.where(is_brk, mon["pos_ev"], st["cur_i"])))
            cur_k_n = jnp.where(init_ok, init["j"] + 1,
                                jnp.where(is_refit, mon["pos_ev"] + 1,
                                          st["cur_k"]))
            alive_n = jnp.where(in_init[..., None], init["alive_init"],
                                jnp.where(in_mon[..., None],
                                          mon["alive_mon"], st["alive"]))
            included_n = jnp.where(
                init_ok[..., None], init["w_stab"],
                jnp.where(is_brk[..., None], False,
                          jnp.where(in_mon[..., None], mon["included_mon"],
                                    st["included"])))
            if fused_on:
                coefs_n, rmse_n = cfull, rfull    # merged in-kernel
            else:
                coefs_n = jnp.where(do_fit[..., None, None], cfull,
                                    st["coefs"])
                rmse_n = jnp.where(do_fit[..., None], rfull, st["rmse"])
            nlast_n = jnp.where(do_fit, n_full.astype(jnp.int32),
                                st["n_last_fit"])
            first_n = st["first_seg"] & ~is_brk

            st_n = dict(st, phase=phase_n.astype(jnp.int32),
                        cur_i=cur_i_n.astype(jnp.int32),
                        cur_k=cur_k_n.astype(jnp.int32),
                        alive=alive_n, included=included_n,
                        coefs=coefs_n, rmse=rmse_n, n_last_fit=nlast_n,
                        first_seg=first_n, nseg=nseg, bufs=bufs)
            counts_n = counts + jnp.stack(
                [any_init, any_fit, any_close]).astype(jnp.int32)

            if compact_on and allow_compact:
                # ---- dense-prefix compaction ----
                n_alive = jnp.sum(st_n["phase"] != PHASE_DONE,
                                  -1).astype(jnp.int32)          # [C]
                dead_since = st_n["base_alive"] - n_alive
                # Slack from the CURRENT lane width: inside the stage-2
                # bucket the "1/16 of lanes died" cadence must mean 1/16
                # of the bucket, or the tail never re-compacts.
                periodic = (((rounds + 1) % every) == 0) \
                    & (jnp.max(dead_since) >= max(Pc // 16, 1))
                if allow_cascade_exit:
                    # Forced compaction on the bucket-entry transition:
                    # survivors must sit in the prefix before the loop
                    # exits and stage 2 slices it.
                    ready = jnp.max(n_alive) <= bucket
                else:
                    ready = jnp.zeros((), bool)
                do_c = periodic | (ready & ~tail)
                st_n = lax.cond(do_c, _compact_state, lambda s: s, st_n)
                st_n = dict(st_n, base_alive=jnp.where(
                    do_c, n_alive, st_n["base_alive"]))
                ncomp = ncomp + do_c.astype(jnp.int32)
                tail = tail | ready
            return (st_n, rounds + 1, counts_n, occ, ncomp, tail)

        return body

    carry0 = (state, jnp.zeros((), jnp.int32), jnp.zeros((3,), jnp.int32),
              jnp.zeros((max_rounds, C, 2), jnp.int32),
              jnp.zeros((), jnp.int32), jnp.zeros((), bool))
    state, rounds, counts, occ, ncomp, tail = lax.while_loop(
        cond, _make_body(cascade_on), carry0)

    lanes_migrated = None
    if cascade_on:
        # ---- stage 2: bucketed re-entry for the long tail ----
        # The exit compaction put every still-working lane in the dense
        # prefix, so the "gather survivors" is a static slice [:, :bucket]
        # of each carried array; the same loop body re-traces at the
        # bucket shape and finishes them; one static slice-assign merges
        # the results back.  All inside the jitted program — no host
        # round-trip, no extra compile shapes for the warm-start cache to
        # predict (a stage-2 that never runs costs zero rounds).
        def _slice_p(a, axis=1):
            idx = [slice(None)] * a.ndim
            idx[axis] = slice(0, bucket)
            return a[tuple(idx)]

        st2 = {k: _slice_p(state[k]) for k in _COMPACT_PIXEL_KEYS}
        st2["bufs"] = tuple(_slice_p(b) for b in state["bufs"])
        st2["resp"] = {
            k: _slice_p(v, 1 + _COMPACT_RESP_AXIS.get(k, 0))
            for k, v in state["resp"].items()}
        st2["perm"] = _slice_p(state["perm"])
        st2["base_alive"] = jnp.sum(st2["phase"] != PHASE_DONE,
                                    -1).astype(jnp.int32)
        if rebalance is not None:
            # ---- cross-device straggler rebalancing ring ----
            # Compaction's per-device alive residue diverges, so without
            # migration every chip waits on the slowest device's tail.
            # At this boundary the survivors sit in a dense prefix per
            # chip: ship the whole stage-2 carry one ring hop rightward
            # (lax.ppermute on simulated meshes, the Pallas
            # async-remote-copy kernel on TPU), activate only the DONATED
            # lanes on the host device, run the tail loop over own+guest
            # chips with lane positions pinned (allow_compact=False —
            # the un-migration merge is positional), then ship the guest
            # results back and merge them into the donor's rows.  Stores
            # stay row-identical by construction; tests/test_fuse.py
            # proves it on the simulated mesh.
            from firebird_tpu.parallel import mesh as _pmesh

            st2cat, shcat, donated, lanes_migrated = \
                _pmesh.rebalance_tail_out(st2, res_shared, rebalance,
                                          bucket)
            carry2 = (st2cat, rounds, counts, occ, ncomp,
                      jnp.zeros((), bool))
            st2cat, rounds, counts, occ, ncomp, _ = lax.while_loop(
                cond, _make_body(False, shared=shcat,
                                 allow_compact=False, occ_fold=C),
                carry2)
            st2 = _pmesh.rebalance_tail_back(st2cat, donated, rebalance,
                                             C)
        else:
            carry2 = (st2, rounds, counts, occ, ncomp,
                      jnp.zeros((), bool))
            st2, rounds, counts, occ, ncomp, _ = lax.while_loop(
                cond, _make_body(False), carry2)
        merge = lambda full, part: full.at[:, :bucket].set(part)
        state = dict(state,
                     nseg=merge(state["nseg"], st2["nseg"]),
                     alive=merge(state["alive"], st2["alive"]),
                     bufs=tuple(merge(f, p) for f, p in
                                zip(state["bufs"], st2["bufs"])),
                     perm=merge(state["perm"], st2["perm"]))

    nseg, bufs, alive = state["nseg"], state["bufs"], state["alive"]
    if compact_on:
        # Land every per-pixel output back in original pixel order (the
        # carried permutation's inverse, one scatter per field).
        perm = state["perm"]
        nseg = _unpermute(nseg, perm)
        alive = _unpermute(alive, perm)
        bufs = tuple(_unpermute(b, perm) for b in bufs)

    meta_b, rmse_b, mag_b, coef_b = bufs
    final_mask = jnp.where(res["is_std"][..., None], alive,
                           jnp.where(res["is_alt"][..., None],
                                     res["alt_mask"], False))
    return ChipSegments(
        n_segments=nseg,
        seg_meta=meta_b.reshape(C, P, S, 6),
        seg_rmse=rmse_b.reshape(C, P, S, B),
        seg_mag=mag_b.reshape(C, P, S, B),
        seg_coef=coef_b.reshape(C, P, S, B, params.MAX_COEFS),
        mask=final_mask, procedure=res["procedure"],
        rounds=jnp.broadcast_to(rounds, (C,)), vario=res["vario"],
        round_counts=jnp.broadcast_to(counts, (C, 3)),
        occupancy=jnp.transpose(occ, (1, 0, 2)),
        # The count lands on the loop's FIRST chip row only (zeros
        # elsewhere): under shard_map each shard runs its own loop over
        # its chip slice, so a per-chip broadcast would make any host
        # aggregation wrong (sum overcounts by chips-per-shard, max
        # drops all but the busiest shard) — one nonzero per loop makes
        # the chip-sum THE batch total (record_occupancy).
        compactions=jnp.where(jnp.arange(C) == 0, ncomp, 0),
        # Zeros (not None) whenever a rebalance spec was armed, even on
        # shapes whose cascade never built — so the sharded program's
        # output structure is one trace and the counter reads 0, not
        # "absent", when the ring had nothing to move.
        lanes_migrated=(lanes_migrated if lanes_migrated is not None
                        else (jnp.zeros((C,), jnp.int32)
                              if rebalance is not None else None)))


# ---------------------------------------------------------------------------
# Host-facing API
# ---------------------------------------------------------------------------

def device_designs(days, n_obs, dtype):
    """The harmonic design matrices, built ON DEVICE from the int32 wire.

    ``days`` [C, T] int32 ordinal days (0-padded past ``n_obs`` [C] int32)
    -> (Xs [C,T,8], Xts [C,T,5], ts [C,T] float, valids [C,T] bool), the
    four host-prepared float planes :func:`prep_batch` used to ship.  The
    design is tiny next to the spectra, but building it here removes the
    last float ingress planes entirely (the wire is all-integer, which
    ``tools/wire_probe.py`` pins) and moves the per-chip host float64
    trig off the staging thread.

    Numerics: the phase uses ``t mod 365.25 == ((4t) mod 1461) / 4`` —
    exact integer arithmetic (4t < 2^23 for any ordinal day), so the
    phase argument is bit-identical to the host float64 ``np.mod`` for
    integer dates in EITHER dtype; ``yr`` subtracts the int anchor before
    widening, so it is exact too.  Only the trig itself is evaluated in
    the compute dtype instead of float64-then-cast, which bounds the
    device-vs-host design difference at trig ulp (~1e-7 relative in f32,
    ~1e-16 in f64) — far inside the measured f32 oracle-parity envelope
    (tests/test_wire.py pins the tolerance; docs/DIVERGENCE.md)."""
    f = jnp.dtype(dtype)
    days = days.astype(jnp.int32)
    C, T = days.shape
    valid = jnp.arange(T)[None, :] < n_obs[:, None]
    quarter = jnp.mod(4 * days, 1461)                          # int, exact
    ph = jnp.asarray(params.OMEGA, f) \
        * (quarter.astype(f) * jnp.asarray(0.25, f))
    anchor = jnp.where(n_obs > 0, days[:, 0], 0)
    yr = (days - anchor[:, None]).astype(f) / jnp.asarray(365.25, f)
    one = jnp.ones_like(yr)
    c1, s1 = jnp.cos(ph), jnp.sin(ph)
    c2, s2 = jnp.cos(2 * ph), jnp.sin(2 * ph)
    c3, s3 = jnp.cos(3 * ph), jnp.sin(3 * ph)
    X = jnp.stack([one, yr, c1, s1, c2, s2, c3, s3], axis=-1)
    Xt = jnp.stack([one, c1, s1, c2, s2], axis=-1)
    # Padding rows contribute nothing (build_designs' zeroing rule).
    X = jnp.where(valid[..., None], X, 0)
    Xt = jnp.where(valid[..., None], Xt, 0)
    return X, Xt, days.astype(f), valid


def _detect_batch_wire(days_i32, n_obs_i32, Y_i16, qa_wire, *, dtype,
                       wcap=None, sensor=LANDSAT_ARD,
                       max_segments=MAX_SEGMENTS, compact=None,
                       fused=None, mixed=None):
    """Batch detect from the all-integer wire: spectra ride int16, QA
    uint8/uint16, and the day ordinals ride int32 — the harmonic design
    matrices, the float date grid, and the validity mask are built on
    device by :func:`device_designs` inside this jitted prologue, so NO
    float plane crosses host->device at all (docs/ROOFLINE.md "Wire
    budget").  The core widens the spectra on device and keeps a
    wire-dtype resident copy so the Pallas fit path reads int16 from HBM.
    ``compact`` (static) is the active-lane-compaction override (None =
    FIREBIRD_COMPACT at trace time)."""
    Xs, Xts, ts, valids = device_designs(days_i32, n_obs_i32, dtype)
    return _detect_batch_core(Xs, Xts, ts, valids, Y_i16,
                              qa_wire.astype(jnp.int32), wcap=wcap,
                              sensor=sensor, max_segments=max_segments,
                              dtype=dtype, compact=compact, fused=fused,
                              mixed=mixed)


_WIRE_STATICS = ("dtype", "wcap", "sensor", "max_segments", "compact",
                 "fused", "mixed")
# Donating twin for the driver's staged steady-state dispatch: the packed
# wire buffers (spectra + QA, the dominant HBM input term) are consumed by
# the dispatch, so a deeper pipeline (Config.pipeline_depth) doesn't pin
# every in-flight batch's inputs alongside its results.  Only safe for
# single-dispatch callers (check_capacity=False) — a capacity retry would
# re-dispatch already-deleted buffers.  (Jitted BEFORE the plain wrapper
# rebinds the name, so both trace the same underlying function and keep
# one HLO module name — persistent cache entries stay shared/valid.)
_detect_batch_wire_donated = jax.jit(_detect_batch_wire,
                                     static_argnames=_WIRE_STATICS,
                                     donate_argnums=(2, 3))
_detect_batch_wire = jax.jit(_detect_batch_wire,
                             static_argnames=_WIRE_STATICS)
# Donated compiles emit jax's "Some donated buffers were not usable"
# advisory once per shape (the wire dtypes rarely alias the float result
# buffers byte-for-byte; the donation is still honored — inputs freed at
# dispatch).  Deliberately NOT suppressed: a process-global filter would
# hide real donation bugs in unrelated jax code, and a per-dispatch
# warnings.catch_warnings races between the warm-compile thread and the
# main dispatch thread (filters are process-global state).


def window_cap(packed) -> int:
    """A rigorous static bound on initialization-window member count.

    A window [i, j] either closes on the observation count (exactly
    MEOW_SIZE members) or on the INIT_DAYS span — in which case all members
    but j lie within INIT_DAYS of t_i, so the count is bounded by the
    densest INIT_DAYS stretch of the (chip-shared) date grid plus one.
    Using all acquisitions (a superset of any alive set) keeps the bound
    valid for every round of the event loop.  Rounded up to a multiple of
    8 so minor date-grid differences reuse the compiled kernel.
    """
    cap = params.MEOW_SIZE
    for c in range(packed.n_chips):
        d = np.asarray(packed.dates[c][: int(packed.n_obs[c])], np.int64)
        if d.size:
            hi = np.searchsorted(d, d + params.INIT_DAYS, side="right")
            cap = max(cap, int((hi - np.arange(d.size)).max()) + 1)
    T = packed.spectra.shape[-1]
    return min(-8 * (-cap // 8), T)


def build_designs(dates: np.ndarray, n_obs: int | None = None,
                  dtype=np.float32) -> tuple[np.ndarray, np.ndarray]:
    """Host-side design matrices for a chip's date grid (float64 phases).

    Padding rows (beyond n_obs) get zeroed so they contribute nothing.
    """
    dates = np.asarray(dates)
    anchor = float(dates[0]) if dates.size else 0.0
    X = harmonic.design_matrix(dates, anchor, params.MAX_COEFS)
    Xt_full = harmonic.design_matrix(dates, anchor, params.TMASK_COEFS + 1)
    Xt = np.concatenate([Xt_full[:, :1], Xt_full[:, 2:]], axis=1)
    if n_obs is not None and n_obs < dates.shape[0]:
        X[n_obs:] = 0.0
        Xt[n_obs:] = 0.0
    return X.astype(dtype), Xt.astype(dtype)


def prep_batch(packed) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side batch prep shared by the single-device and sharded paths:
    stacked design matrices + validity mask for a PackedChips batch."""
    C, _, _, T = packed.spectra.shape
    designs = [build_designs(packed.dates[c], int(packed.n_obs[c]))
               for c in range(C)]
    Xs = np.stack([d[0] for d in designs])
    Xts = np.stack([d[1] for d in designs])
    valid = np.arange(T)[None, :] < packed.n_obs[:, None]
    return Xs, Xts, valid


def ensure_x64(dtype) -> None:
    """Enable jax x64 when a float64 run is requested — without it jnp
    silently downcasts f64 arrays to f32 and a 'bit-parity run' actually
    executes at single precision.  Called by every f64-capable entry
    point (detect_packed, mesh.detect_sharded)."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.float64) \
            and not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)


def working_set_bytes(T: int, W: int | None = None,
                      S: int = MAX_SEGMENTS, sensor=LANDSAT_ARD,
                      dtype_bytes: int = 4) -> int:
    """Estimated peak device bytes one chip needs during a dispatch.

    Drives chips-per-batch auto-sizing (driver.core.auto_chips_per_batch):
    wire arrays (int16 spectra + uint16 QA), the widened float spectra plus
    one [P,B,T]-sized live temporary, ~20 [P,T] loop temporaries (the scale
    the profiled HLO shows), the one-hot window tensors, and the flat
    result buffers (live twice across the while_loop boundary).
    """
    P, B, K = sensor.pixels, sensor.n_bands, params.MAX_COEFS
    W = W or min(T, 48)
    wire = P * B * T * 2 + P * T * 2
    bufs = 2 * P * S * (6 + 2 * B + B * K) * dtype_bytes
    widened = 2 * P * B * T * dtype_bytes
    pt_temps = 20 * P * T * dtype_bytes
    # The [P,W,T] one-hot window tensors exist only on the XLA INIT path;
    # the fused Pallas INIT kernel (FIREBIRD_PALLAS=init) and the
    # whole-loop mega kernel never materialize them, so batches can size
    # past that peak — but mega earns the exemption only on shapes
    # mega_fits ACCEPTS: a refused mega falls back to the XLA init path
    # and its one-hot peak (kernel._detect_batch_impl), which the batch
    # sizing must then have budgeted.  The widened-view and temporary
    # terms stay even for mega: the PROLOGUE (triage/variogram/alt fit)
    # runs identically in every config and its [P,B,T]-scale float peak
    # is the sizing constraint regardless of how lean the loop itself
    # is.  The kernel route is f32-only on TPU (Mosaic), so f64 sizing
    # keeps the term.
    def _mega_applies() -> bool:
        if not use_pallas("mega"):
            return False
        from firebird_tpu.ccd import pallas_ops

        return pallas_ops.mega_fits(T, W, B, S, 2)

    onehot = (0 if (use_pallas("init") or _mega_applies())
              and dtype_bytes == 4
              else P * W * T * (1 + dtype_bytes))
    return int(wire + widened + pt_temps + onehot + bufs)


def result_bytes(T: int, S: int = MAX_SEGMENTS, sensor=LANDSAT_ARD,
                 dtype_bytes: int = 4) -> int:
    """Device bytes one chip's ChipSegments result pins until its drain.

    The pipeline-depth term of batch auto-sizing
    (driver.core.auto_chips_per_batch): each in-flight batch beyond the
    one computing holds its FULL-CAPACITY result buffers on device until
    the drain thread fetches them — the egress diet shrinks what crosses
    the wire, not this residency — so depth must be budgeted against
    HBM explicitly."""
    P, B, K = sensor.pixels, sensor.n_bands, params.MAX_COEFS
    per_px = S * (6 + 2 * B + B * K) * dtype_bytes   # meta+rmse+mag+coef
    per_px += T + (B + 2) * dtype_bytes              # mask + vario + ints
    return int(P * per_px)


def record_first_call(key: tuple, fn):
    """First-call capture per compiled shape (jit compiles synchronously
    inside the first dispatch; warm-cache enqueues are sub-ms, so the
    first-call wall time IS the trace+compile time to within noise).

    Shared by the single-device (detect_packed) and sharded
    (parallel.mesh.detect_sharded) dispatch paths.  Seen-keys live on the
    metrics registry — run-scoped, not process-scoped — so every run's
    obs_report records a kernel_first_call_seconds entry per shape it
    dispatched, even when the jit cache was already warm."""
    from firebird_tpu.obs import metrics, tracing

    reg = metrics.get_registry()
    if not reg.once(("kernel_dispatch",) + tuple(key)):
        return fn()
    t0 = time.perf_counter()
    with tracing.span("first_dispatch", key=str(key)):
        out = fn()
    reg.histogram("kernel_first_call_seconds").observe(
        time.perf_counter() - t0)
    reg.counter("kernel_dispatch_shapes").inc()
    return out


# Histogram buckets for kernel_round_active_fraction (a 0..1 fraction,
# not a latency; sixteenths resolve the tail the compaction targets).
FRACTION_BUCKETS = tuple(i / 16 for i in range(1, 17))


def record_occupancy(seg) -> dict | None:
    """Feed the event loop's occupancy capture into the obs registry.

    ``seg`` is a host-fetched ChipSegments (driver.core.drain_batch calls
    this after its bulk fetch; bench.py after its timed run).  Per
    executed round and chip, ``kernel_round_active_fraction`` observes
    active/padded lanes; the counters accumulate active / wasted
    (paid - active) lane-rounds and compactions — the padded-vs-effective
    accounting flops.occupancy_detail turns into the bench artifact.
    Returns the summary dict, or None when the dispatch carried no
    occupancy capture (mega route, pre-compaction artifacts)."""
    occ = getattr(seg, "occupancy", None)
    if occ is None:
        return None
    from firebird_tpu.ccd import flops
    from firebird_tpu.obs import metrics as obs_metrics

    det = flops.occupancy_detail(
        np.asarray(occ), np.asarray(seg.rounds),
        int(seg.mask.shape[-2]))
    hist = obs_metrics.histogram("kernel_round_active_fraction",
                                 buckets=FRACTION_BUCKETS,
                                 help="active-lane fraction per event-loop "
                                      "round per chip")
    hist.observe_many(det.pop("_fractions"))
    obs_metrics.counter(
        "kernel_active_lane_rounds",
        help="lane-rounds with a working pixel").inc(
        det["active_lane_rounds"])
    obs_metrics.counter(
        "kernel_wasted_lane_rounds",
        help="paid lane-rounds with no working pixel "
             "(effective - active)").inc(det["wasted_lane_rounds"])
    comp = getattr(seg, "compactions", None)
    if comp is not None:
        # Per-loop counts land on each loop's first chip row (zeros
        # elsewhere), so the chip-sum is the batch total across shards.
        obs_metrics.counter(
            "kernel_compactions",
            help="dense-prefix lane compactions").inc(
            int(np.asarray(comp).sum()))
    lm = getattr(seg, "lanes_migrated", None)
    if lm is not None:
        moved = int(np.asarray(lm).sum())
        obs_metrics.counter(
            "kernel_lanes_migrated",
            help="straggler lanes migrated to a neighbor device by the "
                 "rebalancing ring").inc(moved)
        if moved:
            obs_metrics.counter(
                "rebalance_migrations",
                help="dispatches in which the rebalancing ring moved "
                     "lanes").inc()
        det["lanes_migrated"] = moved
    return det


def capacity_bound(packed) -> int:
    """An upper bound on segments any pixel of the batch can close:
    closed segments have disjoint included-observation sets of at least
    MEOW_SIZE members each, so T // MEOW_SIZE bounds the count."""
    T = packed.spectra.shape[-1]
    return max(T // params.MEOW_SIZE, 1)


def capacity_retry(dispatch, read_worst, S: int, bound: int):
    """The one overflow-retry policy, shared by the single-device and
    sharded paths: run ``dispatch(S)``; if any pixel closed more segments
    than S (``read_worst``, a host sync), double S (capped at the
    rigorous ``bound``) and re-dispatch.  S >= bound skips the sync —
    overflow is impossible there."""
    S = max(S, 1)
    while True:
        seg = dispatch(S)
        if S >= bound:
            return seg
        worst = read_worst(seg)
        if worst <= S:
            return seg
        from firebird_tpu.obs import logger

        logger("pyccd").info(
            "segment capacity %d overflowed (deepest pixel closed %d); "
            "re-dispatching at %d", S, worst, min(2 * S, bound))
        S = min(2 * S, bound)


def wire_qa8() -> bool:
    """Whether staging ships the QA plane as uint8 (FIREBIRD_WIRE_QA8,
    default on) — half the uint16 plane, the second-largest h2d term
    after the spectra.  Lossless for the kernel: the QA triage reads bits
    0–5 only (params.QA_*_BIT), all inside the low byte.  Read at
    staging time; the wire dtype is part of the jit key, so both modes
    keep their own compiled program."""
    from firebird_tpu.config import env_knob

    return env_knob("FIREBIRD_WIRE_QA8") not in ("", "0")


def wire_qa_dtype():
    """The staged QA plane's wire dtype under the current knobs."""
    return np.uint8 if wire_qa8() else np.uint16


def wire_args(packed) -> tuple:
    """The host-side ``_detect_batch_wire`` argument tuple (numpy, wire
    dtypes, all integer): day ordinals int32, n_obs int32, spectra int16,
    QA uint8/uint16 (:func:`wire_qa_dtype`).  Shared by stage_packed,
    the sharded stager, bench, and the tools so the wire contract has
    one definition."""
    return (np.asarray(packed.dates, np.int32),
            np.asarray(packed.n_obs, np.int32),
            np.asarray(packed.spectra, np.int16),
            np.asarray(packed.qas).astype(wire_qa_dtype()))


def stage_packed(packed, dtype) -> tuple:
    """Host->device staging of a PackedChips batch: the wire-dtype
    ``_detect_batch_wire`` argument tuple as device arrays, blocking until
    the transfer lands.  Every staged plane is integer (int32 days +
    counts, int16 spectra, uint8/uint16 QA — :func:`wire_args`); the
    float designs/date grid/validity mask are built on device by the
    jitted prologue (:func:`device_designs`).  Split out of
    :func:`detect_packed` so the driver's prefetch thread can ship batch
    i+1's H2D while batch i computes (driver.core.stage_batch); the main
    thread then dispatches with ``staged=``."""
    ensure_x64(dtype)
    args = tuple(jnp.asarray(a) for a in wire_args(packed))
    jax.block_until_ready(args)
    return args


def aot_compile(avatars, *, dtype, wcap, sensor=LANDSAT_ARD,
                max_segments: int = MAX_SEGMENTS, donate: bool = False,
                compact: bool | None = None, fused=None,
                mixed: bool | None = None):
    """AOT lower+compile the wire-dtype batch program for a shape WITHOUT
    running it (``avatars`` are jax.ShapeDtypeStructs in the
    ``_detect_batch_wire`` argument order: days int32 [C,T], n_obs int32
    [C], spectra int16 [C,B,P,T], QA uint8/uint16 [C,P,T] — must match
    :func:`wire_args`' dtypes or the warm entry misses).  With the persistent
    compilation cache on, the serialized executable is what the first
    real dispatch of the same shape deserializes instead of compiling —
    the driver's background warm start (driver.core.warm_start).
    ``compact`` must match what the real dispatch will pass (the drivers
    pass cfg.compact both here and at dispatch) or the warm entry misses
    the jit cache."""
    fn = _detect_batch_wire_donated if donate else _detect_batch_wire
    return fn.lower(*avatars, dtype=jnp.dtype(dtype), wcap=wcap,
                    sensor=sensor, max_segments=max_segments,
                    compact=compact, fused=fused, mixed=mixed).compile()


def detect_packed(packed, dtype=jnp.float32,
                  max_segments: int = MAX_SEGMENTS,
                  check_capacity: bool = True, staged: tuple | None = None,
                  donate: bool = False,
                  compact: bool | None = None,
                  fused=None, mixed: bool | None = None) -> ChipSegments:
    """Run the kernel over a PackedChips batch -> ChipSegments with leading
    chip axis [C, P, ...].  The batch's sensor spec selects the band
    layout the kernel compiles for.

    The segment buffers start at ``max_segments`` capacity; on the rare
    chip where some pixel closes more segments than that (n_segments
    counts true closes, writes past capacity are dropped), the batch is
    re-dispatched with doubled capacity until every segment fits — each
    capacity is a separate compiled program, cached for later batches.
    ``check_capacity=False`` skips the overflow check, keeping the
    dispatch fully asynchronous — the caller must then test
    ``n_segments > capacity`` itself before trusting the buffers (the
    driver does this on its drain thread, driver/core.py::drain_batch).

    ``staged`` takes pre-staged device args from :func:`stage_packed`
    instead of transferring here; ``donate=True`` (honored only with
    ``check_capacity=False`` — a retry would re-dispatch deleted buffers)
    frees the wire input buffers at dispatch.  ``compact`` overrides the
    FIREBIRD_COMPACT default (params.compact_default) per call;
    ``fused`` (False/True/"mon") and ``mixed`` likewise override
    FIREBIRD_FUSED_FIT / FIREBIRD_MIXED_PRECISION.
    """
    ensure_x64(dtype)
    args = staged if staged is not None else stage_packed(packed, dtype)
    kw = dict(dtype=jnp.dtype(dtype), wcap=window_cap(packed),
              sensor=getattr(packed, "sensor", LANDSAT_ARD),
              compact=compact, fused=fused, mixed=mixed)
    fn = _detect_batch_wire_donated if donate and not check_capacity \
        else _detect_batch_wire
    dispatch = lambda S: record_first_call(
        ("single", packed.spectra.shape, str(kw["dtype"]), kw["wcap"],
         kw["sensor"].name, S, compact, fused, mixed),
        lambda: fn(*args, max_segments=S, **kw))
    if not check_capacity:
        return dispatch(max(max_segments, 1))
    return capacity_retry(dispatch,
                          lambda seg: int(np.asarray(seg.n_segments).max()),
                          max_segments, capacity_bound(packed))


# ---------------------------------------------------------------------------
# Int-coded egress: the d2h half of the wire diet (docs/ROOFLINE.md
# "Wire budget").  ChipSegments drains as float32 planes sized for the
# WORST-CASE segment capacity; the store's row values are integers or
# exact functions of the f32 bits, so the drain can cross the wire as
# integer tables sliced to the batch's OBSERVED segment depth — decoded
# bit-exactly on the host (ccd.format.decode_egress), store rows
# byte-identical to the raw-f32 drain (tests/test_wire.py golden).
# ---------------------------------------------------------------------------

def wire_egress_enabled() -> bool:
    """Whether batch drains cross d2h as int-coded tables
    (FIREBIRD_WIRE_EGRESS, default on; f32 results only — the f64
    bit-parity path keeps the raw drain).  Read per drain, not per
    trace: the packing program is a separate jit."""
    from firebird_tpu.config import env_knob

    return env_knob("FIREBIRD_WIRE_EGRESS") not in ("", "0")


def egress_bucket(worst: int, S: int) -> int:
    """The packed egress segment depth: the observed deepest pixel's
    close count rounded up to a power of two (few compiled packing
    shapes), capped at the result buffers' capacity ``S``."""
    w = max(int(worst), 1)
    return min(1 << (w - 1).bit_length(), S)


@functools.partial(jax.jit, static_argnames=("s_eff",))
def pack_egress(seg: ChipSegments, s_eff: int) -> dict:
    """Device-side egress packing of a batched f32 ChipSegments: every
    table integer-dtyped, segment planes sliced to ``s_eff`` slots.

    Codings (all lossless — the golden test requires store rows
    byte-identical to the raw f32 drain):

    - ``meta`` [C,P,s_eff,6] int32: sday/eday/bday/curqa/nobs are exact
      small integers in f32 (ordinal days < 2^24), rint-coded; the
      chprob column is count-coded as ``rint(chprob * PEEK_SIZE)`` —
      chprob is always k/PEEK_SIZE or 1.0, and the host decode re-runs
      the same f32 division the kernel performed, reproducing the f32
      value bit-exactly.
    - ``rmse``/``mag``/``coef``/``vario``: f32 bitcast to int32 (free,
      and it keeps the d2h contract checkable: no float leaves).
    - ``mask``: bitpacked along T (8x).
    - counters/diagnostics (n_segments, procedure, rounds, round_counts,
      occupancy, compactions) are already integer and pass through.

    ``s_eff`` (static; :func:`egress_bucket` of the drain's capacity
    probe) is what buys the big cut: the f32 drain ships S=10 slots per
    pixel while the observed depth is typically 1-2.
    """
    sl = lambda a: a[:, :, :s_eff]
    bc = lambda a: lax.bitcast_convert_type(a, jnp.int32)
    meta = sl(seg.seg_meta)
    meta_i = jnp.rint(meta).astype(jnp.int32)
    meta_i = meta_i.at[..., 3].set(
        jnp.rint(meta[..., 3] * params.PEEK_SIZE).astype(jnp.int32))
    out = dict(n_segments=seg.n_segments, procedure=seg.procedure,
               meta=meta_i, rmse=bc(sl(seg.seg_rmse)),
               mag=bc(sl(seg.seg_mag)), coef=bc(sl(seg.seg_coef)),
               mask=jnp.packbits(seg.mask, axis=-1))
    for f in ("rounds", "round_counts", "occupancy", "compactions",
              "lanes_migrated"):
        v = getattr(seg, f)
        if v is not None:
            out[f] = v
    if seg.vario is not None:
        out["vario"] = bc(seg.vario)
    return out


def chip_slice(seg: ChipSegments, c: int, to_host: bool = False) -> ChipSegments:
    """One chip's view of a batched ChipSegments ([C, ...] -> [...]).

    Single-sources the field set: every field (including future additions)
    is sliced, None-valued optionals pass through.  ``to_host`` fetches the
    slices as numpy arrays.
    """
    out = []
    for f in dataclasses.fields(seg):
        v = getattr(seg, f.name)
        if v is not None:
            v = v[c]
            if to_host:
                v = np.asarray(v)
        out.append(v)
    return ChipSegments(*out)


def segments_to_records(seg: ChipSegments, dates: np.ndarray,
                        pixel: int, sensor=LANDSAT_ARD) -> dict:
    """Convert one pixel's kernel output to the oracle/pyccd result dict
    (change_models + processing_mask), for parity tests and the format
    layer.  ``seg`` must be single-chip ([P, ...]) host-fetched arrays."""
    anchor = float(dates[0]) if len(dates) else 0.0
    # n_segments counts true closes, which can exceed buffer capacity on a
    # raw (non-retried) result; detect_packed re-dispatches so this clip
    # only guards direct _detect_batch_wire callers.
    n = min(int(seg.n_segments[pixel]), seg.seg_meta.shape[-2])
    models = []
    for k in range(n):
        meta = np.asarray(seg.seg_meta[pixel, k], np.float64)
        coefs = np.asarray(seg.seg_coef[pixel, k], np.float64)   # [B,8]
        coefs7, intercept = harmonic.to_pyccd_convention(coefs, anchor)
        rec = {
            "start_day": int(round(meta[0])), "end_day": int(round(meta[1])),
            "break_day": int(round(meta[2])),
            "observation_count": int(round(meta[5])),
            "change_probability": float(meta[3]),
            "curve_qa": int(round(meta[4])),
        }
        for b, name in enumerate(sensor.band_names):
            rec[name] = {
                "magnitude": float(seg.seg_mag[pixel, k, b]),
                "rmse": float(seg.seg_rmse[pixel, k, b]),
                "coefficients": tuple(float(x) for x in coefs7[b]),
                "intercept": float(intercept[b]),
            }
        models.append(rec)
    T = len(dates)
    return {"change_models": models,
            "processing_mask": [int(x) for x in np.asarray(seg.mask[pixel][:T])],
            "procedure": ["standard", "permanent-snow", "insufficient-clear",
                          "no-data"][int(seg.procedure[pixel])]}
