"""Pallas TPU kernels for the CCD hot ops.

:func:`monitor_chain` — the MONITOR event logic (kernel._monitor_chain):
a pipeline of ~15 cumulative/reduce ops over the [P, T] score plane whose
intermediates otherwise stream through HBM between fusions (the round-2
profile shows the loop body paying a ~0.3 ms-per-op floor at these
shapes).  One block computes cursor ranks, break-run lengths (reverse
cummin as a log-step shift scan), refit-ladder crossings (cumsum
likewise), and the tail/break/refit event selection entirely in VMEM,
with the pixel axis on lanes and T on sublanes.

:func:`monitor_chain_scored` — the same chain with the chi2 score plane
computed in VMEM from the wire-dtype detection-band spectra (the
``score`` component; it supersedes ``monitor``).

:func:`tmask_bad` — the Tmask IRLS screen (kernel._tmask_bad): six
sequential weighted SPD solves plus ten masked medians per round, each a
separate fusion paying the per-op floor; the kernel runs the whole IRLS
in VMEM, with a shift-exchange bitonic network for the medians.

:func:`ring_remote_copy` — one hop of the cross-device rebalancing ring
(parallel.mesh).

Enablement: `firebird_tpu.ccd.kernel` routes a component through its
Pallas kernel when FIREBIRD_PALLAS names it — "1" enables all three,
"monitor,tmask"-style lists pick a subset (kernel.pallas_components;
CPU tests run the same kernels under ``interpret=True``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from firebird_tpu.ccd import params

# ---------------------------------------------------------------------------
# Per-block skip guards (active-lane compaction).  Every kernel here
# grids over pixel-lane blocks; with the event loop's dense-prefix
# compaction on (FIREBIRD_COMPACT, kernel._detect_batch_impl), dead
# lanes cluster into whole trailing blocks — so each wrapper accepts an
# optional ``active`` [P] lane mask, reduces it to a per-block count,
# and the block body runs under ``pl.when(count > 0)``: an all-dead
# block costs a predicate plus a zero-fill of its outputs (exactly the
# values the dead lanes would compute — all-zero windows / in_mon=False
# produce zeros through the real math, so the guard is bit-identical).
# ``active=None`` (the default, and every pre-compaction call site)
# traces the unguarded program unchanged.
# ---------------------------------------------------------------------------

def _block_counts(active, BP: int, Pp: int):
    """[1, Pp//BP] i32 per-block active-lane counts (prefix sums over the
    compacted alive mask, differenced per block — computed as one padded
    reshape-reduce)."""
    a = jnp.pad(jnp.asarray(active).astype(jnp.int32),
                (0, Pp - active.shape[0]))
    return jnp.sum(a.reshape(Pp // BP, BP), -1)[None]


# The counts ride whole in SMEM, the scalars the guard branches on: a
# (1, 1) block per grid step is no legal TPU block (the last two block
# dims must tile by 8 x 128 or span the array).
_CNT_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _when_active(cnt_ref, compute, zero):
    """Run ``compute`` when the block has any active lane, else ``zero``
    (the cheap output fill).  ``cnt_ref is None`` means unguarded."""
    if cnt_ref is None:
        compute()
        return
    n = cnt_ref[0, pl.program_id(0)]

    @pl.when(n > 0)
    def _():
        compute()

    @pl.when(n == 0)
    def _():
        zero()


def _zero_refs(*refs):
    for r in refs:
        r[...] = jnp.zeros(r.shape, r.dtype)


# ---------------------------------------------------------------------------
# MONITOR event-chain kernel
# ---------------------------------------------------------------------------

def mon_block_p(T: int) -> int:
    """Lane-block width for the monitor kernel, derived from T.

    The kernel keeps ~12 [T, BP] planes live (inputs + scan temporaries),
    so its VMEM footprint is linear in T; a fixed 512-lane block that fits
    a bucketed 512-obs archive would blow VMEM on a multi-decade T~1800
    series.  Budget ~10 MB of VMEM for the planes (leaving room for the
    pipeline's double-buffered input blocks) and round down to the 128
    lane width, floored at one lane tile.
    """
    budget = 10 * 2 ** 20
    per_lane = 12 * max(T, 1) * 4
    return max(128, min(512, (budget // per_lane) // 128 * 128))


def _shift_scan_min_rev(x, T, fill):
    """Reverse cummin along axis 0 (sublanes) as a log-step shift-min."""
    k = 1
    while k < T:
        pad = jnp.full((k,) + x.shape[1:], fill, x.dtype)
        x = jnp.minimum(x, jnp.concatenate([x[k:], pad], axis=0))
        k *= 2
    return x


def _shift_scan_add(x, T):
    """Inclusive cumsum along axis 0 (sublanes) as a log-step shift-add."""
    k = 1
    while k < T:
        pad = jnp.zeros((k,) + x.shape[1:], x.dtype)
        x = x + jnp.concatenate([pad, x[:T - k]], axis=0)
        k *= 2
    return x


def _pad_helpers(pad):
    """(plane, vec) input builders shared by the monitor wrappers:
    transpose to [T, P] / [1, P] layout and pad the lane axis."""
    plane = lambda x, cv=0: jnp.pad(
        jnp.asarray(x).T, ((0, 0), (0, pad)), constant_values=cv)
    vec = lambda x, cv=0: jnp.pad(
        jnp.asarray(x)[None, :], ((0, 0), (0, pad)), constant_values=cv)
    return plane, vec


def _mon_outs_to_dict(outs, P):
    """Unpack the 10 monitor-kernel outputs (kernel._monitor_chain
    contract) — shared by both wrappers so the two FIREBIRD_PALLAS paths
    cannot diverge on the output assembly."""
    m, istail, isbrk, isrefit, evrank, posev, nexc, nrf, incq, remq = outs
    cut = lambda x: x[0, :P]
    cutb = lambda x: x[0, :P] > 0
    return dict(m=cut(m), is_tail=cutb(istail), is_brk=cutb(isbrk),
                is_refit=cutb(isrefit), ev_rank=cut(evrank),
                pos_ev=cut(posev), n_exceed=cut(nexc), n_rf=cut(nrf),
                inc_q=(incq[:, :P] > 0).T, rem_q=(remq[:, :P] > 0).T)


def _monitor_logic(s, alive, included, rank, cur_k, nlast, in_mon, *,
                   change_thr, outlier_thr, peek, refit_factor, T):
    """The MONITOR event logic on VMEM-resident planes, shared by the
    plain and score-fused blocks.

    Planes are [T, Pb] (T on sublanes, pixels on lanes); per-pixel vectors
    are [1, Pb].  Mirrors the jnp reference op for op — argmax becomes a
    first-index min-reduce with the same no-hit default (0), and the
    rank/count lookups become one-hot reduces (no gather in Mosaic).
    Returns the 10 output planes/vectors in kernel._monitor_chain order.
    """
    INF = jnp.int32(T + 1)
    ti = lax.broadcasted_iota(jnp.int32, s.shape, 0)          # [T,Pb]
    one = jnp.int32(1)
    m = jnp.sum(jnp.where(alive, one, 0), 0, keepdims=True)   # [1,Pb]
    kq = jnp.sum(jnp.where(alive & (ti < cur_k), one, 0), 0, keepdims=True)

    ex = alive & (s > change_thr)
    reset_r = jnp.where(alive & ~ex, rank, INF)
    nrr = _shift_scan_min_rev(reset_r, T, T + 1)
    runlen = jnp.minimum(nrr, m) - rank
    elig = alive & (rank >= kq)
    brk = elig & ex & (runlen >= peek)
    has_brk = jnp.any(brk, 0, keepdims=True)
    b_abs = jnp.where(has_brk,
                      jnp.min(jnp.where(brk, ti, INF), 0, keepdims=True), 0)

    o = s > outlier_thr
    absq = elig & ~o
    n0 = jnp.sum(jnp.where(included, one, 0), 0, keepdims=True)
    n_inc = n0 + _shift_scan_add(jnp.where(absq, one, 0), T)
    refit_hit = absq & (n_inc.astype(s.dtype)
                        >= refit_factor * nlast.astype(s.dtype))
    has_refit = jnp.any(refit_hit, 0, keepdims=True)
    f_abs = jnp.where(
        has_refit,
        jnp.min(jnp.where(refit_hit, ti, INF), 0, keepdims=True), 0)

    q_tail = jnp.maximum(m - (peek - 1), kq)

    def at_idx(plane, idx):
        return jnp.sum(jnp.where(ti == idx, plane, 0), 0, keepdims=True)

    b_ev = jnp.where(has_brk, at_idx(rank, b_abs), INF)
    f_ev = jnp.where(has_refit, at_idx(rank, f_abs), INF)
    is_tail = in_mon & (q_tail <= jnp.minimum(b_ev, f_ev))
    is_brk = in_mon & ~is_tail & has_brk & (b_ev <= f_ev)
    is_refit = in_mon & ~is_tail & ~is_brk & has_refit

    ev_rank = jnp.where(is_tail, q_tail, jnp.where(is_brk, b_ev, f_ev))
    normal_hi = jnp.where(is_refit, ev_rank + 1, ev_rank)
    normalq = elig & (rank < normal_hi)
    inc_q = normalq & ~o
    rem_q = normalq & o
    tailq = elig & (rank >= q_tail) & is_tail
    tail_ex = tailq & (s > change_thr)
    inc_q = inc_q | (tailq & ~tail_ex)
    rem_q = rem_q | tail_ex
    n_exceed = jnp.sum(jnp.where(tail_ex, one, 0), 0, keepdims=True)
    pos_ev = jnp.where(is_brk, b_abs, f_abs)
    n_rf = at_idx(n_inc, pos_ev)

    as_i = lambda b: jnp.where(b, one, 0)
    return (m, as_i(is_tail), as_i(is_brk), as_i(is_refit), ev_rank,
            pos_ev, n_exceed, n_rf, as_i(inc_q), as_i(rem_q))


def _monitor_block(s_ref, alive_ref, inc_ref, rank_ref, curk_ref, nlast_ref,
                   inmon_ref, *refs, change_thr, outlier_thr, peek,
                   refit_factor, T, guarded=False):
    """One pixel block of kernel._monitor_chain, everything in VMEM."""
    cnt_ref, out_refs = ((refs[0], refs[1:]) if guarded
                         else (None, refs))

    def compute():
        outs = _monitor_logic(
            s_ref[...], alive_ref[...] > 0, inc_ref[...] > 0, rank_ref[...],
            curk_ref[...], nlast_ref[...], inmon_ref[...] > 0,
            change_thr=change_thr, outlier_thr=outlier_thr, peek=peek,
            refit_factor=refit_factor, T=T)
        for ref, val in zip(out_refs, outs):
            # x64 mode promotes index arithmetic to int64; ref stores
            # don't auto-cast in interpret mode, so land at the ref's
            # dtype.
            ref[...] = val.astype(ref.dtype)

    # An all-inactive block (no in_mon lane) has every consumer of its
    # outputs masked on in_mon downstream (kernel._mon_block): zeros are
    # inert, same as _mon_zeros' skip branch.
    _when_active(cnt_ref, compute, lambda: _zero_refs(*out_refs))


def _mon_scored_logic(yd_of, coefs_d, dden, X, alive, included, cur_k,
                      nlast, in_mon, *, change_thr, outlier_thr, peek,
                      refit_factor, T, nb):
    """Score + shared event logic on VMEM-resident planes (the scored
    monitor block's body).

    ``yd_of(b)`` -> [T,BP] detection-band plane (wire dtype), coefs_d
    [nb,K,BP], dden [nb,BP], X [T,K], alive/included [T,BP] bool, cur_k/
    nlast [1,BP] i32, in_mon [1,BP] bool.  Returns the 10 outputs of
    kernel._monitor_chain order (i32 planes/vectors).
    """
    f32 = X.dtype
    s = None
    for b in range(nb):
        pred = jnp.dot(X, coefs_d[b], preferred_element_type=f32)
        r = (yd_of(b).astype(f32) - pred) / dden[b][None, :]
        s = r * r if s is None else s + r * r                 # [T, BP]

    rank = _shift_scan_add(jnp.where(alive, jnp.int32(1), 0), T) - 1
    return _monitor_logic(
        s, alive, included, rank, cur_k, nlast, in_mon,
        change_thr=change_thr, outlier_thr=outlier_thr, peek=peek,
        refit_factor=refit_factor, T=T)


def _monitor_scored_block(yd_ref, coef_ref, dden_ref, x_ref, alive_ref,
                          inc_ref, curk_ref, nlast_ref, inmon_ref,
                          *refs, change_thr, outlier_thr, peek,
                          refit_factor, T, nb, guarded=False):
    """Score-fused monitor block: compute the chi2 score plane s — the
    detection-band predictions against the current model — *inside* VMEM
    from wire-dtype spectra, then run the shared event logic.

    Replaces the XLA path's [P,nb,T] prediction einsum + [P,T] score and
    rank materializations (the dominant HBM terms of a steady-state
    monitor round now that the INIT block is cond-gated): spectra stream
    once as int16, predictions are one [T,K]x[K,BP] MXU dot per band,
    and rank is a log-step shift-add over the alive plane.
    """
    cnt_ref, out_refs = ((refs[0], refs[1:]) if guarded
                         else (None, refs))

    def compute():
        outs = _mon_scored_logic(
            lambda b: yd_ref[b], coef_ref[...], dden_ref[...], x_ref[...],
            alive_ref[...] > 0, inc_ref[...] > 0, curk_ref[...],
            nlast_ref[...], inmon_ref[...] > 0, change_thr=change_thr,
            outlier_thr=outlier_thr, peek=peek, refit_factor=refit_factor,
            T=T, nb=nb)
        for ref, val in zip(out_refs, outs):
            ref[...] = val.astype(ref.dtype)   # see _monitor_block

    _when_active(cnt_ref, compute, lambda: _zero_refs(*out_refs))


@functools.partial(jax.jit, static_argnames=("change_thr", "outlier_thr",
                                             "interpret"))
def monitor_chain(s, alive, included, rank, cur_k, n_last_fit, in_mon, *,
                  change_thr, outlier_thr, active=None, interpret=False):
    """Pallas port of kernel._monitor_chain (same output contract).

    Values are identical for every lane the caller uses: argmax' no-hit
    default (0), the INF sentinels, and the normal/tail partition all
    mirror the jnp reference exactly; the only arithmetic is integer.
    ``active`` (normally in_mon) is the per-block skip guard.
    """
    P, T = s.shape
    BP = mon_block_p(T)
    Pp = -BP * (-P // BP)
    pad = Pp - P
    plane, vec = _pad_helpers(pad)

    i32 = jnp.int32
    args = [plane(s), plane(alive.astype(i32)), plane(included.astype(i32)),
            plane(rank.astype(i32)), vec(cur_k.astype(i32)),
            vec(n_last_fit.astype(i32), 1), vec(in_mon.astype(i32))]
    pspec = pl.BlockSpec((T, BP), lambda i: (0, i))
    vspec = pl.BlockSpec((1, BP), lambda i: (0, i))
    in_specs = [pspec, pspec, pspec, pspec, vspec, vspec, vspec]
    if active is not None:
        args.append(_block_counts(active, BP, Pp))
        in_specs.append(_CNT_SPEC)
    kern = functools.partial(_monitor_block, change_thr=float(change_thr),
                             outlier_thr=float(outlier_thr),
                             peek=int(params.PEEK_SIZE),
                             refit_factor=float(params.REFIT_FACTOR), T=T,
                             guarded=active is not None)
    vshape = jax.ShapeDtypeStruct((1, Pp), i32)
    pshape = jax.ShapeDtypeStruct((T, Pp), i32)
    outs = pl.pallas_call(
        kern,
        grid=(Pp // BP,),
        in_specs=in_specs,
        out_specs=[vspec] * 8 + [pspec] * 2,
        out_shape=[vshape] * 8 + [pshape] * 2,
        interpret=interpret,
    )(*args)
    return _mon_outs_to_dict(outs, P)


def scored_block_p(T: int, nb: int, y_bytes: int) -> int:
    """Lane-block width for the score-fused monitor kernel: the monitor
    planes (~12 [T, BP] f32) plus the [nb, T, BP] wire-dtype spectra
    block and the live score/pred temporaries."""
    budget = 10 * 2 ** 20
    per_lane = max(T, 1) * (14 * 4 + nb * y_bytes)
    return max(128, min(512, (budget // per_lane) // 128 * 128))


@functools.partial(jax.jit, static_argnames=("change_thr", "outlier_thr",
                                             "interpret"))
def monitor_chain_scored(Yd, coefs_d, dden, X, alive, included, cur_k,
                         n_last_fit, in_mon, *, change_thr, outlier_thr,
                         active=None, interpret=False):
    """Score-fused Pallas twin of kernel._mon_block's score + chain.

    Args:
        Yd: [nb, T, P] detection-band resident spectra (wire int16 or
            float32; widened in-register, exact).
        coefs_d: [P, nb, K] current model coefficients (detection bands).
        dden: [P, nb] score denominators (max(rmse, vario), detection).
        X: [T, K] design (chip-shared).
        alive, included: [P, T] bool planes.
        cur_k, n_last_fit: [P] int; in_mon: [P] bool.
        active: optional [P] bool per-block skip guard (normally in_mon).
    Returns:
        The kernel._monitor_chain output dict (same contract); rank is
        derived in-kernel from the alive plane.
    """
    nb, T, P = Yd.shape
    K = X.shape[-1]
    f32 = X.dtype
    BP = scored_block_p(T, nb, Yd.dtype.itemsize)
    Pp = -BP * (-P // BP)
    pad = Pp - P
    i32 = jnp.int32

    plane, vec = _pad_helpers(pad)
    yp = jnp.pad(Yd, ((0, 0), (0, 0), (0, pad)))
    cf = jnp.pad(coefs_d.transpose(1, 2, 0), ((0, 0), (0, 0), (0, pad)))
    dd = jnp.pad(dden.T, ((0, 0), (0, pad)), constant_values=1.0)

    kern = functools.partial(
        _monitor_scored_block, change_thr=float(change_thr),
        outlier_thr=float(outlier_thr), peek=int(params.PEEK_SIZE),
        refit_factor=float(params.REFIT_FACTOR), T=T, nb=nb,
        guarded=active is not None)
    pspec = pl.BlockSpec((T, BP), lambda i: (0, i))
    vspec = pl.BlockSpec((1, BP), lambda i: (0, i))
    args = [yp, cf.astype(f32), dd.astype(f32), X,
            plane(alive.astype(i32)), plane(included.astype(i32)),
            vec(cur_k.astype(i32)), vec(n_last_fit.astype(i32), 1),
            vec(in_mon.astype(i32))]
    in_specs = [
        pl.BlockSpec((nb, T, BP), lambda i: (0, 0, i)),
        pl.BlockSpec((nb, K, BP), lambda i: (0, 0, i)),
        pl.BlockSpec((nb, BP), lambda i: (0, i)),
        pl.BlockSpec((T, K), lambda i: (0, 0)),
        pspec, pspec, vspec, vspec, vspec,
    ]
    if active is not None:
        args.append(_block_counts(active, BP, Pp))
        in_specs.append(_CNT_SPEC)
    outs = pl.pallas_call(
        kern,
        grid=(Pp // BP,),
        in_specs=in_specs,
        out_specs=[vspec] * 8 + [pspec] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, Pp), i32)] * 8
        + [jax.ShapeDtypeStruct((T, Pp), i32)] * 2,
        interpret=interpret,
    )(*args)
    return _mon_outs_to_dict(outs, P)


# ---------------------------------------------------------------------------
# Tmask IRLS kernel
# ---------------------------------------------------------------------------

def tmask_block_p(W: int) -> int:
    """Lane-block width for the Tmask kernel (footprint linear in the
    padded window length; ~30 [W, BP] planes live through the IRLS)."""
    budget = 10 * 2 ** 20
    per_lane = 30 * max(W, 1) * 4
    return max(128, min(512, (budget // per_lane) // 128 * 128))


def _bitonic_sublane(x, n, fill):
    """Ascending bitonic sort along axis 0 (length n, a power of two) via
    index-arithmetic shift exchanges — no gather/scatter, Mosaic-friendly.
    Produces the same sorted values as any sort (stability irrelevant for
    order statistics)."""
    i = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            fillp = jnp.full((j,) + x.shape[1:], fill, x.dtype)
            up = jnp.concatenate([x[j:], fillp], axis=0)      # x[i + j]
            dn = jnp.concatenate([fillp, x[:-j]], axis=0)     # x[i - j]
            low = (i & j) == 0
            partner = jnp.where(low, up, dn)
            asc = (i & k) == 0
            keep_small = low == asc
            mn = jnp.minimum(x, partner)
            mx = jnp.maximum(x, partner)
            x = jnp.where(keep_small, mn, mx)
            j //= 2
        k *= 2
    return x


def _median_sublane(r, mask, n_pow):
    """kernel._masked_median along axis 0: sort masked values (+inf
    padding), average the two middle order statistics.  The plane is
    padded up to the power-of-two network size — the bitonic exchange
    indices are only correct on a full n_pow-row array."""
    W = r.shape[0]
    x = jnp.where(mask, r, jnp.inf)
    if n_pow != W:
        x = jnp.concatenate(
            [x, jnp.full((n_pow - W,) + x.shape[1:], jnp.inf, x.dtype)], 0)
    s = _bitonic_sublane(x, n_pow, jnp.inf)
    i = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    n = jnp.sum(jnp.where(mask, 1, 0), 0, keepdims=True)
    lo_i = jnp.maximum((n - 1) // 2, 0)
    hi_i = jnp.maximum(n // 2, 0)
    lo = jnp.sum(jnp.where(i == lo_i, s, 0.0), 0, keepdims=True)
    hi = jnp.sum(jnp.where(i == hi_i, s, 0.0), 0, keepdims=True)
    return jnp.where(n > 0, 0.5 * (lo + hi), 0.0)             # [1, BP]


def _tmask_core(X, Y, wm, vario, *, nt, nb, n_pow, iters, huber_k,
                tmask_const):
    """The Tmask IRLS screen on VMEM-resident window planes (the tmask
    block's body).

    X: list of nt [W, BP] design columns; Y: list of nb [W, BP] band
    planes; wm [W, BP] 0/1; vario [nb, BP].  Returns bad [W, BP] bool.
    Mirrors the jnp reference's arithmetic order exactly: XtXt outer
    products precomputed once, Gram/corr as weight-times-product reduces
    over W, the unrolled 5x5 Cholesky with its NaN-on-non-PD contract,
    MAD/Huber iterations with the same masked-median semantics.
    """
    xx = {}
    for ii in range(nt):
        for jj in range(ii + 1):
            xx[(ii, jj)] = X[ii] * X[jj]

    def chol_solve(G, c):
        # G: dict (i,j)->[1,BP] lower half; c: list of nt [1,BP]
        ok = None
        L = [[None] * nt for _ in range(nt)]
        for ii in range(nt):
            for jj in range(ii + 1):
                sacc = G[(ii, jj)]
                for q in range(jj):
                    sacc = sacc - L[ii][q] * L[jj][q]
                if ii == jj:
                    pos = sacc > 0
                    ok = pos if ok is None else ok & pos
                    L[ii][jj] = jnp.sqrt(jnp.maximum(sacc, 1e-30))
                else:
                    L[ii][jj] = sacc / L[jj][jj]
        yv = [None] * nt
        for ii in range(nt):
            sacc = c[ii]
            for q in range(ii):
                sacc = sacc - L[ii][q] * yv[q]
            yv[ii] = sacc / L[ii][ii]
        xv = [None] * nt
        for ii in reversed(range(nt)):
            sacc = yv[ii]
            for q in range(ii + 1, nt):
                sacc = sacc - L[q][ii] * xv[q]
            xv[ii] = sacc / L[ii][ii]
        nan = jnp.float32(jnp.nan)
        return [jnp.where(ok, v, nan) for v in xv]

    def solve(wt):
        # wt: list of nb [W, BP] weight planes -> beta[b] = list of nt [1,BP]
        betas = []
        for b in range(nb):
            G = {}
            for ii in range(nt):
                for jj in range(ii + 1):
                    G[(ii, jj)] = jnp.sum(wt[b] * xx[(ii, jj)], 0,
                                          keepdims=True) \
                        + (1e-9 if ii == jj else 0.0)
            c = [jnp.sum((Y[b] * wt[b]) * X[ii], 0, keepdims=True)
                 for ii in range(nt)]
            betas.append(chol_solve(G, c))
        return betas

    def pred(betas, b):
        acc = betas[b][0] * X[0]
        for c in range(1, nt):
            acc = acc + betas[b][c] * X[c]
        return acc                                            # [W, BP]

    w0 = [wm for _ in range(nb)]
    betas = solve(w0)
    mask = wm > 0
    for _ in range(iters):
        wts = []
        for b in range(nb):
            r = Y[b] - pred(betas, b)
            med = _median_sublane(r, mask, n_pow)
            mad = _median_sublane(jnp.abs(r - med), mask, n_pow)
            sigma = jnp.maximum(mad / 0.6745, 1e-6)
            a = jnp.abs(r) / (huber_k * sigma)
            huber = jnp.where(a <= 1.0, 1.0, 1.0 / jnp.maximum(a, 1e-12))
            wts.append(wm * huber)
        betas = solve(wts)

    bad = None
    for b in range(nb):
        r = jnp.abs(Y[b] - pred(betas, b))
        bb = (r > tmask_const * vario[b:b + 1]) & mask
        bad = bb if bad is None else bad | bb
    return bad


def _tmask_block(xt_ref, y2_ref, w_ref, vario_ref, *refs, nt, nb,
                 n_pow, iters, huber_k, tmask_const, guarded=False):
    """One pixel block of kernel._tmask_bad, all six IRLS solves in VMEM
    (xt [nt,W,BP], y2 [nb,W,BP], w [W,BP] 0/1, vario [nb,BP] -> bad
    [W,BP] int32 0/1)."""
    cnt_ref, bad_ref = (refs if guarded else (None,) + refs)

    def compute():
        bad = _tmask_core([xt_ref[c] for c in range(nt)],
                          [y2_ref[b] for b in range(nb)],
                          w_ref[...], vario_ref[...], nt=nt, nb=nb,
                          n_pow=n_pow, iters=iters, huber_k=huber_k,
                          tmask_const=tmask_const)
        bad_ref[...] = jnp.where(bad, jnp.int32(1), 0)

    # A dead block carries all-zero window masks: bad = (...) & mask is
    # False everywhere, so the zero fill is the exact computed value.
    _when_active(cnt_ref, compute, lambda: _zero_refs(bad_ref))


@functools.partial(jax.jit, static_argnames=("interpret",))
def tmask_bad(Xtw, Y2, w, vario2, *, active=None, interpret=False):
    """Pallas port of kernel._tmask_bad (same contract: [P,W] bool).

    Replaces the six sequential Gram/corr reduces, Cholesky chains, and
    ten masked medians per round — each a separate [P,*]-sized fusion
    paying the profiled per-op floor — with one VMEM-resident pass per
    pixel block.  ``active`` (normally the caller's in_init set) is the
    per-block skip guard.
    """
    P, W, nt = Xtw.shape
    nb = Y2.shape[1]
    BP = tmask_block_p(W)
    Pp = -BP * (-P // BP)
    pad = Pp - P
    n_pow = 1 << max(1, (W - 1).bit_length())

    xt = jnp.pad(Xtw.transpose(2, 1, 0), ((0, 0), (0, 0), (0, pad)))
    y2 = jnp.pad(Y2.transpose(1, 2, 0), ((0, 0), (0, 0), (0, pad)))
    wp = jnp.pad(w.T, ((0, 0), (0, pad)))
    vp = jnp.pad(vario2.T, ((0, 0), (0, pad)), constant_values=1.0)

    kern = functools.partial(
        _tmask_block, nt=nt, nb=nb, n_pow=n_pow,
        iters=int(params.TMASK_IRLS_ITERS),
        huber_k=float(params.HUBER_K),
        tmask_const=float(params.TMASK_CONST),
        guarded=active is not None)
    args = [xt, y2, wp, vp]
    in_specs = [
        pl.BlockSpec((nt, W, BP), lambda i: (0, 0, i)),
        pl.BlockSpec((nb, W, BP), lambda i: (0, 0, i)),
        pl.BlockSpec((W, BP), lambda i: (0, i)),
        pl.BlockSpec((nb, BP), lambda i: (0, i)),
    ]
    if active is not None:
        args.append(_block_counts(active, BP, Pp))
        in_specs.append(_CNT_SPEC)
    out = pl.pallas_call(
        kern,
        grid=(Pp // BP,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((W, BP), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((W, Pp), jnp.int32),
        interpret=interpret,
    )(*args)
    return (out[:, :P] > 0).T


# ---------------------------------------------------------------------------
# Ring remote-copy kernel (cross-device straggler rebalancing).  One ring
# hop of the rebalancing exchange (parallel.mesh): ship a shard-local
# array to the logical neighbor over ICI via an async remote DMA —
# SNIPPETS.md [1]/[2]'s shard_map + make_async_remote_copy template.
# TPU-compiled only: the CPU/simulated-mesh path uses lax.ppermute
# (mesh._ring_shift), which is semantically identical (a fixed
# source→dest permutation along the ring axis).
# ---------------------------------------------------------------------------

def _ring_copy_kernel(dst_ref, x_ref, out_ref, send_sem, recv_sem):
    copy = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=out_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=dst_ref[0],
        device_id_type=pltpu.DeviceIdType.LOGICAL)
    copy.start()
    copy.wait()


def ring_remote_copy(x, dst_index):
    """Ship ``x`` (shard-local, any shape) to logical device
    ``dst_index`` on the ring; returns the buffer received from whichever
    neighbor targeted THIS device (every device along the axis calls
    with its own neighbor, so the exchange is a pure ring rotation).

    The payload stays in HBM (``TPUMemorySpace.ANY``) — the rebalancing
    slabs are MB-scale state trees, not VMEM blocks — and the DMA
    completes before return (start+wait; the overlap the rebalancer
    needs is across payload FIELDS, which jax schedules as independent
    kernels).  ``dst_index`` is a traced scalar (axis_index ± 1), fed
    through scalar prefetch.
    """
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    return pl.pallas_call(
        _ring_copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid_spec=grid_spec,
    )(jnp.asarray(dst_index, jnp.int32).reshape(1), x)
