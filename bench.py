"""Headline benchmark: CCDC pixels/sec on TPU vs the 2000-core Spark baseline.

Protocol (BASELINE.md): the reference publishes no absolute numbers, so the
baseline is measured — the per-pixel CPU implementation's rate (the NumPy
oracle standing in for pinned lcmap-pyccd's ccd.detect, same spec) scaled by
the reference's "runs on 2000 cores" claim (README.rst:11).  The TPU number
is the steady-state kernel rate on a batch of full 100x100 chips with a
realistic ~20-year archive.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

One process holds the chip and measures.  There is no fallback: without a
TPU the bench exits non-zero and prints no number.
"""

import functools
import json
import os
import re
import sys
import time

# ANSI escape sequences in raw (ESC byte) AND arbitrarily re-escaped
# forms: autotune errors pass through repr() — sometimes more than once
# (error -> repr in the errors dict -> json.dumps -> the harness's
# log-tail capture), so the ESC byte shows up as "\x1b[2m", "\\x1b[2m",
# and deeper.  The single-backslash alternation of the first fix missed
# the double-escaped form, which is how a round-5 artifact ended up with
# kilobytes of escaped compiler log inside its error fields (and a JSON
# line too large for the harness tail to parse).  `\\+` eats any escape
# depth.
_ANSI_RE = re.compile(r"(?:\x1b|\\+x1b|\\+u001b|\\+033)\[[0-9;]*[A-Za-z]")
_ERR_KEYS = frozenset(
    {"error", "errors", "tail", "traceback", "exception", "stderr"})
# Matches the autotune error budget (safe_rate): a Mosaic failure's real
# error often sits past char 600 behind the remote-compile banner, and the
# artifact must stay diagnosable on its own.
ERR_TEXT_LIMIT = 1200


def clean_text(s: str, limit: int | None = None) -> str:
    """Strip ANSI escapes; optionally truncate with an honest marker."""
    s = _ANSI_RE.sub("", s)
    if limit is not None and len(s) > limit:
        s = s[:limit] + f"...[+{len(s) - limit} chars]"
    return s


def scrub_artifact(obj, limit: int | None = None):
    """Sanitize a bench record before it becomes a round artifact: every
    string loses its ANSI escapes, and strings under error-carrying keys
    (_ERR_KEYS, applied to the whole subtree) are truncated to
    ERR_TEXT_LIMIT chars — exception text is for diagnosis, not a
    terminal-log archive, and multi-KB escaped blobs break casual ``jq``
    use of the artifacts."""
    if isinstance(obj, dict):
        return {k: scrub_artifact(
            v, limit=ERR_TEXT_LIMIT
            if isinstance(k, str) and k.lower() in _ERR_KEYS else limit)
            for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [scrub_artifact(v, limit=limit) for v in obj]
    if isinstance(obj, str):
        return clean_text(obj, limit)
    return obj

# Autotune probe-failure classes (ordered; first match wins): a raced
# Pallas config that fails to compile can carry a kilobytes-long compiler
# log with the real cause buried mid-stream — round 5 shipped raw
# JaxRuntimeError reprs for compiler SIGABRTs.
# classify_tune_error turns each into a short structured record so the
# bench tail stays diagnosable AND parseable.
_TUNE_ERR_KINDS = (
    ("sigabrt", "compiler-crash (tpu_compile_helper SIGABRT)"),
    ("exit signal", "compiler-crash (tpu_compile_helper killed)"),
    ("exit code", "compiler-crash (tpu_compile_helper nonzero exit)"),
    ("resource_exhausted", "resource-exhausted"),
    ("vmem", "vmem-exhausted"),
    ("mosaic", "mosaic-lowering-error"),
    ("deadline", "deadline"),
    ("timeout", "timeout"),
)


def classify_tune_error(e) -> dict:
    """One failed autotune probe -> ``{variant-diagnosable record}``:
    the exception class, a classified ``kind`` (_TUNE_ERR_KINDS; a
    SIGABRT'd compile lands as compiler-crash), and a
    short ANSI-stripped ``detail`` — never the raw multi-KB repr."""
    txt = clean_text(repr(e))
    low = txt.lower()
    kind = next((label for needle, label in _TUNE_ERR_KINDS
                 if needle in low), "other")
    return {"class": type(e).__name__, "kind": kind,
            "detail": clean_text(txt, limit=300)}


# Pinned baseline denominator (VERDICT r4 weak #5: the live-measured CPU
# reference rate moved 34% between capture hosts, making vs_baseline
# incomparable across rounds).  This is the canonical measured rate of
# the per-pixel reference implementation — the NumPy oracle standing in
# for pinned lcmap-pyccd's ccd.detect — captured in round 2 on the real
# TPU harness host (BASELINE.md "Pinned denominator").  All vs_baseline
# figures are computed against THIS constant; the live host's measured
# rate is still reported alongside (cpu_ref_pixels_per_sec_per_core_live)
# so drift stays visible without moving the yardstick.
PINNED_CPU_REF_PIXELS_PER_SEC_PER_CORE = 4.88
PINNED_BASELINE_2000_CORES = PINNED_CPU_REF_PIXELS_PER_SEC_PER_CORE * 2000.0


def autotune_parity(probe_outs):
    """Compiled-mode parity of each raced Pallas config vs the '0' XLA
    baseline on the probe chip (Mosaic lowering, real hardware — the
    evidence the interpret-mode CPU suite can't give).

    ``probe_outs`` maps config flag -> (n_segments [C,P], seg_meta
    [C,P,S,6]) host arrays.  Returns ``(parity, decision_exact)``:
    parity[flag] reports nseg_agree (fraction of pixels with identical
    segment counts), decision_agree (additionally requiring the
    day-valued/qa/nobs meta columns 0,1,2,4,5 equal on every segment
    row), and meta_agree (the historical 2e-4 envelope, kept for
    cross-round comparability).  decision_exact[flag] is the EXACT
    all-pixels predicate — the gate must never use the display-rounded
    fraction, which hides single-pixel flips once the probe exceeds
    10k pixels.
    """
    import numpy as np

    parity, decision_exact = {}, {}
    if "0" not in probe_outs:
        return parity, decision_exact
    n0, m0 = probe_outs["0"]
    for flag, (n1, m1) in probe_outs.items():
        if flag == "0":
            continue
        dec = ((n0 == n1)
               & (m0[..., [0, 1, 2, 4, 5]]
                  == m1[..., [0, 1, 2, 4, 5]]).all(-1).all(-1))
        decision_exact[flag] = bool(dec.all())
        parity[flag] = {
            "nseg_agree": round(float((n0 == n1).mean()), 4),
            "decision_agree": round(float(dec.mean()), 4),
            "meta_agree": round(float(
                np.isclose(m0, m1, atol=2e-4)
                .all(-1).all(-1).mean()), 4)}
    return parity, decision_exact


def autotune_pick(rates, errors, decision_exact):
    """Decision-gated autotune pick: a config that flips ANY pixel's
    structural decisions vs the XLA baseline on real hardware is
    demoted — speed never buys back a broken bit-identical contract.
    (CPU interpret-mode tests pin the same equality; this is the
    compiled-Mosaic enforcement.)

    Error-skipped configs are NOT "demoted" (they have no parity entry
    because they never ran) — in the decision-gated branch they drop out
    simply because they have no ``decision_exact`` entry; ``errors`` is
    consulted only in the no-parity fallback.  If the baseline probe itself errored
    there is no parity evidence at all: fall back to the fastest
    measured config and flag parity_unavailable, rather than pinning the
    bench to the one config that demonstrably failed.

    Returns ``(pick, demoted, parity_unavailable)``.
    """
    if decision_exact:
        eligible = [k for k in rates
                    if k == "0" or decision_exact.get(k, False)]
        demoted = sorted(k for k, ok in decision_exact.items() if not ok)
        return max(eligible, key=lambda k: rates[k]), demoted, False
    eligible = [k for k in rates if k not in errors] or list(rates)
    # parity_unavailable means the BASELINE probe produced no decisions
    # to compare against ('0' errored) — not merely that every non-
    # baseline config errored while the baseline itself ran and won
    # (there the errors dict already tells the whole story).
    return (max(eligible, key=lambda k: rates[k]), [], "0" in errors)


def apply_tune_flag(flag: str) -> None:
    """One autotune rung -> the env it means: the FIREBIRD_PALLAS value
    ('0' for the XLA loop, else a component list).  Shared by the probes
    and the final pick so the timed run executes exactly the raced
    configuration."""
    os.environ["FIREBIRD_PALLAS"] = flag


def _fleet_obs_fold() -> dict:
    """{"fleet_obs_report": ...} for the rolling soak directory when a
    driver run left a report there — the merged fleet view under
    multi-host runs, a single process's report otherwise.  Empty dict
    (not an error) when no soak run exists on this host."""
    import os

    soak_dir = os.environ.get("FIREBIRD_SOAK_DIR", "/tmp/fb_soak")
    try:
        from firebird_tpu.obs.report import load_fleet_report

        rep = load_fleet_report(soak_dir)
    except Exception:
        return {}
    if rep is None:
        return {}
    # The full document would dwarf the bench artifact; keep the
    # operator-relevant identity + scale block, plus the deep-dive
    # verdicts: the SLO evaluation and the device time of any profile
    # windows the run captured (obs/profiling.py: busy, idle share, idle
    # under the dispatch thread's waits).
    prof = rep.get("profile") or {}
    return {"fleet_obs_report": {
        "run": rep.get("run", {}),
        "fleet": rep.get("fleet"),
        "counters": rep.get("metrics", {}).get("counters", {}),
        "run_counters": rep.get("run_counters", {}),
        "slo": rep.get("slo"),
        "profile": {"windows": len(prof.get("windows", ())),
                    "device_time": prof.get("device_time")},
    }}


def _artifact_fold(key: str, env_var: str, filename: str) -> dict:
    """{key: ...} when a smoke/soak tool left its JSON artifact on this
    host (under env_var's directory, default from config.KNOBS) —
    per-round evidence folded into the bench record.  Empty dict (not an
    error) when the tool never ran or the artifact is unreadable."""
    import os

    from firebird_tpu.config import env_knob

    path = os.path.join(env_knob(env_var), filename)
    try:
        with open(path) as f:
            return {key: json.load(f)}
    except (OSError, ValueError):
        return {}


def _chaos_fold() -> dict:
    """`make chaos-smoke` evidence (tools/chaos_soak.py): the robustness
    round's store-identity-under-faults report."""
    return _artifact_fold("chaos_report", "FIREBIRD_CHAOS_DIR",
                          "chaos_report.json")


def _compact_fold() -> dict:
    """`make compact-smoke` evidence (tools/compact_smoke.py): the
    on-vs-off store-identity + wasted-lane-round report."""
    return _artifact_fold("compact_smoke", "FIREBIRD_COMPACT_DIR",
                          "compact_smoke.json")


def _serve_fold() -> dict:
    """Serving-layer loadtest evidence (tools/serve_loadtest.py, run by
    `make serve-smoke`): RPS, p50/p95/p99, cache hit rate.  The
    multi-replica fleet artifact (`make serve-fleet`: aggregate RPS,
    304/hit rates, max observed staleness vs the changefeed bound)
    folds next to it when one ran."""
    out = _artifact_fold("serve_loadtest", "FIREBIRD_SERVE_DIR",
                         "serve_loadtest.json")
    out.update(_artifact_fold("serve_fleet_loadtest", "FIREBIRD_SERVE_DIR",
                              "serve_fleet_loadtest.json"))
    return out


def _pyramid_fold() -> dict:
    """`make pyramid-smoke` evidence (tools/pyramid_smoke.py): base
    tiles byte-identical to products.save rasters, surgical ancestor
    invalidation through the changefeed, and the ETag 304->200 flip."""
    return _artifact_fold("pyramid_smoke", "FIREBIRD_PYRAMID_DIR",
                          "pyramid_smoke.json")


def _lint_fold() -> dict:
    """`make lint` evidence (firebird_tpu.analysis): the static contract
    checker's summary — clean flag, per-rule counts, baselined and
    suppressed totals (docs/STATIC_ANALYSIS.md)."""
    return _artifact_fold("lint_report", "FIREBIRD_LINT_DIR",
                          "lint_report.json")


def _fleet_fold() -> dict:
    """`make fleet-smoke` evidence (tools/fleet_chaos.py): the queue's
    kill/partition drill — jobs drained, stale-fence rejections, and the
    merged-store row-identity verdict."""
    return _artifact_fold("fleet_chaos", "FIREBIRD_FLEET_DIR",
                          "fleet_chaos.json")


def _elastic_fold() -> dict:
    """`make elastic-smoke` evidence (tools/elastic_soak.py): the
    726-tile elastic drill — peak/ceiling worker counts, kills +
    partition + supervisor-restart chaos tallies, orphan adoptions,
    store row-identity, the scale-to-zero verdict, and the supervisor's
    scale-decision log."""
    return _artifact_fold("elastic_soak", "FIREBIRD_ELASTIC_DIR",
                          "elastic_soak.json")


def _postmortem_fold() -> dict:
    """`make postmortem-smoke` evidence (tools/postmortem_smoke.py): the
    flight recorder's SIGTERM'd-run bundle validity + row-identical
    resume report."""
    return _artifact_fold("postmortem_smoke", "FIREBIRD_POSTMORTEM_DIR",
                          "postmortem_smoke.json")


def _alert_fold() -> dict:
    """`make alert-smoke` evidence (tools/alert_soak.py): exactly-once
    alerting through SIGKILL + resume, webhook cursor catch-up, repair
    drain, and the evaluated alert_freshness SLO."""
    return _artifact_fold("alert_soak", "FIREBIRD_ALERT_DIR",
                          "alert_soak.json")


def _streamfleet_fold() -> dict:
    """`make streamfleet-smoke` evidence (tools/stream_fleet_soak.py):
    the standing watcher+worker fleet drill — scenes drained through
    watcher/worker SIGKILLs, alerts exactly-once, the packed statestore
    byte-identical to a clean serial leg, and the evaluated end-to-end
    acquisition -> alert freshness SLO."""
    return _artifact_fold("stream_fleet_soak", "FIREBIRD_STREAMFLEET_DIR",
                          "stream_fleet_soak.json")


def _telemetry_fold() -> dict:
    """`make telemetry-smoke` evidence (tools/telemetry_smoke.py): one
    scene's causal chain collected across >=4 OS processes (including a
    SIGKILLed worker's recovered spool) with the per-alert critical-path
    breakdown agreeing with the measured acquisition_to_alert_seconds."""
    return _artifact_fold("telemetry_smoke", "FIREBIRD_TELEMETRY_SMOKE_DIR",
                          "telemetry_smoke.json")


def _slo_fold() -> dict:
    """`make slo-smoke` evidence (tools/slo_smoke.py): the black-box
    canary catching an injected serve brownout and watcher stall, the
    multi-window burn verdict tripping inside its deadline, the durable
    budget-event transitions, and metric history surviving a SIGKILLed
    serving process plus a prober restart."""
    return _artifact_fold("slo_smoke", "FIREBIRD_SLO_DIR",
                          "slo_smoke.json")


def _fanout_fold() -> dict:
    """`make fanout-smoke` evidence (tools/fanout_loadtest.py): the
    fanout plane's scale proof — registration rate, audience-resolution
    latency flat across subscriber milestones, the (subscriber, alert)
    pair census exactly-once through a worker SIGKILL, and the
    per-shard-job completion p50/p99 vs the fanout_p99 budget leg
    (docs/ALERTS.md "Fanout plane")."""
    return _artifact_fold("fanout_loadtest", "FIREBIRD_FANOUT_DIR",
                          "fanout_loadtest.json")


def _objectstore_fold() -> dict:
    """`make objectstore-smoke` evidence (tools/objectstore_chaos.py):
    the chunked conditional-put protocol, 3-way store parity, stale
    object fences rejected with a durable census, torn-upload recovery,
    and the SIGKILL-mid-upload / orphan-scrub legs
    (docs/ROBUSTNESS.md "Object tier")."""
    return _artifact_fold("objectstore_chaos", "FIREBIRD_OBJECTSTORE_DIR",
                          "objectstore_chaos.json")


def _acquisition_freshness_block() -> dict:
    """``acquisition_to_alert_p95`` promoted NEXT TO the e2e block: the
    read-side headline is pixels/sec including transfer; the streaming
    product's headline is how many seconds after a scene publishes its
    alerts are durable (docs/STREAMING.md)."""
    sf = _streamfleet_fold().get("stream_fleet_soak") or {}
    if sf.get("acquisition_to_alert_p95") is None:
        return {}
    return {"acquisition_to_alert_p95": {
        "metric": "acquisition_to_alert_seconds",
        "stat": "p95",
        "value_sec": sf["acquisition_to_alert_p95"],
        "observations": sf.get("acquisition_to_alert_count"),
        "slo": sf.get("slo"),
        "source": "stream_fleet_soak",
    }}


def _wire_fold() -> dict:
    """`make wire-smoke` evidence (tools/wire_probe.py): the staged
    ingress planes proven all-integer and the egress tables int-coded,
    with the measured bytes-on-wire cut."""
    return _artifact_fold("wire_smoke", "FIREBIRD_WIRE_DIR",
                          "wire_smoke.json")


def previous_round_e2e(here: str) -> dict | None:
    """The newest committed TPU evidence artifact's end-to-end figure —
    the denominator of the headline regression gate.  Scans
    docs/BENCH_tpu_evidence_r*.json newest-round first for a
    ``pixels_per_sec_incl_transfer``; returns {value, source} or None
    (no evidence yet — the gate reports 'no previous round')."""
    import glob
    import os

    paths = sorted(glob.glob(os.path.join(
        here, "docs", "BENCH_tpu_evidence_r*.json")))
    for p in reversed(paths):
        try:
            with open(p) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        det = rec.get("detail")
        v = det.get("pixels_per_sec_incl_transfer") \
            if isinstance(det, dict) else None
        if isinstance(v, (int, float)) and v > 0:
            return {"value": float(v), "source": os.path.basename(p)}
    return None


def measure() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from firebird_tpu.ccd import detect as cpu_detect
    from firebird_tpu.ccd import kernel
    from firebird_tpu.ingest import SyntheticSource, pack, pixel_timeseries
    from firebird_tpu.obs import metrics as obs_metrics

    # ---- workload: full chips, ~20-year archive (T ~ 460 obs) ----
    small = "--small" in sys.argv
    n_devices = 1 if small else jax.local_device_count()
    use_mesh = n_devices > 1
    if use_mesh:
        n_chips, runs = n_devices, 1
    else:
        # 8 full chips/dispatch on the accelerator: the event loop's round
        # count is shared across the vmapped chip axis, so a bigger batch
        # amortizes per-round fixed costs (~2.3 GB wire + widened data,
        # comfortable in 16 GB HBM).
        n_chips, runs = 8, 3
    src = SyntheticSource(seed=7, start="1985-01-01", end="2005-01-01",
                          cloud_frac=0.15)
    chips = [src.chip(100 + 3000 * i, 200) for i in range(n_chips)]
    packed = pack(chips, bucket=64)
    n_pixels = packed.n_chips * 10000
    fdtype = jnp.float32

    def device_args(pk):
        # The all-integer wire tuple (kernel.wire_args): int32 days +
        # counts, int16 spectra, uint8 QA — the float designs build on
        # device inside the jitted prologue (kernel.device_designs).
        return tuple(jnp.asarray(a) for a in kernel.wire_args(pk))

    # ---- Pallas component auto-tune (accelerator only) ----
    # The monitor, score and tmask components each have an XLA and a
    # Pallas VMEM-resident implementation (FIREBIRD_PALLAS; f32-on-TPU
    # only).  Which is faster depends on the toolchain, so time them on
    # a small probe chip and keep the winner for the full run.  The flag
    # is read at trace time, and the cache between variants is cleared so
    # each probe really compiles its own path; a Pallas crash just keeps
    # the default.
    pallas_detail = {}
    if not small:
        import functools as _ft
        import os as _os

        # Probe on one FULL chip: a pixel-sliced probe under-weights the
        # HBM terms the Pallas kernels exist to cut (per-op floors
        # dominate small shapes), mispredicting the full-shape winner.
        probe = pack([chips[0]], bucket=64)

        # One transfer for all variants: clear_caches() drops compiled
        # programs, not device arrays, so the probe chip ships once.
        probe_args = device_args(probe)
        jax.block_until_ready(probe_args)

        probe_outs = {}

        def probe_rate(flag: str) -> float:
            apply_tune_flag(flag)
            jax.clear_caches()
            f = _ft.partial(kernel._detect_batch_wire, dtype=jnp.float32,
                            wcap=kernel.window_cap(probe),
                            sensor=probe.sensor)
            np.asarray(f(*probe_args).n_segments)        # compile + warmup
            t0 = time.time()
            for _ in range(2):
                # device_get: see timed_rate
                seg_p = f(*probe_args)
                np.asarray(seg_p.n_segments)
            dt = time.time() - t0
            # Keep each config's decisions: every probe runs the same
            # chip, so diffing against the '0' baseline afterwards is
            # free COMPILED-mode parity evidence (the CPU tests cover
            # interpret mode only — Mosaic is a different lowering).
            probe_outs[flag] = (np.asarray(seg_p.n_segments),
                               np.asarray(seg_p.seg_meta))
            return 2.0 / dt

        rates = {}

        errors = {}

        # Autotune deadline: the race is worth at most half the run's
        # budget (FIREBIRD_BENCH_BUDGET seconds, default 45 min) — a
        # slow Mosaic compile must degrade to fewer raced configs, never
        # to a killed run with no JSON line at all.
        deadline = time.time() + 0.5 * float(
            _os.environ.get("FIREBIRD_BENCH_BUDGET", "2700"))

        def safe_rate(flag: str) -> float:
            if time.time() > deadline and rates:
                errors[flag] = {"class": "Skipped", "kind": "deadline",
                                "detail": "autotune deadline reached "
                                          "before this variant raced"}
                print(f"[autotune] {flag}: skipped (deadline)",
                      file=sys.stderr, flush=True)
                return 0.0
            try:
                rates[flag] = probe_rate(flag)
            except Exception as e:
                rates[flag] = 0.0
                # Classified short record, not the raw repr: a Mosaic
                # remote-compile SIGABRT arrives as kilobytes of escaped
                # terminal log, which r05 let straight into the bench
                # tail.  The failing variant is recorded and the race
                # continues — a crashed config never takes the pick.
                errors[flag] = classify_tune_error(e)
            # Partial evidence on stderr after every probe: if a later
            # variant hangs past the run's time limit (first Mosaic
            # compile of the big kernels), the log still shows every
            # rate measured so far.
            print(f"[autotune] {flag}: {rates[flag]:.3f} runs/s"
                  + (f" (error: {errors[flag]})" if flag in errors else ""),
                  file=sys.stderr, flush=True)
            return rates[flag]

        # Per-component tuning: each Pallas kernel races the default
        # alone, then the individually-winning set races as a combo —
        # a component that loses on this toolchain can't drag down the
        # ones that win (kernel.pallas_components).
        base = safe_rate("0")
        winners = [c for c in ("monitor", "tmask", "score")
                   if safe_rate(c) > base]
        # Keys are canonicalized (sorted join) so set-equal configs are
        # never probed twice — the component list is order-insensitive.
        combo = ",".join(sorted(winners))
        if len(winners) > 1 and combo not in rates:
            safe_rate(combo)
        parity, decision_exact = autotune_parity(probe_outs)
        pick, demoted, parity_unavailable = autotune_pick(
            rates, errors, decision_exact)
        pallas_detail = {"pallas_autotune": {
            "runs_per_sec": {k: round(v, 3) for k, v in rates.items()},
            "picked": pick,
            **({"decision_demoted": demoted} if demoted else {}),
            **({"parity_unavailable": True} if parity_unavailable else {}),
            **({"probe_parity_vs_xla": parity} if parity else {}),
            **({"errors": errors} if errors else {})}}
        apply_tune_flag(pick)
        jax.clear_caches()

    def timed_rate(run_fn, run_args, pixels, n_runs):
        """Steady-state pixels/sec: compile+warmup run, then timed runs.

        Each timed run fetches n_segments to the host (device_get): a
        host materialization cannot complete before the program has.  The
        fetched array is [C,P] int32 (~40 KB/chip) — negligible against
        the kernel time being measured.
        """
        t0_ = time.time()
        seg_ = run_fn(*run_args)
        np.asarray(seg_.n_segments)
        # First-call (compile+run) time feeds the obs registry so the
        # bench artifact's obs snapshot carries compile evidence; the
        # timed loop below stays untouched.
        obs_metrics.histogram("kernel_first_call_seconds").observe(
            time.time() - t0_)
        t0_ = time.time()
        for _ in range(n_runs):
            seg_ = run_fn(*run_args)
            np.asarray(seg_.n_segments)
        return pixels * n_runs / (time.time() - t0_), seg_

    # ---- device kernel rate ----
    # Steady-state, device-resident: production keeps the device fed by
    # prefetch (driver/core.py double-buffers ingest), so the kernel rate
    # is measured on resident arrays; the host->device wire transfer is
    # timed separately and reported in detail.
    wcap = kernel.window_cap(packed)
    if use_mesh:
        from firebird_tpu.parallel import make_mesh
        from firebird_tpu.parallel import mesh as pmesh

        m = make_mesh()
        t0 = time.time()
        args = pmesh.shard_packed(packed, m, fdtype)
        jax.block_until_ready(args)
        run_fn = pmesh.sharded_detect_fn(m, jnp.dtype(fdtype), wcap,
                                         packed.sensor)
    else:
        t0 = time.time()
        args = device_args(packed)
        jax.block_until_ready(args)
        run_fn = functools.partial(kernel._detect_batch_wire,
                                   dtype=fdtype, wcap=wcap,
                                   sensor=packed.sensor)
    t_xfer = time.time() - t0
    wire_mb = sum(a.nbytes for a in args) / 1e6

    dev_rate, seg = timed_rate(run_fn, args, n_pixels, runs)
    e2e_serial = n_pixels / (n_pixels / dev_rate + t_xfer)

    # ---- pipelined e2e: transfer OVERLAPPED with compute ----
    # The serial figure charges the full wire to every batch back to
    # back; the production loop (driver detect_chunk) stages batch i+1
    # on the prefetch thread while batch i computes, so steady state is
    # bounded by max(transfer, compute), not their sum.  Measure the
    # overlap for real — a 2-deep software pipeline over FRESH
    # host->device transfers against live dispatches — and make the
    # measured number the headline e2e.
    import concurrent.futures as _cf

    if use_mesh:
        stage_fn = lambda: jax.block_until_ready(
            pmesh.shard_packed(packed, m, fdtype))
    else:
        stage_fn = lambda: jax.block_until_ready(device_args(packed))
    pipe_runs = max(runs, 2)
    with _cf.ThreadPoolExecutor(max_workers=1) as _stage_ex:
        nxt = _stage_ex.submit(stage_fn)
        t0 = time.time()
        for i in range(pipe_runs):
            cur = nxt.result()
            nxt = _stage_ex.submit(stage_fn) if i + 1 < pipe_runs else None
            np.asarray(run_fn(*cur).n_segments)   # device_get: timed_rate
        e2e_pipelined = n_pixels * pipe_runs / (time.time() - t0)
    e2e_rate = max(e2e_pipelined, e2e_serial)

    # ---- steady-state drain: bulk vs per-chip egress (ISSUE 3) ----
    # The driver's drain is now one jax.device_get of the whole batched
    # result + one vectorized batch_frames pass; time it against the old
    # per-chip chip_slice/chip_frames loop on the same result so the
    # before/after is measured on THIS host, and fold the bulk number
    # into pipeline_drain_seconds so the obs snapshot carries it.
    pipeline_detail = {}
    wire_detail = {}
    if not small:
        from firebird_tpu.ccd import format as ccdformat

        t0 = time.time()
        host_seg = jax.device_get(seg)
        drain_fetch_s = time.time() - t0
        t0 = time.time()
        ccdformat.batch_frames(packed, host_seg, packed.n_chips)
        drain_fmt_s = time.time() - t0
        t0 = time.time()
        for c in range(packed.n_chips):
            ccdformat.chip_frames(
                packed, c, kernel.chip_slice(seg, c, to_host=True))
        drain_per_chip_s = time.time() - t0
        # Int-coded egress (the d2h wire diet, kernel.pack_egress):
        # pack on device to int tables, one buffer per segment slot,
        # fetch the slots to the observed segment depth, decode — bytes
        # + wall vs the raw f32 fetch above.  The decoded result is
        # store-row identical (the golden test in tests/test_wire.py);
        # here we report the wire cut.
        d2h_raw = int(sum(v.nbytes
                          for v in jax.tree_util.tree_leaves(seg)))
        worst = int(np.asarray(seg.n_segments).max())
        s_eff = kernel.egress_bucket(worst, host_seg.seg_meta.shape[-2])
        jax.block_until_ready(kernel.pack_egress(seg))  # compile
        t0 = time.time()
        tables = jax.device_get(kernel.egress_slots(kernel.pack_egress(seg),
                                                    s_eff))
        ccdformat.decode_egress(tables, host_seg.mask.shape[-1])
        drain_packed_s = time.time() - t0
        d2h_packed = int(sum(v.nbytes
                             for v in jax.tree_util.tree_leaves(tables)))
        obs_metrics.histogram("pipeline_drain_seconds").observe(
            drain_fetch_s + drain_fmt_s)
        pipeline_detail = {"pipeline": {
            "steady_state_batch_seconds": round(n_pixels / dev_rate, 4),
            "drain_bulk_seconds": round(drain_fetch_s + drain_fmt_s, 4),
            "drain_bulk_fetch_seconds": round(drain_fetch_s, 4),
            "drain_bulk_format_seconds": round(drain_fmt_s, 4),
            "drain_per_chip_seconds": round(drain_per_chip_s, 4),
            "drain_packed_fetch_decode_seconds": round(drain_packed_s, 4),
        }}
        # The per-batch wire budget (docs/ROOFLINE.md "Wire budget"):
        # what actually crosses h2d (all-integer staged planes) and d2h
        # (int-coded depth-sliced tables vs the raw f32 result).  The
        # before-diet h2d is RECONSTRUCTED from the shapes (the r05-era
        # staging: f32 Xs[C,T,8]+Xts[C,T,5]+dates[C,T], bool valid,
        # int16 spectra, uint16 QA) so total_cut compares two real
        # states, not a post-diet h2d against a pre-diet d2h.
        h2d = int(sum(a.nbytes for a in args))
        C_, T_ = np.asarray(args[0]).shape
        n_px_qa = int(np.asarray(args[3]).size)
        h2d_before = (C_ * T_ * (8 + 5 + 1) * 4 + C_ * T_
                      + int(args[2].nbytes) + 2 * n_px_qa)
        wire_detail = {"wire": {
            "h2d_bytes": h2d,
            "h2d_bytes_before_diet": h2d_before,
            "h2d_planes": {"days_i32": int(args[0].nbytes),
                           "n_obs_i32": int(args[1].nbytes),
                           "spectra_i16": int(args[2].nbytes),
                           "qa": int(args[3].nbytes)},
            "d2h_bytes_raw_f32": d2h_raw,
            "d2h_bytes_packed": d2h_packed,
            "d2h_cut": round(d2h_raw / max(d2h_packed, 1), 2),
            "egress_depth": int(s_eff),
            "total_bytes": h2d + d2h_packed,
            "total_bytes_before_diet": h2d_before + d2h_raw,
            "total_cut": round((h2d_before + d2h_raw)
                               / max(h2d + d2h_packed, 1), 2),
        }}

    # ---- occupancy: padded vs effective lane-rounds (docs/ROOFLINE.md
    # "Occupancy") ----  The kernel's per-round (active, paid) capture,
    # fed through the registry (kernel_round_active_fraction + the
    # wasted/compaction counters land in the obs snapshot below) and
    # embedded per round so artifacts show what compaction saved.
    occupancy_detail = {}
    occ_det = kernel.record_occupancy(seg)
    if occ_det is not None:
        occupancy_detail = {"occupancy": occ_det}

    # ---- closed-form FLOP model -> MFU / roofline (docs/ROOFLINE.md) ----
    from firebird_tpu.ccd import flops as flopsmod

    rc = getattr(seg, "round_counts", None)
    phase_rounds = (tuple(np.asarray(rc).reshape(-1, 3).mean(0))
                    if rc is not None else None)
    roofline = flopsmod.bench_detail(
        pixels_per_sec=dev_rate, P=n_pixels,
        T=int(packed.spectra.shape[-1]), W=wcap,
        S=int(np.asarray(seg.seg_meta).shape[-2]),
        rounds=float(np.asarray(seg.rounds).mean()),
        device_kind=jax.devices()[0].device_kind,
        dtype_bytes=jnp.dtype(fdtype).itemsize, sensor=packed.sensor,
        phase_rounds=phase_rounds,
        # Model the picked FIREBIRD_PALLAS config's actual streams (the
        # autotune sets the env before the timed run); wire int16 = 2 B.
        pallas=kernel.pallas_components(), wire_bytes=2)

    # ---- rebalance: straggler-idle model + what the ring moved ----
    # Per-device round counts bound the idle a perfect balancer could
    # reclaim (each shard's chips all report their loop's count); the
    # lanes_migrated field is present exactly when FIREBIRD_REBALANCE
    # armed the ring for this dispatch.
    lm = getattr(seg, "lanes_migrated", None)
    rebalance_block = {"rebalance": {
        "enabled": lm is not None,
        **flopsmod.rebalance_detail(
            np.asarray(seg.rounds).reshape(-1), n_pixels / dev_rate,
            int(np.asarray(lm).sum()) if lm is not None else 0)}}

    # ---- CPU per-pixel rate (the pyccd stand-in), extrapolated ----
    sample = 12
    rng = np.random.default_rng(0)
    pix = rng.integers(0, 10000, sample)
    t0 = time.time()
    for p_ in pix:
        cpu_detect(**pixel_timeseries(packed, 0, int(p_)))
    cpu_rate = sample / (time.time() - t0)

    # ---- streaming incremental rate (BASELINE.json config #4) ----
    from firebird_tpu.ccd import incremental

    st = incremental.StreamState.from_chip(kernel.chip_slice(seg, 0))
    anchor = float(packed.dates[0][0])
    last = int(packed.n_obs[0]) - 1
    t_new = float(packed.dates[0][last]) + 16.0
    x_row = jnp.asarray(incremental.design_row(t_new, anchor))
    y_new = jnp.asarray(packed.spectra[0, :, :, last].T.astype(np.float32))
    qa_new = jnp.asarray(packed.qas[0, :, last].astype(np.int32))
    st = incremental.step(st, x_row, y_new, qa_new, t_new)   # compile
    np.asarray(st.nobs)
    sruns = 20
    t0 = time.time()
    for _ in range(sruns):
        st = incremental.step(st, x_row, y_new, qa_new, t_new)
    np.asarray(st.nobs)                          # device_get: see timed_rate
    stream_rate = 10000 * sruns / (time.time() - t0)

    # ---- Sentinel-2 12-band rate (BASELINE.json config #5) ----
    # One 300x300-px 10 m chip (9x Landsat pixel density, 12 bands, no
    # thermal); the minimal --small run skips it.
    s2_detail = {}
    if not small:
        from firebird_tpu.ccd.sensor import SENTINEL2

        s2_src = SyntheticSource(seed=11, start="2019-01-01",
                                 end="2021-01-01",
                                 cloud_frac=0.15, sensor=SENTINEL2)
        s2 = pack([s2_src.chip(100, 200)], bucket=64)
        s2_pixels = s2.spectra.shape[2]
        # device-resident, same methodology as the Landsat rate above
        args2 = device_args(s2)
        jax.block_until_ready(args2)
        run2 = functools.partial(kernel._detect_batch_wire, dtype=fdtype,
                                 wcap=kernel.window_cap(s2),
                                 sensor=s2.sensor)
        s2_rate, _ = timed_rate(run2, args2, s2_pixels, 3)
        s2_detail = {
            "sentinel2_pixels_per_sec": round(s2_rate, 1),
            "sentinel2_pixels": int(s2_pixels),
            "sentinel2_obs_per_pixel": int(s2.n_obs[0]),
        }

    # ---- break-dense / gap-dense rung (VERDICT r2 #6) ----
    # Real tiles break: rounds — and both roofline ceilings — scale with
    # segment count, so the friendly 1-change headline can't be the only
    # number.  This rung stacks 3 well-separated step changes on 60% of
    # the area and drops ~70% of winter acquisitions (seasonal gaps), and
    # reports its own px/s + measured rounds + mean segments alongside.
    hard_detail = {}
    if not small:
        hard_src = SyntheticSource(
            seed=23, start="1985-01-01",
            end="2005-01-01",
            cloud_frac=0.15, change_frac=0.6, n_changes=3,
            seasonal_gap_frac=0.7)
        hard_chips = [hard_src.chip(100 + 3000 * i, 200)
                      for i in range(n_chips)]
        hardp = pack(hard_chips, bucket=64)
        hard_pixels = hardp.n_chips * 10000
        argsh = device_args(hardp)
        jax.block_until_ready(argsh)
        runh = functools.partial(kernel._detect_batch_wire, dtype=fdtype,
                                 wcap=kernel.window_cap(hardp),
                                 sensor=hardp.sensor)
        hard_rate, hseg = timed_rate(runh, argsh, hard_pixels, 3)
        hrc = np.asarray(hseg.round_counts).reshape(-1, 3).mean(0)
        hard_detail = {
            "breakdense_pixels_per_sec": round(hard_rate, 1),
            "breakdense_mean_segments": float(
                np.asarray(hseg.n_segments).mean()),
            "breakdense_rounds": int(np.asarray(hseg.rounds)[0]),
            "breakdense_phase_rounds": {
                "init": round(float(hrc[0]), 1),
                "fit": round(float(hrc[1]), 1),
                "close": round(float(hrc[2]), 1)},
            "breakdense_obs_per_pixel": int(hardp.n_obs[0]),
        }

    # ---- RF inference rate (BASELINE.json config #3) ----
    # Same 500-tree forest on every platform (randomforest.py:38) so the
    # number is comparable across bench runs.
    from firebird_tpu.rf import forest

    rngf = np.random.default_rng(1)
    Xf = rngf.normal(0, 1, (2000, 33)).astype(np.float32)
    yf = rngf.integers(1, 9, 2000)
    model = forest.train(Xf, yf)
    Xq = rngf.normal(0, 1, (10000, 33)).astype(np.float32)
    np.asarray(model.raw_predict(Xq))          # compile + warmup
    rf_runs = 5
    t0 = time.time()
    for _ in range(rf_runs):
        np.asarray(model.raw_predict(Xq))
    rf_rate = Xq.shape[0] * rf_runs / (time.time() - t0)

    baseline_2000_cores = PINNED_BASELINE_2000_CORES
    # ---- the HEADLINE end-to-end metric + its regression gate ----
    # r05's lesson: the kernel rate (66.3k px/s) said nothing about the
    # system (334 px/s including transfer).  pixels_per_sec_incl_transfer
    # is therefore promoted to a top-level block gated against the last
    # committed TPU evidence round; kernel-only `value` stays for
    # cross-round comparison.
    import os as _os_e2e

    prev = previous_round_e2e(
        _os_e2e.path.dirname(_os_e2e.path.abspath(__file__)))
    e2e_block = {
        "metric": "ccdc_pixels_per_sec_incl_transfer",
        "value": round(e2e_rate, 1),
        "pipelined": round(e2e_pipelined, 1),
        "serial": round(e2e_serial, 1),
    }
    if prev is None:
        e2e_block["regression_gate"] = "no previous round evidence"
    else:
        e2e_block["previous_round"] = prev
        e2e_block["vs_previous_round"] = round(
            e2e_rate / max(prev["value"], 1e-9), 3)
        # 10% tolerance absorbs host-bandwidth jitter between sessions;
        # anything lower flags the round as a regression.
        e2e_block["regression_ok"] = bool(e2e_rate >= 0.9 * prev["value"])
    out = {
        "metric": "ccdc_pixels_per_sec",
        "value": round(dev_rate, 1),
        "unit": "pixels/sec",
        "vs_baseline": round(dev_rate / baseline_2000_cores, 3),
        "e2e": e2e_block,
        # The streaming product's headline metric, side by side with
        # the batch read-side one: scene publish -> durable alert p95
        # from the last stream-fleet soak on this host (empty when the
        # soak never ran).
        **_acquisition_freshness_block(),
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": n_devices,
            "chips": packed.n_chips,
            "obs_per_pixel": int(packed.n_obs[0]),
            "wire_mb": round(wire_mb, 1),
            "transfer_sec": round(t_xfer, 3),
            "pixels_per_sec_incl_transfer": round(e2e_rate, 1),
            "pixels_per_sec_incl_transfer_serial": round(e2e_serial, 1),
            "pixels_per_sec_incl_transfer_pipelined":
                round(e2e_pipelined, 1),
            **wire_detail,
            "kernel_rounds": int(np.asarray(seg.rounds)[0]),
            "roofline": roofline,
            # Physics check: a measured rate above the closed-form compute
            # ceiling means the timing is broken, not the kernel fast.
            # (Ceiling only exists for known TPU kinds; CPU rungs skip it.)
            "timing_sane": bool(
                dev_rate <= 1.2 * roofline["compute_bound_pixels_per_sec"])
            if "compute_bound_pixels_per_sec" in roofline else None,
            "cpu_ref_pixels_per_sec_per_core":
                PINNED_CPU_REF_PIXELS_PER_SEC_PER_CORE,
            "cpu_ref_pixels_per_sec_per_core_live": round(cpu_rate, 2),
            "baseline_2000_core_pixels_per_sec": round(baseline_2000_cores, 1),
            "mean_segments": float(np.asarray(seg.n_segments).mean()),
            **occupancy_detail,
            **rebalance_block,
            **pipeline_detail,
            **pallas_detail,
            # Per-run telemetry fold (obs_report schema's metrics half):
            # first-call/compile latencies recorded by timed_rate above.
            "obs": obs_metrics.get_registry().snapshot(),
            # Fleet view of the rolling soak run when one exists on this
            # host: prefer the merged multi-host obs_report over any
            # single process's shard (obs.report.load_fleet_report).
            **_fleet_obs_fold(),
            # Last chaos-smoke evidence (faults absorbed, store equality
            # after resume) when a run left its artifact on this host.
            **_chaos_fold(),
            # Last fleet-smoke evidence (SIGKILL/partition drill: queue
            # drained, zero stale-fence writes accepted) when one ran.
            **_fleet_fold(),
            # Last elastic-smoke evidence (726-tile autoscaled drain
            # with supervisor kill/adopt chaos + the scale-decision
            # log) when one ran on this host.
            **_elastic_fold(),
            # Last serve-loadtest evidence (read-path RPS/latency/hit
            # rate) when the serving layer was exercised on this host,
            # plus the multi-replica fleet artifact when one ran.
            **_serve_fold(),
            # Last pyramid-smoke evidence (base-tile byte identity,
            # surgical changefeed invalidation, ETag flip).
            **_pyramid_fold(),
            # Last wire-smoke evidence (all-integer ingress, int-coded
            # egress, measured bytes-on-wire cut) when the probe ran.
            **_wire_fold(),
            # Last compact-smoke evidence (stores identical on vs off,
            # wasted lane-rounds reduced) when one ran on this host.
            **_compact_fold(),
            # Last `make lint` summary (contract-checker clean flag +
            # per-rule counts) when the linter ran on this host.
            **_lint_fold(),
            # Last postmortem-smoke evidence (SIGTERM'd run leaves a
            # valid flight-recorder bundle + row-identical resume).
            **_postmortem_fold(),
            # Last alert-smoke evidence (exactly-once alerting through
            # SIGKILL, webhook catch-up, repair drain, freshness SLO).
            **_alert_fold(),
            # Last streamfleet-smoke evidence (standing watcher+worker
            # fleet through SIGKILLs: scenes drained exactly-once,
            # packed statestore byte-identity, acquisition->alert SLO).
            **_streamfleet_fold(),
            # Last telemetry-smoke evidence (one scene's causal chain
            # collected across >=4 OS processes incl. a SIGKILLed
            # worker's spool; critical-path breakdown vs measured
            # acquisition_to_alert agreement).
            **_telemetry_fold(),
            # Last slo-smoke evidence (black-box canary vs injected
            # serve brownout + watcher stall; burn verdict trip time,
            # durable budget events, history through SIGKILL/restart).
            **_slo_fold(),
            # Last objectstore-smoke evidence (chunked-publish protocol,
            # 3-way store parity, durable stale-fence census, torn
            # uploads recovered, SIGKILL-mid-upload invisibility +
            # orphan scrub).
            **_objectstore_fold(),
            # Last fanout-smoke evidence (quadkey audience resolution
            # flat across subscriber milestones, exactly-once pair
            # census through a fanout-worker SIGKILL, shard-job
            # completion p99 vs the fanout_p99 budget leg).
            **_fanout_fold(),
            "streaming_pixels_per_sec": round(stream_rate, 1),
            **s2_detail,
            **hard_detail,
            "rf_inference_segments_per_sec": round(rf_rate, 1),
        },
    }
    print(json.dumps(scrub_artifact(out)))


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU (jax sees {dev.platform}); nothing measured",
              file=sys.stderr)
        return 1
    from firebird_tpu.driver.core import setup_compile_cache

    setup_compile_cache()       # persist compiles across bench runs
    measure()
    return 0


if __name__ == "__main__":
    sys.exit(main())
