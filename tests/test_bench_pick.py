"""The autotune pick policy (bench.autotune_parity / autotune_pick) is
decision-gated: a Pallas config that flips any pixel's structural
decisions vs the XLA baseline is demoted regardless of speed
(VERDICT r3 #3 enforcement side).

Pure-function tests — the TPU-only autotune block in bench.measure
composes exactly these, so the policy is provable without hardware.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import autotune_parity, autotune_pick  # noqa: E402


def _outs(n, meta):
    return np.asarray(n), np.asarray(meta)


def _probe(n_pixels=4, flip_day=None, flip_nseg=None, jitter_chprob=False):
    """Baseline-shaped probe output [1, P] / [1, P, 2, 6] with optional
    single-pixel decision flips or a float-only chprob jitter."""
    n = np.full((1, n_pixels), 2, np.int32)
    meta = np.tile(np.arange(12, dtype=np.float32).reshape(1, 1, 2, 6),
                   (1, n_pixels, 1, 1))
    if flip_nseg is not None:
        n = n.copy()
        n[0, flip_nseg] = 1
    if flip_day is not None:
        meta = meta.copy()
        meta[0, flip_day, 0, 2] += 1.0          # bday column
    if jitter_chprob:
        meta = meta.copy()
        meta[..., 3] += 1e-5                    # col 3 is NOT decision-gated
    return _outs(n, meta)


def test_parity_exact_and_flips():
    outs = {"0": _probe(), "monitor,tmask": _probe(),
            "score": _probe(flip_day=1),
            "tmask": _probe(flip_nseg=2),
            "monitor": _probe(jitter_chprob=True)}
    parity, exact = autotune_parity(outs)
    assert exact == {"monitor,tmask": True, "score": False,
                     "tmask": False, "monitor": True}
    assert parity["score"]["decision_agree"] == 0.75
    assert parity["tmask"]["nseg_agree"] == 0.75
    # chprob jitter is invisible to the decision gate but visible to the
    # 2e-4 meta envelope only if it exceeds atol (1e-5 doesn't)
    assert parity["monitor"]["decision_agree"] == 1.0
    assert parity["monitor"]["meta_agree"] == 1.0


def test_single_pixel_flip_gates_even_when_fraction_rounds_to_one():
    """The gate must use the exact predicate: one flipped pixel in 20001
    rounds to decision_agree == 1.0 but still demotes."""
    outs = {"0": _probe(n_pixels=20001), "score": _probe(n_pixels=20001,
                                                        flip_day=7)}
    parity, exact = autotune_parity(outs)
    assert parity["score"]["decision_agree"] == 1.0   # display rounds up
    assert exact["score"] is False                    # gate does not
    pick, demoted, unavailable = autotune_pick(
        {"0": 1.0, "score": 9.9}, {}, exact)
    assert pick == "0" and demoted == ["score"] and not unavailable


def test_fastest_clean_config_wins():
    exact = {"monitor,tmask": True, "score": True, "tmask": False}
    pick, demoted, unavailable = autotune_pick(
        {"0": 1.0, "monitor,tmask": 3.0, "score": 2.0, "tmask": 5.0}, {},
        exact)
    assert pick == "monitor,tmask"
    assert demoted == ["tmask"]
    assert not unavailable


def test_errored_config_excluded_but_not_demoted():
    # 'tmask' errored: rate 0.0, no parity entry -> neither picked nor
    # listed as a decision divergence (it never produced decisions).
    exact = {"monitor,tmask": True}
    pick, demoted, _ = autotune_pick(
        {"0": 1.0, "monitor,tmask": 2.0, "tmask": 0.0},
        {"tmask": "RuntimeError('Mosaic')"}, exact)
    assert pick == "monitor,tmask"
    assert demoted == []


def test_baseline_error_falls_back_to_fastest_measured():
    # '0' probe errored -> no parity evidence at all; the fastest config
    # that actually ran wins and the artifact says parity_unavailable.
    pick, demoted, unavailable = autotune_pick(
        {"0": 0.0, "monitor,tmask": 2.0, "score": 1.0},
        {"0": "RuntimeError('compile hiccup')"}, {})
    assert pick == "monitor,tmask"
    assert demoted == []
    assert unavailable


def test_baseline_ok_all_others_errored_is_not_parity_unavailable():
    # The baseline ran and wins by default; the evidence gap is fully
    # described by the errors dict, so parity_unavailable must NOT be
    # set (it is reserved for 'the baseline probe itself errored').
    pick, demoted, unavailable = autotune_pick(
        {"0": 1.0, "monitor,tmask": 0.0, "score": 0.0},
        {"monitor,tmask": "RuntimeError", "score": "RuntimeError"}, {})
    assert pick == "0"
    assert demoted == []
    assert not unavailable


def test_everything_errored_still_returns_a_pick():
    pick, _, _ = autotune_pick(
        {"0": 0.0}, {"0": "RuntimeError"}, {})
    assert pick == "0"


# ---------------------------------------------------------------------------
# Artifact scrubbing (ISSUE 3 satellite): ANSI escapes stripped, error
# text truncated, before the bench JSON line becomes a round artifact.
# ---------------------------------------------------------------------------

from bench import ERR_TEXT_LIMIT, clean_text, scrub_artifact  # noqa: E402


def test_clean_text_strips_raw_and_repr_escaped_ansi():
    raw = "\x1b[32m INFO\x1b[0m compiling"
    assert clean_text(raw) == " INFO compiling"
    # repr() of a string holding ESC bytes yields literal "\x1b[2m" text —
    # the form BENCH_r05.json actually embedded.
    escaped = r"JaxRuntimeError('\x1b[2m2026-08-02\x1b[0m \x1b[33mWARN\x1b[0m boom')"
    assert "\\x1b[" not in clean_text(escaped)
    assert "boom" in clean_text(escaped)


def test_clean_text_truncates_with_marker():
    s = "e" * 1000
    out = clean_text(s, limit=100)
    assert out.startswith("e" * 100)
    assert out.endswith("...[+900 chars]")
    assert clean_text("short", limit=100) == "short"


def test_scrub_artifact_truncates_error_fields_only():
    rec = {
        "value": 1.5,
        "detail": {
            "note": "n" * 2000,                      # not an error key
            "pallas_autotune": {
                "errors": {"score": "\x1b[31m" + "x" * 5000 + "\x1b[0m"},
            },
            "child": {"tail": "t" * 5000},
            "nested": ["\x1b[2mdim\x1b[0m", 3],
        },
    }
    out = scrub_artifact(rec)
    assert out["value"] == 1.5
    err = out["detail"]["pallas_autotune"]["errors"]["score"]
    assert len(err) < ERR_TEXT_LIMIT + 40 and "\x1b" not in err
    assert len(out["detail"]["child"]["tail"]) < ERR_TEXT_LIMIT + 40
    assert out["detail"]["note"] == "n" * 2000       # non-error text intact
    assert out["detail"]["nested"][0] == "dim"
    assert out["detail"]["nested"][1] == 3


def test_clean_text_strips_doubly_escaped_ansi():
    """BENCH_r05's actual failure mode: the error text passed through
    repr() twice (error -> errors dict -> harness log tail), so the ESC
    byte appears as literal backslash-backslash-x1b — the old
    single-backslash alternation missed it and kilobytes of remote
    compiler log survived into the artifact."""
    once = r"\x1b[2m2026-08-02\x1b[0m WARN boom"
    twice = once.replace("\\", "\\\\")
    thrice = twice.replace("\\", "\\\\")
    for s in (once, twice, thrice):
        out = clean_text(s)
        assert "x1b[" not in out and "boom" in out, s


# ---------------------------------------------------------------------------
# No fallback: without a TPU the bench fails and prints no number.
# ---------------------------------------------------------------------------


def test_bench_exits_nonzero_without_tpu(capsys):
    import bench as bench_mod

    assert bench_mod.main() != 0
    out = capsys.readouterr().out
    assert not [line for line in out.splitlines() if line.startswith("{")]


def test_apply_tune_flag_env_grammar():
    """Every rung the autotune races maps to exactly one FIREBIRD_PALLAS
    value, which the kernel parses: the XLA loop and the component
    lists."""
    from bench import apply_tune_flag
    from firebird_tpu.ccd import kernel

    # apply_tune_flag writes os.environ directly, and monkeypatch.delenv
    # on an ABSENT key registers no undo — snapshot/restore by hand or
    # the last case's env leaks into the whole suite.
    saved = os.environ.get("FIREBIRD_PALLAS")
    cases = {
        "0": set(),
        "monitor": {"monitor"},
        "tmask": {"tmask"},
        "score": {"score"},
        "monitor,tmask": {"monitor", "tmask"},
        "monitor,score,tmask": {"monitor", "score", "tmask"},
    }
    try:
        for flag, comps in cases.items():
            apply_tune_flag(flag)
            assert os.environ["FIREBIRD_PALLAS"] == flag
            assert kernel.pallas_components() == comps, flag
    finally:
        if saved is None:
            os.environ.pop("FIREBIRD_PALLAS", None)
        else:
            os.environ["FIREBIRD_PALLAS"] = saved
