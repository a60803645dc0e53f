"""Zero-stall pipeline smoke test (``make pipeline-smoke``).

Runs a tiny end-to-end changedetection on CPU with the full steady-state
pipeline on — prefetch-thread input staging, bulk batch egress, and the
persistent compile cache — TWICE:

run 1 (cold)
    Asserts the obs report carries every driver stage histogram
    (fetch/pack/stage/dispatch/drain/d2h, obs.report.DRIVER_STAGE_
    HISTOGRAMS) with nonzero counts, the h2d/d2h byte counters moved, and
    the compile cache directory gained entries (misses recorded).
run 2 (warm)
    Same run after ``jax.clear_caches()`` (in-memory compiled programs
    dropped, persistent cache kept): asserts ``compile_cache_hits > 0``
    in the report — the second run of the same shape skipped XLA.

Exits non-zero on any violation — the CI-greppable proof that the
zero-stall loop's staging/egress instrumentation wires through and that
the persistent compile cache actually warms repeat runs.
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)

ACQ = "1995-01-01/1996-06-01"


def run_once(cfg, src, label: str) -> dict:
    from firebird_tpu.driver import core

    done = core.changedetection(x=100, y=200, acquired=ACQ, number=2,
                                chunk_size=2, cfg=cfg, source=src)
    if len(done) != 2:
        raise SystemExit(f"pipeline-smoke: {label} processed "
                         f"{len(done)}/2 chips")
    with open(os.path.join(os.path.dirname(cfg.store_path),
                           "obs_report.json")) as f:
        return json.load(f)


def main() -> int:
    import jax

    from firebird_tpu.config import Config
    from firebird_tpu.ingest import SyntheticSource
    from firebird_tpu.obs import report as obs_report

    with tempfile.TemporaryDirectory(prefix="fb_pipe_smoke_") as tmp:
        cache = os.path.join(tmp, "compile_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cfg = Config(store_backend="sqlite",
                     store_path=os.path.join(tmp, "smoke.db"),
                     source_backend="synthetic", chips_per_batch=1,
                     device_sharding="off", fetch_retries=0,
                     pipeline_depth=2)
        src = SyntheticSource(seed=9, start="1995-01-01", end="1998-01-01",
                              cloud_frac=0.1)

        rep1 = run_once(cfg, src, "run 1")
        hists = rep1["metrics"]["histograms"]
        missing = [k for k in obs_report.DRIVER_STAGE_HISTOGRAMS
                   if hists.get(k, {}).get("count", 0) < 1]
        if missing:
            print(f"pipeline-smoke: run-1 report missing stage histograms "
                  f"{missing}", file=sys.stderr)
            return 1
        counters = rep1["metrics"]["counters"]
        for c in ("wire_h2d_bytes", "wire_d2h_bytes", "store_rows_written"):
            if counters.get(c, 0) <= 0:
                print(f"pipeline-smoke: run-1 counter {c!r} did not move "
                      f"(counters: {counters})", file=sys.stderr)
                return 1
        if not os.listdir(cache):
            print("pipeline-smoke: compile cache directory is empty after "
                  "run 1", file=sys.stderr)
            return 1

        # Run 2: drop the in-memory compiled programs so every compile
        # must go back through the persistent cache (separate processes
        # in production; clear_caches() is the in-process equivalent).
        jax.clear_caches()
        rep2 = run_once(cfg, src, "run 2")
        hits = rep2["metrics"]["counters"].get("compile_cache_hits", 0)
        if hits <= 0:
            print("pipeline-smoke: run 2 recorded no compile-cache hits "
                  f"(counters: {rep2['metrics']['counters']})",
                  file=sys.stderr)
            return 1

        occ = rep2["metrics"]["gauges"].get("pipeline_inflight")
        print("pipeline-smoke OK: "
              f"{len(hists)} histograms, "
              f"h2d {counters['wire_h2d_bytes']} B, "
              f"d2h {counters['wire_d2h_bytes']} B, "
              f"run-2 compile-cache hits {hits}, "
              f"final in-flight gauge {occ}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
