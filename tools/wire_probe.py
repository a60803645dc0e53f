"""Wire-diet regression probe (`make wire-smoke`).

Runs ONE staged batch end to end on CPU and asserts the wire contract
that ISSUE 11 put in place (docs/ROOFLINE.md "Wire budget"):

1. **Ingress is all-integer.**  Every plane `driver.core.stage_batch`
   puts on the device is int16/uint16/uint8/int32 — no float ingress.
   The float design matrices / date grid / validity mask must be built
   on device (`kernel.device_designs`), never shipped.
2. **Egress is int-coded.**  `kernel.pack_egress` of the batch result
   yields integer-dtyped tables only, fetched as slot buffers to the
   observed segment depth, and `format.decode_egress` round-trips them BIT-EXACTLY to
   the raw f32 result.
3. **The counters move.**  `wire_h2d_bytes` / `wire_d2h_bytes` record
   the staged/drained volume, and the packed egress is measurably
   smaller than the raw f32 drain.

Writes the JSON artifact to `$FIREBIRD_WIRE_DIR/wire_smoke.json`
(bench.py folds it into round artifacts) and exits nonzero on any
violation, so a future change that quietly re-floats the wire fails CI.
"""

import json
import os
import sys

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, HERE)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from firebird_tpu.ccd import format as ccdformat
    from firebird_tpu.ccd import kernel
    from firebird_tpu.config import env_knob
    from firebird_tpu.driver import core as dcore
    from firebird_tpu.ingest import SyntheticSource, pack
    from firebird_tpu.ingest.packer import PackedChips
    from firebird_tpu.obs import metrics as obs_metrics

    failures: list[str] = []
    report: dict = {"ok": False}

    obs_metrics.reset_registry()
    src = SyntheticSource(seed=5, start="1995-01-01", end="1998-01-01",
                          cloud_frac=0.1, change_frac=0.5)
    p = pack([src.chip(100, 200), src.chip(3100, 200)], bucket=32)
    p = PackedChips(cids=p.cids, dates=p.dates,
                    spectra=p.spectra[:, :, :128, :],
                    qas=p.qas[:, :128, :], n_obs=p.n_obs)

    # ---- 1. ingress: every staged plane is integer ----
    staged = dcore.stage_batch(p, jnp.float32, "off")
    names = ("days", "n_obs", "spectra", "qa")
    planes = {}
    for name, a in zip(names, staged.args):
        planes[name] = {"dtype": str(a.dtype), "bytes": int(a.nbytes)}
        if jnp.dtype(a.dtype).kind not in "iu":
            failures.append(f"float ingress plane {name!r}: {a.dtype}")
    report["ingress_planes"] = planes
    report["h2d_bytes"] = int(sum(a.nbytes for a in staged.args))

    # ---- 2. egress: int-coded tables, bit-exact decode ----
    seg = kernel.detect_packed(p, dtype=jnp.float32, staged=staged.args)
    raw = jax.device_get(seg)
    worst = int(np.asarray(raw.n_segments).max())
    s_eff = kernel.egress_bucket(worst, raw.seg_meta.shape[-2])
    tables = jax.device_get(kernel.egress_slots(kernel.pack_egress(seg),
                                                s_eff))
    leaves = {k: jax.tree_util.tree_leaves(v) for k, v in tables.items()}
    for name, vs in leaves.items():
        for v in vs:
            if v.dtype.kind not in "iu":
                failures.append(f"float egress table {name!r}: {v.dtype}")
    report["egress_tables"] = {k: {"dtype": str(vs[0].dtype),
                                   "bytes": int(sum(v.nbytes for v in vs))}
                               for k, vs in leaves.items()}
    dec = ccdformat.decode_egress(tables, raw.mask.shape[-1])
    for f in ("n_segments", "procedure", "mask", "vario", "rounds",
              "round_counts", "occupancy", "compactions"):
        a, b = getattr(raw, f), getattr(dec, f)
        if (a is None) != (b is None) or (
                a is not None and not np.array_equal(np.asarray(a),
                                                     np.asarray(b))):
            failures.append(f"decode mismatch on {f}")
    for f in ("seg_meta", "seg_rmse", "seg_mag", "seg_coef"):
        a = np.asarray(getattr(raw, f))[:, :, :s_eff]
        if not np.array_equal(a, np.asarray(getattr(dec, f))):
            failures.append(f"decode mismatch on {f}")

    # ---- 3. the bytes and the counters, through the PRODUCTION drain ----
    # fetch_results is the routing the drivers actually take (knob check,
    # f32 gate, packed fetch, counter, transfer span, decode) — drive it
    # so a regression there fails the smoke, not just the unit tests.
    os.environ["FIREBIRD_WIRE_EGRESS"] = "1"
    drained = dcore.fetch_results(seg)
    if np.asarray(drained.seg_meta).dtype != np.float32:
        failures.append("fetch_results did not return decoded f32 arrays")
    if not np.array_equal(np.asarray(drained.n_segments),
                          np.asarray(raw.n_segments)):
        failures.append("fetch_results packed drain changed n_segments")
    d2h_raw = int(sum(np.asarray(v).nbytes
                      for v in jax.tree_util.tree_leaves(raw)))
    d2h_packed = int(sum(v.nbytes for vs in leaves.values() for v in vs))
    report["d2h_bytes_raw_f32"] = d2h_raw
    report["d2h_bytes_packed"] = d2h_packed
    report["d2h_cut"] = round(d2h_raw / max(d2h_packed, 1), 2)
    report["egress_depth"] = int(s_eff)
    if d2h_packed >= d2h_raw:
        failures.append("packed egress is not smaller than the raw drain")
    snap = obs_metrics.get_registry().snapshot()["counters"]
    report["counters"] = {k: snap.get(k, 0)
                          for k in ("wire_h2d_bytes", "wire_d2h_bytes")}
    if snap.get("wire_h2d_bytes", 0) <= 0:
        failures.append("wire_h2d_bytes counter did not move")
    d2h_counted = snap.get("wire_d2h_bytes", 0)
    if not 0 < d2h_counted < d2h_raw:
        failures.append(
            f"wire_d2h_bytes ({d2h_counted}) did not record a packed "
            f"drain smaller than the raw result ({d2h_raw})")

    report["ok"] = not failures
    if failures:
        report["failures"] = failures
    outdir = env_knob("FIREBIRD_WIRE_DIR")
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, "wire_smoke.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    if failures:
        print(f"wire-smoke FAILED ({path}):", file=sys.stderr)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    print(f"wire-smoke OK: h2d {report['h2d_bytes']} B all-integer, "
          f"d2h {d2h_raw} -> {d2h_packed} B "
          f"({report['d2h_cut']}x cut at depth {s_eff}), "
          f"decode bit-exact ({path})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
