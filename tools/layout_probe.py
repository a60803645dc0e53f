"""On-device layout experiment: [P,T] (time in lanes) vs [T,P] (pixels in
lanes) for the CCD kernel's op mix.

Round-2 traces show [10000,512] ops running at ~75 GB/s effective while a
[4096,4096] elementwise loop hits 438 GB/s on the same chip — hypothesis:
the kernel's convention (T minor = 4 lane tiles) starves the VPU/DMA, and
flipping to [T,P] (P minor = 78 lane tiles) recovers it.  Every timing
runs inside one jitted fori_loop with a data dependency (per-dispatch
latency would otherwise swamp the measurement) and device-gets one
scalar at the end.

Run on TPU: python tools/layout_probe.py
"""

import time

import numpy as np


def dev_ms(make, *arrays, n=100):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(*xs):
        def body(i, c):
            acc = c
            r = make(*xs, i)
            return acc + r
        return lax.fori_loop(0, n, body, jnp.zeros((), jnp.float32))

    np.asarray(run(*arrays))
    t0 = time.time()
    np.asarray(run(*arrays))
    return (time.time() - t0) / n * 1e3


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    P, T, D = 10000, 512, 5
    rng = np.random.default_rng(0)
    y_pt = jnp.asarray(rng.random((D, P, T)), jnp.float32)   # 102 MB
    y_tp = jnp.asarray(rng.random((D, T, P)), jnp.float32)
    x_pt = jnp.asarray(rng.random((P, T)), jnp.float32)      # 20 MB
    x_tp = jnp.asarray(rng.random((T, P)), jnp.float32)
    coefs = jnp.asarray(rng.random((P, D, 8)), jnp.float32)
    coefs_t = jnp.asarray(rng.random((D, 8, P)), jnp.float32)
    X = jnp.asarray(rng.random((T, 8)), jnp.float32)

    print(f"device: {jax.devices()[0].device_kind}")
    rows = []

    rows.append(("elementwise 20MB",
                 dev_ms(lambda x, i: jnp.sum(x * (1.0 + i * 1e-9)), x_pt),
                 dev_ms(lambda x, i: jnp.sum(x * (1.0 + i * 1e-9)), x_tp)))
    rows.append(("reduce over T",
                 dev_ms(lambda x, i: jnp.sum(jnp.sum(x + i, axis=1)), x_pt),
                 dev_ms(lambda x, i: jnp.sum(jnp.sum(x + i, axis=0)), x_tp)))
    rows.append(("any over T (bool)",
                 dev_ms(lambda x, i: jnp.sum(jnp.any(x + i > 1.5, 1).astype(jnp.float32)), x_pt),
                 dev_ms(lambda x, i: jnp.sum(jnp.any(x + i > 1.5, 0).astype(jnp.float32)), x_tp)))
    rows.append(("argmax over T",
                 dev_ms(lambda x, i: jnp.sum(jnp.argmax(x + i, 1).astype(jnp.float32)), x_pt),
                 dev_ms(lambda x, i: jnp.sum(jnp.argmax(x + i, 0).astype(jnp.float32)), x_tp)))
    rows.append(("cumsum over T",
                 dev_ms(lambda x, i: jnp.sum(jnp.cumsum(x + i, 1)[:, -1]), x_pt),
                 dev_ms(lambda x, i: jnp.sum(jnp.cumsum(x + i, 0)[-1, :]), x_tp)))
    rows.append(("cummin rev over T",
                 dev_ms(lambda x, i: jnp.sum(lax.cummin(x + i, axis=1, reverse=True)[:, 0]), x_pt),
                 dev_ms(lambda x, i: jnp.sum(lax.cummin(x + i, axis=0, reverse=True)[0, :]), x_tp)))

    # the monitor score: s = sum_b ((Y - pred)/den)^2 with chip-shared X
    def score_pt(y, c, i):
        pred = jnp.einsum("pbc,tc->bpt", c, X,
                          precision=lax.Precision.HIGHEST)
        return jnp.sum(((y + i) - pred) ** 2)

    def score_tp(y, c, i):
        pred = jnp.einsum("bcp,tc->btp", c, X,
                          precision=lax.Precision.HIGHEST)
        return jnp.sum(((y + i) - pred) ** 2)

    rows.append(("monitor score 102MB",
                 dev_ms(lambda y, c, i: score_pt(y, c, i), y_pt, coefs),
                 dev_ms(lambda y, c, i: score_tp(y, c, i), y_tp, coefs_t)))

    print(f"{'op':24s} {'[P,T] ms':>10s} {'[T,P] ms':>10s} {'speedup':>8s}")
    for name, a, b in rows:
        print(f"{name:24s} {a:10.3f} {b:10.3f} {a / b:7.2f}x")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
