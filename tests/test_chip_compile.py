"""Compile for a described TPU v5e: the main step and the Pallas kernels.

Nothing here runs on a chip.  The TPU compiler is installed, so it can
compile for a chip that is described (``topologies.get_topology_desc``)
and not attached; what it refuses here — a misaligned block, more VMEM
than a kernel may use, a program that does not fit HBM — it would refuse
on the chip, at the cost of chip time.  Each kernel is compiled with
``interpret=False`` at the shapes the real-width main step hands it.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports every test file.  Keep all such compiles in this one file.
"""

import functools
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from firebird_tpu.ccd import kernel, pallas_ops  # noqa: E402
from firebird_tpu.config import Config  # noqa: E402
from firebird_tpu.driver import core  # noqa: E402

ACQUIRED = "1985-01-01/2017-12-31"     # the whole archive: T=512
HBM_BYTES = 16 * 2 ** 30               # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _production_tracing(monkeypatch):
    """Trace as the chip does: x64 off (the suite's conftest turns it on
    for the float64 oracle) and no persistent cache (a TPU executable
    written here can never be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("FIREBIRD_PALLAS", raising=False)
    was_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was_cache)
    compilation_cache.reset_cache()


def _main_avatars(sharding=None):
    """The default driver batch at real width: predict_batch_shape's
    (C, T, wcap) x 10,000 px x 7 bands, wire dtypes."""
    cfg = Config(chips_per_batch=Config.chips_per_batch,
                 device_sharding="off")
    C, T, wcap = core.predict_batch_shape(cfg, ACQUIRED)
    sensor = kernel.LANDSAT_ARD
    shapes = ((C, T), (C,), (C, sensor.n_bands, sensor.pixels, T),
              (C, sensor.pixels, T))
    return tuple(jax.ShapeDtypeStruct(s, d, sharding=sharding)
                 for s, d in zip(shapes, core.wire_avatar_dtypes())), wcap


@pytest.mark.parametrize("compact", [
    False,
    # The default (compaction on) compiles here in ~255 s at ~17 GB of
    # host memory (PR 21), too heavy for tier-1's shared workers.
    pytest.param(None, marks=pytest.mark.slow, id="default"),
])
def test_main_step_compiles_and_fits_one_chip(compact, one_chip):
    """The program the driver dispatches on a chip (donated wire inputs,
    XLA routes) compiles for a v5e at (C=8, T=512, wcap=48) x 10,000 px,
    and its own memory analysis fits one chip's HBM."""
    avatars, wcap = _main_avatars(one_chip)
    assert [a.shape for a in avatars][2] == (8, 7, 10000, 512)
    assert wcap == 48
    compiled = kernel._detect_batch_wire_donated.lower(
        *avatars, dtype=jnp.dtype(jnp.float32), wcap=wcap,
        sensor=kernel.LANDSAT_ARD, max_segments=kernel.MAX_SEGMENTS,
        compact=compact).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"main step memory_analysis: args {m.argument_size_in_bytes} "
          f"out {m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
          f"alias {m.alias_size_in_bytes} -> {total} of {HBM_BYTES}")
    assert 0 < total < HBM_BYTES


# Which route each kernel is traced under, and the route's env ("score"
# supersedes "monitor", so the plain chain needs a route without it).
KERNEL_ROUTES = {
    "monitor_chain": {"FIREBIRD_PALLAS": "monitor,tmask"},
    "tmask_bad": {"FIREBIRD_PALLAS": "monitor,tmask"},
    "monitor_chain_scored": {"FIREBIRD_PALLAS": "score"},
}


def _avatar(x, sharding):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return x


@functools.lru_cache(maxsize=None)
def _captured_calls(route: tuple) -> dict:
    """Trace (never run) the real-width main step under one route and
    record the FIRST call of every Pallas kernel: per-chip argument
    shapes/dtypes and static keywords, exactly as the kernel sees them."""
    calls = {}
    originals = {name: getattr(pallas_ops, name) for name in KERNEL_ROUTES}

    def recorder(name):
        def call(*args, **kw):
            calls.setdefault(name, (
                jax.tree_util.tree_map(lambda x: _avatar(x, None), args),
                jax.tree_util.tree_map(lambda x: _avatar(x, None), kw)))
            return originals[name](*args, **kw)
        return call

    old_env = {k: os.environ.get(k) for k, _ in route}
    try:
        os.environ.update(dict(route))
        for name in originals:
            setattr(pallas_ops, name, recorder(name))
        avatars, wcap = _main_avatars()
        jax.clear_caches()      # routes are read at trace time
        jax.eval_shape(functools.partial(
            kernel._detect_batch_wire, dtype=jnp.dtype(jnp.float32),
            wcap=wcap, sensor=kernel.LANDSAT_ARD,
            max_segments=kernel.MAX_SEGMENTS), *avatars)
    finally:
        for name, fn in originals.items():
            setattr(pallas_ops, name, fn)
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return calls


@pytest.mark.parametrize("name", sorted(KERNEL_ROUTES))
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    """Each Pallas kernel, compiled (interpret=False) by the v5e compiler
    at the shapes the real-width main step passes it."""
    calls = _captured_calls(tuple(sorted(KERNEL_ROUTES[name].items())))
    assert name in calls, f"route {KERNEL_ROUTES[name]} never called {name}"
    args, kw = calls[name]
    args = jax.tree_util.tree_map(lambda a: _avatar(a, one_chip), args)
    kw = jax.tree_util.tree_map(lambda a: _avatar(a, one_chip), kw)
    kw["interpret"] = False
    fn = getattr(pallas_ops, name)
    lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args, **kw)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def test_ring_remote_copy_compiles_on_4_chip_mesh(topo):
    """The rebalancing ring's remote DMA, as RebalanceSpec's rdma hop
    issues it inside shard_map, compiled for a 2x2 v5e mesh."""
    from firebird_tpu.parallel.mesh import RebalanceSpec

    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("data",))
    spec = RebalanceSpec(axis="data", n=4, rdma=True)
    part = PartitionSpec("data")
    fn = jax.jit(jax.shard_map(
        lambda x: spec.to_right(x), mesh=mesh, in_specs=part,
        out_specs=part, check_vma=False))
    x = jax.ShapeDtypeStruct((4 * 8, 2048), jnp.float32,
                             sharding=NamedSharding(mesh, part))
    compiled = fn.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
