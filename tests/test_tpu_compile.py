"""The device paths compile for a TPU v5e chip, without the chip.

The TPU compiler is installed next to jax and compiles for a chip that
is described (``topologies.get_topology_desc``) rather than attached, so
these tests catch what only Mosaic / the TPU backend refuses -- casts,
loop-carried mask types, VMEM limits -- at the paper's widths (K=50
bucketed to 56, ``block_b=128``) and the ``fig_load`` serving shapes,
on one chip and under the four-chip ``shard_map`` executor.
Nothing runs; results are pinned by the interpret / reference tests.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
module.  The persistent compilation cache is off around these compiles
(an executable for a described chip cannot be read back on this host).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import samplers
from repro.kernels.we_rounds import ops

B, K, R = 1024, 56, 48        # rows, paper K=50 bucketed, drift rounds
N0, THRESHOLD, MAX_ITER = 1e6, 200.0, 100


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo, no_cache):
    """The 1-D ``grid`` mesh the sharded executor builds on a 2x2 host."""
    return Mesh(np.array(topo.devices), ("grid",))


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("variant", ["known", "unknown", "drift", "panel",
                                     "panel_drift"])
def test_we_rounds_kernel_compiles(one_chip, variant):
    """Every ``we_rounds`` launch the pallas backend makes lowers through
    Mosaic to one ``tpu_custom_call``."""
    lam = _on(one_chip, (B, K))
    seed = _on(one_chip, (1, 2), jnp.uint32)
    sched = _on(one_chip, (B, R, K))
    if variant.startswith("panel"):
        fn = ops._jit_kernel_panel(N0, THRESHOLD, 2e4, MAX_ITER, 128, False)
        args = (lam, seed, _on(one_chip, (B, 1)))
        args += (sched,) if variant == "panel_drift" else ()
    else:
        fn = ops._jit_kernel(N0, THRESHOLD, 2e4, variant == "known",
                             MAX_ITER, 128, False)
        args = (lam, seed) + ((sched,) if variant == "drift" else ())
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("drift", [False, True], ids=["stationary", "drift"])
def test_fused_jax_engine_compiles(one_chip, drift):
    """The ``jax`` sampler backend's fused while-loop engine."""
    key = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    args = [_on(one_chip, key.shape, key.dtype), _on(one_chip, (B, K))]
    if drift:
        args.append(_on(one_chip, (B, R, K)))
    eng = samplers._get_jax_engine(drift)
    eng.lower(*args, N0, THRESHOLD, np.inf, False, MAX_ITER).compile()


def test_one_shot_program_compiles(one_chip):
    """The one-shot schemes' device program at the panel's size (8 points
    x 4096 trials x K=50): the rows are repeated, drawn and reduced in
    fused loops, so the cover's K x K compare never lands in memory."""
    G, T, k = 8, 4096, 50
    compiled = ops._jit_one_shot(T, False).lower(
        _on(one_chip, (G, k)), _on(one_chip, (G, k)), _on(one_chip, (G, k)),
        _on(one_chip, (G,)), _on(one_chip, (2,), jnp.uint32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


def test_wide_panel_programs_compile(one_chip):
    """At K = 2,048 (``cluster_k2048``): the fused-panel kernel at the
    tile height ``tile_rows`` picks, and the one-shot program with its
    int32 cover weights at 8 points x 4096 trials."""
    k, rows = 2048, ops.tile_rows(2048)
    fn = ops._jit_kernel_panel(4.096e7, 8192.0, 2e4, MAX_ITER, rows, False)
    hlo = fn.lower(_on(one_chip, (8 * rows, k)), _on(one_chip, (1, 2),
                                                     jnp.uint32),
                   _on(one_chip, (8 * rows, 1))).compile().as_text()
    assert "tpu_custom_call" in hlo
    G, T = 8, 4096
    ops._jit_one_shot(T, False).lower(
        _on(one_chip, (G, k)), _on(one_chip, (G, k)),
        _on(one_chip, (G, k), jnp.int32), _on(one_chip, (G,), jnp.int32),
        _on(one_chip, (2,), jnp.uint32)).compile()


def test_sharded_we_rounds_panel_compiles(four_chips):
    """The fused-panel kernel under the four-chip ``shard_map`` executor:
    one kernel per device on its block of rows."""
    rows = NamedSharding(four_chips, PartitionSpec("grid"))
    fn = ops._jit_sharded(four_chips, N0, THRESHOLD, 2e4, False, MAX_ITER,
                          128, "kernel", panel=True)
    hlo = fn.lower(_on(rows, (4, 2), jnp.uint32), _on(rows, (4 * B, K)),
                   _on(rows, (4 * B, 1))).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("devices", [1, 4], ids=["one_chip", "four_chips"])
def test_serving_scan_compiles(request, devices, monkeypatch):
    """The serving scan at the full ``fig_load`` sweep shapes (K=16, four
    loads x 16 trials, 2000 slots), on one chip and sharded over four:
    the sweep's own host assembly builds the arguments, and the compiled
    sweep is lowered for the chip in place of running it here."""
    from benchmarks import fig_load
    from repro.serving import scan

    if devices == 1:
        sharding = request.getfixturevalue("one_chip")
    else:
        mesh = request.getfixturevalue("four_chips")
        sharding = NamedSharding(mesh, PartitionSpec())
        monkeypatch.setattr(scan, "active_grid_mesh", lambda: mesh)

    class Lowered(Exception):
        pass

    real = scan._compiled_sweep
    seen = []

    def lower_instead(static):
        fn = real(static)

        def call(*args):
            seen.append(fn.lower(*[
                _on(sharding, np.shape(a), np.asarray(a).dtype)
                for a in args]).compile())
            raise Lowered
        return call

    monkeypatch.setattr(scan, "_compiled_sweep", lower_instead)
    spec = fig_load.experiment()
    het = spec.grid.specs()[0]
    with pytest.raises(Lowered):
        scan.scan_sweep(het, "work_exchange", {}, spec.serving, spec.N,
                        spec.trials, spec.seed, 0)
    assert len(seen) == 1
