"""Compile each cell's device programs for a described TPU v5e, without
the chip.

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse [cell ...]

For every cell of ``BENCHMARK.json`` (or the ones named), on one chip of
a described ``v5e:2x2`` and, where the cell asks for four, on its 1-D
four-chip mesh: the cell's calls are built from its data files, and the
program's jitted device entry is lowered and compiled for the described
chip in place of running.  What the TPU compiler refuses shows here at
no chip time.  A cell with the work-exchange pair compiles the fused
``we_rounds`` panel kernel at its stacked rows; a cell with an MDS
scheme compiles the MDS sweep's Gamma-row programs at the shapes the
scheme's own host code builds for them.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class Lowered(Exception):
    """Raised in place of running a program that was compiled."""


def _described(devices: int):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if devices == 1:
        return None, SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(np.array(topo.devices[:devices]), ("grid",))
    return mesh, NamedSharding(mesh, PartitionSpec("grid"))


def _shape(sharding, a):
    import jax
    import numpy as np
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def rehearse_batch_mc(cell, devices: int) -> list:
    """The fused known/unknown panel kernel at the cell's rows."""
    import numpy as np
    from chipbench import workloads
    from repro.kernels.we_rounds import ops
    c = cell.config
    mesh, sharding = _described(devices)
    lam = workloads.het_rates(c)
    K = lam.shape[1]
    Kb = K if K <= 16 else -(-K // 8) * 8
    rows = 2 * lam.shape[0] * cell.trials
    rows = -(-rows // (128 * devices)) * 128 * devices
    ex = c["exchange"]
    args = dict(n0=float(c["N"]),
                threshold=ex["threshold_frac"] * c["N"] / K,
                cap=float(np.ceil(ex["storage_cap_frac"] * c["N"] / K)),
                max_iter=int(c["max_iterations"]))
    lam_s = _shape(sharding, np.zeros((rows, Kb), np.float32))
    flags = _shape(sharding, np.zeros((rows, 1), np.float32))
    if mesh is None:
        fn = ops._jit_kernel_panel(args["n0"], args["threshold"],
                                   args["cap"], args["max_iter"], 128, False)
        seed = _shape(sharding, np.zeros((1, 2), np.uint32))
        compiled = fn.lower(lam_s, seed, flags).compile()
    else:
        fn = ops._jit_sharded(mesh, args["n0"], args["threshold"],
                              args["cap"], False, args["max_iter"], 128,
                              "kernel", panel=True)
        seed = _shape(sharding, np.zeros((devices, 2), np.uint32))
        compiled = fn.lower(seed, lam_s, flags).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return [(f"we_rounds panel kernel {rows}x{Kb}", compiled)]


def rehearse_mds(cell, devices: int) -> list:
    """The MDS scheme's Gamma-row programs: the scheme runs on the CPU,
    and each program it dispatches is also compiled for the chip."""
    import numpy as np
    from repro.kernels.we_rounds import ops
    _, sharding = _described(devices)
    real = ops._jit_gamma_rows
    out = []

    def compile_too(boost):
        fn = real(boost)

        def call(*args):
            out.append((f"mds gamma rows {tuple(args[1].shape)}",
                        fn.lower(*[_shape(sharding, a)
                                   for a in args]).compile()))
            return fn(*args)
        return call

    ops._jit_gamma_rows = compile_too
    try:
        from repro.core.schemes import get_scheme
        spec = cell.spec(1)
        get_scheme("mds", **cell.scheme_params("mds")).mc_grid(
            spec.grid.specs(), spec.N, spec.trials,
            np.random.default_rng(1), backend=cell.traffic["backend"])
    finally:
        ops._jit_gamma_rows = real
    return out


def main(argv=None) -> int:
    from chipbench import bench, workloads
    spec = bench.load_benchmark()
    names = list(argv if argv is not None else sys.argv[1:])
    failed = 0
    for wl in spec["workloads"]:
        if names and wl["name"] not in names:
            continue
        cell = workloads.Cell(bench.load_config(wl["config"]),
                              bench.load_traffic(wl["traffic"]))
        steps = []
        if "work_exchange" in cell.schemes:
            steps.append(rehearse_batch_mc)
        if "mds" in cell.schemes:
            steps.append(rehearse_mds)
        for devices in sorted({1, int(wl["chips"])}):
            try:
                for what, compiled in (r for step in steps
                                       for r in step(cell, devices)):
                    mem = compiled.memory_analysis()
                    print(f"{wl['name']} x{devices}: {what}: compiled, "
                          f"temp {getattr(mem, 'temp_size_in_bytes', '?')}"
                          f" B, args "
                          f"{getattr(mem, 'argument_size_in_bytes', '?')} B")
            except Exception as e:   # report every cell, then fail
                failed += 1
                print(f"{wl['name']} x{devices}: FAILED {type(e).__name__}:"
                      f" {e}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
