"""Distributed correctness on forced multi-device host meshes.

jax pins the device count at first init, so these tests run pinned
subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8.
They verify:
  * sharded-vs-single-device train step equivalence (GSPMD correctness of
    our spec rules),
  * MoE all-to-all dispatch == scatter dispatch numerics,
  * cache spec / param spec trees are structurally valid for every arch.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> dict:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, sys
        sys.path.insert(0, %r)
        import jax, dataclasses
        import jax.numpy as jnp
        import numpy as np
    """ % os.path.join(REPO, "src")) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    res = _run("""
        from repro.configs import get_config, smoke_config, resolve_for_tp
        from repro.distributed import sharding as shd
        from repro.models import build_model
        from repro.optim import AdamW
        from repro.train.loop import make_train_step
        from jax.sharding import PartitionSpec as P

        cfg = dataclasses.replace(
            smoke_config(get_config("phi4-mini-3.8b")), dtype="float32",
            d_model=64, n_heads=4, head_dim=16, n_kv_heads=2)
        cfg = resolve_for_tp(cfg, 2)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        opt = AdamW(lr=1e-3)
        opt_state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}
        step = make_train_step(model, opt, mode="scan", remat=True)

        # single device reference
        p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pspecs = shd.param_specs(cfg, jax.eval_shape(lambda: params), tp=2)
        ospecs = shd.opt_specs(cfg, None, pspecs)
        bspecs = shd.batch_specs(batch, ("data",))
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                                 out_shardings=(pspecs, ospecs, None))(
                params, opt_state, batch)
        diff = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                          "max_param_diff": diff}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 2e-4, res
    assert res["max_param_diff"] < 2e-3, res


def test_moe_a2a_matches_scatter():
    res = _run("""
        from repro.configs import get_config, smoke_config
        from repro.models import moe as moe_mod
        from jax.sharding import PartitionSpec as P

        cfg = dataclasses.replace(
            smoke_config(get_config("qwen3-moe-30b-a3b")), dtype="float32",
            d_model=32, n_experts=8, experts_per_token=2, d_ff=16,
            capacity_factor=8.0)
        key = jax.random.key(1)
        p = moe_mod.moe_init(key, cfg)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)

        ref, aux_ref = jax.jit(
            lambda p, x: moe_mod.moe_apply_scatter(p, cfg, x))(p, x)

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            out, aux = jax.jit(
                lambda p, x: moe_mod.moe_apply_a2a(
                    p, cfg, x, jax.sharding.get_abstract_mesh()))(p, x)
        diff = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({"diff": diff, "aux_ref": float(aux_ref),
                          "aux": float(aux)}))
    """)
    assert res["diff"] < 1e-4, res
    assert abs(res["aux"] - res["aux_ref"]) < 1e-4, res


def test_moe_a2a_matches_scatter_nondivisible_experts():
    """granite case: E=5 not divisible by tp=2 -> padded dummy experts."""
    res = _run("""
        from repro.configs import get_config, smoke_config
        from repro.models import moe as moe_mod
        cfg = dataclasses.replace(
            smoke_config(get_config("granite-moe-3b-a800m")), dtype="float32",
            d_model=32, n_experts=5, experts_per_token=2, d_ff=16,
            capacity_factor=5.0)
        key = jax.random.key(2)
        p = moe_mod.moe_init(key, cfg)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
        ref, _ = jax.jit(lambda p, x: moe_mod.moe_apply_scatter(p, cfg, x))(p, x)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            out, _ = jax.jit(
                lambda p, x: moe_mod.moe_apply_a2a(
                    p, cfg, x, jax.sharding.get_abstract_mesh()))(p, x)
        import json as j
        print(j.dumps({"diff": float(jnp.max(jnp.abs(out - ref)))}))
    """)
    assert res["diff"] < 1e-4, res


def test_multipod_mesh_and_grad_equivalence():
    """(2,2,2) pod mesh: train step == single device (pod axis pure DP)."""
    res = _run("""
        from repro.configs import get_config, smoke_config, resolve_for_tp
        from repro.distributed import sharding as shd
        from repro.launch.mesh import dp_axes
        from repro.models import build_model
        from repro.optim import AdamW
        from repro.train.loop import make_train_step

        cfg = dataclasses.replace(
            smoke_config(get_config("h2o-danube-3-4b")), dtype="float32",
            d_model=64, n_heads=4, head_dim=16, n_kv_heads=2, window=8)
        cfg = resolve_for_tp(cfg, 2)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        opt = AdamW(lr=1e-3)
        opt_state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}
        step = make_train_step(model, opt, mode="scan", remat=False)
        p1, o1, m1 = jax.jit(step)(params, opt_state, batch)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        pspecs = shd.param_specs(cfg, jax.eval_shape(lambda: params), tp=2)
        ospecs = shd.opt_specs(cfg, None, pspecs)
        bspecs = shd.batch_specs(batch, ("pod", "data"))
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                                 out_shardings=(pspecs, ospecs, None))(
                params, opt_state, batch)
        print(json.dumps({"loss1": float(m1["loss"]),
                          "loss2": float(m2["loss"])}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 2e-4, res
