"""Multi-device checks run in a subprocess with 8 fake host devices.
Invoked by tests/test_distributed.py; prints one OK line per check."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

assert len(jax.devices()) == 8, jax.devices()


def check_mesh_and_shard():
    from repro.launch.mesh import make_mesh
    from repro.sharding.ctx import use_mesh, shard
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = {"batch": ("data",), "mlp": "model"}

    @jax.jit
    def f(x):
        return shard(jnp.tanh(x), "batch", "mlp")

    with use_mesh(mesh, rules):
        y = f(jnp.ones((4, 8)))
        comp = jax.jit(f).lower(jax.ShapeDtypeStruct((4, 8), jnp.float32)).compile()
    assert y.shape == (4, 8)
    print("OK mesh_and_shard")


def check_reduced_arch_sharded_train():
    """A reduced MoE arch trains SPMD on a (2,4) mesh — exercises the
    shard_map EP path with real execution (not just compile)."""
    from repro.configs import get_arch, reduced
    from repro.launch.mesh import make_mesh
    from repro.sharding.ctx import use_mesh
    from repro.sharding.rules import (batch_specs, opt_state_specs,
                                      param_specs, to_named)
    from repro.training import train as TR

    spec = get_arch("olmoe-1b-7b")
    cfg = reduced(spec.model).replace(param_dtype="float32",
                                      compute_dtype="float32")
    tcfg = spec.train.__class__(optimizer="adamw", remat="none")
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = {"batch": ("data",), "heads": "model", "kv_heads": "model",
             "mlp": "model", "vocab": "model", "expert": "model",
             "embed": None, "lora": None, "tp": "model", "seq_q": "model",
             "kv_seq": "model", "ssm_inner": "model", "ssm_heads": "model"}
    with use_mesh(mesh, rules):
        state = TR.init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        state_sh = {
            "params": to_named(param_specs(state["params"], mesh, rules, cfg), mesh),
            "opt": to_named(opt_state_specs(state["opt"], mesh, rules, cfg), mesh),
            "step": NamedSharding(mesh, P()),
        }
        state = jax.device_put(state, state_sh)
        batch = {"tokens": jnp.ones((4, 32), jnp.int32),
                 "targets": jnp.ones((4, 32), jnp.int32)}
        bsh = to_named(batch_specs(batch, mesh, rules), mesh)
        batch = jax.device_put(batch, bsh)
        # out_shardings must pin the state: GSPMD otherwise re-shards the
        # (2,64)/(64,) norm scales onto 'model' on output, and the second
        # call fails the pjit arg-sharding check against state_sh
        step = jax.jit(TR.make_train_step(cfg, tcfg),
                       in_shardings=(state_sh, bsh),
                       out_shardings=(state_sh, None))
        state, m = step(state, batch)
        l1 = float(m["loss"])
        state, m = step(state, batch)
        l2 = float(m["loss"])
    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1
    print("OK sharded_moe_train")


def check_moe_ep_matches_local():
    """EP shard_map output == single-device local dispatch output."""
    from repro.configs import get_arch, reduced
    from repro.launch.mesh import make_mesh
    from repro.models import moe as M
    from repro.sharding.ctx import use_mesh

    cfg = reduced(get_arch("olmoe-1b-7b").model).replace(
        param_dtype="float32", compute_dtype="float32")
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    y_local, aux_local = M.apply_moe(p, cfg, x)          # no mesh -> local

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = {"batch": ("data",), "expert": "model"}
    with use_mesh(mesh, rules):
        y_ep, aux_ep = jax.jit(lambda pp, xx: M.apply_moe(pp, cfg, xx))(p, x)
    err = float(jnp.max(jnp.abs(y_local - y_ep)))
    assert err < 2e-4, err
    print("OK moe_ep_matches_local", err)


def check_moe_a2a_matches_local():
    """all-to-all dispatch EP (§Perf strategy) == local dispatch."""
    from repro.configs import get_arch, reduced
    from repro.launch.mesh import make_mesh
    from repro.models import moe as M
    from repro.sharding.ctx import use_mesh

    cfg = reduced(get_arch("olmoe-1b-7b").model).replace(
        param_dtype="float32", compute_dtype="float32", capacity_factor=4.0)
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model))
    y_local, _ = M.apply_moe(p, cfg, x)
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = {"batch": ("data",), "expert": "model"}
    with use_mesh(mesh, rules, strategy="moe_a2a"):
        y_a2a, _ = jax.jit(lambda pp, xx: M.apply_moe(pp, cfg, xx))(p, x)
    err = float(jnp.max(jnp.abs(y_local - y_a2a)))
    assert err < 2e-4, err
    print("OK moe_a2a_matches_local", err)


def check_compressed_psum():
    from repro.launch.mesh import make_mesh
    from repro.training.compression import compressed_psum_mean
    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))

    def f(gl):
        return compressed_psum_mean(gl[0], "data")[None]

    red = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                        check_vma=False)(g)
    exact = jnp.mean(g, axis=0)
    rel = float(jnp.max(jnp.abs(red[0] - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
    assert rel < 0.05, rel
    print("OK compressed_psum rel_err", rel)


def check_compression_wire_bytes():
    """HLO of the int8 reduce must move ~4x fewer collective bytes than a
    plain fp32 all-reduce of the same tensor."""
    from repro.launch.mesh import make_mesh
    from repro.roofline.analysis import analyze_hlo
    from repro.training.compression import compressed_psum_mean
    mesh = make_mesh((8,), ("data",))
    n = 1 << 16

    def plain(gl):
        return jax.lax.pmean(gl[0], "data")[None]

    def comp(gl):
        return compressed_psum_mean(gl[0], "data")[None]

    sds = jax.ShapeDtypeStruct((8, n), jnp.float32)
    def wire(fn):
        c = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False)
                    ).lower(sds).compile()
        return analyze_hlo(c.as_text()).coll_bytes
    wp, wc = wire(plain), wire(comp)
    assert wc < wp / 2.5, (wp, wc)
    print(f"OK compression_wire_bytes plain={wp:.0f} int8={wc:.0f} "
          f"ratio={wp/wc:.2f}x")


def check_pipeline_parallel():
    from repro.launch.mesh import make_mesh
    from repro.sharding.pipeline_parallel import pipeline_apply
    mesh = make_mesh((4,), ("stage",))
    S, M, mb, D = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (S, D, D)) * 0.3

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    run = pipeline_apply(stage_fn, mesh, num_microbatches=M)
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, D))
    y = run({"w": w}, x)
    # reference: sequential application of all 4 stages
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ w[s])
    err = float(jnp.max(jnp.abs(y - ref)))
    assert err < 1e-5, err
    # autodiff through the pipeline
    g = jax.grad(lambda ww: jnp.sum(run({"w": ww}, x) ** 2))(w)
    assert np.isfinite(np.asarray(g)).all()
    print("OK pipeline_parallel err", err)


def check_elastic_restore():
    """Checkpoint saved from a (2,4) mesh restores onto a (4,2) mesh."""
    import tempfile
    from repro.launch.mesh import make_mesh
    from repro.training.checkpoint import CheckpointManager
    state = {"w": jnp.arange(64.0).reshape(8, 8)}
    m1 = make_mesh((2, 4), ("data", "model"))
    st1 = jax.device_put(state, NamedSharding(m1, P("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, st1)
        m2 = make_mesh((4, 2), ("data", "model"))
        sh2 = {"w": NamedSharding(m2, P("data", "model"))}
        back = mgr.restore(like=state, shardings=sh2)
        assert back["w"].sharding.mesh.shape["data"] == 4
        np.testing.assert_allclose(np.asarray(back["w"]),
                                   np.asarray(state["w"]))
    print("OK elastic_restore")


def check_train_driver():
    """launch.train end-to-end on an in-process 8-device mesh (resume too)."""
    import shutil
    shutil.rmtree("out/_driver_ckpt", ignore_errors=True)
    from repro.launch.train import main as train_main
    train_main(["--arch", "stablelm-1.6b", "--steps", "6", "--mesh", "2x4",
                "--batch", "8", "--seq", "16",
                "--ckpt-dir", "out/_driver_ckpt", "--ckpt-every", "3",
                "--log-every", "3"])
    train_main(["--arch", "stablelm-1.6b", "--steps", "9", "--mesh", "2x4",
                "--batch", "8", "--seq", "16",
                "--ckpt-dir", "out/_driver_ckpt", "--ckpt-every", "3",
                "--log-every", "3"])  # resumes from step 6
    import os
    steps = sorted(os.listdir("out/_driver_ckpt"))
    assert any("00000009" in s for s in steps), steps
    shutil.rmtree("out/_driver_ckpt", ignore_errors=True)
    print("OK train_driver")


if __name__ == "__main__":
    check_mesh_and_shard()
    check_reduced_arch_sharded_train()
    check_moe_ep_matches_local()
    check_moe_a2a_matches_local()
    check_compressed_psum()
    check_compression_wire_bytes()
    check_pipeline_parallel()
    check_elastic_restore()
    check_train_driver()
    print("ALL DISTRIBUTED OK")
