"""Distributed training driver.

On a TPU host this is the entry point per host:

    python -m repro.launch.train --arch stablelm-1.6b --full --steps 1000 \
        --strategy pure_fsdp --mesh 2x2 --ckpt-dir out/ckpt

The default mesh puts every device present on the ``data`` axis. On a CPU
container, pass ``--fake-devices N`` to run a REAL sharded training loop on
N host devices (small mesh, reduced config) — the same code path: mesh ->
sharding rules -> sharded init -> jitted train_step -> async checkpoints ->
restart-from-latest.
"""
from __future__ import annotations

import argparse
import itertools
import os
from typing import List, Optional, Tuple


def train(cfg, tcfg, *, arch: str, strategy: str = "baseline",
          mesh_shape: Optional[Tuple[int, ...]] = None, batch: int = 8,
          seq: int = 64, steps: int = 50, seed: int = 0,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 25,
          log_every: int = 10) -> List[float]:
    """Train ``cfg`` for ``steps`` steps on a ``data`` x ``model`` mesh of
    ``mesh_shape`` (default: all devices on ``data``) and return the loss
    of each step run. The state is initialised already sharded, so a model
    whose state fits only across the mesh never lands whole on one device.
    With ``ckpt_dir`` the loop resumes from the latest checkpoint there, on
    the batches that follow it, and saves every ``ckpt_every`` steps.

    Opens the mesh context itself: ``sharding.ctx`` keeps it per thread, and
    a workflow step runs on an engine worker thread."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import synthetic_batches
    from repro.launch.mesh import make_mesh
    from repro.sharding.ctx import use_mesh
    from repro.sharding.rules import (batch_specs, opt_state_specs,
                                      param_specs, rules_for, to_named)
    from repro.training import train as TR
    from repro.training.checkpoint import CheckpointManager

    if mesh_shape is None:
        mesh_shape = (len(jax.devices()), 1)
    mesh = make_mesh(mesh_shape, ("data", "model")[: len(mesh_shape)])
    rules = rules_for(arch, strategy)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    key = jax.random.PRNGKey(seed)

    with use_mesh(mesh, rules, strategy):
        shapes = jax.eval_shape(lambda: TR.init_train_state(cfg, tcfg, key))
        state_sh = {
            "params": to_named(param_specs(shapes["params"], mesh, rules, cfg,
                                           strategy), mesh),
            "opt": to_named(opt_state_specs(shapes["opt"], mesh, rules, cfg,
                                            strategy), mesh),
            "step": NamedSharding(mesh, P()),
        }
        start = mgr.latest_step() if mgr else None
        if start is not None:
            print(f"resuming from checkpoint step {start}")
            state = mgr.restore(like=shapes, shardings=state_sh)
        else:
            state = jax.jit(lambda k: TR.init_train_state(cfg, tcfg, k),
                            out_shardings=state_sh)(key)
        # pin out_shardings to the input specs: without it GSPMD may hand
        # the state back re-sharded (e.g. norm scales gathered onto
        # 'model'), and the next step_fn call rejects the committed arrays
        step_fn = jax.jit(TR.make_train_step(cfg, tcfg),
                          in_shardings=(state_sh, None),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,))

        losses = []
        batches = synthetic_batches(batch, seq, cfg.vocab_size, seed=seed,
                                    n=steps)
        # the step count on the host: the device's is read only where a
        # step is logged or saved, so that no other step waits on it
        s = start or 0
        for b in itertools.islice(batches, s, None):
            s += 1
            with jax.profiler.StepTraceAnnotation("train", step_num=s):
                b = {k: jnp.asarray(v) for k, v in b.items()}
                b = jax.device_put(b, to_named(batch_specs(b, mesh, rules),
                                               mesh))
                state, m = step_fn(state, b)
            losses.append(m["loss"])
            log = s % log_every == 0
            save = mgr is not None and s % ckpt_every == 0
            if not (log or save):
                continue
            on_device = int(state["step"])
            if on_device != s:
                raise RuntimeError(f"the state is at step {on_device}, "
                                   f"the loop at {s}")
            if log:
                print(f"step {s:5d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f}")
            if save:
                mgr.async_save(s, state)
        if mgr:
            mgr.wait()
            mgr.save(int(state["step"]), state)
            print(f"done at step {int(state['step'])}; "
                  f"checkpoints in {ckpt_dir}")
    return [float(x) for x in losses]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--strategy", default="baseline",
                    choices=["baseline", "dp_zero1", "pure_fsdp",
                             "moe_a2a", "moe_rs"])
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="data x model (e.g. 2x2); default: every device "
                         "on data")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None,
                    help="save here, and resume from the latest checkpoint "
                         "here; no checkpoints when unset")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{args.fake_devices}").strip()

    from repro.configs import get_arch, reduced
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    spec = get_arch(args.arch)
    cfg, tcfg = spec.model, spec.train
    if args.reduced:
        cfg = reduced(cfg).replace(param_dtype="float32",
                                   compute_dtype="float32")
        tcfg = tcfg.__class__(optimizer=tcfg.optimizer, learning_rate=1e-3,
                              remat="none")
    mesh_shape = (tuple(int(x) for x in args.mesh.split("x"))
                  if args.mesh else None)
    train(cfg, tcfg, arch=args.arch, strategy=args.strategy,
          mesh_shape=mesh_shape, batch=args.batch, seq=args.seq,
          steps=args.steps, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, log_every=args.log_every)


if __name__ == "__main__":
    main()
