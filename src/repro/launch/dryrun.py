import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real ``train_step`` / ``prefill_step`` /
``serve_step`` on the production mesh with explicit in/out shardings,
compiles it (AOT, no allocation), prints ``memory_analysis()`` /
``cost_analysis()`` and writes the roofline terms parsed from the SPMD HLO
(see ``repro.roofline.analysis``) to ``out/dryrun/<mesh>/<arch>/<shape>.json``.

Usage:
    python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k
    python -m repro.launch.dryrun --all            # every applicable cell
    python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_arch, get_shape
from repro.configs.base import LM_SHAPES
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cache_specs_shapes, input_specs
from repro.models import transformer as T
from repro.roofline.analysis import analyze_hlo, roofline_report
from repro.sharding.ctx import use_mesh
from repro.sharding.rules import (batch_specs, cache_specs, opt_state_specs,
                                  param_specs, rules_for, to_named)
from repro.training import train as TR

OUT_DIR = Path(os.environ.get("DRYRUN_OUT", "out/dryrun"))


def _metrics_sharding(tree, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
               strategy: str = "baseline", remat: str = None,
               verbose: bool = True):
    """Returns (compiled, lowered, meta) for one cell."""
    spec = get_arch(arch_id)
    cfg, tcfg = spec.model, spec.train
    if remat is not None:
        import dataclasses
        tcfg = dataclasses.replace(tcfg, remat=remat)
    shape = get_shape(shape_name)
    if shape_name in spec.skips:
        raise SystemExit(f"SKIP {arch_id} x {shape_name}: {spec.skips[shape_name]}")

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(arch_id, strategy)
    t0 = time.time()

    with use_mesh(mesh, rules, strategy):
        batch_sds = input_specs(cfg, shape)
        batch_sh = to_named(batch_specs(batch_sds, mesh, rules), mesh)

        if shape.kind == "train":
            key = jax.random.PRNGKey(0)
            state_sds = jax.eval_shape(
                lambda: TR.init_train_state(cfg, tcfg, key))
            state_sh = {
                "params": to_named(param_specs(state_sds["params"], mesh, rules, cfg, strategy), mesh),
                "opt": to_named(opt_state_specs(state_sds["opt"], mesh, rules, cfg, strategy), mesh),
                "step": _metrics_sharding(state_sds["step"], mesh),
            }
            step_fn = TR.make_train_step(cfg, tcfg)
            metrics_sds = jax.eval_shape(step_fn, state_sds, batch_sds)[1]
            jfn = jax.jit(step_fn,
                          in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh,
                                         _metrics_sharding(metrics_sds, mesh)),
                          donate_argnums=(0,))
            lowered = jfn.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            params_sds = jax.eval_shape(
                lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
            params_sh = to_named(param_specs(params_sds, mesh, rules, cfg, strategy), mesh)

            def prefill_step(params, batch):
                kwargs = {}
                if cfg.family == "encdec":
                    kwargs["frames"] = batch["frames"]
                if cfg.family == "vlm":
                    kwargs["patches"] = batch["patches"]
                logits, _ = T.apply_lm(params, cfg, batch["tokens"],
                                       remat=tcfg.remat, **kwargs)
                return logits[:, -1, :]
            jfn = jax.jit(prefill_step, in_shardings=(params_sh, batch_sh))
            lowered = jfn.lower(params_sds, batch_sds)
        else:  # decode
            params_sds = jax.eval_shape(
                lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
            params_sh = to_named(param_specs(params_sds, mesh, rules, cfg, strategy), mesh)
            caches_sds = cache_specs_shapes(cfg, shape)
            caches_sh = to_named(cache_specs(caches_sds, mesh, rules), mesh)
            from jax.sharding import NamedSharding, PartitionSpec as P
            idx_sds = jax.ShapeDtypeStruct((), jnp.int32)

            def serve_step(params, caches, token, index):
                return T.apply_lm_decode(params, cfg, token, caches, index)
            jfn = jax.jit(serve_step,
                          in_shardings=(params_sh, caches_sh, batch_sh["token"],
                                        NamedSharding(mesh, P())),
                          donate_argnums=(1,))
            lowered = jfn.lower(params_sds, caches_sds,
                                batch_sds["token"], idx_sds)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    meta = {"arch": arch_id, "shape": shape_name, "strategy": strategy,
            "multi_pod": multi_pod, "chips": mesh.size,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}
    return compiled, lowered, meta, cfg, shape, mesh


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
             strategy: str = "baseline", remat: str = None, tag: str = None,
             out_dir: Path = OUT_DIR, verbose: bool = True) -> dict:
    compiled, lowered, meta, cfg, shape, mesh = lower_cell(
        arch_id, shape_name, multi_pod=multi_pod, strategy=strategy,
        remat=remat)

    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        mem["total_per_device_bytes"] = (mem["argument_bytes"]
                                         + mem["output_bytes"]
                                         + mem["temp_bytes"]
                                         - mem["alias_bytes"])
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    terms = analyze_hlo(hlo)
    report = roofline_report(terms, cfg, shape, mesh.size)

    rec = dict(meta)
    rec["memory_analysis"] = mem
    rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                            if isinstance(v, (int, float))
                            and k in ("flops", "bytes accessed",
                                      "transcendentals")}
    rec["roofline"] = report
    rec["hlo_instruction_count"] = hlo.count("\n")
    rec["status"] = "ok"

    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    label = tag or strategy
    fname = (f"{shape_name}.json" if label == "baseline"
             else f"{shape_name}.{label}.json")
    path = out_dir / mesh_tag / arch_id / fname
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1))
    if verbose:
        print(f"[{mesh_tag}] {arch_id} x {shape_name}: "
              f"compile={meta['compile_s']}s "
              f"mem/dev={mem.get('total_per_device_bytes', 0)/2**30:.2f}GiB "
              f"dom={report['dominant']} "
              f"terms(c/m/x)=({report['compute_s']:.4f},"
              f"{report['memory_s']:.4f},{report['collective_s']:.4f})s "
              f"useful={report['useful_flops_ratio']:.2f}")
    return rec


def all_cells(multi_pod: bool):
    for arch_id in ARCH_IDS:
        spec = get_arch(arch_id)
        for shape in LM_SHAPES:
            if shape.name in spec.skips:
                yield arch_id, shape.name, "skip", spec.skips[shape.name]
            else:
                yield arch_id, shape.name, "run", None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="baseline",
                    choices=["baseline", "dp_zero1", "pure_fsdp", "moe_a2a", "moe_rs"])
    ap.add_argument("--remat", default=None,
                    choices=[None, "none", "dots", "full"])
    ap.add_argument("--tag", default=None, help="suffix for the output json")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)
    use_compile_cache()

    if args.all:
        failures = []
        for arch_id, shape_name, status, reason in all_cells(args.multi_pod):
            mesh_tag = "pod2x16x16" if args.multi_pod else "pod16x16"
            path = out_dir / mesh_tag / arch_id / f"{shape_name}.json"
            if status == "skip":
                path.parent.mkdir(parents=True, exist_ok=True)
                path.write_text(json.dumps(
                    {"arch": arch_id, "shape": shape_name, "status": "skip",
                     "reason": reason}, indent=1))
                print(f"[{mesh_tag}] {arch_id} x {shape_name}: SKIP ({reason})")
                continue
            if path.exists() and json.loads(path.read_text()).get("status") == "ok":
                print(f"[{mesh_tag}] {arch_id} x {shape_name}: cached")
                continue
            try:
                run_cell(arch_id, shape_name, multi_pod=args.multi_pod,
                         out_dir=out_dir)
            except Exception as e:  # noqa: BLE001
                failures.append((arch_id, shape_name))
                path.parent.mkdir(parents=True, exist_ok=True)
                path.write_text(json.dumps(
                    {"arch": arch_id, "shape": shape_name,
                     "status": "error", "error": f"{type(e).__name__}: {e}",
                     "traceback": traceback.format_exc()[-4000:]}, indent=1))
                print(f"FAIL {arch_id} x {shape_name}: {type(e).__name__}: {e}")
        if failures:
            print("FAILED CELLS:", failures)
            sys.exit(1)
        print("ALL CELLS OK")
        return

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   strategy=args.strategy, remat=args.remat, tag=args.tag,
                   out_dir=out_dir)
    print(json.dumps({k: rec[k] for k in ("memory_analysis", "roofline")},
                     indent=1))


if __name__ == "__main__":
    main()
