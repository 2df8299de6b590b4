"""Readings that the limits of ``bench/limits/<cell>.json`` are set from,
and the sweep that fixes a serve mix's rate. Not part of a benchmark run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]
    python bench/calibrate.py --workload <cell> --sweep 0.2,0.3,0.4 \\
        --seeds 1 --seconds 30

For each seed, in one process: a run of the cell as ``bench/run.py`` makes
it (set-up, a short window, the check), its compared numbers; with
``--control-seeds`` the control's numbers, the reference computed with
float8 operands in the program's place; with ``--fault-seeds`` the numbers
of the timed path broken as ``tests/test_cells.py`` breaks it. One JSON line
per reading on standard output.

``--sweep``: one set-up, then a window at each rate (workflows per second);
for each, how many workflows finished within the window's close plus a
drain of ``GRACE_S`` and the tail of the time to first token: the highest
rate whose queue does not grow is the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

FAULTS = {"finetune": ["half_batch"], "serve": ["token"]}


def ints(s: str):
    return [int(x) for x in s.split(",") if x]


def emit(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def reading(H, cell, seed, seconds, fault=None):
    run = H.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                t_process=time.time(), fault=fault)
    res = H.execute(run)
    return run, res


def control(cell, run):
    from bench import compare
    from bench.reference import common as C
    kind = cell.traffic["kind"]
    if kind == "finetune":
        low = cell.kind.reference_steps(run, C.Numerics(control=True))
        return compare.training_gaps(low, run.records["reference"])
    gaps = cell.kind.reference_gaps(run, run.records["checked"],
                                    control=True)
    return {"max_gap": max(gaps)}


def sweep(H, cell, rates, seed, seconds):
    from bench import records
    kind = cell.kind
    run = H.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                t_process=time.time())
    run.device = H.device_info(cell.chips)
    st = kind.setup(run)
    B = cell.traffic["requests_per_workflow"]
    for rate in rates:
        cell.traffic = {**cell.traffic, "rate_per_s": rate}
        st["schedule"] = kind.schedule(cell.traffic, seconds)
        for p in sorted({w["prompt_len"] for w in st["schedule"]}):
            kind.warm_slices(B, p)
        run.records, run.attempted, run.failed = {}, 0, 0
        run.t_window = time.time()
        kind.window(run, st)
        served = run.records["served"]
        close = run.t_window + seconds
        on_time = sum(1 for s in served
                      if s["ok"] and s["out"]["t_end"] <= close)
        emit(rate=rate, workflows=len(served), failed=run.failed,
             finished_by_close=on_time,
             drain_s=run.t_window_end - close,
             ttft_p95_ms=records.p95(records.ttft_ms(run)),
             tpot_p95_ms=records.p95(records.tpot_ms(run)))
    kind.release(st)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness as H

    cell = H.load_cell(args.workload)
    if args.sweep:
        sweep(H, cell, [float(r) for r in args.sweep.split(",")],
              args.seeds[0], args.seconds)
        return 0
    for seed in args.seeds:
        run, res = reading(H, cell, seed, args.seconds)
        emit(seed=seed, what="program", correct=res["correct"],
             numbers={k: v["value"] for k, v in res["checked"].items()},
             metrics={k: v["value"] for k, v in res["metrics"].items()},
             memory_peak_bytes=res["device"]["memory_peak_bytes"])
        if seed in args.control_seeds:
            emit(seed=seed, what="control", numbers=control(cell, run))
        del run
        for fault in FAULTS[cell.traffic["kind"]]:
            if seed in args.fault_seeds:
                _, r = reading(H, cell, seed, min(args.seconds, 5.0), fault)
                emit(seed=seed, what=fault,
                     numbers={k: v["value"] for k, v in r["checked"].items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
