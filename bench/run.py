"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``). The run needs a TPU
with as many chips as the cell asks for; without one it exits non-zero and
prints no result. Its last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checked``: each number the check compared, beside its limit. Those
numbers are also the last lines of standard error.

JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root of
the checkout, so only a cell's first run there compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse                                                # noqa: E402
import json                                                    # noqa: E402
import os                                                      # noqa: E402
import sys                                                     # noqa: E402
from pathlib import Path                                       # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program keeps its compile cache where this variable says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as H

    try:
        cell = H.load_cell(args.workload)
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        run = H.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_process=T_PROCESS)
        result = H.execute(run)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in result["checked"].items():
        print(f"[checked] {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
