"""Serve a small LM with batched requests through ``ServingEngine``: the
prompt fills the caches (in one call for the dense and ssm families), then
batched greedy decode steps.

    PYTHONPATH=src python examples/serve_lm.py [--arch mamba2-370m]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax

from repro.configs import get_arch, reduced
from repro.models import transformer as T
from repro.serving.engine import ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen-len", type=int, default=24)
    args = ap.parse_args()

    spec = get_arch(args.arch)
    cfg = reduced(spec.model).replace(param_dtype="float32",
                                      compute_dtype="float32")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=args.prompt_len + args.gen_len)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    res = eng.generate(prompts, args.gen_len)

    print(f"arch={args.arch} family={cfg.family}")
    print(f"prefill: {args.prompt_len} toks x {args.batch} reqs "
          f"in {res.prefill_s:.2f}s ({res.prefill_calls} device calls)")
    print(f"decode:  {args.gen_len} toks x {args.batch} reqs "
          f"in {res.decode_s:.2f}s ({res.tokens_per_s:.1f} tok/s)")
    print("sample token ids:", res.tokens[0][:12])


if __name__ == "__main__":
    main()
