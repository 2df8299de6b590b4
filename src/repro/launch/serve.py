"""Serving driver: batched generation through the ServingEngine.

    python -m repro.launch.serve --arch mamba2-370m --batch 4 --gen-len 32

``--full`` serves the architecture at its published widths and dtypes
instead of the reduced CPU-sized config.
"""
import argparse


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_arch, reduced
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import transformer as T
    from repro.serving.engine import ServingEngine

    use_compile_cache()
    cfg = get_arch(args.arch).model
    if args.reduced:
        cfg = reduced(cfg).replace(param_dtype="float32",
                                   compute_dtype="float32")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params,
                        max_len=args.prompt_len + args.gen_len + 1)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    res = eng.generate(prompts, args.gen_len, temperature=args.temperature)
    print(f"arch={args.arch} prefill={res.prefill_s:.2f}s "
          f"decode={res.decode_s:.2f}s ({res.tokens_per_s:.1f} tok/s)")
    print("first request tokens:", res.tokens[0][:16])


if __name__ == "__main__":
    main()
