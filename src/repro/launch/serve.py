"""Production serving launcher: batched prefill + decode on the mesh.

  python -m repro.launch.serve --arch mamba2-1.3b --batch 8 --new-tokens 16

``--host-demo`` runs the REDUCED config instead (same engine the dry-run
lowers at production shapes); nothing is reduced without that flag.
"""
import argparse
import time

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--host-demo", action="store_true",
                    help="run a REDUCED config for real on the local device")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ServeEngine, make_prompt_batch

    cfg = get_config(args.arch)
    if args.host_demo:
        cfg = cfg.reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)
    lora = model.init_lora(rng)
    batch = make_prompt_batch(cfg, rng, args.batch, args.prompt_len)
    engine = ServeEngine(
        model, params, lora, cache_len=args.prompt_len + args.new_tokens
    )
    t0 = time.time()
    res = engine.generate(batch, max_new_tokens=args.new_tokens,
                          temperature=args.temperature)
    dt = time.time() - t0
    print(f"{args.arch}: {res.steps} steps x batch {args.batch} in {dt:.1f}s")
    print(res.tokens)


if __name__ == "__main__":
    main()
