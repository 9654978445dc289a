"""Production training launcher.

On a TPU slice this runs the FibecFed distributed train step on the
production mesh, which needs every one of its devices (the launcher raises,
naming the device count, where they are missing). On a CPU host pass
``--dry-run`` (identical code path to ``python -m repro.launch.dryrun``) or
``--host-demo`` to execute a reduced config for a few steps on the local
device; nothing is reduced without that flag.

  python -m repro.launch.train --arch qwen2-0.5b --steps 200 [--multi-pod]
"""
import os

if os.environ.get("REPRO_FORCE_HOST_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.environ['REPRO_FORCE_HOST_DEVICES']}"
    )

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp_only"])
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile only (CPU-safe; same as repro.launch.dryrun)")
    ap.add_argument("--host-demo", action="store_true",
                    help="run a REDUCED config for real on the local device")
    ap.add_argument("--gal-fraction", type=float, default=0.75)
    ap.add_argument("--sparse-ratio", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    if args.dry_run:
        from repro.launch.dryrun import dryrun_one

        rec = dryrun_one(args.arch, args.shape, multi_pod=args.multi_pod,
                         layout=args.layout)
        print(rec.get("roofline", rec))
        return

    from repro.checkpoint import save_checkpoint
    from repro.configs import get_config, get_shape
    from repro.launch.mesh import dp_axes, make_production_mesh, num_client_groups
    from repro.launch.steps import build_train_step, make_train_state
    from repro.lora import gal_mask_tree, lora_num_logical_layers
    from repro.models import build_model

    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    if args.host_demo:
        cfg = cfg.reduced()
        n_groups, B, S = 4, 16, 128
        mesh = None
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        n_groups = num_client_groups(mesh)
        B, S = shape.global_batch, shape.seq_len

    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)
    state = make_train_state(model, rng, n_groups)
    L = lora_num_logical_layers(cfg)
    gal = np.zeros(L, bool)
    gal[: max(1, int(round(args.gal_fraction * L)))] = True
    state["gal_mask"] = gal_mask_tree(cfg, state["gal_lora"], gal)
    state["local_mask"] = jax.tree.map(jnp.ones_like, state["local_mask"])

    step = jax.jit(
        build_train_step(model, n_groups, learning_rate=args.lr), donate_argnums=(1,)
    )
    t0 = time.time()
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        for i in range(args.steps):
            tokens = jax.random.randint(
                jax.random.fold_in(rng, i), (B, S), 0, cfg.vocab_size
            )
            state, metrics = step(params, state, {"tokens": tokens})
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss={float(metrics['loss']):.4f} "
                      f"({(time.time() - t0) / (i + 1):.2f}s/step)")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, {"gal_lora": state["gal_lora"]})
        print(f"checkpoint -> {args.ckpt_dir}")


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
