"""Training launcher: `python -m repro.launch.train --arch <id> [...]`.

By default it trains a reduced config (the e2e example trains a ~100M
model for a few hundred steps on a CPU). ``--full`` keeps every published
width and ``--layers`` then cuts only the depth, which is how one chip
holds a slice of a large model. The mesh adapts to the device count.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax
from jax.sharding import NamedSharding

from repro.configs import RunConfig, ShapeConfig, get_arch, reduced
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, set_mesh
from repro.parallel import sharding as shd
from repro.runtime import train_loop
from repro.runtime.steps import build_train_step
from repro.runtime.elastic import choose_mesh


def main(argv=None, *, devices=None):
    """Run the training CLI; ``devices`` (default: all) are the devices
    the mesh is built over."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--full", action="store_true",
                    help="keep every published width; --layers then sets "
                         "only the depth")
    ap.add_argument("--layers", type=int, default=None,
                    help="layer count (default: 4 reduced, the published "
                         "count with --full)")
    ap.add_argument("--d-model", type=int, default=256,
                    help="reduced model width (ignored with --full)")
    ap.add_argument("--attention-backend",
                    choices=("dense", "chunked", "pallas"), default=None,
                    help="default: dense up to 512 tokens, chunked beyond")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduced(cfg, layers=args.layers or 4, d_model=args.d_model)
    elif args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    backend = args.attention_backend or (
        "dense" if args.seq <= 512 else "chunked")
    devices = jax.devices() if devices is None else list(devices)
    mesh_cfg = choose_mesh(len(devices))
    shape = ShapeConfig("custom", "train", args.seq, args.batch)
    rcfg = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg,
                     microbatches=args.microbatches,
                     attention_backend=backend,
                     learning_rate=args.lr, param_dtype="float32",
                     warmup_steps=max(10, args.steps // 10))
    mesh = make_mesh(mesh_cfg, devices)
    data = SyntheticLM(cfg, args.batch, args.seq)

    with set_mesh(mesh):
        step_fn, model, opt = build_train_step(rcfg, total_steps=args.steps)
        params = model.init_params(jax.random.PRNGKey(rcfg.seed))
        pspecs = shd.param_pspecs(params, cfg, rcfg)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: not isinstance(x, dict))
        opt_state = opt.init(params)
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

        def data_at(step):
            return data.batch_at(step)

        result = train_loop.run(
            jit_step, params, opt_state, data_at,
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every)
    print(f"final_step={result.final_step} "
          f"first_loss={result.losses[0]:.4f} "
          f"last_loss={result.losses[-1]:.4f} "
          f"resumed_from={result.resumed_from} retries={result.retries} "
          f"stragglers={result.stragglers}")
    return result


if __name__ == "__main__":
    main()
