"""Training launcher — PINN (the paper's workload) and LM (the arch zoo).

PINN (end-to-end driver for the paper's experiments):
  python -m repro.launch.train pinn --pde burgers1d --method xpinn \
      --nx 4 --nt 2 --steps 2000 --ckpt-dir /tmp/run --resume

LM (synthetic-token pipeline; reduced configs run on CPU):
  python -m repro.launch.train lm --arch llama3.2-1b --reduced \
      --steps 50 --batch 4 --seq 256 --ckpt-dir /tmp/lm --resume

Both paths checkpoint every ``--ckpt-every`` steps and resume bitwise with
``--resume`` (fault-tolerance contract; see runtime/failures.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.core import (
    Burgers1D, CartesianDecomposition, DDConfig, DistributedDDTrainer,
    HeatConduction2D, LossWeights, NavierStokes2D, ReferenceTrainer,
    build_topology, evaluate_l2, us_map_decomposition,
)
from repro.core.losses import METHODS
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.core.pdes import REGISTRY as PDE_REGISTRY
from repro.data import make_batch
from repro.models import build_model, make_batch as make_lm_batch
from repro.optim import adam as adam_lib
from repro.utils import enable_compile_cache


# ------------------------------------------------------------------------ PINN

def run_pinn(args) -> dict:
    pde = PDE_REGISTRY[args.pde]()
    if args.pde == "heat2d_inverse":
        decomp = us_map_decomposition()
        nets = {
            "u": MLPConfig(2, 1, args.width, args.depth),
            "k": MLPConfig(2, 1, args.width, args.depth),
        }
        n_interior = args.n_data
    else:
        if args.pde == "burgers1d":
            bounds = ((-1.0, 1.0), (0.0, 1.0))
        elif args.pde == "euler1d":
            bounds = ((0.0, 1.0), (0.0, 0.2))   # Sod shock tube, t in [0, 0.2]
        else:
            bounds = ((0.0, 1.0), (0.0, 1.0))
        decomp = CartesianDecomposition(bounds, args.nx, args.nt)
        nets = {"u": MLPConfig(2, pde.n_fields, args.width, args.depth)}
        n_interior = 0
    topo = build_topology(decomp, args.n_iface)
    model_cfg = SubdomainModelConfig(nets=nets)
    rng = np.random.default_rng(args.seed)
    batch = make_batch(decomp, topo, pde, args.n_res, args.n_bnd, rng,
                       n_interior_data=n_interior, balance=args.balance)

    dd = DDConfig(method=METHODS[args.method], weights=LossWeights(),
                  couple_gradients=args.couple, local_steps=args.local_steps)
    if args.distributed and len(jax.devices()) < topo.n_sub:
        raise ValueError(
            f"--distributed needs one device per subdomain: {topo.n_sub} "
            f"subdomains, {len(jax.devices())} {jax.default_backend()} devices")
    cls = DistributedDDTrainer if args.distributed else ReferenceTrainer
    trainer = cls(pde, model_cfg, topo, dd, lrs=args.lr)
    state = trainer.init(args.seed)
    b = batch.device_arrays()
    if cls is DistributedDDTrainer:
        state, b = trainer.shard_state(state), trainer.shard_batch(b)

    start = 0
    if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, meta = ckpt.restore(args.ckpt_dir, {"params": state.params, "opt": state.opt})
        state.params, state.opt = tree["params"], tree["opt"]
        start = meta["step"]
        print(f"[train] resumed from step {start}")

    t0, terms = time.time(), None
    for s in range(start, args.steps):
        state, terms = trainer.step(state, b)
        if (s + 1) % args.log_every == 0:
            loss = float(np.asarray(terms["loss"]).sum())
            print(f"[train] step {s+1}/{args.steps} loss={loss:.5f} "
                  f"({(s + 1 - start) / (time.time() - t0):.1f} it/s)")
        if args.ckpt_dir and (s + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, s + 1,
                      {"params": state.params, "opt": state.opt},
                      {"step": s + 1, "pde": args.pde, "method": args.method})
    out = {"loss": float(np.asarray(terms["loss"]).sum()) if terms else None}
    if pde.exact(np.zeros((1, 2))) is not None:
        err = evaluate_l2(decomp, model_cfg, state.params, trainer.act_codes, pde)
        out["rel_l2"] = err
        print(f"[train] rel L2 error vs exact: {err:.4f}")
    return out


# -------------------------------------------------------------------------- LM

def run_lm(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.preset == "100m":
        cfg = dataclasses.replace(
            cfg.reduced(), n_layers=8, d_model=768, n_heads=12, n_kv_heads=4,
            head_dim=64, d_ff=2048, vocab=32000, remat=False)
    model = build_model(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    params = model.init(jax.random.PRNGKey(args.seed))
    opt = adam_lib.init_adam(params)

    @jax.jit
    def train_step(params, opt, batch, step):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        grads, gn = adam_lib.clip_by_global_norm(grads, 1.0)
        lr = adam_lib.warmup_cosine(step, args.lr, warmup=20, total=args.steps)
        params, opt = adam_lib.adam_update(grads, opt, params, lr)
        return params, opt, loss, gn

    start = 0
    if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, meta = ckpt.restore(args.ckpt_dir, {"params": params, "opt": opt})
        params, opt = tree["params"], tree["opt"]
        start = meta["step"]
        print(f"[train] resumed from step {start}")

    t0, losses = time.time(), []
    for s in range(start, args.steps):
        batch = make_lm_batch(cfg, shape, "train", seed=args.seed * 100003 + s)
        params, opt, loss, gn = train_step(params, opt, batch, jnp.asarray(s))
        losses.append(float(loss))
        if (s + 1) % args.log_every == 0:
            print(f"[train] step {s+1}/{args.steps} loss={float(loss):.4f} "
                  f"gnorm={float(gn):.3f} ({(s+1-start)/(time.time()-t0):.2f} it/s)")
        if args.ckpt_dir and (s + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, s + 1, {"params": params, "opt": opt},
                      {"step": s + 1, "arch": args.arch})
    return {"final_loss": losses[-1] if losses else None, "losses": losses}


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    pp = sub.add_parser("pinn")
    pp.add_argument("--pde", default="burgers1d", choices=sorted(PDE_REGISTRY))
    pp.add_argument("--method", default="xpinn", choices=["cpinn", "xpinn"])
    pp.add_argument("--nx", type=int, default=4)
    pp.add_argument("--nt", type=int, default=1)
    pp.add_argument("--width", type=int, default=20)
    pp.add_argument("--depth", type=int, default=5)
    pp.add_argument("--n-res", type=int, default=1000)
    pp.add_argument("--n-bnd", type=int, default=80)
    pp.add_argument("--n-iface", type=int, default=20)
    pp.add_argument("--n-data", type=int, default=200)
    pp.add_argument("--steps", type=int, default=500)
    pp.add_argument("--lr", type=float, default=8e-4)
    pp.add_argument("--seed", type=int, default=0)
    pp.add_argument("--couple", action="store_true")
    pp.add_argument("--balance", action="store_true")
    pp.add_argument("--local-steps", type=int, default=1)
    pp.add_argument("--distributed", action="store_true")
    pp.add_argument("--ckpt-dir", default=None)
    pp.add_argument("--ckpt-every", type=int, default=100)
    pp.add_argument("--log-every", type=int, default=50)
    pp.add_argument("--resume", action="store_true")

    lp = sub.add_parser("lm")
    lp.add_argument("--arch", default="llama3.2-1b")
    lp.add_argument("--reduced", action="store_true")
    lp.add_argument("--preset", default=None, choices=[None, "100m"])
    lp.add_argument("--steps", type=int, default=50)
    lp.add_argument("--batch", type=int, default=4)
    lp.add_argument("--seq", type=int, default=256)
    lp.add_argument("--lr", type=float, default=3e-4)
    lp.add_argument("--seed", type=int, default=0)
    lp.add_argument("--ckpt-dir", default=None)
    lp.add_argument("--ckpt-every", type=int, default=25)
    lp.add_argument("--log-every", type=int, default=10)
    lp.add_argument("--resume", action="store_true")

    args = ap.parse_args()
    if args.mode == "pinn":
        run_pinn(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
