"""Inverse heat conduction on a 10-region irregular map (paper §7.6, Figs 11-13).

Variable conductivity K(x,y) inferred from temperature observations: each of 10
irregular (non-convex) polygonal regions gets TWO networks (T-net, K-net) with
per-region activation functions (paper Table 3) and heterogeneous residual-point
counts.  XPINN residual+solution continuity stitches the regions.

    PYTHONPATH=src python examples/inverse_heat_map.py [--steps 2000] [--balance]

Train -> export -> serve (the paper's end product is the FIELD, not the
checkpoint): ``--export DIR`` freezes the trained networks + geometry into a
self-contained serve bundle, and ``--serve-demo`` loads it back and serves a
dense K(x,y) grid through the stitched single-dispatch engine + caching
frontend (see EXPERIMENTS.md §Serving).  ``--supervised`` routes training
through the fault-tolerant chunk supervisor with elastic ``--resume``
(EXPERIMENTS.md §Robustness).
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.core import (  # noqa: E402
    DDConfig, HeatConduction2D, LossWeights, ReferenceTrainer, XPINN,
    build_topology, evaluate_l2, restore_train_state, save_train_state,
    us_map_decomposition,
)
from repro.core.nets import MLPConfig, SubdomainModelConfig  # noqa: E402
from repro.data import make_batch  # noqa: E402

# paper Table 3 (scaled /10 for CPU): residual points + activation per region
TABLE3_COUNTS = [300, 400, 500, 400, 300, 400, 80, 300, 500, 400]
TABLE3_ACTS = ["tanh", "sin", "cos", "tanh", "sin", "cos", "tanh", "sin", "cos", "tanh"]


def serve_demo(export_dir: str, grid_n: int = 80):
    """Load the exported bundle and serve the inferred K(x,y) field."""
    from repro.serve import FieldEngine, ServeFrontend, load_bundle

    bundle = load_bundle(export_dir)
    engine = FieldEngine(bundle)
    frontend = ServeFrontend(engine, order=2)
    verts = np.concatenate(bundle.decomp.polygons)
    lo, hi = verts.min(axis=0), verts.max(axis=0)
    gx, gy = np.meshgrid(np.linspace(lo[0], hi[0], grid_n),
                         np.linspace(lo[1], hi[1], grid_n))
    grid = np.stack([gx.ravel(), gy.ravel()], axis=1)

    t0 = time.time()
    out = frontend.query(grid)            # cold: one fused dispatch
    t_cold = time.time() - t0
    t0 = time.time()
    out2 = frontend.query(grid)           # repeated dashboard grid: cache hit
    t_hot = time.time() - t0
    assert all((out[k] == out2[k]).all() for k in out)

    inside = ~np.isnan(out["u"][:, 0])
    ex = bundle.pde.exact(grid[inside])
    kd = out["u"][inside, 1] - ex[:, 1]
    rel = np.linalg.norm(kd) / np.linalg.norm(ex[:, 1])
    res = np.abs(out["residual"][inside, 0])
    n = len(grid)
    print(f"[serve] {n} grid points ({inside.sum()} inside the map): "
          f"cold {n / t_cold:,.0f} pts/s, cached {n / max(t_hot, 1e-9):,.0f} pts/s "
          f"({t_cold / max(t_hot, 1e-9):.0f}x)")
    print(f"[serve] served K field rel_L2 vs exact: {rel:.4f}; "
          f"residual error-proxy median {np.median(res):.3e} "
          f"p99 {np.quantile(res, 0.99):.3e}")
    print(f"[serve] frontend stats: {frontend.stats()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--balance", action="store_true",
                    help="equalize per-region residual points (straggler fix)")
    ap.add_argument("--chunk", type=int, default=250,
                    help="outer steps per device dispatch (lax.scan driver)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint the TrainState every N steps (0 = off)")
    ap.add_argument("--ckpt", default="ckpt_inverse",
                    help="checkpoint directory for --save-every")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume from the latest checkpoint under DIR")
    ap.add_argument("--supervised", action="store_true",
                    help="route training through the fault-tolerant chunk "
                         "supervisor (checkpoints to --ckpt, recovers crashes "
                         "and NaN divergence; --resume becomes elastic)")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="fault schedule for --supervised: comma-separated "
                         "kind@chunk[:subdomain][*delay] items")
    ap.add_argument("--export", default=None, metavar="DIR",
                    help="freeze the trained field into a serve bundle")
    ap.add_argument("--serve-demo", action="store_true",
                    help="after training, load the --export bundle and serve "
                         "a dense K(x,y) grid (cold vs cached)")
    args = ap.parse_args()
    if args.serve_demo and not args.export:
        ap.error("--serve-demo requires --export DIR")
    if args.inject and not args.supervised:
        ap.error("--inject requires --supervised")

    pde = HeatConduction2D()
    decomp = us_map_decomposition()
    topo = build_topology(decomp, n_iface=16)
    print(f"[inverse] 10 irregular regions, {int(topo.edge_mask.sum()) // 2} "
          f"interfaces, max degree {topo.max_degree}")

    # paper: 3 hidden layers x 80 neurons, separate K network (reduced width)
    model_cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 40, 3),
                                           "k": MLPConfig(2, 1, 40, 3)})
    batch = make_batch(decomp, topo, pde, TABLE3_COUNTS, n_bnd=48,
                       rng=np.random.default_rng(0), n_interior_data=150,
                       balance=args.balance)
    trainer = ReferenceTrainer(
        pde, model_cfg, topo,
        DDConfig(method=XPINN, weights=LossWeights(data=40.0)),
        act_codes=TABLE3_ACTS, lrs=6e-3,
    )
    state = trainer.init(0)
    done = 0
    if args.resume and not args.supervised:
        state = restore_train_state(args.resume, state)
        done = int(state.step)
        print(f"[inverse] resumed from {args.resume} at step {done}")
    b = batch.device_arrays()

    if args.supervised:
        from repro.runtime import (FaultInjector, Supervisor, SupervisorConfig,
                                   elastic_resume, parse_faults)

        if args.resume:
            state, _ = elastic_resume(args.resume, trainer, decomp)
            done = int(np.asarray(state.step))
            print(f"[inverse] elastic resume from {args.resume} at step {done}")
        chunk = max(args.chunk, 1)
        cfg_sup = SupervisorConfig(
            chunk_steps=chunk,
            ckpt_every_chunks=(max(1, args.save_every // chunk)
                               if args.save_every else 1))
        injector = (FaultInjector(parse_faults(args.inject))
                    if args.inject else None)
        sup = Supervisor(trainer, args.ckpt, cfg_sup, injector, decomp=decomp)
        state, report = sup.run(state, b, args.steps)
        for ev in report.events:
            print(f"[supervisor] {ev}")
        print(f"[supervisor] chunks={report.chunks} restarts={report.restarts}"
              f" crashes={report.crashes} guard_trips={report.guard_trips} "
              f"stragglers={report.stragglers}")
    else:
        report_every = 250
        t0 = time.time()
        t_done = done
        while done < args.steps:
            n = min(max(args.chunk, 1), args.steps - done)
            state, terms, health = trainer.run_chunk_guarded(state, b, n)
            if not bool(health["ok"]):
                raise RuntimeError(
                    f"[inverse] loss or parameters went non-finite at step "
                    f"{done + int(health['good_steps'])}: ok_sub="
                    f"{np.asarray(health['ok_sub']).tolist()}; --supervised "
                    "rolls back and retries")
            prev, done = done, done + n
            if args.save_every and done // args.save_every > prev // args.save_every:
                save_train_state(args.ckpt, state)
            if done == args.steps or done // report_every > prev // report_every:
                loss = float(np.asarray(terms["loss"])[-1].sum())
                err = evaluate_l2(decomp, model_cfg, state.params, trainer.act_codes, pde)
                print(f"[inverse] step {done:5d} loss={loss:9.4f} rel_L2(T,K)={err:.4f} "
                      f"({(done - t_done)/(time.time()-t0):.1f} it/s)")

    err = evaluate_l2(decomp, model_cfg, state.params, trainer.act_codes, pde)
    print(f"[inverse] final rel L2 error (T, K stacked) vs exact: {err:.4f}")

    if args.export:
        from repro.serve import export_bundle

        path = export_bundle(args.export, state.params, model_cfg, decomp,
                             act_codes=TABLE3_ACTS, pde=pde, n_iface=16,
                             step=int(state.step),
                             metadata={"rel_l2": err, "steps": int(state.step)})
        print(f"[inverse] exported field bundle -> {path}")
    if args.serve_demo:
        serve_demo(args.export)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    main()
