"""Quickstart: solve viscous Burgers with a space-time XPINN (paper §7.5).

The end-to-end driver for the paper's workload: decompose (-1,1) x (0,1) into
2x2 space-time subdomains, one network each, train a few hundred steps, and
validate against the Cole-Hopf exact solution.

    PYTHONPATH=src python examples/quickstart.py [--steps 1500]

With ``--supervised`` the run goes through the fault-tolerant chunk supervisor
(EXPERIMENTS.md §Robustness): guarded chunks, crash/NaN recovery, and ELASTIC
``--resume`` — a checkpoint taken at one ``--nx/--nt`` restarts at another via
nearest-centroid parameter adoption.  ``--inject`` drives the fault matrix:

    PYTHONPATH=src python examples/quickstart.py --supervised \\
        --inject 'crash@1,nan_params@3:0'
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro.core import (  # noqa: E402
    Burgers1D, CartesianDecomposition, DDConfig, ReferenceTrainer, XPINN,
    build_topology, evaluate_l2, restore_train_state, save_train_state,
)
from repro.core.nets import MLPConfig, SubdomainModelConfig  # noqa: E402
from repro.data import make_batch  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1500)
    ap.add_argument("--nx", type=int, default=2)
    ap.add_argument("--nt", type=int, default=2)
    ap.add_argument("--path", choices=("jvp", "pallas"), default="pallas",
                    help="residual evaluation: fused kernel (default) or the "
                         "per-point jvp oracle")
    ap.add_argument("--chunk", type=int, default=250,
                    help="outer steps per device dispatch (guarded lax.scan "
                         "driver); 1 falls back to the per-step jit loop")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint the TrainState every N steps (0 = off)")
    ap.add_argument("--ckpt", default="ckpt_quickstart",
                    help="checkpoint directory for --save-every")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume from the latest checkpoint under DIR")
    ap.add_argument("--supervised", action="store_true",
                    help="route training through the fault-tolerant chunk "
                         "supervisor: checkpoints to --ckpt, recovers crashes "
                         "and NaN divergence, and makes --resume ELASTIC (the "
                         "checkpoint may have been taken at a different "
                         "--nx/--nt)")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="fault schedule for --supervised: comma-separated "
                         "kind@chunk[:subdomain][*delay] items, e.g. "
                         "'crash@1,nan_params@2:0,straggler@3*0.5'")
    args = ap.parse_args()
    if args.inject and not args.supervised:
        ap.error("--inject requires --supervised")

    pde = Burgers1D()
    decomp = CartesianDecomposition(((-1, 1), (0, 1)), args.nx, args.nt)
    topo = build_topology(decomp, n_iface=20)
    print(f"[quickstart] {decomp.n_sub} space-time subdomains, "
          f"{int(topo.edge_mask.sum()) // 2} interfaces, {topo.n_slots} exchange slots")

    model_cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 24, 4)})
    batch = make_batch(decomp, topo, pde, n_res=1000, n_bnd=80,
                       rng=np.random.default_rng(0))
    trainer = ReferenceTrainer(pde, model_cfg, topo,
                               DDConfig(method=XPINN, residual_path=args.path),
                               lrs=2e-3)
    state = trainer.init(0)
    done = 0
    if args.resume and not args.supervised:
        state = restore_train_state(args.resume, state)
        done = int(state.step)
        print(f"[quickstart] resumed from {args.resume} at step {done}")
    b = batch.device_arrays()

    if args.supervised:
        from repro.runtime import (ChaosInjector, Supervisor, SupervisorConfig,
                                   elastic_resume, parse_faults)

        if args.resume:
            state, meta = elastic_resume(args.resume, trainer, decomp)
            done = int(np.asarray(state.step))
            sig = (meta.get("supervisor") or {}).get("decomp") or {}
            old_n = sig.get("n_sub", decomp.n_sub)
            print(f"[quickstart] elastic resume from {args.resume} at step "
                  f"{done} (checkpoint n_sub={old_n} -> {decomp.n_sub})")
        chunk = max(args.chunk, 1)
        cfg_sup = SupervisorConfig(
            chunk_steps=chunk,
            ckpt_every_chunks=(max(1, args.save_every // chunk)
                               if args.save_every else 1))
        # ChaosInjector so storage faults (ckpt.bit_flip@2, ...) compose with
        # the compute matrix in the same --inject spec; without any it behaves
        # exactly like the plain FaultInjector
        injector = (ChaosInjector(parse_faults(args.inject),
                                  roots={"ckpt": args.ckpt})
                    if args.inject else None)
        sup = Supervisor(trainer, args.ckpt, cfg_sup, injector, decomp=decomp)
        state, report = sup.run(state, b, args.steps)
        for ev in report.events:
            print(f"[supervisor] {ev}")
        print(f"[supervisor] chunks={report.chunks} restarts={report.restarts}"
              f" crashes={report.crashes} guard_trips={report.guard_trips} "
              f"stragglers={report.stragglers} corruptions={report.corruptions}")
        err = evaluate_l2(decomp, model_cfg, state.params, trainer.act_codes,
                          pde)
        print(f"[quickstart] final rel L2 error vs Cole-Hopf exact: {err:.4f}")
        assert err < 0.5, "did not converge"
        return

    report_every = 250
    t0 = time.time()
    t_done = done
    while done < args.steps:
        n = min(max(args.chunk, 1), args.steps - done)
        if args.chunk <= 1:
            state, terms = trainer.step(state, b)
            n, last_loss = 1, float(np.asarray(terms["loss"]).sum())
        else:
            state, terms, health = trainer.run_chunk_guarded(state, b, n)
            if not bool(health["ok"]):
                raise RuntimeError(
                    f"[quickstart] loss or parameters went non-finite at step "
                    f"{done + int(health['good_steps'])}: ok_sub="
                    f"{np.asarray(health['ok_sub']).tolist()}; --supervised "
                    "rolls back and retries")
            last_loss = float(np.asarray(terms["loss"])[-1].sum())
        prev, done = done, done + n
        if args.save_every and done // args.save_every > prev // args.save_every:
            save_train_state(args.ckpt, state)
        if done == args.steps or done // report_every > prev // report_every:
            err = evaluate_l2(decomp, model_cfg, state.params, trainer.act_codes, pde)
            print(f"[quickstart] step {done:5d} loss={last_loss:8.4f} rel_L2={err:.4f} "
                  f"({(done - t_done)/(time.time()-t0):.1f} it/s)")

    err = evaluate_l2(decomp, model_cfg, state.params, trainer.act_codes, pde)
    print(f"[quickstart] final rel L2 error vs Cole-Hopf exact: {err:.4f}")
    assert err < 0.5, "did not converge"


if __name__ == "__main__":
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    main()
