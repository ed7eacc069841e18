"""Lid-driven cavity with distributed cPINN (paper §7.4, Fig 5).

Steady incompressible Navier-Stokes at Re=100 on [0,1]^2, 2x2 subdomains,
normal-flux interface continuity (Table 1 fluxes).  Validates the centerline
u-velocity against Ghia et al. [37] reference values.

    PYTHONPATH=src python examples/navier_stokes_cavity.py [--steps 4000]
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (  # noqa: E402
    CPINN, CartesianDecomposition, DDConfig, LossWeights, NavierStokes2D,
    ReferenceTrainer, build_topology,
)
from repro.core import nets  # noqa: E402
from repro.core.nets import MLPConfig, SubdomainModelConfig  # noqa: E402
from repro.data import make_batch  # noqa: E402

# Ghia et al. (1982) Re=100: u along the vertical centerline x=0.5
GHIA_Y = np.array([0.0547, 0.1719, 0.2813, 0.4531, 0.5000, 0.6172, 0.7344, 0.8516, 0.9531])
GHIA_U = np.array([-0.0372, -0.1015, -0.1566, -0.2109, -0.2058, -0.1364, 0.0033, 0.2315, 0.6872])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4000)
    ap.add_argument("--chunk", type=int, default=500,
                    help="outer steps per device dispatch (guarded lax.scan "
                         "driver)")
    args = ap.parse_args()

    pde = NavierStokes2D(re=100.0)
    decomp = CartesianDecomposition(((0, 1), (0, 1)), 2, 2)
    topo = build_topology(decomp, n_iface=32)
    # paper §7.4: 5 hidden layers x 80 neurons (reduced width for CPU speed)
    model_cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 3, 40, 5)})
    batch = make_batch(decomp, topo, pde, n_res=1500, n_bnd=120,
                       rng=np.random.default_rng(0))
    trainer = ReferenceTrainer(pde, model_cfg, topo,
                               DDConfig(method=CPINN, weights=LossWeights(data=40.0)),
                               lrs=6e-4)
    state = trainer.init(0)
    b = batch.device_arrays()

    t0 = time.time()
    done = 0
    while done < args.steps:
        n = min(max(args.chunk, 1), args.steps - done, 500 - done % 500)
        state, terms, health = trainer.run_chunk_guarded(state, b, n)
        if not bool(health["ok"]):
            raise RuntimeError(
                f"[cavity] loss or parameters went non-finite at step "
                f"{done + int(health['good_steps'])}: ok_sub="
                f"{np.asarray(health['ok_sub']).tolist()}")
        done += n
        if done % 500 == 0 or done == args.steps:
            loss = float(np.asarray(terms["loss"])[-1].sum())
            print(f"[cavity] step {done:5d} loss={loss:9.5f} "
                  f"({done/(time.time()-t0):.1f} it/s)")

    # stitched centerline profile (eq. 4) vs Ghia reference
    pts = np.stack([np.full_like(GHIA_Y, 0.5), GHIA_Y], axis=1).astype(np.float32)
    pred = np.zeros(len(pts))
    for q in range(decomp.n_sub):
        inside = decomp.subdomain_contains(q, pts)
        if inside.any():
            p_q = jax.tree.map(lambda x: x[q], state.params)
            u = nets.model_apply(model_cfg, p_q, jnp.asarray(pts[inside]),
                                 trainer.act_codes[q])
            pred[inside] = np.asarray(u[:, 0])
    rms = float(np.sqrt(np.mean((pred - GHIA_U) ** 2)))
    print("[cavity]   y      u_pred   u_Ghia")
    for y, up, ug in zip(GHIA_Y, pred, GHIA_U):
        print(f"[cavity] {y:6.4f} {up:8.4f} {ug:8.4f}")
    print(f"[cavity] centerline RMS error vs Ghia: {rms:.4f}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    main()
