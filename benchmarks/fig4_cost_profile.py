"""Paper Fig 4: PINN cost profile — data-loss vs residual-loss vs backward time as
functions of (#residual points | depth | width), 1-D Burgers, single worker.

The paper's finding: residual-loss evaluation (AD graph traversal) dominates and
grows with all three knobs.  We time the three phases with separate jitted
closures on CPU.

``--path pallas`` additionally times the fused-kernel residual path
(``losses.residual_eval`` with a ResidualPath — the production hot path: one
fused pass for u / du / d²u instead of per-point jvp closures under vmap; on
non-TPU backends this compiles the batched jnp recurrence, on TPU the Pallas
kernel) and writes ``BENCH_residual.json`` at the repo root with both timings
per configuration.

``--e2e`` times WHOLE training steps on the quickstart workload (2x2 Burgers
XPINN) instead of isolated loss phases: the per-step jit loop vs the scanned
single-dispatch ``run_chunk`` driver, on both residual paths, and writes
``BENCH_step.json`` at the repo root (steps/s + dispatch/entry counts).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# allow `python benchmarks/fig4_cost_profile.py` (script mode) as well as -m,
# with or without PYTHONPATH=src
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import losses
from repro.core.losses import LossWeights, ResidualPath, vanilla_pinn_loss
from repro.core.nets import MLPConfig, SubdomainModelConfig, init_model, ACT_TANH
from repro.core.domain import CartesianDecomposition
from repro.core.pdes import Burgers1D
from repro.data import make_vanilla_batch

from benchmarks.common import REPO, bench_path, emit, history_append


def _phases(pde, cfg, params, batch, res_path: ResidualPath | None = None):
    w = LossWeights()

    @jax.jit
    def data_loss(p):
        from repro.core import nets
        u_fn = nets.scalar_field_fn(cfg, p, ACT_TANH, None)
        pred = jax.vmap(u_fn)(batch.data_pts)
        return jnp.sum((pred - batch.data_vals) ** 2)

    @jax.jit
    def res_loss(p):
        r = losses.residual_eval(pde, cfg, p, ACT_TANH, None, batch.res_pts, res_path)
        return jnp.sum(r ** 2)

    @jax.jit
    def forward(p):
        return vanilla_pinn_loss(pde, cfg, w, p, ACT_TANH, None, batch,
                                 path=res_path)[0]

    @jax.jit
    def grad(p):
        return jax.grad(lambda pp: vanilla_pinn_loss(pde, cfg, w, pp, ACT_TANH,
                                                     None, batch, path=res_path)[0])(p)

    return data_loss, res_loss, forward, grad


def _interleaved(fns: dict, arg, iters: int) -> dict:
    """Per-round us samples per candidate, measured in ROUND-ROBIN.

    The container's CPU quota drifts on minute scales; timing candidate A for
    its full budget and then candidate B confounds the comparison with the
    drift.  One pass per round over every candidate puts competing paths
    seconds (not minutes) apart, so PAIRED per-round statistics (differences,
    ratios) see the same machine.  Returns the raw per-round lists — derive
    medians / paired diffs from them, never a difference of medians.
    """
    import time as _time

    for fn in fns.values():
        jax.block_until_ready(fn(arg))  # compile + warm
        jax.block_until_ready(fn(arg))
    ts = {k: [] for k in fns}
    for _ in range(iters):
        for k, fn in fns.items():
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(arg))
            ts[k].append((_time.perf_counter() - t0) * 1e6)
    return {k: np.asarray(v) for k, v in ts.items()}


def _med(x) -> float:
    return float(np.median(x))


def _paired_ratio(num, den):
    """Median of per-round ratios over rounds where both diffs are positive
    (a quota dip can make a small same-round difference go non-positive);
    falls back to the ratio of median diffs, and to NaN when even the medians
    are non-positive — a visible sentinel, never a fabricated huge speedup."""
    num, den = np.asarray(num), np.asarray(den)
    ok = (num > 0) & (den > 0)
    if ok.any():
        return float(np.median(num[ok] / den[ok]))
    mn, md = float(np.median(num)), float(np.median(den))
    return mn / md if mn > 0 and md > 0 else float("nan")


def run(iters: int = 10, path: str = "jvp", smoke: bool = False):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 1, 1)
    rng = np.random.default_rng(0)
    rows, records = [], []
    pallas = path == "pallas"

    def one(tag, n_res, depth, width):
        cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
        params = init_model(cfg, jax.random.PRNGKey(0))
        batch = make_vanilla_batch(dec, pde, n_res, 200, rng)
        d, r, fwd, grad = _phases(pde, cfg, params, batch)
        fns = {"data": d, "res_jvp": r, "fwd_jvp": fwd, "grad_jvp": grad}
        if pallas:
            # fused hand-derived backward (production) vs checkpointed-ref
            # oracle — SAME forward, the selector changes only the reverse pass
            _, rk, fwd_p, grad_fused = _phases(pde, cfg, params, batch,
                                               ResidualPath(act="tanh"))
            _, _, _, grad_ref = _phases(pde, cfg, params, batch,
                                        ResidualPath(act="tanh", bwd="ref"))
            fns.update(res_pallas=rk, fwd_pallas=fwd_p,
                       grad_pallas_fused=grad_fused, grad_pallas_ref=grad_ref)
        t = _interleaved(fns, params, iters)
        # forward and backward wall-time as SEPARATE columns: bwd = grad - fwd
        # (the VJP application alone; fwd is the loss evaluation it shares).
        # All diffs/ratios are PAIRED within a round — same-machine samples —
        # never a difference of medians (quota drift can make that negative).
        bwd_jvp_r = t["grad_jvp"] - t["fwd_jvp"]
        rows.append((f"fig4/{tag}/data_loss", round(_med(t["data"]), 1), "us"))
        rows.append((f"fig4/{tag}/residual_loss",
                     round(_med(t["res_jvp"]), 1), "us"))
        rows.append((f"fig4/{tag}/forward", round(_med(t["fwd_jvp"]), 1), "us"))
        rows.append((f"fig4/{tag}/backward", round(_med(bwd_jvp_r), 1), "us"))
        if pallas:
            bwd_fused_r = t["grad_pallas_fused"] - t["fwd_pallas"]
            bwd_ref_r = t["grad_pallas_ref"] - t["fwd_pallas"]
            sp_ref = _paired_ratio(bwd_ref_r, bwd_fused_r)
            sp_jvp = _paired_ratio(bwd_jvp_r, bwd_fused_r)
            sp_res = _paired_ratio(t["res_jvp"], t["res_pallas"])
            rows.append((f"fig4/{tag}/residual_loss_pallas",
                         round(_med(t["res_pallas"]), 1), "us"))
            rows.append((f"fig4/{tag}/residual_speedup", round(sp_res, 2), "x"))
            rows.append((f"fig4/{tag}/forward_pallas",
                         round(_med(t["fwd_pallas"]), 1), "us"))
            rows.append((f"fig4/{tag}/backward_pallas_fused",
                         round(_med(bwd_fused_r), 1), "us"))
            rows.append((f"fig4/{tag}/backward_pallas_ref",
                         round(_med(bwd_ref_r), 1), "us"))
            rows.append((f"fig4/{tag}/backward_speedup_vs_ref",
                         round(sp_ref, 2), "x"))
            rows.append((f"fig4/{tag}/backward_speedup_vs_jvp",
                         round(sp_jvp, 2), "x"))
            records.append({
                "config": tag, "n_res": n_res, "depth": depth, "width": width,
                "backend": jax.default_backend(),
                "jvp_us": round(_med(t["res_jvp"]), 1),
                "pallas_us": round(_med(t["res_pallas"]), 1),
                "speedup": round(sp_res, 3),
                # fwd/bwd split columns (whole vanilla-PINN loss): the
                # backward-kernel win is tracked per backward path
                "fwd_jvp_us": round(_med(t["fwd_jvp"]), 1),
                "bwd_jvp_us": round(_med(bwd_jvp_r), 1),
                "fwd_pallas_us": round(_med(t["fwd_pallas"]), 1),
                "bwd_pallas_fused_us": round(_med(bwd_fused_r), 1),
                "bwd_pallas_ref_us": round(_med(bwd_ref_r), 1),
                "bwd_speedup_vs_ref": round(sp_ref, 3),
                "bwd_speedup_vs_jvp": round(sp_jvp, 3),
            })

    if smoke:
        one("nres=1000", 1000, 4, 40)
    else:
        # (a) vs #residual points (200 data pts, 8x40 net)
        for n in (1000, 4000, 10000):
            one(f"nres={n}", n, 8, 40)
        # (b) vs depth (10000 residual points, width 40)
        for depth in (4, 8, 12):
            one(f"depth={depth}", 10000, depth, 40)
        # (c) vs width (10000 residual points, 8 hidden layers)
        for width in (20, 40, 80):
            one(f"width={width}", 10000, 8, width)

    if pallas:
        # smoke runs get their own gitignored file so a CI smoke pass never
        # clobbers the full-grid measurement artifact EXPERIMENTS.md cites
        out = bench_path("residual", smoke)
        with open(out, "w") as f:
            json.dump({"unit": "us", "backend": jax.default_backend(),
                       "iters": iters, "rows": records}, f, indent=1)
        print(f"wrote {out}")
    history_append("fig4", rows, smoke=smoke)
    return rows


def bwd_parity_rows(steps: int = 10):
    """Smoke acceptance: the backward selector round-trips — a quickstart-style
    chunk trained with the hand-derived fused backward lands on the same loss
    as the checkpointed-ref backward.  Raises on divergence."""
    from repro.core import (Burgers1D as _B, CartesianDecomposition as _C,
                            DDConfig, ReferenceTrainer, XPINN, build_topology)
    from repro.data import make_batch

    pde = _B()
    dec = _C(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=20)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 24, 4)})
    b = make_batch(dec, topo, pde, n_res=250, n_bnd=80,
                   rng=np.random.default_rng(0)).device_arrays()
    final = {}
    for bp in ("fused", "ref"):
        tr = ReferenceTrainer(pde, cfg, topo,
                              DDConfig(method=XPINN, residual_path="pallas",
                                       backward_path=bp), lrs=2e-3)
        _, terms = tr.run_chunk(tr.init(0), b, steps)
        final[bp] = float(np.sum(np.asarray(terms["loss"])[-1]))
    if not np.allclose(final["fused"], final["ref"], rtol=5e-3, atol=1e-6):
        raise AssertionError(f"backward selector diverged: {final}")
    return [("fig4/bwd_parity/fused_loss", round(final["fused"], 6), ""),
            ("fig4/bwd_parity/ref_loss", round(final["ref"], 6), "")]


def run_e2e(iters: int = 3, smoke: bool = False):
    """Whole-step timing: per-step jit loop vs the scanned run_chunk driver.

    The quickstart workload (2x2 space-time Burgers XPINN).  Per residual path
    ("jvp" oracle / "pallas" fused megabatch) measures steps/s for (a) a Python
    loop of ``trainer.step`` — one jit dispatch and, pre-megabatch, 4 network
    entries per step (the PR-1 dispatch pattern) — and (b) one
    ``trainer.run_chunk`` dispatch per chunk.  Writes BENCH_step.json.
    """
    import time

    from repro.core import (Burgers1D as _B, CartesianDecomposition, DDConfig,
                            ReferenceTrainer, XPINN, build_topology)
    from repro.data import make_batch

    pde = _B()
    n_res, steps = (250, 20) if smoke else (1000, 100)
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=20)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 24, 4)})
    batch = make_batch(dec, topo, pde, n_res=n_res, n_bnd=80,
                       rng=np.random.default_rng(0))
    b = batch.device_arrays()

    rows, records = [], {}
    # "pallas" = fused hand-derived backward (production default);
    # "pallas-refbwd" = same forward, PR-1 checkpointed-ref backward — the
    # end-to-end measure of the backward-kernel win
    variants = (("jvp", "jvp", "fused"), ("pallas", "pallas", "fused"),
                ("pallas-refbwd", "pallas", "ref"))
    for path, res_path, bwd_path in variants:
        tr = ReferenceTrainer(pde, cfg, topo,
                              DDConfig(method=XPINN, residual_path=res_path,
                                       backward_path=bwd_path), lrs=2e-3)

        def loop_once():
            st = tr.init(0)
            for _ in range(steps):
                st, terms = tr.step(st, b)
            jax.block_until_ready(terms["loss"])

        def chunk_once():
            st = tr.init(0)
            st, terms = tr.run_chunk(st, b, steps)
            jax.block_until_ready(terms["loss"])

        timings = {}
        for tag, fn in (("loop", loop_once), ("chunk", chunk_once)):
            fn()  # compile
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            timings[tag] = steps / float(np.median(ts))
            rows.append((f"fig4/e2e/{path}/{tag}_steps_per_s",
                         round(timings[tag], 2), "it/s"))
        rows.append((f"fig4/e2e/{path}/chunk_speedup",
                     round(timings["chunk"] / timings["loop"], 2), "x"))
        records[path] = {"loop_it_s": round(timings["loop"], 2),
                         "chunk_it_s": round(timings["chunk"], 2),
                         "speedup": round(timings["chunk"] / timings["loop"], 3)}

    quickstart = None
    if not smoke:
        # the acceptance workload: examples/quickstart.py --steps 500 end to
        # end (training + periodic eval), parsed from its own report.  The
        # child needs the device this process already holds, so it can only
        # run where that device is the CPU.
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "the quickstart acceptance child cannot share the "
                f"{jax.default_backend()} device held by this process")
        import re
        import subprocess
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", "quickstart.py"),
             "--steps", "500"],
            capture_output=True, text=True, timeout=1200)
        if res.returncode != 0:
            raise RuntimeError(
                f"quickstart acceptance run failed (rc={res.returncode}):\n"
                f"{res.stderr[-2000:]}")
        m = re.findall(r"step\s+500.*\((\d+\.?\d*) it/s\)", res.stdout)
        if not m:
            raise RuntimeError(
                f"no step-500 rate in quickstart output:\n{res.stdout[-2000:]}")
        quickstart = float(m[-1])
        rows.append(("fig4/e2e/quickstart_500_steps_per_s", quickstart, "it/s"))

    bwd_e2e = round(records["pallas"]["chunk_it_s"]
                    / records["pallas-refbwd"]["chunk_it_s"], 3)
    rows.append(("fig4/e2e/bwd_fused_vs_ref_chunk_speedup", bwd_e2e, "x"))
    out = bench_path("step", smoke)
    with open(out, "w") as f:
        json.dump({
            "workload": f"quickstart 2x2 Burgers XPINN, n_res={n_res}, "
                        f"chunk={steps} steps",
            "backend": jax.default_backend(), "iters": iters,
            "paths": records,
            "bwd_fused_vs_ref_chunk_speedup": bwd_e2e,
            "quickstart_500_it_s": quickstart,
            # static dispatch accounting (see EXPERIMENTS.md §Step fusion)
            "entries_per_loss_eval": {"pre_megabatch": 3, "megabatch": 1},
            "entries_per_step": {"pre_megabatch": 4, "megabatch": 1},
            "dispatches_per_100_steps": {"loop": 100, "chunk": round(100 / steps, 2)},
        }, f, indent=1)
    print(f"wrote {out}")
    history_append("fig4_e2e", rows, smoke=smoke)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--path", choices=("jvp", "pallas"), default="jvp",
                    help="residual evaluation: per-point jvp closures or the "
                         "fused kernel (also times jvp for the comparison)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true", help="single tiny config")
    ap.add_argument("--e2e", action="store_true",
                    help="time whole run_chunk training steps (loop vs scan) "
                         "and write BENCH_step.json")
    args = ap.parse_args()
    if args.e2e:
        emit(run_e2e(iters=max(1, args.iters // 3), smoke=args.smoke))
        return
    emit(run(iters=args.iters, path=args.path, smoke=args.smoke))


if __name__ == "__main__":
    main()
