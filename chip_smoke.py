"""Chip smoke test: the DD-PINN main path, train -> export -> serve, on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one rank per subdomain, four chips

One process runs the phases in order and the first failure exits non-zero:

1. device: JAX must see TPU devices; there is no CPU fallback;
2. train: the quickstart problem (viscous Burgers, 2x2 space-time XPINN, one
   tanh MLP per subdomain) at the paper's Table-2 size (80,000 residual
   points in all, 80 boundary and 20 interface points, width 20, depth 5)
   on the fused Pallas path, through ``Supervisor`` + ``run_chunk_guarded``;
   the compiled chunk must hold the Pallas kernels and the fused backward;
3. oracle: loss and gradients of the fused path against the per-point jvp
   oracle, both at ``highest`` matmul precision;
4. serve: export the trained field, load it into a ``FieldEngine`` (the
   compiled ``_kernel2`` path), compare with the jvp oracle, then answer
   requests through ``repro.launch.serve_field``.

The last line of standard output is one JSON object naming the device.
``--four-chips`` runs only ``DistributedDDTrainer`` (shard_map over four
chips, ppermute halo) against ``ReferenceTrainer`` on one of those chips, for
the 2x2 XPINN and the Table-2 4x1 cPINN strip.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import (  # noqa: E402
    CPINN, XPINN, Burgers1D, CartesianDecomposition, DDConfig,
    DistributedDDTrainer, ReferenceTrainer, build_topology, halo, losses,
    nets, restore_train_state,
)
from repro.core.nets import MLPConfig, SubdomainModelConfig  # noqa: E402
from repro.data import make_batch  # noqa: E402

OUT = os.path.join(REPO, "chip_smoke_out")

# Largest relative error of the fused path against the jvp oracle, both at
# `highest` matmul precision: loss, every gradient leaf, and the served u,
# grad u and residual.  An error is max|fused - oracle| over the largest
# |oracle| of the same array.  The CPU rehearsal at this size (Pallas
# interpreter and batched-jnp recurrence) measured at most 1.0e-7 on the
# loss, 3.8e-6 on the gradients and 7.2e-7 on the served quantities; the
# bound leaves a factor of 25 over the largest for summation order on the
# chip and stays well below the ~1e-3 that one bf16 pass would show.
TOL = 1e-4
# Four chips against one: parameters (largest absolute difference) and the
# per-step losses (relative) after `compare_steps` Adam steps.  The rehearsal
# on four host devices measured 6.0e-8 and 1.3e-7.
TOL_FOUR_CHIPS = 1e-4


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


@dataclass(frozen=True)
class Size:
    n_res: int = 20_000        # per subdomain: 80,000 in all (paper Table 2)
    n_bnd: int = 80
    n_iface: int = 20
    width: int = 20
    depth: int = 5
    chunk_steps: int = 100
    chunks: int = 3
    n_serve: int = 4096
    serve_requests: int = 50
    compare_steps: int = 20


TABLE2 = Size()


@dataclass
class Problem:
    pde: Burgers1D
    decomp: CartesianDecomposition
    topo: object
    model_cfg: SubdomainModelConfig
    method: int
    batch: object              # device SubBatch, stacked over subdomains


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def burgers_problem(size: Size, nx: int = 2, nt: int = 2,
                    method: int = XPINN, seed: int = 0) -> Problem:
    """The quickstart's Burgers problem on an nx x nt space-time grid."""
    pde = Burgers1D()
    decomp = CartesianDecomposition(((-1, 1), (0, 1)), nx, nt)
    topo = build_topology(decomp, n_iface=size.n_iface)
    cfg = SubdomainModelConfig(
        nets={"u": MLPConfig(2, 1, size.width, size.depth)})
    batch = make_batch(decomp, topo, pde, n_res=size.n_res, n_bnd=size.n_bnd,
                       rng=np.random.default_rng(seed)).device_arrays()
    return Problem(pde, decomp, topo, cfg, method, batch)


def make_trainer(prob: Problem, residual_path: str = "pallas", cls=None,
                 **kw):
    cls = cls or ReferenceTrainer
    return cls(prob.pde, prob.model_cfg, prob.topo,
               DDConfig(method=prob.method, residual_path=residual_path),
               lrs=2e-3, **kw)


def rel_err(got, want) -> float:
    """max|got - want| / max|want| (0 when both are all zero)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.max(np.abs(want))
    diff = np.max(np.abs(got - want))
    return float(diff / scale) if scale > 0 else float(diff)


def tree_rel_err(got, want) -> float:
    return max(rel_err(g, w)
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# --------------------------------------------------------------- 1. device

def check_device(want: int = 1) -> dict:
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU: JAX found {len(devs)} {d.platform} device(s); this "
          "smoke test runs only on the chip and never falls back to the CPU")
    check(len(devs) >= want, f"need {want} TPU chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ---------------------------------------------------------------- 2. train

def check_chunk_hlo(hlo: str) -> None:
    """The compiled training chunk runs the Pallas kernels with the fused
    reverse sweep, and nothing stepped down to the checkpointed backward."""
    check("tpu_custom_call" in hlo, "training chunk holds no Pallas kernel")
    check("pinn2-bwd-fused" in hlo, "training chunk lacks the fused backward")
    check("pinn2-bwd-ref" not in hlo,
          "training chunk stepped down to the checkpointed backward")


def train(prob: Problem, size: Size, out_dir: str):
    """Supervised chunked training; returns (trainer, state, info)."""
    from repro.checkpoint import ckpt
    from repro.obs import make_obs, read_events
    from repro.runtime import Supervisor, SupervisorConfig

    tr = make_trainer(prob)
    state = tr.init(0)
    t0 = time.perf_counter()
    compiled = tr._chunk_guarded.lower(state, prob.batch, size.chunk_steps,
                                       jnp.ones_like(tr.lrs)).compile()
    compile_s = time.perf_counter() - t0
    ckpt_dir = os.path.join(out_dir, "ckpt")
    events = os.path.join(out_dir, "train_events.jsonl")
    obs = make_obs(events)
    total = size.chunks * size.chunk_steps
    # one periodic save, at the end (the supervisor also anchors step 0)
    sup = Supervisor(tr, ckpt_dir,
                     SupervisorConfig(chunk_steps=size.chunk_steps,
                                      ckpt_every_chunks=size.chunks),
                     decomp=prob.decomp, obs=obs)
    t0 = time.perf_counter()
    try:
        state, report = sup.run(state, prob.batch, total)
    finally:
        obs.close()
    train_s = time.perf_counter() - t0
    chunk_losses = [e["loss"] for e in read_events(events)
                    if e["kind"] == "chunk"]
    check(report.restarts == 0 and report.guard_trips == 0,
          f"supervisor recovered from faults: {report.events}")
    check(len(chunk_losses) == size.chunks,
          f"{len(chunk_losses)} chunks committed, expected {size.chunks}")
    check(all(np.isfinite(chunk_losses)), f"non-finite loss: {chunk_losses}")
    check(chunk_losses[-1] < chunk_losses[0],
          f"loss did not fall: {chunk_losses}")
    check(ckpt.latest_step(ckpt_dir) == total,
          f"latest checkpoint is step {ckpt.latest_step(ckpt_dir)}, "
          f"expected {total}")
    restored = restore_train_state(ckpt_dir, tr.init(0))
    check(tree_rel_err(restored.params, state.params) == 0.0,
          "checkpoint does not restore the trained parameters")
    return tr, state, {"hlo": compiled.as_text(), "compile_s": compile_s,
                       "train_s": train_s, "chunk_losses": chunk_losses,
                       "chunk_walltimes_s": list(report.walltimes)}


# --------------------------------------------------------------- 3. oracle

def loss_and_grads(tr, params, batch):
    """Summed subdomain loss and its gradients, as the trainer's step
    differentiates them (received payloads enter as constants)."""
    def total(p):
        outs = jax.vmap(tr._net_eval)(p, tr.act_codes, batch)
        recv = jax.lax.stop_gradient(
            halo.exchange_tree_gather(outs[1], tr.topo))
        tot, _ = jax.vmap(tr._assemble)(batch, *outs, recv)
        return jnp.sum(tot)
    return jax.jit(jax.value_and_grad(total))(params)


def oracle(prob: Problem, fused_tr, params) -> dict:
    """Fused-path loss/gradients against the jvp oracle."""
    jvp_tr = make_trainer(prob, residual_path="jvp")
    jvp_tr.init(0)
    with jax.default_matmul_precision("highest"):
        lf, gf = loss_and_grads(fused_tr, params, prob.batch)
        lo, go = loss_and_grads(jvp_tr, params, prob.batch)
    lf_d, gf_d = loss_and_grads(fused_tr, params, prob.batch)
    lo_d, go_d = loss_and_grads(jvp_tr, params, prob.batch)
    errs = {
        "loss_highest": rel_err(lf, lo),
        "grad_highest": tree_rel_err(gf, go),
        # default precision, each side against the `highest` oracle
        "loss_fused_default": rel_err(lf_d, lo),
        "grad_fused_default": tree_rel_err(gf_d, go),
        "loss_jvp_default": rel_err(lo_d, lo),
        "grad_jvp_default": tree_rel_err(go_d, go),
    }
    for k in ("loss_highest", "grad_highest"):
        check(errs[k] <= TOL, f"fused {k} error {errs[k]:.3e} > {TOL:.0e}")
    return errs


# ---------------------------------------------------------------- 4. serve

def serve_oracle(prob: Problem, params, act_codes, pts) -> dict:
    """u, grad u and residual from the per-point jvp closures, at points
    that lie inside exactly one subdomain (the engine's stitch is then a
    plain lookup).  Returns the oracle arrays and the row index they cover."""
    owner = np.stack([prob.decomp.subdomain_contains(q, pts)
                      for q in range(prob.decomp.n_sub)])
    single = owner.sum(axis=0) == 1
    idx, want = [], {"u": [], "grad_u": [], "residual": []}
    cfg, pde = prob.model_cfg, prob.pde
    for q in range(prob.decomp.n_sub):
        rows = np.flatnonzero(owner[q] & single)
        x = jnp.asarray(pts[rows])
        pq = jax.tree.map(lambda a: a[q], params)
        code = int(act_codes[q])
        u_one = lambda xi: nets.model_apply(cfg, pq, xi[None], code, None)[0]
        want["u"].append(nets.model_apply(cfg, pq, x, code, None))
        want["grad_u"].append(
            jnp.swapaxes(jax.vmap(jax.jacfwd(u_one))(x), 1, 2))
        want["residual"].append(
            losses.residual_eval(pde, cfg, pq, code, None, x, None))
        idx.append(rows)
    return ({k: np.concatenate([np.asarray(a) for a in v])
             for k, v in want.items()}, np.concatenate(idx))


def serve(prob: Problem, params, act_codes, size: Size, out_dir: str,
          step: int = 0) -> dict:
    """Export -> load -> FieldEngine against the oracle -> serve_field."""
    from repro.launch import serve_field
    from repro.serve import FieldEngine
    from repro.serve.export import export_bundle, load_bundle

    bundle_dir = os.path.join(out_dir, "bundle")
    export_bundle(bundle_dir, params, prob.model_cfg, prob.decomp, act_codes,
                  pde=prob.pde, n_iface=size.n_iface, step=step)
    engine = FieldEngine(load_bundle(bundle_dir))
    check(engine.uniform_act == "tanh",
          "served field is not on the static-activation kernel path")
    pts = np.random.default_rng(1).uniform(
        (-1.0, 0.0), (1.0, 1.0), size=(size.n_serve, 2)).astype(np.float32)
    t0 = time.perf_counter()
    engine.evaluate(pts, order=2)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got_default = engine.evaluate(pts, order=2)
    again_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        got = engine.evaluate(pts, order=2)
        want, idx = serve_oracle(prob, params, act_codes, pts)
    check(len(idx) > 0.99 * len(pts), "too few points inside one subdomain")
    errs = {f"{k}_highest": rel_err(got[k][idx], want[k]) for k in want}
    for k, e in errs.items():
        check(e <= TOL, f"served {k} error {e:.3e} > {TOL:.0e}")
    # what the engine serves at default precision, against the same oracle
    errs.update({f"{k}_default": rel_err(got_default[k][idx], want[k])
                 for k in want})
    t0 = time.perf_counter()
    rc = serve_field.main(["--bundle", bundle_dir,
                           "--max-requests", str(size.serve_requests),
                           "--rate", "200", "--duration", "120",
                           "--heartbeat", "10", "--no-trace"])
    check(rc == 0, f"serve_field returned {rc}: unanswered tickets")
    return {"errors": errs, "evaluate_first_s": first_s,
            "evaluate_again_s": again_s,
            "serve_field_s": time.perf_counter() - t0, "serve_field_rc": rc}


# ----------------------------------------------------------- four chips

def four_chip_compare(size: Size) -> dict:
    """DistributedDDTrainer over four devices against ReferenceTrainer on
    the first, for the 2x2 XPINN and the 4x1 cPINN strip."""
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("sub",))
    out = {}
    for name, nx, nt, method in (("xpinn_2x2", 2, 2, XPINN),
                                 ("cpinn_4x1", 4, 1, CPINN)):
        prob = burgers_problem(size, nx, nt, method)
        dist = make_trainer(prob, cls=DistributedDDTrainer, mesh=mesh)
        ref = make_trainer(prob)
        s_d = dist.shard_state(dist.init(0))
        b_d = dist.shard_batch(prob.batch)
        leaf = jax.tree.leaves(s_d.params)[0]
        check(len(leaf.sharding.device_set) == 4,
              f"{name}: sharded state spans {leaf.sharding.device_set}")
        t0 = time.perf_counter()
        hlo = dist._chunk_guarded.lower(
            s_d, b_d, size.compare_steps,
            jnp.ones_like(dist.lrs)).compile().as_text()
        compile_s = time.perf_counter() - t0
        check("collective-permute" in hlo,
              f"{name}: compiled chunk has no collective-permute")
        t0 = time.perf_counter()
        s_d, t_d, h_d = dist.run_chunk_guarded(s_d, b_d, size.compare_steps)
        jax.block_until_ready(t_d)
        dist_s = time.perf_counter() - t0
        with jax.default_device(devs[0]):
            s_r, t_r, h_r = ref.run_chunk_guarded(ref.init(0), prob.batch,
                                                  size.compare_steps)
        for who, h in (("sharded", h_d), ("reference", h_r)):
            check(bool(h["ok"]), f"{name}: {who} chunk tripped its guard: "
                  f"ok_sub={np.asarray(h['ok_sub']).tolist()}")
        leaf = jax.tree.leaves(s_d.params)[0]
        check(len(leaf.sharding.device_set) == 4,
              f"{name}: trained state collapsed onto {leaf.sharding.device_set}")
        p_err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                    for a, b in zip(jax.tree.leaves(s_d.params),
                                    jax.tree.leaves(s_r.params)))
        l_err = rel_err(t_d["loss"], t_r["loss"])
        check(p_err <= TOL_FOUR_CHIPS,
              f"{name}: parameters differ by {p_err:.3e}")
        check(l_err <= TOL_FOUR_CHIPS, f"{name}: losses differ by {l_err:.3e}")
        out[name] = {"param_abs_err": p_err, "loss_rel_err": l_err,
                     "compile_s": compile_s, "dist_chunk_s": dist_s}
    return out


# ------------------------------------------------------------------- main

def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the one-rank-per-subdomain path on four "
                         "chips against ReferenceTrainer")
    args = ap.parse_args(argv)
    try:
        device = check_device(4 if args.four_chips else 1)
    except SmokeFailure as e:
        print(f"[smoke] FAILED device: {e}", file=sys.stderr)
        return 1
    from repro.utils import enable_compile_cache

    cache = enable_compile_cache()
    cache_before = _cache_entries(cache)
    log(f"jax {jax.__version__}, {device['kind']} x{device['count']}, "
        f"compile cache {cache} ({cache_before} entries)")
    phase = "setup"
    try:
        if args.four_chips:
            phase = "four-chips"
            res = four_chip_compare(TABLE2)
            for name, r in res.items():
                log(f"{name}: {json.dumps(r)}")
        else:
            shutil.rmtree(OUT, ignore_errors=True)
            os.makedirs(OUT)
            phase = "train"
            t0 = time.perf_counter()
            prob = burgers_problem(TABLE2)
            tr, state, info = train(prob, TABLE2, OUT)
            check_chunk_hlo(info.pop("hlo"))
            log(f"train: chunk compile {info['compile_s']:.2f} s, "
                f"{json.dumps(info)}, phase {time.perf_counter() - t0:.2f} s")
            log("train: compiled chunk holds tpu_custom_call and "
                "pinn2-bwd-fused, no pinn2-bwd-ref")
            phase = "oracle"
            t0 = time.perf_counter()
            errs = oracle(prob, tr, state.params)
            log(f"oracle: {json.dumps(errs)} (tolerance {TOL:.0e}), "
                f"phase {time.perf_counter() - t0:.2f} s")
            phase = "serve"
            t0 = time.perf_counter()
            res = serve(prob, state.params, tr.act_codes, TABLE2, OUT,
                        step=int(state.step))
            log(f"serve: {json.dumps(res)} (tolerance {TOL:.0e}), "
                f"phase {time.perf_counter() - t0:.2f} s")
    except SmokeFailure as e:
        print(f"[smoke] FAILED {phase}: {e}", file=sys.stderr)
        return 1
    log(f"compile cache {cache}: {cache_before} -> "
        f"{_cache_entries(cache)} entries")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
