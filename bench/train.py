"""Training cells: the program's guarded chunk driven as its supervisor drives
it, checked against the plain reference.

Set-up builds one trainer and its state from the seed (the benchmark's own
points and weights), compiles the guarded chunk and drives it through its
first chunk: that chunk's losses, parameters and Adam moments are what the
reference is compared with after the window.  The same trainer and state
then run the window: whole chunks, each followed by the fetch of its health
flags (``bool(health["ok"])``, as ``runtime/supervisor.py`` does), until
``--seconds`` have passed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, flops, harness, problems, reference


def program_parts(cfg: dict):
    """The program's PDE, decomposition, model and loss configuration for a
    configuration file (constructors only; the data is the benchmark's)."""
    from repro.core import (CartesianDecomposition, DDConfig, LossWeights,
                            PolygonDecomposition)
    from repro.core import pdes
    from repro.core.losses import METHODS
    from repro.core.nets import MLPConfig, SubdomainModelConfig

    kind = cfg["pde"]["kind"]
    if kind == "burgers1d":
        pde = pdes.Burgers1D()
        if abs(pde.nu - cfg["pde"]["nu"]) > 1e-12:
            raise ValueError(f"the program's viscosity {pde.nu} is not the "
                             f"configuration's {cfg['pde']['nu']}")
    elif kind == "heat2d_inverse":
        pde = pdes.HeatConduction2D()
    else:
        raise ValueError(kind)
    dom = cfg["domain"]
    if dom["kind"] == "cartesian":
        decomp = CartesianDecomposition(dom["bounds"], dom["nx"], dom["ny"])
    else:
        decomp = PolygonDecomposition([np.asarray(p) for p in dom["polygons"]])
    model = SubdomainModelConfig(nets={
        k: MLPConfig(n["in_dim"], n["out_dim"], n["width"], n["depth"])
        for k, n in cfg["nets"].items()})
    dd = DDConfig(method=METHODS[cfg["method"]],
                  weights=LossWeights(**cfg["loss_weights"]),
                  residual_path=cfg["residual_path"])
    return pde, decomp, model, dd


class Program:
    """The system under test, built once and driven by set-up and window."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devs):
        from repro.core import (DistributedDDTrainer, ReferenceTrainer,
                                build_topology)
        from repro.core.losses import SubBatch
        from repro.core.trainer import TrainState

        self.cfg, self.chunk = cfg, int(traffic["chunk_steps"])
        pde, decomp, model, dd = program_parts(cfg)
        topo = build_topology(decomp, int(cfg["n_iface"]))
        self.geo = problems.Geometry(cfg["domain"])
        self.data = problems.make_data(cfg, self.geo, seed)
        batch = SubBatch(**{k: jnp.asarray(v) for k, v in problems.pack_batch(
            self.data, topo.neighbor, int(cfg["n_iface"])).items()})
        n_sub = self.geo.n_sub
        weights = problems.make_weights(cfg, n_sub, seed)
        self.w0 = jax.tree.map(np.asarray, weights)
        kw = dict(act_codes=cfg["activations"], lrs=float(cfg["lr"]))
        if cfg["trainer"] == "distributed":
            from jax.sharding import Mesh

            tr = DistributedDDTrainer(pde, model, topo, dd,
                                      mesh=Mesh(np.array(devs), ("sub",)),
                                      **kw)
        else:
            tr = ReferenceTrainer(pde, model, topo, dd, **kw)
        st = tr.init(0)
        state = TrainState(params=weights, opt=st.opt, step=st.step)
        if cfg["trainer"] == "distributed":
            state, batch = tr.shard_state(state), tr.shard_batch(batch)
        self.trainer, self.state, self.batch = tr, state, batch

    def chunk_call(self):
        """One guarded chunk and its health fetch: the window's unit."""
        with harness.span("bench.train_chunk"):
            self.state, terms, health = self.trainer.run_chunk_guarded(
                self.state, self.batch, self.chunk)
        with harness.span("bench.fetch_health"):
            ok = bool(health["ok"])
            good = int(health["good_steps"])
        return terms, ok, good

    def first(self) -> dict:
        """Set-up's drive of the first chunk; what the check compares."""
        terms, ok, good = self.chunk_call()
        return {"loss": np.asarray(terms["loss"]), "ok": ok, "good": good,
                "params": jax.tree.map(np.asarray, self.state.params),
                "m": jax.tree.map(np.asarray, self.state.opt["m"]),
                "v": jax.tree.map(np.asarray, self.state.opt["v"])}

    def free(self):
        self.state = self.batch = self.trainer = None


def ref_inputs(prog: Program):
    d = prog.data
    data = {"res": [jnp.asarray(x, jnp.float32) for x in d.res],
            "data_pts": [jnp.asarray(x, jnp.float32) for x in d.data_pts],
            "data_vals": [jnp.asarray(x, jnp.float32) for x in d.data_vals],
            "data_comp": [jnp.asarray(x, jnp.float32) for x in d.data_comp],
            "ifaces": [{"pts": jnp.asarray(f.pts, jnp.float32),
                        "normal_a": jnp.asarray(f.normal_a, jnp.float32)}
                       for f in d.ifaces]}
    pairs = [(f.a, f.b) for f in d.ifaces]
    params = [reference.split(prog.w0, q) for q in range(prog.geo.n_sub)]
    return pairs, params, data


def run_reference(cfg: dict, prog: Program, steps: int, precision: str,
                  data_edit=None, **variant):
    """The reference's losses, parameters and first moments after ``steps``
    steps from the same weights and points, at ``precision`` (``variant``
    selects the control's products or a planted fault)."""
    pairs, params, data = ref_inputs(prog)
    if data_edit is not None:
        data = data_edit(data)
    with jax.default_matmul_precision(precision):
        loss, p, m, v = reference.train_jit(cfg, pairs, steps, **variant)(
            params, data)
    stack = lambda tree: jax.tree.map(lambda *xs: np.stack(  # noqa: E731
        [np.asarray(x) for x in xs]), *tree)
    return {"loss": np.asarray(loss), "params": stack(p), "m": stack(m),
            "v": stack(v)}


def readings(got: dict, want: dict, w0, n_sub: int, loss_steps: int,
             look: bool = False) -> dict:
    """The compared numbers.

    ``loss_gap``: each subdomain's loss in each of the first ``loss_steps``
    steps, worst relative gap.  ``grad_gap``: the gradient as the optimizer
    holds it after the first chunk, the root of Adam's second moment (with
    b2 = 0.999 nearly an even mean of the chunk's squared gradients), and
    ``step_gap``: the parameters' change over the chunk; both by the median
    leaf, since the worst leaf carries the chunk's last steps, where some
    seeds' trajectories part at the level of rounding (``look`` adds the
    worst-leaf readings and the whole chunk's loss gap, not compared)."""
    rms = lambda t: jax.tree.map(np.sqrt, t)  # noqa: E731
    g_got = compare.leaves(rms(got["v"]), n_sub)
    g_want = compare.leaves(rms(want["v"]), n_sub)
    keep = compare.kept(g_want)
    d_got = compare.leaves(jax.tree.map(lambda a, b: a - b, got["params"],
                                        w0), n_sub)
    d_want = compare.leaves(jax.tree.map(lambda a, b: a - b,
                                         want["params"], w0), n_sub)
    out = {
        "loss_gap": compare.loss_gap(got["loss"], want["loss"], loss_steps),
        "grad_gap": compare.norm_gap(g_got, g_want, keep, np.median),
        "step_gap": compare.norm_gap(d_got, d_want, keep, np.median),
    }
    if look:
        out["grad_gap_worst"] = compare.norm_gap(g_got, g_want, keep)
        out["step_gap_worst"] = compare.norm_gap(d_got, d_want, keep)
        out["loss_gap_chunk"] = compare.loss_gap(got["loss"], want["loss"],
                                                 len(want["loss"]))
    return out


def run(cell, devs, t_start: float, cc) -> dict:
    cfg, traffic = cell.config, cell.traffic
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        prog = Program(cfg, traffic, cell.seed, devs)
        first = prog.first()
        setup_s = time.perf_counter() - t_start
        harness.log(f"set-up {setup_s:.3f} s; first chunk ok={first['ok']}")
        steps = attempted = 0
        cc.on = True
        with harness.traced(cell.trace, cell.name) as tdir:
            with harness.span("bench.window"):
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < cell.seconds:
                    _terms, ok, good = prog.chunk_call()
                    steps += good
                    attempted += prog.chunk
                t1 = time.perf_counter()
        cc.on = False
    elapsed = t1 - t0
    harness.log(f"window {elapsed:.3f} s, {steps} steps, compiles in window: "
                f"{cc.compiles} (traces {cc.traces})")
    dev = harness.device_info(devs)
    n_sub = prog.geo.n_sub
    groups = flops.point_groups(cfg, flops.data_counts(prog.data.data_comp),
                                len(prog.data.ifaces))
    counts = flops.step(cfg, groups)
    prog.free()
    want = run_reference(cfg, prog, prog.chunk, "highest")
    checks = readings(first, want, prog.w0, n_sub,
                      int(traffic["loss_steps"]))
    checks = {k: {"value": v, "limit": float(cell.limits[k])}
              for k, v in checks.items()}
    correct = first["ok"] and first["good"] == prog.chunk \
        and harness.checks_ok(checks)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": attempted - steps,
           "e2e": {"train_steps_per_s": steps / elapsed, "setup_s": setup_s},
           "device": dev, "checks": checks,
           "ctx": {"kind": "train", "trace_dir": tdir, "steps": steps,
                   "window_s": elapsed, "steps_per_s": steps / elapsed,
                   "counts": counts, "chips": cell.chips, "config": cfg}}
    return out
