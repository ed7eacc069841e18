"""Fault-tolerant supervisor: in-graph guards, rollback/backoff, recovery.

Covers the robustness contract (EXPERIMENTS.md §Robustness):

* ``run_chunk_guarded`` bitwise-matches the unguarded per-step loop
  (``step``) on healthy runs (all three trainers; Distributed in a 4-device
  subprocess) and adds no compute to the hot path — the guarded chunk body
  still traces/packs the megabatched network entry exactly once per loss
  evaluation (trace + HLO asserted);
* injected NaNs trip the guard within ONE chunk, with per-subdomain
  attribution: ``nan_params`` flags the poisoned subdomain and its interface
  neighbors (never the diagonal), ``nan_grads`` keeps the loss finite and is
  caught by the param-norm check alone; the stacked and the sharded trainer
  share the guard and attribute a trip alike;
* a crash mid-chunk (compute done, checkpoint lost) rolls back and replays —
  the recovered run equals the uninterrupted run BITWISE on ReferenceTrainer
  and DataParallelTrainer;
* a guard trip rolls back with per-subdomain lr backoff and the retried run
  completes; budget/floor exhaustion raise instead of looping forever;
* checkpoint hygiene: orphaned ``.tmp_step_*`` dirs from a crashed save are
  swept on the next save / latest_step.

The unmarked tests here are the always-on tier-1 subset; the full fault
matrix sweep runs under ``-m ft`` (see pytest.ini).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.core import (
    Burgers1D, CartesianDecomposition, DDConfig, ReferenceTrainer, XPINN,
    build_topology,
)
from repro.core.losses import ResidualPath
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.core.trainer import DataParallelTrainer, TrainState
from repro.data import make_batch
from repro.kernels import ops
from repro.runtime import (
    FAULT_KINDS, Fault, FaultInjector, Supervisor, SupervisorConfig,
    inject_nan, parse_faults,
)


def _setup(n_res=48, width=16, depth=2):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=8)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    b = make_batch(dec, topo, pde, n_res=n_res, n_bnd=16,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, residual_path="pallas"))
    return pde, dec, cfg, b, tr


def _max_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _poison(tr, kind, subdomain):
    st = tr.init(0)
    tree = inject_nan({"params": st.params, "opt": st.opt, "step": st.step},
                      kind, subdomain)
    return TrainState(params=tree["params"], opt=tree["opt"],
                      step=tree["step"])


# ------------------------------------------------------------- guarded chunk

def test_guarded_chunk_matches_unguarded_bitwise():
    """On a healthy run the guarded chunk is the unguarded per-step loop:
    same state and, row by row, the same terms."""
    pde, dec, cfg, b, tr = _setup()
    s_u, t_u = tr.init(0), []
    for _ in range(5):
        s_u, t = tr.step(s_u, b)
        t_u.append(t)
    s_g, t_g, health = tr.run_chunk_guarded(tr.init(0), b, 5)
    assert _max_diff(s_u.params, s_g.params) == 0.0
    assert _max_diff(s_u.opt, s_g.opt) == 0.0
    assert int(s_g.step) == 5
    for k in t_g:
        np.testing.assert_array_equal(np.stack([t[k] for t in t_u]),
                                      np.asarray(t_g[k]))
    assert bool(health["ok"]) and np.asarray(health["ok_sub"]).all()
    assert int(health["good_steps"]) == 5


def test_guarded_data_parallel_matches_unguarded_bitwise():
    pde, dec, cfg, b, tr_ref = _setup()
    tr = DataParallelTrainer(pde, cfg, n_workers=1, residual_path="pallas")
    bd = jax.tree.map(lambda x: x[:1], b)
    s_u = tr.init(0)
    for _ in range(4):
        s_u, _ = tr.step(s_u, bd)
    s_g, _, health = tr.run_chunk_guarded(tr.init(0), bd, 4)
    assert _max_diff(s_u["params"], s_g["params"]) == 0.0
    assert _max_diff(s_u["opt"], s_g["opt"]) == 0.0
    assert bool(np.asarray(health["ok"])) and int(health["good_steps"]) == 4


def test_guard_trips_on_nan_params_with_subdomain_attribution():
    """Acceptance: NaN params trip the guard within one chunk.  Attribution:
    the poisoned subdomain AND its interface neighbors go non-finite at the
    same step (the XPINN interface term evaluates both sides), but the
    DIAGONAL subdomain (no shared edge) stays healthy — and the frozen carry
    stops the rot from spreading to it on later steps."""
    pde, dec, cfg, b, tr = _setup()
    s, terms, health = tr.run_chunk_guarded(_poison(tr, "nan_params", 0), b, 5)
    ok_sub = np.asarray(health["ok_sub"])
    assert not bool(health["ok"])
    assert not ok_sub[0]                       # the poisoned subdomain
    assert ok_sub[3]                           # diagonal: no shared interface
    assert int(health["good_steps"]) == 1      # tripped during the first step
    assert int(s.step) == 1                    # carry frozen from then on
    loss = np.asarray(terms["loss"])
    assert np.isnan(loss[0, 0]) and np.isfinite(loss[0, 3])
    assert np.isnan(loss[1:]).all()            # post-trip rows are markers


def test_guard_catches_nan_moments_despite_finite_loss():
    """nan_grads poisons the Adam first moment: the loss computed that step is
    FINITE (params were clean) — only the param-norm check sees the poisoned
    update.  A loss-only guard would ship a corrupted checkpoint."""
    pde, dec, cfg, b, tr = _setup()
    s, terms, health = tr.run_chunk_guarded(_poison(tr, "nan_grads", 0), b, 3)
    ok_sub = np.asarray(health["ok_sub"])
    assert not bool(health["ok"])
    np.testing.assert_array_equal(ok_sub, [False, True, True, True])
    assert np.isfinite(np.asarray(terms["loss"])[0]).all()
    assert int(health["good_steps"]) == 1


def test_guarded_chunk_adds_no_network_entries_or_weight_packs():
    """Acceptance: the guard adds no extra dispatches.  Trace level: the
    guarded body touches the megabatched entry twice per loss eval — one
    abstract ``eval_shape`` structure probe (compiles to nothing) plus the ONE
    live ``lax.cond`` branch — independent of chunk length.  HLO level: the
    compiled guarded chunk packs the layer weight stack once per loss eval,
    so the frozen branch and health checks add no network compute."""
    pde, dec, cfg, b, tr = _setup(n_res=32)
    tr.res_path = ResidualPath(act="tanh", block_n=32, interpret=True)
    state = tr.init(0)
    ones = jnp.ones((4,), jnp.float32)

    def entries(steps):
        calls = []
        orig = ops.pinn_mlp_forward2
        ops.pinn_mlp_forward2 = lambda *a, **k: (calls.append(1),
                                                 orig(*a, **k))[1]
        try:
            tr._chunk_guarded.lower(state, b, steps, ones)
        finally:
            ops.pinn_mlp_forward2 = orig
        return len(calls)

    assert entries(5) == 2 == entries(1)

    def weight_pads(txt):
        return sum(1 for ln in txt.splitlines()
                   if " pad(" in ln and "f32[4,128,128]" in ln)

    guarded = tr._chunk_guarded.lower(state, b, 3, ones).compile().as_text()
    # one (L, 128, 128) stack per loss eval: the depth-2 net's hidden and
    # output matrices (the first layer packs against x's rows)
    assert weight_pads(guarded) == 2


# ---------------------------------------------------------------- supervisor

def test_supervisor_crash_recovery_bitwise(tmp_path):
    """Acceptance: a crash mid-chunk (compute done, checkpoint lost) recovers
    to EXACTLY the uninterrupted run — replay happens at full lr from the last
    good checkpoint, so the trajectory is bit-identical."""
    pde, dec, cfg, b, tr = _setup()
    injector = FaultInjector([Fault(chunk=1, kind="crash")])
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=3), injector, decomp=dec)
    s_f, report = sup.run(tr.init(0), b, 9)
    assert report.crashes == 1 and report.restarts == 1
    assert report.chunks == 3 and injector.exhausted
    assert len(report.recovery_s) == 1

    s_b = tr.init(0)
    for _ in range(3):
        s_b, _, _ = tr.run_chunk_guarded(s_b, b, 3)
    assert int(s_f.step) == int(s_b.step) == 9
    assert _max_diff(s_f.params, s_b.params) == 0.0
    assert _max_diff(s_f.opt, s_b.opt) == 0.0


def test_supervisor_nan_trip_backoff_retry_completes(tmp_path):
    """Acceptance: injected NaN trips the guard within one chunk, rolls back,
    and the retried run (per-subdomain lr backoff on exactly the subdomains
    that went non-finite) trains to completion with finite state."""
    pde, dec, cfg, b, tr = _setup()
    injector = FaultInjector([Fault(chunk=1, kind="nan_params", subdomain=0)])
    root = str(tmp_path / "ckpt")
    sup = Supervisor(tr, root, SupervisorConfig(chunk_steps=3), injector,
                     decomp=dec)
    s_f, report = sup.run(tr.init(0), b, 9)
    assert report.guard_trips == 1 and report.crashes == 0
    assert report.restarts == 1 and int(s_f.step) == 9
    # backoff hit the tripped subdomains only; the diagonal kept full lr
    assert sup.lr_scale is not None
    assert sup.lr_scale[0] == pytest.approx(0.5)
    assert sup.lr_scale[3] == pytest.approx(1.0)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(s_f.params))
    # the backoff state survives in checkpoint metadata for the next restart
    _, manifest = ckpt.raw_leaves(root)
    meta = manifest["metadata"]["supervisor"]
    assert meta["restarts"] == 1
    assert meta["lr_scale"][0] == pytest.approx(0.5)
    assert len(meta["chunk_walltimes"]) == report.chunks


def test_supervisor_straggler_absorbed_and_walltimes_recorded(tmp_path):
    pde, dec, cfg, b, tr = _setup()
    injector = FaultInjector([Fault(chunk=1, kind="straggler", delay=0.05)])
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=2), injector, decomp=dec)
    s_f, report = sup.run(tr.init(0), b, 6)
    assert report.stragglers == 1 and report.restarts == 0
    assert int(s_f.step) == 6 and len(report.walltimes) == 3
    assert report.walltimes[1] >= 0.05          # the delayed chunk


def test_supervisor_restart_budget_exhausted_raises(tmp_path):
    pde, dec, cfg, b, tr = _setup()
    injector = FaultInjector([Fault(chunk=i, kind="crash") for i in range(6)])
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=2, max_restarts=2), injector)
    with pytest.raises(RuntimeError, match="restart budget"):
        sup.run(tr.init(0), b, 8)


def test_supervisor_backoff_floor_raises(tmp_path):
    pde, dec, cfg, b, tr = _setup()
    injector = FaultInjector([Fault(chunk=1, kind="nan_params", subdomain=0),
                              Fault(chunk=2, kind="nan_params", subdomain=0)])
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=2, lr_backoff=0.5,
                                      min_lr_scale=0.3), injector)
    with pytest.raises(RuntimeError, match="floor"):
        sup.run(tr.init(0), b, 8)


def test_supervisor_data_parallel_crash_recovery_bitwise(tmp_path):
    pde, dec, cfg, b, _ = _setup()
    tr = DataParallelTrainer(pde, cfg, n_workers=1, residual_path="pallas")
    bd = jax.tree.map(lambda x: x[:1], b)
    sup0 = Supervisor(tr, str(tmp_path / "a"), SupervisorConfig(chunk_steps=3))
    s_a, _ = sup0.run(tr.init(0), bd, 9)
    injector = FaultInjector([Fault(chunk=1, kind="crash")])
    sup1 = Supervisor(tr, str(tmp_path / "b"), SupervisorConfig(chunk_steps=3),
                      injector)
    s_b, report = sup1.run(tr.init(0), bd, 9)
    assert report.crashes == 1
    assert _max_diff(s_a["params"], s_b["params"]) == 0.0
    assert _max_diff(s_a["opt"], s_b["opt"]) == 0.0
    assert int(np.asarray(s_b["step"])) == 9


# ------------------------------------------------------------ fault schedule

def test_parse_faults_and_injector_fire_once():
    faults = parse_faults("crash@1, nan_params@2:0, straggler@3*0.5,nan_grads@4")
    assert [f.kind for f in faults] == ["crash", "nan_params", "straggler",
                                       "nan_grads"]
    assert faults[1].subdomain == 0 and faults[2].delay == 0.5
    assert parse_faults("straggler@0")[0].delay == 0.25   # default delay
    inj = FaultInjector(faults)
    assert inj.take(0) == [] and not inj.exhausted
    assert inj.take(1) == [faults[0]]
    assert inj.take(1) == []                              # fires exactly once
    for c in (2, 3, 4):
        inj.take(c)
    assert inj.exhausted and inj.fired == faults
    with pytest.raises(ValueError, match="fault kind"):
        Fault(chunk=0, kind="meteor")
    with pytest.raises(ValueError, match="NaN fault"):
        inject_nan({"params": {}, "opt": {}}, "crash")


# -------------------------------------------------------- checkpoint hygiene

def test_ckpt_sweeps_orphan_tmp_dirs(tmp_path):
    """A crash between mkdtemp and rename leaves ``.tmp_step_*`` behind; the
    next save (and latest_step) sweeps it so long-running jobs don't leak."""
    root = str(tmp_path / "ckpt")
    ckpt.save(root, 1, {"w": np.arange(3.0)})
    stale = os.path.join(root, ".tmp_step_7_deadbeef")
    os.makedirs(stale)
    with open(os.path.join(stale, "arrays.npz"), "wb") as f:
        f.write(b"half-written junk")
    assert ckpt.latest_step(root) == 1          # ignored AND swept
    assert not os.path.exists(stale)
    os.makedirs(stale)
    ckpt.save(root, 2, {"w": np.arange(3.0) + 1})
    assert not os.path.exists(stale)
    tree, _ = ckpt.restore(root, {"w": np.zeros(3)})
    np.testing.assert_array_equal(tree["w"], np.arange(3.0) + 1)
    assert ckpt.latest_step(root) == 2


# ------------------------------------------------------- full fault matrix

@pytest.mark.ft
@pytest.mark.parametrize("kind", FAULT_KINDS)
def test_fault_matrix_reference_trainer_recovers(kind, tmp_path):
    """The full matrix sweep (``-m ft``): every fault kind injected mid-run;
    the supervisor absorbs it and trains to the target step count."""
    pde, dec, cfg, b, tr = _setup()
    fault = Fault(chunk=1, kind=kind,
                  subdomain=0 if kind.startswith("nan") else None,
                  delay=0.02 if kind == "straggler" else 0.0)
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=3), FaultInjector([fault]),
                     decomp=dec)
    s_f, report = sup.run(tr.init(0), b, 9)
    assert int(s_f.step) == 9
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(s_f.params))
    expected = {"crash": (1, 0, 0), "nan_params": (0, 1, 0),
                "nan_grads": (0, 1, 0), "straggler": (0, 0, 1)}[kind]
    assert (report.crashes, report.guard_trips,
            report.stragglers) == expected


# --------------------------------------------------- distributed (subprocess)

DIST_FT_CODE = """
import numpy as np, jax, jax.numpy as jnp, tempfile
from repro.core import *
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.core.trainer import TrainState
from repro.data import make_batch
from repro.runtime import Fault, FaultInjector, Supervisor, SupervisorConfig, inject_nan

pde = Burgers1D()
dec = CartesianDecomposition(((-1,1),(0,1)), nx=2, ny=2)
topo = build_topology(dec, n_iface=8)
cfg = SubdomainModelConfig(nets={"u": MLPConfig(2,1,16,2)})
b = make_batch(dec, topo, pde, n_res=48, n_bnd=16,
               rng=np.random.default_rng(0)).device_arrays()
tr = DistributedDDTrainer(pde, cfg, topo, DDConfig(method=XPINN, residual_path="pallas"),
                          lrs=[1e-3, 2e-3, 3e-3, 4e-3])
bd = tr.shard_batch(b)
md = lambda a, c: max(float(np.max(np.abs(np.asarray(x)-np.asarray(y))))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c)))

# guarded == the unguarded step loop on the healthy path (separately compiled
# SPMD programs: float-noise tolerance, same as the chunk-vs-step-loop contract)
s_u = tr.shard_state(tr.init(0))
for _ in range(4):
    s_u, _ = tr.step(s_u, bd)
s_g, t_g, health = tr.run_chunk_guarded(tr.shard_state(tr.init(0)), bd, 4)
assert md(s_u.params, s_g.params) < 1e-7
assert bool(np.asarray(health["ok"])) and int(np.asarray(health["good_steps"])) == 4
assert np.asarray(health["ok_sub"]).shape == (4,)

# the pmin consensus freezes EVERY rank when one subdomain trips
st = tr.shard_state(tr.init(0))
tree = inject_nan({"params": st.params, "opt": st.opt, "step": st.step},
                  "nan_params", 0)
st = TrainState(params=tree["params"], opt=tree["opt"], step=tree["step"])
s, terms, health = tr.run_chunk_guarded(st, bd, 4)
ok_sub = np.asarray(health["ok_sub"])
assert not bool(np.asarray(health["ok"])) and not ok_sub[0] and ok_sub[3]
assert int(np.asarray(health["good_steps"])) == 1

# supervisor crash recovery over the SPMD trainer
with tempfile.TemporaryDirectory() as d:
    sup = Supervisor(tr, d + "/a", SupervisorConfig(chunk_steps=2))
    s_a, _ = sup.run(tr.shard_state(tr.init(0)), bd, 6)
with tempfile.TemporaryDirectory() as d:
    sup = Supervisor(tr, d + "/b", SupervisorConfig(chunk_steps=2),
                     FaultInjector([Fault(chunk=1, kind="crash")]))
    s_b, report = sup.run(tr.shard_state(tr.init(0)), bd, 6)
assert report.crashes == 1 and int(np.asarray(s_b.step)) == 6
assert md(s_a.params, s_b.params) < 1e-7, md(s_a.params, s_b.params)
print("DIST-FT-OK")
"""


@pytest.mark.slow
def test_distributed_guarded_and_crash_recovery(subproc):
    out = subproc(DIST_FT_CODE, n_devices=4, timeout=900)
    assert "DIST-FT-OK" in out


TRIP_ACROSS_CODE = """
import numpy as np, jax
from repro.core import *
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.core.trainer import TrainState
from repro.data import make_batch
from repro.runtime import inject_nan

pde = Burgers1D()
dec = CartesianDecomposition(((-1,1),(0,1)), nx=2, ny=2)
topo = build_topology(dec, n_iface=8)
cfg = SubdomainModelConfig(nets={"u": MLPConfig(2,1,16,2)})
b = make_batch(dec, topo, pde, n_res=48, n_bnd=16,
               rng=np.random.default_rng(0)).device_arrays()
dd = DDConfig(method=XPINN, residual_path="pallas")
lrs = [1e-3, 2e-3, 3e-3, 4e-3]

def poisoned(tr):
    st = tr.init(0)
    tree = inject_nan({"params": st.params, "opt": st.opt, "step": st.step},
                      "nan_params", 0)
    return TrainState(params=tree["params"], opt=tree["opt"], step=tree["step"])

ref = ReferenceTrainer(pde, cfg, topo, dd, lrs=lrs)
s_r, t_r, h_r = ref.run_chunk_guarded(poisoned(ref), b, 4)
dist = DistributedDDTrainer(pde, cfg, topo, dd, lrs=lrs)
s_d, t_d, h_d = dist.run_chunk_guarded(dist.shard_state(poisoned(dist)),
                                       dist.shard_batch(b), 4)
ok_r, ok_d = np.asarray(h_r["ok_sub"]), np.asarray(h_d["ok_sub"])
assert not ok_r[0] and ok_r[3], ok_r
np.testing.assert_array_equal(ok_r, ok_d)
assert int(h_r["good_steps"]) == int(h_d["good_steps"]) == 1
assert not bool(h_r["ok"]) and not bool(h_d["ok"])
assert int(s_r.step) == int(np.asarray(s_d.step)) == 1
# the trip row and the frozen rows after it, term by term: NaN where the
# other layout has NaN, and the finite terms to float32 rounding (the stacked
# and the sharded step are separately compiled programs: the residual sums
# may differ in the last bit, as in the chunk-vs-step-loop contract)
assert set(t_r) == set(t_d)
for k in t_r:
    np.testing.assert_allclose(np.asarray(t_d[k]), np.asarray(t_r[k]),
                               rtol=1e-6, atol=0, err_msg=k)
print("TRIP-ACROSS-OK")
"""


@pytest.mark.slow
def test_guard_trip_attribution_matches_across_trainers(subproc):
    """The one guard on both layouts: a subdomain planted with NaN trips the
    stacked trainer's ``jnp.all`` and the sharded trainer's ``pmin`` vote at
    the same step, with the same ``ok_sub`` attribution, the same NaN rows
    after the trip and the same finite terms before it."""
    out = subproc(TRIP_ACROSS_CODE, n_devices=4, timeout=600)
    assert "TRIP-ACROSS-OK" in out
