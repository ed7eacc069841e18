"""Elastic re-decomposition: nearest-centroid remap, weighted rebalance, resume.

Covers the elastic-restart contract (EXPERIMENTS.md §Robustness):

* ``remap_params`` adopts each new subdomain's parameters from the old
  subdomain with the nearest centroid — verified against a hand-computed
  assignment on Cartesian grids AND the 10-region us_map polygons, and via
  :class:`CentroidSpec` (the metadata-only stand-in used after a restart,
  when the old geometry object is gone);
* ``balanced_counts`` preserves the global point budget exactly — leveled
  without weights, proportional-to-throughput with them (paper §7.6's
  straggler fix);
* ``elastic_resume`` restores a supervisor checkpoint taken at ``n_old``
  subdomains into a trainer built for ``n_new``: params remapped, moments
  fresh, the Adam step count and global step REALLY preserved end-to-end
  through save/restore (not just documented), and training re-converges.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    Burgers1D, CartesianDecomposition, DDConfig, ReferenceTrainer, XPINN,
    build_topology, evaluate_l2, us_map_decomposition,
)
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.runtime import (
    CentroidSpec, Supervisor, SupervisorConfig, balanced_counts,
    decomp_signature, elastic_resume, remap_params, throughput_weights,
)


def _setup(nx, nt, n_res=48, width=16, depth=2, seed=0):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), nx, nt)
    topo = build_topology(dec, n_iface=8)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    b = make_batch(dec, topo, pde, n_res=n_res, n_bnd=16,
                   rng=np.random.default_rng(seed)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, residual_path="pallas"))
    return pde, dec, cfg, b, tr


def _expected_src(old_dec, new_dec):
    oc = np.stack([old_dec.centroid(q) for q in range(old_dec.n_sub)])
    nc = np.stack([new_dec.centroid(q) for q in range(new_dec.n_sub)])
    return np.argmin(((nc[:, None] - oc[None]) ** 2).sum(-1), axis=1)


# ----------------------------------------------------------------- remapping

def test_remap_params_cartesian_hand_checked():
    old = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)   # 4 subdomains
    new = CartesianDecomposition(((-1, 1), (0, 1)), 3, 2)   # 6 subdomains
    params = {"w": jnp.arange(4 * 5, dtype=jnp.float32).reshape(4, 5)}
    remapped, src = remap_params(params, old, new)
    np.testing.assert_array_equal(src, _expected_src(old, new))
    np.testing.assert_array_equal(np.asarray(remapped["w"]),
                                  np.asarray(params["w"])[src])
    assert remapped["w"].shape == (6, 5)
    # every old subdomain's weights survive somewhere (2->3 columns: the old
    # column centroids are each nearest to at least one new column)
    assert set(src.tolist()) == {0, 1, 2, 3}


def test_remap_params_polygon_and_centroidspec():
    dec = us_map_decomposition()
    params = {"w": jnp.arange(dec.n_sub * 3, dtype=jnp.float32).reshape(
        dec.n_sub, 3)}
    # metadata round trip: the CentroidSpec rebuilt from a checkpoint's decomp
    # signature must drive the remap exactly like the live geometry object
    spec = CentroidSpec(decomp_signature(dec)["centroids"])
    assert spec.n_sub == dec.n_sub
    for q in range(dec.n_sub):
        np.testing.assert_allclose(spec.centroid(q), dec.centroid(q))
    # identity restart (same polygons): every subdomain adopts itself
    _, src_id = remap_params(params, spec, dec)
    np.testing.assert_array_equal(src_id, np.arange(dec.n_sub))
    # polygon -> Cartesian over the same footprint: matches the hand argmin
    lo = np.min([p.min(axis=0) for p in dec.polygons], axis=0)
    hi = np.max([p.max(axis=0) for p in dec.polygons], axis=0)
    new = CartesianDecomposition(((lo[0], hi[0]), (lo[1], hi[1])), 3, 2)
    remapped, src = remap_params(params, spec, new)
    np.testing.assert_array_equal(src, _expected_src(dec, new))
    np.testing.assert_array_equal(np.asarray(remapped["w"]),
                                  np.asarray(params["w"])[src])


# ---------------------------------------------------------------- rebalance

def test_balanced_counts_weighted_preserves_total_and_orders_by_speed():
    counts = [800, 3000, 3000, 3000, 3000]      # paper §7.6's idle-worker case
    total = sum(counts)
    level = balanced_counts(counts)
    assert sum(level) == total and max(level) - min(level) <= 1

    weights = [0.5, 1.0, 1.0, 1.0, 2.0]          # worker 0 slow, worker 4 fast
    out = balanced_counts(counts, weights)
    assert sum(out) == total                      # budget exact despite rounding
    assert out[0] < min(out[1:4]) < out[4]
    np.testing.assert_allclose(
        out, np.asarray(weights) / np.sum(weights) * total, atol=1.0)

    with pytest.raises(ValueError, match="weights"):
        balanced_counts(counts, [1.0, 2.0])
    with pytest.raises(ValueError, match="non-negative"):
        balanced_counts(counts, [-1.0, 1.0, 1.0, 1.0, 1.0])


def test_throughput_weights_feed_straggler_aware_rebalance():
    counts = [1000, 1000, 1000, 1000]
    walltimes = [1.0, 1.0, 1.0, 4.0]             # worker 3 is 4x slower
    w = throughput_weights(counts, walltimes)
    np.testing.assert_allclose(w, [1000.0, 1000.0, 1000.0, 250.0])
    out = balanced_counts(counts, w)
    assert sum(out) == 4000
    assert out[3] < out[0] and abs(out[3] - 4000 * 250 / 3250) <= 1.0
    # the supervisor-facing wrapper routes measured walltimes the same way
    pde, dec, cfg, b, tr = _setup(2, 2)
    sup = Supervisor(tr, "/tmp/unused-rebalance", decomp=dec)
    assert sup.rebalance_counts(counts, walltimes) == out
    lvl = sup.rebalance_counts([10, 20, 30, 40])
    assert lvl == [25, 25, 25, 25]


# ------------------------------------------------------------ elastic resume

def test_elastic_resume_same_n_sub_is_bitwise(tmp_path):
    pde, dec, cfg, b, tr = _setup(2, 2)
    root = str(tmp_path / "ckpt")
    sup = Supervisor(tr, root, SupervisorConfig(chunk_steps=3), decomp=dec)
    state, _ = sup.run(tr.init(0), b, 6)
    resumed, meta = elastic_resume(root, tr, dec)
    assert int(np.asarray(resumed.step)) == 6
    for a, c in zip(jax.tree.leaves((state.params, state.opt)),
                    jax.tree.leaves((resumed.params, resumed.opt))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert meta["supervisor"]["decomp"]["n_sub"] == 4


def test_elastic_resume_remaps_and_preserves_adam_count(tmp_path):
    """Adam step count preserved via metadata — REALLY true through
    save/restore: the resumed optimizer continues bias correction from the
    checkpointed count instead of restarting cold."""
    pde, dec, cfg, b, tr = _setup(2, 2)
    root = str(tmp_path / "ckpt")
    sup = Supervisor(tr, root, SupervisorConfig(chunk_steps=4), decomp=dec)
    state, _ = sup.run(tr.init(0), b, 8)
    assert int(np.asarray(state.opt["count"])) == 8

    pde2, dec2, cfg2, b2, tr2 = _setup(3, 2)       # elastic: 4 -> 6 subdomains
    resumed, meta = elastic_resume(root, tr2, dec2)
    src = _expected_src(dec, dec2)
    # params adopted nearest-centroid from the old stacked leaves
    for old_leaf, new_leaf in zip(jax.tree.leaves(state.params),
                                  jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(old_leaf)[src],
                                      np.asarray(new_leaf))
    # moments reset, count + global step preserved from metadata
    for mom in ("m", "v"):
        assert all(float(np.abs(np.asarray(x)).max()) == 0.0
                   for x in jax.tree.leaves(resumed.opt[mom]))
    assert int(np.asarray(resumed.opt["count"])) == 8
    assert int(np.asarray(resumed.step)) == 8
    assert meta["supervisor"]["adam_count"] == 8


def test_elastic_resume_4_to_6_reconverges(tmp_path):
    """Acceptance: a checkpoint taken at 4 subdomains restarts at 6 and
    RE-CONVERGES — the remapped network is a warm start (better than cold
    init) and further training recovers the pre-restart error level."""
    pde, dec, cfg, b, tr = _setup(2, 2, n_res=64, width=20, depth=3)
    root = str(tmp_path / "ckpt")
    sup = Supervisor(tr, root, SupervisorConfig(chunk_steps=100), decomp=dec)
    state, _ = sup.run(tr.init(0), b, 400)
    err_old = evaluate_l2(dec, cfg, state.params, tr.act_codes, pde, n_pts=400)

    pde2, dec2, cfg2, b2, tr2 = _setup(3, 2, n_res=64, width=20, depth=3)
    resumed, _ = elastic_resume(root, tr2, dec2)
    err_cold = evaluate_l2(dec2, cfg2, tr2.init(0).params, tr2.act_codes, pde2,
                           n_pts=400)
    err_warm = evaluate_l2(dec2, cfg2, resumed.params, tr2.act_codes, pde2,
                           n_pts=400)
    assert err_warm < err_cold, (err_warm, err_cold)

    resumed, terms, health = tr2.run_chunk_guarded(resumed, b2, 400)
    assert bool(health["ok"])
    err_new = evaluate_l2(dec2, cfg2, resumed.params, tr2.act_codes, pde2,
                          n_pts=400)
    assert np.isfinite(np.asarray(terms["loss"])).all()
    assert err_new < err_warm, (err_new, err_warm)
    assert err_new < max(1.5 * err_old, 0.5), (err_new, err_old)
