"""Field-serving subsystem: routing, stitching, single-dispatch engine, cache.

Covers the serve contract (EXPERIMENTS.md §Serving):

* vectorized routing agrees with ``Decomposition.subdomain_contains`` on
  random clouds (Cartesian grid AND the 10-region us_map polygons, bitwise);
* engine output matches per-subdomain reference apply to <= 1e-5, interface
  points return the two-sided average, outside points come back NaN;
* one ``evaluate`` call = ONE fused traced network entry (trace-counted for
  both the static-act and the heterogeneous-act select path, on a mixed cloud
  spanning all 10 us_map regions) and one packed weight stack in the compiled
  HLO;
* the frontend LRU returns bitwise-identical arrays on a repeat query without
  a new engine dispatch;
* export -> load roundtrips the full artifact (params, geometry, acts, PDE).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    Burgers1D, CartesianDecomposition, us_map_decomposition,
)
from repro.core import nets
from repro.core.nets import MLPConfig, SubdomainModelConfig, model_apply
from repro.core.pdes import HeatConduction2D
from repro.kernels import ops
from repro.serve import (
    FieldBundle, FieldEngine, ServeFrontend, export_bundle, load_bundle,
    membership_matrix, route,
)
from repro.serve import engine as engine_mod

TABLE3_ACTS = ["tanh", "sin", "cos", "tanh", "sin", "cos", "tanh", "sin",
               "cos", "tanh"]


def _cart_bundle(width=16, depth=3, seed=0):
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    params, codes = nets.stacked_init(cfg, dec.n_sub, jax.random.PRNGKey(seed))
    return FieldBundle(model_cfg=cfg, params=params, decomp=dec,
                       act_codes=np.asarray(codes), pde=Burgers1D())


def _usmap_bundle(two_nets=True, seed=1):
    dec = us_map_decomposition()
    nets_d = {"u": MLPConfig(2, 1, 12, 2)}
    if two_nets:
        nets_d["k"] = MLPConfig(2, 1, 12, 2)
    cfg = SubdomainModelConfig(nets=nets_d)
    params, codes = nets.stacked_init(cfg, dec.n_sub, jax.random.PRNGKey(seed),
                                      TABLE3_ACTS)
    return FieldBundle(model_cfg=cfg, params=params, decomp=dec,
                       act_codes=np.asarray(codes),
                       pde=HeatConduction2D() if two_nets else None)


# ------------------------------------------------------------------- routing

def test_cartesian_routing_matches_contains():
    dec = CartesianDecomposition(((-1, 2), (0, 1)), 3, 2)
    rng = np.random.default_rng(0)
    pts = rng.uniform([-1.5, -0.5], [2.5, 1.5], size=(2000, 2))
    pts = np.concatenate([pts, np.array([[0.0, 0.5], [-1.0, 0.0], [2.0, 1.0]])])
    M = membership_matrix(dec, pts, tol=0.0)
    for q in range(dec.n_sub):
        np.testing.assert_array_equal(M[q], dec.subdomain_contains(q, pts))


def test_polygon_routing_matches_contains():
    dec = us_map_decomposition()
    rng = np.random.default_rng(1)
    pts = rng.uniform([-0.5, -0.5], [5.5, 2.5], size=(3000, 2))
    M = membership_matrix(dec, pts, tol=0.0)
    for q in range(dec.n_sub):
        np.testing.assert_array_equal(M[q], dec.subdomain_contains(q, pts))


def test_polygon_interface_points_claimed_by_both_sides():
    dec = us_map_decomposition()
    # exact shared-edge points from the topology construction
    for e in dec.interface_edges(n_iface=6):
        M = membership_matrix(dec, e.points, tol=1e-9)
        assert M[e.a].all() and M[e.b].all()
    r = route(dec, dec.interface_edges(n_iface=6)[0].points)
    assert (r.claims >= 2).all()


def test_route_buckets_and_claims():
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    pts = np.array([[-0.5, 0.25], [0.5, 0.75], [0.0, 0.25], [9.0, 9.0]])
    r = route(dec, pts, bucket=8)
    assert r.m == 8 and r.X.shape == (4, 8, 2)
    np.testing.assert_array_equal(r.claims, [1, 1, 2, 0])
    np.testing.assert_array_equal(r.owner, [0, 3, 0, -1])
    assert r.n_unclaimed == 1
    # every claimed point has exactly one primary claim
    assert r.primary.sum() == (r.claims > 0).sum()


# -------------------------------------------------------------------- engine

def _single_claim_mask(dec, pts):
    return membership_matrix(dec, pts, tol=1e-9).sum(axis=0) == 1


@pytest.mark.parametrize("mixed_acts", [False, True])
def test_engine_matches_reference_apply(mixed_acts):
    bundle = _usmap_bundle() if mixed_acts else _cart_bundle()
    dec, cfg, params = bundle.decomp, bundle.model_cfg, bundle.params
    codes = bundle.act_codes
    rng = np.random.default_rng(2)
    pts = np.concatenate([dec.sample_interior(q, 40, rng)
                          for q in range(dec.n_sub)])
    out = FieldEngine(bundle).evaluate(pts, order=2)
    assert np.isfinite(out["u"]).all() and np.isfinite(out["residual"]).all()
    single = _single_claim_mask(dec, pts)
    for q in range(dec.n_sub):
        sel = dec.subdomain_contains(q, pts) & single
        p_q = jax.tree.map(lambda x: x[q], params)
        ref = np.asarray(model_apply(cfg, p_q, jnp.asarray(pts[sel], jnp.float32),
                                     int(codes[q])))
        assert np.abs(out["u"][sel] - ref).max() <= 1e-5


def test_engine_interface_average_and_outside_nan():
    bundle = _cart_bundle()
    dec, cfg, params = bundle.decomp, bundle.model_cfg, bundle.params
    eng = FieldEngine(bundle)
    iface = np.stack([np.zeros(7), np.linspace(0.05, 0.45, 7)], axis=1)
    out = eng.evaluate(np.concatenate([iface, [[5.0, 5.0]]]), order=1)
    # x=0, y<0.5 sits between subdomains 0 (ix=0,iy=0) and 2 (ix=1,iy=0)
    ref = lambda q: np.asarray(model_apply(
        cfg, jax.tree.map(lambda x: x[q], params),
        jnp.asarray(iface, jnp.float32), 0))
    want = 0.5 * (ref(0) + ref(2))
    np.testing.assert_allclose(out["u"][:-1], want, atol=1e-6)
    assert np.isnan(out["u"][-1]).all()


def test_engine_first_order_tier():
    """order=1 (d2 stream disabled) returns the SAME u/grad/flux, no residual."""
    bundle = _cart_bundle()
    eng = FieldEngine(bundle)
    pts = np.array([[0.2, 0.2], [-0.7, 0.9]])
    o1 = eng.evaluate(pts, order=1)
    o2 = eng.evaluate(pts, order=2)
    assert sorted(o1) == ["flux", "grad_u", "u"]
    assert sorted(o2) == ["flux", "grad_u", "residual", "u"]
    for k in o1:
        np.testing.assert_array_equal(o1[k], o2[k])


def test_engine_order2_without_pde_raises():
    bundle = _usmap_bundle(two_nets=False)
    bundle = FieldBundle(model_cfg=bundle.model_cfg, params=bundle.params,
                         decomp=bundle.decomp, act_codes=bundle.act_codes,
                         pde=None)
    eng = FieldEngine(bundle)
    with pytest.raises(ValueError, match="order=1"):
        eng.evaluate(np.array([[1.0, 1.0]]), order=2)
    out = eng.evaluate(np.array([[1.0, 1.0]]), order=1)
    assert sorted(out) == ["grad_u", "u"]


# ------------------------------------------------- single-dispatch contract

def _count_entries(fn_names, body):
    """Run ``body`` with the named ops entries wrapped by a trace counter."""
    calls = []
    origs = {n: getattr(ops, n) for n in fn_names}
    for n in fn_names:
        def wrap(*a, _orig=origs[n], _n=n, **k):
            calls.append(_n)
            return _orig(*a, **k)
        setattr(ops, n, wrap)
    try:
        body()
    finally:
        for n, f in origs.items():
            setattr(ops, n, f)
    return calls


def test_engine_single_fused_entry_uniform_act():
    """Acceptance: one evaluate = ONE traced fused entry (static-act path)."""
    engine_mod._EVAL_CACHE.clear()
    bundle = _cart_bundle(width=12, depth=2, seed=3)
    eng = FieldEngine(bundle)
    pts = np.random.default_rng(3).uniform([-1, 0], [1, 1], size=(50, 2))
    calls = _count_entries(["pinn_mlp_forward2", "pinn_mlp_forward2_select"],
                           lambda: eng.evaluate(pts, order=2))
    assert calls == ["pinn_mlp_forward2"], calls


def test_engine_single_fused_entry_usmap_mixed_cloud():
    """Acceptance: a mixed query cloud spanning ALL 10 us_map regions (with
    heterogeneous Table-3 activations) is served by exactly one traced fused
    network entry per field net — the vmapped select entry, not a per-region
    loop."""
    engine_mod._EVAL_CACHE.clear()
    bundle = _usmap_bundle(two_nets=False, seed=4)
    eng = FieldEngine(bundle)
    assert eng.uniform_act is None  # heterogeneous: select path
    rng = np.random.default_rng(4)
    pts = np.concatenate([bundle.decomp.sample_interior(q, 20, rng)
                          for q in range(10)])
    assert (membership_matrix(bundle.decomp, pts).any(axis=1)).all()
    calls = _count_entries(["pinn_mlp_forward2", "pinn_mlp_forward2_select"],
                           lambda: eng.evaluate(pts, order=1))
    assert calls == ["pinn_mlp_forward2_select"], calls
    # repeat evaluates reuse the compiled program: no retrace, still 1 dispatch each
    d0 = eng.n_dispatches
    calls = _count_entries(["pinn_mlp_forward2", "pinn_mlp_forward2_select"],
                           lambda: eng.evaluate(pts, order=1))
    assert calls == [] and eng.n_dispatches == d0 + 1


def test_engine_hlo_packs_weights_once():
    """HLO single-entry assertion (the PR-2 pad-count idiom, serving side):
    the compiled evaluate program packs each layer's weight stack exactly once
    — a per-subdomain or per-segment loop would pad it n times."""
    engine_mod._EVAL_CACHE.clear()
    bundle = _cart_bundle(width=16, depth=2, seed=5)
    eng = FieldEngine(bundle, block_n=32, interpret=True)
    routed = route(bundle.decomp, np.random.default_rng(5).uniform(
        [-1, 0], [1, 1], size=(40, 2)), bucket=32)
    fn = eng._get_fn(order=2)
    txt = fn.lower(*eng._device_args(routed)).compile().as_text()
    # depth-2 MLP: the hidden and the output weight matrix; the first
    # layer packs against x's rows, not into the (L, 128, 128) stack
    n_layer_mats = 2
    pads = sum(1 for ln in txt.splitlines()
               if " pad(" in ln and "f32[4,128,128]" in ln)
    assert pads == n_layer_mats, f"expected {n_layer_mats} weight packs, got {pads}"


# ------------------------------------------------------------------ frontend

def test_frontend_cache_bitwise_no_new_dispatch():
    bundle = _cart_bundle()
    eng = FieldEngine(bundle)
    fe = ServeFrontend(eng, order=2, cache_size=4)
    pts = np.random.default_rng(6).uniform([-1, 0], [1, 1], size=(64, 2))
    a = fe.query(pts)
    d0 = eng.n_dispatches
    b = fe.query(pts)
    assert eng.n_dispatches == d0, "cache hit must not dispatch"
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
        assert a[k].tobytes() == b[k].tobytes()  # bitwise, not just approx
    s = fe.stats()
    assert s["cache_hits"] == 1 and s["cache_misses"] == 1


def test_frontend_microbatch_matches_standalone():
    """Aggregated requests slice back to exactly their standalone results."""
    bundle = _cart_bundle()
    eng = FieldEngine(bundle)
    fe = ServeFrontend(eng, order=1, max_batch=4096)
    rng = np.random.default_rng(7)
    clouds = [rng.uniform([-1, 0], [1, 1], size=(n, 2)) for n in (17, 33, 5)]
    tickets = [fe.submit(c) for c in clouds]
    d0 = eng.n_dispatches
    fe.flush()
    assert eng.n_dispatches == d0 + 1  # three requests, one microbatch dispatch
    for t, c in zip(tickets, clouds):
        got = fe.result(t)
        want = eng.evaluate(c, order=1)
        for k in want:
            np.testing.assert_allclose(got[k], want[k], atol=1e-6)


def test_frontend_failed_flush_requeues_tickets():
    """A failing engine evaluation must not strand queued tickets."""
    bundle = _usmap_bundle(two_nets=False)  # pde=None: order=2 raises
    fe = ServeFrontend(FieldEngine(bundle), order=2)
    t = fe.submit(np.array([[1.0, 1.0]]))
    with pytest.raises(ValueError, match="order=1"):
        fe.flush()
    fe.order = 1                     # recover and serve the queued request
    fe.flush()
    assert sorted(fe.result(t)) == ["grad_u", "u"]


def test_query_cloud_shape_validated():
    """Wrongly-shaped clouds fail loudly instead of being blindly reshaped."""
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    with pytest.raises(ValueError, match="query cloud"):
        route(dec, np.zeros((4, 3)))
    with pytest.raises(ValueError, match="query cloud"):
        membership_matrix(dec, np.zeros((2, 2, 2)))
    assert route(dec, np.array([0.5, 0.5])).pts.shape == (1, 2)  # single point ok


def test_frontend_deadline_flush_stubbed_clock():
    """max_queue_age: the oldest queued request is flushed once it ages out —
    driven by an injected monotonic clock, so no real sleeping."""
    bundle = _cart_bundle()
    eng = FieldEngine(bundle)
    now = [0.0]
    fe = ServeFrontend(eng, order=1, max_queue_age=1.0, clock=lambda: now[0])
    rng = np.random.default_rng(10)
    a = rng.uniform([-1, 0], [1, 1], size=(8, 2))
    ta = fe.submit(a)
    d0 = eng.n_dispatches
    now[0] = 0.5
    assert not fe.poll() and eng.n_dispatches == d0   # under the deadline: queued
    now[0] = 1.0
    assert fe.poll() and eng.n_dispatches == d0 + 1   # head aged out: flushed
    assert sorted(fe.result(ta)) == ["flux", "grad_u", "u"]
    assert fe.stats()["deadline_flushes"] == 1

    # submit() itself triggers the flush when the queue HEAD (not the new
    # request) is past the deadline — and both ride one dispatch
    now[0] = 2.0
    tb = fe.submit(rng.uniform([-1, 0], [1, 1], size=(4, 2)))
    now[0] = 3.5
    d1 = eng.n_dispatches
    tc = fe.submit(rng.uniform([-1, 0], [1, 1], size=(4, 2)))
    assert eng.n_dispatches == d1 + 1
    fe.result(tb), fe.result(tc)
    assert fe.stats()["deadline_flushes"] == 2

    # no deadline configured: poll never force-flushes
    fe2 = ServeFrontend(eng, order=1)
    fe2.submit(a)
    assert not fe2.poll() and len(fe2._pending) == 1


def test_frontend_result_pending_autoflush_and_double_pop():
    """result() on a still-pending ticket used to KeyError opaquely: now it
    auto-flushes; an unknown/already-popped ticket raises a typed error."""
    from repro.serve import UnknownTicketError

    bundle = _cart_bundle()
    eng = FieldEngine(bundle)
    fe = ServeFrontend(eng, order=1)
    pts = np.random.default_rng(11).uniform([-1, 0], [1, 1], size=(6, 2))
    t = fe.submit(pts)
    d0 = eng.n_dispatches
    out = fe.result(t)                 # no explicit flush: auto-flushes
    assert eng.n_dispatches == d0 + 1 and sorted(out) == ["flux", "grad_u", "u"]
    with pytest.raises(UnknownTicketError, match=f"ticket {t}"):
        fe.result(t)                   # results are handed out exactly once
    with pytest.raises(UnknownTicketError, match="ticket 999"):
        fe.result(999)


def test_frontend_cache_point_budget():
    """The cache is bounded by total cached POINTS, not just entry count —
    cache_size huge grids must not pin unbounded result arrays."""
    bundle = _cart_bundle()
    eng = FieldEngine(bundle)
    fe = ServeFrontend(eng, order=1, cache_size=64, cache_points=20)
    rng = np.random.default_rng(12)
    clouds = [rng.uniform([-1, 0], [1, 1], size=(8, 2)) for _ in range(3)]
    for c in clouds:
        fe.query(c)
    s = fe.stats()
    assert s["cache_points"] <= 20 and s["cache_entries"] == 2
    fe.query(clouds[0])                # evicted by the point budget: miss
    assert fe.stats()["cache_misses"] == 4
    fe.query(clouds[2])                # most-recent entries survived: hit
    assert fe.stats()["cache_hits"] == 1

    # an entry larger than the whole budget bypasses the cache instead of
    # evicting everything else and then missing anyway
    giant = rng.uniform([-1, 0], [1, 1], size=(30, 2))
    fe.query(giant)
    s = fe.stats()
    assert s["cache_points"] <= 20
    fe.query(giant)
    assert fe.stats()["cache_misses"] == 6     # giant is never cached


def test_frontend_lru_eviction():
    bundle = _cart_bundle()
    fe = ServeFrontend(FieldEngine(bundle), order=1, cache_size=2)
    rng = np.random.default_rng(8)
    clouds = [rng.uniform([-1, 0], [1, 1], size=(8, 2)) for _ in range(3)]
    for c in clouds:
        fe.query(c)
    fe.query(clouds[0])  # evicted by the LRU (size 2): miss again
    assert fe.stats()["cache_misses"] == 4


# ------------------------------------------------- trainer checkpoint wiring

def test_pinn_train_resume_bitwise(tmp_path):
    """repro.checkpoint wired into the PINN trainers (save_train_state /
    restore_train_state): a run interrupted mid-way through its chunk
    schedule and resumed from the checkpoint matches the uninterrupted
    ReferenceTrainer run BITWISE."""
    from repro.core import (
        DDConfig, ReferenceTrainer, XPINN, build_topology,
        restore_train_state, save_train_state,
    )
    from repro.checkpoint import ckpt
    from repro.data import make_batch

    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=8)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 16, 2)})
    b = make_batch(dec, topo, pde, n_res=48, n_bnd=16,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo, DDConfig(method=XPINN,
                                                   residual_path="pallas"))

    s_full, _, _ = tr.run_chunk_guarded(tr.init(0), b, 4)   # uninterrupted

    s_half, _, _ = tr.run_chunk_guarded(tr.init(0), b, 2)   # interrupted at 2...
    root = str(tmp_path / "ckpt")
    save_train_state(root, s_half)
    del s_half
    s_res = restore_train_state(root, tr.init(0))        # ...resumed
    assert int(s_res.step) == 2 and ckpt.latest_step(root) == 2
    s_res, _, _ = tr.run_chunk_guarded(s_res, b, 2)

    assert int(s_res.step) == int(s_full.step) == 4
    for a, c in zip(jax.tree.leaves((s_full.params, s_full.opt)),
                    jax.tree.leaves((s_res.params, s_res.opt))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ------------------------------------------------------------- export / load

def test_export_load_roundtrip(tmp_path):
    bundle = _usmap_bundle(seed=9)
    root = str(tmp_path / "bundle")
    export_bundle(root, bundle.params, bundle.model_cfg, bundle.decomp,
                  act_codes=bundle.act_codes, pde=bundle.pde, n_iface=12,
                  metadata={"rel_l2": 0.1})
    loaded = load_bundle(root)
    assert loaded.model_cfg == bundle.model_cfg
    assert loaded.pde == bundle.pde and loaded.n_iface == 12
    assert loaded.metadata == {"rel_l2": 0.1}
    np.testing.assert_array_equal(loaded.act_codes, bundle.act_codes)
    for a, b in zip(jax.tree.leaves(loaded.params),
                    jax.tree.leaves(bundle.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for pa, pb in zip(loaded.decomp.polygons, bundle.decomp.polygons):
        np.testing.assert_allclose(pa, pb)
    # the loaded bundle serves bitwise the same field as the in-memory one
    pts = np.random.default_rng(9).uniform([0.2, 0.2], [4.8, 1.8], size=(60, 2))
    a = FieldEngine(bundle).evaluate(pts, order=2)
    b = FieldEngine(loaded).evaluate(pts, order=2)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    # rebuildable topology rides along
    topo = loaded.topology()
    assert topo.n_sub == 10 and topo.n_iface == 12


def test_export_cartesian_spec_roundtrip(tmp_path):
    bundle = _cart_bundle()
    root = str(tmp_path / "b")
    export_bundle(root, bundle.params, bundle.model_cfg, bundle.decomp,
                  act_codes=bundle.act_codes, pde=bundle.pde)
    loaded = load_bundle(root)
    dec = loaded.decomp
    assert isinstance(dec, CartesianDecomposition)
    assert dec.bounds == bundle.decomp.bounds
    assert (dec.nx, dec.ny) == (2, 2)
    assert isinstance(loaded.pde, Burgers1D)
