"""The traffic generator gives the same requests from the same seed, and
every seed the same work in another order."""
import numpy as np

from bench import harness, problems, serve
from bench.tests import tiny

SEED = 2**31 + 12345


def mix():
    cell = tiny.cell("usmap_heat_10.serve_steady", SEED, 10.0)
    geo = problems.Geometry(cell.config["domain"])
    return cell.traffic, geo


def test_same_seed_same_requests():
    traffic, geo = mix()
    d1, c1 = serve.schedule(traffic, 50.0, 4.0, SEED, geo)
    d2, c2 = serve.schedule(traffic, 50.0, 4.0, SEED, geo)
    assert np.array_equal(d1, d2)
    assert all(np.array_equal(a, b) for a, b in zip(c1, c2))


def test_every_seed_gets_the_same_work():
    traffic, geo = mix()
    d1, c1 = serve.schedule(traffic, 50.0, 4.0, SEED, geo)
    d2, c2 = serve.schedule(traffic, 50.0, 4.0, SEED + 1, geo)
    assert sorted(map(len, c1)) == sorted(map(len, c2))
    assert np.allclose(np.sort(np.diff(d1)).sum(), np.sort(np.diff(d2)).sum(),
                       rtol=0.2)
    assert not np.array_equal(d1, d2)
    sizes = np.array(sorted(map(len, c1)))
    assert sizes.min() >= traffic["size_min"]
    assert sizes.max() <= traffic["size_max"]
    lo, hi = geo.lo_hi()
    pts = np.concatenate(c1)
    assert (pts >= lo).all() and (pts <= hi).all()
    assert len(d1) == 200 and d1[-1] < 4.5
    # every block of requests holds one size from each stratum
    b = traffic["balance_block"]
    strata = sizes.reshape(b, -1)
    for blk in np.array(list(map(len, c1))).reshape(-1, b):
        got = np.sort(blk)
        assert ((got >= strata.min(axis=1)) & (got <= strata.max(axis=1))).all()


def test_training_points_repeat_from_the_seed():
    cfg = harness.load_json("configs", "usmap_heat_10.json")
    geo = problems.Geometry(cfg["domain"])
    a = problems.make_data(cfg, geo, SEED)
    b = problems.make_data(cfg, geo, SEED)
    assert all(np.array_equal(x, y) for x, y in zip(a.res, b.res))
    assert [len(x) for x in a.res] == cfg["n_res"]
    for q, x in enumerate(a.res):
        assert problems.in_polygon(x, geo.polys[q]).all()
    # every interface lies on both of its subdomains' boundaries
    for f in a.ifaces:
        for q in (f.a, f.b):
            d = problems.dist_to_polygon_edges(f.pts, geo.polys[q])
            assert d.max() < 1e-9


def test_weights_repeat_from_a_wide_seed():
    import jax

    cfg = harness.load_json("configs", "burgers_xpinn_2x2.json")
    w1 = problems.make_weights(cfg, 4, SEED)
    w2 = problems.make_weights(cfg, 4, SEED)
    w3 = problems.make_weights(cfg, 4, SEED + 2**32)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), w1, w2)
    assert all(jax.tree.leaves(same))
    assert not bool((w1["u"]["W"][1] == w3["u"]["W"][1]).all())
