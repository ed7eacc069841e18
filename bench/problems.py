"""The benchmark's own problem: geometry, training points, exact data and
weights, all made from a configuration file and the run's seed.

Nothing here imports the program.  The geometry is recomputed from the
configuration (grid bounds or polygon vertices), the points are drawn with
numpy from the seed, and the weights are drawn on the device in one jitted
call.  The same arrays feed the program (packed into its padded, slot-ordered
layout by ``pack_batch``) and the plain reference (as unpadded per-subdomain
lists), so the reference takes nothing that the program made.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------------ seeds

def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def key(seed: int) -> jax.Array:
    """A JAX key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


# --------------------------------------------------------------- geometry

@dataclass
class Interface:
    a: int
    b: int
    pts: np.ndarray       # (n_iface, 2)
    normal_a: np.ndarray  # (n_iface, 2), pointing out of subdomain a


def _seg_points(segs, n: int, r: np.random.Generator):
    """n random points on a polyline (list of (p0, p1)), spread over the
    segments in proportion to their length; returns points and the segment
    index of each."""
    lens = np.array([np.linalg.norm(p1 - p0) for p0, p1 in segs])
    alloc = np.floor(n * lens / lens.sum()).astype(int)
    for i in np.argsort(-(n * lens / lens.sum() - alloc))[: n - alloc.sum()]:
        alloc[i] += 1
    pts, idx = [], []
    for i, ((p0, p1), k) in enumerate(zip(segs, alloc)):
        t = r.uniform(0.02, 0.98, size=k)
        pts.append(p0[None] + t[:, None] * (p1 - p0)[None])
        idx.append(np.full(k, i))
    return np.concatenate(pts), np.concatenate(idx)


def _rot_out(p0, p1):
    """Unit normal of a counter-clockwise polygon edge, pointing outward."""
    d = p1 - p0
    nrm = np.array([d[1], -d[0]])
    return nrm / np.linalg.norm(nrm)


def in_polygon(pts: np.ndarray, poly: np.ndarray) -> np.ndarray:
    """Even-odd test of each point against one polygon."""
    x, y = pts[:, 0], pts[:, 1]
    inside = np.zeros(len(pts), bool)
    for i in range(len(poly)):
        (xi, yi), (xj, yj) = poly[i], poly[i - 1]
        with np.errstate(divide="ignore", invalid="ignore"):
            xc = xi + (y - yi) * (xj - xi) / (yj - yi)
        inside ^= ((yi > y) != (yj > y)) & (x < xc)
    return inside


def dist_to_polygon_edges(pts: np.ndarray, poly: np.ndarray) -> np.ndarray:
    out = np.full(len(pts), np.inf)
    for i in range(len(poly)):
        p0, p1 = poly[i - 1], poly[i]
        d = p1 - p0
        t = np.clip(((pts - p0) @ d) / (d @ d), 0.0, 1.0)
        out = np.minimum(out, np.linalg.norm(pts - p0 - t[:, None] * d, axis=1))
    return out


class Geometry:
    """Subdomains, their shared interfaces and their share of the global
    boundary, recomputed from the configuration."""

    def __init__(self, dom: dict):
        self.kind = dom["kind"]
        if self.kind == "cartesian":
            (x0, x1), (y0, y1) = dom["bounds"]
            self.nx, self.ny = int(dom["nx"]), int(dom["ny"])
            self.xs = np.linspace(x0, x1, self.nx + 1)
            self.ys = np.linspace(y0, y1, self.ny + 1)
            polys = []
            for ix in range(self.nx):          # rank q = ix * ny + iy
                for iy in range(self.ny):
                    xa, xb = self.xs[ix], self.xs[ix + 1]
                    ya, yb = self.ys[iy], self.ys[iy + 1]
                    polys.append(np.array([[xa, ya], [xb, ya], [xb, yb],
                                           [xa, yb]]))
        elif self.kind == "polygons":
            polys = [np.asarray(p, np.float64) for p in dom["polygons"]]
        else:
            raise ValueError(f"unknown domain kind {self.kind!r}")
        for p in polys:
            area = 0.5 * np.sum(p[:, 0] * np.roll(p[:, 1], -1)
                                - np.roll(p[:, 0], -1) * p[:, 1])
            if area <= 0:
                raise ValueError("polygons must be counter-clockwise")
        self.polys = polys
        self.n_sub = len(polys)
        # shared edges: identical vertex pairs in two polygons
        owner, shared = {}, {}
        self.boundary = {q: [] for q in range(self.n_sub)}
        for q, p in enumerate(polys):
            for i in range(len(p)):
                p0, p1 = p[i], p[(i + 1) % len(p)]
                k = tuple(sorted((tuple(p0), tuple(p1))))
                if k in owner:
                    q0, seg0 = owner.pop(k)
                    a, b = min(q0, q), max(q0, q)
                    shared.setdefault((a, b), []).append(
                        seg0 if q0 == a else (p0, p1))
                else:
                    owner[k] = (q, (p0, p1))
        for q, seg in owner.values():
            self.boundary[q].append(seg)
        self.pairs = sorted(shared)
        self._shared = shared

    def lo_hi(self):
        v = np.concatenate(self.polys)
        return v.min(axis=0), v.max(axis=0)

    def sample_interior(self, q: int, n: int, r: np.random.Generator):
        p = self.polys[q]
        lo, hi = p.min(axis=0), p.max(axis=0)
        if self.kind == "cartesian":
            return r.uniform(lo, hi, size=(n, 2))
        out = np.zeros((0, 2))
        while len(out) < n:
            c = r.uniform(lo, hi, size=(4 * n + 64, 2))
            out = np.concatenate([out, c[in_polygon(c, p)]])
        return out[:n]

    def interfaces(self, n_iface: int, r: np.random.Generator):
        out = []
        for a, b in self.pairs:
            segs = self._shared[(a, b)]
            pts, idx = _seg_points(segs, n_iface, r)
            nrm = np.stack([_rot_out(*segs[i]) for i in idx])
            out.append(Interface(a, b, pts, nrm))
        return out

    def claims(self, pts: np.ndarray, tol: float) -> np.ndarray:
        """(n_sub, N): subdomain q claims a point inside it or within tol of
        its edges; a point claimed twice is served as the mean of both."""
        return np.stack([in_polygon(pts, p)
                         | (dist_to_polygon_edges(pts, p) <= tol)
                         for p in self.polys])


# -------------------------------------------------------------- PDE data

def exact_heat(pts: np.ndarray) -> np.ndarray:
    """(T, K) of the section-7.6 manufactured solution."""
    T = 20.0 * np.exp(-0.1 * pts[:, 1])
    K = 20.0 + np.exp(0.1 * pts[:, 1]) * np.sin(0.5 * pts[:, 0])
    return np.stack([T, K], axis=-1)


def boundary_data(cfg: dict, geo: Geometry, q: int, n: int,
                  r: np.random.Generator):
    """Points on subdomain q's share of the boundary where data is given,
    with their values and a per-field selector."""
    kind = cfg["pde"]["kind"]
    segs = geo.boundary[q]
    if kind == "burgers1d":
        # initial line t = t0 and the walls x = x0, x1; the final time has
        # no data
        (x0, x1), (t0, _t1) = cfg["domain"]["bounds"]
        segs = [(p0, p1) for p0, p1 in segs
                if (p0[1] == t0 and p1[1] == t0)
                or (p0[0] == p1[0] and p0[0] in (x0, x1))]
    if not segs or n == 0:
        return np.zeros((0, 2)), np.zeros((0, 1)), np.zeros((0, 1))
    pts, _ = _seg_points(segs, n, r)
    if kind == "burgers1d":
        (_x0, _x1), (t0, _t1) = cfg["domain"]["bounds"]
        on_ic = np.isclose(pts[:, 1], t0)
        vals = np.where(on_ic, -np.sin(np.pi * pts[:, 0]), 0.0)[:, None]
        return pts, vals, np.ones_like(vals)
    if kind == "heat2d_inverse":
        return pts, exact_heat(pts), np.ones((len(pts), 2))
    raise ValueError(kind)


def n_fields(cfg: dict) -> int:
    return sum(n["out_dim"] for n in cfg["nets"].values())


@dataclass
class Data:
    """Unpadded per-subdomain training data (the reference's form)."""

    res: list            # n_sub x (n_q, 2)
    data_pts: list       # n_sub x (m_q, 2)
    data_vals: list      # n_sub x (m_q, F)
    data_comp: list      # n_sub x (m_q, F)
    ifaces: list         # [Interface]


def make_data(cfg: dict, geo: Geometry, seed: int) -> Data:
    r = rng(seed)
    res, dp, dv, dc = [], [], [], []
    for q in range(geo.n_sub):
        res.append(geo.sample_interior(q, int(cfg["n_res"][q]), r))
        p, v, c = boundary_data(cfg, geo, q, int(cfg["n_bnd"]), r)
        if cfg.get("n_interior_data", 0):
            ip = geo.sample_interior(q, int(cfg["n_interior_data"]), r)
            iv = exact_heat(ip)
            ic = np.zeros_like(iv)
            ic[:, 0] = 1.0              # T observed inside, K unknown
            p, v, c = (np.concatenate([p, ip]), np.concatenate([v, iv]),
                       np.concatenate([c, ic]))
        dp.append(p)
        dv.append(v)
        dc.append(c)
    return Data(res, dp, dv, dc, geo.interfaces(int(cfg["n_iface"]), r))


def pack_batch(data: Data, neighbor: np.ndarray, n_iface: int) -> dict:
    """The program's padded, slot-ordered batch layout.  ``neighbor`` is the
    program's own (n_sub, K) slot table; each interface's points go into the
    slot where the program pairs the same two subdomains."""
    n_sub, K = neighbor.shape

    def pad(arrs):
        m = max(1, max(len(a) for a in arrs))
        out = np.zeros((n_sub, m) + arrs[0].shape[1:], np.float32)
        mask = np.zeros((n_sub, m), np.float32)
        for q, a in enumerate(arrs):
            out[q, :len(a)] = a
            mask[q, :len(a)] = 1.0
        return out, mask

    res, res_mask = pad(data.res)
    dp, dmask = pad(data.data_pts)
    dv, _ = pad(data.data_vals)
    dc, _ = pad(data.data_comp)
    ipts = np.zeros((n_sub, K, n_iface, 2), np.float32)
    inrm = np.zeros((n_sub, K, n_iface, 2), np.float32)
    inrm[..., 0] = 1.0
    emask = np.zeros((n_sub, K), np.float32)
    for f in data.ifaces:
        ks = np.flatnonzero(neighbor[f.a] == f.b)
        if len(ks) != 1 or neighbor[f.b, ks[0]] != f.a:
            raise RuntimeError(f"the program's topology does not pair "
                               f"subdomains {f.a} and {f.b} in one slot")
        k = int(ks[0])
        ipts[f.a, k] = ipts[f.b, k] = f.pts
        inrm[f.a, k], inrm[f.b, k] = f.normal_a, -f.normal_a
        emask[f.a, k] = emask[f.b, k] = 1.0
    if emask.sum() != (neighbor >= 0).sum():
        raise RuntimeError("the program's topology has interfaces that the "
                           "geometry does not")
    return dict(res_pts=res, res_mask=res_mask, data_pts=dp, data_vals=dv,
                data_comp=dc, data_mask=dmask, iface_pts=ipts,
                iface_nrm=inrm, edge_mask=emask)


# ---------------------------------------------------------------- weights

def make_weights(cfg: dict, n_sub: int, seed: int):
    """Stacked per-subdomain parameters in the program's layout
    ``{net: {"W": [(n_sub, fi, fo)], "b": [(n_sub, fo)], "a": (n_sub, L)}}``,
    Xavier-normal weights, zero biases and unit adaptive slopes, made on the
    device in one jitted call from the seed."""
    nets = cfg["nets"]

    def build(k):
        out = {}
        for i, (name, n) in enumerate(sorted(nets.items())):
            dims = ([n["in_dim"]] + [n["width"]] * n["depth"]
                    + [n["out_dim"]])
            ks = jax.random.split(jax.random.fold_in(k, i), len(dims) - 1)
            Ws = [jax.random.normal(kk, (n_sub, fi, fo), jnp.float32)
                  * math.sqrt(2.0 / (fi + fo))
                  for kk, fi, fo in zip(ks, dims[:-1], dims[1:])]
            bs = [jnp.zeros((n_sub, fo), jnp.float32) for fo in dims[1:]]
            out[name] = {"W": Ws, "b": bs,
                         "a": jnp.ones((n_sub, n["depth"]), jnp.float32)}
        return {name: out[name] for name in nets}

    return jax.jit(build)(key(seed))
