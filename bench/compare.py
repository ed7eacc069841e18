"""The numbers that decide ``correct``: the timed path against the reference.

``rel_err`` is the arithmetic proven on the chip by the repository's smoke
test (largest absolute difference over the largest reference magnitude).
Norm gaps follow the contract: per leaf, the gap between the program's norm
and the reference's (not the norm of their difference), over the larger of
the reference leaf's norm and the median leaf's.
"""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone and is left out of the norm gaps
NEGLIGIBLE = 1e-3


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.max(np.abs(want))
    diff = np.max(np.abs(got - want))
    return float(diff / scale) if scale > 0 else float(diff)


def loss_gap(prog, ref, steps: int) -> float:
    """Worst relative gap of the per-subdomain losses over the first
    ``steps`` steps; (steps, n_sub) arrays."""
    p = np.asarray(prog, np.float64)[:steps]
    r = np.asarray(ref, np.float64)[:steps]
    if not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def leaves(tree, n_sub: int) -> list:
    """Each subdomain's slice of each stacked leaf, as its own leaf."""
    import jax

    flat = [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]
    return [x[q] for x in flat for q in range(n_sub)]


def norm_gap(prog: list, ref: list, keep: np.ndarray, over=np.max) -> float:
    """Worst leaf (or ``over``) of |‖p‖ - ‖r‖| / max(‖r‖, median ‖r‖) over
    kept leaves."""
    pn = np.array([np.linalg.norm(x) for x in prog])
    rn = np.array([np.linalg.norm(x) for x in ref])
    if not np.all(np.isfinite(pn)):
        return float("inf")
    scale = np.maximum(rn, np.median(rn[keep]))
    return float(over((np.abs(pn - rn) / scale)[keep]))


def kept(ref_grad: list) -> np.ndarray:
    g = np.array([np.linalg.norm(x) for x in ref_grad])
    return g >= NEGLIGIBLE * np.median(g)
