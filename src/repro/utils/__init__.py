"""Small shared utilities: pytree helpers, timing, numerics."""
from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Entry points (scripts, launchers, ``chip_smoke.py``) call this before
    their first compile; importing the library never does.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
    directory is set here.  Otherwise the cache is ``<repo>/.jax_cache``: a
    fixed path, because a later run finds its entries only under the same
    path.

    The minimum compile time worth caching drops from JAX's 1 s to 0.  Most
    programs an entry point compiles (eager ops, the serving engine's
    per-bucket programs, single kernels) take less than that each, so the
    default would compile them again on every run; reading an entry back
    costs one small file.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                            "..", "..", ".."))
        path = os.path.join(repo, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def tree_stack(trees: list[Pytree]) -> Pytree:
    """Stack a list of identically-structured pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree: Pytree, n: int) -> list[Pytree]:
    """Inverse of tree_stack."""
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


def tree_bytes(tree: Pytree) -> int:
    """Total bytes of all array leaves."""
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype")
    )


def tree_count(tree: Pytree) -> int:
    """Total number of scalar parameters."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree) if hasattr(x, "shape"))


def tree_allclose(a: Pytree, b: Pytree, rtol: float = 1e-5, atol: float = 1e-6) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    return all(np.allclose(x, y, rtol=rtol, atol=atol) for x, y in zip(la, lb))


def tree_finite(tree: Pytree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


@contextmanager
def timed(out: dict, key: str) -> Iterator[None]:
    """Context manager accumulating wall time into out[key]."""
    t0 = time.perf_counter()
    yield
    out[key] = out.get(key, 0.0) + (time.perf_counter() - t0)


def block_tree(tree: Pytree) -> Pytree:
    """block_until_ready on every leaf (for timing)."""
    return jax.tree.map(lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x, tree)


def time_fn(fn: Callable, *args, iters: int = 10, warmup: int = 2) -> float:
    """Median wall-clock seconds per call of a jitted function."""
    for _ in range(warmup):
        block_tree(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        block_tree(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
