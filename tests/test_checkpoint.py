"""Checkpoint/restart: bitwise resume, atomicity, failure injection, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # container may lack hypothesis; skip, don't error
from hypothesis import given, settings, strategies as st

from repro.checkpoint import ckpt
from repro.runtime import InjectedFailure, balanced_counts, remap_params, run_with_failures


def test_roundtrip(tmp_path):
    tree = {"a": jnp.arange(5.0), "b": [jnp.ones((2, 2)), jnp.zeros(3, jnp.int32)]}
    ckpt.save(str(tmp_path), 7, tree, {"note": "x"})
    out, meta = ckpt.restore(str(tmp_path), tree)
    for x, y in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(x, y)
        assert x.dtype == y.dtype
    assert meta["note"] == "x" and ckpt.latest_step(str(tmp_path)) == 7


def test_keep_last_k_gc(tmp_path):
    tree = {"x": jnp.zeros(2)}
    for s in range(6):
        ckpt.save(str(tmp_path), s, tree, keep=2)
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(dirs) == 2 and ckpt.latest_step(str(tmp_path)) == 5


def test_structure_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.zeros(2)})
    with pytest.raises(ValueError, match="structure mismatch"):
        ckpt.restore(str(tmp_path), {"b": jnp.zeros(2)})
    out, _ = ckpt.restore(str(tmp_path), {"b": jnp.ones(2)}, allow_restructure=True)
    np.testing.assert_array_equal(out["b"], 1.0)  # falls back to template


def test_latest_pointer_survives_gc_races(tmp_path):
    tree = {"x": jnp.zeros(2)}
    ckpt.save(str(tmp_path), 1, tree)
    ckpt.save(str(tmp_path), 2, tree)
    # simulate stale LATEST
    with open(tmp_path / "LATEST", "w") as f:
        f.write("step_0000000099")
    assert ckpt.latest_step(str(tmp_path)) == 2


def test_failure_injection_resumes_to_identical_state(tmp_path):
    """Crash at arbitrary steps; final state equals the uninterrupted run."""
    def init():
        return {"x": jnp.zeros(()), "y": jnp.ones((3,))}

    def step(s):
        return {"x": s["x"] + 1, "y": s["y"] * 1.5 + s["x"]}

    clean = init()
    for _ in range(20):
        clean = step(clean)

    final = run_with_failures(root=str(tmp_path), init_fn=init, step_fn=step,
                              total_steps=20, ckpt_every=4, fail_at=[2, 9, 13, 19])
    np.testing.assert_allclose(final["x"], clean["x"])
    np.testing.assert_allclose(final["y"], clean["y"], rtol=1e-6)


def test_lm_train_resume_bitwise(tmp_path, subproc):
    """launch/train.py --resume: interrupted-then-resumed == straight-through."""
    code = f"""
import sys
sys.argv = ["train", "lm", "--arch", "llama3.2-1b", "--reduced", "--steps", "6",
            "--batch", "2", "--seq", "32", "--ckpt-dir", r"{tmp_path}/a",
            "--ckpt-every", "3", "--log-every", "100"]
from repro.launch.train import main
main()
import numpy as np
from repro.checkpoint import ckpt
a, _ = ckpt.raw_leaves(r"{tmp_path}/a")

# interrupted at 3, then resumed to 6
sys.argv = ["train", "lm", "--arch", "llama3.2-1b", "--reduced", "--steps", "3",
            "--batch", "2", "--seq", "32", "--ckpt-dir", r"{tmp_path}/b",
            "--ckpt-every", "3", "--log-every", "100"]
main()
sys.argv = ["train", "lm", "--arch", "llama3.2-1b", "--reduced", "--steps", "6",
            "--batch", "2", "--seq", "32", "--ckpt-dir", r"{tmp_path}/b",
            "--ckpt-every", "3", "--log-every", "100", "--resume"]
main()
b, _ = ckpt.raw_leaves(r"{tmp_path}/b")
assert set(a) == set(b)
for k in a:
    np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-7), k
print("RESUME-OK")
"""
    out = subproc(code, n_devices=1, timeout=600)
    assert "RESUME-OK" in out


# PINN-trainer checkpoint wiring (save_train_state / restore_train_state with
# bitwise resume through the guarded chunk) is covered in tests/test_serve.py,
# which stays collected when `hypothesis` is absent and this module is skipped.


# ---------------------------------------------------------------- elasticity

def test_remap_params_nearest_centroid():
    from repro.core.domain import CartesianDecomposition
    old = CartesianDecomposition(((0, 1), (0, 1)), 2, 1)   # halves
    new = CartesianDecomposition(((0, 1), (0, 1)), 4, 1)   # quarters
    params = {"w": jnp.asarray(np.array([[1.0], [2.0]]))}
    remapped, src = remap_params(params, old, new)
    np.testing.assert_array_equal(src, [0, 0, 1, 1])
    np.testing.assert_allclose(remapped["w"][:, 0], [1, 1, 2, 2])


@given(st.lists(st.integers(0, 10_000), min_size=1, max_size=32))
@settings(max_examples=50, deadline=None)
def test_balanced_counts_properties(counts):
    out = balanced_counts(counts)
    assert sum(out) == sum(counts)            # budget preserved
    assert max(out) - min(out) <= 1           # perfectly level
    assert len(out) == len(counts)
