"""chip_smoke.py off the chip: its phases at a tiny size on the CPU, the
four-chip comparison on four host devices, its refusal to run without a TPU,
and where the entry points' compile cache goes."""
import dataclasses
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TINY = dataclasses.replace(cs.TABLE2, n_res=64, width=8, depth=2,
                           chunk_steps=20, n_serve=256, serve_requests=5,
                           compare_steps=3)


def test_phases_run_at_tiny_size(tmp_path):
    prob = cs.burgers_problem(TINY)
    tr, state, info = cs.train(prob, TINY, str(tmp_path))
    assert len(info["chunk_losses"]) == TINY.chunks
    # off the chip the chunk holds no Pallas kernel, and the check says so
    with pytest.raises(cs.SmokeFailure, match="no Pallas kernel"):
        cs.check_chunk_hlo(info["hlo"])
    errs = cs.oracle(prob, tr, state.params)
    assert errs["loss_highest"] <= cs.TOL and errs["grad_highest"] <= cs.TOL
    res = cs.serve(prob, state.params, tr.act_codes, TINY, str(tmp_path),
                   step=int(state.step))
    assert res["serve_field_rc"] == 0
    assert max(e for k, e in res["errors"].items()
               if k.endswith("_highest")) <= cs.TOL


@pytest.mark.slow
def test_four_chip_compare_on_host_devices(subproc):
    code = f"""
import dataclasses, json, sys
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
tiny = dataclasses.replace(cs.TABLE2, n_res=64, width=8, depth=2,
                           compare_steps=3)
res = cs.four_chip_compare(tiny)
assert set(res) == {{"xpinn_2x2", "cpinn_4x1"}}, res
print("FOUR-OK", json.dumps(res))
"""
    assert "FOUR-OK" in subproc(code, n_devices=4, timeout=600)


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


CACHE_CODE = """
import os
{env}
import jax, jax.numpy as jnp
from repro.utils import enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
print("CACHE", path, len(os.listdir(path)))
"""


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


@pytest.mark.parametrize("where", ["env", "repo"])
def test_compile_cache_placement(subproc, tmp_path, where):
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set the entries land there and
    nowhere in the repo; otherwise in ``<repo>/.jax_cache``."""
    repo_cache = os.path.join(REPO, ".jax_cache")
    if where == "env":
        env = f"os.environ['JAX_COMPILATION_CACHE_DIR'] = {str(tmp_path)!r}"
    else:
        env = "os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)"
    repo_before = _entries(repo_cache)
    out = subproc(CACHE_CODE.format(env=env))
    _, path, n = next(ln for ln in out.splitlines()
                      if ln.startswith("CACHE")).split()
    assert path == (str(tmp_path) if where == "env" else repo_cache)
    assert int(n) >= 1
    if where == "env":
        assert _entries(repo_cache) == repo_before
