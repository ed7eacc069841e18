"""The main path's Pallas kernels compile for a TPU v5e chip.

Compiles against a described (not attached) ``v5e:2x2`` topology, so the
chip's own compiler refuses here what interpret mode cannot see: block
tiling rules, VMEM limits, device memory.  Shapes are the chip smoke's
(``chip_smoke.py``): 5 hidden layers of width 20 (padded to 128 lanes),
``d_in = 2``, ``block_n = 256`` and 20,224 rows (20,000 residual, 40
interface and 80 boundary points of one subdomain, padded to the block).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  All such compiles stay in this one file for the same reason.
"""
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.obs.profiling import SCOPES
from repro.kernels.pinn_mlp import (WPAD, pinn_mlp_pallas2,
                                    pinn_mlp_pallas2_bwd,
                                    pinn_mlp_pallas2_res)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, D_IN, BLOCK_N, ROWS = 5, 2, 256, 20224
N_SUB, WIDTH, N_POINTS = 4, 20, 20120
HBM_BYTES = 16 * 2**30          # one v5e chip
PRECISIONS = [None, "highest"]  # training default; the smoke's oracle phase


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe the chip with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args, precision):
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used} B does not fit one chip"
    return compiled.as_text()


def _kernel_case(name, sds):
    x = sds(ROWS, WPAD)
    w, b, a = sds(L + 1, WPAD, WPAD), sds(L + 1, WPAD), sds(L + 1)
    kw = dict(d_in=D_IN, act="tanh", block_n=BLOCK_N, interpret=False)
    if name == "_kernel2":
        return lambda *r: pinn_mlp_pallas2(*r, **kw), (x, w, b, a)
    if name == "_kernel2_res":
        return lambda *r: pinn_mlp_pallas2_res(*r, **kw), (x, w, b, a)
    stream = sds(L, D_IN, ROWS, WPAD)
    tangent = sds(D_IN, ROWS, WPAD)
    return (lambda *r: pinn_mlp_pallas2_bwd(*r, **kw),
            (x, w, a, sds(L, ROWS, WPAD), stream, stream, x, tangent,
             tangent))


def _mlp_shapes(sds):
    dims = [D_IN] + [WIDTH] * L + [1]
    Ws = tuple(sds(N_SUB, i, o) for i, o in zip(dims[:-1], dims[1:]))
    bs = tuple(sds(N_SUB, o) for o in dims[1:])
    return sds(N_SUB, N_POINTS, D_IN), Ws, bs, sds(N_SUB, L)


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("kernel", ["_kernel2", "_kernel2_res",
                                    "_kernel2_bwd"])
def test_kernel_compiles_for_v5e(one_chip, kernel, precision):
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    fn, args = _kernel_case(kernel, sds)
    assert "tpu_custom_call" in _compile(fn, args, precision)


@pytest.mark.parametrize("precision", PRECISIONS)
def test_vmapped_training_grad_compiles_for_v5e(one_chip, precision):
    """The training step's kernel use: the grad of the second-order forward,
    vmapped over four subdomains (``interpret=False`` is explicit because
    ``ops._on_tpu()`` sees the CPU here)."""
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    hlo = _compile(_training_grad(), _mlp_shapes(sds), precision)
    assert hlo.count("tpu_custom_call") >= 2   # _kernel2_res + _kernel2_bwd
    assert "pinn2-bwd-fused" in hlo and "pinn2-bwd-ref" not in hlo
    _assert_kernel_names(hlo, ("kernel_res", "bwd_fused"))


def _training_grad():
    def loss(x, Ws, bs, a):
        u, du, d2u = ops.pinn_mlp_forward2(x, Ws, bs, a, interpret=False,
                                           d2_dirs=(0,))
        return jnp.sum(u ** 2) + jnp.sum(du ** 2) + jnp.sum(d2u ** 2)

    def total(x, Ws, bs, a):
        return jnp.sum(jax.vmap(loss)(x, Ws, bs, a))

    return jax.grad(total, argnums=(1, 2, 3))


def _serving_forward(order):
    fwd = ops.pinn_mlp_forward2 if order == 2 else ops.pinn_mlp_forward
    return jax.vmap(lambda x, W, b, a: fwd(x, W, b, a, interpret=False))


def test_vmapped_serving_forward_compiles_for_v5e(one_chip):
    """The serving engine's kernel use: ``_kernel2`` vmapped over the
    stacked subdomain axis."""
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    hlo = _compile(_serving_forward(2), _mlp_shapes(sds), None)
    assert "tpu_custom_call" in hlo
    _assert_kernel_names(hlo, ("kernel_eval2",))


def _kernel_reader(metric):
    """``is_kernel`` of the benchmark's reader ``bench/metrics/<metric>.py``,
    loaded by path as the harness loads it."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        "kernel_reader_" + metric,
        os.path.join(REPO, "bench", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.is_kernel


def _assert_kernel_names(hlo, phases):
    """Every compiled Pallas kernel is ``%<name>.N`` with ``<name>`` from
    ``SCOPES``, whatever jit / vmap / jvp wraps it, and the benchmark's
    kernel readers each accept exactly their training kernel's name and
    never a serving forward's."""
    calls = [ln.split(" = ")[0].strip() for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    bases = [re.fullmatch(r"%(.+)\.\d+", c).group(1) for c in calls]
    assert sorted(set(bases)) == sorted(SCOPES[p] for p in phases), calls
    res, bwd = (_kernel_reader("kernel_res_roofline"),
                _kernel_reader("kernel_bwd_roofline"))
    for c, base in zip(calls, bases):
        name = c + " [tpu_custom_call]"       # as bench/trace.py names it
        assert res(name) == (base == SCOPES["kernel_res"]), name
        assert bwd(name) == (base == SCOPES["bwd_fused"]), name
    for phase in ("kernel_eval2", "kernel_eval1"):
        name = f"%{SCOPES[phase]}.7 [tpu_custom_call]"
        assert not res(name) and not bwd(name)


def test_kernel_names_are_stable_for_v5e(one_chip):
    """The first-order serving forward's kernel, vmapped as the engine
    vmaps it, is named from ``SCOPES`` too (the training grad's and the
    second-order forward's names are checked where they are compiled
    above)."""
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    hlo = _compile(_serving_forward(1), _mlp_shapes(sds), None)
    _assert_kernel_names(hlo, ("kernel_eval1",))
