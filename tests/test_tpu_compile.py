"""The main path's Pallas kernels compile for a TPU v5e chip.

Compiles against a described (not attached) ``v5e:2x2`` topology, so the
chip's own compiler refuses here what interpret mode cannot see: block
tiling rules, VMEM limits, device memory.  Shapes are the chip smoke's
(``chip_smoke.py``): 5 hidden layers of width 20 (the five tangent
streams stacked on the sublanes of one tile), ``d_in = 2``, ``block_n = 256``
and 20,224 rows (20,000 residual, 40 interface and 80 boundary points of
one subdomain, padded to the block).  The four-chip cPINN strip's guarded
chunk compiles over all four described chips at the benchmark cell's size.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.obs import halo_traffic
from repro.obs.profiling import SCOPES, launch_counts
from repro.kernels.pinn_mlp import (WPAD, layout, pinn_mlp_pallas2,
                                    pinn_mlp_pallas2_bwd,
                                    pinn_mlp_pallas2_res)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, D_IN, BLOCK_N, ROWS = 5, 2, 256, 20224
N_SUB, WIDTH, N_POINTS = 4, 20, 20120
HBM_BYTES = 16 * 2**30          # one v5e chip
PRECISIONS = [None, "highest"]  # training default; the smoke's oracle phase


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe the chip with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, args, precision):
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used} B does not fit one chip"
    return compiled.as_text()


def _kernel_case(name, sds, width):
    """A kernel's launch and operand shapes for a net of ``width``: the
    chip smoke's 20 stacks all five streams in one tile, the heat map's
    80 takes a tile per stream."""
    lay = layout(D_IN, width, 1)
    x = sds(lay.x_rows, ROWS)
    w, a = sds(L, WPAD, WPAD), sds(L)
    kw = dict(lay=lay, act="tanh", block_n=BLOCK_N, interpret=False)
    fwd = (x, sds(lay.n_tiles, WPAD, lay.x_rows), w, sds(L, lay.seg), a)
    if name == "_kernel2":
        return lambda *r: pinn_mlp_pallas2(*r, **kw), fwd
    if name == "_kernel2_res":
        return lambda *r: pinn_mlp_pallas2_res(*r, **kw), fwd
    return (lambda *r: pinn_mlp_pallas2_bwd(*r, **kw),
            (x, sds(lay.x_rows, WPAD), w, a, sds(L, lay.res_rows, ROWS),
             sds(lay.out_rows, ROWS)))


def _mlp_shapes(sds):
    dims = [D_IN] + [WIDTH] * L + [1]
    Ws = tuple(sds(N_SUB, i, o) for i, o in zip(dims[:-1], dims[1:]))
    bs = tuple(sds(N_SUB, o) for o in dims[1:])
    return sds(N_SUB, N_POINTS, D_IN), Ws, bs, sds(N_SUB, L)


_PHASES = {"_kernel2": "kernel_eval2", "_kernel2_res": "kernel_res",
           "_kernel2_bwd": "bwd_fused"}


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("kernel", ["_kernel2", "_kernel2_res",
                                    "_kernel2_bwd", "_kernel2_res-w80",
                                    "_kernel2_bwd-w80"])
def test_kernel_compiles_for_v5e(one_chip, kernel, precision):
    """Each second-order kernel compiles under its fixed name: at the
    smoke's width 20 with the streams stacked in one tile, and (``-w80``)
    at width 80 with one stream per tile."""
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    name, _, width = kernel.partition("-w")
    fn, args = _kernel_case(name, sds, int(width or WIDTH))
    _assert_kernel_names(_compile(fn, args, precision), (_PHASES[name],))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_vmapped_training_grad_compiles_for_v5e(one_chip, precision):
    """The training step's kernel use: the grad of the second-order forward,
    vmapped over four subdomains (``interpret=False`` is explicit because
    ``ops._on_tpu()`` sees the CPU here)."""
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    before = launch_counts()
    hlo = _compile(_training_grad(), _mlp_shapes(sds), precision)
    after = launch_counts()
    # width 20: both kernels stack all five streams in one tile
    _assert_launch_layouts(before, after, "5_per_tile")
    assert hlo.count("tpu_custom_call") >= 2   # _kernel2_res + _kernel2_bwd
    assert "pinn2-bwd-fused" in hlo and "pinn2-bwd-ref" not in hlo
    _assert_kernel_names(hlo, ("kernel_res", "bwd_fused"))


def _assert_launch_layouts(before, after, name):
    """At least the two training launches were traced between the two
    ``launch_counts()`` readings, all of them in layout ``name``."""
    grew = {k: n - before.get(k, 0) for k, n in after.items()
            if "/" not in k and n != before.get(k, 0)}
    assert set(grew) == {name} and grew[name] >= 2, grew


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
    calls = [ln.split(" = ")[0].strip().removeprefix("ROOT ")
             for ln in hlo.splitlines()
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


def test_four_chip_cpinn_chunk_compiles_for_v5e(v5e_2x2):
    """The benchmark cell ``burgers_cpinn_4x1.train_4chip``: the sharded
    trainer's guarded 100-step chunk, one subdomain per described chip,
    runs the fused kernels on one tile of streams, and each scan step
    issues the halo's 4 collective-permutes (2 edge colours x ``u`` and the
    flux, 80 B each) and the guard's one all-reduce.  ``interpret=False`` is set on the
    trainer's residual path because ``ops._on_tpu()`` sees the CPU here."""
    import dataclasses

    import numpy as np

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench import harness, problems, train
    from repro.core import DistributedDDTrainer, build_topology
    from repro.core.losses import SubBatch

    cfg = harness.Cell("burgers_cpinn_4x1.train_4chip", 1, 1.0, False).config
    pde, decomp, model, dd = train.program_parts(cfg)
    topo = build_topology(decomp, int(cfg["n_iface"]))
    data = problems.make_data(cfg, problems.Geometry(cfg["domain"]), 1)
    b = problems.pack_batch(data, topo.neighbor, int(cfg["n_iface"]))
    mesh = Mesh(np.array(v5e_2x2.devices), ("sub",))
    tr = DistributedDDTrainer(pde, model, topo, dd, mesh=mesh,
                              act_codes=cfg["activations"],
                              lrs=float(cfg["lr"]))
    tr.res_path = dataclasses.replace(tr.res_path, interpret=False)
    sub, rep = NamedSharding(mesh, P("sub")), NamedSharding(mesh, P())
    sds = lambda x, sh: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                             sharding=sh)
    state = jax.tree.map(lambda x: sds(x, sub if np.ndim(x) else rep),
                         tr.init(0))
    args = (state, SubBatch(**{k: sds(v, sub) for k, v in b.items()}),
            sds(np.ones(4, np.float32), sub))

    before = launch_counts()
    hlo = _compile(lambda s, b, lr: tr._run_chunk_guarded(s, b, 100, lr),
                   args, cfg["matmul_precision"])
    after = launch_counts()
    _assert_launch_layouts(before, after, "5_per_tile")
    assert "tpu_custom_call" in hlo and "pinn2-bwd-ref" not in hlo
    _assert_kernel_names(hlo, ("kernel_res", "bwd_fused"))
    in_step = [ln for ln in hlo.splitlines() if "/while/body/" in ln]
    permutes = [ln for ln in in_step if " collective-permute-start(" in ln]
    agree = [ln for ln in in_step if " all-reduce(" in ln]
    assert len(permutes) == 4 and len(agree) == 1, (permutes, agree)
    assert all(SCOPES["comm"] in ln for ln in permutes)
    assert SCOPES["sync"] in agree[0]
    traffic = halo_traffic(hlo)
    assert traffic["collective_permute_ops"] == 4
    assert traffic["collective_permute_bytes"] == 4 * 20 * 4
