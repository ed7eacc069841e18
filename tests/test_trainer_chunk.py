"""Single-dispatch training: scanned run_chunk drivers + megabatched entry.

Covers the step-fusion contract (EXPERIMENTS.md §Step fusion):

* ``run_chunk(state, batch, n)`` bitwise-matches ``n`` sequential ``step()``
  calls (Reference in-process, Distributed in a 4-device subprocess), incl.
  ``local_steps > 1`` and per-step stacked batches;
* ``TrainState`` donation: buffers really alias in place and repeated chunks
  never trip stale-buffer reuse;
* one loss evaluation == ONE megabatched network entry (trace-counted) and one
  packed weight stack per chunk body (HLO pad count — extends the PR-1 CSE
  test to the scanned driver);
* DataParallelTrainer derives its activation from the model config.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Burgers1D, CartesianDecomposition, DDConfig, ReferenceTrainer, XPINN,
    build_topology, evaluate_l2,
)
from repro.core import nets
from repro.core.losses import CPINN, ResidualPath
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.core.trainer import DataParallelTrainer
from repro.data import make_batch, stack_batches
from repro.kernels import ops


def _setup(n_res=64, width=20, depth=3):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=8)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    batch = make_batch(dec, topo, pde, n_res=n_res, n_bnd=16,
                       rng=np.random.default_rng(0))
    return pde, dec, topo, cfg, batch.device_arrays()


def _max_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("path", ["jvp", "pallas"])
@pytest.mark.parametrize("method,local_steps", [(XPINN, 1), (CPINN, 2)])
def test_reference_chunk_matches_step_loop_bitwise(path, method, local_steps):
    pde, dec, topo, cfg, b = _setup()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=method, residual_path=path,
                                   local_steps=local_steps))
    s_loop = tr.init(0)
    for _ in range(3):
        s_loop, t_loop = tr.step(s_loop, b)
    s_chunk, t_chunk = tr.run_chunk(tr.init(0), b, 3)
    assert _max_diff(s_loop.params, s_chunk.params) == 0.0
    assert _max_diff(s_loop.opt, s_chunk.opt) == 0.0
    assert int(s_chunk.step) == 3
    # terms come back stacked (steps, n_sub); the last row is the loop's terms
    for k in t_loop:
        np.testing.assert_array_equal(np.asarray(t_chunk[k])[-1],
                                      np.asarray(t_loop[k]))


def test_reference_chunk_stacked_batches_matches_sequential_steps():
    """steps=None mode: leaves carry a leading chunk axis, one batch per step."""
    pde, dec, topo, cfg, _ = _setup()
    tr = ReferenceTrainer(pde, cfg, topo, DDConfig(residual_path="pallas"))
    batches = [make_batch(dec, topo, pde, n_res=64, n_bnd=16,
                          rng=np.random.default_rng(s)).device_arrays()
               for s in range(3)]
    s_loop = tr.init(1)
    for bb in batches:
        s_loop, _ = tr.step(s_loop, bb)
    s_chunk, terms = tr.run_chunk(tr.init(1), stack_batches(batches))
    assert _max_diff(s_loop.params, s_chunk.params) == 0.0
    assert np.asarray(terms["loss"]).shape == (3, topo.n_sub)


def test_run_chunk_donates_state_and_chains_cleanly():
    """donate_argnums on TrainState: the old buffers die (no silent copies)
    and chaining chunks off the returned state never hits stale-buffer reuse."""
    pde, dec, topo, cfg, b = _setup()
    tr = ReferenceTrainer(pde, cfg, topo, DDConfig(residual_path="pallas"))
    state = tr.init(0)
    leaves0 = jax.tree.leaves(state.params) + jax.tree.leaves(state.opt)
    state, _ = tr.run_chunk(state, b, 2)
    assert all(leaf.is_deleted() for leaf in leaves0), \
        "donated TrainState buffers were copied instead of aliased"
    # the returned state is fresh and immediately reusable — twice
    for expect in (4, 6):
        state, terms = tr.run_chunk(state, b, 2)
        assert int(state.step) == expect
        assert np.isfinite(np.asarray(terms["loss"])).all()


@pytest.mark.parametrize("local_steps", [1, 3])
def test_chunk_body_has_one_network_entry_per_loss_eval(local_steps):
    """Acceptance: the jitted chunk traces exactly ONE megabatched
    pinn_mlp_forward2 entry per loss evaluation — the exchange payload rides
    on inner step 1's forward (jax.vjp), so local_steps == entries, regardless
    of chunk length."""
    pde, dec, topo, cfg, b = _setup(n_res=32, width=16, depth=2)
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(residual_path="pallas",
                                   local_steps=local_steps))
    state = tr.init(0)
    calls = []
    orig = ops.pinn_mlp_forward2
    ops.pinn_mlp_forward2 = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        jax.jit(tr._run_chunk_const, static_argnums=(2,)).lower(state, b, 5)
    finally:
        ops.pinn_mlp_forward2 = orig
    assert len(calls) == local_steps, (
        f"chunk body traced {len(calls)} network entries for "
        f"{local_steps} loss evaluations")


def test_chunk_hlo_packs_weights_once_per_loss_eval():
    """HLO extension of the PR-1 pad-count test: the compiled scanned chunk
    pads/stacks the layer weights exactly once per loss evaluation (here
    local_steps=1 -> one (L, 128, 128) pack of the layers after the first
    for the whole body, which the reverse reuses), and the megabatch means
    ONE padded point tensor, not one per res/iface/data set."""
    pde, dec, topo, cfg, b = _setup(n_res=32, width=16, depth=2)
    tr = ReferenceTrainer(pde, cfg, topo, DDConfig(residual_path="pallas"))
    # force the padded Pallas dispatch (interpret mode); the CPU production
    # path is the unpadded jnp recurrence, which never packs
    tr.res_path = ResidualPath(act="tanh", block_n=32, interpret=True)
    state = tr.init(0)

    def weight_pads(txt):
        # packed weight stacks under vmap: f32[n_sub, 128, 128] pads
        return sum(1 for ln in txt.splitlines()
                   if " pad(" in ln and "f32[4,128,128]" in ln)

    txt3 = jax.jit(tr._run_chunk_const, static_argnums=(2,)).lower(
        state, b, 3).compile().as_text()
    # depth-2 MLP: the hidden and the output weight matrix; the first
    # layer packs against x's rows, not into the (L, 128, 128) stack
    n_layer_mats = 2
    assert weight_pads(txt3) == n_layer_mats, \
        "chunk body packs the weight stack more than once per loss evaluation"
    # chunk length must not change the per-body pack count
    txt1 = jax.jit(tr._run_chunk_const, static_argnums=(2,)).lower(
        state, b, 1).compile().as_text()
    assert weight_pads(txt1) == weight_pads(txt3)


@pytest.mark.parametrize("backward_path", ["fused", "ref"])
def test_chunk_backward_routes_through_selected_reverse(backward_path):
    """HLO acceptance for the backward kernel: the compiled scanned chunk's
    backward contains the hand-derived fused reverse sweep (named-scope marker
    'pinn2-bwd-fused') and NO unrolled checkpointed-ref chain — and routes to
    the checkpointed oracle when backward_path='ref' is requested."""
    pde, dec, topo, cfg, b = _setup(n_res=32, width=16, depth=2)
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(residual_path="pallas",
                                   backward_path=backward_path))
    state = tr.init(0)
    txt = jax.jit(tr._run_chunk_const, static_argnums=(2,)).lower(
        state, b, 2).compile().as_text()
    has_fused, has_ref = "pinn2-bwd-fused" in txt, "pinn2-bwd-ref" in txt
    if backward_path == "fused":
        assert has_fused and not has_ref, (has_fused, has_ref)
    else:
        assert has_ref and not has_fused, (has_fused, has_ref)


def test_chunk_fused_and_ref_backward_agree():
    """Selector round-trip at the trainer level: a chunk trained with the
    hand-derived fused backward lands on the same loss as the checkpointed-ref
    backward (different implementations of the same gradient)."""
    pde, dec, topo, cfg, b = _setup()
    final = {}
    for bp in ("fused", "ref"):
        tr = ReferenceTrainer(pde, cfg, topo,
                              DDConfig(residual_path="pallas",
                                       backward_path=bp))
        _, terms = tr.run_chunk(tr.init(0), b, 10)
        final[bp] = np.asarray(terms["loss"])[-1]
    np.testing.assert_allclose(final["fused"], final["ref"], rtol=1e-3,
                               atol=1e-6)


def test_evaluate_l2_vectorized_matches_per_subdomain_loop():
    """The vmapped evaluation reproduces the per-subdomain Python loop."""
    pde, dec, topo, cfg, b = _setup()
    tr = ReferenceTrainer(pde, cfg, topo, DDConfig(),
                          act_codes=["tanh", "sin", "cos", "tanh"])
    state = tr.init(0)
    got = evaluate_l2(dec, cfg, state.params, tr.act_codes, pde, n_pts=200)

    rng = np.random.default_rng(0)
    errs, refs = [], []
    for q in range(dec.n_sub):
        pts = dec.sample_interior(q, 200 // dec.n_sub + 1, rng)
        ex = pde.exact(pts)
        p_q = jax.tree.map(lambda x: x[q], state.params)
        pred = nets.model_apply(cfg, p_q, jnp.asarray(pts, jnp.float32),
                                tr.act_codes[q])
        errs.append(np.asarray(pred) - ex)
        refs.append(ex)
    want = float(np.linalg.norm(np.concatenate(errs).ravel())
                 / (np.linalg.norm(np.concatenate(refs).ravel()) + 1e-30))
    np.testing.assert_allclose(got, want, rtol=1e-5)


# --------------------------------------------------- DataParallel activation fix

def test_data_parallel_act_derived_from_model_cfg():
    """Regression: DataParallelTrainer no longer hardcodes tanh — the model
    config's activation reaches both the jvp loss and the fused ResidualPath."""
    pde, dec, topo, cfg_tanh, b = _setup()
    cfg_sin = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 20, 3, act="sin")})
    tr = DataParallelTrainer(pde, cfg_sin, n_workers=1, residual_path="pallas")
    assert tr.act == "sin" and tr.res_path.act == "sin"
    assert tr.act_code == nets.ACT_SIN
    st, terms = tr.step(tr.init(0), jax.tree.map(lambda x: x[:1], b))
    assert np.isfinite(float(terms["loss"]))
    # sin != tanh: the derived activation must actually change the loss
    tr_t = DataParallelTrainer(pde, cfg_tanh, n_workers=1, residual_path="pallas")
    _, terms_t = tr_t.step(tr_t.init(0), jax.tree.map(lambda x: x[:1], b))
    assert abs(float(terms["loss"]) - float(terms_t["loss"])) > 1e-6


def test_data_parallel_mixed_acts_rejected():
    """Raise only on genuinely unsupported configs: per-net mixed activations
    (model_apply evaluates all field nets with one activation code)."""
    pde = Burgers1D()
    mixed = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 16, 2, act="tanh"),
                                       "k": MLPConfig(2, 1, 16, 2, act="sin")})
    with pytest.raises(ValueError, match="mixed activations"):
        DataParallelTrainer(pde, mixed, n_workers=1)


# --------------------------------------------------- distributed (subprocess)

DIST_CHUNK_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch

pde = Burgers1D()
dec = CartesianDecomposition(((-1,1),(0,1)), nx=2, ny=2)
topo = build_topology(dec, n_iface=8)
cfg = SubdomainModelConfig(nets={"u": MLPConfig(2,1,16,2)})
batch = make_batch(dec, topo, pde, n_res=48, n_bnd=16, rng=np.random.default_rng(0))
b = batch.device_arrays()

for path, local_steps in [("pallas", 1), ("jvp", 2)]:
    dd = DDConfig(method=XPINN, residual_path=path, local_steps=local_steps)
    tr = DistributedDDTrainer(pde, cfg, topo, dd, lrs=[1e-3,2e-3,3e-3,4e-3])
    bd = tr.shard_batch(b)
    s_loop = tr.shard_state(tr.init(0))
    for _ in range(3):
        s_loop, t_loop = tr.step(s_loop, bd)
    s_chunk, t_chunk = tr.run_chunk(tr.shard_state(tr.init(0)), bd, 3)
    err = max(float(np.max(np.abs(np.asarray(a)-np.asarray(c))))
              for a, c in zip(jax.tree.leaves(s_loop.params),
                              jax.tree.leaves(s_chunk.params)))
    # the scanned SPMD program is compiled separately from the per-step one,
    # so XLA may fuse (and round) differently: float-noise tolerance here;
    # the single-device Reference trainer equivalence is asserted BITWISE
    assert err < 1e-7, (path, local_steps, err)
    assert int(s_chunk.step) == 3
    tl = np.asarray(t_loop["loss"]); tc = np.asarray(t_chunk["loss"])
    assert tc.shape == (3,) + tl.shape, (tc.shape, tl.shape)
    assert np.allclose(tc[-1], tl, rtol=1e-6, atol=1e-7), (tc[-1], tl)
print("DIST-CHUNK-OK")
"""


@pytest.mark.slow
def test_distributed_chunk_matches_step_loop(subproc):
    out = subproc(DIST_CHUNK_CODE, n_devices=4, timeout=900)
    assert "DIST-CHUNK-OK" in out
