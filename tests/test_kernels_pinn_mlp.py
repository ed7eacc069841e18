"""Second-order fused PINN-MLP kernel: parity sweeps, custom VJP, dispatch.

The correctness chain is

    pallas _kernel2 (interpret)  ==  ref.pinn_mlp_ref2 (batched recurrence)
                                 ==  pdes.dir_deriv / dir_deriv2 (per-point
                                     nested jvp — the paper's §4.1 oracle)

plus: the custom VJP differentiates the fused outputs w.r.t. params, the
packed-weight prepare step is CSE'd inside one jit scope, and
``losses.residual_eval`` ACTUALLY routes through the fused bundle when given a
ResidualPath.  The exhaustive sweep is marked ``kernel`` (deselected by
default); a small unmarked subset keeps tier-1 coverage.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fused, losses, nets
from repro.core.losses import ResidualPath
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.core.pdes import Burgers1D, dir_deriv, dir_deriv2
from repro.kernels import ops, pinn_mlp_forward2, ref


def _seed(*parts):
    """Deterministic per-config seed (Python hash() is salted per process)."""
    return zlib.adler32(repr(parts).encode())


def _mk_mlp(rng, d_in, width, depth, out, dtype):
    dims = [d_in] + [width] * depth + [out]
    Ws = [jnp.asarray(rng.normal(0, np.sqrt(2 / (a + b)), (a, b)), dtype)
          for a, b in zip(dims[:-1], dims[1:])]
    bs = [jnp.asarray(rng.normal(0, 0.1, (b,)), dtype) for b in dims[1:]]
    a = jnp.asarray(rng.uniform(0.9, 1.1, (depth,)), dtype)
    return Ws, bs, a


def _closure(Ws, bs, a, act):
    phi = {"tanh": jnp.tanh, "sin": jnp.sin, "cos": jnp.cos}[act]

    def f(y):
        h = y @ Ws[0] + bs[0]
        for l in range(len(Ws) - 1):
            h = phi(a[l] * h)
            h = h @ Ws[l + 1] + bs[l + 1]
        return h

    return f


def _oracle_bundle(Ws, bs, a, act, x):
    """Per-point nested-jvp oracle (pdes.dir_deriv / dir_deriv2)."""
    f = _closure(Ws, bs, a, act)
    d_in = x.shape[1]
    u = jax.vmap(f)(x)
    basis = [jnp.zeros((d_in,)).at[j].set(1.0) for j in range(d_in)]
    du = jnp.stack([jax.vmap(lambda xi, e=e: dir_deriv(f, xi, e))(x) for e in basis])
    d2u = jnp.stack([jax.vmap(lambda xi, e=e: dir_deriv2(f, xi, e))(x) for e in basis])
    return u, du, d2u


def _check(act, dtype, d_in, width, depth, out, n=96, block_n=32):
    rng = np.random.default_rng(_seed(act, d_in, width, depth, out))
    Ws, bs, a = _mk_mlp(rng, d_in, width, depth, out, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (n, d_in)), jnp.float32)
    u_o, du_o, d2u_o = _oracle_bundle(Ws, bs, a, act, x)
    cast = lambda t: jax.tree.map(lambda z: z.astype(dtype), t)
    u, du, d2u = pinn_mlp_forward2(x.astype(dtype), cast(Ws), cast(bs),
                                   a.astype(dtype), act=act, block_n=block_n,
                                   interpret=True)
    if dtype == jnp.float32:
        rtol_u, rtol_d = 1e-4, 1e-4
        atol_u, atol_d = 1e-5, 5e-4
    else:  # bf16: ~8 mantissa bits; second derivatives compound rounding
        rtol_u, rtol_d = 0.05, 0.2
        atol_u, atol_d = 0.05, 0.5
    np.testing.assert_allclose(np.asarray(u, np.float32), u_o, rtol=rtol_u, atol=atol_u)
    np.testing.assert_allclose(np.asarray(du, np.float32), du_o, rtol=rtol_d, atol=atol_d)
    np.testing.assert_allclose(np.asarray(d2u, np.float32), d2u_o, rtol=rtol_d, atol=atol_d)


# ---- tier-1 subset: one config per activation, incl. a width<128 padding edge
@pytest.mark.parametrize("act", ["tanh", "sin", "cos"])
def test_forward2_vs_dir_deriv2_oracle(act):
    _check(act, jnp.float32, d_in=2, width=20, depth=3, out=1)


def test_forward2_width_128_exact_lanes():
    _check("tanh", jnp.float32, d_in=2, width=128, depth=2, out=1)


# ---- exhaustive sweep: acts x dtypes x shapes (run with `pytest -m kernel`)
@pytest.mark.kernel
@pytest.mark.parametrize("act", ["tanh", "sin", "cos"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d_in,width,depth,out", [
    (2, 16, 3, 1),    # narrow width — heavy lane padding
    (2, 40, 8, 3),    # paper's Fig-4 center config
    (3, 64, 5, 2),    # 3 input directions
    (2, 128, 2, 1),   # exact lane width, no padding
    (1, 33, 4, 1),    # single direction, odd width
])
def test_forward2_parity_sweep(act, dtype, d_in, width, depth, out):
    _check(act, dtype, d_in, width, depth, out)


# ---- megabatch (segment-aware) wrapper -------------------------------------

def _check_segments(act, dtype, d_in, width, depth, out, sizes, interpret,
                    block_n=32):
    """One concatenated dispatch == separate per-segment calls: the kernel math
    is row-independent, so segment membership must not matter.  Pallas blocks
    (interpret=True) match BITWISE; the compiled jnp recurrence may pick a
    different XLA gemm strategy per batch size (observed ~5e-8 on degenerate
    single-row segments), so it gets float-noise tolerance."""
    rng = np.random.default_rng(_seed(act, d_in, width, sizes))
    Ws, bs, a = _mk_mlp(rng, d_in, width, depth, out, dtype)
    segs = tuple(jnp.asarray(rng.uniform(-1, 1, (n, d_in)), dtype) for n in sizes)
    fused_out = ops.pinn_mlp_forward2_segments(segs, Ws, bs, a, act=act,
                                               block_n=block_n,
                                               interpret=interpret)
    assert len(fused_out) == len(sizes)
    for x, (u, du, d2u) in zip(segs, fused_out):
        sep = pinn_mlp_forward2(x, Ws, bs, a, act=act, block_n=block_n,
                                interpret=interpret)
        assert u.shape == (x.shape[0], out)
        for got, want in zip((u, du, d2u), sep):
            if interpret:
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            else:
                np.testing.assert_allclose(
                    np.asarray(got, np.float32), np.asarray(want, np.float32),
                    rtol=1e-5, atol=1e-5)


# tier-1 subset: one layout per dispatch path (compiled jnp recurrence +
# Pallas interpreter), sizes straddling a block boundary
@pytest.mark.parametrize("interpret", [None, True])
def test_forward2_segments_match_separate_calls(interpret):
    _check_segments("tanh", jnp.float32, 2, 20, 3, 1, (40, 17, 9), interpret)


# exhaustive megabatch cases ride the kernel marker so default test time does
# not regress (run with `pytest -m kernel`)
@pytest.mark.kernel
@pytest.mark.parametrize("act", ["tanh", "sin", "cos"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("interpret", [None, True])
@pytest.mark.parametrize("sizes", [
    (96, 32, 32),    # block-aligned residual/iface/data layout
    (100, 7, 1),     # ragged segments, minimum-size data segment
    (1, 1, 1),       # degenerate: every segment a single point
    (256, 80, 33),   # >1 point block with ragged tail
])
def test_forward2_segments_parity_sweep(act, dtype, interpret, sizes):
    _check_segments(act, dtype, 2, 24, 3, 1, sizes, interpret)


@pytest.mark.parametrize("interpret", [None, True])
def test_forward2_d2_dirs_pruning(interpret):
    """PDE-declared second-order pruning: selected d2u rows match the full
    computation, pruned rows are exact zeros, and (u, du) are untouched."""
    rng = np.random.default_rng(31)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 3, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (40, 2)), jnp.float32)
    u_f, du_f, d2u_f = pinn_mlp_forward2(x, Ws, bs, a, block_n=32,
                                         interpret=interpret)
    for dirs in ((0,), (1,), ()):
        u, du, d2u = pinn_mlp_forward2(x, Ws, bs, a, block_n=32,
                                       interpret=interpret, d2_dirs=dirs)
        np.testing.assert_allclose(u, u_f, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(du, du_f, rtol=1e-6, atol=1e-7)
        for j in range(2):
            if j in dirs:
                np.testing.assert_allclose(d2u[j], d2u_f[j], rtol=1e-6,
                                           atol=1e-6)
            else:
                assert not np.any(np.asarray(d2u[j])), \
                    f"pruned direction {j} must come back as exact zeros"


def test_forward2_d2_dirs_pruned_grads_match_full():
    """A loss that only reads the selected d2u rows gets the same gradients
    from the pruned custom VJP as from the full one."""
    rng = np.random.default_rng(37)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 3, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (40, 2)), jnp.float32)

    def loss(Ws, bs, a, dirs):
        u, du, d2u = pinn_mlp_forward2(x, Ws, bs, a, d2_dirs=dirs)
        return jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u[0] ** 2)

    gp = jax.grad(loss, argnums=(0, 1, 2))(Ws, bs, a, (0,))
    gf = jax.grad(loss, argnums=(0, 1, 2))(Ws, bs, a, None)
    for lp, lf in zip(jax.tree.leaves(gp), jax.tree.leaves(gf)):
        np.testing.assert_allclose(lp, lf, rtol=1e-5, atol=1e-6)


def test_euler_residual_path_needs_no_d2(monkeypatch):
    """Euler1D declares d2_dirs=(): the fused residual path runs a pruned
    (empty) second-order stream and still matches the jvp oracle."""
    from repro.core.pdes import Euler1D

    pde = Euler1D()
    assert pde.d2_dirs == ()
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 3, 16, 2)})
    params = nets.init_model(cfg, jax.random.PRNGKey(0))
    pts = jnp.asarray(np.random.default_rng(1).uniform(0.1, 0.9, (24, 2)),
                      jnp.float32)
    r_jvp = losses.residual_eval(pde, cfg, params, nets.ACT_TANH, None, pts, None)
    r_pal = losses.residual_eval(pde, cfg, params, nets.ACT_TANH, None, pts,
                                 ResidualPath(act="tanh"))
    np.testing.assert_allclose(r_pal, r_jvp, rtol=1e-4, atol=1e-5)


def test_forward2_segments_grads_match_separate_calls():
    """The megabatch entry differentiates like the separate calls: one custom
    VJP over the concatenated batch == sum of per-segment VJPs."""
    rng = np.random.default_rng(23)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 3, 1, jnp.float32)
    xs = tuple(jnp.asarray(rng.uniform(-1, 1, (n, 2)), jnp.float32)
               for n in (24, 9, 5))

    def loss_seg(Ws, bs, a):
        outs = ops.pinn_mlp_forward2_segments(xs, Ws, bs, a, interpret=True,
                                              block_n=32)
        return sum(jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)
                   for u, du, d2u in outs)

    def loss_sep(Ws, bs, a):
        return sum(
            jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)
            for u, du, d2u in (pinn_mlp_forward2(x, Ws, bs, a, interpret=True,
                                                 block_n=32) for x in xs))

    gf = jax.grad(loss_seg, argnums=(0, 1, 2))(Ws, bs, a)
    go = jax.grad(loss_sep, argnums=(0, 1, 2))(Ws, bs, a)
    for lf, lo in zip(jax.tree.leaves(gf), jax.tree.leaves(go)):
        np.testing.assert_allclose(lf, lo, rtol=1e-5, atol=1e-5)


def test_forward2_block_padding_edge():
    """N not divisible by block_n: wrapper pads rows and slices correctly."""
    rng = np.random.default_rng(5)
    Ws, bs, a = _mk_mlp(rng, 2, 16, 2, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (37, 2)), jnp.float32)
    u, du, d2u = pinn_mlp_forward2(x, Ws, bs, a, block_n=32, interpret=True)
    assert u.shape == (37, 1) and du.shape == (2, 37, 1) and d2u.shape == (2, 37, 1)
    u_o, du_o, d2u_o = _oracle_bundle(Ws, bs, a, "tanh", x)
    np.testing.assert_allclose(u, u_o, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(d2u, d2u_o, rtol=1e-4, atol=5e-4)


def test_forward2_custom_vjp_grads_match_autodiff():
    """The fused op is differentiable w.r.t. (Ws, bs, a); grads match plain
    autodiff through the per-point closure."""
    rng = np.random.default_rng(11)
    Ws, bs, a = _mk_mlp(rng, 2, 24, 3, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (40, 2)), jnp.float32)

    def loss_fused(Ws, bs, a):
        u, du, d2u = pinn_mlp_forward2(x, Ws, bs, a, interpret=True)
        return jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)

    def loss_oracle(Ws, bs, a):
        u, du, d2u = _oracle_bundle(Ws, bs, a, "tanh", x)
        return jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(Ws, bs, a)
    go = jax.grad(loss_oracle, argnums=(0, 1, 2))(Ws, bs, a)
    for lf, lo in zip(jax.tree.leaves(gf), jax.tree.leaves(go)):
        np.testing.assert_allclose(lf, lo, rtol=1e-4, atol=1e-4)


# ---- hand-derived fused backward -------------------------------------------
#
# The backward correctness chain mirrors the forward one:
#
#     pallas _kernel2_bwd (interpret)  ==  ref._ref2_bwd (hand-derived, jnp)
#                                      ==  jax.vjp(ref.pinn_mlp_ref2) (autodiff)
#
# ref.pinn_mlp_ref2_vjp is an INDEPENDENT closed-form derivation (no autodiff
# anywhere), so agreement is two derivations meeting — not the kernel being
# compared against the machinery it replaces.


def _rand_cts(rng, shapes, dtype):
    return tuple(jnp.asarray(rng.normal(0, 1, s), dtype) for s in shapes)


def _vjp_bundle_check(act, d_in, width, depth, out, d2_dirs=None, n=40,
                      block_n=32, rtol=1e-4, atol=1e-4):
    """All three backwards agree on the same random cotangents."""
    rng = np.random.default_rng(_seed("vjp", act, d_in, width, depth, d2_dirs))
    Ws, bs, a = _mk_mlp(rng, d_in, width, depth, out, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (n, d_in)), jnp.float32)
    shapes = ((n, out), (d_in, n, out), (d_in, n, out))
    cts = _rand_cts(rng, shapes, jnp.float32)

    # (1) independent hand derivation (closed form, no jax.vjp)
    outs_hand, vjp_hand = ref.pinn_mlp_ref2_vjp(x, Ws, bs, a, act=act,
                                                d2_dirs=d2_dirs)
    g_hand = vjp_hand(cts)
    # (2) autodiff of the reference recurrence
    outs_auto, vjp_auto = jax.vjp(
        lambda xx, W, b, aa: ref.pinn_mlp_ref2(xx, W, b, aa, act=act,
                                               d2_dirs=d2_dirs),
        x, tuple(Ws), tuple(bs), a)
    g_auto = vjp_auto(cts)
    # (3) the fused Pallas reverse kernel (interpret mode)
    outs_pal, vjp_pal = jax.vjp(
        lambda xx, W, b, aa: pinn_mlp_forward2(xx, W, b, aa, act=act,
                                               block_n=block_n, interpret=True,
                                               d2_dirs=d2_dirs, bwd="fused"),
        x, tuple(Ws), tuple(bs), a)
    g_pal = vjp_pal(cts)

    for o_h, o_a, o_p in zip(outs_hand, outs_auto, outs_pal):
        np.testing.assert_allclose(o_h, o_a, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(o_p), o_a, rtol=1e-5, atol=1e-5)
    for l_h, l_a, l_p in zip(jax.tree.leaves(g_hand), jax.tree.leaves(g_auto),
                             jax.tree.leaves(g_pal)):
        # hand derivation vs autodiff: same math, different reduction order
        np.testing.assert_allclose(l_h, l_a, rtol=rtol, atol=atol)
        # acceptance bound: kernel vs hand-derived oracle <= 1e-5 relative
        # (scaled by the cotangent magnitude per leaf)
        scale = max(1.0, float(np.max(np.abs(l_h))))
        np.testing.assert_allclose(np.asarray(l_p) / scale,
                                   np.asarray(l_h) / scale,
                                   rtol=1e-5, atol=1e-5)


# tier-1 subset: every activation (narrow width — the padding edge) + one
# pruned-direction case
@pytest.mark.parametrize("act", ["tanh", "sin", "cos"])
def test_bwd_parity_hand_vs_autodiff_vs_kernel(act):
    _vjp_bundle_check(act, d_in=2, width=20, depth=3, out=1)


def test_bwd_parity_pruned_dirs():
    _vjp_bundle_check("tanh", d_in=2, width=20, depth=3, out=1, d2_dirs=(0,))


# exhaustive backward sweep (run with `pytest -m kernel`): acts x widths
# (incl. <128 padding and exact-lane) x d2_dirs subsets x input dims
@pytest.mark.kernel
@pytest.mark.parametrize("act", ["tanh", "sin", "cos"])
@pytest.mark.parametrize("d_in,width,depth,out", [
    (2, 16, 3, 1),    # narrow width — heavy lane padding
    (2, 40, 8, 3),    # paper's Fig-4 center config
    (3, 64, 5, 2),    # 3 input directions
    (2, 128, 2, 1),   # exact lane width, no padding
    (1, 33, 4, 1),    # single direction, odd width
])
@pytest.mark.parametrize("d2_dirs", [None, (0,), ()])
def test_bwd_parity_sweep(act, d_in, width, depth, out, d2_dirs):
    _vjp_bundle_check(act, d_in, width, depth, out, d2_dirs)


def test_bwd_selector_roundtrip():
    """bwd='fused' and bwd='ref' are the SAME gradient (up to float noise):
    the selector changes the implementation, never the math."""
    rng = np.random.default_rng(41)
    Ws, bs, a = _mk_mlp(rng, 2, 24, 3, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (40, 2)), jnp.float32)

    def loss(Ws, bs, a, bwd):
        u, du, d2u = pinn_mlp_forward2(x, Ws, bs, a, bwd=bwd)
        return jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)

    gf = jax.grad(loss, argnums=(0, 1, 2))(Ws, bs, a, "fused")
    gr = jax.grad(loss, argnums=(0, 1, 2))(Ws, bs, a, "ref")
    for lf, lr in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
        np.testing.assert_allclose(lf, lr, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="backward path"):
        loss(Ws, bs, a, "nope")


def test_bwd_segments_megabatch_matches_separate():
    """The fused backward composes with the segment megabatch entry."""
    rng = np.random.default_rng(43)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 3, 1, jnp.float32)
    xs = tuple(jnp.asarray(rng.uniform(-1, 1, (n, 2)), jnp.float32)
               for n in (24, 9, 5))

    def loss_seg(Ws, bs, a):
        outs = ops.pinn_mlp_forward2_segments(xs, Ws, bs, a, interpret=True,
                                              block_n=32, bwd="fused")
        return sum(jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)
                   for u, du, d2u in outs)

    g = jax.grad(loss_seg, argnums=(0, 1, 2))(Ws, bs, a)
    # oracle: independent hand-derived VJP per segment, summed
    acc = None
    for x in xs:
        _, vjp = ref.pinn_mlp_ref2_vjp(x, Ws, bs, a)
        u, du, d2u = ref.pinn_mlp_ref2(x, Ws, bs, a)
        cts = (2.0 * u, 2.0 * du, 0.2 * d2u)
        _, cW, cb, ca = vjp(cts)
        gi = (cW, cb, ca)
        acc = gi if acc is None else jax.tree.map(jnp.add, acc, gi)
    for lf, lo in zip(jax.tree.leaves(g), jax.tree.leaves(acc)):
        np.testing.assert_allclose(lf, lo, rtol=1e-4, atol=1e-4)


def test_select_bwd_matches_static_act():
    """The traced-code serving entry differentiates like the static-act path
    for every code (hand-derived select backward)."""
    rng = np.random.default_rng(47)
    Ws, bs, a = _mk_mlp(rng, 2, 16, 2, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (24, 2)), jnp.float32)
    for code_v, act in ((0, "tanh"), (1, "sin"), (2, "cos")):
        def loss_sel(Ws, bs, a):
            u, du, d2u = ops.pinn_mlp_forward2_select(
                x, Ws, bs, a, jnp.asarray(code_v, jnp.int32))
            return jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)

        def loss_ref(Ws, bs, a):
            u, du, d2u = ref.pinn_mlp_ref2(x, Ws, bs, a, act=act)
            return jnp.sum(u ** 2) + jnp.sum(du ** 2) + 0.1 * jnp.sum(d2u ** 2)

        gs = jax.grad(loss_sel, argnums=(0, 1, 2))(Ws, bs, a)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(Ws, bs, a)
        for l1, l2 in zip(jax.tree.leaves(gs), jax.tree.leaves(gr)):
            np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)


def test_pack_mlp_is_cse_d_within_one_jit_scope():
    """Satellite check: two fused calls on the SAME weights inside one jit
    compile to ONE packed weight stack (XLA CSE) — the padding 'prepare' step
    does not re-run per call site."""
    rng = np.random.default_rng(3)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 3, 1, jnp.float32)
    x1 = jnp.asarray(rng.uniform(-1, 1, (32, 2)), jnp.float32)
    x2 = jnp.asarray(rng.uniform(-1, 1, (64, 2)), jnp.float32)

    # interpret=True forces the padded Pallas path (the CPU production dispatch
    # is the unpadded jnp recurrence, which never packs)
    def one_call(Ws, bs, a):
        return sum(jnp.sum(t) for t in pinn_mlp_forward2(x1, Ws, bs, a,
                                                         interpret=True))

    def twice(Ws, bs, a):
        u1 = sum(jnp.sum(t) for t in pinn_mlp_forward2(x1, Ws, bs, a,
                                                       interpret=True))
        u2 = sum(jnp.sum(t) for t in pinn_mlp_forward2(x2, Ws, bs, a,
                                                       interpret=True))
        return u1 + u2

    def count_weight_pads(fn):
        txt = jax.jit(fn).lower(Ws, bs, a).compile().as_text()
        return sum(1 for ln in txt.splitlines()
                   if " pad(" in ln and "f32[128,128]" in ln)

    baseline = count_weight_pads(one_call)
    # guard against the HLO pattern silently rotting: the single-call compile
    # must actually show the packed-weight pads, else the comparison is vacuous
    assert baseline >= 1, "HLO pad pattern matched nothing — update the matcher"
    assert count_weight_pads(twice) <= baseline


def test_model_bundle_width_mask_folding():
    """Width masks fold into the weight stack: bundle == masked mlp_apply."""
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 24, 3)})
    params = nets.init_model(cfg, jax.random.PRNGKey(0))
    mask = jnp.asarray((np.arange(24) < 16).astype(np.float32))
    x = jnp.asarray(np.random.default_rng(0).uniform(-1, 1, (50, 2)), jnp.float32)
    u, du, d2u = fused.model_bundle(cfg, params, x, "tanh", {"u": mask})
    u_ref = nets.model_apply(cfg, params, x, nets.ACT_TANH, {"u": mask})
    np.testing.assert_allclose(u, u_ref, rtol=1e-5, atol=1e-6)
    # derivative check against the masked per-point closure
    f = nets.scalar_field_fn(cfg, params, nets.ACT_TANH, {"u": mask})
    e0 = jnp.zeros((2,)).at[0].set(1.0)
    d2_o = jax.vmap(lambda xi: dir_deriv2(f, xi, e0))(x)
    np.testing.assert_allclose(d2u[0], d2_o, rtol=1e-4, atol=5e-4)


def test_losses_route_through_fused_bundle(monkeypatch):
    """Acceptance: with a ResidualPath, residual evaluation ACTUALLY goes
    through fused.model_bundle (and not the per-point jvp closures)."""
    pde = Burgers1D()
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 16, 2)})
    params = nets.init_model(cfg, jax.random.PRNGKey(0))
    pts = jnp.asarray(np.random.default_rng(1).uniform(-1, 1, (24, 2)), jnp.float32)

    calls = []
    orig = fused.model_bundle
    monkeypatch.setattr(fused, "model_bundle",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])

    r_jvp = losses.residual_eval(pde, cfg, params, nets.ACT_TANH, None, pts, None)
    assert not calls, "jvp path must not touch the fused bundle"
    r_pal = losses.residual_eval(pde, cfg, params, nets.ACT_TANH, None, pts,
                                 ResidualPath(act="tanh"))
    assert calls, "pallas path must route through fused.model_bundle"
    np.testing.assert_allclose(r_pal, r_jvp, rtol=1e-4, atol=1e-5)


def test_forward_packed_matches_unpacked():
    rng = np.random.default_rng(17)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 3, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (40, 2)), jnp.float32)
    packed = ops.pack_mlp(Ws, bs, a)
    u1, du1 = ops.pinn_mlp_forward(x, Ws, bs, a, interpret=True)
    u2, du2 = ops.pinn_mlp_forward_packed(x, packed, out_dim=1, interpret=True)
    np.testing.assert_allclose(u1, u2, rtol=0, atol=0)
    np.testing.assert_allclose(du1, du2, rtol=0, atol=0)


def test_fused_bwd_step_down_is_not_silent():
    """A block too large for the fused reverse kernel's VMEM budget steps
    down to the checkpointed backward with a warning naming the estimate and
    the budget, and its gradients still match the ref backward."""
    rng = np.random.default_rng(23)
    Ws, bs, a = _mk_mlp(rng, 2, 20, 5, 1, jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (40, 2)), jnp.float32)

    def loss(bwd):
        def f(Ws, bs, a):
            u, du, d2u = pinn_mlp_forward2(x, Ws, bs, a, block_n=4096,
                                           interpret=True, bwd=bwd)
            return jnp.sum(u ** 2) + jnp.sum(du ** 2) + jnp.sum(d2u ** 2)
        return f

    with pytest.warns(RuntimeWarning,
                      match=r"needs ~\d+ B of VMEM .*budget \d+ B"):
        g = jax.grad(loss("fused"), argnums=(0, 1, 2))(Ws, bs, a)
    g_ref = jax.grad(loss("ref"), argnums=(0, 1, 2))(Ws, bs, a)
    for l1, l2 in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-7)
