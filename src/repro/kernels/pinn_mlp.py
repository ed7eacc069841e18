"""Fused PINN-MLP forward + input-Jacobian (+ diagonal Hessian) Pallas TPU kernel.

Paper hot-spot (Fig 4): residual-loss evaluation dominates PINN cost.  On TPU, a
PINN MLP is tiny (width <= ~128) so the naive path is HBM-latency-bound: every
layer round-trips (N, width) activations.  This kernel keeps the ENTIRE layer
stack resident in VMEM and fuses the forward pass with a FORWARD-MODE tangent
propagation for all ``d_in`` input directions (tangent rule
``t_l = phi'(a_l z_l) * a_l * (t_{l-1} @ W_l)``), so one HBM read of the
collocation block produces both u and du/dx — the quantities cPINN/XPINN exchange
at interfaces and the building blocks of flux terms.

The second-order variant additionally carries a forward-over-forward tangent
``s`` per direction (``s_l = phi''(z)·a²·t² + phi'(z)·a·s`` through each
activation, then ``s @ W`` through each affine layer), yielding the diagonal
second derivatives d²u/dx_j² — together with (u, du) everything the Burgers /
Navier-Stokes / heat-conduction residuals and cPINN fluxes consume, in ONE
VMEM-resident pass.

Tiling: grid over collocation-point blocks (``block_n`` rows, 8-row sublane
aligned); weights are padded to (WPAD, WPAD) = (128, 128) lanes — MXU-aligned.
Adaptive activations (tanh/sin/cos x trainable slope, paper refs [26,27]) are
selected statically per call.

``ops.pinn_mlp_forward`` / ``ops.pinn_mlp_forward2`` are the jit'd wrappers
(pad, dispatch, slice; forward2 adds a ``jax.custom_vjp`` for training);
``ref.pinn_mlp_ref`` / ``ref.pinn_mlp_ref2`` are the pure-jnp oracles;
``tests/test_kernels_pinn_mlp.py`` sweeps shapes x dtypes x activations in
interpret mode against the per-point ``pdes.dir_deriv2`` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.obs.profiling import SCOPES

WPAD = 128  # lane-aligned padded width


def _act_pair(name: str):
    if name == "tanh":
        return jnp.tanh, lambda z: 1.0 - jnp.tanh(z) ** 2
    if name == "sin":
        return jnp.sin, jnp.cos
    if name == "cos":
        return jnp.cos, lambda z: -jnp.sin(z)
    raise ValueError(name)


def _act_triple(name: str):
    """(phi, phi', phi'') for the second-order tangent rule."""
    if name == "tanh":
        def d2(z):
            th = jnp.tanh(z)
            return -2.0 * th * (1.0 - th * th)
        return jnp.tanh, lambda z: 1.0 - jnp.tanh(z) ** 2, d2
    if name == "sin":
        return jnp.sin, jnp.cos, lambda z: -jnp.sin(z)
    if name == "cos":
        return jnp.cos, lambda z: -jnp.sin(z), lambda z: -jnp.cos(z)
    raise ValueError(name)


def _act_quad(name: str):
    """(phi, phi', phi'', phi''') — the reverse sweep differentiates the
    second-order tangent rule once more, so it consumes one extra derivative
    order than the forward kernel."""
    if name == "tanh":
        def d3(z):
            th = jnp.tanh(z)
            return (6.0 * th * th - 2.0) * (1.0 - th * th)
        return _act_triple("tanh") + (d3,)
    if name == "sin":
        return _act_triple("sin") + (lambda z: -jnp.cos(z),)
    if name == "cos":
        return _act_triple("cos") + (jnp.sin,)
    raise ValueError(name)


def _kernel(x_ref, w_ref, b_ref, a_ref, u_ref, du_ref, *, n_layers, d_in, act):
    """One block of collocation points.

    x_ref:  (block_n, WPAD)          input block (cols >= d_in are zero)
    w_ref:  (n_layers+1, WPAD, WPAD) padded weight stack
    b_ref:  (n_layers+1, WPAD)       padded biases
    a_ref:  (n_layers+1, WPAD)       adaptive slopes, broadcast over lanes
                                     (last row unused)
    u_ref:  (block_n, WPAD)          primal output (cols >= out_dim are junk)
    du_ref: (d_in, block_n, WPAD)    input-Jacobian
    """
    phi, dphi = _act_pair(act)
    x = x_ref[...]
    h = x @ w_ref[0] + b_ref[0][None, :]
    # first-layer tangents: e_j @ W0 = row j of W0
    ts = [jnp.broadcast_to(w_ref[0][j, :][None, :], h.shape) for j in range(d_in)]
    for l in range(n_layers):
        a = a_ref[l:l + 1, :]
        z = a * h
        g = phi(z)
        dg = dphi(z) * a
        ts = [dg * t for t in ts]
        h = g
        w_next = w_ref[l + 1]
        ts = [t @ w_next for t in ts]
        h = h @ w_next + b_ref[l + 1][None, :]
    u_ref[...] = h
    for j in range(d_in):
        du_ref[j, :, :] = ts[j]


def _kernel2_run(x_ref, w_ref, b_ref, a_ref, u_ref, du_ref, d2u_ref,
                 h_ref, t_ref, s_ref, *, n_layers, d_in, act):
    """Shared second-order recurrence body (ONE copy of the tangent math).

    ``h_ref/t_ref/s_ref`` are the optional residual-spill refs of the
    training-forward variant (None for the inference kernel) — residual
    saving must never fork the recurrence itself.
    """
    phi, dphi, d2phi = _act_triple(act)
    x = x_ref[...]
    h = x @ w_ref[0] + b_ref[0][None, :]
    ts = [jnp.broadcast_to(w_ref[0][j, :][None, :], h.shape) for j in range(d_in)]
    ss = [jnp.zeros_like(h) for _ in range(d_in)]
    for l in range(n_layers):
        if h_ref is not None:
            h_ref[l] = h
            for j in range(d_in):
                t_ref[l, j] = ts[j]
                s_ref[l, j] = ss[j]
        a = a_ref[l:l + 1, :]
        z = a * h
        d1 = dphi(z) * a
        d2 = d2phi(z) * (a * a)
        ss = [d2 * t * t + d1 * s for t, s in zip(ts, ss)]
        ts = [d1 * t for t in ts]
        h = phi(z)
        w_next = w_ref[l + 1]
        ts = [t @ w_next for t in ts]
        ss = [s @ w_next for s in ss]
        h = h @ w_next + b_ref[l + 1][None, :]
    u_ref[...] = h
    for j in range(d_in):
        du_ref[j, :, :] = ts[j]
        d2u_ref[j, :, :] = ss[j]


def _kernel2(x_ref, w_ref, b_ref, a_ref, u_ref, du_ref, d2u_ref, *, n_layers,
             d_in, act):
    """Second-order variant: one block of collocation points.

    Same layout as :func:`_kernel` plus

    d2u_ref: (d_in, block_n, WPAD)   diagonal second derivatives d²u/dx_j²

    Per direction j the kernel carries (t_j, s_j) = (first, second) forward
    tangents of the running affine output h.  Through an activation
    ``g = phi(a h)``:  ``t -> phi'(a h)·a·t``,  ``s -> phi''(a h)·a²·t² +
    phi'(a h)·a·s`` (s BEFORE t is overwritten); through an affine layer both
    just multiply by W.  s_0 = 0 because the input enters linearly.
    """
    _kernel2_run(x_ref, w_ref, b_ref, a_ref, u_ref, du_ref, d2u_ref,
                 None, None, None, n_layers=n_layers, d_in=d_in, act=act)


def _kernel2_res(x_ref, w_ref, b_ref, a_ref, u_ref, du_ref, d2u_ref,
                 h_ref, t_ref, s_ref, *, n_layers, d_in, act):
    """:func:`_kernel2` that ALSO spills the reverse sweep's residuals.

    Training forward variant: identical (u, du, d2u) math, plus per activation
    stage l the streams ENTERING it —

    h_ref: (n_layers, block_n, WPAD)        pre-activation affine outputs h_l
    t_ref: (n_layers, d_in, block_n, WPAD)  first-order tangents t_l
    s_ref: (n_layers, d_in, block_n, WPAD)  second-order tangents s_l

    — exactly what :func:`_kernel2_bwd` re-derives the activation factors from
    (phi^(k)(a·h) are recomputed from h; no matmul is ever recomputed).
    """
    _kernel2_run(x_ref, w_ref, b_ref, a_ref, u_ref, du_ref, d2u_ref,
                 h_ref, t_ref, s_ref, n_layers=n_layers, d_in=d_in, act=act)


def _kernel2_bwd(x_ref, w_ref, a_ref, h_ref, t_ref, s_ref,
                 cu_ref, cdu_ref, cd2u_ref,
                 cx_ref, cw_ref, cb_ref, ca_ref, *, n_layers, d_in, act):
    """Hand-derived fused reverse sweep of :func:`_kernel2` (one VMEM pass).

    One block of collocation points walks the layer stack BACKWARD carrying the
    cotangent streams (h̄, t̄_j, s̄_j); per stage the saved residuals (h, t, s)
    reproduce the activation factors p_k = phi^(k)(a·h) and the cotangent rules
    are the paper-derivation transposes of the forward tangent rules (see
    ``ref._ref2_bwd`` — the jnp twin of this kernel — for the formulas).

    Weight / bias / slope cotangents accumulate ACROSS grid blocks: every grid
    step maps cw/cb/ca to the same block (TPU grid iteration is sequential),
    zero-initialized at step 0.

    cu_ref:  (block_n, WPAD)        ū cotangent block
    cdu_ref: (d_in, block_n, WPAD)  d̄u
    cd2u_ref:(d_in, block_n, WPAD)  d̄2u (pruned rows pre-zeroed by the caller)
    cx_ref:  (block_n, WPAD)        x̄ out
    cw_ref:  (n_layers+1, WPAD, WPAD) accumulated W̄ stack
    cb_ref:  (n_layers+1, WPAD)       accumulated b̄ stack
    ca_ref:  (n_layers+1, WPAD)       ā lane-partials (reduce lanes outside;
                                      row n_layers unused)
    """
    phi, dphi, d2phi, d3phi = _act_quad(act)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cw_ref[...] = jnp.zeros(cw_ref.shape, cw_ref.dtype)
        cb_ref[...] = jnp.zeros(cb_ref.shape, cb_ref.dtype)
        ca_ref[...] = jnp.zeros(ca_ref.shape, ca_ref.dtype)

    bar_h = cu_ref[...]
    bar_t = [cdu_ref[j] for j in range(d_in)]
    bar_s = [cd2u_ref[j] for j in range(d_in)]
    for l in reversed(range(n_layers)):
        a = a_ref[l:l + 1, :]
        h = h_ref[l]
        t = [t_ref[l, j] for j in range(d_in)]
        s = [s_ref[l, j] for j in range(d_in)]
        z = a * h
        p1, p2, p3 = dphi(z), d2phi(z), d3phi(z)
        d1 = p1 * a
        d2v = p2 * (a * a)
        g = phi(z)
        # ---- affine layer l+1: W̄, b̄ and pull cotangents through Wᵀ ------
        cw = g.T @ bar_h
        for j in range(d_in):
            t_tl = d1 * t[j]
            s_tl = d2v * t[j] * t[j] + d1 * s[j]
            cw += t_tl.T @ bar_t[j] + s_tl.T @ bar_s[j]
        cw_ref[l + 1] += cw
        cb_ref[l + 1] += jnp.sum(bar_h, axis=0)
        wt = w_ref[l + 1].T
        bar_g = bar_h @ wt
        bar_tt = [bt @ wt for bt in bar_t]
        bar_st = [bs @ wt for bs in bar_s]
        # ---- activation stage l: ā partial, then (h̄, t̄, s̄) --------------
        e1 = p2 * h * a + p1                    # ∂(phi'·a)/∂a
        e2 = p3 * h * (a * a) + 2.0 * p2 * a    # ∂(phi''·a²)/∂a
        ca = bar_g * (p1 * h)
        for j in range(d_in):
            ca += bar_tt[j] * t[j] * e1
            ca += bar_st[j] * (t[j] * t[j] * e2 + s[j] * e1)
        ca_ref[l] += jnp.sum(ca, axis=0)
        p3a3 = p3 * (a * a * a)
        new_h = bar_g * d1
        for j in range(d_in):
            new_h += bar_tt[j] * t[j] * d2v
            new_h += bar_st[j] * (t[j] * t[j] * p3a3 + s[j] * d2v)
        bar_h = new_h
        bar_t = [bar_tt[j] * d1 + bar_st[j] * (2.0 * d2v) * t[j]
                 for j in range(d_in)]
        bar_s = [bar_st[j] * d1 for j in range(d_in)]
    # ---- input affine layer: t₀,j is row j of W₀ broadcast, s₀ = 0 -------
    x = x_ref[...]
    cx_ref[...] = bar_h @ w_ref[0].T
    cw0 = x.T @ bar_h
    rows = jax.lax.broadcasted_iota(jnp.int32, (WPAD, 1), 0)
    for j in range(d_in):
        cw0 += jnp.where(rows == j, 1.0, 0.0) * jnp.sum(bar_t[j], axis=0)[None, :]
    cw_ref[0] += cw0
    cb_ref[0] += jnp.sum(bar_h, axis=0)


def _lane_slopes(a_vec):
    """(L+1,) slopes -> (L+1, WPAD) lane-broadcast tile.  A 1-D slope operand
    stops compiling once the launch is vmapped over subdomains: its batched
    block (1, L+1) breaks Mosaic's rule that the last two block dims be
    (8, 128)-divisible or span the array."""
    return jnp.broadcast_to(a_vec[:, None], (a_vec.shape[0], WPAD))


def _launch(phase, kernel, args, **spec):
    """Call ``pl.pallas_call(kernel, **spec)`` on ``args`` under the launch's
    fixed name (``SCOPES[phase]``), given both as the call's ``name`` and as
    a named scope: the compiled custom call is then ``%<name>.N`` whatever
    jit / vmap / jvp wraps the launch."""
    name = SCOPES[phase]
    with jax.named_scope(name):
        return pl.pallas_call(kernel, name=name, **spec)(*args)


def pinn_mlp_pallas(x_pad, w_stack, b_stack, a_vec, *, d_in, act="tanh",
                    block_n=256, interpret=False):
    """x_pad: (N, WPAD) with N % block_n == 0. Returns (u (N, WPAD), du (d_in, N, WPAD))."""
    n, wp = x_pad.shape
    assert wp == WPAD and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    grid = (n // block_n,)
    kernel = functools.partial(_kernel, n_layers=n_layers, d_in=d_in, act=act)
    return _launch(
        "kernel_eval1", kernel,
        (x_pad, w_stack, b_stack, _lane_slopes(a_vec)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, WPAD), x_pad.dtype),
            jax.ShapeDtypeStruct((d_in, n, WPAD), x_pad.dtype),
        ],
        interpret=interpret,
    )


def pinn_mlp_pallas2(x_pad, w_stack, b_stack, a_vec, *, d_in, act="tanh",
                     block_n=256, interpret=False):
    """Second-order launch: returns (u (N, WPAD), du (d_in, N, WPAD),
    d2u (d_in, N, WPAD)) with d2u the DIAGONAL second derivatives."""
    n, wp = x_pad.shape
    assert wp == WPAD and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    grid = (n // block_n,)
    kernel = functools.partial(_kernel2, n_layers=n_layers, d_in=d_in, act=act)
    return _launch(
        "kernel_eval2", kernel,
        (x_pad, w_stack, b_stack, _lane_slopes(a_vec)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, WPAD), x_pad.dtype),
            jax.ShapeDtypeStruct((d_in, n, WPAD), x_pad.dtype),
            jax.ShapeDtypeStruct((d_in, n, WPAD), x_pad.dtype),
        ],
        interpret=interpret,
    )


def pinn_mlp_pallas2_res(x_pad, w_stack, b_stack, a_vec, *, d_in, act="tanh",
                         block_n=256, interpret=False):
    """Training-forward launch: :func:`pinn_mlp_pallas2` outputs PLUS the
    reverse-sweep residual stacks (h (L, N, WPAD), t/s (L, d_in, N, WPAD))."""
    n, wp = x_pad.shape
    assert wp == WPAD and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    assert n_layers >= 1, "residual-saving kernel needs >= 1 hidden layer"
    grid = (n // block_n,)
    kernel = functools.partial(_kernel2_res, n_layers=n_layers, d_in=d_in,
                               act=act)
    dt = x_pad.dtype
    return _launch(
        "kernel_res", kernel,
        (x_pad, w_stack, b_stack, _lane_slopes(a_vec)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
            pl.BlockSpec((n_layers, block_n, WPAD), lambda i: (0, i, 0)),
            pl.BlockSpec((n_layers, d_in, block_n, WPAD),
                         lambda i: (0, 0, i, 0)),
            pl.BlockSpec((n_layers, d_in, block_n, WPAD),
                         lambda i: (0, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, WPAD), dt),
            jax.ShapeDtypeStruct((d_in, n, WPAD), dt),
            jax.ShapeDtypeStruct((d_in, n, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers, n, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers, d_in, n, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers, d_in, n, WPAD), dt),
        ],
        interpret=interpret,
    )


def pinn_mlp_pallas2_bwd(x_pad, w_stack, a_vec, h_res, t_res, s_res,
                         cu, cdu, cd2u, *, d_in, act="tanh", block_n=256,
                         interpret=False):
    """Fused reverse-sweep launch (:func:`_kernel2_bwd`).

    Grid over point blocks; x̄ streams out per block while the parameter
    cotangents (W̄ stack, b̄ stack, ā lane-partials) accumulate in one
    revisited VMEM block across the sequential grid.  Returns
    (cx (N, WPAD), cw (L+1, WPAD, WPAD), cb (L+1, WPAD),
    ca_part (L+1, WPAD) — sum the lane axis for ā).
    """
    n, wp = x_pad.shape
    assert wp == WPAD and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    assert n_layers >= 1
    grid = (n // block_n,)
    kernel = functools.partial(_kernel2_bwd, n_layers=n_layers, d_in=d_in,
                               act=act)
    dt = x_pad.dtype
    return _launch(
        "bwd_fused", kernel,
        (x_pad, w_stack, _lane_slopes(a_vec), h_res, t_res, s_res, cu, cdu,
         cd2u),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
            pl.BlockSpec((n_layers, block_n, WPAD), lambda i: (0, i, 0)),
            pl.BlockSpec((n_layers, d_in, block_n, WPAD),
                         lambda i: (0, 0, i, 0)),
            pl.BlockSpec((n_layers, d_in, block_n, WPAD),
                         lambda i: (0, 0, i, 0)),
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
            pl.BlockSpec((d_in, block_n, WPAD), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, WPAD), lambda i: (i, 0)),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers + 1, WPAD, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers + 1, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers + 1, WPAD), dt),
        ],
        interpret=interpret,
    )
