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

Tiling: grid over collocation-point blocks of ``block_n`` points; weights
are padded to (WPAD, WPAD) = (128, 128) — MXU-aligned.  The first-order
kernel carries the points on the sublanes.  The second-order kernels carry
them on the lanes and stack their 1 + 2·d_in streams (h, t_j, s_j) on the
sublanes, 8-row aligned (:class:`Layout`): each affine layer is one matmul
per tile of streams against ``blockdiag(Wᵀ, …, Wᵀ)``, and no value crosses
lanes.
Adaptive activations (tanh/sin/cos x trainable slope, paper refs [26,27]) are
selected statically per call.

``ops.pinn_mlp_forward`` / ``ops.pinn_mlp_forward2`` are the jit'd wrappers
(pad, dispatch, slice; forward2 adds a ``jax.custom_vjp`` for training);
``ref.pinn_mlp_ref`` / ``ref.pinn_mlp_ref2`` are the pure-jnp oracles;
``tests/test_kernels_pinn_mlp.py`` sweeps shapes x dtypes x activations in
interpret mode against the per-point ``pdes.dir_deriv2`` oracle.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.profiling import SCOPES, count_launch

WPAD = 128  # lane-aligned padded width


def _act_pair(name: str):
    if name == "tanh":
        return jnp.tanh, lambda z: 1.0 - jnp.tanh(z) ** 2
    if name == "sin":
        return jnp.sin, jnp.cos
    if name == "cos":
        return jnp.cos, lambda z: -jnp.sin(z)
    raise ValueError(name)


def _act_derivs(name: str, z, order: int):
    """(phi, phi', …, phi^(order)) at z, each transcendental evaluated ONCE
    (a kernel stage needs up to four derivatives of one activation)."""
    if name == "tanh":
        th = jnp.tanh(z)
        p1 = 1.0 - th * th
        ds = (th, p1, -2.0 * th * p1, (6.0 * th * th - 2.0) * p1)
    elif name == "sin":
        s, c = jnp.sin(z), jnp.cos(z)
        ds = (s, c, -s, -c)
    elif name == "cos":
        c, s = jnp.cos(z), jnp.sin(z)
        ds = (c, -s, -c, s)
    else:
        raise ValueError(name)
    return ds[:order + 1]


def _act_funcs(name: str, order: int):
    if name not in ("tanh", "sin", "cos"):
        raise ValueError(name)
    return tuple(lambda z, k=k: _act_derivs(name, z, order)[k]
                 for k in range(order + 1))


def _act_triple(name: str):
    """(phi, phi', phi'') for the second-order tangent rule."""
    return _act_funcs(name, 2)


def _act_quad(name: str):
    """(phi, phi', phi'', phi''') — the reverse sweep differentiates the
    second-order tangent rule once more, so it consumes one extra derivative
    order than the forward kernel."""
    return _act_funcs(name, 3)


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


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the second-order kernels keep their 1 + 2·d_in streams.

    Stream 0 is h (the running affine output), streams 1..d_in the first
    tangents t_j and streams d_in+1..2·d_in the second tangents s_j.  Inside
    a kernel every stream is a ``(seg, block_n)`` block with the points on
    the lanes: ``seg`` rows, the net's widest layer rounded up to whole
    8-row sublane groups.  An affine layer multiplies ``(WPAD, block_n)``
    tiles that stack ``per_tile`` streams on the sublanes by
    ``blockdiag(Wᵀ, …, Wᵀ)``: stream k lies in tile ``k // per_tile`` at
    rows ``(k % per_tile)·seg``.  Every offset is a multiple of 8 rows, so
    putting a stream into a tile or taking it out moves whole vregs, and no
    value ever crosses lanes.  The rule is read from the shapes: width 20 at
    d_in 2 packs five 24-row streams into one tile, width 80 takes a tile
    per stream.

    Per-point kernel I/O crosses HBM the same way, as rows with the points
    on the lanes: x as ``x_rows`` rows (coordinate j in row j, then a row of
    ones that carries the first layer's bias and tangents), output o of
    stream k in row ``k·n_out + o`` of ``out_rows``, and the training
    forward's stash as the streams stacked, ``res_rows`` rows per layer.
    """

    d_in: int
    seg: int    # rows per stream
    n_out: int  # the net's output width

    @property
    def n_streams(self) -> int:
        return 1 + 2 * self.d_in

    @property
    def per_tile(self) -> int:
        return min(WPAD // self.seg, self.n_streams)

    @property
    def n_tiles(self) -> int:
        return -(-self.n_streams // self.per_tile)

    @property
    def name(self) -> str:
        return f"{self.per_tile}_per_tile"

    def row(self, k: int) -> int:
        """Stream k's first row in the stack of tiles."""
        return (k // self.per_tile) * WPAD + (k % self.per_tile) * self.seg

    def tile_rows(self, i: int) -> int:
        """Rows of tile i that hold streams (the rest are zero)."""
        return self.seg * min(self.per_tile,
                              self.n_streams - i * self.per_tile)

    @property
    def x_rows(self) -> int:
        return _round8(self.d_in + 1)

    @property
    def out_rows(self) -> int:
        return _round8(self.n_streams * self.n_out)

    def out_row(self, k: int) -> int:
        """The row of stream k's first output."""
        return k * self.n_out

    @property
    def res_rows(self) -> int:
        return self.n_streams * self.seg


def _round8(n: int) -> int:
    return -(-n // 8) * 8


def layout(d_in: int, width: int, n_out: int) -> Layout:
    """The stream layout of a net whose widest layer (hidden or output) is
    ``width`` <= WPAD."""
    assert width <= WPAD, width
    return Layout(d_in, _round8(width), n_out)


def _tiles(lay, streams):
    """The streams stacked on the sublanes into (WPAD, block_n) tiles."""
    n, tiles = streams[0].shape[1], []
    for i in range(lay.n_tiles):
        blocks = streams[i * lay.per_tile:(i + 1) * lay.per_tile]
        if lay.tile_rows(i) < WPAD:
            blocks = blocks + [jnp.zeros((WPAD - lay.tile_rows(i), n),
                                         streams[0].dtype)]
        tiles.append(jnp.concatenate(blocks, axis=0))
    return tiles


def _affine(lay, ws, tiles):
    """Per tile i, the first ``tile_rows(i)`` rows of the weight block
    ``ws[i]`` (a ref view) times ``tiles[i]``, split back into streams."""
    out = []
    for i, (w, v) in enumerate(zip(ws, tiles)):
        p = w[...][:lay.tile_rows(i)] @ v
        out += [p[r:r + lay.seg] for r in range(0, lay.tile_rows(i), lay.seg)]
    return out


def _dot_nt(u, v):
    """``u @ vᵀ`` contracting the lanes (the points) of both."""
    return jax.lax.dot_general(u, v, (((1,), (1,)), ((), ())))


def _lane_sum(v, m):
    """(r, block_n) -> (r, m): the sum of the m-lane column blocks (whole
    vregs for m = WPAD; the caller reduces the lanes once, outside)."""
    return sum(v[:, c:c + m] for c in range(m, v.shape[1], m)) + v[:, :m]


def _store_outputs(lay, streams, out_ref):
    """Each stream's first ``n_out`` rows -> its rows of ``out_ref``."""
    m = lay.n_out
    for k, v in enumerate(streams):
        out_ref[lay.out_row(k):lay.out_row(k) + m, :] = v[:m]


def _load_outputs(lay, ct_ref):
    """Inverse of :func:`_store_outputs`: the cotangent streams, zero
    outside each stream's outputs."""
    m, n = lay.n_out, ct_ref.shape[1]
    pad = jnp.zeros((lay.seg - m, n), ct_ref.dtype)
    return [jnp.concatenate([ct_ref[lay.out_row(k):lay.out_row(k) + m, :],
                             pad], axis=0)
            for k in range(lay.n_streams)]


def _kernel2_run(x_ref, w0_ref, w_ref, b_ref, a_ref, out_ref, res_ref, *,
                 n_layers, lay, act):
    """Shared second-order recurrence body (ONE copy of the tangent math).

    Per direction j the streams are (t_j, s_j) = (first, second) forward
    tangents of the running affine output h.  Through an activation
    ``g = phi(a h)``:  ``t -> phi'(a h)·a·t``,  ``s -> phi''(a h)·a²·t² +
    phi'(a h)·a·s``; through an affine layer all streams multiply by W.
    The activation factors are evaluated once, on h's rows, and each
    stream's rule runs on its own rows.  The first layer is one matmul
    against x's rows: the ones row brings h's bias and the tangents
    t_j = W₀[j], which do not depend on x (s₀ = 0).

    ``res_ref`` is the optional residual spill of the training forward (None
    for the inference kernel): residual saving must never fork the
    recurrence itself.
    """
    d, seg = lay.d_in, lay.seg
    x = x_ref[...]
    p = _affine(lay, [w0_ref.at[i] for i in range(lay.n_tiles)],
                [x] * lay.n_tiles)
    for l in range(n_layers):
        if res_ref is not None:
            for k, v in enumerate(p):
                res_ref[l, k * seg:(k + 1) * seg, :] = v
        a = a_ref[l:l + 1, :]
        g, p1, p2 = _act_derivs(act, a * p[0], 2)
        d1 = p1 * a
        d2 = p2 * (a * a)
        t, s = p[1:1 + d], p[1 + d:]
        q = ([g] + [d1 * tj for tj in t]
             + [d2 * tj * tj + d1 * sj for tj, sj in zip(t, s)])
        p = _affine(lay, [w_ref.at[l]] * lay.n_tiles, _tiles(lay, q))
        p[0] = p[0] + b_ref[l]
    _store_outputs(lay, p, out_ref)


def _kernel2(x_ref, w0_ref, w_ref, b_ref, a_ref, out_ref, *, n_layers, lay,
             act):
    """Second-order variant: one block of collocation points.

    x_ref:   (x_rows, block_n)            coordinates, one per row, then ones
    w0_ref:  (n_tiles, WPAD, x_rows)      first layer per tile: W₀ᵀ in h's
                                          rows; in the ones column h's bias
                                          and t₀,j = W₀[j] in t_j's rows
    w_ref:   (n_layers, WPAD, WPAD)       blockdiag(W_lᵀ) of layers 1..L
    b_ref:   (n_layers, seg, block_n)     biases of layers 1..L over lanes
    a_ref:   (n_layers, block_n)          adaptive slopes over lanes
    out_ref: (out_rows, block_n)          (u, du_j, d²u/dx_j²) rows
                                          (``Layout.out_row``)
    """
    _kernel2_run(x_ref, w0_ref, w_ref, b_ref, a_ref, out_ref, None,
                 n_layers=n_layers, lay=lay, act=act)


def _kernel2_res(x_ref, w0_ref, w_ref, b_ref, a_ref, out_ref, res_ref, *,
                 n_layers, lay, act):
    """:func:`_kernel2` that ALSO spills the reverse sweep's residuals.

    res_ref: (n_layers, res_rows, block_n)  the streams (h, t, s) ENTERING
             each activation stage, stacked (stream k at rows k·seg)

    — exactly what :func:`_kernel2_bwd` re-derives the activation factors from
    (phi^(k)(a·h) are recomputed from h; no matmul is ever recomputed).
    """
    _kernel2_run(x_ref, w0_ref, w_ref, b_ref, a_ref, out_ref, res_ref,
                 n_layers=n_layers, lay=lay, act=act)


def _kernel2_bwd(x_ref, w0t_ref, w_ref, a_ref, res_ref, ct_ref,
                 cx_ref, cw0_ref, cw_ref, cb_ref, ca_ref, *, n_layers, lay,
                 act):
    """Hand-derived fused reverse sweep of :func:`_kernel2` (one VMEM pass).

    One block of collocation points walks the layer stack BACKWARD carrying
    the cotangent streams (h̄, t̄_j, s̄_j) in the forward's layout; per stage
    the saved streams reproduce the activation factors p_k = phi^(k)(a·h)
    and the cotangent rules are the paper-derivation transposes of the
    forward tangent rules (see ``ref._ref2_bwd`` — the jnp twin of this
    kernel — for the formulas).  h̄ gathers a term from every stream and
    t̄_j one from s̄_j, row block onto row block.

    Weight / bias / slope cotangents accumulate ACROSS grid blocks: every grid
    step maps cw0/cw/cb/ca to the same block (TPU grid iteration is
    sequential), zero-initialized at step 0.

    x_ref:   (x_rows, block_n)           coordinates and the ones row
    w0t_ref: (x_rows, WPAD)              W₀ (row j: W₀[j] over h's rows)
    w_ref:   (n_layers, WPAD, WPAD)      blockdiag(W_l) of layers 1..L
    ct_ref:  (out_rows, block_n)         (ū, d̄u_j, d̄2u_j) rows (pruned rows
                                         pre-zeroed by the caller)
    cx_ref:  (x_rows, block_n)           x̄ rows out (the ones row's is junk)
    cw0_ref: (n_tiles, WPAD, x_rows)     accumulated B̄₀ @ xᵀ per tile: W̄₀ᵀ in
                                         h's rows; the ones column holds each
                                         row's Σ over points (b̄₀, Σ t̄₀,j)
    cw_ref:  (n_layers, WPAD, WPAD)      accumulated Σ_tiles Q @ B̄ᵀ: W̄_l is
                                         the sum of its stream-diagonal blocks
                                         (rows past tile 0's streams stay 0)
    cb_ref:  (n_layers, seg, m)          b̄_l lane-partials (reduce outside;
                                         m = WPAD, or block_n if smaller)
    ca_ref:  (n_layers, seg, m)          ā lane- and row-partials
    """
    d, seg = lay.d_in, lay.seg

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for ref in (cw0_ref, cw_ref, cb_ref, ca_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    bar = _load_outputs(lay, ct_ref)
    for l in reversed(range(n_layers)):
        a = a_ref[l:l + 1, :]
        p = [res_ref[l, k * seg:(k + 1) * seg, :]
             for k in range(lay.n_streams)]
        h, t, s = p[0], p[1:1 + d], p[1 + d:]
        g, p1, p2, p3 = _act_derivs(act, a * h, 3)
        d1 = p1 * a
        d2v = p2 * (a * a)
        tt = [tj * tj for tj in t]
        q = ([g] + [d1 * tj for tj in t]
             + [d2v * ttj + d1 * sj for ttj, sj in zip(tt, s)])
        # ---- affine layer l+1: the cotangents through Wᵀ -----------------
        b_tiles = _tiles(lay, bar)
        qb = _affine(lay, [w_ref.at[l]] * lay.n_tiles, b_tiles)
        gb, tb, sb = qb[0], qb[1:1 + d], qb[1 + d:]
        # ---- activation stage l: ā partial, then (h̄, t̄, s̄) --------------
        e1 = p2 * h * a + p1                    # ∂(phi'·a)/∂a
        e2 = p3 * h * (a * a) + 2.0 * p2 * a    # ∂(phi''·a²)/∂a
        p3a3 = p3 * (a * a * a)
        ca, bar_h = gb * (p1 * h), gb * d1
        for tj, ttj, sj, tbj, sbj in zip(t, tt, s, tb, sb):
            ca = ca + tbj * tj * e1 + sbj * (ttj * e2 + sj * e1)
            bar_h = bar_h + tbj * tj * d2v + sbj * (ttj * p3a3 + sj * d2v)
        ca_ref[l] += _lane_sum(ca, ca_ref.shape[-1])
        # ---- affine layer l+1: W̄ and b̄ (after the activation stage, so
        # the MXU overlaps its work); Q's zero rows past tile 0's streams
        # add nothing to W̄ ----------------------------------------------
        r0 = lay.tile_rows(0)
        cw = None
        for qi, bi in zip(_tiles(lay, q), b_tiles):
            c = _dot_nt(qi[:r0], bi)
            cw = c if cw is None else cw + c
        cw_ref[l, :r0, :] += cw
        cb_ref[l] += _lane_sum(bar[0], cb_ref.shape[-1])
        bar = ([bar_h] + [tbj * d1 + sbj * (2.0 * d2v) * tj
                          for tj, tbj, sbj in zip(t, tb, sb)]
               + [sbj * d1 for sbj in sb])
    # ---- input affine layer: x̄ from h̄; W̄₀, b̄₀ and Σ t̄₀,j against x's rows
    b_tiles = _tiles(lay, bar)
    x = x_ref[...]
    cx_ref[...] = w0t_ref[...] @ b_tiles[0]
    for k, bi in enumerate(b_tiles):
        cw0_ref[k] += _dot_nt(bi, x)


def _lanes(v, n):
    """Broadcast ``v`` over a new last axis of ``n`` lanes.  Slopes and
    biases enter the kernels this way, not as 1-D operands: a batched 1-D
    block (1, L) breaks Mosaic's rule that the last two block dims be
    (8, 128)-divisible or span the array once the launch is vmapped over
    subdomains, and a (1, 1) value cannot be broadcast in both sublanes
    and lanes inside a kernel."""
    return jnp.broadcast_to(v[..., None], v.shape + (n,))


def _launch(phase, kernel, args, layout, **spec):
    """Call ``pl.pallas_call(kernel, **spec)`` on ``args`` under the launch's
    fixed name (``SCOPES[phase]``), given both as the call's ``name`` and as
    a named scope: the compiled custom call is then ``%<name>.N`` whatever
    jit / vmap / jvp wraps the launch.  Each trace of the launch is counted
    under its stream ``layout`` (``repro.obs.launch_counts``)."""
    name = SCOPES[phase]
    count_launch(phase, layout)
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
        (x_pad, w_stack, b_stack, _lanes(a_vec, WPAD)), layout="first_order",
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


def _rows_spec(rows, block_n):
    return pl.BlockSpec((rows, block_n), lambda i: (0, i))


def _whole(shape):
    """A block that is the whole operand, the same at every grid step."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _fwd_operands(lay, x_rows, w0, w_stack, b_stack, a_vec, block_n):
    """The second-order forward launches' operands and their block specs."""
    n_layers = w_stack.shape[0]
    n = x_rows.shape[1]
    assert x_rows.shape[0] == lay.x_rows and n % block_n == 0
    args = (x_rows, w0, w_stack, _lanes(b_stack, block_n),
            _lanes(a_vec, block_n))
    specs = [_rows_spec(lay.x_rows, block_n), _whole(w0.shape),
             _whole(w_stack.shape), _whole((n_layers, lay.seg, block_n)),
             _whole((n_layers, block_n))]
    return n_layers, n, args, specs


def pinn_mlp_pallas2(x_rows, w0, w_stack, b_stack, a_vec, *, lay, act="tanh",
                     block_n=256, interpret=False):
    """Second-order launch over ``ops.pack_mlp2``'s operands and x as
    (x_rows, N) rows: returns the output streams (u, du_j, d²u/dx_j²,
    diagonal only) as (out_rows, N) rows (``Layout.out_row``)."""
    n_layers, n, args, specs = _fwd_operands(lay, x_rows, w0, w_stack,
                                             b_stack, a_vec, block_n)
    kernel = functools.partial(_kernel2, n_layers=n_layers, lay=lay, act=act)
    return _launch(
        "kernel_eval2", kernel, args, layout=lay.name,
        grid=(n // block_n,), in_specs=specs,
        out_specs=_rows_spec(lay.out_rows, block_n),
        out_shape=jax.ShapeDtypeStruct((lay.out_rows, n), x_rows.dtype),
        interpret=interpret,
    )


def pinn_mlp_pallas2_res(x_rows, w0, w_stack, b_stack, a_vec, *, lay,
                         act="tanh", block_n=256, interpret=False):
    """Training-forward launch: :func:`pinn_mlp_pallas2`'s output PLUS the
    reverse sweep's residual stack (L, res_rows, N)."""
    n_layers, n, args, specs = _fwd_operands(lay, x_rows, w0, w_stack,
                                             b_stack, a_vec, block_n)
    assert n_layers >= 1, "residual-saving kernel needs >= 1 hidden layer"
    kernel = functools.partial(_kernel2_res, n_layers=n_layers, lay=lay,
                               act=act)
    dt = x_rows.dtype
    return _launch(
        "kernel_res", kernel, args, layout=lay.name,
        grid=(n // block_n,), in_specs=specs,
        out_specs=[
            _rows_spec(lay.out_rows, block_n),
            pl.BlockSpec((n_layers, lay.res_rows, block_n),
                         lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lay.out_rows, n), dt),
            jax.ShapeDtypeStruct((n_layers, lay.res_rows, n), dt),
        ],
        interpret=interpret,
    )


def pinn_mlp_pallas2_bwd(x_rows, w0t, w_stack, a_vec, res, ct, *, lay,
                         act="tanh", block_n=256, interpret=False):
    """Fused reverse-sweep launch (:func:`_kernel2_bwd`).

    Grid over point blocks; x̄ streams out per block while the parameter
    cotangents (W̄₀ per tile, W̄ stack, b̄ and ā partials) accumulate in one
    revisited VMEM block across the sequential grid.  ``w0t`` is W₀ padded
    to (x_rows, WPAD) and ``w_stack`` holds blockdiag(W_l) (the forward's
    weights in their other orientation); ``res`` is
    :func:`pinn_mlp_pallas2_res`'s residual stack and ``ct`` the output
    streams' cotangents as (out_rows, N) rows.  Returns (cx (x_rows, N),
    cw0 (n_tiles, WPAD, x_rows), cw (L, WPAD, WPAD), cb (L, seg, m),
    ca (L, seg, m)) with m = WPAD lanes of partials (block_n if smaller);
    ``ops`` folds them into (W̄s, b̄s, ā).
    """
    n = x_rows.shape[1]
    assert x_rows.shape[0] == lay.x_rows and n % block_n == 0
    n_layers = w_stack.shape[0]
    assert n_layers >= 1
    kernel = functools.partial(_kernel2_bwd, n_layers=n_layers, lay=lay,
                               act=act)
    dt = x_rows.dtype
    m = WPAD if block_n % WPAD == 0 else block_n   # partials' lanes
    shapes = [(lay.n_tiles, WPAD, lay.x_rows), (n_layers, WPAD, WPAD),
              (n_layers, lay.seg, m), (n_layers, lay.seg, m)]
    return _launch(
        "bwd_fused", kernel,
        (x_rows, w0t, w_stack, _lanes(a_vec, block_n), res, ct),
        layout=lay.name, grid=(n // block_n,),
        in_specs=[
            _rows_spec(lay.x_rows, block_n), _whole(w0t.shape),
            _whole(w_stack.shape), _whole((n_layers, block_n)),
            pl.BlockSpec((n_layers, lay.res_rows, block_n),
                         lambda i: (0, 0, i)),
            _rows_spec(lay.out_rows, block_n),
        ],
        out_specs=[_rows_spec(lay.x_rows, block_n)]
        + [_whole(s) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct((lay.x_rows, n), dt)]
        + [jax.ShapeDtypeStruct(s, dt) for s in shapes],
        interpret=interpret,
    )
