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
The second-order kernels lane-pack their 1 + 2·d_in streams (h, t_j, s_j)
into one tile where they fit (:class:`Layout`), each affine layer then one
matmul against ``blockdiag(W, …, W)``; wider nets keep a tile per stream.
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
    """Where the second-order kernels keep their 1 + 2·d_in row streams.

    Stream 0 is h (the running affine output), streams 1..d_in the first
    tangents t_j and streams d_in+1..2·d_in the second tangents s_j.  Stream
    k lies in tile ``k // per_tile`` at lanes ``[(k % per_tile)·seg,
    (k % per_tile + 1)·seg)``.  A narrow net packs every stream into ONE
    128-lane tile (``seg`` = its width), so each affine layer is one matmul
    against ``blockdiag(W, …, W)``; a wide one gives each stream a tile of
    its own (``seg`` = WPAD, one stream per tile).  Both run the same
    kernel code: only the lane moves and kind selects below differ.

    Per-point kernel I/O crosses HBM with the points on the lanes: x as
    ``x_rows`` rows (coordinate j in row j), the output streams and their
    cotangents as ``out_rows`` rows (output o of stream k in row
    ``k·n_out + o`` of its row block), so neither the kernel nor the XLA
    code around it moves 128-lane rows that hold one or two values.
    """

    d_in: int
    seg: int    # lanes per stream; WPAD for one stream per tile
    n_out: int  # the net's output width

    @property
    def n_streams(self) -> int:
        return 1 + 2 * self.d_in

    @property
    def packed(self) -> bool:
        return self.seg < WPAD

    @property
    def per_tile(self) -> int:
        return self.n_streams if self.packed else 1

    @property
    def n_tiles(self) -> int:
        return self.n_streams // self.per_tile

    @property
    def name(self) -> str:
        return "packed" if self.packed else "per_stream"

    @property
    def x_rows(self) -> int:
        return _round8(self.d_in)

    @property
    def out_per_block(self) -> int:
        """Output streams per row block (all of them unless n_out is wide)."""
        return min(self.n_streams, WPAD // self.n_out)

    @property
    def out_blocks(self) -> int:
        return -(-self.n_streams // self.out_per_block)

    @property
    def block_rows(self) -> int:
        return _round8(self.out_per_block * self.n_out)

    @property
    def out_rows(self) -> int:
        return self.out_blocks * self.block_rows

    def out_row(self, k: int) -> int:
        """The row of stream k's first output."""
        b, r = divmod(k, self.out_per_block)
        return b * self.block_rows + r * self.n_out


def _round8(n: int) -> int:
    return -(-n // 8) * 8


def layout(d_in: int, width: int, n_out: int) -> Layout:
    """Pack all 1 + 2·d_in streams into one tile, ``width`` lanes each, when
    they fit in WPAD lanes (``width``: the net's widest layer, hidden or
    output); else one stream per tile."""
    return Layout(d_in, width if (1 + 2 * d_in) * width <= WPAD else WPAD,
                  n_out)


def _roll(v, lanes):
    """Rotate a tile by ``lanes`` along the lane axis (lane i -> i + lanes,
    mod WPAD): a whole-tile XLU rotation, never an unaligned slice."""
    lanes %= WPAD
    return pltpu.roll(v, lanes, 1) if lanes else v


def _lane():
    return jax.lax.broadcasted_iota(jnp.int32, (1, WPAD), 1)


def _by_kind(lay, i, h, t, s):
    """Tile ``i`` of a stream-wise expression whose value on the h, t_j and
    s_j streams is given by the zero-argument callables ``h``, ``t`` and
    ``s``.  A tile of one stream evaluates only its own kind; the packed
    tile selects by lane (lanes past the last segment take ``s``)."""
    if not lay.packed:
        return (h if i == 0 else t if i <= lay.d_in else s)()
    lane = _lane()
    return jnp.where(lane < lay.seg, h(),
                     jnp.where(lane < (1 + lay.d_in) * lay.seg, t(), s()))


def _spread_h(lay, tiles):
    """One tile whose every stream holds h: what each stream's activation
    factors phi^(k)(a·h) are computed from (the h tile itself when streams
    have tiles of their own)."""
    p = tiles[0]
    if not lay.packed:
        return p
    lane, out = _lane(), p
    for k in range(1, lay.n_streams):
        out = jnp.where(lane >= k * lay.seg, _roll(p, k * lay.seg), out)
    return out


def _shift(lay, tiles, d):
    """Per tile, stream k's lanes hold stream k - d (junk where k - d is
    no stream; callers read only the streams they align)."""
    if lay.packed:
        return [_roll(tiles[0], d * lay.seg)]
    n = lay.n_tiles
    return [tiles[(i - d) % n] for i in range(n)]


def _sum_streams(lay, tiles):
    """A tile whose stream-0 lanes hold the sum over every stream."""
    if not lay.packed:
        return sum(tiles[1:], tiles[0])
    p = tiles[0]
    out = p
    for k in range(1, lay.n_streams):
        out = out + _roll(p, -k * lay.seg)
    return out


def _pad_rows(v):
    """(r, block_n) rows -> (WPAD, block_n), zero rows appended."""
    r, n = v.shape
    if r == WPAD:
        return v
    return jnp.concatenate([v, jnp.zeros((WPAD - r, n), v.dtype)], axis=0)


def _rows_to_tile(v):
    """(r, block_n) rows, points on the lanes -> (block_n, WPAD) tile with
    row j in lane j (one whole-tile transpose)."""
    return _pad_rows(v).T


def _lanes_at(lane, at, n):
    return (lane >= at) & (lane < at + n)


def _store_outputs(lay, tiles, out_ref):
    """The output streams (in ``lay``'s tiles, outputs at the start of each
    stream's lanes) -> ``out_ref`` rows: each row block gathers its streams'
    outputs side by side on the lanes of one tile, then transposes it."""
    lane, per, m = _lane(), lay.out_per_block, lay.block_rows
    for b in range(lay.out_blocks):
        acc = None
        for k in range(b * per, min((b + 1) * per, lay.n_streams)):
            at = (k - b * per) * lay.n_out
            v = _roll(tiles[k // lay.per_tile],
                      at - (k % lay.per_tile) * lay.seg)
            acc = v if acc is None else jnp.where(
                _lanes_at(lane, at, lay.n_out), v, acc)
        out_ref[b * m:(b + 1) * m, :] = acc.T[:m]


def _load_outputs(lay, ct_ref):
    """Inverse of :func:`_store_outputs`: ``ct_ref`` rows -> the cotangent
    streams in ``lay``'s tiles, zero outside each stream's outputs."""
    lane, per, m = _lane(), lay.out_per_block, lay.block_rows
    blocks = [_rows_to_tile(ct_ref[b * m:(b + 1) * m, :])
              for b in range(lay.out_blocks)]
    tiles = []
    for i in range(lay.n_tiles):
        acc = jnp.zeros_like(blocks[0])
        for k in range(i * lay.per_tile, (i + 1) * lay.per_tile):
            at = (k % lay.per_tile) * lay.seg
            v = _roll(blocks[k // per], at - (k % per) * lay.n_out)
            acc = jnp.where(_lanes_at(lane, at, lay.n_out), v, acc)
        tiles.append(acc)
    return tiles


_CHAINS = 4  # independent tile chains the forward's scheduler overlaps


def _row_groups(block_n, lay):
    """Row slices of a block, each carried through the layer stack as its
    own chain, so the scheduler overlaps one chain's matmul with another's
    activation stage (one chain of dependent layers leaves the MXU and the
    vector units taking turns).  One stream per tile already gives
    ``n_tiles`` chains per layer; the packed tile is split into _CHAINS
    row groups (8-row aligned)."""
    n = max(1, _CHAINS // lay.n_tiles)
    while n > 1 and block_n % (8 * n):
        n -= 1
    step = block_n // n
    return [slice(r, r + step) for r in range(0, block_n, step)]


def _kernel2_run(x_ref, w_ref, b_ref, a_ref, out_ref, res_ref, *, n_layers,
                 lay, act):
    """Shared second-order recurrence body (ONE copy of the tangent math).

    Per direction j the streams are (t_j, s_j) = (first, second) forward
    tangents of the running affine output h.  Through an activation
    ``g = phi(a h)``:  ``t -> phi'(a h)·a·t``,  ``s -> phi''(a h)·a²·t² +
    phi'(a h)·a·s``; through an affine layer all streams multiply by W.
    The first layer's tangents t_j = row j of W₀ do not depend on x, so they
    enter as bias (``b_ref[0]``) and s₀ = 0.

    ``res_ref`` is the optional residual spill of the training forward (None
    for the inference kernel): residual saving must never fork the
    recurrence itself.
    """
    x = _rows_to_tile(x_ref[...])
    groups = _row_groups(x.shape[0], lay)
    ps = []
    for rows in groups:
        h0 = x[rows] @ w_ref[0]
        p = [h0 + b_ref[0, 0][None, :]]
        p += [jnp.broadcast_to(b_ref[0, i][None, :], h0.shape)
              for i in range(1, lay.n_tiles)]
        ps.append(p)
    for l in range(n_layers):
        a = a_ref[l:l + 1, :]
        w = w_ref[l + 1]
        for r, rows in enumerate(groups):
            p = ps[r]
            if res_ref is not None:
                for i in range(lay.n_tiles):
                    res_ref[l, i, rows, :] = p[i]
            g, p1, p2 = _act_derivs(act, a * _spread_h(lay, p), 2)
            d1 = p1 * a
            d2 = p2 * (a * a)
            t = _shift(lay, p, lay.d_in)   # t_j aligned with s_j
            q = [_by_kind(lay, i, lambda: g, lambda i=i: d1 * p[i],
                          lambda i=i: d2 * t[i] * t[i] + d1 * p[i])
                 for i in range(lay.n_tiles)]
            p = [qi @ w for qi in q]
            p[0] = p[0] + b_ref[l + 1, 0][None, :]
            ps[r] = p
    cat = (lambda vs: vs[0]) if len(ps) == 1 else (
        lambda vs: jnp.concatenate(vs, axis=0))
    _store_outputs(lay, [cat([p[i] for p in ps]) for i in range(lay.n_tiles)],
                   out_ref)


def _kernel2(x_ref, w_ref, b_ref, a_ref, out_ref, *, n_layers, lay, act):
    """Second-order variant: one block of collocation points.

    x_ref:   (x_rows, block_n)             coordinates, one per row
    w_ref:   (n_layers+1, WPAD, WPAD)      W₀ padded, then blockdiag(W_l)
    b_ref:   (n_layers+1, n_tiles, WPAD)   biases in stream 0; row 0 also
                                           holds t₀,j = W₀[j] in stream j+1
    a_ref:   (n_layers+1, WPAD)            adaptive slopes over lanes
    out_ref: (out_rows, block_n)           (u, du_j, d²u/dx_j²) rows
                                           (``Layout.out_row``)
    """
    _kernel2_run(x_ref, w_ref, b_ref, a_ref, out_ref, None,
                 n_layers=n_layers, lay=lay, act=act)


def _kernel2_res(x_ref, w_ref, b_ref, a_ref, out_ref, res_ref, *, n_layers,
                 lay, act):
    """:func:`_kernel2` that ALSO spills the reverse sweep's residuals.

    res_ref: (n_layers, n_tiles, block_n, WPAD)  the streams (h, t, s)
             ENTERING each activation stage

    — exactly what :func:`_kernel2_bwd` re-derives the activation factors from
    (phi^(k)(a·h) are recomputed from h; no matmul is ever recomputed).
    """
    _kernel2_run(x_ref, w_ref, b_ref, a_ref, out_ref, res_ref,
                 n_layers=n_layers, lay=lay, act=act)


def _kernel2_bwd(x_ref, w_ref, a_ref, res_ref, ct_ref,
                 cx_ref, cw_ref, cb_ref, ca_ref, *, n_layers, lay, act):
    """Hand-derived fused reverse sweep of :func:`_kernel2` (one VMEM pass).

    One block of collocation points walks the layer stack BACKWARD carrying
    the cotangent streams (h̄, t̄_j, s̄_j) in the forward's layout; per stage
    the saved streams reproduce the activation factors p_k = phi^(k)(a·h)
    and the cotangent rules are the paper-derivation transposes of the
    forward tangent rules (see ``ref._ref2_bwd`` — the jnp twin of this
    kernel — for the formulas).  h̄ gathers a term from every stream
    (:func:`_sum_streams`), t̄_j one from s̄_j (:func:`_shift`).

    Weight / bias / slope cotangents accumulate ACROSS grid blocks: every grid
    step maps cw/cb/ca to the same block (TPU grid iteration is sequential),
    zero-initialized at step 0.

    x_ref:  (x_rows, block_n)            coordinates, one per row
    ct_ref: (out_rows, block_n)          (ū, d̄u_j, d̄2u_j) rows (pruned rows
                                         pre-zeroed by the caller)
    cx_ref: (x_rows, block_n)            x̄ rows out
    cw_ref: (n_layers+1, WPAD, WPAD)     accumulated Σ_tiles Aᵀ@B̄: W̄_l is the
                                         sum of its stream-diagonal blocks
    cb_ref: (n_layers+1, n_tiles, WPAD)  accumulated row sums of B̄: b̄_l in
                                         stream 0; row 0 also Σ t̄₀,j (W̄₀[j])
    ca_ref: (n_layers+1, WPAD)           ā lane-partials (reduce lanes
                                         outside; row n_layers unused)
    """
    n = lay.n_tiles
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cw_ref[...] = jnp.zeros(cw_ref.shape, cw_ref.dtype)
        cb_ref[...] = jnp.zeros(cb_ref.shape, cb_ref.dtype)
        ca_ref[...] = jnp.zeros(ca_ref.shape, ca_ref.dtype)

    bar = _load_outputs(lay, ct_ref)
    for l in reversed(range(n_layers)):
        a = a_ref[l:l + 1, :]
        wt = w_ref[l + 1].T
        p = [res_ref[l, k] for k in range(n)]
        hs = _spread_h(lay, p)
        t = _shift(lay, p, lay.d_in)   # t_j aligned with s_j
        g, p1, p2, p3 = _act_derivs(act, a * hs, 3)
        d1 = p1 * a
        d2v = p2 * (a * a)
        q = [_by_kind(lay, k, lambda: g, lambda k=k: d1 * p[k],
                      lambda k=k: d2v * t[k] * t[k] + d1 * p[k])
             for k in range(n)]
        # ---- affine layer l+1: pull the cotangents through Wᵀ ------------
        qb = [bk @ wt for bk in bar]
        # ---- activation stage l: ā partial, then (h̄, t̄, s̄) --------------
        e1 = p2 * hs * a + p1                    # ∂(phi'·a)/∂a
        e2 = p3 * hs * (a * a) + 2.0 * p2 * a    # ∂(phi''·a²)/∂a
        ca = [_by_kind(lay, k, lambda k=k: qb[k] * (p1 * hs),
                       lambda k=k: qb[k] * p[k] * e1,
                       lambda k=k: qb[k] * (t[k] * t[k] * e2 + p[k] * e1))
              for k in range(n)]
        ca_ref[l] += jnp.sum(sum(ca[1:], ca[0]), axis=0)
        p3a3 = p3 * (a * a * a)
        c = [_by_kind(lay, k, lambda k=k: qb[k] * d1,
                      lambda k=k: qb[k] * p[k] * d2v,
                      lambda k=k: qb[k] * (t[k] * t[k] * p3a3 + p[k] * d2v))
             for k in range(n)]
        bar_h = _sum_streams(lay, c)
        v = [qb[k] * (2.0 * d2v) * t[k] if lay.packed or k > lay.d_in
             else None for k in range(n)]
        v = _shift(lay, v, -lay.d_in)  # s̄_j's term in t_j's lanes
        # ---- affine layer l+1: W̄ and b̄ (after the activation stage, so
        # the MXU overlaps its work) -------------------------------------
        cw = q[0].T @ bar[0]
        for k in range(1, n):
            cw += q[k].T @ bar[k]
        cw_ref[l + 1] += cw
        cb_ref[l + 1, 0] += jnp.sum(bar[0], axis=0)
        bar = [_by_kind(lay, k, lambda: bar_h,
                        lambda k=k: qb[k] * d1 + v[k],
                        lambda k=k: qb[k] * d1)
               for k in range(n)]
    # ---- input affine layer: x̄ and W̄₀ from h̄; t₀,j entered as bias ------
    cx_ref[...] = (bar[0] @ w_ref[0].T).T[:lay.x_rows]
    cw_ref[0] += _pad_rows(x_ref[...]) @ bar[0]        # xᵀ @ h̄
    for k in range(n):
        cb_ref[0, k] += jnp.sum(bar[k], axis=0)


def _lane_slopes(a_vec):
    """(L+1,) slopes -> (L+1, WPAD) lane-broadcast tile.  A 1-D slope operand
    stops compiling once the launch is vmapped over subdomains: its batched
    block (1, L+1) breaks Mosaic's rule that the last two block dims be
    (8, 128)-divisible or span the array."""
    return jnp.broadcast_to(a_vec[:, None], (a_vec.shape[0], WPAD))


def _launch(phase, kernel, args, layout="per_stream", **spec):
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


def _specs2(lay, n_layers, block_n):
    """Block specs of the second-order launches' shared operands: x rows,
    the weight stack, the bias stack, the slopes."""
    return [
        pl.BlockSpec((lay.x_rows, block_n), lambda i: (0, i)),
        pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
        pl.BlockSpec((n_layers + 1, lay.n_tiles, WPAD), lambda i: (0, 0, 0)),
        pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
    ]


def _rows_spec(rows, block_n):
    return pl.BlockSpec((rows, block_n), lambda i: (0, i))


def pinn_mlp_pallas2(x_rows, w_stack, b_stack, a_vec, *, lay, act="tanh",
                     block_n=256, interpret=False):
    """Second-order launch over ``ops.pack_mlp2``'s stacks and x as
    (x_rows, N) rows: returns the output streams (u, du_j, d²u/dx_j²,
    diagonal only) as (out_rows, N) rows (``Layout.out_row``)."""
    n = x_rows.shape[1]
    assert x_rows.shape[0] == lay.x_rows and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    kernel = functools.partial(_kernel2, n_layers=n_layers, lay=lay, act=act)
    return _launch(
        "kernel_eval2", kernel,
        (x_rows, w_stack, b_stack, _lane_slopes(a_vec)), layout=lay.name,
        grid=(n // block_n,),
        in_specs=_specs2(lay, n_layers, block_n),
        out_specs=_rows_spec(lay.out_rows, block_n),
        out_shape=jax.ShapeDtypeStruct((lay.out_rows, n), x_rows.dtype),
        interpret=interpret,
    )


def pinn_mlp_pallas2_res(x_rows, w_stack, b_stack, a_vec, *, lay,
                         act="tanh", block_n=256, interpret=False):
    """Training-forward launch: :func:`pinn_mlp_pallas2`'s output PLUS the
    reverse sweep's residual stack (L, n_tiles, N, WPAD)."""
    n = x_rows.shape[1]
    assert x_rows.shape[0] == lay.x_rows and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    assert n_layers >= 1, "residual-saving kernel needs >= 1 hidden layer"
    kernel = functools.partial(_kernel2_res, n_layers=n_layers, lay=lay,
                               act=act)
    dt = x_rows.dtype
    return _launch(
        "kernel_res", kernel,
        (x_rows, w_stack, b_stack, _lane_slopes(a_vec)), layout=lay.name,
        grid=(n // block_n,),
        in_specs=_specs2(lay, n_layers, block_n),
        out_specs=[
            _rows_spec(lay.out_rows, block_n),
            pl.BlockSpec((n_layers, lay.n_tiles, block_n, WPAD),
                         lambda i: (0, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lay.out_rows, n), dt),
            jax.ShapeDtypeStruct((n_layers, lay.n_tiles, n, WPAD), dt),
        ],
        interpret=interpret,
    )


def pinn_mlp_pallas2_bwd(x_rows, w_stack, a_vec, res, ct, *, lay,
                         act="tanh", block_n=256, interpret=False):
    """Fused reverse-sweep launch (:func:`_kernel2_bwd`).

    Grid over point blocks; x̄ streams out per block while the parameter
    cotangents (W̄ stack, b̄ stack, ā lane-partials) accumulate in one
    revisited VMEM block across the sequential grid.  ``res`` is
    :func:`pinn_mlp_pallas2_res`'s residual stack and ``ct`` the output
    streams' cotangents as (out_rows, N) rows.  Returns
    (cx (x_rows, N), cw (L+1, WPAD, WPAD), cb (L+1, n_tiles, WPAD),
    ca_part (L+1, WPAD) — sum the lane axis for ā); ``ops`` folds cw's
    stream blocks and cb's streams into W̄ and b̄.
    """
    n = x_rows.shape[1]
    assert x_rows.shape[0] == lay.x_rows and n % block_n == 0
    n_layers = w_stack.shape[0] - 1
    assert n_layers >= 1
    kernel = functools.partial(_kernel2_bwd, n_layers=n_layers, lay=lay,
                               act=act)
    dt = x_rows.dtype
    nt = lay.n_tiles
    return _launch(
        "bwd_fused", kernel,
        (x_rows, w_stack, _lane_slopes(a_vec), res, ct), layout=lay.name,
        grid=(n // block_n,),
        in_specs=[
            _rows_spec(lay.x_rows, block_n),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
            pl.BlockSpec((n_layers, nt, block_n, WPAD),
                         lambda i: (0, 0, i, 0)),
            _rows_spec(lay.out_rows, block_n),
        ],
        out_specs=[
            _rows_spec(lay.x_rows, block_n),
            pl.BlockSpec((n_layers + 1, WPAD, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, nt, WPAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers + 1, WPAD), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lay.x_rows, n), dt),
            jax.ShapeDtypeStruct((n_layers + 1, WPAD, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers + 1, nt, WPAD), dt),
            jax.ShapeDtypeStruct((n_layers + 1, WPAD), dt),
        ],
        interpret=interpret,
    )
