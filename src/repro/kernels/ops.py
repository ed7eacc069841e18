"""Jit'd public wrappers for the Pallas kernels: padding, dispatch, interpret-mode
selection (TPU targets compiled kernels; CPU validates via interpret=True)."""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.pinn_mlp import (
    WPAD, _act_quad, pinn_mlp_pallas, pinn_mlp_pallas2, pinn_mlp_pallas2_bwd,
    pinn_mlp_pallas2_res,
)
from repro.obs.profiling import scope


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pack_mlp(Ws, bs, a):
    """Pad + stack an MLP pytree into the kernel's MXU-aligned layout.

    Returns (w_stack (L, WPAD, WPAD), b_stack (L, WPAD), a_vec (L,)).

    This is the hoistable 'prepare' step: the pad/stack ops are pure, so when a
    jitted step evaluates several fused calls on the SAME weights (residual +
    interface payload inside one loss), XLA CSE collapses the duplicate packing
    into one instance (verified by an HLO pad-count test in
    tests/test_kernels_pinn_mlp.py).  Callers outside a common jit scope (e.g.
    a serve loop with frozen weights) should call this once and use
    :func:`pinn_mlp_forward_packed`.
    """
    L = len(Ws)
    w_stack = jnp.stack([_pad_to(_pad_to(w, WPAD, 0), WPAD, 1) for w in Ws])
    b_stack = jnp.stack([_pad_to(b, WPAD, 0) for b in bs])
    a_vec = _pad_to(a, L, 0)
    return w_stack, b_stack, a_vec


def _pad_points(x, block_n):
    N = x.shape[0]
    n_pad = ((N + block_n - 1) // block_n) * block_n
    return _pad_to(_pad_to(x, n_pad, 0), WPAD, 1)


@partial(jax.jit, static_argnames=("act", "block_n", "interpret"))
def pinn_mlp_forward(x, Ws, bs, a, act="tanh", block_n=256, interpret=None):
    """Fused PINN MLP forward + input-Jacobian.

    x: (N, d_in); Ws: list[(in,out)]; bs: list[(out,)]; a: (n_hidden,) slopes.
    Returns (u (N, out), du (d_in, N, out)).
    """
    if interpret is None:
        interpret = not _on_tpu()
    N, d_in = x.shape
    out_dim = Ws[-1].shape[1]
    w_stack, b_stack, a_vec = pack_mlp(Ws, bs, a)
    x_pad = _pad_points(x, block_n)
    u, du = pinn_mlp_pallas(x_pad, w_stack, b_stack, a_vec, d_in=d_in, act=act,
                            block_n=block_n, interpret=interpret)
    return u[:N, :out_dim], du[:, :N, :out_dim]


@partial(jax.jit, static_argnames=("out_dim", "act", "block_n", "interpret"))
def pinn_mlp_forward_packed(x, packed, out_dim, act="tanh", block_n=256,
                            interpret=None):
    """First-order fused forward on a pre-packed weight stack (see pack_mlp)."""
    if interpret is None:
        interpret = not _on_tpu()
    N, d_in = x.shape
    w_stack, b_stack, a_vec = packed
    u, du = pinn_mlp_pallas(_pad_points(x, block_n), w_stack, b_stack, a_vec,
                            d_in=d_in, act=act, block_n=block_n,
                            interpret=interpret)
    return u[:N, :out_dim], du[:, :N, :out_dim]


# --------------------------------------------------------------- second order
#
# pinn_mlp_forward2 is the production residual path: one fused pass yields
# (u, du/dx_j, d²u/dx_j²) for all d_in directions.  Dispatch:
#   * TPU backend            -> compiled Pallas kernel (pinn_mlp._kernel2)
#   * non-TPU, interpret=None -> ref.pinn_mlp_ref2 (same math, batched jnp —
#       the compiled CPU fast path; the Pallas interpreter is a correctness
#       tool, far too slow for production)
#   * interpret=True         -> Pallas interpreter (kernel validation)
# The jax.custom_vjp makes the fused outputs differentiable w.r.t. (x, Ws, bs,
# a).  Two backward paths (static ``bwd`` selector):
#   * bwd="fused" (default) — the hand-derived reverse sweep: the forward
#       variant saves per-layer pre-activations + tangent streams as kernel
#       residuals and ONE reverse pass produces all cotangents
#       (pinn_mlp._kernel2_bwd on the Pallas dispatch, ref._ref2_bwd — the
#       same closed-form derivation as batched jnp — on the non-TPU fast
#       path).  No forward recompute, no autodiff of the recurrence.
#   * bwd="ref" — the PR-1 checkpointed oracle: save only the inputs and
#       jax.vjp through ref.pinn_mlp_ref2 inside the backward (op-granular
#       checkpointing).  Kept as the correctness reference and the fallback
#       for stacks the residual-saving kernel does not cover.
# Both paths are wrapped in named-scope markers ("pinn2-bwd-fused" /
# "pinn2-bwd-ref", repro.obs.profiling.SCOPES) so compiled-HLO tests can
# assert WHICH backward a training step actually contains; every Pallas
# launch also carries its own fixed name from the same table.


def _zero_pruned_rows(d2u, d2_dirs, d_in):
    """Zero d2u rows outside d2_dirs (kernel path parity with the pruned ref)."""
    if d2_dirs is None or tuple(d2_dirs) == tuple(range(d_in)):
        return d2u
    return d2u * _prune_mask(d2_dirs, d_in, d2u.dtype)


def _forward2_impl(x, Ws, bs, a, act, block_n, interpret, d2_dirs):
    N, d_in = x.shape
    out_dim = Ws[-1].shape[1]
    if interpret is None:
        if not _on_tpu():
            return ref.pinn_mlp_ref2(x, Ws, bs, a, act=act, d2_dirs=d2_dirs)
        interpret = False
    w_stack, b_stack, a_vec = pack_mlp(Ws, bs, a)
    u, du, d2u = pinn_mlp_pallas2(_pad_points(x, block_n), w_stack, b_stack,
                                  a_vec, d_in=d_in, act=act, block_n=block_n,
                                  interpret=interpret)
    # the VMEM-resident kernel computes every direction (pruning buys nothing
    # there); zero the unused rows so every dispatch path agrees with the ref
    d2u = _zero_pruned_rows(d2u, d2_dirs, d_in)
    return u[:N, :out_dim], du[:, :N, :out_dim], d2u[:, :N, :out_dim]


BWD_PATHS = ("fused", "ref")  # valid custom-VJP backward selectors

# conservative per-block VMEM cap for the fused reverse sweep (TPU VMEM is
# ~16 MB; leave headroom for Mosaic temporaries)
_BWD_VMEM_BUDGET = 12 * 1024 * 1024


def _use_jnp_recurrence(interpret) -> bool:
    """True when dispatch lands on the batched-jnp recurrence (non-TPU fast
    path) — decided statically, so forward and backward always agree."""
    return interpret is None and not _on_tpu()


def _fused_bwd_fits(n_weights, d_in, block_n, itemsize) -> bool:
    """Static VMEM estimate for one `_kernel2_bwd` block: residual streams
    (L·(1+2d) row tiles) + x/cu/cx + cotangent tiles + weight & cotangent
    stacks.  When the stack is too deep/wide to fit, the "fused" selector
    degrades to the checkpointed-ref save/recompute instead of dying in the
    Mosaic compiler — decided from static shapes, so forward and backward
    always agree — and warns (once per shape, by the default warning filter)
    with the estimate and the budget, so the step-down is never silent.
    Hidden-layer-free stacks (depth 0: one affine, nothing to spill) also take
    the checkpointed path — the residual-saving kernel requires >= 1 hidden
    layer."""
    L = n_weights - 1
    if L < 1:
        return False
    row_tiles = (1 + 2 * d_in) * L + 3 + 2 * d_in     # (block_n, WPAD) tiles
    fixed = 2 * n_weights * WPAD * WPAD + 3 * n_weights * WPAD
    estimate = (row_tiles * block_n * WPAD + fixed) * itemsize
    if estimate <= _BWD_VMEM_BUDGET:
        return True
    warnings.warn(
        f"fused reverse kernel needs ~{estimate} B of VMEM per block "
        f"(budget {_BWD_VMEM_BUDGET} B) at {L} hidden layers, d_in={d_in}, "
        f"block_n={block_n}: using the checkpointed jnp backward "
        "(pinn2-bwd-ref) instead", RuntimeWarning)
    return False


def _prune_mask(d2_dirs, d_in, dtype):
    mask = np.zeros((d_in, 1, 1), dtype)
    for j in d2_dirs:
        mask[j] = 1.0
    return mask


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _pinn_mlp_forward2(x, Ws, bs, a, act, block_n, interpret, d2_dirs, bwd):
    return _forward2_impl(x, Ws, bs, a, act, block_n, interpret, d2_dirs)


def _pinn_mlp_forward2_fwd(x, Ws, bs, a, act, block_n, interpret, d2_dirs, bwd):
    N, d_in = x.shape
    pallas = not _use_jnp_recurrence(interpret)
    if bwd == "ref" or (pallas and not _fused_bwd_fits(
            len(Ws), d_in, block_n, np.dtype(x.dtype).itemsize)):
        # checkpointed oracle: save inputs, recompute in bwd — explicitly
        # requested, or the fused reverse sweep's residual blocks won't fit
        return (_forward2_impl(x, Ws, bs, a, act, block_n, interpret, d2_dirs),
                (x, Ws, bs, a))
    out_dim = Ws[-1].shape[1]
    if not pallas:
        outs, res = ref._ref2_impl(x, Ws, bs, a, _act_quad(act)[:3], d2_dirs,
                                   save=True)
        return outs, (x, Ws, a, res)
    w_stack, b_stack, a_vec = pack_mlp(Ws, bs, a)
    u, du, d2u, h_res, t_res, s_res = pinn_mlp_pallas2_res(
        _pad_points(x, block_n), w_stack, b_stack, a_vec, d_in=d_in, act=act,
        block_n=block_n, interpret=bool(interpret))
    d2u = _zero_pruned_rows(d2u, d2_dirs, d_in)
    outs = (u[:N, :out_dim], du[:, :N, :out_dim], d2u[:, :N, :out_dim])
    # w_stack/a_vec are NOT saved: the bwd repacks them from (Ws, a) — a pure
    # pad/stack that XLA CSEs against the forward's pack (PR-1 HLO test), so
    # the residual footprint doesn't carry the padded weights twice
    return outs, (x, Ws, a, h_res, t_res, s_res)


def _pinn_mlp_forward2_bwd(act, block_n, interpret, d2_dirs, bwd, saved, cts):
    # mirror the fwd's STATIC dispatch (selector + backend + shape-derived
    # VMEM fit) so the saved-pytree structure is always interpreted correctly
    pallas = not _use_jnp_recurrence(interpret)
    if bwd == "ref" or (pallas and not _fused_bwd_fits(
            len(saved[1]), saved[0].shape[1], block_n,
            np.dtype(saved[0].dtype).itemsize)):
        x, Ws, bs, a = saved
        with scope("bwd_ref"):
            _, vjp = jax.vjp(lambda xx, W, b, aa: ref.pinn_mlp_ref2(
                xx, W, b, aa, act=act, d2_dirs=d2_dirs), x, Ws, bs, a)
            return vjp(cts)
    if not pallas:
        x, Ws, a, res = saved
        with scope("bwd_fused"):
            return ref._ref2_bwd(x, Ws, a, res, _act_quad(act), d2_dirs, cts)
    x, Ws, a, h_res, t_res, s_res = saved
    L = len(Ws)
    w_stack = jnp.stack([_pad_to(_pad_to(w, WPAD, 0), WPAD, 1) for w in Ws])
    a_vec = _pad_to(a, L, 0)
    N, d_in = x.shape
    cu, cdu, cd2u = cts
    if d2_dirs is not None and tuple(d2_dirs) != tuple(range(d_in)):
        # pruned rows of the kernel output are masked constants: their
        # cotangents must not flow (parity with the pruned jnp backward)
        cd2u = cd2u * _prune_mask(d2_dirs, d_in, cd2u.dtype)
    n_pad = ((N + block_n - 1) // block_n) * block_n
    pad2 = lambda c: _pad_to(_pad_to(c, n_pad, 0), WPAD, 1)
    pad3 = lambda c: _pad_to(_pad_to(c, n_pad, 1), WPAD, 2)
    with scope("bwd_fused"):
        cx, cw, cb, ca_part = pinn_mlp_pallas2_bwd(
            _pad_points(x, block_n), w_stack, a_vec, h_res, t_res, s_res,
            pad2(cu), pad3(cdu), pad3(cd2u), d_in=d_in, act=act,
            block_n=block_n, interpret=bool(interpret))
    cWs = tuple(cw[i, :w.shape[0], :w.shape[1]] for i, w in enumerate(Ws))
    cbs = tuple(cb[i, :w.shape[1]] for i, w in enumerate(Ws))
    ca = jnp.sum(ca_part, axis=1)[:a.shape[0]].astype(a.dtype)
    return cx[:N, :d_in], cWs, cbs, ca


_pinn_mlp_forward2.defvjp(_pinn_mlp_forward2_fwd, _pinn_mlp_forward2_bwd)


@partial(jax.jit, static_argnames=("act", "block_n", "interpret", "d2_dirs",
                                   "bwd"))
def pinn_mlp_forward2(x, Ws, bs, a, act="tanh", block_n=256, interpret=None,
                      d2_dirs=None, bwd="fused"):
    """Fused PINN MLP forward + input-Jacobian + diagonal input-Hessian.

    x: (N, d_in); Ws: list[(in,out)]; bs: list[(out,)]; a: (n_hidden,) slopes.
    Returns (u (N, out), du (d_in, N, out), d2u (d_in, N, out)) with
    d2u[j] = d²u/dx_j² (diagonal only — what the repo's PDE residuals need).
    Differentiable w.r.t. (x, Ws, bs, a) via a custom VJP.

    ``bwd`` (static) selects the backward implementation: ``"fused"`` is the
    hand-derived single-sweep reverse kernel over saved layer residuals (the
    production path); ``"ref"`` is the checkpointed jax.vjp through
    ``ref.pinn_mlp_ref2`` (correctness oracle / fallback).

    ``d2_dirs`` (static, None = all) prunes the second-order tangent stream to
    the listed input directions on the recurrence path — the rows a PDE's
    ``residual_from_derivs`` actually reads (``PDE.d2_dirs``); pruned rows are
    exact zeros, and both backwards prune identically.
    """
    if bwd not in BWD_PATHS:
        raise ValueError(f"unknown backward path {bwd!r}")
    return _pinn_mlp_forward2(x, tuple(Ws), tuple(bs), a, act, block_n,
                              interpret,
                              None if d2_dirs is None else tuple(d2_dirs),
                              bwd)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _forward2_select(x, Ws, bs, a, code, d2_dirs):
    return ref.pinn_mlp_ref2_select(x, Ws, bs, a, code, d2_dirs=d2_dirs)


def _forward2_select_fwd(x, Ws, bs, a, code, d2_dirs):
    outs, res = ref._ref2_impl(x, Ws, bs, a, ref._select_quad(code)[:3],
                               d2_dirs, save=True)
    return outs, (x, Ws, a, code, res)


def _forward2_select_bwd(d2_dirs, saved, cts):
    x, Ws, a, code, res = saved
    with scope("bwd_fused_select"):
        cx, cWs, cbs, ca = ref._ref2_bwd(x, Ws, a, res,
                                         ref._select_quad(code), d2_dirs, cts)
    # the integer activation code has no tangent space
    return cx, cWs, cbs, ca, np.zeros(np.shape(code), jax.dtypes.float0)


_forward2_select.defvjp(_forward2_select_fwd, _forward2_select_bwd)


@partial(jax.jit, static_argnames=("d2_dirs",))
def pinn_mlp_forward2_select(x, Ws, bs, a, code, d2_dirs=None):
    """Fused second-order bundle with a TRACED activation code (serving path).

    Same (u, du, d2u) contract as :func:`pinn_mlp_forward2`, but the activation
    is selected per call by ``code`` (0=tanh, 1=sin, 2=cos) instead of being a
    static specialization — so a ``vmap`` over stacked subdomain params with
    per-subdomain codes stays ONE traced network entry even when subdomains use
    heterogeneous (paper Table 3) activations.  Always the batched jnp
    recurrence (``ref.pinn_mlp_ref2_select``): the Pallas kernel specializes
    the activation statically, and a data-dependent activation select inside
    VMEM buys nothing on the serving path.  ``d2_dirs=()`` disables the
    second-order tangent stream entirely (value + first-order inference).

    Differentiable w.r.t. (x, Ws, bs, a): the backward is the same
    hand-derived reverse sweep as the static-act path, with the traced-code
    activation-derivative chain (``ref._select_quad``).
    """
    return _forward2_select(x, tuple(Ws), tuple(bs), a, code,
                            None if d2_dirs is None else tuple(d2_dirs))


def pinn_mlp_forward2_segments(x_segs, Ws, bs, a, act="tanh", block_n=256,
                               interpret=None, d2_dirs=None, bwd="fused"):
    """Segment-aware megabatch entry: ONE fused dispatch for several point sets.

    x_segs: sequence of (n_i, d_in) arrays sharing d_in (e.g. residual points,
    flattened interface points, data points).  The segments are concatenated
    into one megabatch, run through a single :func:`pinn_mlp_forward2` call
    (one pack_mlp + one kernel launch + one custom-VJP backward instead of
    len(x_segs) of each), and the (u, du, d2u) bundle is sliced back per
    segment.  The kernel math is row-independent (every output row depends only
    on its input row), so each returned bundle is identical to a separate
    ``pinn_mlp_forward2(x_segs[i], ...)`` call — the jvp-oracle semantics are
    preserved exactly; only the dispatch count changes.

    Returns a tuple of (u (n_i, out), du (d_in, n_i, out), d2u (d_in, n_i, out))
    bundles, one per segment.  Segment sizes must be static (they come from the
    padded batch layout).
    """
    sizes = [int(x.shape[0]) for x in x_segs]
    u, du, d2u = pinn_mlp_forward2(jnp.concatenate(list(x_segs), axis=0), Ws, bs,
                                   a, act=act, block_n=block_n,
                                   interpret=interpret, d2_dirs=d2_dirs,
                                   bwd=bwd)
    out, ofs = [], 0
    for n in sizes:
        out.append((u[ofs:ofs + n], du[:, ofs:ofs + n], d2u[:, ofs:ofs + n]))
        ofs += n
    return tuple(out)


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal=True, bq=256, bk=256, interpret=None):
    """Causal GQA flash attention. q: (B,H,S,dh); k/v: (B,Hk,T,dh)."""
    if interpret is None:
        interpret = not _on_tpu()
    dh = q.shape[-1]
    dh_pad = max(128, ((dh + 127) // 128) * 128)
    qp = _pad_to(q, dh_pad, 3)
    kp = _pad_to(k, dh_pad, 3)
    vp = _pad_to(v, dh_pad, 3)
    # keep the softmax scale of the TRUE head dim
    qp = qp * float(np.sqrt(dh_pad / dh))  # keep weak type: combined scale = 1/sqrt(dh)
    bq = min(bq, q.shape[2])
    bk = min(bk, k.shape[2])
    out = flash_attention_pallas(qp, kp, vp, causal=causal, bq=bq, bk=bk,
                                 interpret=interpret)
    return out[..., :dh]


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, chunk=64, interpret=None):
    """WKV6 linear attention. r/k/v/w: (B, T, H, P); u: (H, P). Returns (B,T,H,P)."""
    from repro.kernels.wkv6 import wkv6_pallas

    if interpret is None:
        interpret = not _on_tpu()
    B, T, H, P = r.shape
    P_pad = max(128, ((P + 127) // 128) * 128)
    def prep(x):
        x = _pad_to(x, P_pad, 3)
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, P_pad)
    up = _pad_to(u, P_pad, 1)
    up = jnp.broadcast_to(up[None], (B, H, P_pad)).reshape(B * H, P_pad)
    wp = prep(w)
    if P_pad != P:  # padded decay channels must not blow up cumsum(log w)
        pad_mask = jnp.arange(P_pad) >= P
        wp = jnp.where(pad_mask[None, None, :], 1.0, wp)
    y = wkv6_pallas(prep(r), prep(k), prep(v), wp, up, chunk=chunk,
                    interpret=interpret)
    y = y.reshape(B, H, T, P_pad).transpose(0, 2, 1, 3)
    return y[..., :P]
