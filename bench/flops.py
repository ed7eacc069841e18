"""Operations and bytes that one training step and each kernel launch need,
from the configuration's true shapes.

A network entry is counted as the streams the PDE consumes pushed through
every affine layer at the net's true widths: the value, the first
derivative along each input direction, and the second derivative along each
direction the residual uses.  A stream through a (fan_in, fan_out) layer
costs 2 * fan_in * fan_out operations per point.  The backward pass counts
twice the forward.  Residual points need every stream, boundary and data
points only the value, interface points what the payload needs (XPINN: the
residual; cPINN: the flux, so no second derivatives).

Lane padding, the residual stash and any other choice of how a kernel is
built are not work and are not counted here; bytes are a kernel's inputs and
outputs at true widths.  A faster layout of the same work therefore shows as
a higher share of the roofline, never as a different count.
"""
from __future__ import annotations

import numpy as np

F32 = 4

# per PDE: net -> (first-derivative directions, second-derivative directions)
STREAMS = {
    "burgers1d": {"u": (2, 1)},               # u, u_x, u_t, u_xx
    "heat2d_inverse": {"u": (2, 2),           # T, T_x, T_y, T_xx, T_yy
                       "k": (2, 0)},          # K, K_x, K_y
}


def layer_macs(net: dict) -> int:
    dims = [net["in_dim"]] + [net["width"]] * net["depth"] + [net["out_dim"]]
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def n_weights(net: dict) -> int:
    return layer_macs(net) + net["width"] * net["depth"] + net["out_dim"] \
        + net["depth"]


def point_groups(cfg: dict, n_data: list[int], n_pairs: int) -> dict:
    """True points per step: residual, data, interface (both sides)."""
    return {"res": int(sum(cfg["n_res"])), "data": int(sum(n_data)),
            "iface": 2 * n_pairs * int(cfg["n_iface"])}


def forward(cfg: dict, groups: dict) -> dict:
    """Per net: forward operations and true kernel bytes of one step."""
    kind = cfg["pde"]["kind"]
    cpinn = cfg["method"] == "cpinn"
    out = {}
    for name, net in cfg["nets"].items():
        d1, d2 = STREAMS[kind][name]
        full = 1 + d1 + d2
        iface = 1 + d1 if cpinn else full
        macs = layer_macs(net)
        flops = 2 * macs * (groups["res"] * full + groups["data"]
                            + groups["iface"] * iface)
        pts = groups["res"] + groups["data"] + groups["iface"]
        outs = (groups["res"] * full + groups["data"]
                + groups["iface"] * iface) * net["out_dim"]
        nbytes = F32 * (pts * net["in_dim"] + outs + n_weights(net))
        out[name] = {"flops": flops, "bytes": nbytes, "points": pts,
                     "outputs": outs}
    return out


def step(cfg: dict, groups: dict) -> dict:
    """Whole-step and per-kernel counts.

    ``kernel_res``: the fused forward, which computes every stream of every
    point of the step for every net.  ``kernel_bwd``: its reverse sweep,
    twice the forward's operations; it reads the inputs, the weights and one
    cotangent per forward output, and writes the input and weight
    cotangents."""
    fwd = forward(cfg, groups)
    f = sum(v["flops"] for v in fwd.values())
    res_bytes = sum(v["bytes"] for v in fwd.values())
    bwd_bytes = sum(F32 * (2 * v["points"] * net["in_dim"] + v["outputs"]
                           + 2 * n_weights(net))
                    for v, net in zip(fwd.values(), cfg["nets"].values()))
    return {"step_flops": 3 * f,
            "kernel_res": {"flops": f, "bytes": res_bytes},
            "kernel_bwd": {"flops": 2 * f, "bytes": bwd_bytes}}


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The chip's least time for the work, and which bound sets it."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def data_counts(data_comp: list) -> list[int]:
    """Data points with at least one observed field, per subdomain."""
    return [int(np.sum(np.any(np.asarray(c) > 0, axis=1))) if len(c) else 0
            for c in data_comp]
