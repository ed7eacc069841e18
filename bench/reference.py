"""Plain reference of the cPINN/XPINN losses, Adam and the served field.

Written from the paper's equations (3), (5) and (6), straightforward
``jax.numpy`` in float32 with no kernels, padding or batching: one network
per field and subdomain, derivatives by nested ``jax.jvp`` per point, one
Python loop over subdomains and their interfaces.  It imports nothing of the
program.  The caller sets the matmul precision (``highest`` for the
reference, a lower one for the control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ACTS = {"tanh": jnp.tanh, "sin": jnp.sin, "cos": jnp.cos}


def bf16x3(a, b):
    """A float32 product from three bfloat16 passes (hi*hi + hi*lo + lo*hi),
    the scheme of the TPU's ``high`` matmul precision, written out so that
    the control computes the same on any backend."""
    bf, f32 = jnp.bfloat16, jnp.float32
    ah, bh = a.astype(bf), b.astype(bf)
    al, bl = (a - ah.astype(f32)).astype(bf), (b - bh.astype(f32)).astype(bf)
    mm = lambda x, y: jnp.matmul(x, y, preferred_element_type=f32)  # noqa: E731
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


DOTS = {"f32": jnp.matmul, "bf16x3": bf16x3}


def mlp(p: dict, x: jax.Array, act, dot=jnp.matmul) -> jax.Array:
    """One point through one net: phi(a_l * (h W_l + b_l)) per hidden layer,
    linear output layer."""
    h = x
    n = len(p["W"])
    for i in range(n):
        h = dot(h, p["W"][i]) + p["b"][i]
        if i < n - 1:
            h = act(p["a"][i] * h)
    return h


def fields(params: dict, act, names, dot=jnp.matmul) -> callable:
    """x (2,) -> concatenated field outputs (F,), nets in config order."""
    return lambda x: jnp.concatenate([mlp(params[k], x, act, dot)
                                      for k in names])


def derivs(f, x, e):
    """(f, df/de, d2f/de2) at one point along direction e."""
    g = lambda y: jax.jvp(f, (y,), (e,))[1]
    u, du = jax.jvp(f, (x,), (e,))
    return u, du, jax.jvp(g, (x,), (e,))[1]


EX, EY = jnp.array([1.0, 0.0]), jnp.array([0.0, 1.0])


def residual(kind: str, pde: dict, f, x):
    """PDE residual at one point, (1,)."""
    if kind == "burgers1d":
        u, ux, uxx = derivs(f, x, EX)
        ut = jax.jvp(f, (x,), (EY,))[1]
        return ut + u * ux - pde["nu"] * uxx
    if kind == "heat2d_inverse":
        w, wx, wxx = derivs(f, x, EX)
        _, wy, wyy = derivs(f, x, EY)
        T_x, K_x, T_y, K_y = wx[0], wx[1], wy[0], wy[1]
        K = w[1]
        r = (K_x * T_x + K * wxx[0] + K_y * T_y + K * wyy[0]
             - 4.0 * jnp.exp(-0.1 * x[1]))
        return r[None]
    raise ValueError(kind)


def flux(kind: str, pde: dict, f, x):
    """Flux tensor at one point, (1, 2)."""
    if kind == "burgers1d":
        u, ux = jax.jvp(f, (x,), (EX,))
        return jnp.stack([0.5 * u * u - pde["nu"] * ux, u], axis=-1)
    if kind == "heat2d_inverse":
        w, wx = jax.jvp(f, (x,), (EX,))
        wy = jax.jvp(f, (x,), (EY,))[1]
        return jnp.stack([w[1] * wx[0], w[1] * wy[0]])[None, :]
    raise ValueError(kind)


def split(params: dict, q: int) -> dict:
    """Subdomain q's own nets out of the stacked arrays."""
    return jax.tree.map(lambda a: a[q], params)


# ------------------------------------------------------------------ loss

def losses(cfg: dict, pairs: list, params: list, data: dict,
           dot: str = "f32", exchange: bool = True):
    """Per-subdomain losses (n_sub,) of eq. (5) (cPINN) or (6) (XPINN).

    ``params`` is a list of per-subdomain parameter dicts; ``pairs[i]`` is
    the (a, b) of ``data["ifaces"][i]``.  What a subdomain receives from a
    neighbour enters as a constant (Algorithm 1); ``exchange=False`` is the
    fault in which nothing arrives (zeros, as a missing ppermute leaves)."""
    kind, pde = cfg["pde"]["kind"], cfg["pde"]
    w = cfg["loss_weights"]
    acts = [ACTS[a] for a in cfg["activations"]]
    fs = [fields(p, a, list(cfg["nets"]), DOTS[dot])
          for p, a in zip(params, acts)]
    cpinn = cfg["method"] == "cpinn"
    F = sum(n["out_dim"] for n in cfg["nets"].values())
    n_iface = int(cfg["n_iface"])

    def payload(q, pts, nrm):
        u = jax.vmap(fs[q])(pts)
        if cpinn:
            g = jnp.einsum("ned,nd->ne",
                           jax.vmap(lambda x: flux(kind, pde, fs[q], x))(pts),
                           nrm)
        else:
            g = jax.vmap(lambda x: residual(kind, pde, fs[q], x))(pts)
        return u, g

    out = []
    for q in range(len(params)):
        res = jax.vmap(lambda x: residual(kind, pde, fs[q], x))(data["res"][q])
        mse_res = jnp.mean(res ** 2)
        pred = jax.vmap(fs[q])(data["data_pts"][q])
        c = data["data_comp"][q]
        mse_data = (jnp.sum(c * (pred - data["data_vals"][q]) ** 2)
                    / jnp.maximum(jnp.sum(c), 1.0))
        avg = jnp.float32(0.0)
        ifc = jnp.float32(0.0)
        for (a, b), f in zip(pairs, data["ifaces"]):
            if q not in (a, b):
                continue
            other = b if q == a else a
            sign = 1.0 if q == a else -1.0
            u_q, g_q = payload(q, f["pts"], sign * f["normal_a"])
            u_o, g_o = jax.lax.stop_gradient(
                payload(other, f["pts"], -sign * f["normal_a"]))
            if not exchange:
                u_o, g_o = jnp.zeros_like(u_o), jnp.zeros_like(g_o)
            avg = avg + jnp.sum((0.5 * (u_q - u_o)) ** 2) / (n_iface * F)
            d = g_q + g_o if cpinn else g_q - g_o
            ifc = ifc + jnp.sum(d ** 2) / n_iface
        out.append(w["data"] * mse_data + w["residual"] * mse_res
                   + w["u_avg"] * avg + w["iface"] * ifc)
    return jnp.stack(out)


def adam(params, m, v, grads, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step (Kingma & Ba, bias-corrected); t counts from 1."""
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
        params, m, v)
    return params, m, v


def train(cfg: dict, pairs: list, params: list, data: dict, steps: int,
          **variant):
    """``steps`` Adam steps from ``params`` (a list over subdomains).

    Returns the loss of every step before its update, (steps, n_sub), and
    the parameters and both Adam moments after the last step."""
    lr = jnp.float32(cfg["lr"])

    def total(p):
        per = losses(cfg, pairs, p, data, **variant)
        return jnp.sum(per), per

    def body(carry, t):
        p, m, v = carry
        (_, loss), g = jax.value_and_grad(total, has_aux=True)(p)
        p, m, v = adam(p, m, v, g, t, lr)
        return (p, m, v), loss

    zeros = jax.tree.map(jnp.zeros_like, params)
    ts = jnp.arange(1, steps + 1, dtype=jnp.float32)
    (p, m, v), loss = jax.lax.scan(body, (params, zeros, zeros), ts)
    return loss, p, m, v


def train_jit(cfg: dict, pairs: list, steps: int, **variant):
    return jax.jit(lambda params, data: train(cfg, pairs, params, data, steps,
                                              **variant))


# ----------------------------------------------------------------- serve

def serve(cfg: dict, params: list, pts: np.ndarray, claims: np.ndarray,
          dot: str = "f32"):
    """u (N, F), grad u (N, 2, F), flux (N, 1, 2) and residual (N, 1) at
    ``pts``, each point the mean over the subdomains that claim it."""
    kind = cfg["pde"]["kind"]
    nu = float(cfg["pde"].get("nu", 0.0))
    acc = {}
    for q, act in enumerate(cfg["activations"]):
        rows = np.flatnonzero(claims[q])
        if len(rows) == 0:
            continue
        n = max(64, 1 << int(np.ceil(np.log2(len(rows)))))
        x = np.zeros((n, 2), np.float32)
        x[:len(rows)] = pts[rows]
        got = _serve_rows(kind, act, nu, tuple(cfg["nets"]), dot, params[q],
                          jnp.asarray(x))
        for k, v in got.items():
            v = np.asarray(v)[:len(rows)].astype(np.float64)
            if k not in acc:
                acc[k] = np.zeros((len(pts),) + v.shape[1:])
            np.add.at(acc[k], rows, v)
    n = claims.sum(axis=0).astype(np.float64)
    return {k: v / n.reshape((-1,) + (1,) * (v.ndim - 1))
            for k, v in acc.items()}


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _serve_rows(kind, act, nu, names, dot, p, x):
    pde = {"nu": nu}
    f = fields(p, ACTS[act], names, DOTS[dot])

    def point(y):
        return {"u": f(y),
                "grad_u": jnp.stack([jax.jvp(f, (y,), (EX,))[1],
                                     jax.jvp(f, (y,), (EY,))[1]]),
                "flux": flux(kind, pde, f, y),
                "residual": residual(kind, pde, f, y)}
    return jax.vmap(point)(x)
