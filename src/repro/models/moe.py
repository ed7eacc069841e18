"""Mixture-of-Experts block (deepseek-moe-16b fine-grained, phi3.5-moe).

Dispatch is capacity-based scatter/gather (GSPMD-friendly, EP-shardable):

1. router top-k over E experts; normalized top-k gates;
2. per-(token,slot) position within its expert via a cumsum over a one-hot
   (tokens past capacity C = ceil(T*k/E * cf) are DROPPED — standard);
3. scatter-add into an (E, C, d) buffer, experts sharded over ``model`` (EP) —
   XLA lowers the resharding to an all-to-all;
4. batched SwiGLU over experts;
5. gather back and gate-combine.

DeepSeek's 2 always-on shared experts run as a dense SwiGLU of width
``n_shared * d_expert`` fused alongside.  The router load-balance auxiliary loss
(mean_e f_e * p_e * E) is returned through the scan's per-layer output channel and
added to the LM loss in train mode.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.causal_lm import BlockDef, register_block
from repro.models.sharding import constrain


def init(rng, cfg: ModelConfig):
    ks = L.split_tree(rng, 6)
    E, d, de = cfg.n_experts, cfg.d_model, cfg.d_expert
    p = {
        "attn_norm": jnp.ones((d,)),
        "attn": L.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.hd, bias=cfg.qkv_bias),
        "mlp_norm": jnp.ones((d,)),
        "router": L.normal_init(ks[1], (d, E), std=0.02),
        "experts": {
            "wi": L.normal_init(ks[2], (E, d, de)),
            "wg": L.normal_init(ks[3], (E, d, de)),
            "wo": L.normal_init(ks[4], (E, de, d)),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_swiglu(ks[5], d, cfg.n_shared_experts * de)
    return p


def logical(cfg: ModelConfig):
    add_L = lambda t: jax.tree.map(lambda dm: (None,) + dm, t,
                                   is_leaf=lambda v: isinstance(v, tuple))
    p = {
        "attn_norm": (None, "embed"),
        "attn": add_L(L.gqa_logical(bias=cfg.qkv_bias)),
        "mlp_norm": (None, "embed"),
        "router": (None, "embed", None),
        "experts": {
            "wi": (None, "expert", "embed", None),
            "wg": (None, "expert", "embed", None),
            "wo": (None, "expert", None, "embed"),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = add_L(L.swiglu_logical())
    return p


def _n_groups(T: int) -> int:
    """Token groups for GROUPED dispatch (GShard-style): capacity is enforced per
    group, and the group dim shards over ``data`` so the sort/scatter stays local
    to a shard.  A global scatter into an (E, C, d) buffer is NOT GSPMD-shardable:
    measured on deepseek-moe train_4k it replicated the 32GB buffer and emitted a
    ~700GB/device all-reduce."""
    g = 256
    while g > 1 and T // g < 64:
        g //= 2
    return g


def capacity(cfg: ModelConfig, group_tokens: int) -> int:
    return max(4, int(math.ceil(group_tokens * cfg.top_k / cfg.n_experts
                                * cfg.capacity_factor)))


def moe_ffn(cfg: ModelConfig, p, x):
    """Grouped sort-based dispatch. x: (B, S, d) -> (out, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    G = _n_groups(T)
    t = T // G                                                     # tokens per group
    dt = x.dtype
    xg = x.reshape(G, t, d)
    xg = constrain(xg, "batch", None, None)

    logits = (xg @ p["router"].astype(dt)).astype(jnp.float32)     # (G, t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                            # (G, t, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)

    C = capacity(cfg, t)
    e_flat = idx.reshape(G, t * k)                                 # token-major order

    def dispatch_one(e_row, x_row):
        """One group: sort slots by expert, position = rank within expert."""
        order = jnp.argsort(e_row, stable=True)                    # (t*k,)
        e_sorted = e_row[order]
        counts = jnp.zeros((E,), jnp.int32).at[e_row].add(1)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(t * k, dtype=jnp.int32) - starts[e_sorted]
        keep = (pos < C)
        pos_c = jnp.minimum(pos, C - 1)
        tok = order // k                                           # source token
        vals = x_row[tok] * keep[:, None].astype(dt)
        buf = jnp.zeros((E, C, d), dt).at[e_sorted, pos_c].add(vals)
        return buf, (order, e_sorted, pos_c, keep, tok)

    buf, meta = jax.vmap(dispatch_one)(e_flat, xg)                 # (G, E, C, d)
    buf = constrain(buf, "batch", "expert", None, None)

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["experts"]["wg"].astype(dt)))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["experts"]["wi"].astype(dt))
    h = constrain(h, "batch", "expert", None, None)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["experts"]["wo"].astype(dt))
    out_buf = constrain(out_buf, "batch", "expert", None, None)

    def combine_one(ob, m, g_row):
        order, e_sorted, pos_c, keep, tok = m
        back = ob[e_sorted, pos_c] * keep[:, None].astype(dt)      # sorted slot order
        slot = order % k
        w = g_row[tok, slot].astype(dt)                            # (t*k,)
        return jnp.zeros((t, d), dt).at[tok].add(back * w[:, None])

    out = jax.vmap(combine_one)(out_buf, meta, gate)               # (G, t, d)
    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + L.swiglu(p["shared"], x)
    return out, aux


def moe_ffn_shardmap(cfg: ModelConfig, p, x):
    """Explicit expert-parallel MoE via shard_map (beyond-paper §Perf change).

    The GSPMD gather/scatter across the model-sharded (E, C, d) buffer lowers to
    FULL-BUFFER all-reduces (measured 360 GiB/device on deepseek train_4k).  Here
    each model rank dispatches its data-shard's tokens to ITS OWN E/16 experts
    locally and contributes a partial (tokens, d) output; the only model-axis
    collective is the psum of that partial — the same locality lesson as the
    paper's halo exchange (neighbor-scope communication instead of global).
    Capacity is per data-shard (t_loc * k / E * cf).
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.sharding import current_rules

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    dt = x.dtype
    xf = x.reshape(T, d)
    logits = (xf @ p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)).astype(dt)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)

    rules = current_rules() or {}
    batch_axes = rules.get("batch", None)

    def body(xl, il, gl, wi, wg, wo):
        r = jax.lax.axis_index("model")
        E_loc, tl = wi.shape[0], xl.shape[0]
        C = capacity(cfg, tl)
        e_flat = il.reshape(tl * k)
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = e_flat[order]
        counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(tl * k, dtype=jnp.int32) - starts[e_sorted]
        local_e = e_sorted - r * E_loc
        mine = ((local_e >= 0) & (local_e < E_loc) & (pos < C))
        le = jnp.clip(local_e, 0, E_loc - 1)
        pc = jnp.minimum(pos, C - 1)
        tok = order // k
        vals = xl[tok] * mine[:, None].astype(xl.dtype)
        buf = jnp.zeros((E_loc, C, d), xl.dtype).at[le, pc].add(vals)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wi)
        ob = jnp.einsum("ecf,efd->ecd", h, wo)
        back = ob[le, pc] * mine[:, None].astype(xl.dtype)
        w = gl[tok, order % k]
        part = jnp.zeros((tl, d), xl.dtype).at[tok].add(back * w[:, None])
        return jax.lax.psum(part, "model")

    tok_spec = P(batch_axes, None)
    w_spec = P("model", None, None)
    out = jax.shard_map(
        body,
        in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec),
        out_specs=tok_spec,
        check_vma=False,
    )(xf, idx, gate, p["experts"]["wi"].astype(dt), p["experts"]["wg"].astype(dt),
      p["experts"]["wo"].astype(dt))
    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + L.swiglu(p["shared"], x)
    return out, aux


def apply(cfg: ModelConfig, lp, x, lc, ctx):
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    attn_out, new_cache = L.attention_block(
        lp["attn"], h, cfg=cfg, positions=ctx["positions"], cache=lc,
        pos=ctx["pos"], causal=True, q_offset=ctx["q_offset"],
    )
    x = x + attn_out
    h = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    impl = moe_ffn_shardmap if getattr(cfg, "moe_shard_map", False) else moe_ffn
    ff, aux = impl(cfg, lp, h)
    x = x + ff
    if new_cache is None:
        # train mode: route the per-layer aux loss out through the scan's y channel
        return x, {"aux": aux}
    return x, new_cache


def init_cache(cfg: ModelConfig, B, T, dtype):
    kv = (B, T, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}


def cache_logical(cfg: ModelConfig):
    dims = ("batch", "kv_seq", "kv_heads", None)
    return {"k": dims, "v": dims}


register_block("moe", BlockDef(init=init, logical=logical, apply=apply,
                               init_cache=init_cache, cache_logical=cache_logical))
