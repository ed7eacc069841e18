"""Interface (halo) exchange — the paper's MPI.Isend/Irecv stage on TPU ICI.

Two implementations with identical semantics (tested equal):

* :func:`exchange_ppermute` — runs INSIDE ``shard_map`` over the ``"sub"`` mesh axis.
  One ``jax.lax.ppermute`` per topology slot (edge color).  ppermute leaves devices
  that receive nothing with ZEROS — exactly the paper's ``MPI.PROC_NULL`` + zeroed
  buffer convention; the loss layer re-masks those slots anyway.  Because the slot
  perms pair each edge bidirectionally and both endpoints store the SAME physical
  points under the same slot, the received buffer aligns pointwise with local data.

* :func:`exchange_gather` — single-process reference on STACKED arrays (leading
  ``n_sub`` axis) using neighbor-index gathers.  Used by the vmap reference trainer
  and the equivalence tests.

Both are differentiable: the transpose of ppermute is the reversed ppermute, and the
transpose of gather is scatter-add — so the *fully-coupled* gradient mode
(``couple_gradients=True``, beyond-paper) costs one reversed exchange in the backward
pass, the same O(N_iface) bytes as the forward exchange.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.domain import Topology
from repro.obs.profiling import count_collective, scope


def exchange_ppermute(payload: jax.Array, topo: Topology, axis_name: str = "sub") -> jax.Array:
    """payload: (K, n_iface, C) local per-device slot data -> received (K, n_iface, C).

    Bracketed by the ``dd-comm-halo`` named scope (repro.obs.profiling): every
    collective-permute the chunk driver issues carries the scope in its HLO
    op_name, so profilers and the comp/comm splitter attribute it to the
    communication phase.  Each trace counts its K collective-permutes, with
    the bytes each device sends, in ``repro.obs.collective_counts()``."""
    with scope("comm"):
        outs = []
        for k in range(topo.n_slots):
            outs.append(
                jax.lax.ppermute(payload[k], axis_name=axis_name, perm=topo.perms[k])
            )
            count_collective("comm", "collective-permute",
                             payload[k].size * payload.dtype.itemsize)
        return jnp.stack(outs, axis=0)


def exchange_gather(payload: jax.Array, topo: Topology) -> jax.Array:
    """payload: (n_sub, K, n_iface, C) stacked -> received, zeros where no neighbor."""
    with scope("comm"):
        nbr = jnp.asarray(topo.neighbor)                # (n_sub, K)
        safe = jnp.maximum(nbr, 0)
        k_idx = jnp.arange(topo.n_slots)[None, :]       # (1, K)
        recv = payload[safe, k_idx]                     # (n_sub, K, n_iface, C)
        mask = (nbr >= 0).astype(payload.dtype)[..., None, None]
        return recv * mask


def exchange_tree_ppermute(payload: dict, topo: Topology, axis_name: str = "sub") -> dict:
    return jax.tree.map(lambda x: exchange_ppermute(x, topo, axis_name), payload)


def exchange_tree_gather(payload: dict, topo: Topology) -> dict:
    return jax.tree.map(lambda x: exchange_gather(x, topo), payload)
