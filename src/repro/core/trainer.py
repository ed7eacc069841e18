"""Distributed cPINN/XPINN trainers — the paper's Algorithm 1 in JAX.

Three trainers share one loss assembly:

* :class:`DistributedDDTrainer` — production path.  ``shard_map`` over a 1-D
  ``("sub",)`` mesh (one device per subdomain, the paper's one-rank-per-subdomain).
  Each step: (compute) local interface payload -> (communicate) one ppermute per
  topology slot -> (loss) eq. (5)/(6) -> independent Adam updates with per-subdomain
  learning rates.  Received payloads enter the loss as constants of the current
  step (Algorithm 1: each rank differentiates only its own subdomain loss), so
  the global gradient decomposes per subdomain — no collective in the backward.

* :class:`ReferenceTrainer` — bit-identical semantics on ONE device (vmap over the
  stacked subdomain axis + neighbor gathers).  Oracle for the equivalence tests, and
  the practical path when #devices < #subdomains.

* :class:`DataParallelTrainer` — the paper's Fig 1a baseline: one network, points
  sharded across workers, gradient allreduce (+ optional int8/top-k compression with
  error feedback), lr scaled by world size (Goyal et al. [21]).

Straggler mitigation / communication avoidance: ``local_steps = k`` runs k Adam
steps per halo exchange (received payloads frozen in between) — beyond-paper, see
EXPERIMENTS.md §Perf.

Single-dispatch training (EXPERIMENTS.md §Step fusion): every trainer exposes
``run_chunk(state, batch, steps)`` — a ``lax.scan`` over outer steps compiled
into ONE jitted dispatch with ``TrainState`` buffers donated (params/opt update
in place), the halo exchange living inside the scan body.  Each loss evaluation
enters the network exactly once: ``losses.network_eval`` megabatches residual +
interface + data points, ``jax.vjp`` captures that single forward so the
exchange payload and the differentiated loss share it, and the assembled loss's
cotangents chain back through the saved VJP.

Guarded chunks (EXPERIMENTS.md §Robustness): every trainer also exposes
``run_chunk_guarded(state, batch, steps, lr_scale)`` — the same scanned
single-dispatch driver with an IN-GRAPH health guard in the scan body.  After
each outer step the body checks that the per-subdomain losses and the updated
parameters are finite; once any check trips, a ``lax.cond`` freezes the carried
state for the remaining steps (early exit without breaking the static scan
length, donation, or the one-entry-per-loss-eval contract).  The chunk returns
``(state, terms, health)`` where ``health`` records the per-subdomain ok flags
and the number of applied steps, so the supervisor (``runtime.supervisor``)
can roll back to the last good checkpoint and retry with per-subdomain
learning-rate backoff — ``lr_scale`` rides the dispatch as a plain argument,
so backoff never recompiles.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fused, halo, losses, nets
from repro.kernels import ops
from repro.core.domain import Decomposition, Topology
from repro.core.losses import CPINN, XPINN, LossWeights, SubBatch
from repro.core.nets import SubdomainModelConfig
from repro.core.pdes import PDE
from repro.optim import adam as adam_lib
from repro.optim.compress import CompressionConfig, compress_decompress


@dataclass(frozen=True)
class DDConfig:
    method: int = XPINN
    weights: LossWeights = field(default_factory=LossWeights)
    couple_gradients: bool = False   # beyond-paper: grads flow through the exchange
    local_steps: int = 1             # k Adam steps per halo exchange (k=1: Algorithm 1)
    adam: adam_lib.AdamConfig = field(default_factory=adam_lib.AdamConfig)
    disable_exchange: bool = False   # benchmark ablation: comm replaced by own payload
    residual_path: str = "jvp"       # "jvp" (per-point closures) | "pallas" (fused kernel)
    backward_path: str = "fused"     # "fused" (hand-derived reverse sweep) | "ref"
                                     # (checkpointed jax.vjp oracle); pallas path only
    telemetry: bool = False          # in-graph per-step metric rows (grad/param
                                     # norms, iface mismatch, lr) on the terms


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    params: Any
    opt: dict
    step: jax.Array


# ------------------------------------------------------------- in-graph health

def _sqnorm(tree) -> jax.Array:
    """Scalar sum of squares over all leaves (f32 accumulation); NaN/Inf in any
    leaf makes the result non-finite — ONE cheap reduction guards the whole
    parameter pytree."""
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(tree))


def _stacked_sqnorm(tree) -> jax.Array:
    """(n_sub,) per-subdomain sum of squares over stacked (n_sub, ...) leaves."""
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)),
                       axis=tuple(range(1, x.ndim)))
               for x in jax.tree.leaves(tree))


def _nan_like(shapes):
    """NaN-filled pytree matching a ``jax.eval_shape`` result — the frozen
    branch's stand-in for the loss terms it did not compute."""
    return jax.tree.map(lambda s: jnp.full(s.shape, jnp.nan, s.dtype), shapes)


def _traced_dispatch(trainer, name: str, steps, call):
    """Host-side chunk span around a public ``run_chunk*`` dispatch.

    ``trainer.tracer is None`` (the default) takes ``call()`` verbatim — no
    span object, no annotation, no clock read, and the jitted program is the
    same object either way (trace-count/HLO parity asserted in
    tests/test_tracing.py).  With a tracer attached, the span brackets the
    host's dispatch only: it does not wait for the device, so tracing leaves
    the host/device overlap as it is.  The device's side is read from the
    device trace, where this span also stands (the tracer mirrors it onto
    the profiler); the supervisor's chunk root, which ends after the health
    fetch, gives the chunk's whole extent.  The span parents to the
    caller's active span via the tracer's stack."""
    tr = getattr(trainer, "tracer", None)
    if tr is None:
        return call()
    with tr.span(name, lane="train", steps=steps,
                 trainer=type(trainer).__name__):
        return call()


# ------------------------------------------------------- in-graph telemetry

def _telemetry_terms(terms: dict, params, grads, lr, stacked: bool) -> dict:
    """Per-step metric rows riding the scan's ``terms`` output (EXPERIMENTS.md
    §Observability).  Pure arithmetic on values the step already computed —
    two parameter-tree reductions, a few scalar ops — so the chunk stays ONE
    dispatch and the measured overhead is bounded at 2%:

    * ``grad_norm`` / ``param_norm`` — L2 norms of the (last local step's)
      loss gradient and the updated parameters, per subdomain on stacked
      trees; the early-warning signals for the divergences the guard trips on;
    * ``lr`` — the EFFECTIVE per-subdomain learning rate of this step
      (includes the supervisor's recovery ``lr_scale`` backoff);
    * ``iface_mismatch`` — RMS interface disagreement sqrt(MSE_avg + MSE_F/flux),
      the paper's Figs 6-9 coupling-quality axis, when the loss has interface
      terms (the data-parallel baseline has none).
    """
    norm = _stacked_sqnorm if stacked else _sqnorm
    t = dict(terms)
    t["grad_norm"] = jnp.sqrt(norm(grads))
    t["param_norm"] = jnp.sqrt(norm(params))
    t["lr"] = jnp.broadcast_to(jnp.asarray(lr, jnp.float32),
                               t["loss"].shape)
    if "mse_avg" in t:
        t["iface_mismatch"] = jnp.sqrt(t["mse_avg"] + t["mse_iface"])
    return t


class _DDCommon:
    """Shared setup + per-subdomain step body."""

    def __init__(
        self,
        pde: PDE,
        model_cfg: SubdomainModelConfig,
        topo: Topology,
        cfg: DDConfig,
        act_codes: Sequence[str | int] | None = None,
        lrs: float | Sequence[float] = 1e-3,
        width_fracs: dict[str, Sequence[float]] | None = None,
    ):
        self.pde, self.model_cfg, self.topo, self.cfg = pde, model_cfg, topo, cfg
        n = topo.n_sub
        self._act_codes_in = act_codes
        # optional repro.obs.Tracer: host-side chunk spans around the public
        # run_chunk* dispatches (the supervisor wires its obs tracer in here)
        self.tracer = None
        # fused-kernel residual dispatch: requires (a) a single activation
        # shared by all subdomains (the kernel is specialized statically) and
        # (b) a PDE exposing the batched derivative-bundle methods.  An
        # explicitly requested pallas path that can't be honored is an error,
        # not a silent fallback.
        self.res_path = None
        if cfg.backward_path not in ops.BWD_PATHS:
            raise ValueError(f"unknown backward_path {cfg.backward_path!r}")
        if cfg.residual_path == "pallas":
            act = (nets.uniform_model_act(model_cfg) if act_codes is None
                   else fused.uniform_act_name(act_codes))
            if act is None:
                raise ValueError(
                    "residual_path='pallas' needs one activation shared by all "
                    f"subdomains; got {act_codes}")
            if not type(pde).supports_derivs():
                raise ValueError(
                    f"residual_path='pallas': {pde.name} lacks residual_from_derivs/"
                    "flux_from_derivs")
            self.res_path = losses.ResidualPath(act=act, bwd=cfg.backward_path)
        elif cfg.residual_path != "jvp":
            raise ValueError(f"unknown residual_path {cfg.residual_path!r}")
        self.lrs = jnp.full((n,), float(lrs)) if np.isscalar(lrs) else jnp.asarray(
            np.array(lrs, np.float32)
        )
        assert self.lrs.shape == (n,)
        # per-subdomain width masks (paper: per-subdomain architecture freedom)
        self.width_masks = None
        if width_fracs is not None:
            self.width_masks = {}
            for name, fr in width_fracs.items():
                w = model_cfg.nets[name].width
                m = np.zeros((n, w), np.float32)
                for q, f in enumerate(fr):
                    m[q, : max(1, int(round(f * w)))] = 1.0
                self.width_masks[name] = jnp.asarray(m)

    def init(self, seed: int = 0) -> TrainState:
        params, self.act_codes = nets.stacked_init(
            self.model_cfg, self.topo.n_sub, jax.random.PRNGKey(seed), self._act_codes_in
        )
        opt = adam_lib.init_adam(params)
        return TrainState(params=params, opt=opt, step=jnp.zeros((), jnp.int32))

    # ---- single-subdomain pieces (no stacked axis) -------------------------------
    def _net_eval(self, params, act_code, wmask, batch: SubBatch):
        """All network-dependent quantities in one entry (megabatched on the
        fused path): (res, normal-projected own payload, data_pred)."""
        return losses.network_eval(
            self.pde, self.model_cfg, self.cfg.method, params, act_code, wmask,
            batch, self.res_path,
        )

    def _assemble(self, batch: SubBatch, res, own, data_pred, recv):
        """Loss arithmetic on precomputed network outputs — no network entry."""
        return losses.assemble_subdomain_loss(
            self.pde, self.cfg.method, self.cfg.weights, batch, res, own,
            data_pred, recv["u"], recv["g"],
        )

    def _maybe_stop(self, recv):
        if self.cfg.couple_gradients:
            return recv
        return jax.tree.map(jax.lax.stop_gradient, recv)


class ReferenceTrainer(_DDCommon):
    """Single-device oracle: vmap over subdomains + gather exchange."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.step = jax.jit(self._step)
        self._chunk_const = jax.jit(self._run_chunk_const, static_argnums=(2,),
                                    donate_argnums=(0,))
        self._chunk_stacked = jax.jit(self._run_chunk_stacked, donate_argnums=(0,))
        self._chunk_guarded = jax.jit(self._run_chunk_guarded, static_argnums=(2,),
                                      donate_argnums=(0,))

    def _outer_body(self, carry, batch: SubBatch, lrs=None):
        """One outer step (exchange + local_steps Adam updates) on stacked
        arrays.  ONE network entry per loss evaluation: ``jax.vjp`` captures
        the megabatched forward, the exchange payload is a slice of that SAME
        forward (no separate payload entry), and the assembled loss's
        cotangents chain back through the saved VJP.  ``lrs`` overrides the
        per-subdomain learning rates (guarded chunks scale them for recovery
        backoff)."""
        lrs = self.lrs if lrs is None else lrs
        params, opt, step = carry
        wm = self.width_masks  # dict of (n_sub, w) or None (None = empty pytree: vmap ok)
        net_eval = lambda p: jax.vmap(self._net_eval)(p, self.act_codes, wm, batch)

        def assemble_all(outs, recv):
            res, own, data_pred = outs
            total, terms = jax.vmap(self._assemble)(batch, res, own, data_pred, recv)
            return jnp.sum(total), terms

        # communicate once per outer step (Algorithm 1), then k local updates;
        # the exchange payload rides on inner step 1's forward
        with jax.named_scope("dd-comp-forward"):
            outs, vjp_fn = jax.vjp(net_eval, params)
        own0 = outs[1]
        if self.cfg.disable_exchange:
            recv = self._maybe_stop(own0)
        else:
            recv = self._maybe_stop(halo.exchange_tree_gather(own0, self.topo))

        terms = None
        for i in range(self.cfg.local_steps):
            if i > 0:  # received payloads stay frozen; fresh forward on new params
                with jax.named_scope("dd-comp-forward"):
                    outs, vjp_fn = jax.vjp(net_eval, params)
            with jax.named_scope("dd-comp-update"):
                (_, terms), gouts = jax.value_and_grad(assemble_all, has_aux=True)(outs, recv)
                (grads,) = vjp_fn(gouts)
                params, opt = adam_lib.adam_update(grads, opt, params, lrs, self.cfg.adam)
        if self.cfg.telemetry:
            terms = _telemetry_terms(terms, params, grads, lrs, stacked=True)
        return (params, opt, step + 1), terms

    def _step(self, state: TrainState, batch: SubBatch) -> tuple[TrainState, dict]:
        carry, terms = self._outer_body((state.params, state.opt, state.step), batch)
        params, opt, step = carry
        return TrainState(params=params, opt=opt, step=step), terms

    def _run_chunk_const(self, state, batch, steps):
        carry, terms = jax.lax.scan(
            lambda c, _: self._outer_body(c, batch),
            (state.params, state.opt, state.step), None, length=steps)
        params, opt, step = carry
        return TrainState(params=params, opt=opt, step=step), terms

    def _run_chunk_stacked(self, state, batches):
        carry, terms = jax.lax.scan(
            self._outer_body, (state.params, state.opt, state.step), batches)
        params, opt, step = carry
        return TrainState(params=params, opt=opt, step=step), terms

    def run_chunk(self, state: TrainState, batch: SubBatch, steps: int | None = None):
        """Run a whole chunk of outer steps in ONE jitted dispatch (lax.scan).

        ``batch`` is either a normal stacked SubBatch reused every step
        (``steps`` gives the chunk length) or, with ``steps=None``, a SubBatch
        whose leaves carry an extra LEADING chunk axis (one batch per step —
        e.g. resampled collocation points).  ``state`` is DONATED: params and
        optimizer buffers alias in place, so the caller must rebind
        (``state, terms = trainer.run_chunk(state, batch, n)``) and never touch
        the old state again.  Returns (state, terms) with every term stacked
        over the chunk axis, shape (steps, n_sub).
        """
        if steps is None:
            return _traced_dispatch(self, "train.run_chunk", None,
                                    lambda: self._chunk_stacked(state, batch))
        return _traced_dispatch(self, "train.run_chunk", steps,
                                lambda: self._chunk_const(state, batch, steps))

    # ------------------------------------------------------------ guarded chunk
    def _guarded_body(self, carry, batch: SubBatch, lrs):
        """Scan body with the in-graph health guard: run one outer step only
        while every subdomain is healthy, then freeze the carry.  The live
        branch IS ``_outer_body`` — same trace, same single network entry per
        loss evaluation — so guarding never adds a dispatch."""
        inner, ok_sub, good = carry
        live = lambda c: self._outer_body(c, batch, lrs)
        nan_terms = _nan_like(jax.eval_shape(live, inner)[1])
        all_ok = jnp.all(ok_sub)
        inner, terms = jax.lax.cond(all_ok, live, lambda c: (c, nan_terms), inner)
        # health of the step just applied: finite per-subdomain loss AND finite
        # updated params (catches NaN grads/moments the loss can't see yet)
        healthy = (jnp.isfinite(terms["loss"])
                   & jnp.isfinite(_stacked_sqnorm(inner[0])))
        # after a trip the NaN terms would flag everyone — keep the trip-time
        # ok vector so the supervisor sees WHICH subdomains diverged
        ok_sub = jnp.where(all_ok, ok_sub & healthy, ok_sub)
        if self.cfg.telemetry:
            # per-step guard row: which subdomains were still ok AFTER this
            # step (added outside the cond so the frozen branch records too)
            terms = dict(terms, step_ok=ok_sub)
        return (inner, ok_sub, good + all_ok.astype(jnp.int32)), terms

    def _run_chunk_guarded(self, state, batch, steps, lr_scale):
        lrs = self.lrs * lr_scale
        carry0 = ((state.params, state.opt, state.step),
                  jnp.ones((self.topo.n_sub,), bool), jnp.zeros((), jnp.int32))
        (inner, ok_sub, good), terms = jax.lax.scan(
            lambda c, _: self._guarded_body(c, batch, lrs), carry0, None,
            length=steps)
        params, opt, step = inner
        health = {"ok": jnp.all(ok_sub), "ok_sub": ok_sub, "good_steps": good}
        return TrainState(params=params, opt=opt, step=step), terms, health

    def run_chunk_guarded(self, state: TrainState, batch: SubBatch, steps: int,
                          lr_scale=None):
        """``run_chunk`` with the in-graph health guard — still ONE jitted
        dispatch with ``state`` donated.  Returns ``(state, terms, health)``:
        ``health["ok_sub"]`` (n_sub,) marks subdomains whose loss/params went
        non-finite, ``health["good_steps"]`` counts applied outer steps (the
        carry freezes once tripped; terms rows after the trip are NaN).
        ``lr_scale`` (n_sub,) scales the per-subdomain learning rates without
        recompiling (recovery backoff)."""
        if lr_scale is None:
            lr_scale = jnp.ones_like(self.lrs)
        return _traced_dispatch(
            self, "train.run_chunk_guarded", steps,
            lambda: self._chunk_guarded(state, batch, steps,
                                        jnp.asarray(lr_scale)))


class DistributedDDTrainer(_DDCommon):
    """shard_map over the ("sub",) mesh — one device per subdomain (Algorithm 1)."""

    def __init__(self, *args, mesh: Mesh | None = None, **kw):
        super().__init__(*args, **kw)
        n = self.topo.n_sub
        if mesh is None:
            devs = jax.devices()
            assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
            mesh = Mesh(np.array(devs[:n]), ("sub",))
        assert mesh.shape["sub"] == n
        self.mesh = mesh
        self.step = self._build_step()
        self._chunk_cache: dict[int, Any] = {}

    def init(self, seed: int = 0) -> TrainState:
        state = super().init(seed)
        # per-subdomain Adam step counter so every leaf carries the stacked axis
        state.opt["count"] = jnp.zeros((self.topo.n_sub,), jnp.int32)
        return state

    def _local_outer_body(self, params, opt, act_code, lr, wmask, batch: SubBatch):
        """One outer step for ONE shard (no leading axis), inside shard_map.
        Same single-entry-per-loss-evaluation structure as the reference
        trainer, with ppermute as the exchange."""
        cfg = self.cfg
        net_eval = lambda p: self._net_eval(p, act_code, wmask, batch)

        def assemble(outs, recv):
            res, own, data_pred = outs
            return self._assemble(batch, res, own, data_pred, recv)

        with jax.named_scope("dd-comp-forward"):
            outs, vjp_fn = jax.vjp(net_eval, params)
        own0 = outs[1]
        if cfg.disable_exchange:
            recv = self._maybe_stop(own0)
        else:
            recv = self._maybe_stop(halo.exchange_tree_ppermute(own0, self.topo, "sub"))

        terms = None
        for i in range(cfg.local_steps):
            if i > 0:
                with jax.named_scope("dd-comp-forward"):
                    outs, vjp_fn = jax.vjp(net_eval, params)
            with jax.named_scope("dd-comp-update"):
                (_, terms), gouts = jax.value_and_grad(assemble, has_aux=True)(outs, recv)
                (grads,) = vjp_fn(gouts)
                params, opt = adam_lib.adam_update(grads, opt, params, lr, cfg.adam)
        if cfg.telemetry:
            terms = _telemetry_terms(terms, params, grads, lr, stacked=False)
        return params, opt, terms

    def _build_step(self):
        spec = P("sub")

        def local_step(params, opt, step, act_code, lr, wmask, batch: SubBatch):
            # leading axis is the local shard (size 1): squeeze
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            params, opt_l, terms = self._local_outer_body(
                sq(params), sq(opt), act_code[0], lr[0], sq(wmask), sq(batch))
            unsq = lambda t: jax.tree.map(lambda x: x[None], t)
            return unsq(params), unsq(opt_l), step + 1, unsq(terms)

        shmapped = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(spec, spec, P(), spec, spec, spec, spec),
            out_specs=(spec, spec, P(), spec),
            check_vma=False,
        )

        @jax.jit
        def step(state: TrainState, batch: SubBatch):
            p, o, s, terms = shmapped(
                state.params, state.opt, state.step, self.act_codes, self.lrs,
                self.width_masks, batch,
            )
            return TrainState(params=p, opt=o, step=s), terms

        return step

    def _build_chunk(self, steps: int):
        spec = P("sub")

        def local_chunk(params, opt, step, act_code, lr, wmask, batch: SubBatch):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            p, o = sq(params), sq(opt)
            ac, l, wm, b = act_code[0], lr[0], sq(wmask), sq(batch)

            def body(carry, _):
                p, o = carry
                p, o, terms = self._local_outer_body(p, o, ac, l, wm, b)
                return (p, o), terms

            (p, o), terms = jax.lax.scan(body, (p, o), None, length=steps)
            unsq = lambda t: jax.tree.map(lambda x: x[None], t)
            # term leaves are (steps,); the shard axis goes SECOND so the
            # stitched result is (steps, n_sub)
            terms = jax.tree.map(lambda x: x[:, None], terms)
            return unsq(p), unsq(o), step + steps, terms

        shmapped = jax.shard_map(
            local_chunk,
            mesh=self.mesh,
            in_specs=(spec, spec, P(), spec, spec, spec, spec),
            out_specs=(spec, spec, P(), P(None, "sub")),
            check_vma=False,
        )

        def chunk(state: TrainState, batch: SubBatch):
            p, o, s, terms = shmapped(
                state.params, state.opt, state.step, self.act_codes, self.lrs,
                self.width_masks, batch,
            )
            return TrainState(params=p, opt=o, step=s), terms

        return jax.jit(chunk, donate_argnums=(0,))

    def run_chunk(self, state: TrainState, batch: SubBatch, steps: int):
        """`steps` outer steps (exchange inside the scan body) in ONE jitted
        dispatch; ``state`` is donated — rebind it.  Returns (state, terms)
        with term leaves stacked (steps, n_sub)."""
        fn = self._chunk_cache.get(steps)
        if fn is None:
            fn = self._chunk_cache[steps] = self._build_chunk(steps)
        return _traced_dispatch(self, "train.run_chunk", steps,
                                lambda: fn(state, batch))

    # ------------------------------------------------------------ guarded chunk
    def _build_guarded_chunk(self, steps: int):
        spec = P("sub")

        def local_chunk(params, opt, step, act_code, lr, lr_scale, wmask,
                        batch: SubBatch):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            p, o = sq(params), sq(opt)
            ac, l, wm, b = act_code[0], lr[0] * lr_scale[0], sq(wmask), sq(batch)

            def live(args):
                p, o = args
                p2, o2, t = self._local_outer_body(p, o, ac, l, wm, b)
                return (p2, o2), t

            nan_terms = _nan_like(jax.eval_shape(live, (p, o))[1])

            def body(carry, _):
                (p, o), ok, good = carry
                # collective agreement: every shard freezes the moment ANY
                # shard trips (one scalar pmin per step — the SPMD analogue of
                # the reference trainer's jnp.all over the stacked ok vector)
                all_ok = jax.lax.pmin(ok.astype(jnp.int32), "sub") > 0
                (p, o), terms = jax.lax.cond(all_ok, live,
                                             lambda a: (a, nan_terms), (p, o))
                healthy = jnp.isfinite(terms["loss"]) & jnp.isfinite(_sqnorm(p))
                ok = jnp.where(all_ok, ok & healthy, ok)
                if self.cfg.telemetry:
                    terms = dict(terms, step_ok=ok)
                return ((p, o), ok, good + all_ok.astype(jnp.int32)), terms

            carry0 = ((p, o), jnp.ones((), bool), jnp.zeros((), jnp.int32))
            ((p, o), ok, good), terms = jax.lax.scan(body, carry0, None,
                                                     length=steps)
            unsq = lambda t: jax.tree.map(lambda x: x[None], t)
            terms = jax.tree.map(lambda x: x[:, None], terms)
            # good is collectively agreed -> identical on all shards (out P())
            return unsq(p), unsq(o), step + good, ok[None], good, terms

        shmapped = jax.shard_map(
            local_chunk,
            mesh=self.mesh,
            in_specs=(spec, spec, P(), spec, spec, spec, spec, spec),
            out_specs=(spec, spec, P(), spec, P(), P(None, "sub")),
            check_vma=False,
        )

        def chunk(state: TrainState, batch: SubBatch, lr_scale):
            p, o, s, ok, good, terms = shmapped(
                state.params, state.opt, state.step, self.act_codes, self.lrs,
                lr_scale, self.width_masks, batch,
            )
            health = {"ok": jnp.all(ok), "ok_sub": ok, "good_steps": good}
            return TrainState(params=p, opt=o, step=s), terms, health

        return jax.jit(chunk, donate_argnums=(0,))

    def run_chunk_guarded(self, state: TrainState, batch: SubBatch, steps: int,
                          lr_scale=None):
        """Guarded ``run_chunk`` (see :meth:`ReferenceTrainer.run_chunk_guarded`)
        on the SPMD path: each shard checks its own loss/params, a per-step
        scalar ``pmin`` agrees the freeze collectively, and ``health["ok_sub"]``
        comes back stitched (n_sub,).  Still one jitted dispatch, state
        donated; ``lr_scale`` is sharded over "sub" like the learning rates."""
        if lr_scale is None:
            lr_scale = jnp.ones_like(self.lrs)
        fn = self._chunk_cache.get(("guarded", steps))
        if fn is None:
            fn = self._chunk_cache[("guarded", steps)] = self._build_guarded_chunk(steps)
        return _traced_dispatch(
            self, "train.run_chunk_guarded", steps,
            lambda: fn(state, batch, jnp.asarray(lr_scale)))

    def shard_batch(self, batch: SubBatch) -> SubBatch:
        sh = NamedSharding(self.mesh, P("sub"))
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

    def shard_state(self, state: TrainState) -> TrainState:
        sh = NamedSharding(self.mesh, P("sub"))
        rep = NamedSharding(self.mesh, P())
        return TrainState(
            params=jax.tree.map(lambda x: jax.device_put(x, sh), state.params),
            opt=jax.tree.map(
                lambda x: jax.device_put(x, sh if x.ndim > 0 else rep), state.opt
            ),
            step=jax.device_put(state.step, rep),
        )


class DataParallelTrainer:
    """Paper Fig 1a: same net on every worker, sharded points, gradient allreduce."""

    def __init__(
        self,
        pde: PDE,
        model_cfg: SubdomainModelConfig,
        n_workers: int,
        weights: LossWeights = LossWeights(),
        lr: float = 1e-3,
        scale_lr: bool = True,  # Goyal et al. [21]: lr *= world size
        compression: CompressionConfig | None = None,
        mesh: Mesh | None = None,
        adam_cfg: adam_lib.AdamConfig = adam_lib.AdamConfig(),
        residual_path: str = "jvp",
        backward_path: str = "fused",
        telemetry: bool = False,
    ):
        self.pde, self.model_cfg, self.weights = pde, model_cfg, weights
        self.n = n_workers
        self.lr = lr * (n_workers if scale_lr else 1)
        self.compression = compression
        self.adam_cfg = adam_cfg
        self.telemetry = telemetry
        # activation comes from the model config (raises only on genuinely
        # unsupported configs: mixed per-net activations or an unknown name)
        self.act = nets.uniform_model_act(model_cfg)
        self.act_code = nets.act_code(self.act)
        self.res_path = None
        if backward_path not in ops.BWD_PATHS:
            raise ValueError(f"unknown backward_path {backward_path!r}")
        if residual_path == "pallas":
            if not type(pde).supports_derivs():
                raise ValueError(f"residual_path='pallas': {pde.name} lacks bundle methods")
            self.res_path = losses.ResidualPath(act=self.act, bwd=backward_path)
        elif residual_path != "jvp":
            raise ValueError(f"unknown residual_path {residual_path!r}")
        if mesh is None:
            devs = jax.devices()
            assert len(devs) >= n_workers
            mesh = Mesh(np.array(devs[:n_workers]), ("sub",))
        self.mesh = mesh
        self.step = self._build_step()
        self._chunk_cache: dict[int, Any] = {}
        self.tracer = None   # optional repro.obs.Tracer (host chunk spans)

    def init(self, seed: int = 0):
        params = nets.init_model(self.model_cfg, jax.random.PRNGKey(seed))
        opt = adam_lib.init_adam(params)
        # error-feedback buffer is PER-WORKER state (each rank accumulates the
        # error of compressing ITS OWN pre-allreduce gradient): stacked leading
        # n axis, sharded over "sub" — replicating it would silently average
        # away the feedback (regression-tested in test_parallel_equivalence).
        err = (jax.tree.map(lambda x: jnp.zeros((self.n,) + x.shape, x.dtype), params)
               if self.compression else None)
        return {"params": params, "opt": opt, "err": err, "step": jnp.zeros((), jnp.int32)}

    def _local_update(self, params, opt, err_l, batch: SubBatch, lr_scale=None):
        """One allreduce-Adam update for ONE worker (err_l: this worker's
        error-feedback slice, no leading axis).  The fused path's
        vanilla_pinn_loss is already a single [res | data] megabatch entry."""
        comp = self.compression
        lr = self.lr if lr_scale is None else self.lr * lr_scale

        def loss_fn(p):
            return losses.vanilla_pinn_loss(
                self.pde, self.model_cfg, self.weights, p, self.act_code, None,
                batch, path=self.res_path,
            )

        with jax.named_scope("dd-comp-forward"):
            (_, terms), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if comp is not None:
            g, err_l = compress_decompress(g, err_l, comp)
        # the paper's distributed optimizer: allreduce-mean of loss gradients
        g = jax.lax.pmean(g, "sub")
        with jax.named_scope("dd-comp-update"):
            new_params, new_opt = adam_lib.adam_update(g, opt, params, lr, self.adam_cfg)
        terms = jax.lax.pmean(terms, "sub")
        if self.telemetry:
            # post-allreduce gradient and updated (replicated) params: rows are
            # identical on every worker, matching the terms' P() out-spec
            terms = _telemetry_terms(terms, new_params, g, lr, stacked=False)
        return new_params, new_opt, err_l, terms

    def _specs(self):
        err_spec = P("sub") if self.compression else P()
        return (P(), P(), err_spec, P(), P("sub"))

    def _build_step(self):
        comp = self.compression

        def local_step(params, opt, err, step, batch: SubBatch):
            batch = jax.tree.map(lambda x: x[0], batch)
            err_l = jax.tree.map(lambda x: x[0], err) if comp is not None else err
            params, opt, err_l, terms = self._local_update(params, opt, err_l, batch)
            err_new = jax.tree.map(lambda x: x[None], err_l) if comp is not None else err
            return params, opt, err_new, step + 1, terms

        in_specs = self._specs()
        shmapped = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=in_specs[:4] + (P(),),
            check_vma=False,
        )

        @jax.jit
        def step(state, batch: SubBatch):
            p, o, e, s, terms = shmapped(
                state["params"], state["opt"], state["err"], state["step"], batch
            )
            return {"params": p, "opt": o, "err": e, "step": s}, terms

        return step

    def _build_chunk(self, steps: int):
        comp = self.compression

        def local_chunk(params, opt, err, step, batch: SubBatch):
            batch = jax.tree.map(lambda x: x[0], batch)
            err_l = jax.tree.map(lambda x: x[0], err) if comp is not None else err

            def body(carry, _):
                params, opt, err_l = carry
                params, opt, err_l, terms = self._local_update(params, opt, err_l, batch)
                return (params, opt, err_l), terms

            (params, opt, err_l), terms = jax.lax.scan(
                body, (params, opt, err_l), None, length=steps)
            err_new = jax.tree.map(lambda x: x[None], err_l) if comp is not None else err
            return params, opt, err_new, step + steps, terms

        in_specs = self._specs()
        shmapped = jax.shard_map(
            local_chunk,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=in_specs[:4] + (P(),),
            check_vma=False,
        )

        def chunk(state, batch: SubBatch):
            p, o, e, s, terms = shmapped(
                state["params"], state["opt"], state["err"], state["step"], batch
            )
            return {"params": p, "opt": o, "err": e, "step": s}, terms

        return jax.jit(chunk, donate_argnums=(0,))

    def run_chunk(self, state, batch: SubBatch, steps: int):
        """`steps` allreduce-Adam updates in ONE jitted dispatch (lax.scan with
        donated state); term leaves come back stacked (steps,)."""
        fn = self._chunk_cache.get(steps)
        if fn is None:
            fn = self._chunk_cache[steps] = self._build_chunk(steps)
        return _traced_dispatch(self, "train.run_chunk", steps,
                                lambda: fn(state, batch))

    # ------------------------------------------------------------ guarded chunk
    def _build_guarded_chunk(self, steps: int):
        comp = self.compression

        def local_chunk(params, opt, err, step, lr_scale, batch: SubBatch):
            batch = jax.tree.map(lambda x: x[0], batch)
            err_l = jax.tree.map(lambda x: x[0], err) if comp is not None else err

            def live(args):
                params, opt, err_l = args
                p, o, e, t = self._local_update(params, opt, err_l, batch,
                                                lr_scale)
                return (p, o, e), t

            nan_terms = _nan_like(jax.eval_shape(live, (params, opt, err_l))[1])

            def body(carry, _):
                args, ok, good = carry
                # params/loss are replicated after the allreduce, so every
                # worker computes the same verdict — no extra collective
                args, terms = jax.lax.cond(ok, live,
                                           lambda a: (a, nan_terms), args)
                healthy = jnp.isfinite(terms["loss"]) & jnp.isfinite(_sqnorm(args[0]))
                ok, good = ok & healthy, good + ok.astype(jnp.int32)
                if self.telemetry:
                    terms = dict(terms, step_ok=ok)
                return (args, ok, good), terms

            carry0 = ((params, opt, err_l), jnp.ones((), bool),
                      jnp.zeros((), jnp.int32))
            ((params, opt, err_l), ok, good), terms = jax.lax.scan(
                body, carry0, None, length=steps)
            err_new = jax.tree.map(lambda x: x[None], err_l) if comp is not None else err
            return params, opt, err_new, step + good, ok, good, terms

        in_specs = self._specs()[:4] + (P(), P("sub"))
        shmapped = jax.shard_map(
            local_chunk,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=self._specs()[:4] + (P(), P(), P()),
            check_vma=False,
        )

        def chunk(state, batch: SubBatch, lr_scale):
            p, o, e, s, ok, good, terms = shmapped(
                state["params"], state["opt"], state["err"], state["step"],
                lr_scale, batch,
            )
            health = {"ok": ok, "ok_sub": ok, "good_steps": good}
            return {"params": p, "opt": o, "err": e, "step": s}, terms, health

        return jax.jit(chunk, donate_argnums=(0,))

    def run_chunk_guarded(self, state, batch: SubBatch, steps: int,
                          lr_scale=None):
        """Guarded ``run_chunk``: in-graph non-finite loss/param detection with
        ``lax.cond`` freeze (see :meth:`ReferenceTrainer.run_chunk_guarded`).
        One network + replicated state means ``health["ok_sub"]`` is the scalar
        ``ok`` and ``lr_scale`` is a replicated scalar."""
        if lr_scale is None:
            lr_scale = jnp.ones(())
        fn = self._chunk_cache.get(("guarded", steps))
        if fn is None:
            fn = self._chunk_cache[("guarded", steps)] = self._build_guarded_chunk(steps)
        return _traced_dispatch(
            self, "train.run_chunk_guarded", steps,
            lambda: fn(state, batch, jnp.asarray(lr_scale, jnp.float32)))


# ------------------------------------------------------------------ checkpointing

def save_train_state(root: str, state: TrainState, keep: int = 3,
                     metadata: dict | None = None) -> str:
    """Checkpoint a trainer's :class:`TrainState` (atomic npz + manifest)."""
    from repro.checkpoint import ckpt

    tree = {"params": state.params, "opt": state.opt, "step": state.step}
    return ckpt.save(root, int(state.step), tree, metadata=metadata, keep=keep)


def restore_train_state(root: str, like: TrainState,
                        step: int | None = None) -> TrainState:
    """Restore a :class:`TrainState` saved by :func:`save_train_state`.

    ``like`` (e.g. a fresh ``trainer.init()``) fixes the pytree structure;
    restored leaves come back as committed device arrays so the result feeds
    straight into the donating ``run_chunk`` drivers.  Bitwise resume is
    asserted in ``tests/test_serve.py``.
    """
    from repro.checkpoint import ckpt

    tree, _ = ckpt.restore(
        root, {"params": like.params, "opt": like.opt, "step": like.step},
        step=step)
    tree = jax.tree.map(jnp.asarray, tree)
    return TrainState(params=tree["params"], opt=tree["opt"],
                      step=tree["step"])


# ----------------------------------------------------------------------- evaluation

def evaluate_l2(
    decomp: Decomposition,
    model_cfg: SubdomainModelConfig,
    params,
    act_codes,
    pde: PDE,
    n_pts: int = 2000,
    seed: int = 0,
    width_masks=None,
) -> float:
    """Relative L2 error of the stitched solution (eq. 4) against pde.exact.

    Runs on the serving engine: one fused network entry for ALL subdomains
    (``repro.serve.engine.FieldEngine`` — the same route -> evaluate -> stitch
    path production queries take), not a per-subdomain Python loop.  Engine
    compilations are cached process-wide, so the periodic in-training eval
    stays one dispatch per call.
    """
    from repro.serve.engine import FieldEngine
    from repro.serve.export import FieldBundle

    rng = np.random.default_rng(seed)
    m = n_pts // decomp.n_sub + 1
    pts = np.stack([decomp.sample_interior(q, m, rng)
                    for q in range(decomp.n_sub)])        # (n_sub, m, dim)
    ex = pde.exact(pts.reshape(-1, decomp.dim))
    if ex is None:
        raise ValueError("PDE has no exact solution")
    # pde stays OUT of the bundle: only u is consumed here, and a PDE without
    # the batched *_from_derivs methods (jvp-fallback-only) must still eval
    bundle = FieldBundle(model_cfg=model_cfg, params=params, decomp=decomp,
                         act_codes=np.asarray(act_codes, np.int32),
                         width_masks=width_masks, pde=None)
    # tol=0: the points are sampled strictly inside their subdomains (no
    # interface widening needed), and plain containment routing keeps custom
    # Decomposition subclasses working (tol > 0 is Cartesian/Polygon-only)
    pred = FieldEngine(bundle, tol=0.0).evaluate(pts.reshape(-1, decomp.dim),
                                                 order=1)["u"]
    e = (pred.reshape(ex.shape) - ex).ravel()
    r = ex.ravel()
    return float(np.linalg.norm(e) / (np.linalg.norm(r) + 1e-30))
