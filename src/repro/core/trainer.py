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

One step body (``_DDCommon._outer_body``) serves both domain-decomposed
trainers; each names only how a per-subdomain function maps over its layout
(``jax.vmap`` over the stacked axis, or the squeezed shard as it is) and how
the interface payloads cross (gather, or ppermute over ``"sub"``).  Each loss
evaluation enters the network exactly once: ``losses.network_eval``
megabatches residual + interface + data points, ``jax.vjp`` captures that
single forward so the exchange payload and the differentiated loss share it,
and the assembled loss's cotangents chain back through the saved VJP.

One training driver (EXPERIMENTS.md §Step fusion, §Robustness): every trainer
exposes ``run_chunk_guarded(state, batch, steps, lr_scale)`` — a ``lax.scan``
over outer steps compiled into ONE jitted dispatch with the state donated
(params/opt update in place), the exchange inside the scan body, and the
in-graph health guard of :func:`_guarded_scan`.  After each outer step the
guard checks that the per-subdomain losses and the updated parameters are
finite; once any check trips, a ``lax.cond`` freezes the carried state for the
remaining steps.  The chunk returns ``(state, terms, health)``, where
``health`` records the per-subdomain ok flags and the number of applied steps,
so the supervisor (``runtime.supervisor``) can roll back to the last good
checkpoint and retry with per-subdomain learning-rate backoff — ``lr_scale``
rides the dispatch as a plain argument, so backoff never recompiles.  A caller
without a supervisor stops on ``health["ok"] == False``.  ``step`` runs one
plain outer step; it is the per-step reference the chunk is tested against.
"""
from __future__ import annotations

import functools
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
from repro.obs.profiling import count_collective, scope
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


def _guarded_scan(live, probe, carry, ok_shape, steps, agree, norm,
                  telemetry, hoist_nan=False):
    """``steps`` guarded outer steps as one ``lax.scan`` — the health guard of
    every trainer's chunk.

    ``live(carry) -> (carry, terms)`` is one outer step; the guard runs it
    only while ``agree(ok)`` holds (``jnp.all`` over a stacked ok vector, a
    collective vote across shards, or a replicated flag as it is), then
    freezes the carry.  The live branch IS the step — same trace, same single
    network entry per loss evaluation — so guarding never adds a dispatch.
    The frozen branch's NaN terms take their shapes from ``probe`` (``live``
    without the exchange), so a trace issues (and counts) the halo once.
    They are built in the scan body, or once before the scan with
    ``hoist_nan``: the values are the same, but XLA compiles the two
    placements into different programs, so each trainer keeps the one its
    benchmark cell was measured with.  After each step, ``ok`` keeps the
    subdomains whose loss and updated parameters (``norm`` of ``carry[0]``)
    are finite; after a trip it stays as it was, so the caller sees WHICH
    subdomains diverged.  Returns
    ``((carry, ok, good_steps), terms)``; with ``telemetry`` each terms row
    also holds ``step_ok``, the ok flags after that step."""
    nan_like = lambda c: _nan_like(jax.eval_shape(probe, c)[1])
    hoisted = nan_like(carry) if hoist_nan else None

    def body(c, _):
        inner, ok, good = c
        nan_terms = hoisted if hoist_nan else nan_like(inner)
        all_ok = agree(ok)
        inner, terms = jax.lax.cond(all_ok, live, lambda a: (a, nan_terms),
                                    inner)
        # health of the step just applied: finite loss AND finite updated
        # params (catches NaN grads/moments the loss can't see yet)
        healthy = jnp.isfinite(terms["loss"]) & jnp.isfinite(norm(inner[0]))
        ok = jnp.where(all_ok, ok & healthy, ok)
        if telemetry:
            # added outside the cond so the frozen branch records too
            terms = dict(terms, step_ok=ok)
        return (inner, ok, good + all_ok.astype(jnp.int32)), terms

    carry0 = (carry, jnp.ones(ok_shape, bool), jnp.zeros((), jnp.int32))
    return jax.lax.scan(body, carry0, None, length=steps)


def _traced_dispatch(trainer, name: str, steps, call):
    """Host-side chunk span around a public ``run_chunk_guarded`` dispatch.

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

def _telemetry_terms(terms: dict, params, grads, lr, norm) -> dict:
    """Per-step metric rows riding the scan's ``terms`` output (EXPERIMENTS.md
    §Observability).  Pure arithmetic on values the step already computed —
    two parameter-tree reductions (``norm``: per subdomain on stacked trees),
    a few scalar ops — so the chunk stays ONE dispatch and the measured
    overhead is bounded at 2%:

    * ``grad_norm`` / ``param_norm`` — L2 norms of the (last local step's)
      loss gradient and the updated parameters; the early-warning signals for
      the divergences the guard trips on;
    * ``lr`` — the EFFECTIVE per-subdomain learning rate of this step
      (includes the supervisor's recovery ``lr_scale`` backoff);
    * ``iface_mismatch`` — RMS interface disagreement sqrt(MSE_avg + MSE_F/flux),
      the paper's Figs 6-9 coupling-quality axis, when the loss has interface
      terms (the data-parallel baseline has none).
    """
    t = dict(terms)
    t["grad_norm"] = jnp.sqrt(norm(grads))
    t["param_norm"] = jnp.sqrt(norm(params))
    t["lr"] = jnp.broadcast_to(jnp.asarray(lr, jnp.float32),
                               t["loss"].shape)
    if "mse_avg" in t:
        t["iface_mismatch"] = jnp.sqrt(t["mse_avg"] + t["mse_iface"])
    return t


class _DDCommon:
    """Shared setup, the one outer step and the public guarded driver of the
    domain-decomposed trainers.  A subclass names its layout: ``_per_sub``
    (how a per-subdomain function maps over it), ``_exchange`` (how the
    interface payloads cross), ``_agree`` and ``_norm`` (the guard's vote and
    parameter norm), and builds ``_step`` and ``_run_chunk_guarded``."""

    def __init__(
        self,
        pde: PDE,
        model_cfg: SubdomainModelConfig,
        topo: Topology,
        cfg: DDConfig,
        act_codes: Sequence[str | int] | None = None,
        lrs: float | Sequence[float] = 1e-3,
    ):
        self.pde, self.model_cfg, self.topo, self.cfg = pde, model_cfg, topo, cfg
        n = topo.n_sub
        self._act_codes_in = act_codes
        # optional repro.obs.Tracer: host-side chunk spans around the public
        # run_chunk_guarded dispatch (the supervisor wires its obs tracer in)
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
        self.step = jax.jit(self._step)
        self._chunk_guarded = jax.jit(self._run_chunk_guarded,
                                      static_argnums=(2,), donate_argnums=(0,))

    def init(self, seed: int = 0) -> TrainState:
        params, self.act_codes = nets.stacked_init(
            self.model_cfg, self.topo.n_sub, jax.random.PRNGKey(seed), self._act_codes_in
        )
        opt = adam_lib.init_adam(params)
        return TrainState(params=params, opt=opt, step=jnp.zeros((), jnp.int32))

    # ---- single-subdomain pieces (no stacked axis) -------------------------------
    def _net_eval(self, params, act_code, batch: SubBatch):
        """All network-dependent quantities in one entry (megabatched on the
        fused path): (res, normal-projected own payload, data_pred)."""
        return losses.network_eval(
            self.pde, self.model_cfg, self.cfg.method, params, act_code, None,
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

    # ---- the outer step ----------------------------------------------------------
    def _outer_body(self, params, opt, act_codes, lrs, batch: SubBatch,
                    exchange: bool = True):
        """One outer step (exchange + ``local_steps`` Adam updates) on this
        trainer's layout.  ONE network entry per loss evaluation: ``jax.vjp``
        captures the megabatched forward, the exchange payload is a slice of
        that SAME forward (no separate payload entry), and the assembled
        loss's cotangents chain back through the saved VJP.  ``lrs`` are the
        effective learning rates (guarded chunks scale them for recovery
        backoff).  ``exchange=False`` puts the local payload in place of the
        received one, as ``disable_exchange`` does: same shapes, no
        communication.  Returns ``(params, opt, terms)``."""
        cfg, per_sub = self.cfg, self._per_sub
        net_eval = lambda p: per_sub(self._net_eval)(p, act_codes, batch)

        def assemble(outs, recv):
            res, own, data_pred = outs
            total, terms = per_sub(self._assemble)(batch, res, own, data_pred,
                                                   recv)
            # the stacked layout sums its subdomains' losses: their gradients
            # stay separate, as each rank differentiates only its own
            return (jnp.sum(total) if total.ndim else total), terms

        # communicate once per outer step (Algorithm 1), then k local updates;
        # the exchange payload rides on inner step 1's forward
        with jax.named_scope("dd-comp-forward"):
            outs, vjp_fn = jax.vjp(net_eval, params)
        own0 = outs[1]
        if cfg.disable_exchange or not exchange:
            recv = self._maybe_stop(own0)
        else:
            recv = self._maybe_stop(self._exchange(own0))

        terms = None
        for i in range(cfg.local_steps):
            if i > 0:  # received payloads stay frozen; fresh forward on new params
                with jax.named_scope("dd-comp-forward"):
                    outs, vjp_fn = jax.vjp(net_eval, params)
            with jax.named_scope("dd-comp-update"):
                (_, terms), gouts = jax.value_and_grad(assemble, has_aux=True)(outs, recv)
                (grads,) = vjp_fn(gouts)
                params, opt = adam_lib.adam_update(grads, opt, params, lrs, cfg.adam)
        if cfg.telemetry:
            terms = _telemetry_terms(terms, params, grads, lrs, self._norm)
        return params, opt, terms

    def run_chunk_guarded(self, state: TrainState, batch: SubBatch, steps: int,
                          lr_scale=None):
        """Run ``steps`` guarded outer steps in ONE jitted dispatch.

        ``state`` is DONATED: params and optimizer buffers alias in place, so
        the caller must rebind (``state, terms, health =
        trainer.run_chunk_guarded(state, batch, n)``) and never touch the old
        state again.  Returns ``(state, terms, health)``: term leaves are
        stacked (steps, n_sub); ``health["ok_sub"]`` (n_sub,) marks
        subdomains whose loss/params went non-finite, ``health["good_steps"]``
        counts applied outer steps (the carry freezes once tripped; terms rows
        after the trip are NaN) and ``health["ok"]`` is their conjunction.
        ``lr_scale`` (n_sub,) scales the per-subdomain learning rates without
        recompiling (recovery backoff)."""
        if lr_scale is None:
            lr_scale = jnp.ones_like(self.lrs)
        return _traced_dispatch(
            self, "train.run_chunk_guarded", steps,
            lambda: self._chunk_guarded(state, batch, steps,
                                        jnp.asarray(lr_scale)))


class ReferenceTrainer(_DDCommon):
    """Single-device oracle: vmap over subdomains + gather exchange."""

    _per_sub = staticmethod(jax.vmap)
    _agree = staticmethod(jnp.all)
    _norm = staticmethod(_stacked_sqnorm)

    def _exchange(self, own):
        return halo.exchange_tree_gather(own, self.topo)

    def _live(self, carry, batch: SubBatch, lrs, exchange: bool = True):
        params, opt, step = carry
        params, opt, terms = self._outer_body(params, opt, self.act_codes, lrs,
                                              batch, exchange)
        return (params, opt, step + 1), terms

    def _step(self, state: TrainState, batch: SubBatch) -> tuple[TrainState, dict]:
        carry, terms = self._live((state.params, state.opt, state.step), batch,
                                  self.lrs)
        params, opt, step = carry
        return TrainState(params=params, opt=opt, step=step), terms

    def _run_chunk_guarded(self, state, batch, steps, lr_scale):
        lrs = self.lrs * lr_scale
        live = functools.partial(self._live, batch=batch, lrs=lrs)
        (inner, ok_sub, good), terms = _guarded_scan(
            live, functools.partial(live, exchange=False),
            (state.params, state.opt, state.step), (self.topo.n_sub,), steps,
            self._agree, self._norm, self.cfg.telemetry)
        params, opt, step = inner
        health = {"ok": jnp.all(ok_sub), "ok_sub": ok_sub, "good_steps": good}
        return TrainState(params=params, opt=opt, step=step), terms, health


class DistributedDDTrainer(_DDCommon):
    """shard_map over the ("sub",) mesh — one device per subdomain (Algorithm 1).

    Each shard runs the one outer step on its own subdomain (no leading
    axis), with ppermute as the exchange; the guard's per-step ``all_ok`` is
    a scalar ``pmin`` vote, so every shard freezes the moment ANY shard trips,
    and ``health["ok_sub"]`` comes back stitched (n_sub,)."""

    _per_sub = staticmethod(lambda f: f)
    _norm = staticmethod(_sqnorm)

    def __init__(self, *args, mesh: Mesh | None = None, **kw):
        super().__init__(*args, **kw)
        n = self.topo.n_sub
        if mesh is None:
            devs = jax.devices()
            assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
            mesh = Mesh(np.array(devs[:n]), ("sub",))
        assert mesh.shape["sub"] == n
        self.mesh = mesh

    def init(self, seed: int = 0) -> TrainState:
        state = super().init(seed)
        # per-subdomain Adam step counter so every leaf carries the stacked axis
        state.opt["count"] = jnp.zeros((self.topo.n_sub,), jnp.int32)
        return state

    def _exchange(self, own):
        return halo.exchange_tree_ppermute(own, self.topo, "sub")

    @staticmethod
    def _agree(ok):
        """Collective agreement: one scalar pmin per step — the SPMD analogue
        of the reference trainer's ``jnp.all`` over the stacked ok vector."""
        vote = ok.astype(jnp.int32)
        with scope("sync"):
            all_ok = jax.lax.pmin(vote, "sub") > 0
        count_collective("sync", "all-reduce", vote.dtype.itemsize)
        return all_ok

    def _sharded(self, local, out_specs, state: TrainState, batch: SubBatch,
                 lr_scale):
        """Run ``local(params, opt, step, act_code, lr, batch)``, written for
        ONE subdomain, as a shard_map over "sub": the per-subdomain arguments
        come in split over "sub" with the shard axis squeezed (``lr`` already
        scaled by ``lr_scale``), the step replicated.  Each output gets its
        shard axis back where its spec in ``out_specs`` puts ``"sub"``."""
        sub = P("sub")

        def shard(params, opt, step, act_code, lr, lr_scale, batch):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            out = local(sq(params), sq(opt), step, act_code[0],
                        lr[0] * lr_scale[0], sq(batch))
            return tuple(
                o if "sub" not in spec else jax.tree.map(
                    functools.partial(jnp.expand_dims, axis=spec.index("sub")), o)
                for o, spec in zip(out, out_specs))

        return jax.shard_map(shard, mesh=self.mesh,
                             in_specs=(sub, sub, P(), sub, sub, sub, sub),
                             out_specs=out_specs, check_vma=False)(
            state.params, state.opt, state.step, self.act_codes, self.lrs,
            lr_scale, batch)

    def _step(self, state: TrainState, batch: SubBatch):
        def local(params, opt, step, act_code, lr, batch):
            params, opt, terms = self._outer_body(params, opt, act_code, lr,
                                                  batch)
            return params, opt, step + 1, terms

        sub = P("sub")
        p, o, s, terms = self._sharded(local, (sub, sub, P(), sub), state,
                                       batch, jnp.ones_like(self.lrs))
        return TrainState(params=p, opt=o, step=s), terms

    def _run_chunk_guarded(self, state, batch, steps, lr_scale):
        def local(params, opt, step, act_code, lr, batch):
            def live(carry, exchange=True):
                p, o, terms = self._outer_body(*carry, act_code, lr, batch,
                                               exchange)
                return (p, o), terms

            ((params, opt), ok, good), terms = _guarded_scan(
                live, functools.partial(live, exchange=False), (params, opt),
                (), steps, self._agree, self._norm, self.cfg.telemetry,
                hoist_nan=True)
            # good is collectively agreed -> identical on all shards (out P());
            # term leaves are (steps,), the shard axis goes SECOND so the
            # stitched result is (steps, n_sub)
            return params, opt, step + good, ok, good, terms

        sub = P("sub")
        p, o, s, ok, good, terms = self._sharded(
            local, (sub, sub, P(), sub, P(), P(None, "sub")), state, batch,
            lr_scale)
        health = {"ok": jnp.all(ok), "ok_sub": ok, "good_steps": good}
        return TrainState(params=p, opt=o, step=s), terms, health

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
        self._chunk_guarded = jax.jit(self._run_chunk_guarded,
                                      static_argnums=(2,), donate_argnums=(0,))
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
            terms = _telemetry_terms(terms, new_params, g, lr, _sqnorm)
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

    def _run_chunk_guarded(self, state, batch, steps, lr_scale):
        comp = self.compression

        def local_chunk(params, opt, err, step, lr_scale, batch: SubBatch):
            batch = jax.tree.map(lambda x: x[0], batch)
            err_l = jax.tree.map(lambda x: x[0], err) if comp is not None else err

            def live(args):
                params, opt, err_l = args
                p, o, e, t = self._local_update(params, opt, err_l, batch,
                                                lr_scale)
                return (p, o, e), t

            # params/loss are replicated after the allreduce, so every worker
            # computes the same verdict — no extra collective
            ((params, opt, err_l), ok, good), terms = _guarded_scan(
                live, live, (params, opt, err_l), (), steps, lambda ok: ok,
                _sqnorm, self.telemetry, hoist_nan=True)
            err_new = jax.tree.map(lambda x: x[None], err_l) if comp is not None else err
            return params, opt, err_new, step + good, ok, good, terms

        in_specs = self._specs()[:4] + (P(), P("sub"))
        p, o, e, s, ok, good, terms = jax.shard_map(
            local_chunk,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=self._specs()[:4] + (P(), P(), P()),
            check_vma=False,
        )(state["params"], state["opt"], state["err"], state["step"],
          lr_scale, batch)
        health = {"ok": ok, "ok_sub": ok, "good_steps": good}
        return {"params": p, "opt": o, "err": e, "step": s}, terms, health

    def run_chunk_guarded(self, state, batch: SubBatch, steps: int,
                          lr_scale=None):
        """The guarded chunk (see :meth:`_DDCommon.run_chunk_guarded`) of the
        data-parallel baseline.  One network + replicated state means
        ``health["ok_sub"]`` is the scalar ``ok`` and ``lr_scale`` is a
        replicated scalar."""
        if lr_scale is None:
            lr_scale = jnp.ones(())
        return _traced_dispatch(
            self, "train.run_chunk_guarded", steps,
            lambda: self._chunk_guarded(state, batch, steps,
                                        jnp.asarray(lr_scale, jnp.float32)))


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
    straight into the donating guarded chunk.  Bitwise resume is
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
                         pde=None)
    # tol=0: the points are sampled strictly inside their subdomains (no
    # interface widening needed), and plain containment routing keeps custom
    # Decomposition subclasses working (tol > 0 is Cartesian/Polygon-only)
    pred = FieldEngine(bundle, tol=0.0).evaluate(pts.reshape(-1, decomp.dim),
                                                 order=1)["u"]
    e = (pred.reshape(ex.shape) - ex).ravel()
    r = ex.ravel()
    return float(np.linalg.norm(e) / (np.linalg.norm(r) + 1e-30))
