"""Profiling hooks: named scopes, the compile tracer, comp-vs-comm split.

Three tools that turn the repo's recurring forensic questions into one-line
assertions:

* **named-scope annotation scheme** — :func:`scope` extends the PR-4
  ``pinn2-bwd-*`` convention to the whole chunk driver: communication is
  bracketed ``dd-comm-halo`` (the ppermute/gather interface exchange), compute
  ``dd-comp-forward`` / ``dd-comp-update`` (megabatched network entry + loss
  backward + Adam).  The scopes land in compiled-HLO ``op_name`` metadata, so
  tests and the comp/comm splitter can attribute ops by phase
  (:func:`repro.utils.hlo.named_scope_counts`) instead of guessing.  The same
  table names every Pallas kernel launch;

* **compile tracer** — :class:`CompileWatcher` keeps the intervals JAX
  reports for each compile phase (trace, lower, backend compile or
  persistent-cache load) with the function each one works on, so set-up
  time splits by phase and by function, and can land as spans on a
  :class:`~repro.obs.tracing.Tracer`.  Cache-hit dispatches of a jitted
  function emit ZERO events, so "no retracing across batch buckets /
  lr_scale changes / guarded chunks" is a flat-line assertion:
  ``watcher.backend_compiles == 0``;

* **comp-vs-comm walltime splitter** — :func:`comp_comm_split` times the full
  chunk (ppermute halo exchange inside the scan body) against the
  exchange-ablated chunk (``disable_exchange=True`` replaces comm with the
  local payload, keeping compute identical) in INTERLEAVED rounds with paired
  per-round statistics — the drift-robust protocol every benchmark here uses —
  and reports comp/comm/total per step.  :func:`halo_traffic` complements the
  walltime split with the analytic per-device collective-permute bytes parsed
  from the compiled chunk HLO (:mod:`repro.utils.hlo`), i.e. the paper's
  O(N_iface) communication-cost argument, measured.
"""
from __future__ import annotations

import re
import time
from collections import defaultdict

import jax
import numpy as np

# The annotation scheme: one stable name per phase.  Keys are the phase
# vocabulary ("comm", "comp_forward", ...), values the HLO-visible scope
# names.  ``comm`` brackets the halo's collective-permutes and ``sync`` the
# guard's per-step all-reduce (the shards' agreement to freeze).  The
# ``kernel_*`` names (and ``bwd_fused``) are also the Pallas
# launches' own names (``kernels/pinn_mlp.py``): each compiled custom call
# is ``%<name>.N`` in the HLO and in the device trace, whatever jit / vmap /
# jvp wraps it.  The training forward's name keeps ``pinn_mlp_forward2`` and
# the serving forwards' names leave it out, which is what the benchmark's
# kernel readers match on.
SCOPES = {
    "comm": "dd-comm-halo",
    "sync": "dd-comm-agree",
    "comp_forward": "dd-comp-forward",
    "comp_update": "dd-comp-update",
    "kernel_res": "pinn_mlp_forward2-res",
    "bwd_fused": "pinn2-bwd-fused",
    "kernel_eval2": "pinn2-eval",
    "kernel_eval1": "pinn1-eval",
    "bwd_ref": "pinn2-bwd-ref",
    "bwd_fused_select": "pinn2-bwd-fused-select",
}


def scope(phase: str):
    """``with scope("comm"): ...`` — named scope from the phase vocabulary
    (unknown phases raise: the scheme only works if names stay canonical)."""
    try:
        return jax.named_scope(SCOPES[phase])
    except KeyError:
        raise ValueError(f"unknown profiling phase {phase!r}; "
                         f"known: {sorted(SCOPES)}") from None


# ------------------------------------------------------- compile tracer

# JAX's compile path reports each phase as a time span (``time.time()``
# seconds, with the ``fun_name`` it works on) and the persistent cache's
# look-ups as plain events inside the backend phase.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
}
# lower and backend name the module (``jit(<fun>)``); trace names the function
_MODULE_NAME = re.compile(r"^(?:jit|pmap)\((.*)\)$")
_counts: dict[str, int] = defaultdict(int)
_seconds: dict[str, float] = defaultdict(float)
_cache_marks: list[tuple[str, float]] = []   # since the last backend span
_active: list["CompileWatcher"] = []
_installed = False


def _fun(phase: str, name: str) -> str:
    m = _MODULE_NAME.match(name) if phase != "trace" else None
    return m.group(1) if m else name


def _on_span(event: str, start: float, end: float, fun_name: str = "?",
             **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    cache = None
    if phase == "backend":
        marks = [k for k, t in _cache_marks if start <= t <= end]
        _cache_marks.clear()
        if "hit" in marks:
            cache = "hit"
        elif "asked" in marks and jax.config.jax_compilation_cache_dir:
            cache = "miss"   # JAX asks even with no cache directory set
    _counts[phase] += 1
    _seconds[phase] += end - start
    iv = (phase, _fun(phase, fun_name), start, end, cache)
    for w in _active:
        w._add(iv)


def _on_event(event: str, **_kw) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is not None:
        _cache_marks.append((kind, time.time()))


def _install() -> None:
    """Register the process-wide listeners once (a single pair that feeds
    the process totals and every active watcher, so no watcher ever needs
    to unregister)."""
    global _installed
    if _installed:
        return
    import jax.monitoring as monitoring

    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_listener(_on_event)
    _installed = True


def compile_counts() -> dict:
    """Process-lifetime compile/trace counts since the first watcher or
    call here (monotone; use :class:`CompileWatcher` for scoped deltas)."""
    _install()
    return {"backend_compiles": _counts["backend"],
            "traces": _counts["trace"],
            "compile_seconds": round(_seconds["backend"], 6)}


_launches: dict[tuple[str, str], int] = defaultdict(int)


def count_launch(phase: str, layout: str) -> None:
    """Count one trace of a Pallas PINN launch (``phase`` from ``SCOPES``)
    under its stream layout: ``"<n>_per_tile"`` for the second-order
    kernels (n streams stacked on the sublanes of each tile,
    ``kernels.pinn_mlp.Layout``) or ``"first_order"``.  Trace time only, so
    a step that runs a compiled program costs nothing."""
    _launches[(phase, layout)] += 1


def launch_counts() -> dict:
    """Process-lifetime traced Pallas PINN launches by layout, and by
    ``phase/layout`` (monotone, like :func:`compile_counts`)."""
    out: dict[str, int] = defaultdict(int)
    for (phase, layout), n in sorted(_launches.items()):
        out[layout] += n
        out[f"{phase}/{layout}"] = n
    return dict(out)


_collectives: dict[str, list[int]] = defaultdict(lambda: [0, 0])


def count_collective(phase: str, opcode: str, nbytes: int) -> None:
    """Count one trace of a collective the program issues under the scope
    ``SCOPES[phase]``: ``opcode`` is its HLO opcode (``collective-permute``,
    ``all-reduce``) and ``nbytes`` the payload each device sends.  Trace
    time only, like :func:`count_launch`."""
    c = _collectives[f"{SCOPES[phase]}/{opcode}"]
    c[0] += 1
    c[1] += int(nbytes)


def collective_counts() -> dict:
    """Process-lifetime traced collectives keyed ``"<scope>/<opcode>"``,
    each ``{"ops": n, "bytes": bytes per device}`` (monotone, like
    :func:`launch_counts`)."""
    return {k: {"ops": n, "bytes": b}
            for k, (n, b) in sorted(_collectives.items())}


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals: a nested jit's
    trace lies inside its parent's, so summing would count it twice."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


class CompileWatcher:
    """The program's compile tracer: ``with CompileWatcher() as w: ...``.

    Keeps every interval of the three compile phases that JAX reports inside
    the block (``w.intervals``: ``(phase, fun, start, end, cache)`` on
    ``time.time()``), with ``phase`` one of ``trace`` (Python tracing to a
    jaxpr; JAX reports one on every dispatch that leaves its C++ fast path,
    even where the jaxpr comes from its cache), ``lower`` (jaxpr to MLIR,
    Pallas to Mosaic included) and ``backend`` (XLA compile, or the
    persistent cache's load, which ``cache`` tags ``"hit"``; ``"miss"``
    where the cache was asked and compiled anyway, None where it is off).

    ``w.seconds(phase)`` is the union of that phase's intervals;
    ``w.by_fun[kind]`` counts ``trace``, ``lower``, ``backend``,
    ``cache_hit`` and ``cache_miss`` per ``fun`` (the jitted function's
    name; the ``jit(...)`` around a module's name is dropped).
    ``backend_compiles``, ``traces`` and ``compile_seconds`` are the
    totals.  A cache-hit dispatch of a jitted function reports nothing, so
    ``w.backend_compiles == 0`` over a serving loop IS the no-retrace-storm
    regression test.

    ``tracer`` (a :class:`repro.obs.tracing.Tracer`): each interval is also
    committed as a retrospective span ``compile.<phase>`` with
    ``fun=<fun>`` (and ``cache=`` on backend spans), moved onto the
    tracer's clock by one offset taken at entry, under the span active when
    JAX reports it.  ``registry`` mirrors the two counts into
    ``obs.compile/*`` counters and ``events`` emits one ``compile`` event
    at exit.
    """

    def __init__(self, registry=None, events=None, tracer=None):
        _install()
        self._registry, self._events, self._tracer = registry, events, tracer
        self.intervals: list[tuple] = []
        self._offset = 0.0

    def _add(self, iv) -> None:
        self.intervals.append(iv)
        tr = self._tracer
        if tr is not None:
            phase, fun, a, b, cache = iv
            attrs = {"fun": fun}
            if cache is not None:
                attrs["cache"] = cache
            tr.record("compile." + phase, a + self._offset, b + self._offset,
                      parent=tr.active(), lane="compile", **attrs)

    # ----------------------------------------------------------- reading
    def seconds(self, phase: str) -> float:
        return union_seconds((a, b) for p, _f, a, b, _c in self.intervals
                             if p == phase)

    @property
    def by_fun(self) -> dict:
        out = {k: defaultdict(int) for k in
               ("trace", "lower", "backend", "cache_hit", "cache_miss")}
        for phase, fun, _a, _b, cache in self.intervals:
            out[phase][fun] += 1
            if cache is not None:
                out["cache_" + cache][fun] += 1
        return {k: dict(v) for k, v in out.items()}

    @property
    def traces(self) -> int:
        return sum(1 for iv in self.intervals if iv[0] == "trace")

    @property
    def backend_compiles(self) -> int:
        return sum(1 for iv in self.intervals if iv[0] == "backend")

    @property
    def compile_seconds(self) -> float:
        return self.seconds("backend")

    # ---------------------------------------------------------- lifecycle
    def __enter__(self):
        if self._tracer is not None:
            self._offset = self._tracer.clock() - time.time()
        _active.append(self)
        return self

    def __exit__(self, *exc):
        _active.remove(self)
        if self._registry is not None:
            g = self._registry.group("obs.compile",
                                     ("backend_compiles", "traces"))
            g["backend_compiles"] += self.backend_compiles
            g["traces"] += self.traces
        if self._events is not None:
            self._events.emit("compile", backend_compiles=self.backend_compiles,
                              traces=self.traces,
                              compile_seconds=round(self.compile_seconds, 6))
        return False


# ------------------------------------------------------------- comp/comm split

def comp_comm_split(run_total, run_comp_only, iters: int = 5,
                    warmup: int = 1, steps: int = 1,
                    clock=time.perf_counter, tracer=None) -> dict:
    """Wall-time comp-vs-comm split of a chunked training step.

    ``run_total`` runs one chunk WITH the halo exchange; ``run_comp_only``
    runs the identical chunk with the exchange ablated
    (``DDConfig.disable_exchange=True``: the loss consumes the local payload,
    so compute is identical and the difference is the communication term —
    the paper's Fig-6 protocol).  Both callables must block until ready and
    handle their own state rebinding (donated buffers).

    Timed in interleaved rounds (total, comp, total, comp, ...) so the
    container's CPU-quota drift hits both paths equally; ``comm`` is the
    median of PAIRED per-round differences, floored at 0 (a noisy round can
    go negative).  ``steps`` divides everything down to per-step seconds.

    ``tracer`` (optional :class:`repro.obs.tracing.Tracer`): each timed round
    lands as a ``train.ablation`` trace with ``train.total`` /
    ``train.comp_only`` child spans, so the comp/comm split is visible on the
    Perfetto timeline next to the chunk spans it explains.
    """
    for _ in range(max(warmup, 1)):
        run_total()
        run_comp_only()
    t_tot, t_comp = [], []
    for i in range(iters):
        root = (tracer.start_trace("train.ablation", lane="train", round=i)
                if tracer is not None else None)
        t0 = clock()
        run_total()
        t1 = clock()
        t_tot.append(t1 - t0)
        t2 = clock()
        run_comp_only()
        t3 = clock()
        t_comp.append(t3 - t2)
        if root is not None:
            tracer.record("train.total", t0, t1, parent=root, round=i)
            tracer.record("train.comp_only", t2, t3, parent=root, round=i)
            root.end()
    tot, comp = np.asarray(t_tot), np.asarray(t_comp)
    comm = float(np.median(tot - comp))
    return {
        "total_s": float(np.median(tot)) / steps,
        "comp_s": float(np.median(comp)) / steps,
        "comm_s": max(0.0, comm) / steps,
        "comm_frac": max(0.0, comm) / max(float(np.median(tot)), 1e-30),
        "rounds": int(iters),
    }


def halo_traffic(hlo_text: str) -> dict:
    """Analytic per-device halo-exchange traffic of a compiled chunk: the
    collective-permute byte/op accounting (:mod:`repro.utils.hlo`) plus the
    named-scope attribution — how many collective ops sit under the
    ``dd-comm-halo`` scope (all of them, if the annotation scheme holds)."""
    from repro.utils import hlo as hlo_lib

    coll = hlo_lib.collective_bytes(hlo_text)
    scopes = hlo_lib.named_scope_counts(hlo_text, prefix="dd-")
    return {
        "collective_permute_ops": coll["counts"].get("collective-permute", 0),
        "collective_permute_bytes":
            coll["bytes_by_kind"].get("collective-permute", 0.0),
        "total_collective_bytes": coll["total_bytes"],
        "scope_op_counts": scopes,
    }
