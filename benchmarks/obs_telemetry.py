"""Observability acceptance: telemetry overhead, retrace flatness, JSONL schema.

Three claims of EXPERIMENTS.md §Observability, measured and enforced:

* **telemetry overhead <= 2%** — the in-graph per-step metric rows
  (grad/param norms, interface mismatch, lr, guard flags) ride the scanned
  chunk's stacked outputs; the guarded chunk with ``telemetry=True`` must
  stay within 2% of the plain guarded chunk (round-robin + paired-ratio
  idiom of ``benchmarks.common``, so CPU-quota drift cancels);
* **retrace flatness** — once warmed, serve batch-size buckets, the per-step
  reference and the guarded chunk, and ``lr_scale`` changes must all dispatch
  with ZERO new backend compiles (``repro.obs.CompileWatcher``; a cache-hit
  dispatch emits no compile events, so the assertion is a flat line, not a
  heuristic);
* **JSONL schema** — a supervised training run with an attached event log
  must produce a stream that passes ``repro.obs.validate_events`` (manifest
  first, schema version match, typed required fields); a malformed stream
  must FAIL validation.  Wired into ``benchmarks/run.py --smoke``: a broken
  schema breaks CI.

Writes ``benchmarks/results/obs_telemetry.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Burgers1D, CartesianDecomposition, DDConfig,
                        ReferenceTrainer, XPINN, build_topology)
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.obs import (CompileWatcher, ObsSchemaError, make_obs,
                       validate_events)
from repro.runtime import Fault, FaultInjector, Supervisor, SupervisorConfig

from benchmarks.common import (_interleaved, _med, _paired_ratio, emit,
                               save_json)

OVERHEAD_BOUND_PCT = 2.0


def _workload(n_res=1000, width=24, depth=4, telemetry=False):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=20)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    b = make_batch(dec, topo, pde, n_res=n_res, n_bnd=80,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, telemetry=telemetry),
                          lrs=2e-3)
    return pde, dec, cfg, b, tr


# ------------------------------------------------------------------ overhead

def overhead_rows(iters: int = 10, smoke: bool = False):
    """Guarded chunk with telemetry rows vs without, paired round-robin.
    Enforces the <= 2% acceptance bound (full mode; smoke reports only —
    a 20-step smoke chunk is too noisy for a hard 2% gate)."""
    n_res, chunk = (250, 20) if smoke else (1000, 100)
    _, _, _, b, tr_off = _workload(n_res=n_res, telemetry=False)
    _, _, _, _, tr_on = _workload(n_res=n_res, telemetry=True)
    fns = {
        "plain": lambda _: tr_off.run_chunk_guarded(tr_off.init(0), b, chunk),
        "telemetry": lambda _: tr_on.run_chunk_guarded(tr_on.init(0), b, chunk),
    }
    t = _interleaved(fns, None, iters)
    ratio = _paired_ratio(t["telemetry"], t["plain"])
    pct = (ratio - 1.0) * 100.0
    rows = [
        ("obs/telemetry_chunk_ms", round(_med(t["telemetry"]) / 1e3, 2), "ms"),
        ("obs/plain_chunk_ms", round(_med(t["plain"]) / 1e3, 2), "ms"),
        ("obs/telemetry_overhead", round(pct, 2), "%"),
    ]
    if not smoke and not pct <= OVERHEAD_BOUND_PCT:
        raise AssertionError(
            f"telemetry overhead {pct:.2f}% exceeds the "
            f"{OVERHEAD_BOUND_PCT}% acceptance bound")
    detail = {"plain_ms": round(_med(t["plain"]) / 1e3, 3),
              "telemetry_ms": round(_med(t["telemetry"]) / 1e3, 3),
              "paired_ratio": round(ratio, 4),
              "overhead_pct": round(pct, 2),
              "acceptance_bound_pct": OVERHEAD_BOUND_PCT}
    return rows, detail


def trace_overhead_rows(iters: int = 10, smoke: bool = False):
    """Causal-tracer overhead, paired round-robin: (a) the guarded training
    chunk with the trainer's dispatch span on vs off (same jitted program —
    only the host-side span wrapper differs) and (b) the serve hot path
    (frontend submit -> microbatch -> engine -> result) with a tracer-carrying
    Obs vs a bare frontend.  Enforces the <= 2% acceptance bound in full mode
    (smoke reports only; sub-ms smoke dispatches are too noisy to gate)."""
    from repro.obs import MetricsRegistry, Obs, Tracer
    from repro.serve.engine import FieldEngine
    from repro.serve.export import FieldBundle
    from repro.serve.frontend import ServeFrontend

    n_res, chunk = (250, 20) if smoke else (1000, 100)
    _, dec, cfg, b, tr = _workload(n_res=n_res)
    tracer = Tracer()

    def chunk_run(traced):
        tr.tracer = tracer if traced else None
        out = tr.run_chunk_guarded(tr.init(0), b, chunk)
        tr.tracer = None
        return out

    t = _interleaved({"plain": lambda _: chunk_run(False),
                      "traced": lambda _: chunk_run(True)}, None, iters)
    chunk_ratio = _paired_ratio(t["traced"], t["plain"])
    chunk_pct = (chunk_ratio - 1.0) * 100.0

    # serve path: one bundle, two frontends — bare vs tracer-carrying Obs;
    # caches disabled so every round pays the full admission->dispatch path
    state = tr.init(0)
    bundle = FieldBundle(model_cfg=cfg, params=state.params, decomp=dec,
                         act_codes=np.zeros((4,), np.int32), pde=None)
    rng = np.random.default_rng(0)
    cloud = rng.uniform((-1, 0), (1, 1), size=(500, 2))

    def mk_frontend(traced):
        obs = (Obs(registry=MetricsRegistry(), events=None, tracer=tracer)
               if traced else None)
        eng = FieldEngine(bundle, tol=0.0, obs=obs)
        return ServeFrontend(eng, order=1, cache_size=0, obs=obs)

    fes = {False: mk_frontend(False), True: mk_frontend(True)}
    for fe in fes.values():
        fe.result(fe.submit(cloud))           # warm the compile cache

    def serve_run(traced):
        fe = fes[traced]
        return fe.result(fe.submit(cloud))

    t2 = _interleaved({"plain": lambda _: serve_run(False),
                       "traced": lambda _: serve_run(True)}, None,
                      max(iters, 5))
    serve_ratio = _paired_ratio(t2["traced"], t2["plain"])
    serve_pct = (serve_ratio - 1.0) * 100.0

    rows = [
        ("obs/trace/chunk_overhead", round(chunk_pct, 2), "%"),
        ("obs/trace/serve_overhead", round(serve_pct, 2), "%"),
        ("obs/trace/spans_recorded", tracer.stats()["spans_recorded"], ""),
    ]
    if not smoke:
        for name, pct in (("chunk", chunk_pct), ("serve", serve_pct)):
            if not pct <= OVERHEAD_BOUND_PCT:
                raise AssertionError(
                    f"tracer {name} overhead {pct:.2f}% exceeds the "
                    f"{OVERHEAD_BOUND_PCT}% acceptance bound")
    detail = {"chunk_paired_ratio": round(chunk_ratio, 4),
              "chunk_overhead_pct": round(chunk_pct, 2),
              "serve_paired_ratio": round(serve_ratio, 4),
              "serve_overhead_pct": round(serve_pct, 2),
              "acceptance_bound_pct": OVERHEAD_BOUND_PCT}
    return rows, detail


# ------------------------------------------------------------------ flatness

def retrace_rows():
    """Flat-line compile assertions: serve batch buckets, guarded/unguarded
    chunks, lr_scale changes.  Every case warms first, then asserts ZERO
    backend compiles across the varied dispatches."""
    from repro.serve.engine import FieldEngine
    from repro.serve.export import FieldBundle

    _, dec, cfg, b, tr = _workload(n_res=64, width=16, depth=2)
    state = tr.init(0)
    rows = []

    # (a) serve batch buckets: clouds of different sizes map to padded bucket
    # shapes; after one warm pass per bucket, traffic must never recompile
    bundle = FieldBundle(model_cfg=cfg, params=state.params, decomp=dec,
                         act_codes=np.zeros((4,), np.int32), pde=None)
    eng = FieldEngine(bundle, tol=0.0)
    rng = np.random.default_rng(0)
    clouds = [rng.uniform((-1, 0), (1, 1), size=(n, 2))
              for n in (16, 100, 500)]
    for c in clouds:
        eng.evaluate(c, order=1)                      # warm each bucket
    with CompileWatcher() as w_serve:
        for _ in range(3):
            for c in clouds:
                eng.evaluate(c, order=1)
    rows.append(("obs/retrace/serve_buckets_compiles",
                 w_serve.backend_compiles, ""))

    # (b) the per-step reference and the guarded chunk: both warmed, then
    # interleaved
    st = tr.step(tr.init(0), b)[0]
    st2, _t, _h = tr.run_chunk_guarded(tr.init(0), b, 3)
    with CompileWatcher() as w_chunk:
        st = tr.step(st, b)[0]
        st2 = tr.run_chunk_guarded(st2, b, 3)[0]
    rows.append(("obs/retrace/chunk_guard_compiles",
                 w_chunk.backend_compiles, ""))

    # (c) lr_scale rides the dispatch as a plain argument: changing it must
    # never recompile (the supervisor's backoff guarantee, now asserted)
    with CompileWatcher() as w_lr:
        for s in (1.0, 0.5, 0.25, 0.125):
            st2 = tr.run_chunk_guarded(st2, b, 3,
                                       lr_scale=jnp.full((4,), s))[0]
    rows.append(("obs/retrace/lr_scale_compiles", w_lr.backend_compiles, ""))

    for name, n, _u in rows:
        if n != 0:
            raise AssertionError(f"{name}: expected 0 backend compiles, "
                                 f"got {n} — retrace storm")
    return rows


# ----------------------------------------------------------------- jsonl/smoke

def jsonl_rows():
    """Supervised run with an attached JSONL event log; the stream must pass
    schema validation (and a corrupted stream must fail it)."""
    _, dec, _, b, tr = _workload(n_res=250, telemetry=True)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "obs.jsonl")
        obs = make_obs(path, run_id="obs-smoke",
                       config={"workload": "quickstart 2x2 Burgers XPINN"})
        sup = Supervisor(tr, os.path.join(d, "ckpt"),
                         SupervisorConfig(chunk_steps=20),
                         FaultInjector([Fault(chunk=1, kind="nan_params",
                                              subdomain=0)]),
                         decomp=dec, obs=obs)
        _st, rep = sup.run(tr.init(0), b, 60)
        obs.emit("metrics", snapshot=obs.registry.snapshot())
        obs.close()

        manifest = validate_events(path)      # raises ObsSchemaError on breakage
        events = [json.loads(ln) for ln in open(path)]
        kinds = {e["kind"] for e in events}
        for needed in ("manifest", "chunk", "guard_trip", "rollback",
                       "metrics"):
            if needed not in kinds:
                raise AssertionError(
                    f"obs smoke: expected a {needed!r} event in the stream, "
                    f"got kinds {sorted(kinds)}")

        # negative control: a corrupted stream must FAIL validation
        bad = os.path.join(d, "bad.jsonl")
        lines = open(path).read().splitlines()
        broken = json.loads(lines[1])
        broken.pop("t", None)                 # strip the required timestamp
        lines[1] = json.dumps(broken)
        with open(bad, "w") as f:
            f.write("\n".join(lines) + "\n")
        try:
            validate_events(bad)
        except ObsSchemaError:
            pass
        else:
            raise AssertionError("obs smoke: corrupted stream passed "
                                 "schema validation")

    return [
        ("obs/jsonl/events", len(events), ""),
        ("obs/jsonl/schema_version", manifest["schema_version"], ""),
        ("obs/jsonl/guard_trips", rep.guard_trips, ""),
        ("obs/jsonl/malformed_rejected", 1, "bool"),
    ]


def smoke_rows():
    """CI-fast acceptance for ``run.py --smoke``: overhead measurement (report
    only), flat-line retrace assertions, schema-validated JSONL."""
    rows, _detail = overhead_rows(iters=3, smoke=True)
    rows += trace_overhead_rows(iters=3, smoke=True)[0]
    rows += retrace_rows()
    rows += jsonl_rows()
    return rows


def run(iters: int = 10, smoke: bool = False):
    rows, detail = overhead_rows(iters=iters, smoke=smoke)
    t_rows, t_detail = trace_overhead_rows(iters=iters, smoke=smoke)
    rows += t_rows
    rows += retrace_rows()
    rows += jsonl_rows()
    save_json("obs_telemetry.json", {
        "backend": jax.default_backend(), "iters": iters,
        "telemetry_overhead": detail,
        "trace_overhead": t_detail,
        "retrace": "all flat (asserted zero backend compiles)",
    })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    emit(run(iters=args.iters, smoke=args.smoke))


if __name__ == "__main__":
    main()
