"""Causal-trace observatory: end-to-end span-tree exports, validated.

Two acceptance runs of EXPERIMENTS.md §Tracing, wired into
``benchmarks/run.py --smoke`` (and ``--only trace``):

* **serve** — a us_map 10-region bundle behind the full resilient stack
  (:class:`~repro.serve.resilience.ResilientFrontend`) with an injected
  flaky engine, so the exported trace shows the interesting hops: admission,
  microbatch packing, engine eval, quarantine, retry, ladder degrade, cache
  hits.  Every ticket's :class:`ServeResult` carries the trace_id of ONE
  root span whose subtree records the whole lifecycle;

* **train** — a 4-subdomain supervised run (crash + NaN faults) under the
  :class:`~repro.runtime.Supervisor`: one ``train.chunk`` root per attempt
  with the trainer dispatch span plus rollback/recovery children nested
  under it, fanned out to per-subdomain lanes with halo-exchange flow
  arrows (byte-weighted by the analytic ``halo_traffic`` HLO parse in full
  mode; an ``n_iface``-scaled estimate in smoke, labeled as such).

Both exports go through :func:`repro.obs.export_chrome_trace`, which
validates the Chrome trace-event structural contract (matched B/E pairs,
monotone timestamps, finished flows) BEFORE writing — a malformed trace
fails the benchmark, not the Perfetto import three weeks later.
"""
from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "src"))

import numpy as np

from repro.core import (Burgers1D, CartesianDecomposition, DDConfig,
                        ReferenceTrainer, XPINN, build_topology)
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.obs import make_obs
from repro.obs.trace_export import export_chrome_trace, training_timeline
from repro.runtime import Fault, FaultInjector, Supervisor, SupervisorConfig
from repro.serve import ResilienceConfig, ResilientFrontend

from benchmarks.common import BENCH_OUT, RESULTS, emit, run_worker

# analytic halo parse of the compiled 4-device fused chunk (full mode): one
# lowering, no timed rounds — the bytes weight the timeline's flow arrows
HALO_WORKER = """
import json
import numpy as np
from repro.core import *
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.obs import halo_traffic

pde = Burgers1D()
dec = CartesianDecomposition(((-1, 1), (0, 1)), 4, 1)
topo = build_topology(dec, 20)
cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 20, 5)})
b = make_batch(dec, topo, pde, 200, 20, np.random.default_rng(0)).device_arrays()
tr = DistributedDDTrainer(pde, cfg, topo, DDConfig(method=XPINN), lrs=1e-3)
hlo = tr._chunk_guarded.lower(tr.shard_state(tr.init(0)), tr.shard_batch(b), 4,
                              np.ones(4, np.float32)).compile().as_text()
print("RESULT:" + json.dumps(halo_traffic(hlo)))
"""


def _out_path(name: str, smoke: bool) -> str:
    d = BENCH_OUT if smoke else RESULTS
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{name}{'_smoke' if smoke else ''}.json")


# ----------------------------------------------------------------- serve run

class _FlakyEngine:
    """Engine proxy failing every ``period``-th dispatch: drives the retry/
    degrade hops the serve trace is supposed to record."""

    def __init__(self, engine, period: int = 4):
        self.engine, self.period, self.n = engine, period, 0

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def evaluate(self, pts, order: int = 2):
        self.n += 1
        if self.n % self.period == 0:
            raise RuntimeError(f"injected engine fault #{self.n}")
        return self.engine.evaluate(pts, order=order)


def serve_trace_rows(smoke: bool = False):
    from benchmarks.serve_throughput import _bundle, _grid

    bundle = _bundle()
    obs = make_obs(None, trace=True)
    from repro.serve import FieldEngine

    engine = FieldEngine(bundle, obs=obs)
    now = [0.0]
    fe = ResilientFrontend(
        _FlakyEngine(engine), ResilienceConfig(retry_backoff=0.01, order=2),
        clock=lambda: now[0],
        sleep=lambda s: now.__setitem__(0, now[0] + s), obs=obs)
    rng = np.random.default_rng(0)
    n_req = 12 if smoke else 48
    dashboard = _grid(64, bundle.decomp)
    tickets = []
    for i in range(n_req):
        pts = (dashboard if i % 3 == 0 else
               rng.uniform([-0.5, -0.5], [0.5, 0.5], size=(32, 2)))
        tickets.append(fe.submit(pts))
        now[0] += 0.01
        fe.poll()
    fe.drain()
    results = [fe.result(t) for t in tickets]
    tids = {r.trace_id for r in results}
    assert None not in tids and len(tids) == n_req, \
        "every ticket must carry its own trace_id"
    path = _out_path("trace_serve", smoke)
    report = export_chrome_trace(path, obs.tracer.spans(),
                                 process_name="serve_observatory")
    st = obs.tracer.stats()
    assert st["traces"] == n_req and st["spans_evicted"] == 0
    print(f"[trace_observatory] wrote {path}", file=sys.stderr)
    return [
        ("trace/serve/requests", n_req, ""),
        ("trace/serve/span_pairs", report["span_pairs"], ""),
        ("trace/serve/hop_instants", report["instants"], ""),
        ("trace/serve/lanes", report["lanes"], ""),
    ]


# ----------------------------------------------------------------- train run

def train_trace_rows(smoke: bool = False):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=20)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 16, 2)})
    b = make_batch(dec, topo, pde, n_res=64 if smoke else 250, n_bnd=16,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, residual_path="pallas"))
    obs = make_obs(None, trace=True)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        sup = Supervisor(tr, os.path.join(d, "ckpt"),
                         SupervisorConfig(chunk_steps=3),
                         FaultInjector([Fault(1, "crash"),
                                        Fault(3, "nan_params", subdomain=0)]),
                         obs=obs)
        _st, rep = sup.run(tr.init(0), b, total_steps=5 * 3)
    assert rep.crashes == 1 and rep.guard_trips == 1 and rep.chunks == 5

    if smoke:
        # analytic estimate: one f32 "u" halo payload per interface point per
        # directed edge — labeled estimate, NOT the HLO parse (that needs the
        # 4-device distributed lowering; full mode does it in a subprocess)
        halo = {"collective_permute_bytes": 20 * 4, "estimated": True}
    else:
        halo = run_worker(HALO_WORKER, n_devices=4)
    spans = obs.tracer.spans()
    chunks = [s for s in spans if s.name == "train.chunk"]
    lane_spans, flows = training_timeline(chunks, topo, halo=halo)
    path = _out_path("trace_train", smoke)
    report = export_chrome_trace(path, list(spans) + lane_spans, flows=flows,
                                 process_name="train_observatory")
    assert report["flows"] > 0, "expected halo flow arrows"
    assert report["lanes"] >= topo.n_sub + 1, "expected per-subdomain lanes"
    print(f"[trace_observatory] wrote {path}", file=sys.stderr)
    return [
        ("trace/train/chunk_attempts", len(chunks), ""),
        ("trace/train/span_pairs", report["span_pairs"], ""),
        ("trace/train/halo_flows", report["flows"], ""),
        ("trace/train/lanes", report["lanes"], ""),
        ("trace/train/halo_bytes_per_device",
         round(float(halo["collective_permute_bytes"]), 1), "B"),
    ]


def smoke_rows():
    return serve_trace_rows(smoke=True) + train_trace_rows(smoke=True)


def run(smoke: bool = False):
    return serve_trace_rows(smoke=smoke) + train_trace_rows(smoke=smoke)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    emit(run(smoke=args.smoke))


if __name__ == "__main__":
    main()
