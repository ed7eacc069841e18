"""Causal tracing: span trees, sampling, export validity, trajectory gate.

The contracts under test, in dependency order:

* **Tracer core** — stack-based parenting, deterministic systematic sampling
  (unsampled traces still carry real trace_ids), bounded ring buffer with
  eviction accounting, retrospective ``record``;
* **off-mode is bitwise non-intrusive** — with ``tracer=None`` the serve and
  training integration points take the exact pre-tracing code path:
  ``ServeResult`` fields unchanged (``trace_id`` None), guarded-chunk terms
  BITWISE equal with the tracer attached vs absent, and the lowered chunk
  HLO byte-identical (the tracer wraps dispatch on the host; the compiled
  program must not know it exists);
* **one trace_id per ticket through failure paths** — a retried, ladder-
  degraded, finally-served request carries ONE trace whose subtree records
  every hop; shed and deadline-exceeded tickets still close their root span;
* **Chrome export** — structural validity (matched B/E pairs, monotone ts,
  finished flows) on serve and 4-subdomain supervised training exports, and
  the validator REJECTS malformed documents;
* **perf-trajectory gate** — passes on stable history, TRIPS on an injected
  2x single-metric slowdown (negative control), does not trip on common-mode
  drift (container quota wobble), and never records a tripped run.

Heavy end-to-end sweeps live behind ``-m trace`` (deselected from tier-1).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs import Obs, MetricsRegistry, Tracer, make_obs
from repro.obs.trace_export import (ChromeTraceError, export_chrome_trace,
                                    halo_flow_events, to_chrome,
                                    training_timeline, validate_chrome_trace)
from repro.obs.trajectory import (PerfRegressionError, append_record,
                                  detect_regressions, gate, read_history)
from repro.runtime import InjectedFailure
from repro.serve import ResilienceConfig, ResilientFrontend

POISON_X = 777.0


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        self.t += 0.001
        return self.t


# ---------------------------------------------------------------- tracer core

def test_span_stack_parenting_and_tree():
    tr = Tracer(clock=FakeClock())
    with tr.start_trace("root", lane="serve") as root:
        with tr.span("mid") as mid:
            tr.span("leaf").end()
        assert mid.parent_id == root.span_id
    spans = tr.spans()
    assert [s.name for s in spans] == ["leaf", "mid", "root"]
    leaf = spans[0]
    assert leaf.parent_id == mid.span_id and leaf.trace_id == root.trace_id
    tree = tr.tree(root.trace_id)
    assert tree["span"].name == "root"
    assert tree["children"][0]["span"].name == "mid"
    assert tree["children"][0]["children"][0]["span"].name == "leaf"


def test_explicit_parent_beats_stack():
    tr = Tracer(clock=FakeClock())
    a = tr.start_trace("a")
    with tr.start_trace("b"):
        sp = tr.span("child-of-a", parent=a)
        assert sp.trace_id == a.trace_id and sp.parent_id == a.span_id
        sp.end()


def test_retrospective_record_inherits_trace():
    tr = Tracer(clock=FakeClock())
    root = tr.start_trace("root")
    sp = tr.record("queue_wait", 1.0, 2.5, parent=root, ticket=7)
    assert sp.trace_id == root.trace_id and sp.t1 - sp.t0 == 1.5
    assert sp.attrs["ticket"] == 7
    root.end()
    assert {s.name for s in tr.spans(root.trace_id)} == {"root", "queue_wait"}


def test_systematic_sampling_is_deterministic():
    tr = Tracer(clock=FakeClock(), sample_rate=0.25)
    decisions = [tr.start_trace("r").sampled for _ in range(8)]
    assert decisions == [False, False, False, True] * 2
    # unsampled traces still carry REAL trace ids: propagation stays intact
    unsampled = tr.start_trace("r")
    assert not unsampled.sampled and unsampled.trace_id.startswith("t")
    unsampled.end()
    assert tr.spans(unsampled.trace_id) == []
    st = tr.stats()
    assert st["traces"] == 9 and st["traces_sampled"] == 2
    assert st["spans_dropped_sampling"] >= 1


def test_ring_buffer_bounds_and_watermark():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(7):
        tr.start_trace(f"s{i}").end()
    st = tr.stats()
    assert st["buffer"] == 4 and st["spans_evicted"] == 3
    assert st["watermark"] == 4 and st["spans_recorded"] == 7
    assert [s.name for s in tr.spans()] == ["s3", "s4", "s5", "s6"]


def test_exception_exits_annotate_error_and_close():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(ValueError):
        with tr.start_trace("boom") as sp:
            raise ValueError("x")
    assert sp._ended and sp.attrs["error"] == "ValueError"
    assert tr._stack == []


# ------------------------------------------------------------- chrome export

def _spans_fixture():
    tr = Tracer(clock=FakeClock())
    with tr.start_trace("req", lane="serve") as root:
        with tr.span("dispatch"):
            tr.span("engine", lane="engine").end()
        root.event("hop")
    return tr.spans()


def test_to_chrome_valid_and_name_matched():
    rep = validate_chrome_trace(to_chrome(_spans_fixture()))
    assert rep["span_pairs"] == 3 and rep["instants"] == 1
    assert rep["lanes"] == 2        # serve + engine


def test_overlapping_traces_pack_into_slots():
    tr = Tracer(clock=FakeClock())
    a = tr.start_trace("a", lane="serve")
    b = tr.start_trace("b", lane="serve")   # overlaps a on the same lane
    a.end()
    b.end()
    rep = validate_chrome_trace(to_chrome(tr.spans()))
    assert rep["span_pairs"] == 2 and rep["lanes"] == 2   # serve + serve#2


def test_validator_rejects_malformed_documents():
    good = to_chrome(_spans_fixture())["traceEvents"]
    with pytest.raises(ChromeTraceError):
        validate_chrome_trace({"nope": []})
    # unmatched E: drop the B of a matched pair
    b_idx = next(i for i, e in enumerate(good) if e["ph"] == "B")
    with pytest.raises(ChromeTraceError):
        validate_chrome_trace(
            {"traceEvents": good[:b_idx] + good[b_idx + 1:]})
    # time travel: non-monotone ts in file order
    bad = [dict(e) for e in good]
    bad[-1]["ts"] = -5
    with pytest.raises(ChromeTraceError):
        validate_chrome_trace({"traceEvents": bad})


def test_halo_flows_and_training_timeline():
    tr = Tracer(clock=FakeClock())
    for k in range(2):
        tr.start_trace("train.chunk", lane="train", chunk=k).end()
    topo = SimpleNamespace(n_sub=2,
                           neighbor=np.array([[1, -1], [0, -1]]))
    lanes, flows = training_timeline(tr.spans(), topo,
                                     halo={"collective_permute_bytes": 4096})
    assert len(lanes) == 4                      # 2 chunks x 2 subdomain lanes
    assert len(flows) == 4                      # 2 directed edges x 2 chunks
    assert all(f["bytes"] == 2048 for f in flows)
    rep = validate_chrome_trace(
        to_chrome(list(tr.spans()) + lanes, flows=flows))
    assert rep["flows"] == 4 and rep["lanes"] == 3


def test_export_chrome_trace_writes_validated_file(tmp_path):
    path = str(tmp_path / "trace.json")
    rep = export_chrome_trace(path, _spans_fixture())
    assert rep["span_pairs"] == 3
    doc = json.load(open(path))
    assert validate_chrome_trace(doc)["events"] == len(doc["traceEvents"])


# ------------------------------------------------- serve failure-path traces

class StubEngine:
    """u = pts @ [1, 2]; clouds containing POISON_X fail the first
    ``fail_times`` dispatches (transient fault -> retry/degrade hops)."""

    def __init__(self, fail_times=0):
        self.bundle = SimpleNamespace(decomp=SimpleNamespace(dim=2))
        self.n_dispatches = 0
        self.poison_evals = 0
        self.fail_times = fail_times
        self.last_claims = None
        self.obs = None

    def evaluate(self, pts, order=2):
        # mirror FieldEngine: an engine span nested under the caller's
        # active (microbatch) span, so the hop shows up in the trace
        tracer = self.obs.tracer if self.obs is not None else None
        if tracer is not None:
            with tracer.span("serve.engine", lane="engine", order=order,
                             points=len(pts)):
                return self._eval(pts, order)
        return self._eval(pts, order)

    def _eval(self, pts, order):
        pts = np.asarray(pts, float)
        if POISON_X in pts[:, 0]:
            self.poison_evals += 1
            if self.poison_evals <= self.fail_times:
                raise InjectedFailure("stub engine failure")
        self.n_dispatches += 1
        self.last_claims = np.ones(len(pts), np.int64)
        return {"u": pts @ np.array([[1.0], [2.0]])}


def _traced_rf(engine, **cfg_kw):
    now = [0.0]
    obs = Obs(registry=MetricsRegistry(clock=lambda: now[0]),
              tracer=Tracer(clock=FakeClock()))
    engine.obs = obs
    fe = ResilientFrontend(engine, ResilienceConfig(**cfg_kw),
                           clock=lambda: now[0],
                           sleep=lambda s: now.__setitem__(0, now[0] + s),
                           obs=obs)
    return fe, now, obs.tracer


def _cloud(n, seed=0, poison=False):
    c = np.random.default_rng(seed).uniform(-1.0, 1.0, size=(n, 2))
    if poison:
        c[0, 0] = POISON_X
    return c


def test_failure_path_one_trace_id_records_every_hop():
    eng = StubEngine(fail_times=3)
    fe, _now, tr = _traced_rf(eng, retry_limit=4, retry_backoff=0.01,
                              order=2)
    t = fe.submit(_cloud(4, poison=True))
    fe.drain()
    res = fe.result(t)
    assert res.ok and res.status == "degraded" and res.order == 1
    assert res.trace_id is not None
    names = [s.name for s in tr.spans(res.trace_id)]
    # ONE trace records admission, the quarantine hops of each failed
    # attempt, the retries, the ladder step-down, and the final service
    for hop in ("serve.admitted", "serve.quarantine", "serve.retry",
                "serve.degrade", "serve.microbatch", "serve.engine",
                "serve.queue_wait", "serve.dispatch"):
        assert hop in names, (hop, names)
    root = [s for s in tr.spans(res.trace_id) if s.parent_id is None]
    assert len(root) == 1 and root[0].attrs["status"] == "degraded"
    # no other trace leaked a span
    assert set(tr.trace_ids()) == {res.trace_id}


def test_shed_and_deadline_tickets_close_their_roots():
    eng = StubEngine()
    fe, now, tr = _traced_rf(eng, max_queue_requests=1,
                             default_deadline=0.5)
    t1 = fe.submit(_cloud(4))
    t2 = fe.submit(_cloud(5, seed=1))          # over the bound: shed
    now[0] += 1.0                              # t1 expires in the queue
    fe.poll()
    r1, r2 = fe.result(t1), fe.result(t2)
    assert r1.status == "deadline_exceeded" and r2.status == "shed"
    for r in (r1, r2):
        assert r.trace_id is not None
        roots = [s for s in tr.spans(r.trace_id) if s.parent_id is None]
        assert len(roots) == 1 and roots[0]._ended
        assert roots[0].attrs["status"] == r.status


def test_cache_hit_trace_has_hop_event():
    eng = StubEngine()
    fe, _now, tr = _traced_rf(eng)
    c = _cloud(6)
    t1 = fe.submit(c)
    fe.flush()
    fe.result(t1)
    t2 = fe.submit(c)                           # admission-time cache hit
    r2 = fe.result(t2)
    assert r2.ok and r2.reason == "cache" and r2.trace_id is not None
    names = [s.name for s in tr.spans(r2.trace_id)]
    assert "serve.cache_hit" in names


def test_off_mode_serve_result_unchanged():
    eng = StubEngine()
    now = [0.0]
    fe = ResilientFrontend(eng, ResilienceConfig(), clock=lambda: now[0],
                           sleep=lambda s: None)
    t = fe.submit(_cloud(4))
    fe.drain()
    res = fe.result(t)
    assert res.ok and res.trace_id is None


# --------------------------------------------------- training parity + spans

@pytest.fixture(scope="module")
def trainer_setup():
    from repro.core import (Burgers1D, CartesianDecomposition, DDConfig,
                            ReferenceTrainer, XPINN, build_topology)
    from repro.core.nets import MLPConfig, SubdomainModelConfig
    from repro.data import make_batch

    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=8)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 16, 2)})
    b = make_batch(dec, topo, pde, n_res=48, n_bnd=16,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, residual_path="pallas"))
    return topo, b, tr


def test_traced_guarded_chunk_bitwise_and_hlo_parity(trainer_setup):
    import jax
    import jax.numpy as jnp

    _topo, b, tr = trainer_setup
    assert tr.tracer is None                    # off by default
    lr = jnp.ones_like(tr.lrs)
    s_off, t_off, h_off = tr.run_chunk_guarded(tr.init(0), b, 4)
    hlo_off = tr._chunk_guarded.lower(tr.init(0), b, 4, lr).as_text()

    tracer = Tracer(clock=FakeClock())
    tr.tracer = tracer
    try:
        s_on, t_on, h_on = tr.run_chunk_guarded(tr.init(0), b, 4)
        hlo_on = tr._chunk_guarded.lower(tr.init(0), b, 4, lr).as_text()
    finally:
        tr.tracer = None
    # the compiled program must not know the tracer exists
    assert hlo_on == hlo_off
    # bitwise: the tracer wraps the dispatch on the host, nothing else
    for a, c in zip(jax.tree.leaves(s_off.params), jax.tree.leaves(s_on.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    for k in t_off:
        np.testing.assert_array_equal(np.asarray(t_off[k]),
                                      np.asarray(t_on[k]))
    assert bool(h_off["ok"]) == bool(h_on["ok"])
    # exactly one dispatch span per chunk call: the host's dispatch
    spans = tracer.spans()
    assert [s.name for s in spans] == ["train.run_chunk_guarded"]
    assert spans[0].t1 > spans[0].t0


def test_tracer_puts_dispatch_span_on_host_plane(trainer_setup, tmp_path):
    """A traced chunk's dispatch span is also a host event of the recorded
    profile, where the device's operations are."""
    import glob

    import jax
    from jax.profiler import ProfileData

    _topo, b, tr = trainer_setup
    state = tr.init(0)
    tr.tracer = Tracer()
    try:
        with jax.profiler.trace(str(tmp_path)):
            state, _terms, health = tr.run_chunk_guarded(state, b, 4)
            assert bool(health["ok"])
    finally:
        tr.tracer = None
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    assert host.count("train.run_chunk_guarded") == 1


def test_dispatch_builds_annotations_only_with_a_tracer(trainer_setup,
                                                        monkeypatch):
    """With ``tracer=None`` the dispatch path builds no annotation (nor any
    span); an attached tracer builds one per chunk dispatch."""
    import jax.profiler

    built = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        built.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    _topo, b, tr = trainer_setup
    tr.tracer = None
    tr.run_chunk_guarded(tr.init(0), b, 4)
    assert built == []
    tr.tracer = Tracer()
    try:
        tr.run_chunk_guarded(tr.init(0), b, 4)
    finally:
        tr.tracer = None
    assert built == ["train.run_chunk_guarded"]


def test_supervisor_one_trace_per_attempt_and_event_trace_ids(tmp_path,
                                                              trainer_setup):
    from repro.runtime import (Fault, FaultInjector, Supervisor,
                               SupervisorConfig)

    _topo, b, tr = trainer_setup
    obs = make_obs(str(tmp_path / "ev.jsonl"), trace=True)
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=3),
                     FaultInjector([Fault(1, "crash"),
                                    Fault(3, "nan_params", subdomain=0)]),
                     obs=obs)
    try:
        sup.run(tr.init(0), b, total_steps=12)
    finally:
        obs.close()
    roots = [s for s in obs.tracer.spans() if s.parent_id is None]
    outcomes = [s.attrs["outcome"] for s in roots]
    assert outcomes.count("crash") == 1 and outcomes.count("guard_trip") == 1
    # each attempt's trace nests its dispatch; failures add a rollback child
    for r in roots:
        kids = {s.name for s in obs.tracer.spans(r.trace_id)
                if s.parent_id == r.span_id}
        assert "train.run_chunk_guarded" in kids
        if r.attrs["outcome"] != "committed":
            assert "train.rollback" in kids
    # every emitted supervisor event carries the trace_id of a known attempt
    tids = {r.trace_id for r in roots}
    events = [json.loads(ln) for ln in open(tmp_path / "ev.jsonl")][1:]
    for e in events:
        if e["kind"] in ("chunk", "crash", "rollback", "guard_trip"):
            assert e["trace_id"] in tids, e


def test_supervisor_off_mode_emits_no_trace_ids(tmp_path, trainer_setup):
    from repro.runtime import Supervisor, SupervisorConfig

    _topo, b, tr = trainer_setup
    tr.tracer = None          # module fixture: undo any earlier test's wiring
    obs = make_obs(str(tmp_path / "ev.jsonl"))          # trace=False default
    assert obs.tracer is None
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=3), obs=obs)
    try:
        sup.run(tr.init(0), b, total_steps=6)
    finally:
        obs.close()
    assert tr.tracer is None
    events = [json.loads(ln) for ln in open(tmp_path / "ev.jsonl")][1:]
    assert events and all("trace_id" not in e for e in events)


# ----------------------------------------------------------- trajectory gate

def _hist_rows(scale=1.0):
    return [("bench/lat_ms", 10.0 * scale, "ms"),
            ("bench/throughput", 100.0 / scale, "pts/s"),
            ("bench/aux_ms", 5.0 * scale, "ms")]


def _seed_history(path, runs=4):
    for i in range(runs):
        append_record(path, "b", _hist_rows(1.0 + 0.02 * i), mode="smoke",
                      sha=f"s{i}", clock=lambda: float(i))


def test_gate_passes_on_stable_history(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed_history(path)
    rep = gate(path, "b", _hist_rows(1.03), mode="smoke", clock=lambda: 9.0)
    assert rep["recorded"] and not rep["regressions"]
    assert len(read_history(path)) == 5


def test_gate_trips_on_single_metric_2x_and_does_not_record(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed_history(path)
    rows = _hist_rows(1.0)
    rows[0] = ("bench/lat_ms", 20.0, "ms")      # injected 2x slowdown
    with pytest.raises(PerfRegressionError) as ei:
        gate(path, "b", rows, mode="smoke", clock=lambda: 9.0)
    assert "bench/lat_ms" in str(ei.value)
    assert len(read_history(path)) == 4         # the bad run was NOT recorded


def test_common_mode_drift_does_not_trip(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed_history(path)
    # everything 1.8x slower: container quota wobble, not a regression
    rep = detect_regressions(read_history(path), _hist_rows(1.8),
                             mode="smoke")
    assert rep["gated"] == 3 and not rep["regressions"]


def test_modes_never_share_baselines(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed_history(path)                          # smoke-mode history only
    rep = detect_regressions(read_history(path), _hist_rows(5.0),
                             mode="full")
    assert rep["gated"] == 0                     # no full-mode baseline yet


def test_unknown_units_never_gate(tmp_path):
    path = str(tmp_path / "h.jsonl")
    for i in range(4):
        append_record(path, "b", [("bench/count", 10 + i, "")],
                      mode="smoke", sha=f"s{i}", clock=lambda: float(i))
    rep = detect_regressions(read_history(path), [("bench/count", 99, "")],
                             mode="smoke")
    assert rep["gated"] == 0


# ------------------------------------------------------ end-to-end (marked)

@pytest.mark.trace
def test_trace_observatory_smoke_exports_validate():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import trace_observatory

    rows = dict((r[0], r[1]) for r in trace_observatory.smoke_rows())
    assert rows["trace/serve/span_pairs"] > 0
    assert rows["trace/train/halo_flows"] > 0


@pytest.mark.trace
def test_sampled_serving_keeps_ids_but_records_fraction():
    eng = StubEngine()
    now = [0.0]
    obs = Obs(registry=MetricsRegistry(clock=lambda: now[0]),
              tracer=Tracer(clock=FakeClock(), sample_rate=0.25))
    fe = ResilientFrontend(eng, ResilienceConfig(), clock=lambda: now[0],
                           sleep=lambda s: None, obs=obs)
    tickets = [fe.submit(_cloud(4, seed=i)) for i in range(8)]
    fe.drain()
    results = [fe.result(t) for t in tickets]
    assert all(r.trace_id is not None for r in results)     # ids always flow
    assert len(set(r.trace_id for r in results)) == 8
    st = obs.tracer.stats()
    assert st["traces"] == 8 and st["traces_sampled"] == 2
    assert len(obs.tracer.trace_ids()) == 2                 # recorded subset
