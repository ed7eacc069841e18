"""Unified telemetry: registry, JSONL events, in-graph training rows,
retrace flatness, staged serve latency.

Covers the observability contract (EXPERIMENTS.md §Observability):

* the metrics registry: counters/gauges/log-bucket histograms with percentile
  export, the ``CounterGroup`` view that keeps legacy ``stats()`` shapes, and
  ONE injectable clock shared by everything hanging off it;
* the JSONL event stream: manifest-first, schema-versioned, strictly
  validated — malformed streams FAIL;
* in-graph telemetry rows (``DDConfig(telemetry=True)``): per-step
  per-subdomain grad/param norms, lr, interface mismatch, and guard ``step_ok``
  flags ride the scanned chunk's stacked outputs; ``telemetry=False`` keeps
  the terms dict AND the trained parameters bitwise identical to before;
* the telemetry-enabled guarded chunk stays a single donated dispatch — the
  megabatched network entry still traces exactly twice (eval_shape probe +
  the one live cond branch), the compiled HLO packs weights exactly as often
  as the plain chunk;
* retrace flatness, asserted with a flat-line compile counter
  (``CompileWatcher`` over ``jax.monitoring``): serve batch buckets,
  guarded/unguarded chunks, and ``lr_scale`` changes dispatch with ZERO new
  backend compiles once warm;
* supervisor and serve frontends publish into the shared registry (reports
  and ``stats()`` unchanged) and stamp staged latencies (queue wait /
  dispatch / e2e) onto every answered ticket.

Unmarked tests are the tier-1 subset; the timing-sensitive overhead bound and
the multi-device subprocess sweep run under ``-m obs`` (see pytest.ini).
"""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    Burgers1D, CartesianDecomposition, DDConfig, ReferenceTrainer, XPINN,
    build_topology,
)
from repro.core.losses import ResidualPath
from repro.core.nets import MLPConfig, SubdomainModelConfig, stacked_init
from repro.core.trainer import DataParallelTrainer
from repro.data import make_batch
from repro.kernels import ops
from repro.obs import (
    CompileWatcher, Counter, EventLog, Histogram, MetricsRegistry, Obs,
    ObsSchemaError, SCHEMA_VERSION, Tracer, make_obs, read_events,
    validate_events,
)
from repro.runtime import Fault, FaultInjector, Supervisor, SupervisorConfig
from repro.serve import (
    FieldBundle, FieldEngine, ResilienceConfig, ResilientFrontend,
)
from repro.utils.hlo import named_scope_counts


# ------------------------------------------------------------------ registry

def test_counter_gauge_and_group_keep_stats_shapes():
    reg = MetricsRegistry()
    c = reg.counter("x/hits")
    c.inc()
    c.inc(2)
    assert c.snapshot() == 3 and isinstance(c.snapshot(), int)
    reg.gauge("x/depth").set(7)
    assert reg.gauge("x/depth").snapshot() == 7.0
    # the legacy dict idiom, backed by registry counters
    g = reg.group("serve.test", ("requests", "shed"))
    g["requests"] += 1
    g["new_key"] = 5
    assert dict(g) == {"requests": 1, "shed": 0, "new_key": 5}
    assert reg.counter("serve.test/requests").snapshot() == 1
    with pytest.raises(TypeError):
        del g["shed"]
    with pytest.raises(TypeError):   # name collision across metric types
        reg.gauge("x/hits")
    snap = reg.snapshot("serve.test")
    assert snap == {"serve.test/new_key": 5, "serve.test/requests": 1,
                    "serve.test/shed": 0}


def test_histogram_percentiles_within_bucket_error():
    h = Histogram("t", lo=1e-6, hi=10.0)
    for v in np.linspace(0.001, 0.1, 1000):
        h.record(v)
    h.record(float("nan"))           # skipped, never poisons the summary
    s = h.snapshot()
    assert s["count"] == 1000
    assert s["min"] == pytest.approx(0.001) and s["max"] == pytest.approx(0.1)
    # log-bucket guarantee: quantile within one growth factor (2**0.25)
    for p, true in ((50, 0.0505), (90, 0.0901), (99, 0.099)):
        assert true / 2 ** 0.25 <= h.percentile(p) <= true * 2 ** 0.25
    assert h.percentile(0) == s["min"] and h.percentile(100) == s["max"]
    empty = Histogram("e")
    assert empty.percentile(50) is None
    assert empty.snapshot()["count"] == 0


def test_registry_timer_uses_injected_clock():
    now = [0.0]
    reg = MetricsRegistry(clock=lambda: now[0])
    with reg.timer("x/op_s"):
        now[0] += 0.25
    s = reg.histogram("x/op_s").snapshot()
    assert s["count"] == 1 and s["max"] == pytest.approx(0.25)


# -------------------------------------------------------------------- events

def test_eventlog_manifest_first_and_validates(tmp_path):
    path = str(tmp_path / "run.jsonl")
    now = [10.0]
    log = EventLog(path, clock=lambda: now[0], run_id="r1",
                   config={"n_sub": 4})
    now[0] = 11.5
    log.emit("chunk", step=3, steps=3, loss=0.5, walltime_s=0.2)
    log.emit("guard_trip", chunk=1, bad_subdomains=[0, 2], good_steps=2)
    log.close()
    manifest = validate_events(path)
    assert manifest["run_id"] == "r1"
    assert manifest["schema_version"] == SCHEMA_VERSION
    events = read_events(path)
    assert [e["kind"] for e in events] == ["manifest", "chunk", "guard_trip"]
    assert events[1]["t"] == pytest.approx(11.5)   # injected-clock timestamps


@pytest.mark.parametrize("corrupt", ["drop_t", "bad_kind", "bad_version",
                                     "missing_field", "no_manifest"])
def test_validate_rejects_malformed_streams(tmp_path, corrupt):
    path = str(tmp_path / "run.jsonl")
    log = EventLog(path, clock=time.perf_counter, run_id="r")
    log.emit("heartbeat", status="ok")
    log.close()
    lines = open(path).read().splitlines()
    if corrupt == "drop_t":
        e = json.loads(lines[1]); e.pop("t"); lines[1] = json.dumps(e)
    elif corrupt == "bad_kind":
        e = json.loads(lines[1]); e["kind"] = "nonsense"
        lines[1] = json.dumps(e)
    elif corrupt == "bad_version":
        m = json.loads(lines[0]); m["schema_version"] = 999
        lines[0] = json.dumps(m)
    elif corrupt == "missing_field":
        e = json.loads(lines[1]); e.pop("status"); lines[1] = json.dumps(e)
    elif corrupt == "no_manifest":
        lines = lines[1:]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ObsSchemaError):
        validate_events(path)


def test_obs_bundle_metrics_only_emit_is_noop():
    obs = Obs(registry=MetricsRegistry())
    obs.emit("heartbeat", status="ok")   # no sink: must not raise
    obs.close()
    assert obs.clock is obs.registry.clock


# ---------------------------------------------------- in-graph telemetry rows

def _setup(n_res=48, width=16, depth=2, telemetry=False, lrs=1e-3):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=8)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    b = make_batch(dec, topo, pde, n_res=n_res, n_bnd=16,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, telemetry=telemetry),
                          lrs=lrs)
    return pde, dec, cfg, b, tr


def _max_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_telemetry_rows_shapes_and_values():
    _, _, _, b, tr = _setup(telemetry=True, lrs=1e-3)
    _, terms = tr.run_chunk(tr.init(0), b, 3)
    for k in ("grad_norm", "param_norm", "lr", "iface_mismatch"):
        assert terms[k].shape == (3, 4), k
        assert np.isfinite(np.asarray(terms[k])).all(), k
    assert np.asarray(terms["lr"]) == pytest.approx(1e-3)
    assert (np.asarray(terms["grad_norm"]) > 0).all()
    # iface_mismatch is the rms of the two interface penalties
    im = np.sqrt(np.asarray(terms["mse_avg"]) + np.asarray(terms["mse_iface"]))
    assert np.asarray(terms["iface_mismatch"]) == pytest.approx(im)


def test_telemetry_off_keeps_terms_and_params_bitwise():
    _, _, _, b, tr_off = _setup(telemetry=False)
    _, _, _, _, tr_on = _setup(telemetry=True)
    s_off, t_off = tr_off.run_chunk(tr_off.init(0), b, 3)
    s_on, t_on = tr_on.run_chunk(tr_on.init(0), b, 3)
    assert set(t_off) == {"loss", "mse_data", "mse_res", "mse_avg",
                          "mse_iface"}           # off-mode key regression
    assert set(t_on) > set(t_off)
    assert _max_diff(s_off.params, s_on.params) == 0.0   # rows are pure reads
    assert _max_diff(t_off["loss"], t_on["loss"]) == 0.0


def test_guarded_telemetry_step_ok_and_lr_scale_row():
    _, _, _, b, tr = _setup(telemetry=True, lrs=1e-3)
    scale = jnp.asarray([1.0, 0.5, 0.25, 1.0], jnp.float32)
    _, terms, health = tr.run_chunk_guarded(tr.init(0), b, 3,
                                            lr_scale=scale)
    assert bool(np.asarray(health["ok_sub"]).all())
    ok = np.asarray(terms["step_ok"])
    assert ok.shape == (3, 4) and ok.all()
    # the lr row reports the EFFECTIVE per-subdomain rate (backoff included)
    assert np.asarray(terms["lr"]) == pytest.approx(
        np.broadcast_to(1e-3 * np.asarray(scale), (3, 4)))


def test_data_parallel_telemetry_rows():
    pde, dec, cfg, b, _tr = _setup()
    tr = DataParallelTrainer(pde, cfg, n_workers=1, lr=1e-3, telemetry=True)
    _, terms = tr.run_chunk(tr.init(0), b, 2)
    assert "iface_mismatch" not in terms    # data-parallel has no interfaces
    for k in ("grad_norm", "param_norm", "lr"):
        assert terms[k].shape[0] == 2 and np.isfinite(np.asarray(terms[k])).all()
    # linear-scaling rule [Goyal et al.]: effective lr = base lr * world size
    assert np.asarray(terms["lr"]) == pytest.approx(1e-3 * tr.n)


def test_telemetry_guarded_single_dispatch_donation_and_hlo():
    """The telemetry-enabled guarded chunk is STILL one donated dispatch: the
    megabatched entry traces exactly twice (abstract eval_shape probe + the
    one live cond branch), the compiled program packs the weight stack exactly
    as often as the plain guarded chunk, and donation holds."""
    _, _, _, b, tr = _setup(n_res=32, telemetry=True)
    tr.res_path = ResidualPath(act="tanh", block_n=32, interpret=True)
    _, _, _, _, tr_plain = _setup(n_res=32, telemetry=False)
    tr_plain.res_path = tr.res_path
    state = tr.init(0)
    ones = jnp.ones((4,), jnp.float32)

    calls = []
    orig = ops.pinn_mlp_forward2
    ops.pinn_mlp_forward2 = lambda *a, **k: (calls.append(1),
                                             orig(*a, **k))[1]
    try:
        jax.jit(tr._run_chunk_guarded, static_argnums=(2,)).lower(
            state, b, 5, ones)
    finally:
        ops.pinn_mlp_forward2 = orig
    assert len(calls) == 2

    def weight_pads(txt):
        return sum(1 for ln in txt.splitlines()
                   if " pad(" in ln and "f32[4,128,128]" in ln)

    telem = jax.jit(tr._run_chunk_guarded, static_argnums=(2,)).lower(
        state, b, 3, ones).compile().as_text()
    plain = jax.jit(tr_plain._run_chunk_guarded, static_argnums=(2,)).lower(
        tr_plain.init(0), b, 3, ones).compile().as_text()
    # one (L, 128, 128) stack per loss eval: the depth-2 net's hidden and
    # output matrices (the first layer packs against x's rows)
    assert weight_pads(telem) == weight_pads(plain) == 2

    # donation: the telemetry chunk consumes its input state buffers
    st0 = tr.init(0)
    st1, _, _ = tr.run_chunk_guarded(st0, b, 2)
    assert any(x.is_deleted() for x in jax.tree.leaves(st0.params))
    st2, _, _ = tr.run_chunk_guarded(st1, b, 2)   # rebind keeps working
    assert int(st2.step) == 4


def test_named_scopes_survive_into_compiled_hlo():
    _, _, _, b, tr = _setup(n_res=32)
    hlo = jax.jit(tr._run_chunk_const, static_argnums=(2,)).lower(
        tr.init(0), b, 2).compile().as_text()
    scopes = named_scope_counts(hlo, prefix="dd-")
    assert scopes.get("dd-comp-forward", 0) > 0
    assert scopes.get("dd-comp-update", 0) > 0


# --------------------------------------------------------- retrace flatness

def test_compile_watcher_counts_compiles_not_cache_hits():
    """The watched blocks dispatch ``f`` and nothing else.  Inputs are made
    outside them: once a trainer's ``init`` has run ``jnp.ones`` under
    ``jax.vmap`` (``nets.stacked_init``), JAX keeps a dispatch-cache entry
    for the eager ``convert_element_type`` that ``jnp.ones`` starts with
    that has no fast path, so every later eager ``jnp.ones`` in the process
    re-enters Python dispatch and reports a jaxpr trace (no compile)."""
    f = jax.jit(lambda x: x * 2 + 1)
    xs = [jnp.ones((7,)) for _ in range(6)]
    with CompileWatcher() as w1:
        f(xs[0])                      # fresh shape: at least one compile
    assert w1.backend_compiles >= 1
    with CompileWatcher() as w2:
        for x in xs[1:]:
            f(x)                      # cache hits: dead flat
    assert w2.backend_compiles == 0 and w2.traces == 0


def _fresh_compile(tmp_path):
    def f(x):
        return x * 2 + 1

    x = jnp.ones((5,))
    with CompileWatcher() as w:
        jax.jit(f)(x)
    for phase in ("trace", "lower", "backend"):
        assert w.by_fun[phase]["f"] == 1, phase
        assert w.seconds(phase) > 0
    # jnp's jitted multiply and add trace inside f; one module is built
    assert w.traces == 3 and w.backend_compiles == 1
    assert sum(w.by_fun["lower"].values()) == 1
    # no persistent cache here
    assert w.by_fun["cache_hit"] == w.by_fun["cache_miss"] == {}
    assert w.compile_seconds == w.seconds("backend")


def _nested_union(tmp_path):
    inner = jax.jit(lambda x: jnp.sin(x) + 1)

    def outer(x):
        return inner(x) * 2

    x = jnp.ones((6,))
    with CompileWatcher() as w:
        jax.jit(outer)(x)
    traces = [(a, b) for p, _f, a, b, _c in w.intervals if p == "trace"]
    assert w.by_fun["trace"]["outer"] == w.by_fun["trace"]["<lambda>"] == 1
    assert w.by_fun["lower"] == w.by_fun["backend"] == {"outer": 1}
    (oa, ob), = [(a, b) for p, f, a, b, _c in w.intervals
                 if p == "trace" and f == "outer"]
    assert all(oa <= a and b <= ob for a, b in traces)   # inner inside outer
    assert w.seconds("trace") == pytest.approx(ob - oa)
    assert w.seconds("trace") < sum(b - a for a, b in traces)


def _cache_hit(tmp_path):
    from jax._src import compilation_cache

    def g(x):
        return jnp.cos(x) * 3

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    x = jnp.ones((9,))
    tracer = Tracer()
    try:
        for k, v in zip(keys, (str(tmp_path / "cache"), 0.0, 0)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        with CompileWatcher() as w1:
            jax.jit(g)(x)
        jax.clear_caches()
        with tracer.span("outer"), CompileWatcher(tracer=tracer) as w2:
            jax.jit(g)(x)
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    # counts per fun_name: the eager ops of jnp.ones have their own programs
    assert w1.by_fun["cache_miss"].get("g") == 1
    assert "g" not in w1.by_fun["cache_hit"]
    assert w2.by_fun["cache_hit"].get("g") == 1
    assert "g" not in w2.by_fun["cache_miss"]
    backend = [s for s in tracer.spans() if s.name == "compile.backend"
               and s.attrs["fun"] == "g"]
    assert len(backend) == 1 and backend[0].attrs["cache"] == "hit"


def _tracer_spans(tmp_path):
    def h(x):
        return jnp.tanh(x) - 1

    x = jnp.ones((3,))
    tracer = Tracer()
    reg = MetricsRegistry()
    with tracer.span("setup") as root:
        with CompileWatcher(registry=reg, tracer=tracer) as w:
            jax.jit(h)(x)
    spans = [s for s in tracer.spans() if s.name.startswith("compile.")]
    assert sorted(s.name for s in spans if s.attrs["fun"] == "h") == [
        "compile.backend", "compile.lower", "compile.trace"]
    for s in spans:
        assert s.parent_id == root.span_id and s.trace_id == root.trace_id
        # one offset moves time.time() onto the tracer's clock
        assert root.t0 - 0.05 <= s.t0 <= s.t1 <= root.t1 + 0.05
    snap = reg.snapshot("obs.compile/")
    assert snap["obs.compile/backend_compiles"] == w.backend_compiles >= 1
    assert snap["obs.compile/traces"] == w.traces >= 1


@pytest.mark.parametrize("case", [_fresh_compile, _nested_union, _cache_hit,
                                  _tracer_spans], ids=lambda c: c.__name__[1:])
def test_compile_tracer(case, tmp_path):
    """The compile tracer's phases, per-function counts, unions, cache tags
    and spans, each on a function no other test compiles."""
    case(tmp_path)


def test_retrace_flat_across_guard_and_lr_scale():
    """Warm both chunk drivers once; interleaving them and sweeping lr_scale
    must never compile again (the supervisor backoff guarantee, asserted)."""
    _, _, _, b, tr = _setup(n_res=32, telemetry=True)
    st = tr.run_chunk(tr.init(0), b, 2)[0]
    stg = tr.run_chunk_guarded(tr.init(0), b, 2)[0]
    with CompileWatcher() as w:
        st = tr.run_chunk(st, b, 2)[0]
        for s in (1.0, 0.5, 0.25):
            stg = tr.run_chunk_guarded(stg, b, 2,
                                       lr_scale=jnp.full((4,), s))[0]
    assert w.backend_compiles == 0


def test_retrace_flat_across_serve_batch_buckets():
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 12, 2)})
    params, codes = stacked_init(cfg, dec.n_sub, jax.random.PRNGKey(0))
    eng = FieldEngine(FieldBundle(model_cfg=cfg, params=params, decomp=dec,
                                  act_codes=np.asarray(codes), pde=None))
    rng = np.random.default_rng(0)
    clouds = [rng.uniform((-1, 0), (1, 1), size=(n, 2)) for n in (8, 60, 200)]
    for c in clouds:
        eng.evaluate(c, order=1)       # warm each padded bucket once
    with CompileWatcher() as w:
        for _ in range(2):
            for c in clouds:
                eng.evaluate(c, order=1)
    assert w.backend_compiles == 0


# ----------------------------------------------------- supervisor integration

def test_supervisor_injected_clock_and_registry_mirror(tmp_path):
    """The supervisor times chunks/straggler recovery on the obs clock (a
    5s injected straggler is 'absorbed' instantly under a fake sleep) and
    mirrors its report counters into the shared registry."""
    _, dec, _, b, tr = _setup()
    now = [0.0]
    obs = Obs(registry=MetricsRegistry(clock=lambda: now[0]))
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=2),
                     FaultInjector([Fault(chunk=1, kind="straggler",
                                          delay=5.0)]),
                     decomp=dec, obs=obs,
                     sleep=lambda s: now.__setitem__(0, now[0] + s))
    _, report = sup.run(tr.init(0), b, 6)
    assert report.stragglers == 1 and report.chunks == 3
    assert report.walltimes[1] >= 5.0          # fake clock saw the delay
    snap = obs.registry.snapshot("train.supervisor")
    assert snap["train.supervisor/chunks"] == report.chunks
    assert snap["train.supervisor/stragglers"] == 1
    assert snap["train.supervisor/crashes"] == 0
    assert snap["train.supervisor/chunk_walltime_s"]["count"] == 3


def test_supervisor_event_stream_validates(tmp_path):
    _, dec, _, b, tr = _setup()
    path = str(tmp_path / "run.jsonl")
    obs = make_obs(path, run_id="sup-test")
    sup = Supervisor(tr, str(tmp_path / "ckpt"),
                     SupervisorConfig(chunk_steps=2),
                     FaultInjector([Fault(chunk=0, kind="nan_params",
                                          subdomain=0)]),
                     decomp=dec, obs=obs)
    _, report = sup.run(tr.init(0), b, 4)
    obs.close()
    assert report.guard_trips == 1
    validate_events(path)
    kinds = [e["kind"] for e in read_events(path)]
    assert kinds[0] == "manifest"
    assert "guard_trip" in kinds and "rollback" in kinds
    assert kinds.count("chunk") == report.chunks
    trip = next(e for e in read_events(path) if e["kind"] == "guard_trip")
    assert 0 in trip["bad_subdomains"]


# ---------------------------------------------------------- serve integration

class _StubEngine:
    """Deterministic engine double (cf. tests/test_resilience.py)."""

    def __init__(self, dim=2):
        self.bundle = SimpleNamespace(decomp=SimpleNamespace(dim=dim))
        self.n_dispatches = 0
        self.last_claims = None

    def evaluate(self, pts, order=2):
        pts = np.asarray(pts, float)
        self.n_dispatches += 1
        self.last_claims = np.ones(len(pts), np.int64)
        return {"u": pts @ np.array([[1.0], [2.0]])}


def test_serve_staged_latency_on_result_and_stats():
    now = [0.0]
    fe = ResilientFrontend(_StubEngine(), ResilienceConfig(),
                           clock=lambda: now[0],
                           sleep=lambda s: now.__setitem__(0, now[0] + s))
    res = fe.query(np.array([[0.1, 0.2], [0.3, 0.4]]))
    assert res.ok
    assert res.queue_wait is not None and res.queue_wait >= 0.0
    assert res.dispatch is not None and res.dispatch >= 0.0
    lat = fe.stats()["latency"]
    for stage in ("e2e_s", "queue_wait_s", "dispatch_s"):
        assert lat[stage]["count"] >= 1, stage
    # cache hit: answered at admission, zero queue/dispatch time by definition
    res2 = fe.query(np.array([[0.1, 0.2], [0.3, 0.4]]))
    assert res2.ok and res2.reason == "cache"
    assert res2.queue_wait == 0.0 and res2.dispatch == 0.0
    # one registry spans both layers
    snap = fe.obs.registry.snapshot()
    assert snap["serve.resilience/admitted"] == 2
    assert snap["serve.frontend/dispatches"] >= 1


def test_engine_publishes_dispatch_metrics():
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 12, 2)})
    params, codes = stacked_init(cfg, dec.n_sub, jax.random.PRNGKey(0))
    obs = Obs(registry=MetricsRegistry())
    eng = FieldEngine(FieldBundle(model_cfg=cfg, params=params, decomp=dec,
                                  act_codes=np.asarray(codes), pde=None),
                      obs=obs)
    pts = np.random.default_rng(0).uniform((-1, 0), (1, 1), size=(10, 2))
    eng.evaluate(pts, order=1)
    eng.evaluate(pts, order=1)
    snap = obs.registry.snapshot("serve.engine")
    assert snap["serve.engine/dispatches"] == 2
    assert snap["serve.engine/points"] == 20
    assert snap["serve.engine/dispatch_s"]["count"] == 2


# ------------------------------------------------------------ marked sweeps

@pytest.mark.obs
def test_telemetry_overhead_within_bound():
    """The in-graph rows must cost <= 2% on a quickstart-sized guarded chunk
    (paired interleaved timing; the benchmark enforces the same bound)."""
    from benchmarks.obs_telemetry import OVERHEAD_BOUND_PCT, overhead_rows
    _, detail = overhead_rows(iters=8, smoke=False)
    assert detail["overhead_pct"] <= OVERHEAD_BOUND_PCT


@pytest.mark.obs
@pytest.mark.slow
def test_distributed_telemetry_and_halo_scope(subproc):
    """4-device shard_map chunk: telemetry rows come back with per-subdomain
    columns and the compiled program attributes its collective-permutes to
    the dd-comm-halo named scope."""
    out = subproc("""
import json
import numpy as np, jax
from repro.core import *
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.obs import halo_traffic
from repro.utils.hlo import named_scope_counts

pde = Burgers1D()
dec = CartesianDecomposition(((-1, 1), (0, 1)), 4, 1)
topo = build_topology(dec, 8)
cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 12, 2)})
b = make_batch(dec, topo, pde, 32, 8, np.random.default_rng(0)).device_arrays()
tr = DistributedDDTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, telemetry=True), lrs=1e-3)
bd = tr.shard_batch(b)
st, terms = tr.run_chunk(tr.shard_state(tr.init(0)), bd, 2)
assert terms["grad_norm"].shape == (2, 4), terms["grad_norm"].shape
assert terms["lr"].shape == (2, 4)
assert np.isfinite(np.asarray(terms["iface_mismatch"])).all()
hlo = tr._build_chunk(2).lower(tr.shard_state(tr.init(0)), bd)\\
    .compile().as_text()
traffic = halo_traffic(hlo)
assert traffic["collective_permute_ops"] > 0
scopes = named_scope_counts(hlo, prefix="dd-")
assert scopes.get("dd-comm-halo", 0) > 0, scopes
print("OK", json.dumps(traffic["collective_permute_bytes"]))
""", n_devices=4)
    assert "OK" in out
