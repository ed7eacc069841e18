"""What every cell shares: the manifest, the device check, the compile cache,
the traced window, the per-layer metric readers and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
CACHE = os.path.join(ROOT, ".bench_cache", "jax")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One workload of the manifest with its configuration, traffic mix and
    limits, each read from the file its name points to.  ``workload``
    stands in for a manifest entry that is not (yet) there."""

    def __init__(self, name: str, seed: int, seconds: float, trace: bool,
                 workload: dict | None = None):
        m = manifest()
        w = [workload] if workload else [c for c in m["workloads"]
                                         if c["name"] == name]
        if not w:
            raise SystemExit(f"unknown workload {name!r}")
        self.workload = w[0]
        self.name, self.seed = name, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.chips = int(self.workload["chips"])
        self.config = load_json("configs", self.workload["config"] + ".json")
        self.traffic = load_json("traffic", self.workload["traffic"] + ".json")
        lim = os.path.join(BENCH, "limits", name + ".json")
        self.limits = load_json(lim) if os.path.exists(lim) else {}
        self.end_to_end = [e for e in m["end_to_end"]
                           if name in e.get("workloads", [name])]
        reported = {e["name"] for e in self.end_to_end}
        self.per_layer = [p for p in m["per_layer"]
                          if name in p.get("workloads", [name])
                          and p["moves"] in reported]


# ---------------------------------------------------------------- device

def setup_jax(chips: int, require_tpu: bool = True):
    """Point JAX's persistent cache at a fixed directory and check the
    devices.  Returns the devices the cell uses.  ``require_tpu=False`` is
    for the CPU tests, which drive a run short of the look for a chip and
    leave the process's cache settings alone."""
    import jax

    if require_tpu:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found {len(devs)} {devs[0].platform} "
                         "device(s) and no TPU; this benchmark runs only on "
                         "the chip")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    import jax

    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def peak(kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    table = load_json("peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


class CompileCount:
    """Counts traces and backend compiles from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.traces = self.compiles = 0
        self.on = False

        def listen(name, _dur, **_kw):
            if not self.on:
                return
            if name == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
            elif name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        mon.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------- tracing

def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(enabled: bool, name: str):
    """Profile the block when ``enabled``; the trace lands in
    ``.bench_out/<name>/trace``."""
    import jax

    d = os.path.join(OUT, name, "trace")
    if not enabled:
        yield None
        return
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


def read_metrics(cell: Cell, ctx: dict) -> dict:
    """Run each per-layer metric's reader; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------- result

def checks_ok(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def emit(result: dict) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, then the result as the last line of standard output, with the
    checks under the key that comes last."""
    checks = result.pop("checks")
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
