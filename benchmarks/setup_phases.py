"""Set-up by compile phase, and the traced training window with and without
the program's own tracing, for the chip benchmark's training cell.

    python3 benchmarks/setup_phases.py --seed <n> --obs <0|1> [--seconds 10]

One process holds the chip.  It builds the benchmark's ``Program``
(``bench/train.py``) through its first chunk, then runs the window under the
profiler as ``bench/run.py --trace 1`` does.  ``--obs 1`` attaches the
program's ``Tracer`` to the trainer and wraps set-up in the program's
compile tracer (``repro.obs.CompileWatcher``); ``--obs 0`` drives the
program bare, as the benchmark does.  Prints one JSON line:

* ``setup_s``, from the first line of this script to the first timed step,
  and (``--obs 1``) each compile phase's seconds (the union of its
  intervals), counts per phase and cache result, and the functions that
  took longest in each phase;
* ``steps_per_s`` and the cell's per-layer metrics, by the benchmark's own
  readers;
* the window's compiles and traces, by ``bench/harness.CompileCount`` and
  (``--obs 1``) by ``CompileWatcher``;
* the Pallas PINN launches traced in the process, by stream layout
  (``repro.obs.launch_counts``: streams stacked per tile), and the
  collectives traced, by scope and opcode with their bytes per device
  (``repro.obs.collective_counts``; none in a one-chip cell);
* the program's ``train.run_chunk_guarded`` spans on the profile's host
  plane, and the chunk-boundary idle split between the harness's
  ``bench.fetch_health`` span and that dispatch span (median microseconds);
* the reverse kernel's launches on the device's ``XLA Ops`` line against
  the launches ``bench/trace.leaves`` keeps, with their device seconds.

It exits non-zero where JAX finds no TPU.  ``measure`` also takes a smaller
cell on the host's CPU, which has no device plane: the device numbers are
then left out.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

BWD = "pinn2-bwd-fused"


def _host_events(path: str, names) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out.extend((e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for line in plane.lines for e in line.events
                       if e.name in names)
    return sorted(out, key=lambda h: h[1])


def _raw_ops(path: str, device: str) -> list:
    """The device's ``XLA Ops`` events before ``trace.leaves``."""
    from jax.profiler import ProfileData

    from bench import trace

    return [(trace.short_name(e.name), int(e.start_ns),
             int(e.start_ns + e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name == device for line in plane.lines
            if line.name == trace.OPS_LINE for e in line.events]


def _boundary_split(busy, fetches, dispatches) -> dict:
    """Median microseconds of each part of the device's idle between one
    chunk's last operation and the next chunk's first."""
    parts = {k: [] for k in ("gap", "in_fetch", "fetch_to_dispatch",
                             "in_dispatch", "dispatch_to_device")}
    for f in fetches:
        d = next((x for x in dispatches if x[1] >= f[2]), None)
        ends = [b for _a, b in busy if b <= f[2]]
        starts = [a for a, _b in busy if d is not None and a >= d[1]]
        if not ends or not starts:
            continue
        dev_end, dev_start = ends[-1], starts[0]
        parts["gap"].append(dev_start - dev_end)
        parts["in_fetch"].append(f[2] - max(dev_end, f[1]))
        parts["fetch_to_dispatch"].append(d[1] - f[2])
        parts["in_dispatch"].append(d[2] - d[1])
        parts["dispatch_to_device"].append(dev_start - d[2])
    out = {k: statistics.median(v) / 1e3 for k, v in parts.items() if v}
    out["n"] = len(parts["gap"])
    return out


def measure(cell, devs, t_start: float, obs: bool) -> dict:
    import jax

    from bench import flops, harness, trace
    from bench import train as btrain
    from repro.obs import (CompileWatcher, Tracer, collective_counts,
                           launch_counts)

    cfg = cell.config
    tracer = Tracer() if obs else None
    cc = harness.CompileCount()
    watch = CompileWatcher if obs else (lambda **_kw: contextlib.nullcontext())
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        with watch(tracer=tracer) as setup:
            prog = btrain.Program(cfg, cell.traffic, cell.seed, devs)
            prog.trainer.tracer = tracer
            first = prog.first()
        setup_s = time.perf_counter() - t_start
        steps = 0
        cc.on = True
        with harness.traced(True, cell.name + ".setup_phases") as tdir:
            with watch() as window, harness.span("bench.window"):
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < cell.seconds:
                    steps += prog.chunk_call()[2]
                t1 = time.perf_counter()
        cc.on = False
    out = {"seed": cell.seed, "obs": int(obs), "setup_s": setup_s,
           "first_ok": first["ok"], "steps_per_s": steps / (t1 - t0),
           "window_compiles": {"compile_count": [cc.compiles, cc.traces]},
           "pinn_launches": launch_counts(),
           "collectives": collective_counts()}
    if obs:
        by_fun = setup.by_fun
        per = {}
        for phase, fun, a, b, _c in setup.intervals:
            per.setdefault(phase, {}).setdefault(fun, 0.0)
            per[phase][fun] += b - a
        out["setup_phase_s"] = {p: setup.seconds(p)
                                for p in ("trace", "lower", "backend")}
        out["setup_counts"] = {k: sum(v.values()) for k, v in by_fun.items()}
        out["setup_top_fun_s"] = {
            p: sorted(d.items(), key=lambda kv: -kv[1])[:5]
            for p, d in per.items()}
        out["compile_spans"] = sum(1 for s in tracer.spans()
                                   if s.name.startswith("compile."))
        out["window_compiles"]["compile_watcher"] = [window.backend_compiles,
                                                     window.traces]
    tr = trace.load(tdir)
    summ = trace.summary(tr, cell.chips)
    ctx = {"kind": "train", "trace_dir": tdir, "steps": steps,
           "window_s": t1 - t0, "steps_per_s": steps / (t1 - t0),
           "counts": flops.step(cfg, flops.point_groups(
               cfg, flops.data_counts(prog.data.data_comp),
               len(prog.data.ifaces))),
           "chips": cell.chips, "config": cfg, "trace": tr, "summary": summ,
           "peak": harness.peak(jax.devices()[0].device_kind
                                if devs[0].platform == "tpu"
                                else "TPU v5 lite")}
    out["metrics"] = {k: v["value"] for k, v in
                      harness.read_metrics(cell, ctx).items()}
    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    host = _host_events(path, ("train.run_chunk_guarded", "bench.train_chunk",
                               "bench.fetch_health"))
    disp = [h for h in host if h[0] == "train.run_chunk_guarded"]
    chunks = [h for h in host if h[0] == "bench.train_chunk"]
    out["program_spans_on_host_plane"] = len(disp)
    out["program_spans_inside_chunk_span"] = sum(
        1 for d in disp if any(c[1] <= d[1] and d[2] <= c[2] for c in chunks))
    if summ["devices"]:
        dev = summ["devices"][0]
        busy = trace.union((a, b) for _n, a, b in tr["devices"][dev])
        fetches = [h for h in host if h[0] == "bench.fetch_health"
                   and summ["t0"] <= h[1] and h[2] <= summ["t1"]]
        out["boundary_us"] = _boundary_split(busy, fetches, disp)
        kept = [o for o in trace.clip(tr["devices"][dev], summ["t0"],
                                      summ["t1"]) if BWD in o[0]]
        raw = [o for o in trace.clip(_raw_ops(path, dev), summ["t0"],
                                     summ["t1"]) if BWD in o[0]]
        out["bwd_launches"] = {
            "raw": len(raw), "kept": len(kept),
            "raw_s": sum(b - a for _n, a, b in raw) / 1e9,
            "kept_s": sum(b - a for _n, a, b in kept) / 1e9}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args()
    from bench import harness

    cell = harness.Cell("burgers_xpinn_2x2.train", a.seed, a.seconds, True)
    devs = harness.setup_jax(cell.chips)
    print(json.dumps(measure(cell, devs, T_START, bool(a.obs))), flush=True)


if __name__ == "__main__":
    main()
