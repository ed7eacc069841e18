"""Benchmark driver: one function per paper table/figure.

``python -m benchmarks.run [--quick] [--smoke] [--only fig13,serve,...]``

Prints ``name,value,unit`` CSV rows per benchmark; raw measurements land in
benchmarks/results/*.json.  The roofline rows read the dry-run outputs
(run ``python -m repro.launch.dryrun`` first for those).

``--smoke`` is the CI-fast mode: seconds, not minutes; the full kernel
sweeps stay on-demand (``pytest -m kernel`` / the unflagged benchmark runs).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import common
from benchmarks.common import emit


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig13,serve,slo,chaos,obs,trace,"
                         "roofline")
    ap.add_argument("--quick", action="store_true", help="fewer sizes/iters")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-fast subset: serve throughput + "
                         "serve-SLO clean/faulted acceptance + storage-chaos "
                         "durability acceptance + validated trace exports + "
                         "perf-regression gate + roofline")
    args = ap.parse_args()

    from benchmarks import (chaos_soak, fig13_inverse, obs_telemetry,
                            roofline, serve_slo, serve_throughput,
                            trace_observatory)

    if args.smoke:
        # history appends buffer until the gate below: a regressing run is
        # flagged BEFORE it can enter its own baseline
        common.defer_history()
        rows = serve_throughput.run(iters=2, smoke=True)
        # serve-SLO acceptance: Poisson load, clean + injected fault matrix;
        # FAILS if any ticket is lost / the queue wedges / goodput under
        # faults drops below the floor
        rows += serve_slo.slo_smoke_rows()
        # durability acceptance: seeded storage faults against checkpoint
        # generations AND exported bundles through the full train -> crash ->
        # restore -> export -> serve -> reload script; FAILS unless every
        # fault is detected (100%) and every run recovers (generation
        # fallback / refused-swap-then-repair)
        rows += chaos_soak.chaos_smoke_rows()
        # observability acceptance: telemetry + tracer overhead reports,
        # flat-line retrace assertions, schema-validated obs JSONL
        rows += obs_telemetry.smoke_rows()
        # causal-trace acceptance: serve + supervised-training runs must
        # export structurally VALID Chrome traces (matched B/E pairs,
        # per-subdomain lanes, halo flows) with one trace_id per ticket
        rows += trace_observatory.smoke_rows()
        rows += roofline.residual_rows("both")
        emit(rows)
        # perf-trajectory gate: fresh headline rows vs trailing same-mode
        # history (drift-adjusted paired ratios); raises PerfRegressionError
        # on a trip and only records the run when it passes
        for rep in common.flush_history_gate():
            print(f"[gate] {rep['bench']}/{rep['mode']}: "
                  f"{rep['gated']}/{rep['checked']} metrics gated, "
                  f"drift x{rep['drift']}, recorded={rep['recorded']}",
                  file=sys.stderr)
        return

    quick = args.quick
    suite = {
        "fig13": lambda: fig13_inverse.run(iters=3 if quick else 5),
        "serve": lambda: serve_throughput.run(iters=3 if quick else 5),
        "slo": lambda: serve_slo.run(smoke=quick),
        "chaos": lambda: chaos_soak.run(iters=3 if quick else 8,
                                        smoke=quick),
        "obs": lambda: obs_telemetry.run(iters=3 if quick else 10,
                                         smoke=quick),
        "trace": lambda: trace_observatory.run(smoke=quick),
        "roofline": roofline.run,
    }
    only = args.only.split(",") if args.only else list(suite)

    all_rows, failures = [], []
    for name in only:
        try:
            rows = suite[name]()
            all_rows.extend(rows)
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
    emit(all_rows)
    if failures:
        print(f"FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    if common.SRC not in sys.path:
        sys.path.insert(0, common.SRC)
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    main()
