"""Benchmark driver: one function per paper table/figure.

``python -m benchmarks.run [--quick] [--smoke] [--only fig4,fig6,...]``

Prints ``name,value,unit`` CSV rows per benchmark; raw measurements land in
benchmarks/results/*.json.  The roofline rows read the dry-run outputs
(run ``python -m repro.launch.dryrun`` first for those).

``--smoke`` is the CI-fast mode: a single tiny fig4 configuration with the
jvp-vs-pallas residual comparison plus the analytic residual roofline —
seconds, not minutes; the full kernel sweeps stay on-demand
(``pytest -m kernel`` / the unflagged benchmark runs).
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
                    help="comma list: fig4,fig6,fig8,fig9,table2,fig13,serve,"
                         "slo,ft,chaos,obs,trace,roofline")
    ap.add_argument("--quick", action="store_true", help="fewer sizes/iters")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-fast subset: tiny fig4 jvp-vs-pallas + "
                         "run_chunk e2e + supervisor crash/NaN recovery + "
                         "serve-SLO clean/faulted acceptance + storage-chaos "
                         "durability acceptance + validated trace exports + "
                         "perf-regression gate + roofline")
    args = ap.parse_args()

    from benchmarks import (chaos_soak, fig4_cost_profile, fig6_comp_comm,
                            fig8_weak_scaling, fig9_strong_scaling,
                            fig13_inverse, ft_overhead, obs_telemetry,
                            roofline, serve_slo, serve_throughput,
                            table2_spacetime, trace_observatory)

    if args.smoke:
        # history appends buffer until the gate below: a regressing run is
        # flagged BEFORE it can enter its own baseline
        common.defer_history()
        # the pallas fig4 pass exercises BOTH custom-VJP backwards (fused
        # hand-derived vs checkpointed-ref) and reports the fwd/bwd split
        rows = fig4_cost_profile.run(iters=3, path="pallas", smoke=True)
        # selector round-trip: fused-bwd and ref-bwd training must agree
        rows += fig4_cost_profile.bwd_parity_rows()
        rows += fig4_cost_profile.run_e2e(iters=1, smoke=True)
        rows += serve_throughput.run(iters=2, smoke=True)
        # supervisor recovery acceptance: one crash (bitwise replay) + one NaN
        # (guard trip -> backoff -> finite completion)
        rows += ft_overhead.recovery_smoke_rows()
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
        "fig4": lambda: fig4_cost_profile.run(iters=3 if quick else 10),
        "fig6": lambda: fig6_comp_comm.run(sizes=(4,) if quick else (4, 8, 12),
                                           iters=3 if quick else 5),
        "fig8": lambda: fig8_weak_scaling.run(sizes=(1, 4) if quick else (1, 2, 4, 8),
                                              iters=3 if quick else 5),
        "fig9": lambda: fig9_strong_scaling.run(sizes=(1, 4) if quick else (1, 2, 4, 8),
                                                iters=3 if quick else 5),
        "table2": lambda: table2_spacetime.run(iters=3 if quick else 5),
        "fig13": lambda: fig13_inverse.run(iters=3 if quick else 5),
        "serve": lambda: serve_throughput.run(iters=3 if quick else 5),
        "slo": lambda: serve_slo.run(smoke=quick),
        "ft": lambda: ft_overhead.run(iters=3 if quick else 10),
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
