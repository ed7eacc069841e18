"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s): it loads the cell's configuration and traffic
mix (found by name under ``bench/configs`` and ``bench/traffic``), builds
the inputs and weights from the seed, warms up every shape the window uses,
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of standard
output.  With ``--trace 1`` the window runs under the profiler and the
line carries the cell's per-layer metrics instead of its end-to-end ones.
It exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def execute(cell, devs, t_start: float) -> dict:
    """Drive the cell and assemble its result line (without printing)."""
    from bench import harness, trace

    cc = harness.CompileCount()
    driver = importlib.import_module("bench." + cell.traffic["kind"])
    out = driver.run(cell, devs, t_start, cc)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": out["device"]}
    if cell.trace:
        tr = trace.load(out["ctx"]["trace_dir"])
        summ = trace.summary(tr, cell.chips)
        ctx = dict(out["ctx"], trace=tr, summary=summ,
                   peak=harness.peak(out["device"]["kind"]))
        result["metrics"] = harness.read_metrics(cell, ctx)
        result["device"].update(busy_s=summ["busy_s"],
                                window_s=summ["window_s"])
        t0, t1 = summ["t0"], summ["t1"]
        result["breakdown"] = {"device_ops": trace.top_ops(tr, t0, t1),
                               "idle_gaps": trace.idle_gaps(tr, t0, t1)}
    else:
        result["metrics"] = {e["name"]: {"value": float(out["e2e"][e["name"]]),
                                         "unit": e["unit"]}
                             for e in cell.end_to_end}
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.Cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    try:
        devs = harness.setup_jax(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    harness.emit(execute(cell, devs, T_START))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
