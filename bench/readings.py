"""The readings that the limits in ``bench/limits`` are set from.

    python3 -m bench.readings --workload <cell> --seeds a,b,c --what program,control

One process, one chip (or the cell's chips): for each seed it builds the
cell's inputs and weights and reads the numbers that decide ``correct``.

- ``program``: the timed path against the reference at ``highest`` (the
  lower reading: largest over a dozen seeds or more);
- ``control``: the reference at the TPU's ``high`` precision put in the
  program's place; ``control_bf16x3``: the same with its three bfloat16
  passes written out, which any backend computes alike;
- ``half_batch`` (training): the reference with the second half of each
  subdomain's residual points left out, the mean taken over the rest;
- ``no_exchange`` (training): the reference with nothing received from the
  neighbours.

Prints one JSON line per seed and reading.  The benchmark's own runs do not
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

CONTROLS = {"control": ("high", {}), "control_bf16x3": ("highest",
                                                        {"dot": "bf16x3"})}


def half_batch(data):
    data = dict(data)
    data["res"] = [x[: len(x) // 2] for x in data["res"]]
    return data


def train_readings(cell, seeds, what, devs):
    import jax

    from bench import train

    cfg, traffic = cell.config, cell.traffic
    steps, k = int(traffic["chunk_steps"]), int(traffic["loss_steps"])
    for seed in seeds:
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            prog = train.Program(cfg, traffic, seed, devs)
            first = prog.first() if "program" in what else None
        prog.free()
        want = train.run_reference(cfg, prog, steps, "highest")
        n_sub = prog.geo.n_sub
        got = {}
        if first is not None:
            got["program"] = first
        for name, (prec, variant) in CONTROLS.items():
            if name in what:
                got[name] = train.run_reference(cfg, prog, steps, prec,
                                                **variant)
        if "half_batch" in what:
            got["half_batch"] = train.run_reference(cfg, prog, steps,
                                                    "highest",
                                                    data_edit=half_batch)
        if "no_exchange" in what:
            got["no_exchange"] = train.run_reference(cfg, prog, steps,
                                                     "highest",
                                                     exchange=False)
        for name, g in got.items():
            r = train.readings(g, want, prog.w0, n_sub, k, look=True)
            yield {"seed": seed, "reading": name, **r}


def serve_readings(cell, seeds, what, seconds):
    import jax

    from bench import serve

    cfg, traffic = cell.config, cell.traffic
    order = int(traffic["order"])
    for i, seed in enumerate(seeds):
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            server = serve.Server(cfg, traffic, seed)
            if i == 0:
                server.warm(seed)
            due, clouds = serve.schedule(traffic, float(traffic["rate_per_s"]),
                                         seconds, seed, server.geo)
            res = serve.drive(server.frontend(), due, clouds, seconds)
        res["clouds"] = clouds
        got, want, _n, pts = serve.sample_check(
            cfg, server, res, order, seed, int(traffic["sample_requests"]))
        out = {"program": got} if "program" in what else {}
        for name, (prec, variant) in CONTROLS.items():
            if name in what:
                out[name] = serve.ref_answers(cfg, server, pts, prec,
                                              **variant)
        for name, g in out.items():
            yield {"seed": seed, "reading": name, **serve.errors(g, want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,control_bf16x3")
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="serving cells: length of each seed's window")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    what = set(args.what.split(","))
    cell = harness.Cell(args.workload, seeds[0], args.seconds, False)
    try:
        devs = harness.setup_jax(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    t0 = time.perf_counter()
    gen = (train_readings(cell, seeds, what, devs)
           if cell.traffic["kind"] == "train"
           else serve_readings(cell, seeds, what, args.seconds))
    for row in gen:
        print(json.dumps(row), flush=True)
    harness.log(f"readings took {time.perf_counter() - t0:.1f} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
