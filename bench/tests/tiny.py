"""Small copies of the manifest's cells that a CPU test run can hold."""
from __future__ import annotations

import os
import subprocess
import sys
import time

from bench import harness

ROOT = harness.ROOT


# cells whose configuration, traffic and readers are in bench/ but which the
# manifest does not run yet (PERF.md, Open questions), with the end-to-end
# metrics they would report; a cell without limits of its own is held to the
# one-chip training cell's
WAITING = {
    "burgers_cpinn_4x1.train_4chip": (
        {"config": "burgers_cpinn_4x1", "traffic": "train_4chip", "chips": 4},
        [("train_steps_per_s", "steps/s"), ("setup_s", "s")]),
    "usmap_heat_10.serve_steady": (
        {"config": "usmap_heat_10", "traffic": "serve_steady", "chips": 1},
        [("serve_p95_ms", "ms"), ("setup_s", "s")]),
}


def cell(name: str, seed: int = 2**31 + 7, seconds: float = 0.5):
    """A manifest cell, or one that waits for a later benchmark PR."""
    if name not in WAITING:
        return harness.Cell(name, seed, seconds, False)
    w, e2e = WAITING[name]
    c = harness.Cell(name, seed, seconds, False, workload=dict(w, name=name))
    c.limits = c.limits or harness.load_json(
        "limits", "burgers_xpinn_2x2.train.json")
    c.end_to_end = [{"name": n, "unit": u} for n, u in e2e]
    return c


def tiny_cell(name: str, seed: int = 2**31 + 7, seconds: float = 0.5):
    """The named cell with few points, narrow heat nets, short chunks and a
    slow serving rate; everything else as its files state it."""
    cell_ = cell(name, seed, seconds)
    cfg = cell_.config
    cfg["n_res"] = [48] * len(cfg["n_res"])
    if cfg["pde"]["kind"] == "heat2d_inverse":
        cfg["nets"] = {k: dict(v, width=16) for k, v in cfg["nets"].items()}
        cfg["n_interior_data"] = 8
    if cell_.traffic["kind"] == "train":
        cell_.traffic["chunk_steps"] = 4
    else:
        cell_.traffic.update(rate_per_s=16.0, size_max=128,
                             microbatch_points=256, sample_requests=6)
    return cell_


def execute(cell) -> dict:
    """Everything a run does but the look for a chip."""
    from bench import run

    devs = harness.setup_jax(cell.chips, require_tpu=False)
    return run.execute(cell, devs, time.perf_counter())


def run_four_devices(code: str, timeout: int = 600) -> str:
    """Run ``code`` in a child with four host devices (the cell's mesh)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout
