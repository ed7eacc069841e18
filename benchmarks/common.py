"""Shared benchmark harness: subprocess multi-device timing + CSV emission.

This container exposes ONE physical core; multi-device runs use
``--xla_force_host_platform_device_count`` so devices TIMESHARE the core.
Wall-clock therefore measures algorithmic + collective overhead, not true
parallel speedup — the paper's hardware-scaling story is carried by the dry-run
roofline (EXPERIMENTS.md §Roofline).  Each benchmark prints ``name,value,unit``
CSV rows and states which paper artifact it reproduces.

Output layout: full-mode headline JSONs stay tracked at the repo root
(``BENCH_*.json``); smoke-mode outputs go to the gitignored ``bench_out/``
(:func:`bench_path`).  Every benchmark also appends its headline rows to the
append-only ``BENCH_history.jsonl`` perf trajectory (:func:`history_append` →
:mod:`repro.obs.trajectory`), which the ``--smoke`` regression gate reads.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
RESULTS = os.path.join(REPO, "benchmarks", "results")
BENCH_OUT = os.path.join(REPO, "bench_out")
HISTORY = os.path.join(REPO, "BENCH_history.jsonl")


def run_worker(code: str, n_devices: int = 1, timeout: int = 1200,
               extra_env: dict | None = None) -> dict:
    """Run a snippet in a fresh process; the snippet must print one JSON line
    prefixed with ``RESULT:``.  The worker is held to the CPU (its devices
    are fake host devices): an accelerator belongs to one process at a time,
    and the parent may hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if n_devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                            + env.get("XLA_FLAGS", ""))
    env.update(extra_env or {})
    res = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                         capture_output=True, text=True)
    if res.returncode != 0:
        raise RuntimeError(f"worker failed:\n{res.stderr[-3000:]}")
    for line in res.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise RuntimeError(f"no RESULT line in worker output:\n{res.stdout[-2000:]}")


def emit(rows: list[tuple], header=("name", "value", "unit")):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))


def save_json(name: str, obj) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path


def bench_path(name: str, smoke: bool = False) -> str:
    """Headline-JSON output path: full runs keep the tracked repo-root
    ``BENCH_<name>.json``; smoke runs land in the gitignored ``bench_out/``
    so CI passes never dirty the tree."""
    if not smoke:
        return os.path.join(REPO, f"BENCH_{name}.json")
    os.makedirs(BENCH_OUT, exist_ok=True)
    return os.path.join(BENCH_OUT, f"BENCH_{name}_smoke.json")


# run.py --smoke arms this: history appends buffer here so the regression
# gate can compare the fresh rows against trailing history BEFORE they are
# recorded (a regressing run must not become part of its own baseline)
_DEFERRED: list | None = None


def history_append(bench: str, rows, smoke: bool = False):
    """Append this run's headline rows to the perf trajectory
    (``BENCH_history.jsonl``), keyed on git SHA + bench id + mode.  Smoke and
    full runs never share a baseline (different workload sizes)."""
    mode = "smoke" if smoke else "full"
    if _DEFERRED is not None:
        _DEFERRED.append((bench, list(rows), mode))
        return None
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.obs.trajectory import append_record

    return append_record(HISTORY, bench, rows, mode=mode)


def defer_history() -> None:
    """Buffer subsequent :func:`history_append` calls until
    :func:`flush_history_gate` (the ``--smoke`` gate protocol)."""
    global _DEFERRED
    _DEFERRED = []


def flush_history_gate() -> list[dict]:
    """Gate every deferred bench's rows against its trailing history, then
    record them.  Raises :class:`repro.obs.trajectory.PerfRegressionError`
    on the first tripped bench — WITHOUT recording it."""
    global _DEFERRED
    pending, _DEFERRED = _DEFERRED or [], None
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.obs.trajectory import gate

    return [gate(HISTORY, bench, rows, mode=mode)
            for bench, rows, mode in pending]


# ------------------------------------------------------ paired CPU timing

def _interleaved(fns: dict, arg, iters: int) -> dict:
    """Per-round us samples per candidate, measured in ROUND-ROBIN.

    A shared host's CPU quota drifts on minute scales; timing candidate A for
    its full budget and then candidate B confounds the comparison with the
    drift.  One pass per round over every candidate puts competing paths
    seconds (not minutes) apart, so PAIRED per-round statistics (differences,
    ratios) see the same machine.  Returns the raw per-round lists — derive
    medians / paired diffs from them, never a difference of medians.
    """
    import time as _time

    import jax

    for fn in fns.values():
        jax.block_until_ready(fn(arg))  # compile + warm
        jax.block_until_ready(fn(arg))
    ts = {k: [] for k in fns}
    for _ in range(iters):
        for k, fn in fns.items():
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(arg))
            ts[k].append((_time.perf_counter() - t0) * 1e6)
    return {k: np.asarray(v) for k, v in ts.items()}


def _med(x) -> float:
    return float(np.median(x))


def _paired_ratio(num, den):
    """Median of per-round ratios over rounds where both diffs are positive
    (a quota dip can make a small same-round difference go non-positive);
    falls back to the ratio of median diffs, and to NaN when even the medians
    are non-positive — a visible sentinel, never a fabricated huge speedup."""
    num, den = np.asarray(num), np.asarray(den)
    ok = (num > 0) & (den > 0)
    if ok.any():
        return float(np.median(num[ok] / den[ok]))
    mn, md = float(np.median(num)), float(np.median(den))
    return mn / md if mn > 0 and md > 0 else float("nan")
