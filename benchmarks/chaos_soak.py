"""Storage-chaos soak: corrupt artifacts on purpose, measure the recovery.

The durability acceptance (EXPERIMENTS.md §Durability) is that NO corrupt
state ever enters the trainer or the serving engine: every injected storage
fault (bit flip, truncation, torn write, missing file — against checkpoint
generations AND exported serve bundles) must be *detected* at restore/load
time, and recovery must come from generation fallback (training) or a refused
hot-swap followed by a clean re-export (serving).  This driver scripts the
full train → crash → restore → export → serve → reload loop once per storage
fault kind and measures:

* **detection rate** — injected vs detected faults; the acceptance is 100%,
* **fallback depth** — how many generations the verified restore walked back,
* **MTTR** — rollback→retrained latency on the train side
  (``SupervisorReport.recovery_s``, stamped by the injectable obs clock) and
  corrupt→reswapped latency on the serve side,
* **integrity write overhead** — paired ``ckpt.save`` with and without the
  checksum envelope (round-robin + paired-ratio idiom of
  ``benchmarks.common``, acceptance <= 5%).

Writes ``BENCH_chaos.json`` at the repo root, appends headline rows to the
``BENCH_history.jsonl`` perf trajectory, and routes every ``corruption`` /
``fallback`` / ``bundle_swap`` event through the schema-validated
:mod:`repro.obs.events` JSONL sink.  ``chaos_smoke_rows`` is the CI-fast
subset wired into ``benchmarks/run.py --smoke``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "src"))

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.core import (Burgers1D, CartesianDecomposition, DDConfig,
                        ReferenceTrainer, XPINN, build_topology)
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.launch.serve_field import reload_bundle
from repro.obs import make_obs, read_events, validate_events
from repro.runtime import (ChaosInjector, Fault, STORAGE_FAULT_KINDS,
                           Supervisor, SupervisorConfig, corrupt_generation)
from repro.serve import FieldEngine, ServeFrontend, export_bundle, load_bundle

from benchmarks.common import (_interleaved, _med, _paired_ratio, bench_path,
                               emit, history_append)

OVERHEAD_BOUND_PCT = 5.0


def _workload(n_res=250, width=24, depth=4, n_iface=20):
    pde = Burgers1D()
    dec = CartesianDecomposition(((-1, 1), (0, 1)), 2, 2)
    topo = build_topology(dec, n_iface=n_iface)
    cfg = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, width, depth)})
    b = make_batch(dec, topo, pde, n_res=n_res, n_bnd=80,
                   rng=np.random.default_rng(0)).device_arrays()
    tr = ReferenceTrainer(pde, cfg, topo,
                          DDConfig(method=XPINN, residual_path="pallas"),
                          lrs=2e-3)
    return pde, dec, cfg, b, tr


# ------------------------------------------------------------ soak scripting

def soak_once(kind: str, *, chunk: int = 20, n_chunks: int = 4, seed: int = 0,
              clock=time.perf_counter, obs=None) -> dict:
    """One scripted durability pass for one storage fault kind.

    Train under a composed chaos schedule (the newest checkpoint generation
    is corrupted right before an injected crash, so the rollback MUST detect
    it and fall back a generation), then export the survivor, serve it,
    corrupt the bundle, watch the watchdog refuse the swap while the old
    field keeps answering, repair by re-export, and confirm the hot-swap.
    """
    pde, dec, cfg, b, tr = _workload()
    out = {"kind": kind}
    with tempfile.TemporaryDirectory() as d:
        root = os.path.join(d, "ckpt")
        # chunk 2: two generations exist (steps chunk, 2*chunk).  The storage
        # fault rots the NEWEST one, then the crash forces a rollback through
        # the verified-restore path — detection + quarantine + depth-1
        # fallback + bitwise replay, all in one supervised run.
        inj = ChaosInjector(
            [Fault(chunk=2, kind=kind, target="ckpt", index=0),
             Fault(chunk=2, kind="crash")],
            roots={"ckpt": root}, seed=seed)
        sup = Supervisor(tr, root,
                         SupervisorConfig(chunk_steps=chunk,
                                          ckpt_every_chunks=1),
                         inj, decomp=dec, obs=obs)
        st, rep = sup.run(tr.init(0), b, n_chunks * chunk)
        out["ckpt_injected"] = len(inj.storage_fired)
        out["ckpt_detected"] = rep.corruptions
        out["fallback_depths"] = list(rep.fallback_depths)
        out["ckpt_mttr_s"] = list(rep.recovery_s)
        out["final_step"] = int(st.step)
        out["finite"] = bool(all(np.isfinite(np.asarray(x)).all()
                                 for x in jax.tree.leaves(st.params)))
        out["recovered"] = (out["finite"]
                            and out["final_step"] == n_chunks * chunk)

        # serve side: export the trained field, corrupt the bundle, demand a
        # refused swap (old field keeps serving), then repair and swap.
        broot = os.path.join(d, "bundle")
        export_bundle(broot, st.params, cfg, dec, pde=pde, n_iface=20,
                      step=int(st.step))
        fe = ServeFrontend(FieldEngine(load_bundle(broot)), order=1, obs=obs)
        pts = np.random.default_rng(seed).uniform((-1, 0), (1, 1), (32, 2))
        r0 = fe.query(pts)
        t0 = clock()
        corrupt_generation(broot, kind, 0, np.random.default_rng(seed + 1))
        refused = reload_bundle(fe, broot)
        out["bundle_injected"] = 1
        out["bundle_detected"] = int(not refused["swapped"])
        r1 = fe.query(pts + 1e-7)  # distinct signature: misses the LRU cache
        out["served_through_refusal"] = bool(np.allclose(
            np.nan_to_num(r1["u"]), np.nan_to_num(r0["u"]), atol=1e-5))
        export_bundle(broot, st.params, cfg, dec, pde=pde, n_iface=20,
                      step=int(st.step) + 1)
        swapped = reload_bundle(fe, broot)
        out["bundle_mttr_s"] = clock() - t0
        out["reswapped"] = bool(swapped["swapped"])
    return out


def _summarize(results: list[dict]) -> dict:
    injected = sum(r["ckpt_injected"] + r["bundle_injected"] for r in results)
    detected = sum(r["ckpt_detected"] + r["bundle_detected"] for r in results)
    depths = [dep for r in results for dep in r["fallback_depths"]]
    ckpt_mttr = [s for r in results for s in r["ckpt_mttr_s"]]
    bundle_mttr = [r["bundle_mttr_s"] for r in results]
    return {
        "injected": injected,
        "detected": detected,
        "detection_rate_pct": round(100.0 * detected / max(injected, 1), 2),
        "unrecovered": sum(not (r["recovered"] and r["reswapped"]
                                and r["served_through_refusal"])
                           for r in results),
        "fallback_depth_max": max(depths, default=0),
        "ckpt_mttr_ms_med": round(float(np.median(ckpt_mttr)) * 1e3, 2),
        "bundle_mttr_ms_med": round(float(np.median(bundle_mttr)) * 1e3, 2),
    }


def _check(summary: dict) -> None:
    if summary["detection_rate_pct"] != 100.0:
        raise AssertionError(
            f"storage-fault detection {summary['detected']}/"
            f"{summary['injected']} — a corrupt artifact went unnoticed")
    if summary["unrecovered"]:
        raise AssertionError(
            f"{summary['unrecovered']} soak run(s) did not recover "
            "(fallback, refusal-serving, or re-swap failed)")


# ------------------------------------------------------ integrity overhead

def save_overhead(iters: int = 8) -> dict:
    """Paired checkpoint-write cost with vs without the integrity envelope.

    Round-robin interleaved saves into two sibling roots so the host's
    CPU-quota drift cancels in the paired ratio (``benchmarks.common``).  The tree
    is ~16 MB so array bytes dominate the save (the quickstart tree is a few
    hundred KB — at that size a save is ~4 ms of filesystem latency and the
    paired ratio measures noise, not the envelope)."""
    rng = np.random.default_rng(0)
    tree = {"params": {"W": [rng.standard_normal((4, 512, 512))
                             .astype(np.float32) for _ in range(4)]}}
    steps = itertools.count(1)
    with tempfile.TemporaryDirectory() as d:
        roots = {k: os.path.join(d, k) for k in ("plain", "integrity")}
        fns = {
            "plain": lambda _: ckpt.save(roots["plain"], next(steps), tree,
                                         keep=2, integrity=False),
            "integrity": lambda _: ckpt.save(roots["integrity"], next(steps),
                                             tree, keep=2, integrity=True),
        }
        t = _interleaved(fns, None, iters)
    ratio = _paired_ratio(t["integrity"], t["plain"])
    return {
        "plain_save_ms": round(_med(t["plain"]) / 1e3, 3),
        "integrity_save_ms": round(_med(t["integrity"]) / 1e3, 3),
        "paired_ratio": round(ratio, 4),
        "overhead_pct": round((ratio - 1.0) * 100.0, 2),
        "acceptance_bound_pct": OVERHEAD_BOUND_PCT,
    }


# ---------------------------------------------------------------- entrypoints

def _soak_rows(results: list[dict], summary: dict, prefix: str) -> list[tuple]:
    return [
        (f"{prefix}/detection_rate", summary["detection_rate_pct"], "%"),
        (f"{prefix}/injected_faults", summary["injected"], ""),
        (f"{prefix}/unrecovered", summary["unrecovered"], ""),
        (f"{prefix}/fallback_depth_max", summary["fallback_depth_max"], ""),
        (f"{prefix}/ckpt_mttr_ms", summary["ckpt_mttr_ms_med"], "ms"),
        (f"{prefix}/bundle_mttr_ms", summary["bundle_mttr_ms_med"], "ms"),
    ]


def run(iters: int = 8, smoke: bool = False):
    """Full soak: every storage fault kind, overhead pairs, event validation."""
    kinds = STORAGE_FAULT_KINDS if not smoke else STORAGE_FAULT_KINDS[:2]
    rows = []

    oh = save_overhead(iters=iters)
    rows.append(("chaos/integrity_save_overhead", oh["overhead_pct"], "%"))
    if not smoke and not oh["overhead_pct"] <= OVERHEAD_BOUND_PCT:
        raise AssertionError(
            f"integrity save overhead {oh['overhead_pct']:.2f}% exceeds the "
            f"{OVERHEAD_BOUND_PCT}% acceptance bound")

    with tempfile.TemporaryDirectory() as d:
        ev_path = os.path.join(d, "chaos_events.jsonl")
        obs = make_obs(ev_path, run_id="chaos_soak")
        results = [soak_once(k, seed=i, obs=obs)
                   for i, k in enumerate(kinds)]
        obs.close()
        validate_events(ev_path)  # schema-checked corruption/fallback stream
        ev = read_events(ev_path)
        events = {k: sum(e["kind"] == k for e in ev)
                  for k in ("corruption", "fallback", "bundle_swap")}
    summary = _summarize(results)
    _check(summary)
    rows += _soak_rows(results, summary, "chaos")

    out = bench_path("chaos", smoke)
    with open(out, "w") as f:
        json.dump({
            "workload": "quickstart 2x2 Burgers XPINN, chunked supervised "
                        "train + exported-bundle serving",
            "backend": jax.default_backend(),
            "fault_kinds": list(kinds),
            "save_overhead": oh,
            "soak": results,
            "summary": summary,
            "events": events,
        }, f, indent=1)
    print(f"wrote {out}")
    history_append("chaos", rows, smoke=smoke)
    return rows


def chaos_smoke_rows(kinds=("bit_flip", "truncate")) -> list[tuple]:
    """CI-fast durability acceptance (wired into ``run.py --smoke``).

    Two storage fault kinds through the full scripted soak; FAILS unless
    every injected fault is detected (100%) and every run recovers —
    generation fallback on the train side, refused-swap-then-repair on the
    serve side."""
    with tempfile.TemporaryDirectory() as d:
        ev_path = os.path.join(d, "chaos_events.jsonl")
        obs = make_obs(ev_path, run_id="chaos_smoke")
        results = [soak_once(k, seed=i, obs=obs)
                   for i, k in enumerate(kinds)]
        obs.close()
        validate_events(ev_path)
        n_corruption = sum(e["kind"] == "corruption"
                           for e in read_events(ev_path))
    summary = _summarize(results)
    _check(summary)
    if n_corruption < summary["detected"]:
        raise AssertionError(
            f"only {n_corruption} corruption events for "
            f"{summary['detected']} detections — obs stream incomplete")
    rows = _soak_rows(results, summary, "chaos/smoke")
    history_append("chaos", rows, smoke=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="two fault kinds + the CI acceptance subset")
    args = ap.parse_args()
    rows = run(iters=args.iters, smoke=args.smoke)
    if args.smoke:
        rows += chaos_smoke_rows()
    emit(rows)


if __name__ == "__main__":
    main()
