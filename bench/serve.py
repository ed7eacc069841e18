"""Serving cells: open-loop Poisson traffic through the program's
``ResilientFrontend`` over a ``FieldEngine``, checked against the reference.

The traffic file gives the rate, the range of cloud sizes and the frontend's
settings.  Every seed gets the same work in another order: the sizes are the
log-uniform distribution's quantiles and the gaps the exponential
distribution's, arranged by the seed so that every block of
``balance_block`` requests holds the same spread of both; the points are
drawn uniformly over the map's bounding box.  One thread submits each request at its due
time and polls the frontend in between, as a serving loop does; a request's
latency runs from its due time to the moment its answer is in hand.

``python3 -m bench.serve --workload <cell> --seed <n> --seconds <s>
--sweep r1,r2,...`` runs the same window at each rate in one process and
prints a row per rate (p95, backlog, answered at full order): the sweep that
finds the rate a cell is set at.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import numpy as np

from bench import compare, harness, problems, reference

CLOCK = time.monotonic
QUANTITIES = ("u", "grad_u", "flux", "residual")


def balanced(values: np.ndarray, block: int, r: np.random.Generator):
    """Reorder ``values`` (sorted, a multiple of ``block`` long) so that every
    run of ``block`` consecutive entries holds one value from each
    ``block``-th of the distribution, in a random order: every seed then
    offers the same work in every stretch of the window, and only the order
    within a stretch changes."""
    strata = values.reshape(block, -1)            # stratum s: row s
    cols = np.stack([r.permutation(strata.shape[1]) for _ in range(block)])
    out = np.take_along_axis(strata, cols, axis=1).T.copy()  # (blocks, block)
    for row in out:
        r.shuffle(row)
    return out.ravel()


def schedule(traffic: dict, rate: float, seconds: float, seed: int,
             geo: problems.Geometry):
    """Due times (s from window start) and point clouds of one window.

    Gaps are exponential with mean 1/rate and sizes log-uniform, each taken
    as the distribution's quantiles and arranged by ``balanced``."""
    b = int(traffic["balance_block"])
    n = b * max(1, int(round(rate * seconds / b)))
    r = problems.rng(seed)
    q = (np.arange(n) + 0.5) / n
    lo, hi = math.log(traffic["size_min"]), math.log(traffic["size_max"])
    sizes = balanced(np.round(np.exp(lo + q * (hi - lo))).astype(int), b, r)
    gaps = balanced(-np.log1p(-q) / rate, b, r)
    due = np.cumsum(gaps) - gaps[0]
    blo, bhi = geo.lo_hi()
    clouds = [r.uniform(blo, bhi, size=(int(s), 2)) for s in sizes]
    return due, clouds


class Server:
    """The program's serving stack for one configuration and seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core.nets import act_code
        from repro.obs import MetricsRegistry, Obs
        from repro.serve import FieldBundle, FieldEngine, ResilienceConfig
        from bench.train import program_parts

        self.cfg, self.traffic = cfg, traffic
        pde, decomp, model, _dd = program_parts(cfg)
        self.geo = problems.Geometry(cfg["domain"])
        self.weights = problems.make_weights(cfg, self.geo.n_sub, seed)
        self.w0 = jax.tree.map(np.asarray, self.weights)
        bundle = FieldBundle(
            model_cfg=model, params=self.weights, decomp=decomp,
            act_codes=np.array([act_code(a) for a in cfg["activations"]],
                               np.int32),
            pde=pde, n_iface=int(cfg["n_iface"]))
        self.obs = Obs(registry=MetricsRegistry(clock=CLOCK))
        self.engine = FieldEngine(bundle, obs=self.obs)
        self.rcfg = ResilienceConfig(
            order=int(traffic["order"]),
            max_queue_requests=int(traffic["queue_requests"]),
            max_queue_points=int(traffic["queue_points"]),
            max_queue_age=float(traffic["queue_age_s"]))

    def frontend(self):
        """A fresh frontend over the warmed engine, as a server starts one."""
        from repro.serve import ResilientFrontend

        return ResilientFrontend(self.engine, self.rcfg, obs=self.obs)

    def warm(self, seed: int) -> int:
        """Compile every bucket the traffic can produce: the engine pads each
        subdomain's share of a microbatch to a multiple of its bucket (64),
        and a microbatch holds at most ``microbatch_points``, so the largest
        subdomain's share bounds the bucket (five standard deviations above
        its expected count)."""
        areas = np.array([_area(p) for p in self.geo.polys])
        p = areas.max() / areas.sum()
        n = int(self.traffic["microbatch_points"])
        top = p * n + 5.0 * math.sqrt(n * p * (1 - p))
        bucket = self.engine.bucket
        q = int(np.argmax(areas))
        r = problems.rng(seed)
        m_max = bucket * int(math.ceil(top / bucket))
        for m in range(bucket, m_max + 1, bucket):
            self.engine.evaluate(self.geo.sample_interior(q, m, r),
                                 order=int(self.traffic["order"]))
        return m_max // bucket


def _area(p):
    return 0.5 * float(np.sum(p[:, 0] * np.roll(p[:, 1], -1)
                              - np.roll(p[:, 0], -1) * p[:, 1]))


def drive(fe, due, clouds, seconds: float, grace: float = 60.0) -> dict:
    """Submit each cloud at its due time, poll in between, and collect every
    answer.  Returns per-request answer times and results, generator
    lateness and backlog samples."""
    n = len(due)
    t0 = CLOCK()
    due_abs = t0 + due
    got_t = np.full(n, np.nan)
    results = [None] * n
    late = np.zeros(n)
    backlog = np.zeros(n)
    pending: dict = {}
    i = 0
    stop = t0 + seconds + grace

    def collect():
        if fe.next_flush_due() is not None or not pending:
            return
        now = CLOCK()
        with harness.span("bench.result"):
            for tk, j in list(pending.items()):
                results[j] = fe.result(tk)
                got_t[j] = now
        pending.clear()

    while True:
        now = CLOCK()
        if i < n and now >= due_abs[i]:
            late[i] = now - due_abs[i]
            backlog[i] = len(pending)
            with harness.span("bench.submit"):
                pending[fe.submit(clouds[i])] = i
            i += 1
            collect()
            continue
        nf = fe.next_flush_due()
        nxt = min(due_abs[i] if i < n else math.inf,
                  nf if nf is not None else math.inf)
        if nxt == math.inf or now > stop:
            break
        if nxt > now:
            with harness.span("bench.idle"):
                time.sleep(nxt - now)
        else:
            with harness.span("bench.poll"):
                fe.poll()
            collect()
    t_end = CLOCK()
    return {"t0": t0, "t_end": t_end, "due_abs": due_abs, "got_t": got_t,
            "results": results, "late": late, "backlog": backlog,
            "unanswered": len(pending) + (n - i)}


def full_order(res, order: int) -> bool:
    return (res is not None and res.status == "served"
            and res.order == order and not res.degraded)


def latencies(run: dict, order: int):
    """Per request: due time to answer in hand; a request not answered at
    full order counts as missing, with the latency of the run's end."""
    ok = np.array([full_order(r, order) for r in run["results"]])
    lat = np.where(ok, run["got_t"] - run["due_abs"],
                   run["t_end"] - run["due_abs"])
    return lat, ok


def pick(run: dict, order: int, seed: int, n_sample: int) -> list:
    """A seed-drawn sample of the requests answered at full order, with the
    largest of them always in it."""
    _lat, ok = latencies(run, order)
    idx = np.flatnonzero(ok)
    if len(idx) == 0:
        return []
    sizes = np.array([len(run["clouds"][j]) for j in idx])
    first = int(idx[np.argmax(sizes)])
    rest = [int(j) for j in idx if j != first]
    r = problems.rng(seed + 1)
    more = r.choice(rest, size=min(n_sample - 1, len(rest)), replace=False) \
        if rest else []
    return sorted({first, *[int(j) for j in more]})


def ref_answers(cfg, server, pts, precision: str, dot: str = "f32"):
    """The reference's answers at ``pts``, claimed as the engine's routing
    tolerance claims them."""
    claims = server.geo.claims(pts, server.engine.tol)
    params = [reference.split(server.w0, q) for q in range(server.geo.n_sub)]
    with jax.default_matmul_precision(precision):
        return reference.serve(cfg, params, pts, claims, dot=dot)


def sample_check(cfg, server, run, order, seed, n_sample):
    """The sampled answers and the reference's at ``highest``."""
    picked = pick(run, order, seed, n_sample)
    if not picked:
        return None, None, 0, np.zeros((0, 2))
    pts = np.concatenate([run["clouds"][j] for j in picked])
    got = {k: np.concatenate([run["results"][j].data[k] for j in picked])
           for k in QUANTITIES}
    return got, ref_answers(cfg, server, pts, "highest"), len(picked), pts


def errors(got, want) -> dict:
    return {f"{k}_err": compare.rel_err(got[k], want[k]) for k in QUANTITIES}


def run(cell, devs, t_start: float, cc) -> dict:
    cfg, traffic = cell.config, cell.traffic
    order = int(traffic["order"])
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        server = Server(cfg, traffic, cell.seed)
        n_buckets = server.warm(cell.seed)
        due, clouds = schedule(traffic, float(traffic["rate_per_s"]),
                               cell.seconds, cell.seed, server.geo)
        fe = server.frontend()
        hist = server.obs.registry.histogram("serve.engine/dispatch_s")
        c0, s0 = hist.count, hist.sum
        setup_s = time.perf_counter() - t_start
        harness.log(f"set-up {setup_s:.3f} s ({n_buckets} buckets warmed); "
                    f"{len(due)} requests due")
        cc.on = True
        with harness.traced(cell.trace, cell.name) as tdir:
            with harness.span("bench.window"):
                res = drive(fe, due, clouds, cell.seconds)
        cc.on = False
    res["clouds"] = clouds
    lat, ok = latencies(res, order)
    n = len(due)
    harness.log(f"answered at full order {int(ok.sum())}/{n}; unanswered "
                f"{res['unanswered']}; generator late p99 "
                f"{np.percentile(res['late'], 99) * 1e3:.3f} ms max "
                f"{res['late'].max() * 1e3:.3f} ms; compiles in window: "
                f"{cc.compiles} (traces {cc.traces})")
    dev = harness.device_info(devs)
    waits = [r.latency - r.dispatch for r, k in zip(res["results"], ok) if k]
    dispatch = ((hist.sum - s0) / (hist.count - c0)
                if hist.count > c0 else None)
    got, want, n_req, pts = sample_check(
        cfg, server, res, order, cell.seed, int(traffic["sample_requests"]))
    harness.log(f"compared {n_req} requests, {len(pts)} points")
    if got is None:
        checks = {k: {"value": float("inf"), "limit": float(cell.limits[k])}
                  for k in cell.limits}
    else:
        checks = {k: {"value": v, "limit": float(cell.limits[k])}
                  for k, v in errors(got, want).items()}
    correct = res["unanswered"] == 0 and harness.checks_ok(checks)
    return {"correct": bool(correct), "attempted": n,
            "failed": int(n - ok.sum()),
            "e2e": {"serve_p95_ms": float(np.percentile(lat, 95)) * 1e3,
                    "setup_s": setup_s},
            "device": dev, "checks": checks,
            "ctx": {"kind": "serve", "trace_dir": tdir, "chips": cell.chips,
                    "queue_wait_s": waits, "dispatch_s": dispatch,
                    "window_s": res["t_end"] - res["t0"], "config": cfg}}


def sweep(cell, rates, seconds: float) -> list:
    """The cell's window at each rate, one process, one warm-up."""
    cfg, traffic = cell.config, cell.traffic
    order = int(traffic["order"])
    rows = []
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        server = Server(cfg, traffic, cell.seed)
        server.warm(cell.seed)
        for rate in rates:
            due, clouds = schedule(traffic, rate, seconds, cell.seed,
                                   server.geo)
            res = drive(server.frontend(), due, clouds, seconds)
            lat, ok = latencies(res, order)
            q = len(due) // 4
            b = res["backlog"]
            rows.append({
                "rate_per_s": rate, "requests": len(due),
                "p95_ms": float(np.percentile(lat, 95)) * 1e3,
                "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                "answered_full_order": float(ok.mean()),
                "backlog_first_quarter": float(b[:q].mean()),
                "backlog_last_quarter": float(b[-q:].mean()),
                "late_p99_ms": float(np.percentile(res["late"], 99)) * 1e3})
            print(rows[-1], file=sys.stderr, flush=True)
    return rows


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description="rate sweep of a serving cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sweep", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, args.seed, args.seconds, False)
    try:
        harness.setup_jax(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    rows = sweep(cell, [float(r) for r in args.sweep.split(",")],
                 args.seconds)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, harness.ROOT + "/src")
    raise SystemExit(main())
