"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a small plain form: for
every device, the innermost operations of its ``XLA Ops`` line as
(name, start, end) in nanoseconds, and the host spans the harness opened
with ``jax.profiler.TraceAnnotation`` (names starting ``bench.``).  On a
v5e the Pallas kernels of the training step appear as custom calls named
after their scope: ``%jvp_vmap_jit_pinn_mlp_forward2___.N`` (the forward
that stashes the reverse sweep's residuals, ``_kernel2_res``) and
``%pinn2-bwd-fused.N`` (``_kernel2_bwd``).  Every other
function here works on that plain form, so a trimmed recorded trace can be
kept as JSON and the reduction tested without a chip.
"""
from __future__ import annotations

import glob
import json
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
_COLLECTIVE = re.compile(r" (collective-permute|all-reduce|all-gather|"
                         r"reduce-scatter|all-to-all)(-start|-done)?\(")


def short_name(text: str) -> str:
    """An operation's HLO instruction name, e.g. ``%pinn2-bwd-fused.3``,
    tagged with what kind of operation it is where that matters here
    (``[tpu_custom_call]`` for a Pallas kernel, the collective's opcode)."""
    name = text.split(" = ", 1)[0]
    tags = []
    if 'custom_call_target="tpu_custom_call"' in text:
        tags.append("tpu_custom_call")
    m = _COLLECTIVE.search(text)
    if m:
        tags.append(m.group(1) + (m.group(2) or ""))
    return name + "".join(f" [{t}]" for t in tags)


def leaves(ops):
    """Drop operations that contain others (a ``while`` loop, a ``cond``):
    their time is their children's and the gaps between them."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    keep = []
    for i, (n, a, b) in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][1] < b and ops[i + 1][2] <= b:
            continue
        keep.append((n, a, b))
    return keep


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SYSTEM" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((short_name(e.name), int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
            devices[plane.name] = leaves(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1])}


def window(tr: dict, name: str = "bench.window") -> tuple[int, int]:
    """(start, end) of the host span that brackets the measured window."""
    spans = [h for h in tr["host"] if h[0] == name]
    if not spans:
        raise ValueError(f"trace holds no {name} span")
    return spans[0][1], spans[0][2]


def clip(ops, t0: int, t1: int):
    return [(n, max(a, t0), min(b, t1)) for n, a, b in ops if b > t0 and a < t1]


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def busy_ns(tr: dict, t0: int, t1: int) -> dict:
    """Per device: nanoseconds in which some operation ran."""
    return {d: length(union((a, b) for _n, a, b in clip(ops, t0, t1)))
            for d, ops in tr["devices"].items()}


def op_ns(tr: dict, t0: int, t1: int, match) -> int:
    """Summed device time, over all devices, of operations whose name
    ``match`` accepts."""
    return sum(b - a for ops in tr["devices"].values()
               for n, a, b in clip(ops, t0, t1) if match(n))


def exposed_ns(tr: dict, t0: int, t1: int, match) -> dict:
    """Per device: time in which an operation that ``match`` accepts runs
    and no other operation does."""
    out = {}
    for d, ops in tr["devices"].items():
        ops = clip(ops, t0, t1)
        coll = union((a, b) for n, a, b in ops if match(n))
        other = union((a, b) for n, a, b in ops if not match(n))
        exp, j = 0, 0
        for a, b in coll:
            cov = 0
            while j < len(other) and other[j][1] <= a:
                j += 1
            k = j
            while k < len(other) and other[k][0] < b:
                cov += min(b, other[k][1]) - max(a, other[k][0])
                k += 1
            exp += (b - a) - cov
        out[d] = exp
    return out


def top_ops(tr: dict, t0: int, t1: int, n: int = 10) -> list:
    """The operations that took the most device time, in seconds per chip."""
    tot: dict = {}
    for ops in tr["devices"].values():
        for name, a, b in clip(ops, t0, t1):
            tot[name] = tot.get(name, 0) + (b - a)
    nd = max(1, len(tr["devices"]))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def idle_gaps(tr: dict, t0: int, t1: int, n: int = 10) -> list:
    """The longest gaps in which the first device ran nothing, each named by
    the innermost harness host span open at its midpoint."""
    if not tr["devices"]:
        return []
    ops = clip(tr["devices"][sorted(tr["devices"])[0]], t0, t1)
    busy = union((a, b) for _n, a, b in ops)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [h for h in tr["host"] if h[0] != "bench.window"]
    out = []
    for a, b in gaps:
        mid = (a + b) // 2
        open_ = [h for h in host if h[1] <= mid < h[2]]
        name = (min(open_, key=lambda h: h[2] - h[1])[0] if open_
                else "host:outside-harness-spans")
        out.append([name, (b - a) / 1e9])
    return out


def summary(tr: dict, chips: int) -> dict:
    """The numbers every cell reports from its traced window."""
    t0, t1 = window(tr)
    busy = busy_ns(tr, t0, t1)
    used = sorted(busy)[:chips]
    return {"t0": t0, "t1": t1, "window_s": (t1 - t0) / 1e9,
            "busy_s": float(np.mean([busy[d] for d in used])) / 1e9
            if used else 0.0,
            "devices": used}


def trim(tr: dict, t0: int, t1: int, max_ops: int) -> dict:
    """A smaller copy for tests: the first ``max_ops`` operations of each
    device inside [t0, t1] and the host spans that overlap them."""
    devs = {d: clip(ops, t0, t1)[:max_ops] for d, ops in tr["devices"].items()}
    end = max([o[2] for ops in devs.values() for o in ops] + [t0])
    host = [h for h in tr["host"] if h[2] > t0 and h[1] < end]
    host = [("bench.window", t0, end)] + [h for h in host
                                          if h[0] != "bench.window"]
    return {"devices": devs, "host": host}


def save(tr: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr, f)


def read(path: str) -> dict:
    with open(path) as f:
        tr = json.load(f)
    tr["devices"] = {d: [tuple(o) for o in ops]
                     for d, ops in tr["devices"].items()}
    tr["host"] = [tuple(h) for h in tr["host"]]
    return tr
