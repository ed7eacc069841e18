"""The collective readers: ``halo_exposed_share`` (the halo's
collective-permutes) and ``guard_sync_exposed_share`` (the guard's
all-reduce), on a recorded four-chip trace against the exposed time worked
out here segment by segment, and on synthetic traces."""
import importlib.util
import os

import pytest

from bench import harness, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "burgers_cpinn_4x1.train_4chip_trace.json")
READERS = {"halo_exposed_share": "[collective-permute",
           "guard_sync_exposed_share": "[all-reduce"}


def reader(metric):
    spec = importlib.util.spec_from_file_location(
        "collective_reader_" + metric,
        os.path.join(harness.BENCH, "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx(tr):
    return {"kind": "train", "trace": tr,
            "summary": trace.summary(tr, len(tr["devices"]))}


def by_hand(tr, tag):
    """Mean over devices of the time in which an operation whose name holds
    ``tag`` runs and no other does, as a share of the window: every
    stretch between two consecutive op boundaries is looked at alone."""
    t0, t1 = trace.window(tr)
    shares = []
    for ops in tr["devices"].values():
        ops = [(n, max(a, t0), min(b, t1)) for n, a, b in ops
               if b > t0 and a < t1]
        coll = [(a, b) for n, a, b in ops if tag in n]
        other = [(a, b) for n, a, b in ops if tag not in n]
        cuts = sorted({x for _n, a, b in ops for x in (a, b)})
        exposed = 0
        for x, y in zip(cuts, cuts[1:]):
            if any(a <= x and y <= b for a, b in coll) and \
                    not any(a <= x and y <= b for a, b in other):
                exposed += y - x
        shares.append(exposed / (t1 - t0))
    return 100.0 * sum(shares) / len(shares)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_recorded_four_chip_trace(metric):
    tr = trace.read(RECORDED)
    assert len(tr["devices"]) == 4
    got = reader(metric)(ctx(tr))
    assert got is not None and got > 0
    assert got == pytest.approx(by_hand(tr, READERS[metric]), rel=1e-9)


def synthetic(ops):
    return {"devices": {"/device:TPU:0": ops},
            "host": [("bench.window", 0, 100)]}


def test_collective_under_a_kernel_is_not_exposed():
    # the all-reduce runs wholly under the kernel; the permute's start
    # overlaps it for 5 and its done runs alone for 10
    tr = synthetic([("%k.1 [tpu_custom_call]", 10, 40),
                    ("%pmin.9 [all-reduce]", 20, 30),
                    ("%cp.1 [collective-permute-start]", 35, 45),
                    ("%cp.2 [collective-permute-done]", 50, 60)])
    assert reader("guard_sync_exposed_share")(ctx(tr)) == pytest.approx(0.0)
    assert reader("halo_exposed_share")(ctx(tr)) == pytest.approx(15.0)
    for metric, tag in READERS.items():
        assert reader(metric)(ctx(tr)) == pytest.approx(by_hand(tr, tag))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_no_collective_reads_none(metric):
    tr = synthetic([("%k.1 [tpu_custom_call]", 10, 40), ("%add.3", 50, 60)])
    assert reader(metric)(ctx(tr)) is None
