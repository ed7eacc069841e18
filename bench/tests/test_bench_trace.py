"""The reduction from a device trace to idle share, kernel time and exposed
collective time."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def synthetic():
    # one device: window 0..100; ops 10-30 (kernel), 20-40 (permute),
    # 50-60 (permute alone), 70-80 (other)
    return {"devices": {"/device:TPU:0": [
        ("fusion.1 _kernel2_res", 10, 30), ("collective-permute-done", 20, 40),
        ("collective-permute-start", 50, 60), ("add.3", 70, 80)]},
        "host": [("bench.window", 0, 100), ("bench.train_chunk", 0, 45),
                 ("bench.fetch_health", 45, 100)]}


def test_busy_and_idle():
    tr = synthetic()
    s = trace.summary(tr, 1)
    assert s["window_s"] == pytest.approx(100e-9)
    # busy: 10-40, 50-60, 70-80 = 50
    assert s["busy_s"] == pytest.approx(50e-9)


def test_kernel_time_and_exposed_collective():
    tr = synthetic()
    assert trace.op_ns(tr, 0, 100, lambda n: "_kernel2_res" in n) == 20
    exp = trace.exposed_ns(tr, 0, 100, lambda n: "collective-permute" in n)
    # 20-40 overlaps the kernel for 10; 50-60 runs alone
    assert exp["/device:TPU:0"] == 10 + 10


def test_gaps_named_by_host_span():
    gaps = trace.idle_gaps(synthetic(), 0, 100)
    assert gaps[0] == ["bench.fetch_health", pytest.approx(20e-9)]
    assert {g[0] for g in gaps} <= {"bench.train_chunk", "bench.fetch_health"}


def test_top_ops_per_chip():
    top = trace.top_ops(synthetic(), 0, 100, n=2)
    assert top[0][0] in ("fusion.1 _kernel2_res", "collective-permute-done")
    assert top[0][1] == pytest.approx(20e-9)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith("_trace.json")))
def test_recorded_chip_trace(name):
    tr = trace.read(os.path.join(DATA, name))
    s = trace.summary(tr, len(tr["devices"]))
    assert 0 < s["busy_s"] <= s["window_s"]
    ops = trace.top_ops(tr, s["t0"], s["t1"])
    assert ops and all(v > 0 for _n, v in ops)
    assert sum(v for _n, v in ops) <= s["window_s"] * 1.0001 * len(
        tr["devices"])
