"""The set-up phase driver (``benchmarks/setup_phases.py``) on a tiny copy of
the benchmark's training cell, on the host's CPU."""
import time

import pytest

from bench.tests import tiny
from benchmarks import setup_phases


@pytest.mark.parametrize("obs", [False, True], ids=["bare", "obs"])
def test_setup_phases_driver(obs):
    import jax

    # start from empty JAX caches: what an earlier test in this worker left
    # there (the chip smoke's per-point jvp serving oracle does) can send
    # every dispatch of the window off JAX's C++ fast path, and each such
    # dispatch reports a trace
    jax.clear_caches()
    cell = tiny.tiny_cell("burgers_xpinn_2x2.train", 2**31 + 11, 0.5)
    out = setup_phases.measure(cell, jax.devices()[:1], time.perf_counter(),
                               obs)
    assert out["first_ok"] and out["steps_per_s"] > 0
    assert isinstance(out["collectives"], dict)
    # the traced window compiles nothing, by either counter
    assert out["window_compiles"]["compile_count"] == [0, 0]
    spans = out["program_spans_on_host_plane"]
    if not obs:
        assert spans == 0 and "setup_phase_s" not in out
        return
    assert out["window_compiles"]["compile_watcher"] == [0, 0]
    assert all(out["setup_phase_s"][p] > 0
               for p in ("trace", "lower", "backend"))
    assert sum(out["setup_phase_s"].values()) < out["setup_s"]
    assert out["setup_counts"]["backend"] >= 1
    assert out["compile_spans"] == sum(
        out["setup_counts"][p] for p in ("trace", "lower", "backend"))
    assert "_run_chunk_guarded" in dict(out["setup_top_fun_s"]["trace"])
    # one dispatch span per chunk call, each inside the harness's chunk span
    assert spans >= 2 and out["program_spans_inside_chunk_span"] == spans
