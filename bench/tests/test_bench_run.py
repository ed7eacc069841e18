"""A whole run at a small size, short of the look for a chip: the last line's
keys, and the refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name", ["burgers_xpinn_2x2.train",
                                  "usmap_heat_10.serve_steady"])
def test_result_line_has_the_contract_keys(name, capsys):
    cell = tiny.tiny_cell(name)
    r = tiny.execute(cell)
    harness.emit(r)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line) == KEYS, list(line)
    assert line["correct"] is True, line["checks"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {e["name"] for e in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    err = out.err.strip().splitlines()
    assert all(e.startswith("check ") for e in err[-len(line["checks"]):])


def _run(args, cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(["--workload", "burgers_xpinn_2x2.train", "--seed", "1",
              "--seconds", "1", "--trace", "0"], harness.ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(["--workload", "burgers_xpinn_2x2.train", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
