"""CPU rehearsal: every driver end to end through the harness at the
configurations' smoke sizes (interpret-mode kernels), the last line's
schema, and the refusal of a device that is not a TPU."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bench import run as brun

BENCH = brun.load_json(brun.CHECKOUT / "BENCHMARK.json")


def smoke_cell(name):
    cell = brun.Cell(BENCH, name)
    cell.traffic = dict(cell.traffic, **cell.traffic["smoke"])
    return cell


def check_schema(result, cell):
    assert list(result)[-1] == "checks"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert {m["name"] for m in cell.end_to_end} == set(result["metrics"])
    for m in cell.end_to_end:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(result)


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_one_chip_cells_end_to_end(name):
    cell = smoke_cell(name)
    res = brun.execute(cell, seed=2**40 + 7, seconds=1.0, trace=False,
                       devices=jax.devices()[:1], t0=time.perf_counter(),
                       smoke=True)
    check_schema(res, cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(brun.BENCH / "run.py"), "--workload",
         "yi6b-train-h4", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=brun.CHECKOUT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_cpu_trace_is_refused(tmp_path):
    """The device-metric path reads only TPU planes: a CPU trace has
    none, and its reduction is refused rather than read as idle."""
    from bench import trace as tr
    f = jax.jit(lambda x: x @ x)
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    with pytest.raises(ValueError):
        tr.load(tmp_path, n_devices=1)


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files (no program) exits nonzero and prints no result."""
    import shutil
    shutil.copy(brun.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(brun.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b-train-h4",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
