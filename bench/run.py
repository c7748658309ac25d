"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<traffic>.json``), the driver the mix names
(``bench/drivers/<driver>.py``), the limits of its output check
(``bench/limits/<cell>.json``) and every per-layer metric it reports
(``bench/metrics/<metric>.py``) are files of their own, found by name.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device`` and, traced, ``breakdown``;
``checks`` comes last and holds every number the output check compared,
each beside its limit (also printed as the last lines of standard
error).  Exits nonzero without a result line when JAX finds no TPU, or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

#: set-up is timed from here: process start, before JAX is imported
T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
#: what a run writes (traces), inside the checkout and gitignored
OUT = CHECKOUT / ".bench_out"


def _paths() -> None:
    for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric readers have dots in their names)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry of BENCHMARK.json with its files loaded."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have "
                             f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(CHECKOUT / configs[self.entry["config"]]["file"])
        self.traffic = load_json(BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = load_json(BENCH / "limits" / f"{name}.json")
        self.driver = self.traffic["driver"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]


def require_chip(chips: int):
    """The TPU devices this cell runs on, or exit nonzero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
              f"this benchmark measures the chip only", file=sys.stderr)
        sys.exit(3)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        sys.exit(3)
    return devs[:chips]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def read_per_layer(cell: Cell, facts: dict, trace) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(trace, facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(cell: Cell, *, seed: int, seconds: float, trace: bool,
            devices, t0: float, smoke: bool = False) -> dict:
    """Drive the cell and build the result line (without printing).
    ``smoke``: the configuration's CPU-sized model (rehearsals only)."""
    from bench import checks
    driver = load_module(BENCH / "drivers" / f"{cell.driver}.py")
    trace_dir = None
    if trace:
        trace_dir = OUT / "trace" / f"{cell.name}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = driver.run(config=cell.config, traffic=cell.traffic, seed=seed,
                     seconds=seconds, devices=devices, t0=t0,
                     trace_dir=trace_dir, smoke=smoke)
    device = device_info(devices)
    # the program's state is freed by now: the reference runs after the
    # peak was read, so it never sets it
    compared = driver.verify(run, limits=cell.limits)
    correct = checks.all_within(compared)
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if trace:
        from bench import trace as tr
        reduced = tr.load(trace_dir, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        facts = dict(run.facts, chips=len(devices),
                     device_kind=device["kind"])
        result["metrics"] = read_per_layer(cell, facts, reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": float(run.metrics[m["name"]]),
                        "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = compared
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    cell = Cell(load_json(CHECKOUT / "BENCHMARK.json"), args.workload)
    devices = require_chip(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of the cell goes to the persistent cache, so only
    # the first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = execute(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices, t0=T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    main()
