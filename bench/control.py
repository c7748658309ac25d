"""Readings that set the limits of the output check: the control (the
plain reference computed one precision step below the configuration's,
fp8 for bf16, put in the program's place) and the faults the check must
catch, at a cell's own size on the chip.  The benchmark's runs never run
this; the limits in ``bench/limits/<cell>.json`` are set from its
readings and from the program's own over many seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --variant control|half_batch

The reference and the variant follow the cell's check steps from each
seed; the numbers are those the check compares, and ``correct`` is the
check's verdict on them under the cell's committed limits.  One JSON
line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def training(cell, seed: int, variant: str, devices,
             smoke: bool = False) -> dict:
    """The check's numbers of ``variant`` against the reference over
    the cell's check steps from ``seed`` (``smoke``: the configuration's
    CPU-sized model, for tests)."""
    from bench import checks
    from bench import model as bmodel
    from bench.drivers import train
    from bench.reference import llama
    cfg = bmodel.program_config(cell.config, smoke=smoke)
    t = cell.traffic
    n_check = -(-int(t["check_steps"]) // int(t["H"]))
    check = {"dm": bmodel.dims(cfg), "seed": seed, "traffic": t,
             "blocks": train.token_blocks(cfg.vocab, int(t["mesh"][0]), t,
                                          seed)[:n_check],
             "steps": n_check * int(t["H"]), "devices": list(devices)}
    ref = train.reference(check)
    if variant == "control":
        got = train.reference(check, num=llama.FP8)
    else:
        got = train.reference(check, fault=variant)
    return checks.training_numbers(got, ref)


def main(argv=None) -> None:
    from bench import run as brun
    brun._paths()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="control",
                    choices=("control", "half_batch"))
    args = ap.parse_args(argv)
    cell = brun.Cell(brun.load_json(brun.CHECKOUT / "BENCHMARK.json"),
                     args.workload)
    devices = brun.require_chip(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import checks
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        nums = training(cell, seed, args.variant, devices)
        correct = checks.all_within(checks.with_limits(nums, cell.limits))
        print(json.dumps({"workload": cell.name, "variant": args.variant,
                          "seed": seed, "numbers": nums,
                          "limits": {k: cell.limits[k] for k in nums},
                          "correct": correct,
                          "seconds": time.perf_counter() - t}), flush=True)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
